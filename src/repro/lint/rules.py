"""The registered project invariants.

Each rule encodes one hard-won discipline of the one-shot stack — the
properties the test suite can only spot-check but the paper's claims
ride on: byte-reproducible rounds, exact wire costs, and registry-
routed kernel dispatch. See docs/TESTING.md ("rung 6") for the policy
table and how to add a rule.
"""
from __future__ import annotations

import ast
import re
from typing import Iterator, Set

from repro.lint.base import (
    FileContext,
    Violation,
    call_leaf,
    dotted_name,
    from_imports,
    rule,
)

# ----------------------------------------------------------------------
# rng-discipline
# ----------------------------------------------------------------------

_GLOBAL_SEEDERS = {"np.random.seed", "numpy.random.seed", "random.seed"}


@rule(
    "rng-discipline",
    "no arithmetic seed derivation or global seeding; derive streams "
    "via SeedSequence (utils.seeds)",
    blessed=("repro/utils/seeds.py",),
)
def rng_discipline(ctx: FileContext) -> Iterator[Violation]:
    """Ban collision-prone ad-hoc seed arithmetic.

    ``default_rng(seed * 100003 + t)`` maps distinct (seed, t) pairs
    onto the SAME stream (run seed s+1 device t-100003 == run seed s
    device t), silently coupling "independent" federations — the bug
    class PR 9 swept out of data/ and sim/. Seeds must come through
    ``derive_device_seed`` / ``derive_stream_seed`` / an explicit
    ``SeedSequence``. Global seeding (``np.random.seed``) and legacy
    ``RandomState`` are banned outright: they create action-at-a-
    distance between unrelated draws.
    """
    for node in ctx.calls():
        leaf = call_leaf(node)
        dotted = dotted_name(node.func) or ""
        if leaf == "default_rng" and node.args and isinstance(node.args[0], ast.BinOp):
            yield ctx.violation(
                node, "rng-discipline",
                f"arithmetic seed derivation `{ast.unparse(node.args[0])}` "
                "is collision-prone across (seed, index) pairs; use "
                "derive_device_seed/derive_stream_seed (SeedSequence)",
            )
        elif dotted in _GLOBAL_SEEDERS:
            yield ctx.violation(
                node, "rng-discipline",
                f"global seeding `{dotted}(...)` couples unrelated draws; "
                "pass an explicit Generator derived via utils.seeds",
            )
        elif leaf == "RandomState" and "random" in dotted:
            yield ctx.violation(
                node, "rng-discipline",
                "legacy RandomState has no SeedSequence spawning; use "
                "np.random.default_rng over a derived seed",
            )


# ----------------------------------------------------------------------
# wall-clock-ban
# ----------------------------------------------------------------------

_WALL_FNS = {
    "time", "perf_counter", "monotonic", "process_time",
    "time_ns", "perf_counter_ns", "monotonic_ns", "process_time_ns",
}
_DATETIME_NOW = {
    "datetime.now", "datetime.utcnow", "datetime.today",
    "datetime.datetime.now", "datetime.datetime.utcnow",
    "date.today", "datetime.date.today",
}


@rule(
    "wall-clock-ban",
    "no wall-clock reads outside repro/obs and benchmarks; time via "
    "obs.stopwatch/timed_call/tracer spans",
    blessed=("repro/obs/", "benchmarks/"),
)
def wall_clock_ban(ctx: FileContext) -> Iterator[Violation]:
    """Keep wall-clock reads inside the observability layer.

    Fleet runs and fleet traces are byte-reproducible from a seed
    because the control plane runs on simulated milliseconds — one
    stray ``time.time()`` in a hot path breaks that audit. Engine and
    launch code measures durations with ``obs.stopwatch()`` (and spans
    land the timings in the trace); only ``repro/obs`` and the
    benchmark harnesses read the clock directly.
    """
    time_aliases = {
        alias for alias, orig in from_imports(ctx.tree, "time").items()
        if orig in _WALL_FNS
    }
    for node in ctx.calls():
        dotted = dotted_name(node.func) or ""
        parts = dotted.split(".")
        if len(parts) == 2 and parts[0] == "time" and parts[1] in _WALL_FNS:
            yield ctx.violation(
                node, "wall-clock-ban",
                f"wall-clock read `{dotted}()`; use obs.stopwatch() / "
                "timed_call / a tracer span (sim paths must stay "
                "deterministic from the seed)",
            )
        elif dotted in _DATETIME_NOW:
            yield ctx.violation(
                node, "wall-clock-ban",
                f"wall-clock read `{dotted}()`; derive timestamps from "
                "the run's clock source, not the host clock",
            )
        elif isinstance(node.func, ast.Name) and node.func.id in time_aliases:
            yield ctx.violation(
                node, "wall-clock-ban",
                f"wall-clock read `{node.func.id}()` (from time import); "
                "use obs.stopwatch() / timed_call / a tracer span",
            )


# ----------------------------------------------------------------------
# kernel-registry-bypass
# ----------------------------------------------------------------------

_PALLAS_RE = re.compile(r"^\w+_pallas$")


@rule(
    "kernel-registry-bypass",
    "no direct *_pallas / ref.*_ref oracle calls outside kernels/; "
    "route through the kernels.ops dispatchers",
    blessed=("repro/kernels/", "tests/test_kernels.py"),
)
def kernel_registry_bypass(ctx: FileContext) -> Iterator[Violation]:
    """Every kernel call goes through the registry dispatch.

    ``kernels/ops.py`` owns backend choice (TPU pallas / interpret /
    jnp oracle) and jit caching under the wrapper names the device
    trace is read by; the ROADMAP autotuner will hang tile-config
    choice off the same dispatchers. A direct ``*_pallas`` or
    ``ref.*_ref`` call sidesteps all three — it runs uncompiled
    off-TPU, under no name a trace reader knows, and will silently
    miss autotuned tile configs. Only ``repro/kernels``
    itself and the kernel parity suite touch implementations directly.
    """
    ref_aliases = {
        alias for alias, orig in from_imports(ctx.tree, "repro.kernels.ref").items()
        if orig.endswith("_ref")
    }
    for node in ctx.calls():
        leaf = call_leaf(node)
        if leaf and _PALLAS_RE.match(leaf):
            yield ctx.violation(
                node, "kernel-registry-bypass",
                f"direct kernel call `{leaf}(...)` bypasses the registry "
                "dispatch (backend policy, jit cache, profiling); call "
                f"kernels.ops.{leaf.removesuffix('_pallas')} instead",
            )
        elif (
            isinstance(node.func, ast.Attribute)
            and node.func.attr.endswith("_ref")
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "ref"
        ):
            yield ctx.violation(
                node, "kernel-registry-bypass",
                f"direct oracle call `ref.{node.func.attr}(...)` bypasses "
                "the registry dispatch; call the kernels.ops wrapper",
            )
        elif isinstance(node.func, ast.Name) and node.func.id in ref_aliases:
            yield ctx.violation(
                node, "kernel-registry-bypass",
                f"direct oracle call `{node.func.id}(...)` (imported from "
                "kernels.ref) bypasses the registry dispatch",
            )


# ----------------------------------------------------------------------
# wire-cost-honesty
# ----------------------------------------------------------------------


@rule(
    "wire-cost-honesty",
    "no .nbytes / .itemsize / pickle-length payload sizing; wire cost "
    "is len(encode(...)) or the shape pricers",
    blessed=(
        "repro/comm/ledger.py",     # CommEvent carries the priced nbytes field
        "repro/checkpoint/",        # manifest sizes are storage, not comm
        "tests/test_comm.py",       # assert on recorded ledger fields
        "tests/test_distill.py",
    ),
)
def wire_cost_honesty(ctx: FileContext) -> Iterator[Violation]:
    """Communication cost is the exact encoded size, nothing else.

    The paper's communication claim is only auditable because every
    ledger entry equals ``len(encode(payload))`` (or its shape-priced
    twins ``svm_wire_nbytes`` / ``agg_extra_wire_nbytes``, proven equal
    in tests). ``array.nbytes`` is the in-memory fp32 footprint — it
    over-counts an int8 upload 4x — ``dtype.itemsize`` arithmetic
    rebuilds that same in-memory price by hand (an aggregator extra
    priced as ``count * itemsize`` misses headers, names, and int8
    scale/zero columns), and pickled length prices the pickle protocol,
    not the wire format. The ledger module itself (whose events carry
    an ``nbytes`` field) and checkpoint manifests (in-memory
    accounting, not comm) are blessed; tests assert on recorded ledger
    fields.
    """
    for node in ctx.walk():
        if (
            isinstance(node, ast.Attribute)
            and node.attr == "nbytes"
            and isinstance(node.ctx, ast.Load)
        ):
            yield ctx.violation(
                node, "wire-cost-honesty",
                "`.nbytes` is the in-memory array size, not the wire "
                "cost; price payloads with len(encode(...)) or "
                "comm.wire.svm_wire_nbytes/agg_extra_wire_nbytes",
            )
        elif (
            isinstance(node, ast.Attribute)
            and node.attr == "itemsize"
            and isinstance(node.ctx, ast.Load)
        ):
            yield ctx.violation(
                node, "wire-cost-honesty",
                "`.itemsize` arithmetic hand-rolls the in-memory array "
                "size, not the wire cost (headers, names, and int8 "
                "scale/zero columns are missing); price payloads with "
                "len(encode(...)) or the comm.wire shape pricers",
            )
        elif isinstance(node, ast.Call):
            dotted = dotted_name(node.func) or ""
            if dotted == "sys.getsizeof":
                yield ctx.violation(
                    node, "wire-cost-honesty",
                    "`sys.getsizeof` prices the interpreter object, not "
                    "the wire payload; use len(encode(...))",
                )
            elif (
                isinstance(node.func, ast.Name) and node.func.id == "len"
                and node.args and isinstance(node.args[0], ast.Call)
                and (dotted_name(node.args[0].func) or "").endswith("pickle.dumps")
            ):
                yield ctx.violation(
                    node, "wire-cost-honesty",
                    "pickle-length sizing prices the pickle protocol, not "
                    "the versioned wire format; use len(encode(...))",
                )


# ----------------------------------------------------------------------
# salted-hash-ban
# ----------------------------------------------------------------------


@rule(
    "salted-hash-ban",
    "no builtin hash() for routing/partitioning; crc32 only "
    "(hash() is salted per process)",
)
def salted_hash_ban(ctx: FileContext) -> Iterator[Violation]:
    """Builtin ``hash()`` changes per process (PYTHONHASHSEED).

    The PR-7 bug class: cache-shard routing through ``hash(key)`` works
    in one process and resharded every restart, so replay and the
    byte-reproducible fleet baselines silently diverged. Stable
    partitioning goes through ``zlib.crc32`` (``fleet.registry
    .shard_for``); equality-hashing objects implement ``__hash__``
    normally — only explicit ``hash(...)`` calls are flagged.
    """
    for node in ctx.calls():
        if isinstance(node.func, ast.Name) and node.func.id == "hash":
            yield ctx.violation(
                node, "salted-hash-ban",
                "builtin hash() is salted per process (PYTHONHASHSEED) — "
                "routing/partitioning must use zlib.crc32",
            )


# ----------------------------------------------------------------------
# jit-hostile-patterns
# ----------------------------------------------------------------------

_JIT_DECOS = re.compile(r"\b(jit|vmap|pmap|shard_map)\b")
_HOST_CASTS = {"float", "int", "bool"}
_HOST_NP_CALLS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}


def _static_argnames(fn: ast.AST) -> Set[str]:
    """String constants under any ``static_argnames=...`` keyword in
    the decorator expressions — casts of static args are trace-safe."""
    names: Set[str] = set()
    for deco in getattr(fn, "decorator_list", []):
        for node in ast.walk(deco):
            if isinstance(node, ast.keyword) and node.arg in (
                "static_argnames", "static_argnums"
            ):
                for const in ast.walk(node.value):
                    if isinstance(const, ast.Constant) and isinstance(const.value, str):
                        names.add(const.value)
    return names


@rule(
    "jit-hostile-patterns",
    "no host casts / .item() / np.asarray on traced values inside "
    "jit/vmap/shard_map-decorated functions",
)
def jit_hostile_patterns(ctx: FileContext) -> Iterator[Violation]:
    """Traced functions must stay on the device.

    Inside a ``jax.jit`` / ``vmap`` / ``shard_map``-decorated function,
    ``float(x)`` / ``int(x)`` / ``bool(x)``, ``.item()`` / ``.tolist()``
    and ``np.asarray`` force the tracer to concretize — a
    ``TracerConversionError`` at best, a silent host sync and
    recompile-per-value at worst. Casts of ``static_argnames``
    arguments are recognized and allowed (they are Python values at
    trace time). Functions wrapped post-hoc (``fn = jax.jit(fn)``)
    are out of scope for this rule.
    """
    for fn in ctx.walk():
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        deco_src = " ".join(ast.unparse(d) for d in fn.decorator_list)
        if not _JIT_DECOS.search(deco_src):
            continue
        static = _static_argnames(fn)
        for stmt in fn.body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                leaf = call_leaf(node)
                dotted = dotted_name(node.func) or ""
                if (
                    isinstance(node.func, ast.Name)
                    and node.func.id in _HOST_CASTS
                    and node.args
                    and not isinstance(node.args[0], ast.Constant)
                    and not (
                        isinstance(node.args[0], ast.Name)
                        and node.args[0].id in static
                    )
                ):
                    yield ctx.violation(
                        node, "jit-hostile-patterns",
                        f"host cast `{node.func.id}(...)` inside the "
                        f"jit/vmap-decorated `{fn.name}` concretizes a "
                        "traced value (sync + recompile-per-value)",
                    )
                elif leaf in ("item", "tolist") and isinstance(node.func, ast.Attribute):
                    yield ctx.violation(
                        node, "jit-hostile-patterns",
                        f"`.{leaf}()` inside the jit/vmap-decorated "
                        f"`{fn.name}` forces a device->host transfer",
                    )
                elif dotted in _HOST_NP_CALLS:
                    yield ctx.violation(
                        node, "jit-hostile-patterns",
                        f"`{dotted}(...)` inside the jit/vmap-decorated "
                        f"`{fn.name}` materializes a traced value on the "
                        "host; use jnp",
                    )
