"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json`` at the checkout root.
Its configuration (``bench/configs/<config>.json``), traffic mix
(``bench/traffic/<traffic>.json``, which names its runner in
``bench/runners/``) and limits (``bench/limits/<cell>.json``) are data
files found by name; each per-layer metric is read by
``bench/metrics/<metric>.py``. A cell or a metric is added by adding
files and entries, never by editing one of these.

One run, in one process: set-up (data from ``--seed``, the program
built and warmed up on every shape the window uses), then the measured
window of ``--seconds``, then the comparison with the plain reference
(``bench/check.py``), which runs after the window and after the peak of
device memory has been read. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` reports its per-layer metrics instead,
from the program's spans over the whole window and from a JAX profiler
trace (Python frames off) of the part the runner profiles: the first
round of a round cell, the whole window of a serve cell. The last line of standard
output is one JSON object; the numbers compared, each beside its limit,
are the last lines of standard error and the last key of that object.

The run refuses (exit 1, no result line) when JAX finds no TPU or fewer
chips than the cell needs, and when ``REPRO_PALLAS_INTERPRET`` is set.
JAX's persistent compilation cache is kept in ``.jax_cache/`` at the
checkout root (or where ``JAX_COMPILATION_CACHE_DIR`` says), with no
minimum compile time, so that only a cell's first run compiles.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType, SimpleNamespace
from typing import Optional

STARTED = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def say(text: str) -> None:
    print(text, file=sys.stderr, flush=True)


def load(path: Path, name: str) -> ModuleType:
    """Import a file of the benchmark by path (metric files have dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, bench: Optional[dict] = None) -> SimpleNamespace:
    """Everything a run of cell ``name`` needs, from the data files."""
    bench = read_json(ROOT / "BENCHMARK.json") if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    return SimpleNamespace(
        name=name, chips=int(w["chips"]),
        config=read_json(ROOT / cfg["file"]),
        traffic=read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=read_json(BENCH / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
    )


class CompileCount:
    """Programs JAX compiles or loads from its persistent cache, from its
    monitoring events (the duration event times both; ``cached`` counts
    the loads)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.n, self.seconds, self.cached = 0, 0.0, 0

    def __call__(self, event, secs, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += secs

    def hit(self, event, **_):
        if event == self.HIT:
            self.cached += 1


class SpanRecorder:
    """A tracer for the program that keeps its spans and nothing else.

    ``enabled`` stays False, so the program's kernel-timing hook and
    ledger instants stay off: the traced window runs the same calls as
    an untraced one. Spans are (name, start, end) in perf_counter
    seconds."""

    enabled = False

    def __init__(self):
        self.spans = []

    @contextlib.contextmanager
    def span(self, name, cat="app", **attrs):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))

    def instant(self, *a, **k):
        return None

    def complete(self, *a, **k):
        return None

    def export(self, path):
        return False


def runner_of(cell: SimpleNamespace) -> ModuleType:
    """The runner the cell's traffic names."""
    name = cell.traffic["runner"]
    return load(BENCH / "runners" / f"{name}.py", f"bench_runner_{name}")


def context(cell: SimpleNamespace, seed: int) -> SimpleNamespace:
    """What a runner is handed: the cell, the seed, and a loader for the
    configuration's generator (``bench/configs/<stem>.py``)."""
    return SimpleNamespace(cell=cell, config=cell.config, traffic=cell.traffic,
                           seed=int(seed), chips=cell.chips, root=ROOT,
                           load_config=lambda stem: load(BENCH / "configs" / f"{stem}.py",
                                                         f"bench_config_{stem}"))


def use_cache(jax) -> str:
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def check_chip(jax, chips: int) -> Optional[str]:
    """Why this machine cannot run the cell, or None."""
    if os.environ.get("REPRO_PALLAS_INTERPRET"):
        return "REPRO_PALLAS_INTERPRET is set; the kernels must run compiled"
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return f"JAX found no TPU (platform {devices[0].platform!r})"
    if len(devices) < chips:
        return f"the cell needs {chips} chips, JAX found {len(devices)}"
    return None


def run_cell(cell: SimpleNamespace, seed: int, seconds: float, trace: bool,
             require_chip: bool = True) -> Optional[dict]:
    """One run of one cell; the result object, or None when refused."""
    import jax

    if require_chip:
        why = check_chip(jax, cell.chips)
        if why:
            say(f"bench: {why}")
            return None
    sys.path.insert(0, str(ROOT / "src"))
    cache = use_cache(jax) if require_chip else "off"
    compiles = CompileCount()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    jax.monitoring.register_event_listener(compiles.hit)
    devices = jax.devices()
    dev = devices[0]
    say(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; cache {cache}")

    runner = runner_of(cell)
    ctx = context(cell, seed)
    state = runner.setup(ctx)
    setup_s = time.perf_counter() - STARTED
    say(f"setup: {setup_s:.3f} s, {compiles.n} programs compiled or loaded "
        f"({compiles.seconds:.3f} s), {compiles.cached} of them from the persistent cache")

    from repro.obs import use_tracer

    recorder = SpanRecorder()
    traced = {}

    @contextlib.contextmanager
    def profiled():
        """Profile the block (``--trace 1``); the runner says which."""
        if not trace or traced:
            yield
            return
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0       # Python frames would swamp the trace
        traced["dir"] = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(traced["dir"], profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("bench.traced"):
                t_in = time.perf_counter()
                yield
                traced["span"] = (t_in, time.perf_counter())
        finally:
            jax.profiler.stop_trace()

    def mark(name: str):
        return jax.profiler.TraceAnnotation(name) if trace else contextlib.nullcontext()

    c0 = compiles.n
    with use_tracer(recorder) if trace else contextlib.nullcontext():
        win = runner.window(state, float(seconds), mark, profiled)
    say(f"window: {win.window_s:.3f} s, {win.attempted} attempted, {win.failed} failed, "
        f"{compiles.n - c0} compilations inside the window")
    if hasattr(win, "generator_late_s"):
        say(f"generator: at worst {win.generator_late_s * 1e3:.3f} ms late; garbage "
            f"collector pauses over 10 ms: {[round(p, 1) for p in win.gc_pauses_ms][:20]}")

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    say(f"peak_bytes_in_use: {peak} (fullest chip)")

    reduction = None
    if trace:
        from bench import trace_reduce

        try:
            events = trace_reduce.load_dir(traced["dir"])
        finally:
            shutil.rmtree(traced["dir"], ignore_errors=True)
        reduction = trace_reduce.reduce(events, chips=cell.chips, window=traced["span"],
                                        host_spans=recorder.spans)
        del events

    gc.collect()
    from bench.check import judge

    numbers, per_unit = runner.check(state, ctx)
    correct, rows, readings = judge(numbers, cell.limits)

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(win.attempted),
              "failed": int(win.failed)}
    if trace:
        from bench.peaks import peaks_for

        run = SimpleNamespace(spans=recorder.spans,
                              units=win.units, window_s=win.window_s, stats=win.stats,
                              metrics=win.metrics,
                              trace=reduction, ops=per_unit * win.traced_units,
                              chips=cell.chips,
                              peaks=peaks_for(dev.device_kind) if require_chip else None)
        metrics = {}
        for m in cell.per_layer:
            value = load(BENCH / "metrics" / f"{m['name']}.py",
                         "bench_metric_" + m["name"].replace(".", "_")).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if reduction is not None:
            device.update(busy_s=reduction.busy_s, window_s=reduction.window_s)
            result["breakdown"] = reduction.breakdown()
    else:
        values = dict(win.metrics, setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
                   for m in cell.end_to_end}
    result.update(metrics=metrics, device=device)
    for r in readings:
        say(f"reading {r['name']}: {r['value']!r} (no limit holds; not compared)")
    for r in rows:
        say(f"check {r['name']}: {r['value']!r} (limit {r['limit']!r})")
    result["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]} for r in rows}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    cell = find_cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    if result is None:
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
