"""Device-parallel local training engine (population-scale simulation).

Four tiers, each the oracle for the next (docs/TESTING.md):

  mode="loop"      sequential per-device oracle: one Gram, one SDCA
                   solve, one scoring pass per device
  mode="bucketed"  whole cohorts per vectorized pass on ONE accelerator
  mode="sharded"   the bucketed passes laid out over the sim mesh
                   (`launch.mesh.make_sim_mesh`, 1-D ``devices`` axis)
                   with `shard_map` — pure data parallelism over the
                   group axis, one gather at the aggregation barrier
  mode="streamed"  the bucketed passes over BOUNDED CHUNKS of a lazy
                   `DeviceStream` — devices are generated, trained, and
                   released chunk by chunk, so peak host memory is
                   O(chunk_devices), not O(population)

The paper's round trains every device's RBF-SVM independently — which
the sequential loop dispatches one device at a time. At hundreds-to-
thousands of devices the per-dispatch overhead dominates and
experiments cap out at tens of devices.

`mode="bucketed"` instead fits whole cohorts of devices in single
vectorized passes:

  1. every device's local data is split 50/40/10 with an explicit
     per-device seed (`derive_device_seed` — identical streams in both
     modes, independent of iteration order);
  2. data-deficient / single-class devices fall back to constant
     classifiers immediately (no accelerator work);
  3. trainable devices are grouped by their SDCA pad bucket
     (64-multiples — the same bucket `train_svm` would use, so the
     solve is numerically aligned with the sequential path), groups are
     chunked to bound the batched Gram's memory footprint, and the
     device count is padded to a power of two so shapes recompile
     O(log) times, not per group;
  4. per group, ONE `batched_rbf_gram` call (Pallas kernel on TPU,
     vmap'd jnp oracle elsewhere — see `kernels/ops.py`) produces all
     Gram matrices, a vmap'd SDCA solves all duals, and two more
     batched Gram calls score every device's val and test splits;
  5. results stream back one `GroupUpdate` at a time, so callers render
     progress and running metrics while later buckets are still
     training.

Numerics: padded Gram rows/cols are masked to zero and padded labels
are +1, exactly matching `train_svm`'s padding, so per-device dual
coefficients — and hence val/test AUCs — match the sequential loop to
float-accumulation-order noise (the equivalence bar in tests is 1e-4).

`mode="sharded"` reuses the bucketed host-side pipeline byte-for-byte
(same seeds, same bucketing, same padding) and only swaps the two jit
calls for their `shard_map` twins. Per-device AUCs match the bucketed
tier EXACTLY on any mesh; models and scores additionally match bitwise
on the mesh sizes CI pins (1-4 shards, where per-shard batches keep
the bucketed op shapes — larger meshes may re-associate reductions, so
there the agreement is tight float tolerance). tests/test_engines.py
holds both bars, on 1-shard degenerate meshes and real multi-device
splits alike. Per-device streaming evaluation composes through the
merge-able accumulators in `utils.metrics`.

`mode="streamed"` consumes a lazy `scenarios.DeviceStream` in bounded
chunks (``chunk_devices``), running the SAME per-device classification,
bucketing, padding, and fit/score math as the bucketed tier — only the
group COMPOSITION differs (chunk-local buckets instead of population-
global ones). Per-device splits and seeds depend only on the device id,
and per-device results are invariant to group composition (the
grouping-invariance bar in tests/test_engines.py), so the streamed tier
matches the bucketed tier per device while holding O(chunk) devices in
memory at once. Callers that drain it into a `PopulationResult` give
that bound back; the streaming round in `sim.population` folds instead.
`train_selected` regenerates only a chosen id set through the same
math — the server-side path that rebuilds just the k selected models
after a streamed selection pass.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.svm import (
    SDCA_BUCKET,
    ConstantModel,
    SVMModel,
    _sdca,
    default_gamma,
    train_svm,
)
from repro.core.selection import DeviceReport
from repro.data.federated import DeviceData, FederatedDataset
from repro.data.partition import derive_device_seed, split_train_test_val
from repro.obs.registry import default_registry
from repro.obs.trace import current_tracer, stopwatch
from repro.utils.metrics import roc_auc
from repro.utils.logging import get_logger

log = get_logger("sim.engine")

QUERY_PAD = 8             # val/test query rows pad to multiples of this
GRAM_ELEM_BUDGET = 2**25  # max fp32 elements of one batched (g, b, b) Gram


@dataclasses.dataclass
class DeviceOutcome:
    """Everything the protocol needs from one device's local phase."""

    device_id: int
    splits: Dict[str, DeviceData]
    model: object  # SVMModel | ConstantModel
    report: DeviceReport
    val_scores: np.ndarray          # own model on own val split
    local_test_scores: np.ndarray   # own model on own test split

    @property
    def local_test_auc(self) -> float:
        return roc_auc(self.splits["test"].y, self.local_test_scores)


@dataclasses.dataclass
class GroupUpdate:
    """One streamed unit of progress: a trained bucket (or loop chunk)."""

    bucket: int                     # SDCA pad size (0 for fallback devices)
    outcomes: List[DeviceOutcome]
    seconds: float
    done: int                       # devices finished so far (cumulative)
    total: int                      # devices this run will train

    @property
    def mean_val_auc(self) -> float:
        return float(np.mean([o.report.val_auc for o in self.outcomes]))


@dataclasses.dataclass
class PopulationResult:
    outcomes: List[DeviceOutcome]   # sorted by device_id
    seconds: float
    groups: List[GroupUpdate]

    @property
    def reports(self) -> List[DeviceReport]:
        return [o.report for o in self.outcomes]

    @property
    def mean_local_auc(self) -> float:
        return float(np.mean([o.local_test_auc for o in self.outcomes]))


def _split_device(dev_id: int, dev: DeviceData, seed: int) -> Dict[str, DeviceData]:
    return split_train_test_val(dev, seed=derive_device_seed(seed, dev_id))


def _constant_outcome(dev_id: int, splits: Dict[str, DeviceData]) -> DeviceOutcome:
    """Paper's local baseline for data-deficient devices."""
    model = ConstantModel(float(np.mean(splits["train"].y)))
    report = DeviceReport(dev_id, splits["train"].n, 0.5, eligible=False)
    return DeviceOutcome(
        dev_id, splits, model, report,
        val_scores=model.predict(splits["val"].x),
        local_test_scores=model.predict(splits["test"].x),
    )


def train_device(
    dev_id: int, dev: DeviceData, min_samples: int, lam: float, seed: int,
    epochs: int = 20,
) -> DeviceOutcome:
    """Sequential oracle: one device end-to-end (the pre-engine path)."""
    splits = _split_device(dev_id, dev, seed)
    tr, va = splits["train"], splits["val"]
    if dev.n < min_samples or len(np.unique(tr.y)) < 2:
        return _constant_outcome(dev_id, splits)
    model = train_svm(tr.x, tr.y, lam=lam, epochs=epochs)
    val_scores = model.predict(va.x)
    report = DeviceReport(dev_id, tr.n, roc_auc(va.y, val_scores), eligible=True)
    return DeviceOutcome(
        dev_id, splits, model, report,
        val_scores=val_scores,
        local_test_scores=model.predict(splits["test"].x),
    )


# ----------------------------------------------------------------------
# bucketed (device-parallel) path
# ----------------------------------------------------------------------

def _fit_group_body(xp, yp, n_real, gammas, lam, epochs):
    """Batched Gram + vmap'd SDCA for one bucket of devices.

    xp: (g, b, d) zero-padded train features; yp: (g, b) labels padded
    with +1 (train_svm's padding); n_real: (g,) real sample counts;
    gammas: (g,). Returns alpha (g, b) with padded coordinates zero.
    """
    from repro.kernels import ops as kops

    K = kops.batched_rbf_gram(xp, xp, gammas)
    valid = jnp.arange(xp.shape[1])[None, :] < n_real[:, None]  # (g, b)
    K = K * valid[:, :, None] * valid[:, None, :]  # zero pad rows/cols
    return jax.vmap(lambda Kg, yg, ng: _sdca(Kg, yg, ng, lam, epochs))(K, yp, n_real)


def _score_group_body(xq, sup, coef, gammas):
    """Batched decision scores: (g, q, d) queries against (g, b, d)
    supports. Zero-padded supports contribute nothing via zero coefs;
    padded query rows are sliced off by the caller."""
    from repro.kernels import ops as kops

    Kq = kops.batched_rbf_gram(xq, sup, gammas)  # (g, q, b)
    return jnp.einsum("gqb,gb->gq", Kq, coef, precision=jax.lax.Precision.HIGHEST)


_fit_group = jax.jit(_fit_group_body, static_argnames=("epochs",))
_score_group = jax.jit(_score_group_body)


# ----------------------------------------------------------------------
# sharded (mesh-parallel) dispatch
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh-parallel dispatch for one engine run: the same `_fit_group`
    / `_score_group` math, `shard_map`-ped over the sim mesh's
    ``devices`` axis on the leading group dim.

    Every batch element (one device's SDCA problem) is independent, so
    laying groups out along the mesh is pure data parallelism: each
    accelerator fits and scores its slice of the bucket, and the only
    collective is the output gather at the aggregation barrier (the
    out_specs ``devices`` layout — no psum is needed because nothing is
    reduced across devices before selection). Host-side bucketing,
    padding, and seeds are byte-identical to the bucketed tier, which
    is why per-device AUCs agree exactly on any mesh — and models and
    scores bitwise on the CI-pinned 1-4 shard meshes (see
    tests/test_engines.py for the precise bars).
    """

    mesh: object
    fit: Callable
    score: Callable

    @property
    def n_shards(self) -> int:
        return int(np.prod(self.mesh.devices.shape))


_SHARD_CTX_CACHE: Dict[tuple, ShardCtx] = {}


def make_shard_ctx(shards: Optional[int] = None, epochs: int = 20) -> ShardCtx:
    """Build (and cache) the sharded dispatch context.

    The mesh comes from ``launch.mesh.make_sim_mesh`` (1-D ``devices``
    axis over local accelerators, power-of-two sized); the shard_map
    boundary specs come from ``sharding.rules.group_shard_specs`` — the
    same logical-axis table the LM side uses, with bucket groups on the
    logical "group" axis.
    """
    from repro.launch.mesh import make_sim_mesh
    from repro.sharding.rules import group_shard_specs

    mesh = make_sim_mesh(shards)
    key = (mesh.devices.shape, tuple(mesh.axis_names), epochs)
    if key in _SHARD_CTX_CACHE:
        return _SHARD_CTX_CACHE[key]

    # fit: (xp, yp, n_real, gammas) sharded on the group axis; lam is a
    # replicated scalar; alpha comes back group-sharded (the gather).
    fit_specs = group_shard_specs(mesh, (3, 2, 1, 1, 0))
    fit = jax.jit(jax.shard_map(
        partial(_fit_group_body, epochs=epochs),
        mesh=mesh, in_specs=fit_specs, out_specs=fit_specs[1],
    ))
    score_specs = group_shard_specs(mesh, (3, 3, 2, 1))
    score = jax.jit(jax.shard_map(
        _score_group_body,
        mesh=mesh, in_specs=score_specs, out_specs=score_specs[2],
    ))
    ctx = ShardCtx(mesh, fit, score)
    _SHARD_CTX_CACHE[key] = ctx
    return ctx


def _pad_pow2(n: int, lo: int = 8) -> int:
    return max(lo, 1 << (n - 1).bit_length())


def _train_bucket_group(
    members: List[tuple], bucket: int, lam: float, epochs: int,
    pad_floor: int = 8,
    shard: Optional[ShardCtx] = None,
) -> List[DeviceOutcome]:
    """members: [(dev_id, splits)] sharing one SDCA bucket size.

    ``pad_floor`` bounds the power-of-two device padding; callers lower
    it when the Gram memory budget allows fewer than 8 devices. With a
    ``shard`` context the group axis additionally pads to the mesh size
    (a power of two, so the pow-of-two padding absorbs it) and the fit
    and scoring passes run mesh-parallel.
    """
    score_fn = _score_group if shard is None else shard.score
    if shard is not None:
        pad_floor = max(pad_floor, shard.n_shards)
    g_real = len(members)
    g = _pad_pow2(g_real, lo=pad_floor)
    trains = [sp["train"] for _, sp in members]
    n_real = np.zeros(g, np.int32)
    n_real[:g_real] = [t.n for t in trains]
    # full-precision gammas for the stored models (train_svm keeps the
    # float64 heuristic); the kernels see float32 either way
    gamma_list = [default_gamma(t.x) for t in trains]
    gammas = np.ones(g, np.float32)
    gammas[:g_real] = gamma_list
    xp = np.zeros((g, bucket, trains[0].x.shape[1]), np.float32)
    yp = np.ones((g, bucket), np.float32)  # +1 padding, as in train_svm
    for i, t in enumerate(trains):
        xp[i, : t.n] = t.x
        yp[i, : t.n] = t.y

    fit_args = (jnp.asarray(xp), jnp.asarray(yp), jnp.asarray(n_real),
                jnp.asarray(gammas), lam)
    alpha = np.asarray(
        shard.fit(*fit_args) if shard is not None else _fit_group(*fit_args, epochs)
    )
    # coef = alpha * y / (lam * n); zero-label padding zeroes padded coefs
    y0 = np.where(np.arange(bucket)[None, :] < n_real[:, None], yp, 0.0)
    coef = alpha * y0 / (lam * np.maximum(n_real, 1)[:, None])

    scores: Dict[str, np.ndarray] = {}
    for split in ("val", "test"):
        qs = [sp[split].x for _, sp in members]
        q = -(-max(len(a) for a in qs) // QUERY_PAD) * QUERY_PAD
        xq = np.zeros((g, q, xp.shape[2]), np.float32)
        for i, a in enumerate(qs):
            xq[i, : len(a)] = a
        scores[split] = np.asarray(
            score_fn(jnp.asarray(xq), jnp.asarray(xp),
                     jnp.asarray(coef.astype(np.float32)), jnp.asarray(gammas))
        )

    outcomes = []
    for i, (dev_id, splits) in enumerate(members):
        tr, va, te = splits["train"], splits["val"], splits["test"]
        model = SVMModel(
            support_x=tr.x.astype(np.float32),
            coef=coef[i, : tr.n].astype(np.float32),
            gamma=gamma_list[i],
        )
        val_scores = scores["val"][i, : va.n]
        report = DeviceReport(dev_id, tr.n, roc_auc(va.y, val_scores), eligible=True)
        outcomes.append(DeviceOutcome(
            dev_id, splits, model, report,
            val_scores=val_scores,
            local_test_scores=scores["test"][i, : te.n],
        ))
    return outcomes


def _classify_device(dev_id, dev, min_samples, seed=0):
    """Shared per-device triage: split, then constant-fallback or the
    (bucket, splits) pair the SDCA path will train. Identical in every
    engine tier — the root of cross-tier equivalence."""
    splits = _split_device(dev_id, dev, seed)
    tr = splits["train"]
    if dev.n < min_samples or len(np.unique(tr.y)) < 2:
        return None, _constant_outcome(dev_id, splits)
    bucket = max(-(-tr.n // SDCA_BUCKET) * SDCA_BUCKET, SDCA_BUCKET)
    return bucket, splits


def _bucket_group_caps(bucket, group_cap, shard):
    """Power-of-two group chunk size under the Gram memory budget.

    The budget is PER DEVICE: a sharded run holds 1/n_shards of each
    group per accelerator, so its groups grow n_shards x larger at the
    same per-device footprint (fewer dispatches)."""
    budget = GRAM_ELEM_BUDGET * (shard.n_shards if shard else 1)
    cap = max(1, min(group_cap, budget // (bucket * bucket)))
    return 1 << (cap.bit_length() - 1)


def _train_buckets(by_bucket, lam, epochs, group_cap, shard):
    """Yield (bucket, outcomes, seconds) for every bucket group, caps
    floored to powers of two so `_train_bucket_group`'s pow2 group
    padding cannot overshoot the Gram memory budget; huge buckets
    (rare, giant devices) drop below 8 per group.

    Each group is a ``cat="engine"`` span; the span closes before the
    yield so consumer work between yields never lands inside it."""
    tracer = current_tracer()
    reg = default_registry()
    for bucket in sorted(by_bucket):
        members = by_bucket[bucket]
        cap = _bucket_group_caps(bucket, group_cap, shard)
        for lo in range(0, len(members), cap):
            elapsed = stopwatch()
            with tracer.span("engine.group", cat="engine", bucket=bucket,
                             members=len(members[lo : lo + cap]), cap=cap):
                outs = _train_bucket_group(
                    members[lo : lo + cap], bucket, lam, epochs,
                    pad_floor=min(8, cap), shard=shard,
                )
            secs = elapsed()
            reg.counter("engine.groups").inc()
            reg.counter("engine.devices_trained").inc(len(outs))
            reg.histogram("engine.group_seconds").observe(secs)
            yield bucket, outs, secs


def iter_population(
    dataset,
    *,
    lam: float = 0.01,
    seed: int = 0,
    min_samples: Optional[int] = None,
    mode: str = "bucketed",
    epochs: int = 20,
    group_cap: int = 256,
    available: Optional[np.ndarray] = None,
    shards: Optional[int] = None,
    chunk_devices: int = 1024,
) -> Iterator[GroupUpdate]:
    """Train a device population, streaming one GroupUpdate per batch.

    ``dataset`` is a materialized `FederatedDataset` or (for
    ``mode="streamed"``; accepted everywhere) a lazy
    `scenarios.DeviceStream`. Passing a stream to a materializing mode
    realizes it first; passing a dataset to the streamed mode wraps it
    — the streamed tier then bounds ACCELERATOR batches but host memory
    is already O(population).

    ``available`` (optional bool mask, len n_devices) drops absent
    devices entirely — they neither train nor report. A stream's own
    lazy availability mask composes with it (logical AND).

    ``mode="sharded"`` runs the bucketed passes mesh-parallel across
    local accelerators (``shards`` caps how many; default all — see
    ``make_shard_ctx``). Bucketing, seeds, and padding are identical to
    ``"bucketed"``, so the two tiers produce the same federation.

    ``mode="streamed"`` generates, trains, and releases devices in
    ``chunk_devices``-sized chunks: peak host memory is O(chunk), and
    per-device results still match the bucketed tier (chunk-local
    bucketing only changes group composition, which per-device results
    are invariant to). Pass ``shards`` to run each chunk's passes
    mesh-parallel as well.
    """
    from repro.sim.scenarios import DeviceStream

    if mode not in ("bucketed", "loop", "sharded", "streamed"):
        raise ValueError(f"unknown engine mode {mode!r}")

    if mode == "streamed":
        if isinstance(dataset, DeviceStream):
            stream = dataset
        else:
            stream = _dataset_as_stream(dataset)
        yield from _iter_streamed(
            stream, lam=lam, seed=seed,
            min_samples=stream.min_samples if min_samples is None else min_samples,
            epochs=epochs, group_cap=group_cap, available=available,
            shards=shards, chunk_devices=chunk_devices,
        )
        return

    if isinstance(dataset, DeviceStream):
        fed = dataset.materialize()
        mask = np.asarray(fed.available)
        if available is not None:
            mask = mask & np.asarray(available, bool)
        dataset, available = fed.dataset, mask

    shard = make_shard_ctx(shards, epochs) if mode == "sharded" else None
    min_samples = dataset.min_samples if min_samples is None else min_samples
    ids = [
        i for i in range(dataset.n_devices)
        if available is None or bool(available[i])
    ]
    total = len(ids)
    done = 0

    if mode == "loop":
        chunk = 32
        for lo in range(0, total, chunk):
            elapsed = stopwatch()
            outs = [
                train_device(i, dataset.devices[i], min_samples, lam, seed, epochs)
                for i in ids[lo : lo + chunk]
            ]
            done += len(outs)
            yield GroupUpdate(0, outs, elapsed(), done, total)
        return

    # --- bucketed mode ---
    elapsed = stopwatch()
    fallback: List[DeviceOutcome] = []
    by_bucket: Dict[int, List[tuple]] = {}
    for i in ids:
        bucket, payload = _classify_device(i, dataset.devices[i], min_samples,
                                           seed=seed)
        if bucket is None:
            fallback.append(payload)
        else:
            by_bucket.setdefault(bucket, []).append((i, payload))
    if fallback:
        done += len(fallback)
        yield GroupUpdate(0, fallback, elapsed(), done, total)

    for bucket, outs, secs in _train_buckets(by_bucket, lam, epochs,
                                             group_cap, shard):
        done += len(outs)
        yield GroupUpdate(bucket, outs, secs, done, total)


def _dataset_as_stream(dataset: FederatedDataset):
    """View a materialized dataset through the stream interface."""
    from repro.sim.scenarios import DeviceStream, ScenarioSpec

    spec = ScenarioSpec(
        name=dataset.name, n_devices=dataset.n_devices,
        dim=dataset.dim, min_samples=dataset.min_samples,
    )
    return DeviceStream(spec=spec, gen=lambda i: dataset.devices[i])


def _iter_streamed(
    stream, *, lam, seed, min_samples, epochs, group_cap, available,
    shards, chunk_devices,
) -> Iterator[GroupUpdate]:
    if chunk_devices < 1:
        raise ValueError(f"chunk_devices must be >= 1, got {chunk_devices}")
    shard = make_shard_ctx(shards, epochs) if shards is not None else None

    def admitted(i: int) -> bool:
        if available is not None and not bool(available[i]):
            return False
        return stream.available(i)

    if available is None:
        total = stream.count_available()
    else:
        total = sum(1 for i in range(stream.n_devices) if admitted(i))
    done = 0

    tracer = current_tracer()
    reg = default_registry()
    for lo in range(0, stream.n_devices, chunk_devices):
        hi = min(lo + chunk_devices, stream.n_devices)
        with tracer.span("engine.chunk", cat="engine", lo=lo, hi=hi):
            elapsed = stopwatch()
            fallback: List[DeviceOutcome] = []
            by_bucket: Dict[int, List[tuple]] = {}
            for i in range(lo, hi):
                if not admitted(i):
                    continue
                bucket, payload = _classify_device(i, stream.device(i),
                                                   min_samples, seed=seed)
                if bucket is None:
                    fallback.append(payload)
                else:
                    by_bucket.setdefault(bucket, []).append((i, payload))
            if fallback:
                done += len(fallback)
                yield GroupUpdate(0, fallback, elapsed(), done, total)
            for bucket, outs, secs in _train_buckets(by_bucket, lam, epochs,
                                                     group_cap, shard):
                done += len(outs)
                yield GroupUpdate(bucket, outs, secs, done, total)
        reg.counter("engine.chunks").inc()
        # the chunk's devices die with these locals on the next pass —
        # nothing population-sized is ever retained here


def train_selected(
    stream,
    ids,
    *,
    lam: float = 0.01,
    seed: int = 0,
    min_samples: Optional[int] = None,
    epochs: int = 20,
    group_cap: int = 256,
    shards: Optional[int] = None,
) -> Dict[int, DeviceOutcome]:
    """Regenerate and train ONLY the given device ids from a stream.

    The server-side rebuild after a streamed selection pass: with k
    winners out of a 10^6-device population, this touches k devices
    instead of re-streaming everyone. Same classification, bucketing,
    and fit/score math as every other tier, so the outcomes equal what
    the full pass produced for those ids (group-composition invariance
    again).
    """
    min_samples = stream.min_samples if min_samples is None else min_samples
    shard = make_shard_ctx(shards, epochs) if shards is not None else None
    out: Dict[int, DeviceOutcome] = {}
    by_bucket: Dict[int, List[tuple]] = {}
    for i in sorted(set(int(i) for i in ids)):
        bucket, payload = _classify_device(i, stream.device(i), min_samples,
                                           seed=seed)
        if bucket is None:
            out[payload.device_id] = payload
        else:
            by_bucket.setdefault(bucket, []).append((i, payload))
    for _, outs, _ in _train_buckets(by_bucket, lam, epochs, group_cap, shard):
        for o in outs:
            out[o.device_id] = o
    return out


def train_population(
    dataset: FederatedDataset, on_update=None, **kw
) -> PopulationResult:
    """Drain `iter_population` into a result sorted by device id,
    invoking ``on_update(GroupUpdate)`` after each streamed group."""
    elapsed = stopwatch()
    groups = []
    for update in iter_population(dataset, **kw):
        groups.append(update)
        if on_update is not None:
            on_update(update)
    outcomes = sorted(
        (o for g in groups for o in g.outcomes), key=lambda o: o.device_id
    )
    seconds = elapsed()
    log.info(
        "trained %d devices in %d groups (%.2fs, mode=%s)",
        len(outcomes), len(groups), seconds, kw.get("mode", "bucketed"),
    )
    return PopulationResult(outcomes, seconds, groups)
