"""One-shot federated learning protocol simulation (the paper, end to end).

Simulates the full round on a federated dataset:
  1. every device splits its data 50/40/10 (train/test/val);
  2. devices train local RBF-SVMs to completion (data-deficient devices
     fall back to constant classifiers — the paper's local baseline);
  3. devices report scalar metadata (n_train, val AUC);
  4. the server selects k models per strategy (cv / data / random) and
     receives them — the SINGLE round of communication;
  5. ensembles are evaluated on every device's test split (mean AUC);
  6. optionally, the server distills the best ensemble on proxy data
     via ``repro.distill`` (``distill=DistillConfig(...)`` selects the
     solver, proxy source, proxy size, and an independent student
     download codec; ``distill_proxy=l`` remains as shorthand). The
     proxy draw runs on its own SeedSequence-derived stream, so it is
     reproducible regardless of ``ideal_cap`` or pooled-data size.

Communication is accounted on a ``repro.comm`` ledger: every protocol
message — each device's pre-round ``DeviceReport`` (18 wire bytes),
every selected model upload, the distilled-student download — is
recorded as a typed ``CommEvent`` with its EXACT wire-encoded size
(``len(wire.encode(...))``), and ``comm_bytes`` is the ledger's per-tag
sum. Uploads go through a wire codec (``codec=``: fp32 / fp16 / int8 /
topk); ensembles are evaluated on the DECODED models, so lossy codecs
honestly pay their AUC cost, and int8 payloads score through the
``rbf_gram_q8`` kernel without materializing fp32 supports. An optional
``budget_bytes`` cap turns selection into the greedy knapsack of
``repro.comm.budget`` (strategy-rank order, unaffordable models
skipped; a slack budget changes nothing).

Ensemble evaluation is STREAMING: device test splits feed the fused
``ensemble_score`` serve path in ``eval_chunk``-sized blocks whose
scores fold straight into merge-able per-device AUC accumulators
(``utils.metrics.streaming_grouped_auc``) — each Ensemble is packed
once, and neither the concatenated test matrix nor a full score vector
ever materializes.

Local training runs on the ``repro.sim`` engine: ``engine="bucketed"``
(default) fits whole buckets of devices in vectorized batched-Gram +
vmap'd-SDCA passes; ``engine="sharded"`` lays the same buckets across
all local accelerators (bitwise-identical results — see
tests/test_engines.py); ``engine="streamed"`` trains through the lazy
chunked tier (same per-device math — here the dataset is already
materialized, so it only bounds accelerator batches);
``engine="loop"`` is the original sequential path, kept as the oracle
for equivalence tests. Per-device randomness is derived via
``derive_device_seed`` in every mode, so results are bit-reproducible
regardless of device iteration order, batching, or mesh shape.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Sequence, TYPE_CHECKING

import numpy as np

from repro.core.svm import train_svm
from repro.core.ensemble import Ensemble
from repro.obs.trace import span
from repro.data.federated import FederatedDataset, DeviceData
from repro.data.partition import pool_devices
from repro.utils.metrics import roc_auc, streaming_grouped_auc
from repro.utils.logging import get_logger

if TYPE_CHECKING:  # runtime import would cycle: comm.budget <- core.selection
    from repro.comm import CommLedger
    from repro.distill import DistillConfig

log = get_logger("protocol")


@dataclasses.dataclass
class ProtocolResult:
    dataset: str
    local_mean_auc: float
    ideal_mean_auc: float
    ensemble_auc: Dict[str, Dict[int, float]]  # strategy -> k -> mean AUC
    full_ensemble_auc: float
    best: Dict[str, float]  # strategy -> best-k mean AUC
    comm_bytes: Dict[str, float]  # ledger per-tag byte totals
    per_device: Dict[str, np.ndarray]
    ledger: Optional["CommLedger"] = None
    codec: str = "fp32"
    # the distilled student AS DEVICES RECEIVE IT (decoded from its
    # download wire form) — drop it straight into serve.EnsembleScorer
    student: Optional[object] = None
    student_codec: Optional[str] = None
    # which repro.agg strategy combined the members, and the best
    # cell's server scorer (deployable via serve/fleet when there is
    # no distilled student)
    aggregator: str = "mean"
    server_scorer: Optional[object] = None

    def relative_gain_over_local(self) -> float:
        b = max(self.best.values())
        return (b - self.local_mean_auc) / max(self.local_mean_auc, 1e-9)

    def fraction_of_ideal(self) -> float:
        return max(self.best.values()) / max(self.ideal_mean_auc, 1e-9)


def _train_device(dev_id: int, dev: DeviceData, min_samples: int, lam: float, seed: int):
    """Sequential per-device oracle; canonical body lives in the engine."""
    from repro.sim.engine import train_device

    return train_device(dev_id, dev, min_samples, lam, seed)


def _mean_auc_over_devices(
    devices: Sequence["DeviceOutcome"], scores_fn, chunk: int = 8192
) -> tuple:
    """scores_fn(X_block) -> scores for one (b, d) query block.

    Streams every device's test split through merge-able per-device AUC
    accumulators (``utils.metrics.streaming_grouped_auc``) in
    ``chunk``-row blocks: the concatenated (N, d) test matrix never
    materializes (feature memory is O(chunk)); the accumulators retain
    the scores as per-device rank-statistic state (O(N) scalars in
    exact mode — see the metrics module docstring for the fixed-memory
    binned trade-off)."""
    ga = streaming_grouped_auc(
        scores_fn,
        ((d.device_id, d.splits["test"].x, d.splits["test"].y) for d in devices),
        chunk=chunk,
    )
    with span("eval.auc"):
        per = ga.compute()
    aucs = np.array([per[d.device_id] for d in devices])
    return float(np.mean(aucs)), aucs


def run_protocol(
    dataset: FederatedDataset,
    ks: Sequence[int] = (1, 10, 50, 100),
    strategies: Sequence[str] = ("cv", "data", "random"),
    lam: float = 0.01,
    seed: int = 0,
    ideal_cap: int = 2000,
    random_trials: int = 5,
    distill_proxy: int = 0,
    eval_chunk: int = 8192,
    engine: str = "bucketed",
    codec: str = "fp32",
    budget_bytes: Optional[int] = None,
    distill: Optional["DistillConfig"] = None,
    aggregator: str = "mean",
) -> ProtocolResult:
    # deferred: repro.comm pulls core.selection back in at import time
    from repro.agg import build_cell, get_aggregator
    from repro.comm import CommLedger, ModelExchange
    from repro.distill import DistillConfig
    from repro.sim.engine import train_population

    # ``distill=`` is the full config; the legacy ``distill_proxy=l``
    # shorthand maps onto it (and fills in a config without a size)
    if distill is None:
        distill = DistillConfig(proxy_size=distill_proxy)
    elif distill.proxy_size == 0 and distill_proxy > 0:
        distill = dataclasses.replace(distill, proxy_size=distill_proxy)

    m = dataset.n_devices
    with span("round.train", cat="round", devices=m, engine=engine):
        devices = train_population(dataset, lam=lam, seed=seed,
                                   mode=engine).outcomes
    reports = [d.report for d in devices]
    eligible_ids = [r.device_id for r in reports if r.eligible]

    # --- the wire: priced uploads, decoded models, metadata on ledger ---
    with span("round.encode", cat="round", codec=codec):
        ex = ModelExchange({d.device_id: d.model for d in devices}, reports,
                           codec=codec, budget_bytes=budget_bytes)
    codec_spec = ex.codec
    log.info("trained %d local models (%s, engine=%s, codec=%s)",
             m, dataset.name, engine, codec_spec)
    ledger = CommLedger()
    ex.record_metadata(ledger)

    # server aggregation strategy (repro.agg); extras are computed from
    # the by-id outcomes and recorded per canonical cell in the sweep
    agg = get_aggregator(aggregator)
    by_id = {d.device_id: d for d in devices}

    def outcomes_for(want):
        return by_id

    # --- local baseline (paper Fig. 1 "local") ---
    with span("round.local", cat="round"):
        local_aucs = [
            roc_auc(d.splits["test"].y, d.local_test_scores) for d in devices
        ]
    local_mean = float(np.mean(local_aucs))

    # --- unattainable ideal: pooled-data SVM (subsampled for tractability) ---
    with span("round.ideal", cat="round", cap=ideal_cap):
        pooled = pool_devices([d.splits["train"] for d in devices])
        rng = np.random.default_rng(seed)
        if len(pooled.y) > ideal_cap:
            idx = rng.choice(len(pooled.y), ideal_cap, replace=False)
            pooled = DeviceData(pooled.x[idx], pooled.y[idx])
        ideal_model = train_svm(pooled.x, pooled.y, lam=lam)
        ideal_mean, ideal_aucs = _mean_auc_over_devices(
            devices, ideal_model.predict)

    # --- aggregated cells per strategy and k (DECODED models + DECODED
    # extras; extras ride the ledger once per canonical cell, mirroring
    # record_uploads) ---
    ensemble_auc: Dict[str, Dict[int, float]] = {}
    cell_scorers: Dict[tuple, object] = {}
    for strat in strategies:
        ensemble_auc[strat] = {}
        with span("round.select", cat="round", strategy=strat):
            for k in ks:
                extra_tag = f"agg_extra_{strat}_k{k}"
                if strat == "random":
                    trials = []
                    for t in range(random_trials):
                        tids = ex.pick("random", k, seed + 17 * t)
                        if not tids:
                            continue
                        scorer = build_cell(agg, ex, tids, outcomes_for, ledger,
                                            extra_tag, seed, record=False)
                        auc, _ = _mean_auc_over_devices(
                            devices, partial(scorer.predict, chunk=eval_chunk), eval_chunk)
                        trials.append(auc)
                    if trials:
                        ensemble_auc[strat][k] = float(np.mean(trials))
                    ids = ex.pick("random", k, seed)
                    if ids:
                        cell_scorers[(strat, k)] = build_cell(
                            agg, ex, ids, outcomes_for, ledger, extra_tag, seed)
                else:
                    ids = ex.pick(strat, k, seed)
                    if not ids:
                        continue
                    scorer = build_cell(agg, ex, ids, outcomes_for, ledger,
                                        extra_tag, seed)
                    cell_scorers[(strat, k)] = scorer
                    auc, _ = _mean_auc_over_devices(
                        devices, partial(scorer.predict, chunk=eval_chunk), eval_chunk)
                    ensemble_auc[strat][k] = auc
                ex.record_uploads(ledger, ids, f"upload_{strat}_k{k}")
        log.info("%s/%s: %s", dataset.name, strat, ensemble_auc[strat])

    # --- full ensemble of all eligible devices ---
    with span("round.eval", cat="round", ensemble=len(eligible_ids)):
        full_ens = Ensemble([ex.received(i) for i in eligible_ids])
        full_auc, full_aucs = _mean_auc_over_devices(
            devices, partial(full_ens.predict, chunk=eval_chunk), eval_chunk)
    ex.record_uploads(ledger, eligible_ids, "upload_full")

    best = {s: max(v.values()) for s, v in ensemble_auc.items() if v}
    per_device = {
        "local": np.array(local_aucs),
        "ideal": ideal_aucs,
        "full_ensemble": full_aucs,
    }
    # the best cell's server scorer — what the round actually deploys
    # when no distillation compresses it further
    server_scorer = None
    if best:
        bs = max(best, key=best.get)
        bk = max(ensemble_auc[bs], key=ensemble_auc[bs].get)
        server_scorer = cell_scorers.get((bs, bk))
    # --- optional distillation of the best aggregated cell ---
    student_recv = None
    student_codec = None
    if distill.proxy_size > 0 and best:
        from repro.distill import distill_round

        best_strat = max(best, key=best.get)
        best_k = max(ensemble_auc[best_strat], key=ensemble_auc[best_strat].get)
        ids = ex.pick(best_strat, best_k, seed)
        teacher = cell_scorers.get((best_strat, best_k))
        if teacher is None:
            teacher = build_cell(agg, ex, ids, outcomes_for, ledger,
                                 f"agg_extra_{best_strat}_k{best_k}", seed,
                                 record=False)
        # the distillation leg (proxy draw on its OWN SeedSequence
        # stream — independent of the ideal-subsample rng above —
        # solve, wire through the student codec, ledger) is shared with
        # run_population; devices decode ``dr.student``, so its AUC and
        # its bytes match up. The teacher is the AGGREGATED scorer, so
        # non-mean strategies distill what they actually serve.
        dr = distill_round(teacher.predict, devices, distill, seed, codec_spec,
                           ledger, dim=dataset.dim)
        student_recv, student_codec = dr.student, dr.codec
        with span("round.student", cat="round"):
            dist_auc, dist_aucs = _mean_auc_over_devices(devices, student_recv.predict)
        per_device["distilled"] = dist_aucs
        ledger.record("down", "ensemble_download", ex.ensemble_nbytes(ids),
                      codec=codec_spec, tag="download_ensemble")
        ensemble_auc.setdefault("distilled", {})[best_k] = dist_auc

    return ProtocolResult(
        dataset=dataset.name,
        local_mean_auc=local_mean,
        ideal_mean_auc=ideal_mean,
        ensemble_auc=ensemble_auc,
        full_ensemble_auc=full_auc,
        best=best,
        comm_bytes=ledger.as_dict(),
        per_device=per_device,
        ledger=ledger,
        codec=codec_spec,
        student=student_recv,
        student_codec=student_codec,
        aggregator=agg.spec,
        server_scorer=server_scorer,
    )
