"""Runner for round traffic: the paper's one-shot round, back to back.

Set-up makes the federation from the seed and runs one whole round on
it (``run_protocol``), which compiles and warms every shape the window
uses. The window starts rounds on the same federation while fewer than
``seconds`` have passed, and ends when the last round ends; ``round_s``
is the window over the rounds it holds. The last round's result is
compared with the plain reference (``bench/round_ref.py``).

Every round keeps the scores its evaluation produced: each evaluated
ensemble's (and the ideal's and the distilled student's) score of
every device's test rows, as the round folds them into its AUCs. They
are kept by handing the round's AUC fold an accumulator that keeps
each device's scores beside its rank state, so the round runs the same
calls as without it.
"""
from __future__ import annotations

import contextlib
import time
from types import SimpleNamespace

import numpy as np

from bench.check import round_numbers
from bench.round_ref import best_cell, reference_round


def _evaluations_kept():
    """A stand-in for the round's ``streaming_grouped_auc`` that keeps
    every evaluation's scores, one array a device, in call order."""
    import repro.core.protocol as protocol
    from repro.utils.metrics import GroupedAUC

    fold = protocol.streaming_grouped_auc
    kept = []

    class Keeping(GroupedAUC):
        def update(self, group, labels, scores):
            kept[-1].append(scores)
            return super().update(group, labels, scores)

    def keeping(score_fn, groups, *, chunk=8192, acc=None):
        kept.append([])
        return fold(score_fn, groups, chunk=chunk, acc=Keeping() if acc is None else acc)

    @contextlib.contextmanager
    def installed():
        kept.clear()
        protocol.streaming_grouped_auc = keeping
        try:
            yield
        finally:
            protocol.streaming_grouped_auc = fold

    return kept, installed


def setup(ctx) -> SimpleNamespace:
    from repro.core import run_protocol
    from repro.data.federated import DeviceData, FederatedDataset
    from repro.distill import DistillConfig

    cfg, rnd, seed = ctx.config, ctx.config["round"], ctx.seed
    devices = ctx.load_config(cfg["federation"]).make(cfg, seed)
    ds = FederatedDataset(name=cfg["name"], devices=[DeviceData(x, y) for x, y in devices],
                          min_samples=int(cfg["min_samples"]), dim=int(cfg["dim"]))
    d = rnd["distill"]
    dcfg = DistillConfig(proxy_size=d["proxy_size"], solver=d["solver"], proxy=d["proxy"],
                         eps=d["eps"], tol=d["tol"], maxiter=d["maxiter"])
    kept, keeping = _evaluations_kept()

    def call():
        with keeping():
            return run_protocol(ds, ks=tuple(rnd["ks"]), strategies=tuple(rnd["strategies"]),
                                lam=rnd["lam"], seed=seed, ideal_cap=rnd["ideal_cap"],
                                random_trials=rnd["random_trials"], eval_chunk=rnd["eval_chunk"],
                                engine=rnd["engine"], codec=rnd["codec"], distill=dcfg)

    last = call()                       # the warm-up round
    return SimpleNamespace(call=call, devices=devices, last=last, kept=kept)


def window(state, seconds: float, mark, profiled) -> SimpleNamespace:
    """Rounds while fewer than ``seconds`` have passed; the first one is
    the one profiled in a traced run (a round is some 10^5 device
    operations, so a whole window would not fit a run)."""
    t0 = time.perf_counter()
    n = 0
    while n == 0 or time.perf_counter() - t0 < seconds:
        with profiled() if n == 0 else contextlib.nullcontext(), mark("bench.round"):
            state.last = state.call()
        n += 1
    w = time.perf_counter() - t0
    return SimpleNamespace(metrics={"round_s": w / n}, attempted=n, failed=0,
                           window_s=w, units=n, traced_units=1, stats={})


def outputs_of(res, rnd: dict, kept) -> dict:
    """The program's round result in the reference's form; ``kept``:
    every evaluation's per-device scores, in the round's call order."""
    aucs = {"local_mean": res.local_mean_auc, "ideal_mean": res.ideal_mean_auc,
            "full": res.full_ensemble_auc}
    for s, per_k in res.ensemble_auc.items():
        for k, v in per_k.items():
            aucs["distilled" if s == "distilled" else f"{s}.{k}"] = v
    members = [(m.support_x, m.coef, m.gamma) for m in res.server_scorer.members]
    st = res.student
    return {"aucs": {k: float(v) for k, v in aucs.items()},
            "best": best_cell(aucs, rnd), "members": members,
            "student": None if st is None else (st.support_x, st.coef, st.gamma),
            "evals": [np.concatenate([np.asarray(s, np.float32) for s in e]) if e
                      else np.zeros(0, np.float32) for e in kept]}


def check(state, ctx):
    """(numbers compared, operations of one round)."""
    cfg, rnd = ctx.config, ctx.config["round"]
    out = outputs_of(state.last, rnd, state.kept)
    state.last = state.call = state.kept = None      # free the program's state first
    ref = reference_round(state.devices, int(cfg["min_samples"]), rnd, ctx.seed, "highest")
    return round_numbers(out, ref, rnd), ref["ops"]


def control(ctx, precision: str, seconds: float):
    """The numbers of the reference computed at ``precision`` and put in
    the program's place (the program is not run; a round's work does not
    depend on ``seconds``)."""
    cfg, rnd = ctx.config, ctx.config["round"]
    devices = ctx.load_config(cfg["federation"]).make(cfg, ctx.seed)
    ref = reference_round(devices, int(cfg["min_samples"]), rnd, ctx.seed, "highest")
    ctl = reference_round(devices, int(cfg["min_samples"]), rnd, ctx.seed, precision)
    return round_numbers(ctl, ref, rnd)
