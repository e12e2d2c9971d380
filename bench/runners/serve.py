"""Runner for serve traffic: open-loop Poisson arrivals of one-row requests.

Set-up makes the federation from the seed, trains it with the program's
engine, and serves the ensemble the traffic names (``strategy``, ``k``)
through ``EnsembleScorer(...).scheduler(ServeConfig(...))``; it scores
one batch at each bucket size, so the window compiles nothing.

The window draws arrival times at ``rate_per_s`` (exponential gaps) over
``seconds`` and a test row for each, from the seed. It submits every
request that is due, then calls ``flush()``, and again; when nothing is
due it sleeps until the next arrival. A request's latency runs from its
due time to the end of the flush that answered it, so a slow flush
delays every request behind it. A request the queue refuses is failed
and counts as missing: its latency is ``MISSING_S``, past any percentile
that could pass. Every answer is compared with the reference; the
generator's worst lateness (how long a due request waited for the
previous flush to end before it was submitted) goes to standard error.
"""
from __future__ import annotations

import gc
import time
from types import SimpleNamespace

import numpy as np

from bench.check import answer_gap
from bench.reference import ensemble_scores, split, train_local
from bench.round_ref import select

MISSING_S = 3600.0


def setup(ctx) -> SimpleNamespace:
    from repro.comm import ModelExchange
    from repro.core import Ensemble
    from repro.data.federated import DeviceData, FederatedDataset
    from repro.serve import EnsembleScorer, ServeConfig
    from repro.sim import train_population

    cfg, tr, seed = ctx.config, ctx.traffic, ctx.seed
    devices = ctx.load_config(cfg["federation"]).make(cfg, seed)
    ds = FederatedDataset(name=cfg["name"], devices=[DeviceData(x, y) for x, y in devices],
                          min_samples=int(cfg["min_samples"]), dim=int(cfg["dim"]))
    rnd = cfg["round"]
    outcomes = train_population(ds, lam=rnd["lam"], seed=seed, mode=rnd["engine"]).outcomes
    ex = ModelExchange({o.device_id: o.model for o in outcomes}, [o.report for o in outcomes],
                       codec=rnd["codec"])
    ens = Ensemble([ex.received(i) for i in ex.pick(tr["strategy"], int(tr["k"]), seed)])
    sched = EnsembleScorer(ens).scheduler(ServeConfig(
        max_batch=int(tr["max_batch"]), max_queue=int(tr["max_queue"]),
        buckets=tuple(tr["buckets"]), cache_size=int(tr["cache_size"])))
    pool = request_pool(devices, seed)
    for b in sched.config.buckets:                  # one batch per compiled shape
        sched.run(list(pool[:b]))
    supports = sum(len(m.coef) for m in ens.members)
    # what set-up built lives for the whole run: keep it out of the
    # collector's full passes, as a long-running server would
    gc.collect()
    gc.freeze()
    return SimpleNamespace(sched=sched, devices=devices, pool=pool, supports=supports,
                           rate=float(tr["rate_per_s"]), seed=seed, rows=None, answers=None)


def request_pool(devices, seed: int) -> np.ndarray:
    """Every device's test rows, split as the protocol splits them."""
    return np.concatenate([split(x, y, seed, i)["test"][0] for i, (x, y) in enumerate(devices)])


class _GcPauses:
    """gc callback: the garbage collector's pauses over 10 ms, in ms."""

    def __init__(self):
        self.ms, self._t = [], 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif (dt := (time.perf_counter() - self._t) * 1e3) > 10:
            self.ms.append(dt)


def arrivals(rate: float, seconds: float, seed: int, pool_rows: int):
    """Due times (s) in [0, seconds) and the pool row of each request."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5E4E]))
    n = int(rate * seconds * 1.2 + 64)
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(rng.exponential(1.0 / rate, n))])
    t = t[t < seconds]
    return t, rng.integers(pool_rows, size=len(t))


def percentile_ms(lat_s: np.ndarray, q: float) -> float:
    """Nearest-rank percentile over all requests, in ms."""
    return float(np.quantile(lat_s, q / 100.0, method="inverted_cdf") * 1e3)


def window(state, seconds: float, mark, profiled, latencies=None) -> SimpleNamespace:
    """The whole window is profiled in a traced run (a flush is a handful
    of device operations). ``latencies``, a dict, receives every
    request's latency in ms, in arrival order."""
    from repro.serve import QueueFullError

    sched = state.sched
    due, rows = arrivals(state.rate, seconds, state.seed, len(state.pool))
    n = len(due)
    lat = np.full(n, MISSING_S)
    answers = np.full(n, np.nan)
    s0 = dict(vars(sched.stats))
    failed = i = 0
    late = 0.0
    pauses = _GcPauses()
    gc.callbacks.append(pauses)
    with profiled():
        t0 = time.perf_counter()
        while i < n:
            now = time.perf_counter() - t0
            if due[i] > now:
                time.sleep(due[i] - now)
                continue
            j = int(np.searchsorted(due, now, side="right"))
            late = max(late, now - due[i])
            tickets = []
            for r in range(i, j):
                try:
                    tickets.append((r, sched.submit(state.pool[rows[r]])))
                except QueueFullError:
                    failed += 1
            i = j
            with mark("bench.flush"):
                sched.flush()
            end = time.perf_counter() - t0
            for r, t in tickets:
                answers[r] = float(sched.result(t))
                lat[r] = end - due[r]
        w = time.perf_counter() - t0
    gc.callbacks.remove(pauses)
    state.rows, state.answers = rows, answers
    if latencies is not None:
        latencies["ms"] = lat * 1e3
    s1 = vars(sched.stats)
    return SimpleNamespace(
        metrics={"serve_p50_ms": percentile_ms(lat, 50), "serve_p99_ms": percentile_ms(lat, 99)},
        attempted=n, failed=failed, window_s=w, units=n - failed, traced_units=n - failed,
        stats={k: s1[k] - s0[k] for k in s0}, generator_late_s=late, gc_pauses_ms=pauses.ms)


def _served(ctx, devices, pool, rows, precision: str) -> np.ndarray:
    """The reference's answers to ``rows`` of the pool: it trains the
    local phase and picks the traffic's cell itself."""
    cfg, tr, seed = ctx.config, ctx.traffic, ctx.seed
    rnd = cfg["round"]
    splits = [split(x, y, seed, i) for i, (x, y) in enumerate(devices)]
    loc = train_local(splits, int(cfg["min_samples"]), rnd["lam"], rnd["epochs"], precision)
    ids = select(tr["strategy"], np.arange(len(splits)), loc.n_train, loc.val_auc,
                 loc.eligible, int(tr["k"]), seed)
    members = [(splits[i]["train"][0], loc.coef[i], loc.gamma[i]) for i in ids]
    return ensemble_scores(pool[rows], members, precision)


def check(state, ctx):
    """(numbers compared, operations of one answered request)."""
    state.sched = None                  # free the program's state first
    answered = np.isfinite(state.answers)      # refused requests are failed, not wrong
    want = _served(ctx, state.devices, state.pool, state.rows[answered], "highest")
    ops = 2.0 * state.supports * (int(ctx.config["dim"]) + 1)
    return [("answer_gap", answer_gap(state.answers[answered], want))], ops


def control(ctx, precision: str, seconds: float):
    """The numbers of the reference computed at ``precision`` and put in
    the program's place, on every request a window of ``seconds`` draws
    (the program is not run)."""
    cfg = ctx.config
    devices = ctx.load_config(cfg["federation"]).make(cfg, ctx.seed)
    pool = request_pool(devices, ctx.seed)
    rows = arrivals(float(ctx.traffic["rate_per_s"]), seconds, ctx.seed, len(pool))[1]
    want = _served(ctx, devices, pool, rows, "highest")
    return [("answer_gap", answer_gap(_served(ctx, devices, pool, rows, precision), want))]
