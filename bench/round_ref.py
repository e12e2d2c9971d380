"""The round's semantics on top of ``reference``: what the round reports.

``reference_round`` computes, from the raw devices and the seed alone,
every number the paper's round (``run_protocol``) reports and every
model it keeps, in the same form that ``runners/round.outputs_of``
reads off the program's result:

    {"aucs": {name: value}, "best": (strategy, k),
     "members": [(support, coef, gamma), ...]   # the best cell
     "student": (support, coef, gamma) | None,
     "evals": [scores of every device's test rows, one array an evaluation]}

The evaluations come in the round's order: the pooled ideal, every
strategy's cells (five random draws per k), the full ensemble, the
distilled student. From the reference alone come ``cell_members``
(every cell's members), ``eval`` (the test rows, labels and device of
each row), ``proxy`` (the distillation's proxy rows, their bandwidth
and the teacher's scores on them) and ``ops``, the round's operations
as ``counting.round_ops`` counts them (the distillation's CG iterations
are not counted: the program does not report how many it ran).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.counting import round_ops
from bench.reference import (ensemble_scores, gamma_of, grouped_auc, kernel_ridge,
                             split, train_local)

DISTILL_STREAM = 0xD157


def select(strategy: str, ids: np.ndarray, n_train: np.ndarray, val_auc: np.ndarray,
           eligible: np.ndarray, k: int, seed: int) -> List[int]:
    """The paper's three strategies over the reports (ids ascending)."""
    if strategy == "cv":
        m = eligible & (val_auc >= 0.5)
        order = np.lexsort((ids[m], -val_auc[m]))
        return [int(i) for i in ids[m][order][:k]]
    if strategy == "data":
        m = eligible
        order = np.lexsort((ids[m], -n_train[m]))
        return [int(i) for i in ids[m][order][:k]]
    if strategy == "random":
        return [int(i) for i in np.random.default_rng(seed).permutation(ids[eligible])[:k]]
    raise KeyError(strategy)


def best_cell(aucs: Dict[str, float], rnd: dict) -> Tuple[str, int]:
    """The round's best cell: best strategy first, then its best k
    (first of equals, in configuration order)."""
    ks = rnd["ks"]
    best = {s: max(aucs[f"{s}.{k}"] for k in ks) for s in rnd["strategies"]}
    s = max(best, key=best.get)
    return s, max(ks, key=lambda k: aucs[f"{s}.{k}"])


def reference_round(devices: Sequence[Tuple[np.ndarray, np.ndarray]], min_samples: int,
                    rnd: dict, seed: int, precision: str) -> dict:
    m = len(devices)
    splits = [split(x, y, seed, i) for i, (x, y) in enumerate(devices)]
    loc = train_local(splits, min_samples, rnd["lam"], rnd["epochs"], precision)
    ids = np.arange(m)
    ex = np.concatenate([s["test"][0] for s in splits])
    ey = np.concatenate([s["test"][1] for s in splits])
    eg = np.concatenate([np.full(len(s["test"][1]), i) for i, s in enumerate(splits)])

    def member(i):
        return splits[i]["train"][0], loc.coef[i], loc.gamma[i]

    ev = (ex, ey, eg, m)
    scored: List[np.ndarray] = []         # every evaluation's scores, in order
    evals: List[Tuple[int, int]] = []     # (rows, supports) of every evaluation

    def mean_auc(members) -> float:
        evals.append((len(ex), sum(len(mb[1]) for mb in members)))
        scored.append(ensemble_scores(ex, members, precision))
        return float(grouped_auc(eg, ey, scored[-1], m).mean())

    # the pooled ideal, evaluated first, as the round does
    pooled_x = np.concatenate([s["train"][0] for s in splits])
    pooled_y = np.concatenate([s["train"][1] for s in splits])
    if len(pooled_y) > rnd["ideal_cap"]:
        pick = np.random.default_rng(seed).choice(len(pooled_y), rnd["ideal_cap"],
                                                  replace=False)
        pooled_x, pooled_y = pooled_x[pick], pooled_y[pick]
    ideal = train_local([{"n": len(pooled_y), "train": (pooled_x, pooled_y),
                          "test": (pooled_x[:1], pooled_y[:1]),
                          "val": (pooled_x[:1], pooled_y[:1])}],
                        0, rnd["lam"], rnd["epochs"], precision)
    aucs = {"local_mean": float(loc.local_auc.mean()),
            "ideal_mean": mean_auc([(pooled_x, ideal.coef[0], ideal.gamma[0])])}
    cells: Dict[Tuple[str, int], List[int]] = {}
    for s in rnd["strategies"]:
        for k in rnd["ks"]:
            pick = select(s, ids, loc.n_train, loc.val_auc, loc.eligible, k, seed)
            cells[(s, k)] = pick
            if s == "random":
                trials = [select(s, ids, loc.n_train, loc.val_auc, loc.eligible, k,
                                 seed + 17 * t) for t in range(rnd["random_trials"])]
                aucs[f"{s}.{k}"] = float(np.mean([mean_auc([member(i) for i in t])
                                                  for t in trials]))
            else:
                aucs[f"{s}.{k}"] = mean_auc([member(i) for i in pick])
    aucs["full"] = mean_auc([member(i) for i in ids[loc.eligible]])

    out = {"aucs": aucs, "student": None, "proxy": None, "eval": ev, "evals": scored}
    out["cell_members"] = {c: [member(i) for i in pick] for c, pick in cells.items()}
    out["best"] = best_cell(aucs, rnd)
    out["members"] = out["cell_members"][out["best"]]

    dist = rnd.get("distill")
    if dist:
        pool = np.concatenate([s["val"][0] for s in splits])
        rng = np.random.default_rng(np.random.SeedSequence([seed, DISTILL_STREAM, 0]))
        if len(pool) > dist["proxy_size"]:
            pool = pool[rng.choice(len(pool), dist["proxy_size"], replace=False)]
        xp = np.unique(pool.astype(np.float32), axis=0)
        g = gamma_of(xp)
        soft = ensemble_scores(xp, out["members"], precision)
        evals.append((len(xp), sum(len(mb[1]) for mb in out["members"])))
        alpha = kernel_ridge(xp, soft, g, dist["eps"], dist["tol"], dist["maxiter"],
                             precision)
        out["student"] = (xp, alpha, g)
        out["proxy"] = (xp, g, soft)
        aucs["distilled"] = mean_auc([out["student"]])
    local = [(int(loc.n_train[i]), len(splits[i]["val"][1]) + len(splits[i]["test"][1]))
             for i in ids[loc.eligible]]
    out["ops"] = round_ops(local, devices[0][0].shape[1], rnd["epochs"], evals,
                           [len(pooled_y)])
    return out

