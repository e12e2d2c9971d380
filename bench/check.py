"""The comparison that decides ``correct``: each number beside its limit.

Every function here takes outputs in the common form of
``round_ref.reference_round`` (the program's, read off its result, or
the control's) and the reference's own, and returns ``(name, value)``
pairs; ``judge`` puts each that the cell's ``bench/limits`` file names
beside its limit.
Values are gaps, so that 0 is agreement; a structural mismatch (another
device, another proxy row, another number of scored rows) reads as
``MISMATCH``, far past any limit.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.reference import ensemble_scores, ridge_residual

MISMATCH = 1e30


def coef_gaps(members: Sequence[tuple], ref_members: Sequence[tuple]) -> Tuple[float, float]:
    """(worst member's max |coef - ref| / max |ref|, median member's
    ||coef - ref||_2 / ||ref||_2); the members must be the same devices
    (identical supports), in order, else both read ``MISMATCH``."""
    if len(members) != len(ref_members) or not members:
        return MISMATCH, MISMATCH
    worst, l2 = 0.0, []
    for (sp, cp, _), (sr, cr, _) in zip(members, ref_members):
        sp, sr = np.asarray(sp, np.float32), np.asarray(sr, np.float32)
        if sp.shape != sr.shape or not np.array_equal(sp, sr):
            return MISMATCH, MISMATCH
        cp, cr = np.asarray(cp, np.float64), np.asarray(cr, np.float64)
        worst = max(worst, float(np.max(np.abs(cp - cr)) / max(np.max(np.abs(cr)), 1e-30)))
        l2.append(float(np.linalg.norm(cp - cr) / max(np.linalg.norm(cr), 1e-30)))
    return worst, float(np.median(l2))


def answer_gap(answers: np.ndarray, want: np.ndarray) -> float:
    """max |answer - reference| over max |reference|; a missing answer
    (NaN) or another number of answers is a mismatch."""
    answers = np.asarray(answers, np.float64)
    want = np.asarray(want, np.float64)
    if answers.shape != want.shape or not np.all(np.isfinite(answers)):
        return MISMATCH
    return float(np.max(np.abs(answers - want)) / max(np.max(np.abs(want)), 1e-30))


def student_ok(out: dict, ref: dict) -> bool:
    """The student stands on the reference's proxy rows."""
    st = out.get("student")
    return st is not None and np.shape(st[0]) == ref["proxy"][0].shape \
        and np.array_equal(st[0], ref["proxy"][0])


def eval_gap(out: dict, ref: dict) -> float:
    """The worst ``answer_gap`` over the round's evaluations: every score
    the round's evaluation produced on every device's test rows, against
    the reference's own ensembles (the ideal, every cell, every random
    draw, the full ensemble), and the distilled student's scores against
    the reference scoring that same student at full precision."""
    got, want = out["evals"], ref["evals"]
    if len(got) != len(want) or not student_ok(out, ref):
        return MISMATCH
    ex = ref["eval"][0]
    want = list(want[:-1]) + [ensemble_scores(ex, [out["student"]], "highest")]
    return max(answer_gap(g, w) for g, w in zip(got, want))


def round_numbers(out: dict, ref: dict, rnd: dict) -> List[Tuple[str, float]]:
    """coef_gap, coef_median_gap (the best cell's members), eval_gap
    (every evaluation's scores), student_residual (the student's
    kernel-ridge residual ||K a + eps a - soft|| / ||soft|| on the
    reference's system: the proxy rows and the reference teacher's
    scores on them) and auc_gap (every AUC the round reports but the
    student's)."""
    worst, median = coef_gaps(out["members"], ref["cell_members"].get(tuple(out["best"]), []))
    nums = [("coef_gap", worst), ("coef_median_gap", median), ("eval_gap", eval_gap(out, ref))]
    resid = MISMATCH
    if student_ok(out, ref):
        xp, g, soft = ref["proxy"]
        resid = ridge_residual(xp, out["student"][1], soft, g, rnd["distill"]["eps"])
    nums.append(("student_residual", resid))
    gaps = [MISMATCH if out["aucs"].get(key) is None else abs(float(out["aucs"][key]) - want)
            for key, want in ref["aucs"].items() if key != "distilled"]
    return nums + [("auc_gap", max(gaps))]


def judge(numbers: Sequence[Tuple[str, float]], limits: Dict[str, float]
          ) -> Tuple[bool, List[dict], List[dict]]:
    """Each number the cell's limits name beside its limit; correct when
    none is past it. The others are readings only: no limit holds for
    them (``PERF.md`` says why, per cell)."""
    rows = [{"name": n, "value": float(v), "limit": float(limits[n])}
            for n, v in numbers if n in limits]
    missing = set(limits) - {n for n, _ in numbers}
    if missing:
        raise KeyError(f"limits name numbers the runner does not read: {sorted(missing)}")
    readings = [{"name": n, "value": float(v)} for n, v in numbers if n not in limits]
    return all(r["value"] <= r["limit"] for r in rows), rows, readings
