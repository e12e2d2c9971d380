"""Seconds a round spends folding scores into per-device AUCs: the
program's ``eval.fold`` (accumulator updates) and ``eval.auc`` (rank
statistics) spans, over the rounds in the window."""

NAMES = ("eval.fold", "eval.auc")


def read(run):
    spans = [e - s for name, s, e in run.spans if name in NAMES]
    return sum(spans) / run.units if spans else None
