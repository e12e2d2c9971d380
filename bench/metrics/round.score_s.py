"""Seconds a round spends scoring evaluation blocks: the program's
``eval.score`` spans (block concat, padding, the scoring call through
the host copy of its scores, and an ensemble's packing on its first
block), over the rounds in the window."""


def read(run):
    spans = [e - s for name, s, e in run.spans if name == "eval.score"]
    return sum(spans) / run.units if spans else None
