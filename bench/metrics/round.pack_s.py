"""Seconds a round spends packing ensembles for scoring: the program's
``ensemble.pack`` spans (host fill of the padded supports and their
upload's dispatch), over the rounds in the window. An ensemble packs
on its first scored block, so these spans sit inside ``eval.score``."""


def read(run):
    spans = [e - s for name, s, e in run.spans if name == "ensemble.pack"]
    return sum(spans) / run.units if spans else None
