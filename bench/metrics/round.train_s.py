"""Seconds of local training per round: the program's ``round.train``
spans in the window, over the rounds it held."""


def read(run):
    spans = [e - s for name, s, e in run.spans if name == "round.train"]
    return sum(spans) / run.units if spans else None
