"""Seconds of evaluation per round: the program's ``round.ideal``,
``round.select`` and ``round.eval`` spans (ensembles built and scored
on the evaluated devices' test splits), over the rounds in the window."""

NAMES = ("round.ideal", "round.select", "round.eval")


def read(run):
    spans = [e - s for name, s, e in run.spans if name in NAMES]
    return sum(spans) / run.units if spans else None
