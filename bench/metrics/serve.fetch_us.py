"""Time a flush spends in ``serve.fetch``: waiting for the device's
scores and copying them back, summed over the window's spans, over the
number of ``serve.flush`` spans."""


def read(run):
    n = sum(1 for name, _, _ in run.spans if name == "serve.flush")
    fetch = [e - s for name, s, e in run.spans if name == "serve.fetch"]
    return 1e6 * sum(fetch) / n if n else None
