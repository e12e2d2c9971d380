"""Host time of a flush: the program's ``serve.flush`` spans less the
``serve.fetch`` spans inside them (waiting for the device and copying
scores back), over the number of flushes in the window."""


def read(run):
    flush = [e - s for name, s, e in run.spans if name == "serve.flush"]
    fetch = [e - s for name, s, e in run.spans if name == "serve.fetch"]
    return 1e6 * (sum(flush) - sum(fetch)) / len(flush) if flush else None
