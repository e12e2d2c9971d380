"""The 99th percentile of request latency over every request of the
window (refused ones count as missing), as the serve runner takes it.
Kept beside ``serve_p50_ms`` and not as an end-to-end metric: host stalls
of about 120 ms, some runs none and some several, swing it between
about 4 ms and 190 ms from run to run (PERF.md)."""


def read(run):
    return run.metrics.get("serve_p99_ms")
