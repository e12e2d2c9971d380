"""Per-layer readers that more than one metric shares.

A quantity that moves a different end-to-end metric in different cells
has one name a cell kind (``mfu.round``, ``mfu.serve``); each name's
file under ``bench/metrics/`` re-exports the one reader here, and the
metric's ``workloads`` in ``BENCHMARK.json`` chooses its cells.
"""
from bench.trace_reduce import kernel_roofline_share


def idle_share(run):
    """Share of the traced window in which no operation ran on the
    device, mean over the chips used."""
    return run.trace.idle_share() if run.trace else None


def mfu(run):
    """The traced work's operations (``bench/counting.py``) over the
    traced window x chips x the chip's bf16 peak. The SVM path runs
    float32 at HIGHEST, several matrix passes a product, so this share
    stays far under what bf16 would reach."""
    if not run.peaks or not run.ops or run.trace is None:
        return None
    return 100.0 * run.ops / (run.trace.window_s * run.chips * run.peaks.bf16_flops)


def ensemble_score_roofline(run):
    """``ensemble_score``'s share of its roofline: the least time its
    calls could take at the chip's peaks over their time on the device."""
    return kernel_roofline_share(run, "ensemble_score")
