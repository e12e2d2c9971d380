"""Cells cut to a size a CPU test can hold, for the benchmark's own tests.

Each keeps its configuration's shapes (feature width, strategies, codec,
solver, traffic) and cuts only scale: devices, samples, ks, the proxy,
the serve ensemble and rate. The limits are the cell's own.
"""
from __future__ import annotations

import contextlib
from types import SimpleNamespace

from bench import run


def cell(name: str) -> SimpleNamespace:
    c = run.find_cell(name)
    cfg = c.config
    cfg.update(n_devices=40, total_samples=4000, max_per_device=200)
    cfg["round"]["distill"]["proxy_size"] = 300
    cfg["round"]["ks"] = [2, 5]
    if c.traffic["runner"] == "serve":
        c.traffic.update(k=5, rate_per_s=3000.0)     # several rows a flush
    return c


def result(name: str, seed: int, seconds: float = 1.0) -> dict:
    """One whole run of the cut cell, without the look for a chip."""
    return run.run_cell(cell(name), seed, seconds, False, require_chip=False)


@contextlib.contextmanager
def patched(obj, attr: str, wrap):
    """Replace ``obj.attr`` by ``wrap(original)`` for the block."""
    orig = getattr(obj, attr)
    setattr(obj, attr, wrap(orig))
    try:
        yield
    finally:
        setattr(obj, attr, orig)
