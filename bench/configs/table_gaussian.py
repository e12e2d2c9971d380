"""A federation matched to one row of the paper's Table 1, drawn in bulk.

The benchmark's own copy of the repo's Gaussian-mixture federation
(two classes, a few anisotropic clusters each, a per-device class mix
from a Beta, a per-device feature shift, label noise), drawn with a
handful of array operations over the whole population instead of one
row at a time, so that making the data is a small part of set-up.

Two departures from the repo's generator, both for the benchmark:

* the per-device sample counts span the table's range and sum to its
  total exactly (the repo's rescaling lifts the smallest device to 45
  samples, and its rounding and clipping lose about 2.5% of the rows);
* the counts are one fixed multiset, drawn from ``size_seed`` in the
  configuration, and ``--seed`` only permutes them over the devices.
  Every seed then trains the same set of SVM problem sizes, so the
  run seed changes the data and not the amount of work.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np


def table_sizes(n: int, lo: int, hi: int, total: int, sigma: float,
                size_seed: int) -> np.ndarray:
    """Long-tailed per-device counts from ``lo`` to ``hi`` (both taken)
    summing to ``total``: lognormal draws mapped onto [0, 1], raised to
    the power that puts their mean where the total needs it."""
    if not n * lo < total < n * hi or n < 2:
        raise ValueError(f"{total} samples cannot fill {n} devices of {lo}..{hi}")
    raw = np.random.default_rng(size_seed).lognormal(0.0, sigma, n)
    w = (raw - raw.min()) / (raw.max() - raw.min())
    target = (total / n - lo) / (hi - lo)
    a, b = -20.0, 20.0                  # bisect log(power): mean(w**p) falls as p grows
    for _ in range(200):
        p = np.exp(0.5 * (a + b))
        a, b = (a, 0.5 * (a + b)) if np.mean(w ** p) < target else (0.5 * (a + b), b)
    sizes = np.round(lo + (hi - lo) * w ** np.exp(0.5 * (a + b))).astype(np.int64)
    # hand the rounding remainder out one sample a device, largest devices
    # first for a deficit and smallest first for a surplus, within [lo, hi]
    order = np.argsort(-sizes, kind="stable")
    while (gap := total - int(sizes.sum())) != 0:
        step = 1 if gap > 0 else -1
        room = order[(sizes[order] < hi) if step > 0 else (sizes[order] > lo)]
        if step < 0:
            room = room[::-1]
        take = room[: abs(gap)]
        sizes[take] += step
    return sizes


def sizes(cfg: dict, seed: int) -> np.ndarray:
    """Every device's sample count: the configuration's one multiset,
    permuted by ``seed``."""
    n_dev = int(cfg["n_devices"])
    fixed = table_sizes(n_dev, int(cfg["min_per_device"]), int(cfg["max_per_device"]),
                        int(cfg["total_samples"]), float(cfg["size_sigma"]),
                        int(cfg["size_seed"]))
    return fixed[_rng(seed).permutation(n_dev)]


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, 0x7AB1E]))


_BLOCK = 1 << 16                        # rows drawn at a time


def make(cfg: dict, seed: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Every device's (x float32 (n, dim), y float32 in {-1, +1})."""
    n_dev, dim = int(cfg["n_devices"]), int(cfg["dim"])
    rng = _rng(seed)
    per = sizes(cfg, seed)
    rng.permutation(n_dev)              # the draw ``sizes`` made
    c = int(cfg["clusters"])
    off = float(cfg["separation"]) / np.sqrt(dim)
    means = np.stack([rng.normal(0, 1, (c, dim)) + off,
                      rng.normal(0, 1, (c, dim)) - off]).astype(np.float32)  # (2, c, dim)
    scales = (0.6 + 0.8 * rng.random((2, c))).astype(np.float32)
    a = float(cfg["class_mix_beta"])
    pos_frac = np.clip(rng.beta(a, a, n_dev), 0.05, 0.95)
    shift = (float(cfg["shift_scale"]) * rng.normal(0, 1, (n_dev, dim))).astype(np.float32)

    dev = np.repeat(np.arange(n_dev), per)
    rows = len(dev)
    y = np.where(rng.random(rows) < pos_frac[dev], 1.0, -1.0)
    cls = (y < 0).astype(np.intp)
    k = rng.integers(c, size=rows)
    x = rng.standard_normal((rows, dim), dtype=np.float32)
    for lo in range(0, rows, _BLOCK):   # in blocks: no (rows, dim) temporaries
        b = slice(lo, lo + _BLOCK)
        x[b] *= scales[cls[b], k[b], None]
        x[b] += means[cls[b], k[b]]
        x[b] += shift[dev[b]]
    y = np.where(rng.random(rows) < float(cfg["label_noise"]), -y, y)
    cuts = np.cumsum(per)[:-1]
    return list(zip(np.split(x, cuts), np.split(y.astype(np.float32), cuts)))
