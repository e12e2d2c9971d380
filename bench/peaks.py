"""Published peaks of each chip the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI). A kind
that is not listed is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float       # FLOP/s per chip
    hbm_bytes_per_s: float  # bytes/s per chip
    hbm_bytes: float        # bytes per chip


PEAKS = {"TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9)}


def peaks_for(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}") from None
