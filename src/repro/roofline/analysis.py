"""Three-term roofline model from compiled dry-run artifacts.

    compute    = HLO_FLOPs_per_chip / peak_FLOP/s
    memory     = HLO_bytes_per_chip / HBM_bw
    collective = collective_bytes_per_chip / link_bw

``cost_analysis()`` on the compiled (post-SPMD) module reports
per-device flops/bytes. Collective bytes are NOT in cost_analysis: we
parse the compiled HLO text and sum the output-shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute instruction (per-device program, so per-chip bytes).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # bytes/s per chip
    link_bw: float  # ICI bytes/s per link


V5E = HardwareSpec(name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9, link_bw=50e9)

# Peak sheets keyed by ``jax.Device.device_kind``. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
# ICI per chip).
HARDWARE_BY_KIND: Dict[str, HardwareSpec] = {"TPU v5 lite": V5E}


def hardware_for(device_kind: str) -> HardwareSpec:
    """The peak sheet for a device kind; an unlisted kind is an error,
    never a silent default."""
    try:
        return HARDWARE_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak sheet for device kind {device_kind!r}; add one to "
            f"HARDWARE_BY_KIND with its source") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")

# shapes like  bf16[16,512,128]{2,1,0}  or  f32[]  possibly inside tuples
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# instruction line:  %name = <shape-or-tuple> opcode(...)
_INSTR_RE = re.compile(r"=\s*(\([^)]*\)|[^\s]+)\s+([\w-]+)")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Per-collective-op byte totals from a (post-SPMD) HLO module."""
    out = {k: 0 for k in _COLLECTIVES}
    out["start_ops"] = 0
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        shape_str, opcode = m.groups()
        base = opcode
        for suffix in ("-start", "-done"):
            if base.endswith(suffix):
                base = base[: -len(suffix)]
        if base in _COLLECTIVES:
            if opcode.endswith("-done"):
                continue  # avoid double count of async pairs
            out[base] += _shape_bytes(shape_str)
            if opcode.endswith("-start"):
                out["start_ops"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def roofline_report(
    flops_per_chip: float,
    bytes_per_chip: float,
    collective_bytes_per_chip: float,
    hw: HardwareSpec = V5E,
    model_flops: Optional[float] = None,
    chips: int = 1,
) -> Dict[str, float]:
    t_compute = flops_per_chip / hw.peak_flops
    t_memory = bytes_per_chip / hw.hbm_bw
    t_coll = collective_bytes_per_chip / hw.link_bw
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    report = {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "step_lower_bound_s": bound,
        "flops_per_chip": flops_per_chip,
        "bytes_per_chip": bytes_per_chip,
        "collective_bytes_per_chip": collective_bytes_per_chip,
        "chips": chips,
    }
    if model_flops:
        report["model_flops"] = model_flops
        report["useful_flops_ratio"] = model_flops / max(flops_per_chip * chips, 1.0)
        # MFU bound if the step ran exactly at the roofline bound
        report["mfu_at_bound"] = model_flops / (chips * hw.peak_flops * bound) if bound > 0 else 0.0
    return report
