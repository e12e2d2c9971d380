"""Benchmark timing and the peak sheet a device is priced against.

``timed_call`` is the shared benchmark timing helper (warmup + repeats
+ block_until_ready), each timed repeat emitted as a ``bench.*`` span,
so benchmark CSV numbers and trace spans agree by construction
(``benchmarks/common.py`` re-exports it). ``peak_sheet`` names the
peaks of a TPU by its ``device_kind`` (``chip_smoke.py`` reports it).

Kernel dispatch in ``kernels/ops.py`` carries no hook: a kernel's time
is read from the device trace of a ``jax.profiler`` session, where the
program's spans (``repro.obs.span``) also land on the same clock.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax

from repro.obs.trace import current_tracer
from repro.roofline.analysis import HardwareSpec, hardware_for


def peak_sheet(device) -> Optional[HardwareSpec]:
    """The peaks ``device`` is priced against: None off the TPU; for a
    TPU, its ``device_kind``'s sheet (unknown kinds raise)."""
    if device.platform != "tpu":
        return None
    return hardware_for(device.device_kind)


def timed_call(name: str, fn: Callable, repeats: int = 5, warmup: int = 2) -> float:
    """Warmup + repeat timing of ``fn()`` with completion blocking;
    returns mean microseconds per call. Each timed repeat is emitted as
    a ``cat="bench"`` span on the current tracer, so a traced benchmark
    run's spans are the exact calls its CSV numbers average over."""
    tracer = current_tracer()
    for _ in range(warmup):
        jax.block_until_ready(fn())
    total = 0.0
    for i in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        dt = time.perf_counter() - t0
        total += dt
        if tracer.enabled:
            ts = tracer.clock() if hasattr(tracer, "clock") else 0.0
            tracer.complete(f"bench.{name}", ts - dt * 1e6, dt * 1e6,
                            cat="bench", repeat=i)
    return total / repeats * 1e6
