"""Kernel profiling hooks — every dispatched kernel call becomes a span.

``kernels/ops.py`` routes every public kernel dispatch through
``maybe_profile(name, fn, *args)``. With no tracer installed this is a
single attribute check and a tail call — the dispatch hot path pays
nothing. With a tracer active, each call is timed to completion
(``jax.block_until_ready`` on the result, so async dispatch cannot
hide the work) and emitted as a ``cat="kernel"`` complete event whose
attributes carry the achieved-vs-roofline accounting:

  * ``flops`` / ``bytes_accessed`` — XLA ``cost_analysis()`` of the
    compiled module (``fn.lower(*args).compile()``), cached per
    (kernel, shape/dtype signature) so the lowering cost is paid once
    per shape bucket, the way the engines already amortize compiles;
  * ``achieved_gflops`` — flops / measured seconds;
  * ``roofline_bound_us`` / ``roofline_frac`` / ``dominant`` — the
    three-term model from ``roofline.analysis.roofline_report`` (no
    collective term for single-kernel calls): how close this call ran
    to the hardware bound, and which term bounds it. The peaks come
    from ``roofline.analysis.hardware_for`` keyed by the device's
    ``device_kind``; an accelerator kind missing from that table
    raises. Off the TPU, spans carry timing and XLA cost only — no
    roofline share, since no peak sheet describes that backend.

Non-jitted paths (the Pallas interpreter) have no ``lower``; their
spans carry timing only. ``timed_call`` is the shared benchmark timing
helper (warmup + repeats + block_until_ready) built on the same span
emission, so benchmark CSV numbers and trace spans agree by
construction (``benchmarks/common.py`` re-exports it).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Tuple

import jax

from repro.obs.trace import current_tracer
from repro.roofline.analysis import HardwareSpec, hardware_for, roofline_report
from repro.utils.logging import get_logger, kv

log = get_logger("obs")

# (kernel name, arg signature) -> (flops, bytes) | None when unknowable
_COST_CACHE: Dict[tuple, Optional[Tuple[float, float]]] = {}


def _signature(args: tuple) -> tuple:
    sig = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is not None:
            sig.append((tuple(shape), str(getattr(a, "dtype", "?"))))
        else:
            sig.append(a)
    return tuple(sig)


def kernel_cost(name: str, fn: Callable, args: tuple) -> Optional[Tuple[float, float]]:
    """(flops, bytes accessed) of the compiled module for these shapes,
    from XLA cost_analysis; cached per signature. None when the path
    cannot be lowered (interpret mode) or analysis fails."""
    key = (name, _signature(args))
    if key in _COST_CACHE:
        return _COST_CACHE[key]
    cost: Optional[Tuple[float, float]] = None
    lower = getattr(fn, "lower", None)
    if lower is not None:
        try:
            ca = lower(*args).compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            cost = (float(ca.get("flops", 0.0)),
                    float(ca.get("bytes accessed", 0.0)))
        except Exception as e:  # cost analysis is best-effort telemetry
            log.warning("%s", kv(event="kernel_cost_failed", kernel=name,
                                 error=str(e)))
    _COST_CACHE[key] = cost
    return cost


def peak_sheet(device) -> Optional[HardwareSpec]:
    """The peaks spans on ``device`` are priced against: None off the
    TPU; for a TPU, its ``device_kind``'s sheet (unknown kinds raise)."""
    if device.platform != "tpu":
        return None
    return hardware_for(device.device_kind)


def maybe_profile(name: str, fn: Callable, *args):
    """The ops.py dispatch hook: call through, and when a tracer is
    installed, time the call to completion and attach the roofline
    accounting to a kernel span."""
    tracer = current_tracer()
    if not tracer.enabled:
        return fn(*args)
    cost = kernel_cost(name, fn, args)
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    attrs = {"backend": jax.default_backend(), "dur_s": dt}
    if cost is not None:
        flops, nbytes = cost
        attrs.update(
            flops=flops,
            bytes_accessed=nbytes,
            achieved_gflops=flops / max(dt, 1e-12) / 1e9,
        )
        hw = peak_sheet(jax.devices()[0])
        if hw is not None:
            rl = roofline_report(flops, nbytes, 0.0, hw=hw)
            bound = rl["step_lower_bound_s"]
            attrs.update(
                roofline_bound_us=bound * 1e6,
                roofline_frac=bound / max(dt, 1e-12),
                dominant=rl["dominant"],
            )
    ts = tracer.clock() if hasattr(tracer, "clock") else 0.0
    tracer.complete(f"kernel.{name}", ts - dt * 1e6, dt * 1e6,
                    cat="kernel", **attrs)
    return out


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
