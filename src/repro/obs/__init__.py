"""repro.obs — the observability spine: tracing, metrics, profiling.

Three layers, one discipline (see docs/ARCHITECTURE.md):

  * ``trace``    span tracer → Chrome trace-event JSON (Perfetto);
                 wall clock for engines/benchmarks, ``sim_clock`` for
                 the fleet so fleet traces are byte-reproducible;
                 ``span`` is the entry point instrumented code calls,
                 and writes into any recording ``jax.profiler`` trace
  * ``registry`` named counters/gauges/histograms + the schema-
                 versioned envelope the existing metric silos
                 (CommLedger, FleetMetrics, SchedulerStats) export
                 through
  * ``profile``  benchmark timing (``timed_call``) and the peak sheet
                 of a TPU (``peak_sheet``); kernel time comes from the
                 device trace, not from a dispatch hook

With no tracer installed and no profiler recording, a span is one
reusable no-op context manager.
"""
from repro.obs.trace import (
    NULL_TRACER,
    NullTracer,
    Tracer,
    current_tracer,
    sim_clock,
    span,
    stopwatch,
    traced,
    use_tracer,
    wall_clock,
)
from repro.obs.registry import (
    MetricsRegistry,
    comm_section,
    default_registry,
    envelope,
    fleet_section,
    scheduler_section,
)
from repro.obs.profile import timed_call

__all__ = [
    "NULL_TRACER",
    "NullTracer",
    "Tracer",
    "current_tracer",
    "sim_clock",
    "span",
    "stopwatch",
    "traced",
    "use_tracer",
    "wall_clock",
    "MetricsRegistry",
    "comm_section",
    "default_registry",
    "envelope",
    "fleet_section",
    "scheduler_section",
    "timed_call",
]
