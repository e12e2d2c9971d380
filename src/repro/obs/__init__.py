"""repro.obs — the observability spine: tracing, metrics, profiling.

Three layers, one discipline (see docs/ARCHITECTURE.md):

  * ``trace``    span tracer → Chrome trace-event JSON (Perfetto);
                 wall clock for engines/benchmarks, ``sim_clock`` for
                 the fleet so fleet traces are byte-reproducible
  * ``registry`` named counters/gauges/histograms + the schema-
                 versioned envelope the existing metric silos
                 (CommLedger, FleetMetrics, SchedulerStats) export
                 through
  * ``profile``  kernel dispatch hooks: timed compiled calls with
                 achieved-vs-roofline FLOPs/bytes attributes

Everything is gated behind the null tracer: uninstrumented runs pay
one attribute check per site.
"""
from repro.obs.trace import (
    NULL_TRACER,
    NullTracer,
    Tracer,
    current_tracer,
    sim_clock,
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
from repro.obs.profile import kernel_cost, maybe_profile, timed_call

__all__ = [
    "NULL_TRACER",
    "NullTracer",
    "Tracer",
    "current_tracer",
    "sim_clock",
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
    "kernel_cost",
    "maybe_profile",
    "timed_call",
]
