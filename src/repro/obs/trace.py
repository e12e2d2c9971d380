"""Span-based tracing to Chrome trace-event JSON (Perfetto-viewable).

One ``Tracer`` collects events for one run and exports the standard
Chrome trace-event format (``{"traceEvents": [...]}`` — open the file
at https://ui.perfetto.dev or chrome://tracing). Three event shapes
cover the whole stack:

  * nested spans — ``with tracer.span("round.train", cat="engine")``
    emits a begin/end ("B"/"E") pair; spans nest naturally with the
    ``with`` stack, which is how the streamed engine's chunk → bucket
    group hierarchy renders;
  * complete events — ``tracer.complete(name, ts_us, dur_us)`` for
    spans whose duration is known up front (the fleet's simulated batch
    services);
  * instants — ``tracer.instant(name)`` for point events (every
    ``CommLedger`` record mirrors here).

Two clock sources, one per determinism regime (docs/TESTING.md):

  * ``wall_clock()`` (the default) — microseconds since tracer
    construction via ``time.perf_counter``; engines and benchmarks use
    it because their spans measure real hardware time;
  * ``sim_clock(SimClock)`` — the fleet's simulated milliseconds. A
    fleet trace contains no wall-clock reads anywhere, so the whole
    trace file is byte-reproducible from the traffic seed (the baseline
    ``benchmarks/fleet_trace_baseline.json`` is diffed in CI exactly
    like ``serve_load_bench.json``). Fleet events pass explicit
    timestamps either way, so any tracer they land in stays
    deterministic.

Instrumented code opens spans through the module-level ``span(name)``:
it opens a span on the installed tracer and, while a ``jax.profiler``
session is recording, a ``jax.profiler.TraceAnnotation`` of the same
name, so the span also lands on the profiler's host plane, on the same
clock as the device ops. With no tracer installed, ``current_tracer()``
returns ``NULL_TRACER`` whose ``enabled`` is False and whose ``span``
hands back one reusable no-op context manager, and with no profiler
recording no annotation is built: a span then costs a function call and
one check of the profiler (the overhead bar in tests/test_obs.py).
Instrumented code calls ``span`` unconditionally — a duck-typed tracer
whose ``enabled`` is False still receives every span. Install a real
tracer for a region with::

    tracer = Tracer()
    with use_tracer(tracer):
        run_population(cfg)
    tracer.export("out.json")

Attributes are typed: span/instant ``**attrs`` accept str, bool, int,
and float (numpy scalars are coerced); anything else raises at record
time rather than at export time, so a bad attribute fails next to the
instrumentation that produced it.
"""
from __future__ import annotations

import contextlib
import functools
import json
import time
from typing import Callable, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

from repro.utils.logging import get_logger, kv

log = get_logger("obs")

SCHEMA = "repro.obs/v1"


def wall_clock() -> Callable[[], float]:
    """Microseconds of wall time since this clock was created."""
    t0 = time.perf_counter()
    return lambda: (time.perf_counter() - t0) * 1e6


def stopwatch() -> Callable[[], float]:
    """Elapsed wall-clock SECONDS since creation.

    The blessed duration primitive for engine and launch code: all
    wall-clock reads live inside ``repro.obs`` (``repro.lint``'s
    ``wall-clock-ban`` rule enforces it), so determinism-sensitive
    paths — the fleet, anything traced against ``sim_clock`` — can be
    audited for clock reads by module, not by call site.

        elapsed = stopwatch()
        ...work...
        seconds = elapsed()
    """
    t0 = time.perf_counter()
    return lambda: time.perf_counter() - t0


# Nanoseconds on the monotonic clock, for counters that sum waits
# (``SchedulerStats.queue_wait_ns``); read through this module so code
# outside ``repro.obs`` holds no clock read of its own.
now_ns = time.perf_counter_ns


def sim_clock(clock) -> Callable[[], float]:
    """Microseconds of *simulated* time read off a ``fleet.SimClock``
    (or anything with ``now_ms``) — no wall-clock reads, so traces
    built on it are byte-reproducible from the run's seed."""
    return lambda: clock.now_ms * 1000.0


def _coerce_attr(name: str, key: str, val):
    if isinstance(val, (str, bool)):
        return val
    if isinstance(val, (int, float)):
        return val
    # numpy scalars (np.int64 counts, np.float64 times) quack like this
    item = getattr(val, "item", None)
    if item is not None:
        val = item()
        if isinstance(val, (str, bool, int, float)):
            return val
    raise TypeError(
        f"span {name!r} attribute {key}={val!r} is not a typed attribute "
        "(str | bool | int | float)"
    )


class _NullSpan:
    """The reusable no-op context manager the null tracer hands out."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Does nothing, as fast as possible. ``enabled`` gates events that
    cost something to build; spans never check it."""

    enabled = False

    def span(self, name: str, cat: str = "app", **attrs):
        return _NULL_SPAN

    def instant(self, name: str, cat: str = "app", ts_us: Optional[float] = None, **attrs):
        return None

    def complete(self, name: str, ts_us: float, dur_us: float,
                 cat: str = "app", **attrs):
        return None

    def export(self, path: str) -> bool:
        return False


NULL_TRACER = NullTracer()


class Tracer:
    """Collects Chrome trace events for one run.

    ``clock`` is a zero-arg callable returning the current timestamp in
    microseconds (``wall_clock()`` by default, ``sim_clock(...)`` for
    simulated time). ``pid`` namespaces the events — merged traces
    (``merge``) keep each source on its own process track, which is how
    ``fed_run --trace`` shows wall-clock engine spans and simulated-ms
    fleet spans in one file without conflating the two time bases.
    """

    enabled = True

    def __init__(self, clock: Optional[Callable[[], float]] = None,
                 pid: int = 1, tid: int = 1,
                 process_name: Optional[str] = None):
        self.clock = clock if clock is not None else wall_clock()
        self.pid = int(pid)
        self.tid = int(tid)
        self.events: List[Dict] = []
        self._depth = 0
        if process_name is not None:
            self.events.append({
                "ph": "M", "name": "process_name", "pid": self.pid,
                "tid": self.tid, "ts": 0.0, "args": {"name": process_name},
            })

    # -- emission -------------------------------------------------------
    def _event(self, ph: str, name: str, cat: str, ts: float, attrs: dict,
               **extra) -> None:
        args = {k: _coerce_attr(name, k, v) for k, v in attrs.items()}
        ev = {"ph": ph, "name": name, "cat": cat, "ts": float(ts),
              "pid": self.pid, "tid": self.tid, "args": args}
        ev.update(extra)
        self.events.append(ev)

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "app", **attrs) -> Iterator[None]:
        """Begin/end pair; nests with the ``with`` stack."""
        self._event("B", name, cat, self.clock(), attrs)
        self._depth += 1
        try:
            yield
        finally:
            self._depth -= 1
            self._event("E", name, cat, self.clock(), {})

    def instant(self, name: str, cat: str = "app",
                ts_us: Optional[float] = None, **attrs) -> None:
        """Point event (scope "t" = thread-local in the viewer)."""
        ts = self.clock() if ts_us is None else ts_us
        self._event("i", name, cat, ts, attrs, s="t")

    def complete(self, name: str, ts_us: float, dur_us: float,
                 cat: str = "app", **attrs) -> None:
        """One "X" event whose duration is known up front — the fleet's
        simulated batch services land here with explicit timestamps."""
        self._event("X", name, cat, ts_us, attrs, dur=float(dur_us))

    def merge(self, other: "Tracer") -> None:
        """Append another tracer's events (they keep their own pid —
        give sub-tracers a distinct one)."""
        self.events.extend(other.events)

    # -- export ---------------------------------------------------------
    def to_json(self) -> str:
        """Deterministic serialization: fixed top-level shape, sorted
        keys — two tracers holding equal events serialize identically,
        which is what the fleet-trace baseline diff rides on."""
        payload = {
            "traceEvents": self.events,
            "displayTimeUnit": "ms",
            "otherData": {"schema": SCHEMA},
        }
        return json.dumps(payload, sort_keys=True, separators=(",", ":"))

    def export(self, path: str) -> bool:
        """Write the trace JSON; failures are logged (structured, via
        ``utils.logging``) rather than raised — a full disk must not
        kill the run whose trace it was recording."""
        try:
            with open(path, "w") as f:
                f.write(self.to_json())
                f.write("\n")
            return True
        except OSError as e:
            log.warning("%s", kv(event="trace_write_failed", path=path,
                                 error=str(e)))
            return False


# ----------------------------------------------------------------------
# the installed tracer: one module-level slot, null by default
# ----------------------------------------------------------------------

_CURRENT: object = NULL_TRACER


def current_tracer():
    """The installed tracer (``NULL_TRACER`` unless ``use_tracer`` is
    active). Spans go through ``span``; ``.enabled`` gates only events
    that cost something to build (comm instants, fleet completes)."""
    return _CURRENT


@contextlib.contextmanager
def use_tracer(tracer) -> Iterator[object]:
    """Install ``tracer`` as the current tracer for the region."""
    global _CURRENT
    prev = _CURRENT
    _CURRENT = tracer
    try:
        yield tracer
    finally:
        _CURRENT = prev


class _Annotated:
    """A tracer span inside a profiler annotation of the same name."""

    __slots__ = ("_span", "_mark")

    def __init__(self, span, mark):
        self._span, self._mark = span, mark

    def __enter__(self):
        self._mark.__enter__()
        self._span.__enter__()
        return None

    def __exit__(self, *exc):
        try:
            self._span.__exit__(*exc)
        finally:
            self._mark.__exit__(*exc)
        return False


def span(name: str, cat: str = "app", **attrs):
    """The one span entry point for instrumented code: a span on the
    installed tracer, inside a ``jax.profiler.TraceAnnotation`` while a
    profiler session is recording (``TraceMe.is_enabled``, checked once
    a span). Hot-path spans pass no attributes, or ints already at hand."""
    s = _CURRENT.span(name, cat=cat, **attrs)
    if TraceAnnotation.is_enabled():
        return _Annotated(s, TraceAnnotation(name))
    return s


def traced(name: Optional[str] = None, cat: str = "app") -> Callable:
    """Decorator form: ``span`` the whole call."""
    def deco(fn):
        span_name = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(span_name, cat=cat):
                return fn(*args, **kwargs)
        return wrapper
    return deco
