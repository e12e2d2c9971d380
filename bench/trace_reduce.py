"""From a JAX profiler trace to device busy time, kernel time and idle gaps.

``load_dir`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps two kinds of event: the operations each TPU ran (the ``XLA Ops``
line of each ``/device:TPU:<n>`` plane) and the benchmark's own host
annotations (``bench.*``, from ``jax.profiler.TraceAnnotation``). Both
are on the profiler's clock. ``reduce`` cuts them to the traced window
(the ``bench.traced`` annotation) and works out, per chip, the union of
operation intervals (busy time), the gaps between them, and the time of
each kernel; it names each idle gap by the host span open at its middle:
the program's spans (``round.*``, ``engine.*``, ``distill.round``, moved
onto the profiler's clock through ``bench.traced``) or the benchmark's.
Operation events nest (a ``while`` op holds its body's ops), so busy
time is a union and the breakdown ranks operations by self time.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."


@dataclasses.dataclass(frozen=True, slots=True)
class Event:
    chip: Optional[int]     # None for a host event
    name: str               # an op's HLO text, or the annotation's name
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_file(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    events: List[Event] = []
    names: Dict[str, str] = {}          # one string per distinct op text
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        host = plane.name.startswith("/host:")
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                chip = int(m.group(1))
                for e in line.events:
                    name = e.name
                    events.append(Event(chip, names.setdefault(name, name),
                                        float(e.start_ns), float(e.duration_ns)))
            elif host:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        events.append(Event(None, e.name, float(e.start_ns),
                                            float(e.duration_ns)))
    return events


def load_dir(directory: str) -> List[Event]:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return load_file(max(paths, key=os.path.getmtime))


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclasses.dataclass
class Reduction:
    window_ns: Tuple[float, float]
    busy_ns: Dict[int, float]                     # chip -> busy ns in the window
    ops: List[Event]                              # device ops inside the window
    gaps: List[Tuple[str, float]]                 # (host span, seconds), longest first
    chips: int

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds, mean over the chips used."""
        return sum(self.busy_ns.get(c, 0.0) for c in range(self.chips)) / self.chips / 1e9

    def idle_share(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def op_seconds(self) -> Dict[str, float]:
        """Device self seconds per operation (shortened name), summed
        over chips: an op's time less that of the ops nested in it."""
        tot: Dict[str, float] = defaultdict(float)
        for c in range(self.chips):
            for e, self_ns in self_times([e for e in self.ops if e.chip == c]):
                tot[short_name(e.name)] += self_ns / 1e9
        return dict(tot)

    def breakdown(self) -> dict:
        top = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:10]
        gaps: Dict[str, float] = defaultdict(float)
        for label, secs in self.gaps:
            gaps[label] += secs
        idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in idle]}


_LAYOUT = re.compile(r"\{[^{}]*\}")
_CUT = re.compile(r", (custom_call_target|kind|calls|dimensions|padding|slice|to_apply)=")


def short_name(name: str) -> str:
    """An HLO op's text without layouts and attributes, at most 160 chars."""
    for _ in range(2):
        name = _LAYOUT.sub("", name)
    return _CUT.split(name, maxsplit=1)[0][:160]


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """(event, duration less its directly nested events) for one chip."""
    out: List[Tuple[Event, float]] = []
    stack: List[list] = []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and e.start_ns >= stack[-1][0].end_ns:
            top, child = stack.pop()
            out.append((top, top.dur_ns - child))
        if stack:
            stack[-1][1] += e.dur_ns
        stack.append([e, 0.0])
    out.extend((top, top.dur_ns - child) for top, child in reversed(stack))
    return out


class _Spans:
    """Innermost host span open at a time. Host spans come from ``with``
    blocks on one thread, so they nest: each span's parent is the
    nearest earlier span still open when it starts."""

    def __init__(self, spans: Sequence[Tuple[str, float, float]]):
        spans = sorted(spans, key=lambda t: (t[1], -t[2]))
        self.names = [n for n, _, _ in spans]
        self.starts = [s for _, s, _ in spans]
        self.ends = [e for _, _, e in spans]
        self.parent: List[int] = []
        stack: List[int] = []
        for i, s in enumerate(self.starts):
            while stack and self.ends[stack[-1]] <= s:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.ends[i] <= t:
            i = self.parent[i]
        return self.names[i] if i >= 0 else "(no host span)"


def reduce(events: Sequence[Event], chips: int, window: Tuple[float, float],
           host_spans: Sequence[Tuple[str, float, float]] = ()) -> Reduction:
    """``window`` is the (start, end) perf_counter seconds of the
    ``bench.traced`` annotation; ``host_spans`` are the program's spans
    in perf_counter seconds. With several chips each gap's label names
    its chip."""
    marks = [e for e in events if e.chip is None and e.name == "bench.traced"]
    if not marks:
        raise ValueError("the trace holds no bench.traced annotation")
    w = marks[-1]
    lo, hi = w.start_ns, w.end_ns
    offset = w.start_ns - window[0] * 1e9
    spans = [(n, s * 1e9 + offset, e * 1e9 + offset) for n, s, e in host_spans]
    spans = _Spans(spans + [(e.name, e.start_ns, e.end_ns) for e in events
                            if e.chip is None and e.name != "bench.traced"])
    ops = [e for e in events if e.chip is not None and e.chip < chips
           and e.end_ns > lo and e.start_ns < hi]
    busy: Dict[int, float] = {}
    gaps: List[Tuple[str, float]] = []
    for c in range(chips):
        iv = union([(max(e.start_ns, lo), min(e.end_ns, hi)) for e in ops if e.chip == c])
        busy[c] = sum(e - s for s, e in iv)
        edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                label = spans.at(0.5 * (s + e))
                gaps.append((f"chip {c}: {label}" if chips > 1 else label, (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Reduction((lo, hi), busy, ops, gaps, chips)


def kernel_roofline_share(run, kernel: str) -> Optional[float]:
    """Percent of the roofline reached by ``kernel``'s calls in the
    traced window, or None when the trace holds none of them."""
    from bench.counting import cost_of, roofline_seconds

    if run.trace is None or run.peaks is None:
        return None
    least, took = 0.0, 0.0
    for e in run.trace.ops:
        cost = cost_of(kernel, e.name)
        if cost is None:
            continue
        least += roofline_seconds(*cost, run.peaks.bf16_flops, run.peaks.hbm_bytes_per_s)
        took += e.dur_ns / 1e9
    return 100.0 * least / took if took > 0 else None
