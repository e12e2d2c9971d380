"""CPU tests of the reduction from a profiler trace to device metrics.

The synthetic cases pin the arithmetic; ``testdata/small.xplane.pb`` is
a trace recorded on a TPU v5e by ``bench/record_trace.py`` (a 40-device
federation trained, then eight flushes served), so the loader is
checked against what the chip's profiler really writes.
"""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import trace_reduce as tr
from bench.counting import cost_of
from bench.peaks import peaks_for

SMALL = Path(__file__).resolve().parent / "testdata" / "small.xplane.pb"


def ev(chip, name, start, dur):
    return tr.Event(chip, name, float(start), float(dur))


def test_union_merges_overlaps_and_nesting():
    assert tr.union([(5, 7), (0, 2), (1, 3), (6, 6.5)]) == [(0, 3), (5, 7)]
    assert tr.union([]) == []


def test_self_time_subtracts_nested_ops():
    outer = ev(0, "%while.1 = (s32[]) while", 0, 100)
    inner = [ev(0, "%a", 10, 20), ev(0, "%b", 40, 30)]
    after = ev(0, "%c", 120, 5)
    got = {e.name: t for e, t in tr.self_times([after, inner[1], outer, inner[0]])}
    assert got == {"%while.1 = (s32[]) while": 50, "%a": 20, "%b": 30, "%c": 5}


def test_reduce_busy_idle_and_gap_labels():
    events = [ev(None, "bench.traced", 1000, 1000),
              ev(None, "bench.flush", 1100, 300),
              ev(0, "%x", 900, 200),             # starts before the window
              ev(0, "%y", 1500, 100),
              ev(0, "%z", 1550, 20),             # nested in %y
              ev(0, "%late", 1990, 50)]          # ends after the window
    # program spans in perf_counter seconds: bench.traced began at 2.0 s
    spans = [("round.eval", 2.0 + 700e-9, 2.0 + 900e-9)]
    red = tr.reduce(events, chips=1, window=(2.0, 3.0), host_spans=spans)
    assert red.window_s == pytest.approx(1e-6)
    assert red.busy_ns[0] == pytest.approx(100 + 100 + 10)
    assert red.idle_share() == pytest.approx(100 * (1 - 210 / 1000))
    gaps = dict(red.breakdown()["idle_gaps"])
    # gaps: 1100-1500 (mid 1300, in bench.flush), 1600-1990 (mid 1795, in round.eval)
    assert gaps == {"bench.flush": pytest.approx(400e-9), "round.eval": pytest.approx(390e-9)}


def test_reduce_four_chips_means_busy_and_names_chips():
    events = [ev(None, "bench.traced", 0, 100)] + [ev(c, "%op", 0, 10 * (c + 1)) for c in range(4)]
    red = tr.reduce(events, chips=4, window=(0.0, 1e-7))
    assert red.busy_s == pytest.approx((10 + 20 + 30 + 40) / 4 / 1e9)
    labels = {g for g, _ in red.gaps}
    assert labels == {f"chip {c}: (no host span)" for c in range(4)}


def test_reduce_needs_the_window_mark():
    with pytest.raises(ValueError):
        tr.reduce([ev(0, "%op", 0, 1)], chips=1, window=(0.0, 1.0))


def test_short_names_drop_layouts_and_attributes():
    text = ('%_ens_tpu.1 = f32[8,1]{1,0:T(8,128)} custom-call(f32[8,32]{1,0:T(8,128)} %a), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={f32[8,32]{1,0}}')
    assert tr.short_name(text) == "%_ens_tpu.1 = f32[8,1] custom-call(f32[8,32] %a)"


def test_recorded_chip_trace():
    events = tr.load_file(str(SMALL))
    marks = [e for e in events if e.chip is None and e.name == "bench.traced"]
    assert len(marks) == 1
    flushes = [e for e in events if e.name == "bench.flush"]
    assert len(flushes) == 8
    ops = [e for e in events if e.chip == 0]
    assert ops and all(e.dur_ns >= 0 for e in ops)
    gram = [e for e in ops if cost_of("batched_rbf_gram", e.name)]
    ens = [e for e in ops if cost_of("ensemble_score", e.name)]
    assert gram and len(ens) >= 8
    m = marks[0]
    red = tr.reduce(events, chips=1, window=(0.0, m.dur_ns / 1e9))
    assert 0.0 < red.busy_s < red.window_s
    assert 0.0 < red.idle_share() < 100.0
    assert any(label == "bench.flush" for label, _ in red.gaps)
    run = SimpleNamespace(trace=red, peaks=peaks_for("TPU v5 lite"))
    for kernel in ("batched_rbf_gram", "ensemble_score"):
        share = tr.kernel_roofline_share(run, kernel)
        assert 0.0 < share < 100.0
    top = red.breakdown()["device_ops"]
    assert 0 < len(top) <= 10 and all(s >= 0 for _, s in top)
