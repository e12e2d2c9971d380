"""CPU tests that ``correct`` separates what it must, at a size a test holds.

A sound run of each cell reads correct, with its checks last in the
result line; and the control (the reference one precision step below
the configuration's, in the program's place) fails one of the cell's
numbers while the program passes them all. ``test_bench_faults.py``
breaks the timed path underneath.
"""
import pytest

from bench import calibrate, tiny

CELLS = ["emnist_paper.round", "emnist_paper.serve_k50"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = tiny.result(name, 2**31 + 3)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(name):
    cell = tiny.cell(name)
    rows = calibrate.readings(cell, [17, 18, 19], {17, 18, 19}, 1.0, require_chip=False,
                              on_reading=lambda r: None)
    for row in rows:
        assert all(row["program"][k] <= lim for k, lim in cell.limits.items()), row
        assert any(row["control"][k] > lim for k, lim in cell.limits.items()), row
