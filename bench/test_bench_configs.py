"""CPU tests of the benchmark's own federation generator."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.configs import table_gaussian

ROOT = Path(__file__).resolve().parents[1]
EMNIST = json.loads((ROOT / "bench" / "configs" / "emnist_paper.json").read_text())
# the same federation with fewer devices, for the tests that draw rows
SMALL = dict(EMNIST, n_devices=40, total_samples=4000, max_per_device=200)


def test_emnist_paper_matches_table_1():
    """arXiv:1902.11175 Table 1: 3,462 devices, 406,048 samples, 10..460."""
    sizes = table_gaussian.sizes(EMNIST, 2**32 + 5)
    assert len(sizes) == 3462
    assert sizes.sum() == 406_048
    assert sizes.min() == 10 and sizes.max() == 460


def test_emnist_paper_seeds_permute_one_set_of_sizes():
    a = table_gaussian.sizes(EMNIST, 2**32 + 5)
    b = table_gaussian.sizes(EMNIST, 3)
    assert sorted(a) == sorted(b) and not np.array_equal(a, b)


def test_rows_have_the_configured_width_and_are_seeded():
    devices = table_gaussian.make(SMALL, 2**32 + 5)
    assert [len(y) for _, y in devices] == list(table_gaussian.sizes(SMALL, 2**32 + 5))
    x, y = devices[0]
    assert x.dtype == np.float32 and x.shape[1] == EMNIST["dim"] == 784
    assert set(np.unique(y)) <= {-1.0, 1.0}
    again = table_gaussian.make(SMALL, 2**32 + 5)
    other = table_gaussian.make(SMALL, 2**32 + 6)
    assert all(np.array_equal(p[0], q[0]) and np.array_equal(p[1], q[1])
               for p, q in zip(devices, again))
    assert not np.array_equal(devices[0][0][:5], other[0][0][:5])


def test_table_sizes_hit_the_total_exactly():
    for total in (4000, 4100, 5500):
        s = table_gaussian.table_sizes(40, 10, 200, total, 0.9, 1)
        assert s.sum() == total and s.min() >= 10 and s.max() <= 200
    with pytest.raises(ValueError):
        table_gaussian.table_sizes(4, 10, 20, 100, 0.9, 1)
