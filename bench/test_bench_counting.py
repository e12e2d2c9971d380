"""CPU tests of the benchmark's counting and of its refusals.

Operations and bytes from shapes, the round's operation count on a tiny
federation, percentiles over all requests with missing ones, the shape
of BENCHMARK.json and the files it names, and that ``bench/run.py``
refuses to run, and prints no result line, without a TPU.
"""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import counting
from bench.runners import serve as serve_runner
from bench.reference import split
from bench.round_ref import reference_round

ROOT = Path(__file__).resolve().parents[1]

ENS_OP = ('%_ens_tpu.1 = f32[8192,1]{1,0:T(8,128)S(1)} custom-call(f32[8192,32]{1,0:T(8,128)S(1)} '
          '%copy.5, f32[2819,256,32]{2,1,0:T(8,128)} %pad.0, f32[2819,1,256]{2,1,0:T(1,128)S(1)} '
          '%reshape.1, f32[2819]{0:T(1024)S(1)} %copy-done.1), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={f32[8192,32]{1,0}}')
GRAM_OP = ('%_bgram_tpu.1 = f32[256,32,64]{2,1,0:T(8,128)S(1)} custom-call(f32[256,32,16]'
           '{2,1,0:T(8,128)S(1)} %copy.3, f32[256,64,16]{2,1,0:T(8,128)S(1)} %copy.4, '
           'f32[256]{0:T(256)S(1)} %copy-done.2), custom_call_target="tpu_custom_call"')


def test_kernel_costs_from_shapes():
    assert counting.batched_rbf_gram(2, 3, 4, 5) == (2 * 2 * 3 * 4 * 5,
                                                    4 * (2 * 3 * 5 + 2 * 4 * 5 + 2 + 2 * 3 * 4))
    assert counting.ensemble_score(8, 3, 16, 4) == (2 * 8 * 3 * 16 * 5,
                                                   4 * (8 * 4 + 3 * 16 * 4 + 3 * 16 + 3 + 8))
    assert counting.roofline_seconds(197e12, 1.0, 197e12, 819e9) == 1.0
    assert counting.roofline_seconds(1.0, 819e9, 197e12, 819e9) == 1.0


def test_kernel_calls_read_from_trace_text():
    assert counting.call_shapes(ENS_OP) == (
        "_ens_tpu.1", [(8192, 32), (2819, 256, 32), (2819, 1, 256), (2819,)])
    assert counting.cost_of("ensemble_score", ENS_OP) == counting.ensemble_score(8192, 2819, 256, 32)
    assert counting.cost_of("batched_rbf_gram", GRAM_OP) == counting.batched_rbf_gram(256, 32, 64, 16)
    assert counting.cost_of("batched_rbf_gram", ENS_OP) is None
    assert counting.cost_of("ensemble_score", "%while.2 = (s32[], f32[10000]) while(...)") is None


def test_round_ops_by_hand():
    # one device: n=4 train rows, 3 scored rows, d=2, one epoch; one
    # evaluation of 10 rows against 5 supports; one extra fit of 2 rows
    fit = 2 * 4 * 4 * 2 + 1 * 2 * 4 * 4
    own = 2 * 3 * 4 * 3
    ev = 2 * 10 * 5 * 3
    extra = 2 * 2 * 2 * 2 + 1 * 2 * 2 * 2
    assert counting.round_ops([(4, 3)], 2, 1, [(10, 5)], [2]) == fit + own + ev + extra


def test_mfu_count_on_a_tiny_federation():
    rng = np.random.default_rng(0)
    sizes = rng.integers(20, 40, 12)
    devices = [(rng.normal(size=(n, 3)).astype(np.float32),
                np.where(rng.random(n) < 0.5, 1.0, -1.0).astype(np.float32)) for n in sizes]
    rnd = {"entry": "run_protocol", "ks": [2, 4], "strategies": ["cv", "data"],
           "lam": 0.01, "epochs": 3, "ideal_cap": 50}
    out = reference_round(devices, 25, rnd, 5, "highest")
    splits = [split(x, y, 5, i) for i, (x, y) in enumerate(devices)]
    elig = [s for s in splits if s["n"] >= 25 and len(np.unique(s["train"][1])) == 2]
    local = [(len(s["train"][1]), len(s["val"][1]) + len(s["test"][1])) for s in elig]
    rows = sum(len(s["test"][1]) for s in splits)
    cells = [(rows, sum(len(m[1]) for m in out["cell_members"][(s, k)]))
             for s in rnd["strategies"] for k in rnd["ks"]]
    full = sum(n for n, _ in local)
    want = counting.round_ops(local, 3, 3, cells + [(rows, full), (rows, 50)], [50])
    assert out["ops"] == want


def test_percentiles_count_missing_requests():
    lat = np.array([0.001] * 98 + [serve_runner.MISSING_S] * 2)
    assert serve_runner.percentile_ms(lat, 50) == pytest.approx(1.0)
    assert serve_runner.percentile_ms(lat, 99) == serve_runner.MISSING_S * 1e3
    lat = np.arange(1, 201) / 1000.0           # 1..200 ms, nearest rank
    assert serve_runner.percentile_ms(lat, 99) == pytest.approx(198.0)
    assert serve_runner.percentile_ms(lat, 50) == pytest.approx(100.0)


def test_arrivals_follow_the_seed():
    t1, r1 = serve_runner.arrivals(1000.0, 2.0, 2**33 + 7, 50)
    t2, r2 = serve_runner.arrivals(1000.0, 2.0, 2**33 + 7, 50)
    assert np.array_equal(t1, t2) and np.array_equal(r1, r2)
    assert 1800 < len(t1) < 2200 and t1[-1] < 2.0 and np.all(np.diff(t1) > 0)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_file_names_what_exists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").is_file()
        reported = [m for m in bench["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]]) for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        for w in m.get("workloads", []):
            assert w in e2e[m["moves"]].get("workloads", [w])


@pytest.mark.parametrize("env", [{}, {"REPRO_PALLAS_INTERPRET": "1"}], ids=["no_tpu", "interpret"])
def test_run_refuses_without_a_tpu(env):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", "emnist_paper.round",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
