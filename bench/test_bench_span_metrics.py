"""CPU tests of the per-layer readers of the program's evaluation and
serve spans and of the scheduler's queue-wait counter: each against a
hand-built run, including the run of a program without them (None),
and each in a traced run of its cell cut to test size."""
from types import SimpleNamespace

import pytest

from bench import run as bench_run
from bench import tiny

ROUND_SPANS = [
    ("round.select", 0.0, 10.0),
    ("eval.score", 1.0, 3.0), ("ensemble.pack", 1.0, 1.5), ("eval.fold", 3.0, 3.25),
    ("eval.score", 4.0, 5.0), ("eval.fold", 5.0, 5.5), ("eval.auc", 6.0, 6.75),
    ("ensemble.pack", 7.0, 7.5),
]
SERVE_SPANS = [
    ("serve.flush", 0.0, 100e-6), ("serve.score", 10e-6, 80e-6),
    ("serve.fetch", 40e-6, 70e-6),
    ("serve.flush", 200e-6, 260e-6), ("serve.score", 210e-6, 240e-6),
    ("serve.fetch", 220e-6, 230e-6),
]
STATS = {"scored_rows": 30, "answered_from_cache": 6, "deduped_in_flight": 4,
         "padded_rows": 2, "batches": 2, "queue_wait_ns": 20_000_000}
PARENT_STATS = {k: v for k, v in STATS.items() if k != "queue_wait_ns"}


def read(metric: str, **run):
    mod = bench_run.load(bench_run.BENCH / "metrics" / f"{metric}.py",
                         "bench_metric_" + metric.replace(".", "_"))
    return mod.read(SimpleNamespace(**dict(dict(spans=[], units=2, stats={}), **run)))


@pytest.mark.parametrize("metric,run,want", [
    ("round.pack_s", dict(spans=ROUND_SPANS), (0.5 + 0.5) / 2),
    ("round.score_s", dict(spans=ROUND_SPANS), (2.0 + 1.0) / 2),
    ("round.fold_s", dict(spans=ROUND_SPANS), (0.25 + 0.5 + 0.75) / 2),
    ("serve.queue_wait_ms", dict(stats=STATS), 20.0 / 40),
    ("serve.host_us", dict(spans=SERVE_SPANS), ((100 + 60) - (30 + 10)) / 2),
    ("serve.fetch_us", dict(spans=SERVE_SPANS), (30 + 10) / 2),
])
def test_reader_on_a_hand_built_run(metric, run, want):
    assert read(metric, **run) == pytest.approx(want)


@pytest.mark.parametrize("metric,run", [
    ("round.pack_s", dict(spans=[("round.select", 0.0, 1.0)])),
    ("round.score_s", dict(spans=[("round.select", 0.0, 1.0)])),
    ("round.fold_s", dict(spans=[("round.select", 0.0, 1.0)])),
    ("serve.queue_wait_ms", dict(stats=PARENT_STATS)),
    ("serve.queue_wait_ms", dict(stats=dict(STATS, scored_rows=0, answered_from_cache=0,
                                            deduped_in_flight=0))),
    ("serve.host_us", dict(spans=[("round.select", 0.0, 1.0)])),
    ("serve.fetch_us", dict(spans=[])),
])
def test_reader_without_its_spans_or_counter_reads_none(metric, run):
    assert read(metric, **run) is None


def test_host_and_fetch_add_up_to_the_mean_flush():
    flushes = [e - s for n, s, e in SERVE_SPANS if n == "serve.flush"]
    total = read("serve.host_us", spans=SERVE_SPANS) + read("serve.fetch_us", spans=SERVE_SPANS)
    assert total == pytest.approx(1e6 * sum(flushes) / len(flushes))


NEW = {"emnist_paper.round": ("round.pack_s", "round.score_s", "round.fold_s"),
       "emnist_paper.serve_k50": ("serve.queue_wait_ms", "serve.host_us", "serve.fetch_us")}


@pytest.mark.parametrize("name", sorted(NEW))
def test_traced_tiny_run_reports_the_new_metrics(name):
    res = bench_run.run_cell(tiny.cell(name), 2**31 + 11, 1.0, True, require_chip=False)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    for metric in NEW[name]:
        assert isinstance(got.get(metric), float) and got[metric] >= 0, metric
