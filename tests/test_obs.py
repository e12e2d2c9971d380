"""Observability bars (docs/TESTING.md):

  * the null tracer is the default and a true no-op — instrumented hot
    paths must behave identically with tracing off;
  * spans nest with the ``with`` stack and export valid Chrome
    trace-event JSON (balanced B/E, typed attrs);
  * a streamed-engine round emits exactly ceil(population / chunk)
    chunk spans, with monotonically nested begin/end events;
  * a seeded fleet run's trace is byte-identical across two runs (the
    simulated-ms clock regime — no wall-clock reads anywhere);
  * kernel spans carry the achieved-vs-roofline FLOPs/bytes attributes
    from XLA cost analysis;
  * the metrics registry folds the existing silos (CommLedger,
    FleetMetrics, SchedulerStats) into one schema-versioned envelope.
"""
import json
import math

import numpy as np
import pytest

from repro.obs import (
    NULL_TRACER,
    MetricsRegistry,
    Tracer,
    current_tracer,
    envelope,
    sim_clock,
    timed_call,
    use_tracer,
)
from repro.obs.registry import SCHEMA, SCHEMA_VERSION


# ---------------------------------------------------------------- tracer

def _stack_check(events):
    """Walk B/E events like a parser: depth never goes negative, every
    E matches the open B's name, and the stack drains to zero."""
    stack = []
    for e in events:
        if e["ph"] == "B":
            stack.append(e["name"])
        elif e["ph"] == "E":
            assert stack, "E event with no open span"
            stack.pop()
    assert stack == [], f"unclosed spans: {stack}"


def test_null_tracer_is_default_and_noop():
    assert current_tracer() is NULL_TRACER
    assert not NULL_TRACER.enabled
    with NULL_TRACER.span("x", cat="t", anything="goes"):
        pass
    NULL_TRACER.instant("y")
    NULL_TRACER.complete("z", 0.0, 1.0)
    assert NULL_TRACER.export("/nonexistent/dir/t.json") is False


def test_use_tracer_installs_and_restores():
    t = Tracer()
    with use_tracer(t):
        assert current_tracer() is t
        with t.span("outer"):
            pass
    assert current_tracer() is NULL_TRACER


def test_span_nesting_and_valid_json(tmp_path):
    t = Tracer(process_name="test")
    with t.span("outer", cat="a", n=1):
        with t.span("inner", cat="a"):
            pass
        t.instant("tick", cat="a", flag=True)
    _stack_check(t.events)
    path = tmp_path / "trace.json"
    assert t.export(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    names = [e["name"] for e in doc["traceEvents"]]
    assert names == ["process_name", "outer", "inner", "inner", "tick", "outer"]
    # B timestamps are monotone per the wall clock
    begins = [e["ts"] for e in doc["traceEvents"] if e["ph"] == "B"]
    assert begins == sorted(begins)


def test_typed_attrs_coerce_and_reject():
    t = Tracer()
    t.instant("ok", count=np.int64(3), frac=np.float32(0.5), label="s", b=False)
    args = t.events[-1]["args"]
    assert args["count"] == 3 and isinstance(args["count"], int)
    assert isinstance(args["frac"], float)
    with pytest.raises(TypeError):
        t.instant("bad", listy=[1, 2])


def test_sim_clock_reads_simulated_ms():
    class FakeClock:
        now_ms = 12.5

    t = Tracer(clock=sim_clock(FakeClock()))
    t.instant("at")
    assert t.events[-1]["ts"] == 12500.0


def test_merge_keeps_pids_and_export_is_deterministic():
    a, b = Tracer(pid=1), Tracer(pid=2)
    with a.span("wall"):
        pass
    b.complete("sim", ts_us=1000.0, dur_us=50.0)
    a.merge(b)
    pids = {e["pid"] for e in a.events}
    assert pids == {1, 2}
    a2 = Tracer(pid=1)
    a2.events = [dict(e) for e in a.events]
    assert a.to_json() == a2.to_json()


# ------------------------------------------------------- engine spans

def test_streamed_round_emits_exact_chunk_spans():
    from repro.sim import make_federation
    from repro.sim.engine import iter_population

    n, chunk = 40, 12
    fed = make_federation("iid", n_devices=n, seed=0, mean_samples=80)
    t = Tracer()
    with use_tracer(t):
        updates = list(iter_population(fed.dataset, mode="streamed",
                                       chunk_devices=chunk))
    assert sum(len(u.outcomes) for u in updates) == n
    chunks = [e for e in t.events
              if e["name"] == "engine.chunk" and e["ph"] == "B"]
    assert len(chunks) == math.ceil(n / chunk)
    _stack_check(t.events)
    # group spans nest strictly inside chunk spans
    depth = 0
    for e in t.events:
        if e["ph"] == "B":
            if e["name"] == "engine.group":
                assert depth >= 1, "group span outside any chunk span"
            depth += 1
        elif e["ph"] == "E":
            depth -= 1


def test_engine_counters_accumulate():
    from repro.obs import default_registry
    from repro.sim import make_federation
    from repro.sim.engine import train_population

    reg = default_registry()
    reg.reset()
    fed = make_federation("iid", n_devices=24, seed=1, mean_samples=80)
    train_population(fed.dataset, mode="bucketed")
    out = reg.collect()["engine"]
    assert out["devices_trained"]["value"] == 24
    assert out["groups"]["value"] >= 1


# -------------------------------------------------------- fleet traces

def _fleet_trace_json(seed: int) -> str:
    from repro.fleet import (CostModel, FleetConfig, ServeFleet, TenantRegistry,
                             TenantSLO, nominal_capacity_qps, open_loop_trace)
    from repro.serve import ServeConfig
    from repro.core import Ensemble
    from repro.core.svm import SVMModel

    rng = np.random.default_rng(seed)
    ens = Ensemble([
        SVMModel(support_x=rng.normal(0, 1, (20, 8)).astype(np.float32),
                 coef=rng.normal(0, 0.1, 20).astype(np.float32), gamma=0.2)
        for _ in range(2)
    ])
    serve = ServeConfig(max_batch=8, max_queue=512, buckets=(8,), cache_size=64)
    registry = TenantRegistry()
    registry.register("t00", ens, slo=TenantSLO(deadline_ms=20.0, priority=1,
                                                quota=64),
                      serve=serve, n_shards=2)
    config = FleetConfig(n_servers=1, max_global_queue=128, cost=CostModel())
    rate = 2.0 * nominal_capacity_qps(1, serve, config.cost)
    trace = open_loop_trace({"t00": rate}, horizon_ms=6.0, dim=8, seed=seed,
                            pool_size=64)
    tracer = Tracer(process_name="fleet (simulated ms)")
    fleet = ServeFleet(registry, config, tracer=tracer)
    fleet.run(trace, horizon_ms=6.0)
    return tracer.to_json()


def test_fleet_trace_byte_identical_across_runs():
    a, b = _fleet_trace_json(7), _fleet_trace_json(7)
    assert a == b
    evs = json.loads(a)["traceEvents"]
    execs = [e for e in evs if e["name"] == "fleet.execute"]
    assert execs, "overloaded fleet produced no execute spans"
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in execs)
    # explicit simulated timestamps only: completes are time-ordered
    ts = [e["ts"] for e in execs]
    assert ts == sorted(ts)


def test_fleet_untraced_runs_match_traced_metrics():
    # the tracer must observe, never perturb, the simulation
    import re
    a = _fleet_trace_json(3)
    evs = json.loads(a)["traceEvents"]
    assert any(e["name"] == "fleet.shed" for e in evs)


# ------------------------------------------------------- kernel spans

def test_kernel_spans_carry_roofline_attrs():
    import jax
    from repro.kernels import ops

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    t = Tracer()
    with use_tracer(t):
        ops.rbf_gram(x, x, 0.5)
    spans = [e for e in t.events if e["name"] == "kernel.rbf_gram"]
    assert len(spans) == 1
    args = spans[0]["args"]
    assert args["flops"] > 0 and args["bytes_accessed"] > 0
    assert args["achieved_gflops"] > 0 and args["dur_s"] > 0
    # no peak sheet describes the CPU, so a CPU span has no roofline share
    assert args["backend"] == "cpu"
    assert not {"roofline_frac", "roofline_bound_us", "dominant"} & set(args)
    # a TPU is priced by its device kind; an unknown kind is an error
    from types import SimpleNamespace
    from repro.obs.profile import peak_sheet
    from repro.roofline import V5E

    assert peak_sheet(jax.devices()[0]) is None
    assert peak_sheet(SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")) is V5E
    with pytest.raises(ValueError, match="no peak sheet"):
        peak_sheet(SimpleNamespace(platform="tpu", device_kind="TPU v99"))
    # untouched dispatch result when tracing is off
    out_off = ops.rbf_gram(x, x, 0.5)
    with use_tracer(Tracer()):
        out_on = ops.rbf_gram(x, x, 0.5)
    np.testing.assert_array_equal(np.asarray(out_off), np.asarray(out_on))


def test_timed_call_times_and_emits_bench_spans():
    import jax.numpy as jnp

    t = Tracer()
    with use_tracer(t):
        us = timed_call("toy", lambda: jnp.ones(4) + 1, repeats=3, warmup=1)
    assert us > 0
    bench = [e for e in t.events if e["name"] == "bench.toy"]
    assert len(bench) == 3
    assert sorted(e["args"]["repeat"] for e in bench) == [0, 1, 2]


# ----------------------------------------------------------- registry

def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.counter("a.b").inc(2)
    reg.counter("a.b").inc()
    reg.gauge("a.g").set(1.5)
    for v in range(10):
        reg.histogram("h").observe(float(v))
    out = reg.collect()
    assert out["a"]["b"] == {"type": "counter", "value": 3}
    assert out["a"]["g"]["value"] == 1.5
    h = out["h"]
    assert h["count"] == 10 and h["min"] == 0.0 and h["max"] == 9.0
    assert h["p50"] == 4.0  # nearest-rank, like fleet.metrics
    with pytest.raises(ValueError):
        reg.counter("a.b").inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("a.b")
    with pytest.raises(ValueError):
        reg.counter("a.b.c")  # collides with existing metric "a.b"
        reg.collect()


def test_envelope_adapts_all_silos():
    from repro.comm import CommLedger
    from repro.serve.scheduler import SchedulerStats

    ledger = CommLedger()
    ledger.record("up", "model_upload", 100, codec="fp32", tag="u")
    stats = [SchedulerStats(submitted=3, answered_from_cache=1),
             SchedulerStats(submitted=2)]
    reg = MetricsRegistry()
    reg.counter("x").inc(1)
    env = envelope(reg, comm=ledger, fleet={"global": {"submitted": 5}},
                   scheduler=stats, extra={"note": "hi"})
    assert env["schema"] == SCHEMA
    assert env["schema_version"] == SCHEMA_VERSION
    sec = env["sections"]
    assert sec["comm"]["messages"] == 1
    assert sec["comm"]["summary"]["total_up"] == 100.0
    assert sec["fleet"]["global"]["submitted"] == 5
    assert sec["scheduler"]["submitted"] == 5
    assert sec["scheduler"]["shards"] == 2
    assert sec["metrics"]["x"]["value"] == 1
    assert sec["note"] == "hi"
    json.dumps(env)  # envelope must be JSON-serializable end to end


# ------------------------------------------------ logging satellites

def test_log_level_env(monkeypatch):
    import logging

    from repro.utils.logging import _env_level

    monkeypatch.delenv("REPRO_LOG_LEVEL", raising=False)
    assert _env_level() == logging.INFO
    monkeypatch.setenv("REPRO_LOG_LEVEL", "DEBUG")
    assert _env_level() == logging.DEBUG
    monkeypatch.setenv("REPRO_LOG_LEVEL", "30")
    assert _env_level() == logging.WARNING
    monkeypatch.setenv("REPRO_LOG_LEVEL", "bogus")
    assert _env_level() == logging.INFO


def test_kv_formatting():
    from repro.utils import kv

    assert kv(event="x", n=3) == "event=x n=3"
    assert kv(msg="two words") == "msg='two words'"
    assert kv(empty="") == "empty=''"
    assert kv(eq="a=b") == "eq='a=b'"


# ----------------------------------------------------- fed_run --trace

def test_fed_run_trace_covers_subsystems(tmp_path, capsys):
    from repro.launch.fed_run import main

    trace_path = tmp_path / "trace.json"
    out = main([
        "--mode", "sim", "--scenario", "iid", "--devices", "24",
        "--mean-samples", "80", "--k", "2", "--engine", "streamed",
        "--chunk-devices", "8", "--distill-proxy", "32", "--serve-fleet",
        "--fleet-horizon-ms", "30", "--trace", str(trace_path),
    ])
    capsys.readouterr()
    doc = json.loads(trace_path.read_text())
    cats = {e.get("cat") for e in doc["traceEvents"] if "cat" in e}
    # the acceptance bar: spans from >= 4 subsystems in one trace
    assert {"engine", "comm", "distill", "fleet"} <= cats
    # the report embeds the schema-versioned envelope
    assert out["obs"]["schema"] == SCHEMA
    assert "comm" in out["obs"]["sections"]
    assert "fleet" in out["obs"]["sections"]
    # pid 2 = the fleet's simulated-ms process track
    fleet_evs = [e for e in doc["traceEvents"] if e.get("cat") == "fleet"]
    assert all(e["pid"] == 2 for e in fleet_evs)
