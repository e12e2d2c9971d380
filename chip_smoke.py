"""Chip smoke test: the one-shot round and ensemble serving, once, on a TPU.

Run from the root of a checkout on a machine with a TPU:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the sharded engine only

With no arguments it drives four phases through the entry points a user
calls, each checked against the repo's own references:

  paper-round     ``run_protocol`` on the full EMNIST-like federation
                  (3,462 devices, dim 32) with ks=(10, 50) and CG
                  distillation on 10,000 validation proxies; the best
                  ensemble AUC must reach the mean local AUC.
  serving         the round's k=50 ``cv`` ensemble behind
                  ``EnsembleScorer(...).scheduler(...)``, in fp32 and from
                  its int8 wire form; 4,096 requests each, every answer
                  compared on the chip with the ``kernels.ref`` oracle at
                  the registry tolerance.
  streamed-cli    ``repro.launch.fed_run.main`` with 16,384 streamed
                  devices, the int8 codec and the serve fleet; the fleet
                  must conserve requests.
  engine-parity   per-device AUCs of ``engine="bucketed"`` and
                  ``engine="loop"`` on 256 devices agree to 1e-4.

``--chips 4`` runs only ``sharded``: ``run_population`` with
``engine="sharded", mesh_shards=4`` on 4,096 devices against the same
config on one chip with ``engine="bucketed"``. The mesh must have 4
shards, each holding its own slice, and ``best`` plus every per-device
AUC must be identical.

Findings (device kind, per-phase seconds with tracing, lowering and
compiling shown apart, AUCs, requests answered, parity errors) go to
earlier lines. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script refuses to run (non-zero exit, no result line) when JAX finds
no TPU or ``REPRO_PALLAS_INTERPRET`` is set, and it catches no phase
failure: any failed check ends the run with a traceback.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
from pathlib import Path


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from
    its monitoring events (register with
    ``jax.monitoring.register_event_duration_secs_listener``)."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs


def say(name: str, **fields) -> None:
    print(f"{name}: {json.dumps(fields, default=float)}", flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    from repro.obs import stopwatch

    c0 = clock.seconds
    elapsed = stopwatch()
    yield
    say(f"phase {name}", seconds=elapsed(), compile_seconds=clock.seconds - c0)


# ---------------------------------------------------------------- phases

def paper_round(scale: float = 1.0, proxy_size: int = 10_000):
    """The paper's round on the full EMNIST-like federation."""
    from repro.core import run_protocol
    from repro.data import make_dataset
    from repro.distill import DistillConfig

    ds = make_dataset("emnist", seed=0, scale=scale)
    res = run_protocol(ds, ks=(10, 50), distill=DistillConfig(
        proxy_size=proxy_size, solver="cg", proxy="validation"))
    best = max(res.best.values())
    say("paper-round", devices=ds.n_devices, samples=ds.total_samples,
        dim=ds.dim, local_mean_auc=res.local_mean_auc,
        ideal_mean_auc=res.ideal_mean_auc,
        full_ensemble_auc=res.full_ensemble_auc,
        ensemble_auc=res.ensemble_auc, best=res.best)
    require(best >= res.local_mean_auc,
            f"best ensemble AUC {best} >= mean local AUC {res.local_mean_auc}")
    return ds, res


def serving(ds, res, n_requests: int = 4096, k: int = 50):
    """The round's k-member cv ensemble behind the micro-batch
    scheduler, fp32 and int8-wire, checked against the oracles."""
    from functools import partial

    import jax
    import numpy as np

    from repro.comm import ModelExchange
    from repro.comm.wire import decode, encode
    from repro.core import Ensemble
    from repro.kernels import ref
    from repro.kernels.ops import KERNEL_REGISTRY
    from repro.obs import Tracer, use_tracer
    from repro.serve import EnsembleScorer, ServeConfig
    from repro.sim import train_population

    # the round's local phase again (same seed, same engine): its
    # reports pick the same cv members the round evaluated
    devices = train_population(ds, seed=0).outcomes
    ex = ModelExchange({d.device_id: d.model for d in devices},
                       [d.report for d in devices])
    ens = Ensemble([ex.received(i) for i in ex.pick("cv", k, 0)])
    cell_auc = EnsembleScorer(ens).evaluate(
        ((d.device_id, d.splits["test"].x, d.splits["test"].y) for d in devices),
        chunk=8192).mean()
    say("serving.cell", k=ens.k, auc=cell_auc, round_auc=res.ensemble_auc["cv"][k])
    require(abs(cell_auc - res.ensemble_auc["cv"][k]) <= 1e-4,
            "the served ensemble is the round's cv cell")

    pool = np.concatenate([d.splits["test"].x for d in devices])
    rows = pool[np.random.default_rng(0).choice(len(pool), n_requests, replace=False)]
    cfg = ServeConfig(max_batch=256, buckets=(8, 32, 128, 256))
    forms = {
        "fp32": (Ensemble(ens.members), "ensemble_score", ref.ensemble_score_ref),
        "int8": (decode(encode(ens, "int8")), "ensemble_score_q8",
                 ref.ensemble_score_q8_ref),
    }
    for form, (model, kernel, oracle) in forms.items():
        scorer = EnsembleScorer(model)
        sched = scorer.scheduler(cfg)
        tracer = Tracer()
        with use_tracer(tracer):
            answers = sched.run(list(rows))
        dispatches = [e for e in tracer.events
                      if e["name"] == "serve.dispatch" and e["ph"] == "B"]
        st = scorer.stacked
        if form == "fp32":
            oracle = partial(oracle, k=st.k)
            packed = (st.sup, st.coef, st.gamma)
        else:
            packed = (st.q, st.scale, st.zero, st.coef, st.gammas)
        want = np.asarray(jax.jit(oracle)(rows, *packed))
        err = float(np.max(np.abs(answers - want)))
        tol = KERNEL_REGISTRY[kernel].tol
        say(f"serving.{form}", requests=int(sched.stats.submitted),
            answered=int(len(answers)), batches=int(sched.stats.batches),
            padded_rows=int(sched.stats.padded_rows),
            dispatches=len(dispatches), max_abs_err=err, tol=tol)
        require(len(answers) == n_requests and answers.shape == (n_requests,),
                f"{form}: every request answered")
        require(len(dispatches) == sched.stats.batches,
                f"{form}: one scoring dispatch per batch")
        require(err <= tol, f"{form}: answers within {tol} of the oracle")


def streamed_cli(devices: int = 16384, chunk: int = 1024):
    """The streamed CLI round with the int8 codec and the serve fleet."""
    from repro.launch.fed_run import main

    out = io.StringIO()  # main prints its whole JSON report
    with contextlib.redirect_stdout(out):
        rep = main(["--mode", "sim", "--scenario", "dirichlet",
                    "--devices", str(devices), "--engine", "streamed",
                    "--chunk-devices", str(chunk), "--k", "10", "50",
                    "--codec", "int8", "--serve-fleet"])
    g = rep["fleet"]["global"]
    say("streamed-cli", devices=rep["devices"], eligible=rep["eligible"],
        mean_local_auc=rep["mean_local_auc"], best=rep["best"],
        train_seconds=rep["train_seconds"],
        fleet={key: g[key] for key in ("submitted", "completed", "shed",
                                       "conserved")})
    require(g["conserved"] is True, "fleet.global.conserved")


def engine_parity(n_devices: int = 256):
    """bucketed vs the sequential loop oracle, per device."""
    import numpy as np

    from repro.sim import make_federation, train_population

    ds = make_federation("dirichlet", n_devices=n_devices, seed=0).dataset
    runs = {m: train_population(ds, seed=0, mode=m).outcomes
            for m in ("bucketed", "loop")}
    b, l = runs["bucketed"], runs["loop"]
    require([o.device_id for o in b] == [o.device_id for o in l], "same devices")
    val = max(abs(x.report.val_auc - y.report.val_auc) for x, y in zip(b, l))
    test = max(abs(x.local_test_auc - y.local_test_auc) for x, y in zip(b, l))
    say("engine-parity", devices=len(b),
        eligible=sum(o.report.eligible for o in b),
        mean_local_auc=float(np.mean([o.local_test_auc for o in b])),
        max_val_auc_diff=val, max_test_auc_diff=test)
    require(max(val, test) <= 1e-4, "bucketed and loop per-device AUCs agree to 1e-4")


def sharded(n_devices: int = 4096, mean_samples: int = 200, shards: int = 4):
    """The sharded engine over a real mesh against bucketed on one chip."""
    import dataclasses

    import jax
    import numpy as np

    from repro.obs import stopwatch
    from repro.sim import PopulationConfig, make_shard_ctx, run_population

    ctx = make_shard_ctx(shards)
    require(ctx.n_shards == shards, f"a {shards}-shard mesh (got {ctx.n_shards})")
    # one fit through the sharded dispatch: its output must be laid out
    # over every mesh device, one slice each
    g, b, d = 2 * shards, 64, 8
    x = np.random.default_rng(0).standard_normal((g, b, d)).astype(np.float32)
    y = np.where(np.arange(b) % 2, 1.0, -1.0).astype(np.float32)
    alpha = ctx.fit(x, np.tile(y, (g, 1)), np.full(g, b, np.int32),
                    np.ones(g, np.float32), 0.01)
    devs = set(alpha.sharding.device_set)
    slices = {s.device: s.data.shape for s in alpha.addressable_shards}
    say("sharded.layout", mesh=ctx.n_shards, devices=sorted(str(v) for v in devs),
        slice_shapes=sorted(set(slices.values())))
    require(len(devs) == shards and len(slices) == shards
            and set(slices.values()) == {(g // shards, b)},
            "each shard holds its own slice")

    cfg = PopulationConfig(scenario="dirichlet", n_devices=n_devices, seed=0,
                           mean_samples=mean_samples, engine="sharded",
                           mesh_shards=shards)
    reports, per_device = {}, {}
    for engine in ("sharded", "bucketed"):
        seen = {}
        elapsed = stopwatch()
        reports[engine] = run_population(
            dataclasses.replace(cfg, engine=engine),
            on_update=lambda u: seen.update(
                (o.device_id, (o.report.val_auc, o.local_test_auc))
                for o in u.outcomes))
        per_device[engine] = seen
        say(f"sharded.{engine}", seconds=elapsed(),
            devices=len(seen), eligible=reports[engine].n_eligible,
            train_seconds=reports[engine].train_seconds,
            best=reports[engine].best)
    require(reports["sharded"].best == reports["bucketed"].best, "identical best")
    require(per_device["sharded"] == per_device["bucketed"],
            "identical per-device AUCs")
    say("sharded.parity", devices=len(per_device["sharded"]), identical=True,
        default_device=str(jax.devices()[0]))


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip phases; 4: only the sharded "
                         "engine on a 4-chip mesh")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_PALLAS_INTERPRET"):
        print("chip_smoke: REPRO_PALLAS_INTERPRET is set; the kernels must "
              "run compiled", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache
    from repro.obs import stopwatch
    from repro.obs.profile import peak_sheet

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    say("device", platform=dev.platform, kind=dev.device_kind,
        count=len(devices), jax=jax.__version__,
        peak_sheet=peak_sheet(dev).name, compile_cache=use_compile_cache())
    elapsed = stopwatch()
    if args.chips == 4:
        with phase("sharded", clock):
            sharded()
    else:
        with phase("paper-round", clock):
            ds, res = paper_round()
        with phase("serving", clock):
            serving(ds, res)
        with phase("streamed-cli", clock):
            streamed_cli()
        with phase("engine-parity", clock):
            engine_parity()
    say("total", seconds=elapsed(), compile_seconds=clock.seconds)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
