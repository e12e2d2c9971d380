"""One-shot federated learning driver.

Two modes share this entry point:

``--mode lm`` (default) — the transformer instantiation: M clients
train SMALL models of an assigned family to completion
(client-parallel via vmap — the member axis shards over the mesh
'data' axis on real hardware), the server ensembles their predictions,
then distills into a student in ONE round.

  PYTHONPATH=src python -m repro.launch.fed_run --arch llama3.2-1b \
      --clients 4 --local-steps 30 --distill-steps 30

``--mode sim`` — the population-scale SVM protocol on the
device-parallel ``repro.sim`` engine: pick any registered scenario,
train hundreds of local models in bucketed vectorized passes, and
report selection/ensembling quality. ``--engine sharded`` lays the
bucket groups across all local accelerators (``--mesh N`` caps the
mesh; results are bitwise-identical to the bucketed tier).

  PYTHONPATH=src python -m repro.launch.fed_run --mode sim \
      --scenario dirichlet --devices 512 --k 10 50
  PYTHONPATH=src python -m repro.launch.fed_run --mode sim \
      --scenario dirichlet --devices 4096 --engine sharded --mesh 4
  PYTHONPATH=src python -m repro.launch.fed_run --mode sim \
      --scenario dirichlet --devices 1000000 --engine streamed \
      --chunk-devices 1024

``--engine streamed`` never materializes the federation: devices are
generated lazily from their per-device seeds, trained in
``--chunk-devices``-sized chunks, and folded into scalar columns, so
peak host memory is O(chunk) however large ``--devices`` is — with
results identical to the materialized engines.

Sim-mode uploads go through the ``repro.comm`` wire (``--codec fp32 |
fp16 | int8 | topk[:ratio]``) with an optional per-selection byte cap
(``--budget-bytes``); the report includes the ledger's exact per-tag
byte totals. ``--distill-proxy N`` distills the best selected ensemble
through ``repro.distill`` (``--distill-solver dense|cg|nystrom|auto``,
``--proxy-source validation|public|gaussian|scenario``,
``--student-codec`` for an independent download codec).
``--aggregator mean | fisher | reweight[:T] | feature_stats`` selects
the server aggregation strategy from the ``repro.agg`` registry; any
side payload a strategy needs (Fisher diagonals, validation columns,
feature moments) is wire-encoded and priced on the ledger under
``kind=agg_extra``. ``--serve-fleet`` then deploys the round's artifact
behind the multi-tenant serve fleet (``repro.fleet``) — the distilled
student when distillation ran, otherwise the chosen aggregator's server
scorer — wire blob -> checkpoint -> tenant registry -> simulated
open-loop load — and appends the SLO metrics (latency percentiles,
goodput, shed rate) to the report under ``"fleet"``.
"""
from __future__ import annotations

import argparse
import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import deepfed
from repro.data import make_federated_lm_data, token_batches
from repro.launch.compile_cache import use_compile_cache
from repro.models import ShardCtx
from repro.obs import (Tracer, current_tracer, default_registry, envelope,
                       stopwatch, use_tracer)
from repro.utils.logging import get_logger

log = get_logger("fed_run")


def run_sim(args) -> dict:
    """Scenario-driven population round on the repro.sim engine."""
    from repro.sim import PopulationConfig, list_scenarios, run_population

    if args.scenario == "list":
        for name, doc in list_scenarios().items():
            print(f"{name:16s} {doc}")
        return {}
    params = dict(kv.split("=", 1) for kv in args.scenario_param)
    params = {k: float(v) if v.replace(".", "", 1).isdigit() else v
              for k, v in params.items()}
    distill = None
    if args.distill_proxy > 0:
        from repro.distill import DistillConfig

        distill = DistillConfig(
            proxy_size=args.distill_proxy,
            solver=args.distill_solver,
            proxy=args.proxy_source,
            codec=args.student_codec,
        )
    cfg = PopulationConfig(
        scenario=args.scenario,
        n_devices=args.devices,
        seed=args.seed,
        mean_samples=args.mean_samples,
        ks=tuple(args.k),
        engine=args.engine,
        mesh_shards=args.mesh,
        chunk_devices=args.chunk_devices,
        scenario_params=params,
        codec=args.codec,
        budget_bytes=args.budget_bytes,
        aggregator=args.aggregator,
        distill=distill,
    )

    def progress(u):
        log.info("bucket %4d: +%3d devices (%d/%d done)",
                 u.bucket, len(u.outcomes), u.done, u.total)

    # report the ACTUAL shard count (make_sim_mesh clamps the request
    # to local devices and floors to a power of two), so a degenerated
    # mesh is visible in the JSON instead of echoing the flag back
    mesh_used = None
    if args.engine == "sharded":
        from repro.sim import make_shard_ctx

        mesh_used = make_shard_ctx(args.mesh).n_shards

    # --trace: one wall-clock tracer for the round, one explicit-ts
    # sub-tracer (pid 2 = its own Perfetto process track) for the
    # fleet's simulated-ms events; merged into a single trace file
    tracer = fleet_tracer = None
    stack = contextlib.ExitStack()
    if args.trace:
        tracer = Tracer(pid=1, process_name="fed_run")
        fleet_tracer = Tracer(pid=2, process_name="fleet (simulated ms)")
        stack.enter_context(use_tracer(tracer))

    with stack:
        report = run_population(cfg, on_update=progress)
    out = {
        "mode": "sim",
        "scenario": report.scenario,
        "engine": args.engine,
        "mesh": mesh_used,
        "mesh_requested": args.mesh,
        "devices": report.n_devices,
        "available": report.n_available,
        "eligible": report.n_eligible,
        "mean_local_auc": report.mean_local_auc,
        "mean_val_auc": report.mean_val_auc,
        "ensemble_auc": {s: dict(v) for s, v in report.ensemble_auc.items()},
        "best": report.best,
        "train_seconds": report.train_seconds,
        "devices_per_second": report.devices_per_second,
        "codec": report.codec,
        "budget_bytes": report.budget_bytes,
        "aggregator": report.aggregator,
        "comm": report.comm,
    }
    if report.student is not None:
        out["student_codec"] = report.student_codec
        out["distill_solver"] = args.distill_solver
        out["proxy_source"] = args.proxy_source
    if report.time_to_aggregate:
        out["time_to_aggregate"] = {
            s: dict(v) for s, v in report.time_to_aggregate.items()
        }
    if args.serve_fleet:
        # deploy what the round actually produced: the distilled
        # student when distillation ran, otherwise the chosen
        # aggregator's server scorer (the best selected cell)
        artifact = report.student if report.student is not None \
            else report.server_scorer
        if artifact is None:
            raise SystemExit(
                "--serve-fleet deploys the round's artifact (distilled "
                "student or aggregated server scorer), but the round "
                "produced neither — no selection cell had any members"
            )
        from repro.fleet import serve_round_artifact

        # deploy the round's artifact through the wire -> checkpoint ->
        # fleet path and measure it under load (simulated time: this
        # adds metrics, not wall-clock minutes)
        out["fleet"] = serve_round_artifact(
            artifact,
            seed=args.seed,
            horizon_ms=args.fleet_horizon_ms,
            load=args.fleet_load,
            tracer=fleet_tracer,
        )
        out["fleet"]["handoff"]["artifact"] = (
            "student" if report.student is not None else "server_scorer"
        )
    # the schema-versioned observability envelope: registry counters
    # (engine chunks/groups/devices) + the round's exact comm ledger
    out["obs"] = envelope(
        default_registry(),
        comm=report.ledger,
        fleet=out.get("fleet"),
    )
    if tracer is not None:
        tracer.merge(fleet_tracer)
        if tracer.export(args.trace):
            log.info("trace written to %s (open at https://ui.perfetto.dev)",
                     args.trace)
    print(json.dumps(out, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", default="lm", choices=["lm", "sim"])
    ap.add_argument("--scenario", default="dirichlet",
                    help="sim mode: registered scenario name, or 'list'")
    ap.add_argument("--devices", type=int, default=256, help="sim mode")
    ap.add_argument("--mean-samples", type=int, default=80, help="sim mode")
    ap.add_argument("--k", type=int, nargs="+", default=[10], help="sim mode")
    ap.add_argument("--engine", default="bucketed",
                    choices=["bucketed", "sharded", "loop", "streamed"],
                    help="sim mode: bucketed (one device) | sharded "
                         "(mesh-parallel across local accelerators) | "
                         "loop (sequential oracle) | streamed (lazy "
                         "chunked federation, O(chunk) host memory)")
    ap.add_argument("--mesh", type=int, default=None,
                    help="sim mode, --engine sharded: cap the sim mesh "
                         "at this many devices (default: all local)")
    ap.add_argument("--chunk-devices", type=int, default=1024,
                    help="sim mode, --engine streamed: devices resident "
                         "at once (peak host memory is O(this))")
    ap.add_argument("--scenario-param", action="append", default=[],
                    metavar="KEY=VALUE", help="sim mode: e.g. alpha=0.1")
    ap.add_argument("--codec", default="fp32",
                    help="sim mode: wire codec for model uploads "
                         "(fp32 | fp16 | int8 | topk[:ratio])")
    ap.add_argument("--budget-bytes", type=int, default=None,
                    help="sim mode: upload byte budget per selection "
                         "(strategy-rank greedy knapsack over encoded sizes)")
    ap.add_argument("--aggregator", default="mean",
                    help="sim mode: server aggregation strategy from the "
                         "repro.agg registry (mean | fisher | "
                         "reweight[:T] | feature_stats); extras ride "
                         "the ledger under kind=agg_extra")
    ap.add_argument("--distill-proxy", type=int, default=0,
                    help="sim mode: distill the best ensemble on this "
                         "many proxy points (0 disables)")
    ap.add_argument("--distill-solver", default="auto",
                    help="sim mode: distill solver "
                         "(dense | cg | nystrom | auto)")
    ap.add_argument("--proxy-source", default="validation",
                    help="sim mode: proxy registry source "
                         "(validation | public | gaussian | scenario)")
    ap.add_argument("--student-codec", default=None,
                    help="sim mode: student download codec "
                         "(default: the round's --codec)")
    ap.add_argument("--serve-fleet", action="store_true",
                    help="sim mode: after the round, deploy its artifact "
                         "behind the multi-tenant serve fleet (repro.fleet) "
                         "and report SLO metrics under load — the distilled "
                         "student when --distill-proxy ran, otherwise the "
                         "chosen --aggregator's server scorer")
    ap.add_argument("--fleet-horizon-ms", type=float, default=250.0,
                    help="--serve-fleet: simulated traffic window (ms)")
    ap.add_argument("--fleet-load", type=float, default=1.0,
                    help="--serve-fleet: offered load as a multiple of "
                         "the fleet's nominal scoring capacity")
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=30)
    ap.add_argument("--distill-steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--tokens-per-client", type=int, default=4000)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--distill-loss", default="kl", choices=["kl", "l2"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the run "
                         "(spans from engine/round/comm/distill/fleet; "
                         "open at https://ui.perfetto.dev)")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.mode == "sim":
        return run_sim(args)

    cfg = get_config(args.arch).reduced()
    M, B, S = args.clients, args.batch, args.seq
    log.info("one-shot FL: %d clients of reduced %s", M, args.arch)

    tracer = Tracer(process_name="fed_run") if args.trace else None
    stack = contextlib.ExitStack()
    if tracer is not None:
        stack.enter_context(use_tracer(tracer))
    stack.__enter__()

    clients = make_federated_lm_data(M, cfg.vocab, args.tokens_per_client, seed=args.seed)
    wins = []
    for c in clients:
        it = token_batches(c, B, S, seed=args.seed + 1)
        wins.append(np.stack([next(it) for _ in range(args.local_steps)]))
    wins = jnp.asarray(np.stack(wins))  # (M, steps, B, S+1)

    # --- phase 1: local training to completion (client-parallel) ---
    key = jax.random.PRNGKey(args.seed)
    stacked = deepfed.stacked_init(cfg, M, key)
    train = deepfed.make_local_train(cfg, lr=args.lr)
    elapsed = stopwatch()
    with current_tracer().span("lm.local_train", cat="round", clients=M):
        stacked, losses = train(stacked, wins)
    t_local = elapsed()
    log.info(
        "local training: loss %.3f -> %.3f in %.1fs (all %d clients in parallel)",
        float(losses[:, 0].mean()), float(losses[:, -1].mean()), t_local, M,
    )

    # --- held-out eval data: a mix of every client's distribution ---
    test = jnp.asarray(
        np.stack([next(token_batches(clients[i % M], B, S, seed=args.seed + 7)) for i in range(2 * M)])
    )
    single_nll = deepfed.ensemble_eval_loss(jax.tree.map(lambda x: x[:1], stacked), cfg, test)
    ens_nll = deepfed.ensemble_eval_loss(stacked, cfg, test)
    log.info("NLL: best-effort single member %.4f | %d-member ensemble %.4f", single_nll, M, ens_nll)

    # --- phase 2: the single communication round + server distillation ---
    proxy = jnp.asarray(
        np.stack([next(token_batches(clients[i % M], B, S, seed=args.seed + 13)) for i in range(M)])
    )
    elapsed = stopwatch()
    with current_tracer().span("lm.distill", cat="distill",
                               steps=args.distill_steps):
        student, dlosses = deepfed.distill_to_student(
            cfg, cfg, stacked, proxy, steps=args.distill_steps, lr=args.lr,
            loss_kind=args.distill_loss, seed=args.seed,
        )
    t_distill = elapsed()
    student_nll = deepfed.ensemble_eval_loss(
        jax.tree.map(lambda x: x[None], student), cfg, test
    )
    log.info("distilled student NLL %.4f (distill loss %.4f -> %.4f, %.1fs)",
             student_nll, dlosses[0], dlosses[-1], t_distill)

    comm = deepfed.one_shot_comm_bytes(stacked, n_selected=M, student_params=student, n_devices=M)
    fedavg_equiv = deepfed.fedavg_comm_bytes(student, rounds=10, clients_per_round=M)
    report = {
        "arch": args.arch,
        "clients": M,
        "single_member_nll": float(single_nll),
        "ensemble_nll": float(ens_nll),
        "student_nll": float(student_nll),
        "one_shot_comm_bytes": comm,
        "fedavg10_comm_bytes": fedavg_equiv,
        "comm_reduction_vs_fedavg10": fedavg_equiv["total"] / max(comm["upload"], 1.0),
    }
    stack.__exit__(None, None, None)
    if tracer is not None and tracer.export(args.trace):
        log.info("trace written to %s", args.trace)
    print(json.dumps(report, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    return report


if __name__ == "__main__":
    main()
