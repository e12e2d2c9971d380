"""Training driver: real steps on real data (any arch, any mesh).

On this CPU container use ``--reduced`` (smoke-scale model, synthetic
federated LM tokens); on a TPU cluster drop the flag and pick a mesh.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --reduced --steps 100 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import stopwatch
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import SHAPES, get_config
from repro.data import make_federated_lm_data, token_batches
from repro.launch import specs as S
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models import ShardCtx, init_params, logical_axes, make_train_step
from repro.sharding.rules import ShardingRules, logical_to_spec
from repro.utils.logging import get_logger

log = get_logger("train")


def build_mesh(kind: str):
    if kind == "none":
        return None
    if kind == "debug":
        return make_debug_mesh()
    return make_production_mesh(multi_pod=(kind == "multi"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="none", choices=["none", "debug", "single", "multi"])
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = cfg.replace(remat=args.remat)
    mesh = build_mesh(args.mesh)
    rules = ShardingRules(fsdp=args.fsdp)
    ctx = ShardCtx(mesh=mesh, rules=rules)

    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    opt = S.make_optimizer(args.lr)
    opt_state = opt.init(params)
    step_fn = make_train_step(cfg, opt, ctx)
    if mesh is not None:
        la = logical_axes(cfg)
        psh = jax.tree.map(
            lambda p, l: NamedSharding(mesh, logical_to_spec(p.shape, l, mesh, rules)), params, la
        )
        osh = jax.tree.map(
            lambda p, l: NamedSharding(mesh, logical_to_spec(p.shape, l, mesh, rules)),
            opt_state,
            S.opt_state_logical(cfg),
        )
        params = jax.device_put(params, psh)
        opt_state = jax.device_put(opt_state, osh)
        step_fn = jax.jit(step_fn, in_shardings=(psh, osh, None), out_shardings=(psh, osh, None))
    else:
        step_fn = jax.jit(step_fn)

    # pooled synthetic federated LM data (per-client Markov sources)
    clients = make_federated_lm_data(8, cfg.vocab, 20_000, seed=args.seed)
    stream = token_batches(np.concatenate(clients), args.batch, args.seq, seed=args.seed)
    extra = {}
    if cfg.n_patches:
        extra["patches"] = jnp.zeros((args.batch, cfg.n_patches, cfg.d_model), jnp.float32)
    if cfg.is_encdec:
        extra["frames"] = jnp.zeros((args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)

    ckpt = CheckpointManager(args.ckpt) if args.ckpt else None
    elapsed = stopwatch()
    for step in range(args.steps):
        window = next(stream)
        batch = {"tokens": jnp.asarray(window[:, :-1]), "labels": jnp.asarray(window[:, 1:]), **extra}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            log.info(
                "step %4d  loss %.4f  ce %.4f  aux %.4f  (%.2f s/step)",
                step,
                float(metrics["loss"]),
                float(metrics["ce"]),
                float(metrics["aux"]),
                elapsed() / (step + 1),
            )
        if ckpt and (step + 1) % 50 == 0:
            ckpt.save(step + 1, {"params": params})
    if ckpt:
        ckpt.save(args.steps, {"params": params})
    print(f"final loss: {float(metrics['loss']):.4f}")
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
