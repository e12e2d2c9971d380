"""Serving driver: batched prefill + greedy decode against the KV cache.

Requests (one prompt per synthetic client) flow through the
``repro.serve.MicroBatchScheduler``: prompts are submitted
individually, assembled into one bucket-padded batch, scored with a
single prefill + greedy-decode pipeline, and de-multiplexed back in
submission order — the same control plane the SVM-ensemble path uses
(see the ``repro.serve`` package docstring, including the kernel
dispatch policy the model's flash-attention path follows).

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-2.7b \
      --reduced --batch 4 --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import stopwatch

from repro.configs import get_config
from repro.data import make_federated_lm_data
from repro.launch.compile_cache import use_compile_cache
from repro.models import (
    ShardCtx,
    init_cache,
    init_params,
    make_decode_step,
    make_prefill_step,
)
from repro.serve import MicroBatchScheduler, ServeConfig
from repro.utils.logging import get_logger

log = get_logger("serve")


def make_lm_score_fn(cfg, params, prefill, decode, gen: int):
    """Scheduler score_fn: (bucket, prompt_len) tokens -> (bucket, gen).

    Runs batched prefill then greedy decode; padded (all-zero) prompt
    rows decode garbage that the scheduler discards.
    """

    def score_fn(prompts: np.ndarray) -> np.ndarray:
        bucket, prompt_len = prompts.shape
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if cfg.n_patches:
            batch["patches"] = jnp.zeros((bucket, cfg.n_patches, cfg.d_model), jnp.float32)
        if cfg.is_encdec:
            batch["frames"] = jnp.zeros((bucket, cfg.encoder_seq, cfg.d_model), jnp.float32)
        cache = init_cache(cfg, bucket, kv_len=prompt_len + gen + 1)
        elapsed = stopwatch()
        logits, cache = prefill(params, batch, cache)
        log.info("prefill %d x %d tokens in %.2fs", bucket, prompt_len, elapsed())
        out = []
        tok = jnp.argmax(logits, axis=-1)[:, None]
        elapsed = stopwatch()
        for _ in range(gen):
            out.append(np.asarray(tok)[:, 0])
            logits, cache = decode(params, tok, cache)
            tok = jnp.argmax(logits, axis=-1)[:, None]
        dt = elapsed()
        log.info("decoded %d tokens/seq in %.2fs (%.1f tok/s total)", gen, dt, bucket * gen / dt)
        return np.stack(out, axis=1)  # (bucket, gen)

    return score_fn


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(max_decode_len=args.prompt_len + args.gen + 1)
    ctx = ShardCtx()
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    prefill = jax.jit(make_prefill_step(cfg, ctx))
    decode = jax.jit(make_decode_step(cfg, ctx))

    # requests: prompts from distinct synthetic clients, batched by the
    # scheduler (one bucket == the serving batch; no partial batches here)
    clients = make_federated_lm_data(args.batch, cfg.vocab, args.prompt_len + 8, seed=args.seed)
    prompts = np.stack([c[: args.prompt_len] for c in clients]).astype(np.int32)

    score_fn = make_lm_score_fn(cfg, params, prefill, decode, args.gen)
    sched = MicroBatchScheduler(
        score_fn,
        ServeConfig(max_batch=args.batch, max_queue=4 * args.batch, buckets=(args.batch,)),
    )
    gen = sched.run(list(prompts))
    log.info(
        "served %d requests in %d scoring batch(es), %d padded rows",
        sched.stats.submitted, sched.stats.batches, sched.stats.padded_rows,
    )
    for b in range(min(args.batch, 2)):
        print(f"req{b}: prompt={prompts[b, -8:].tolist()} -> gen={gen[b, :16].tolist()}")
    return gen


if __name__ == "__main__":
    main()
