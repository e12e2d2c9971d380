"""Micro-benchmarks of the kernel hot-spots (CPU reference path; the
Pallas kernels target TPU and are validated in interpret mode by tests).
Derived column reports achieved GFLOP/s of the jnp reference."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref

from benchmarks.common import csv_row, timed_call


def run():
    rows = []
    key = jax.random.PRNGKey(0)
    # RBF Gram: the per-device SVM hot spot (paper-size: n<=460, d<=64)
    for (m, n, d) in [(256, 256, 32), (460, 460, 64), (1024, 1024, 64)]:
        x1 = jax.random.normal(key, (m, d))
        x2 = jax.random.normal(key, (n, d))
        f = jax.jit(lambda a, b: ref.rbf_gram_ref(a, b, 0.5))
        us = timed_call(f"rbf_gram.{m}x{n}x{d}", lambda: f(x1, x2))
        flops = 2 * m * n * d
        rows.append(csv_row(f"kernel.rbf_gram.{m}x{n}x{d}", f"{us:.1f}",
                            f"us_per_call; {flops / us / 1e3:.2f} GFLOP/s (jnp ref)"))
    # fused ensemble scoring: the serve-path hot spot (mean over k members)
    for (b, k, n, d) in [(1024, 8, 200, 32), (1024, 32, 200, 32)]:
        ks = jax.random.split(key, 4)
        x = jax.random.normal(ks[0], (b, d))
        tiles = -(-k * n // 128)  # k members of n rows in 128-row tiles
        sup = jax.random.normal(ks[1], (tiles, 128, d))
        coef = jax.random.normal(ks[2], (tiles, 128))
        gamma = jax.random.uniform(ks[3], (tiles, 128), minval=0.1, maxval=1.0)
        f = jax.jit(ref.ensemble_score_ref, static_argnames=("k",))
        us = timed_call(f"ensemble_score.b{b}k{k}n{n}d{d}",
                        lambda: f(x, sup, coef, gamma, k=k))
        flops = 2 * k * b * n * d
        rows.append(csv_row(f"kernel.ensemble_score.b{b}k{k}n{n}d{d}", f"{us:.1f}",
                            f"us_per_call; {flops / us / 1e3:.2f} GFLOP/s (jnp ref)"))
    # flash attention reference
    for (B, S, H, K, hd) in [(1, 512, 8, 2, 64), (2, 1024, 8, 8, 64)]:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
        f = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True))
        us = timed_call(f"attention.B{B}S{S}H{H}K{K}", lambda: f(q, k, v))
        flops = 4 * B * H * S * S * hd
        rows.append(csv_row(f"kernel.attention.B{B}S{S}H{H}K{K}", f"{us:.1f}",
                            f"us_per_call; {flops / us / 1e3:.2f} GFLOP/s (jnp ref)"))
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
