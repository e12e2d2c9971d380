"""Pallas TPU kernel: RBF Gram matrix (the paper's SVM compute hot spot).

TPU-native formulation: ||a-b||^2 = ||a||^2 + ||b||^2 - 2 a.b, so the
dominant term is a plain matmul that runs on the MXU; squared norms and
the exp epilogue run on the VPU while the (bm, bn) tile is still
resident in VMEM. Tiles are 128-aligned to match MXU systolic shape.

Grid: (M/bm, N/bn). The feature dim d streams whole into VMEM (SVM
feature dims here are <= a few hundred; for larger d add a k-loop).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK = 128


def _rbf_gram_kernel(x1_ref, x2_ref, o_ref, *, gamma: float):
    x1 = x1_ref[...].astype(jnp.float32)  # (bm, d)
    x2 = x2_ref[...].astype(jnp.float32)  # (bn, d)
    sq1 = jnp.sum(x1 * x1, axis=1)[:, None]  # VPU
    sq2 = jnp.sum(x2 * x2, axis=1)[None, :]
    cross = jax.lax.dot_general(  # MXU: (bm, d) x (bn, d)^T
        x1, x2, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    d2 = jnp.maximum(sq1 + sq2 - 2.0 * cross, 0.0)
    o_ref[...] = jnp.exp(-gamma * d2)  # fused epilogue in VMEM


def rbf_gram_pallas(
    x1, x2, gamma: float, *, block_m: int = DEFAULT_BLOCK, block_n: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """x1: (m, d), x2: (n, d) -> (m, n) fp32. Pads to tile multiples."""
    m, d = x1.shape
    n = x2.shape[0]
    mp = -(-m // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    x1p = jnp.pad(x1.astype(jnp.float32), ((0, mp - m), (0, 0)))
    x2p = jnp.pad(x2.astype(jnp.float32), ((0, np_ - n), (0, 0)))
    grid = (mp // block_m, np_ // block_n)
    out = pl.pallas_call(
        functools.partial(_rbf_gram_kernel, gamma=float(gamma)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(x1p, x2p)
    return out[:m, :n]
