"""Pallas TPU kernel: streaming RBF-Gram matvec (the distill CG hot path).

Computes ``K(x1, x2; gamma) @ v`` without ever materializing the
``(m, n)`` Gram matrix in HBM: the grid walks ``(m/bm, n/bn)`` tiles
with the support-tile loop innermost, each tile is built in VMEM (the
``rbf_gram`` formulation — cross matmul on the MXU, norms + exp
epilogue on the VPU), immediately reduced against its ``v`` slice, and
accumulated into a ``(bm, 1)`` VMEM-resident partial sum. HBM traffic
is O(m·d + n·d + n + m) per matvec instead of O(m·n).

This is the matvec inside the blocked conjugate-gradient kernel-ridge
solver (``repro.distill.solvers.cg``): the CG iteration re-streams the
Gram blocks every step, trading FLOPs for the O(l^2) memory the dense
distillation path would need.

Dispatch policy (TPU vs. CPU oracle, REPRO_PALLAS_INTERPRET) is
documented once in ``repro/serve/__init__.py``; ``kernels/ops.py``
routes accordingly. The CPU oracle (``ref.gram_matvec_ref``) is
row-chunked for the same reason — no full Gram on any backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 256


def _gram_matvec_kernel(x1_ref, x2_ref, v_ref, o_ref, acc_scr, *, gamma: float, nn: int):
    j = pl.program_id(1)  # support (x2) tile index — innermost

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x1 = x1_ref[...].astype(jnp.float32)  # (bm, d)
    x2 = x2_ref[...].astype(jnp.float32)  # (bn, d)
    v = v_ref[...].astype(jnp.float32)    # (bn, 1)

    sq1 = jnp.sum(x1 * x1, axis=1)[:, None]  # VPU
    sq2 = jnp.sum(x2 * x2, axis=1)[None, :]
    cross = jax.lax.dot_general(  # MXU: (bm, d) x (bn, d)^T
        x1, x2, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    d2 = jnp.maximum(sq1 + sq2 - 2.0 * cross, 0.0)
    # fused epilogue: exp + matvec slice while the tile is in VMEM.
    # zero-padded v rows annihilate padded x2 rows.
    part = jax.lax.dot_general(  # (bm, bn) x (bn, 1)
        jnp.exp(-gamma * d2), v,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    acc_scr[...] += part

    @pl.when(j == nn - 1)
    def _finalize():
        o_ref[...] = acc_scr[...]


def gram_matvec_pallas(
    x1, x2, v, gamma: float, *,
    block_m: int = DEFAULT_BLOCK_M, block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
):
    """``K(x1, x2; gamma) @ v`` streamed in tiles.

    x1: (m, d); x2: (n, d); v: (n,). Returns (m,) fp32. Pads every axis
    to tile multiples; padded v entries are zero so padded x2 rows
    contribute nothing.
    """
    m, d = x1.shape
    n = x2.shape[0]
    bm = min(block_m, max(-(-m // 8) * 8, 8))
    bn = min(block_n, max(-(-n // 8) * 8, 8))
    nm = -(-m // bm)
    nn = -(-n // bn)
    x1p = jnp.pad(x1.astype(jnp.float32), ((0, nm * bm - m), (0, 0)))
    x2p = jnp.pad(x2.astype(jnp.float32), ((0, nn * bn - n), (0, 0)))
    vp = jnp.pad(v.astype(jnp.float32), (0, nn * bn - n)).reshape(-1, 1)

    kernel = functools.partial(_gram_matvec_kernel, gamma=float(gamma), nn=nn)
    out = pl.pallas_call(
        kernel,
        grid=(nm, nn),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, 1), jnp.float32)],
        interpret=interpret,
    )(x1p, x2p, vp)
    return out[:m, 0]
