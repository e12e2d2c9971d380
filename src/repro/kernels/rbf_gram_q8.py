"""Pallas TPU kernel: RBF Gram tiles from int8-quantized supports.

The comm subsystem (``repro.comm.wire``) ships support vectors over the
wire as per-column affine int8: q[i, j] = round((x[i, j] - zero[j]) /
scale[j]). Scoring a quantized ensemble naively would dequantize every
member back to fp32 in HBM — 4x the memory the codec just saved. This
kernel keeps supports int8 end-to-end and dequantizes on the fly: each
(bn, d) support tile is expanded to fp32 *in VMEM* (one VPU
multiply-add against the broadcast per-column scale/zero rows) right
before the Gram math, so HBM only ever holds the int8 payload.

Layout (same playbook as rbf_gram.py):
  * grid = (M/bm, N/bn); each program owns one output tile;
  * dequant + squared norms + exp epilogue on the VPU; the dominant
    x @ s^T cross term on the MXU, all while the tile is resident;
  * scale/zero ride in as (1, d) rows broadcast to every program; the
    feature dim streams whole into VMEM (comm feature dims are tens to
    a few hundred).

Padding: callers pad q with zeros, which dequantize to the per-column
``zero`` point (NOT 0.0) — padded output rows/cols are garbage and are
sliced off on return, exactly as in the fp32 kernel.

Dispatch policy (TPU vs. CPU oracle, REPRO_PALLAS_INTERPRET) is
documented once in ``repro/serve/__init__.py``; ``kernels/ops.py``
routes accordingly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK = 128


def _rbf_gram_q8_kernel(x_ref, q_ref, scale_ref, zero_ref, o_ref, *, gamma: float):
    x = x_ref[...].astype(jnp.float32)        # (bm, d) fp32 queries
    q = q_ref[...].astype(jnp.float32)        # (bn, d) int8 -> fp32 on the VPU
    s = q * scale_ref[...] + zero_ref[...]    # on-the-fly dequant in VMEM
    sq1 = jnp.sum(x * x, axis=1)[:, None]     # VPU
    sq2 = jnp.sum(s * s, axis=1)[None, :]
    cross = jax.lax.dot_general(              # MXU: (bm, d) x (bn, d)^T
        x, s, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    d2 = jnp.maximum(sq1 + sq2 - 2.0 * cross, 0.0)
    o_ref[...] = jnp.exp(-gamma * d2)         # fused epilogue in VMEM


def rbf_gram_q8_pallas(
    x, q, scale, zero, gamma: float, *,
    block_m: int = DEFAULT_BLOCK, block_n: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """x: (m, d) fp32; q: (n, d) int8; scale, zero: (d,) per-column affine
    params. Returns (m, n) fp32 with out[i, j] =
    exp(-gamma ||x_i - (q_j * scale + zero)||^2). Pads to tile multiples.
    """
    m, d = x.shape
    n = q.shape[0]
    mp = -(-m // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    xp = jnp.pad(x.astype(jnp.float32), ((0, mp - m), (0, 0)))
    qp = jnp.pad(q.astype(jnp.int8), ((0, np_ - n), (0, 0)))
    sc = scale.astype(jnp.float32).reshape(1, d)
    ze = zero.astype(jnp.float32).reshape(1, d)
    grid = (mp // block_m, np_ // block_n)
    out = pl.pallas_call(
        functools.partial(_rbf_gram_q8_kernel, gamma=float(gamma)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(xp, qp, sc, ze)
    return out[:m, :n]
