"""Pallas TPU kernel: fused ensemble scoring from int8 supports.

``ensemble_score`` (PR 1) fused Gram tile + coefficient reduction +
member mean so the serve path never materializes the (k, b, n_max)
Gram tensor. This is the same kernel for ensembles that arrived over
the wire as int8 (``repro.comm``'s per-column affine codec): supports
stay int8 in HBM — a quarter of the fp32 footprint — and each (bn, d)
tile is dequantized on the fly in VMEM (one VPU multiply-add against
the member's broadcast scale/zero rows) right before the MXU cross
matmul. Without this, a quantized ensemble would fall back to one
dispatch per member, losing both the fusion and the compression.

Layout: identical to ensemble_score.py — grid (nb, k, nn) with the
support-tile loop innermost, (bq, 1) accumulator resident in VMEM for
the whole k x nn reduction; the per-member affine params ride in as
(k, 1, d) arrays read one (1, d) row per member step, coefs as
(k, 1, n) and gammas whole in SMEM, so every block keeps the TPU's
(8, 128) rule. Zero-padded int8 support
rows dequantize to the member's zero-point vector (NOT 0), but their
zero coefficients annihilate them in the coef matvec, so padding is
still free.

Dispatch policy (TPU vs. CPU oracle, REPRO_PALLAS_INTERPRET) is
documented once in ``repro/serve/__init__.py``; ``kernels/ops.py``
routes accordingly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_B = 128
DEFAULT_BLOCK_N = 128


def _ensemble_score_q8_kernel(x_ref, q_ref, scale_ref, zero_ref, coef_ref,
                              gamma_ref, o_ref, acc_scr,
                              *, inv_k: float, k: int, nn: int):
    t = pl.program_id(1)  # member index
    j = pl.program_id(2)  # support tile index

    @pl.when((t == 0) & (j == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)        # (bq, d)
    q = q_ref[0].astype(jnp.float32)          # (bn, d) int8 -> fp32 on the VPU
    s = q * scale_ref[0] + zero_ref[0]        # member-t dequant in VMEM
    c = coef_ref[0].astype(jnp.float32)       # (1, bn)
    g = gamma_ref[t]                          # member-t bandwidth (SMEM)

    x2 = jnp.sum(x * x, axis=1)[:, None]      # VPU
    s2 = jnp.sum(s * s, axis=1)[None, :]
    cross = jax.lax.dot_general(              # MXU: (bq, d) x (bn, d)^T
        x, s, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    d2 = jnp.maximum(x2 + s2 - 2.0 * cross, 0.0)
    part = jax.lax.dot_general(               # (bq, bn) x (1, bn)^T
        jnp.exp(-g * d2), c,
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    acc_scr[...] += part * inv_k

    @pl.when((t == k - 1) & (j == nn - 1))
    def _finalize():
        o_ref[...] = acc_scr[...]


def ensemble_score_q8_pallas(
    x, q, scale, zero, coef, gammas, *,
    block_b: int = DEFAULT_BLOCK_B, block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
):
    """Fused mean-of-member scores from int8-quantized supports.

    x: (b, d) fp32 queries; q: (k, n_max, d) int8 supports; scale, zero:
    (k, d) per-member per-column affine params; coef: (k, n_max) fp32
    (zero on padding); gammas: (k,). Returns (b,) fp32 scores.
    """
    b, d = x.shape
    k, n_max, _ = q.shape
    bq = min(block_b, max(-(-b // 8) * 8, 8))
    bn = min(block_n, max(-(-n_max // 8) * 8, 8))
    nb = -(-b // bq)
    nn = -(-n_max // bn)
    xp = jnp.pad(x.astype(jnp.float32), ((0, nb * bq - b), (0, 0)))
    qp = jnp.pad(q.astype(jnp.int8), ((0, 0), (0, nn * bn - n_max), (0, 0)))
    coefp = jnp.pad(coef.astype(jnp.float32),
                    ((0, 0), (0, nn * bn - n_max))).reshape(k, 1, nn * bn)
    sc = scale.astype(jnp.float32).reshape(k, 1, d)
    ze = zero.astype(jnp.float32).reshape(k, 1, d)
    gam = gammas.astype(jnp.float32).reshape(k)

    kernel = functools.partial(
        _ensemble_score_q8_kernel, inv_k=1.0 / float(k), k=k, nn=nn
    )
    out = pl.pallas_call(
        kernel,
        grid=(nb, k, nn),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, t, j: (i, 0)),
            pl.BlockSpec((1, bn, d), lambda i, t, j: (t, j, 0)),
            pl.BlockSpec((1, 1, d), lambda i, t, j: (t, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i, t, j: (t, 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda i, t, j: (t, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bq, 1), lambda i, t, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * bq, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32)],
        interpret=interpret,
    )(xp, qp, sc, ze, coefp, gam)
    return out[:b, 0]
