"""Pallas TPU kernel: fused ensemble scoring (the serve hot path).

The paper's global model is F_k(x) = mean_t f_t(x) with each member an
RBF dual SVM: f_t(x) = sum_j coef_tj exp(-gamma_t ||x - s_tj||^2). The
naive serving path materializes the full (k, batch, n) Gram tensor in
HBM before reducing it twice (over supports, then members). This
kernel fuses all three stages — Gram tile, coefficient reduction, and
the member mean — into one tiled pass so nothing bigger than a
(bq, bn) tile ever exists.

Support layout (``core.ensemble.StackedEnsemble``): every member's
supports, concatenated in member order, cut into T row tiles of bn
rows: supports (T, bn, d), dual coefs (T, bn), and a per-row bandwidth
(T, bn) that repeats each member's gamma over its own rows. Only the
total is padded, up to a whole tile, with zero rows, coefs and gammas;
a member may straddle a tile boundary. The mean over members is a sum
over rows divided by k, so the grid walks the real supports only, not
k x n_max padded ones.

Layout decisions (same playbook as flash_attention.py):
  * grid = (nb, T) with the support-tile loop innermost, so the
    (bq, 1) score accumulator stays resident in VMEM scratch for the
    whole reduction (sequential grid semantics on TPU make this safe);
  * the dominant term of ||x - s||^2 is the x @ s^T cross matmul, which
    runs on the MXU; squared norms, the exp epilogue, and the coef
    matvec run on the VPU while the tile is resident;
  * coefs and gammas ride in as (T, 1, bn) so each (1, bn) row keeps
    the TPU's (8, 128) block rule (each of a block's last two dims is a
    multiple of 8 / 128 or the array's own); the gamma row broadcasts
    over the queries in the exp epilogue. Zero tail rows are
    annihilated by their zero coefficients, and padded query rows are
    sliced off on return.

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


def _ensemble_score_kernel(x_ref, sup_ref, coef_ref, gamma_ref, o_ref, acc_scr,
                           *, inv_k: float, tiles: int):
    j = pl.program_id(1)  # support tile index

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)        # (bq, d)
    s = sup_ref[0].astype(jnp.float32)        # (bn, d)
    c = coef_ref[0].astype(jnp.float32)       # (1, bn)
    g = gamma_ref[0].astype(jnp.float32)      # (1, bn) per-row bandwidth

    x2 = jnp.sum(x * x, axis=1)[:, None]      # VPU
    s2 = jnp.sum(s * s, axis=1)[None, :]
    cross = jax.lax.dot_general(              # MXU: (bq, d) x (bn, d)^T
        x, s, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    d2 = jnp.maximum(x2 + s2 - 2.0 * cross, 0.0)
    # fused epilogue: exp + coef reduction while the tile is in VMEM.
    # zero tail rows contribute exp(..) * 0 via their coef.
    part = jax.lax.dot_general(               # (bq, bn) x (1, bn)^T
        jnp.exp(-g * d2), c,
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    acc_scr[...] += part * inv_k

    @pl.when(j == tiles - 1)
    def _finalize():
        o_ref[...] = acc_scr[...]


def ensemble_score_pallas(x, sup, coef, gamma, k: int, *,
                          block_b: int = DEFAULT_BLOCK_B, interpret: bool = False):
    """Fused mean-of-member RBF-SVM scores over row-tiled supports.

    x: (b, d) queries; sup: (T, bn, d) support rows of all k members;
    coef: (T, bn) dual coefs and gamma: (T, bn) per-row bandwidths, both
    zero on the tail; k: the member count the row sum is divided by.
    Returns (b,) fp32 scores. Supports arrive tile-aligned; only the
    queries are padded.
    """
    b, d = x.shape
    tiles, bn, _ = sup.shape
    bq = min(block_b, max(-(-b // 8) * 8, 8))
    nb = -(-b // bq)
    xp = jnp.pad(x.astype(jnp.float32), ((0, nb * bq - b), (0, 0)))
    coef3 = coef.astype(jnp.float32).reshape(tiles, 1, bn)
    gamma3 = gamma.astype(jnp.float32).reshape(tiles, 1, bn)

    kernel = functools.partial(
        _ensemble_score_kernel, inv_k=1.0 / float(k), tiles=tiles
    )
    out = pl.pallas_call(
        kernel,
        grid=(nb, tiles),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn, d), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * bq, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32)],
        interpret=interpret,
    )(xp, sup.astype(jnp.float32), coef3, gamma3)
    return out[:b, 0]
