"""Pallas TPU kernel: fused ensemble scoring (the serve hot path).

The paper's global model is F_k(x) = mean_t f_t(x) with each member an
RBF dual SVM: f_t(x) = sum_j coef_tj exp(-gamma_t ||x - s_tj||^2). The
naive serving path materializes the full (k, batch, n_max) Gram tensor
in HBM before reducing it twice (over supports, then members). This
kernel fuses all three stages — Gram tile, per-member coefficient
reduction, and the member mean — into one tiled pass so nothing bigger
than a (bq, bn) tile ever exists.

Layout decisions (same playbook as flash_attention.py):
  * grid = (nb, k, nn) with the support-tile loop as the *innermost*
    grid dim and the member loop next, so the (bq, 1) score accumulator
    stays resident in VMEM scratch for the whole k x nn reduction
    (sequential grid semantics on TPU make this safe);
  * the dominant term of ||x - s||^2 is the x @ s^T cross matmul, which
    runs on the MXU; squared norms, the exp epilogue, and the coef
    matvec run on the VPU while the tile is resident;
  * per-member gammas ride in whole in SMEM as a (k,) array, read one
    scalar per member step; coefs ride in as (k, 1, n) so each (1, bn)
    tile keeps the TPU's (8, 128) block rule (each of a block's last
    two dims is a multiple of 8 / 128 or the array's own); zero-padded
    support rows are annihilated by their zero coefficients, and padded
    query rows are sliced off on return.

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


def _ensemble_score_kernel(x_ref, sup_ref, coef_ref, gamma_ref, o_ref, acc_scr,
                           *, inv_k: float, k: int, nn: int):
    t = pl.program_id(1)  # member index
    j = pl.program_id(2)  # support tile index

    @pl.when((t == 0) & (j == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)        # (bq, d)
    s = sup_ref[0].astype(jnp.float32)        # (bn, d)
    c = coef_ref[0].astype(jnp.float32)       # (1, bn)
    g = gamma_ref[t]                          # member-t bandwidth (SMEM)

    x2 = jnp.sum(x * x, axis=1)[:, None]      # VPU
    s2 = jnp.sum(s * s, axis=1)[None, :]
    cross = jax.lax.dot_general(              # MXU: (bq, d) x (bn, d)^T
        x, s, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    d2 = jnp.maximum(x2 + s2 - 2.0 * cross, 0.0)
    # fused epilogue: exp + coef reduction while the tile is in VMEM.
    # zero-padded support rows contribute exp(..) * 0 via their coef.
    part = jax.lax.dot_general(               # (bq, bn) x (1, bn)^T
        jnp.exp(-g * d2), c,
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    acc_scr[...] += part * inv_k

    @pl.when((t == k - 1) & (j == nn - 1))
    def _finalize():
        o_ref[...] = acc_scr[...]


def ensemble_score_pallas(
    x, sup, coef, gammas, *,
    block_b: int = DEFAULT_BLOCK_B, block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
):
    """Fused mean-of-member RBF-SVM scores.

    x: (b, d) queries; sup: (k, n_max, d) padded supports;
    coef: (k, n_max) padded dual coefs (zero on padding);
    gammas: (k,) per-member bandwidths. Returns (b,) fp32 scores.
    """
    b, d = x.shape
    k, n_max, _ = sup.shape
    bq = min(block_b, max(-(-b // 8) * 8, 8))
    bn = min(block_n, max(-(-n_max // 8) * 8, 8))
    nb = -(-b // bq)
    nn = -(-n_max // bn)
    xp = jnp.pad(x.astype(jnp.float32), ((0, nb * bq - b), (0, 0)))
    supp = jnp.pad(sup.astype(jnp.float32), ((0, 0), (0, nn * bn - n_max), (0, 0)))
    coefp = jnp.pad(coef.astype(jnp.float32),
                    ((0, 0), (0, nn * bn - n_max))).reshape(k, 1, nn * bn)
    gam = gammas.astype(jnp.float32).reshape(k)

    kernel = functools.partial(
        _ensemble_score_kernel, inv_k=1.0 / float(k), k=k, nn=nn
    )
    out = pl.pallas_call(
        kernel,
        grid=(nb, k, nn),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, t, j: (i, 0)),
            pl.BlockSpec((1, bn, d), lambda i, t, j: (t, j, 0)),
            pl.BlockSpec((1, 1, bn), lambda i, t, j: (t, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bq, 1), lambda i, t, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * bq, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32)],
        interpret=interpret,
    )(xp, supp, coefp, gam)
    return out[:b, 0]
