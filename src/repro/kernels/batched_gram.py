"""Pallas TPU kernel: batched RBF Gram matrices (the repro.sim hot path).

The population-scale simulation engine (``repro.sim.engine``) trains
hundreds-to-thousands of local RBF-SVMs in one vectorized pass: devices
are padded into size buckets and their Gram matrices are computed as one
batched call instead of one dispatch per device. Each device carries its
own bandwidth ``gamma`` (the sklearn 'scale' heuristic on its local
data), so unlike ``rbf_gram`` the bandwidth rides in as a (g,) array.

Layout (same playbook as rbf_gram.py / ensemble_score.py):
  * grid = (g, M/bm, N/bn) with the device index outermost — each
    (bm, bn) output tile is produced by exactly one program, so no
    scratch accumulator is needed;
  * the dominant term of ||x1 - x2||^2 is the x1 @ x2^T cross matmul on
    the MXU; squared norms and the exp epilogue run on the VPU while
    the tile is resident in VMEM;
  * per-device gammas ride in whole in SMEM as a (g,) array, read one
    scalar per device step; the feature dim streams whole into VMEM
    (sim feature dims are tens, not thousands).

The caller is responsible for masking: zero-padded rows of x1/x2 yield
exp(-gamma * ||x_pad||^2) != 0, exactly as in the unbatched kernel.
``repro.sim.engine`` masks Gram rows/cols beyond each device's real
sample count before the solve.

Dispatch policy (TPU vs. CPU vmap'd oracle, REPRO_PALLAS_INTERPRET) is
documented once in ``repro/serve/__init__.py``; ``kernels/ops.py``
routes accordingly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 128


def _batched_gram_kernel(x1_ref, x2_ref, gamma_ref, o_ref):
    x1 = x1_ref[0].astype(jnp.float32)  # (bm, d)
    x2 = x2_ref[0].astype(jnp.float32)  # (bn, d)
    g = gamma_ref[pl.program_id(0)]     # this device's bandwidth (SMEM)
    sq1 = jnp.sum(x1 * x1, axis=1)[:, None]  # VPU
    sq2 = jnp.sum(x2 * x2, axis=1)[None, :]
    cross = jax.lax.dot_general(  # MXU: (bm, d) x (bn, d)^T
        x1, x2, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    d2 = jnp.maximum(sq1 + sq2 - 2.0 * cross, 0.0)
    o_ref[0] = jnp.exp(-g * d2)  # fused epilogue in VMEM


def batched_rbf_gram_pallas(
    x1, x2, gammas, *,
    block_m: int = DEFAULT_BLOCK_M, block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
):
    """Per-device Gram matrices with per-device bandwidths.

    x1: (g, m, d); x2: (g, n, d); gammas: (g,). Returns (g, m, n) fp32
    with out[t] = exp(-gammas[t] ||x1[t,i] - x2[t,j]||^2).
    """
    g, m, d = x1.shape
    n = x2.shape[1]
    bm = min(block_m, max(-(-m // 8) * 8, 8))
    bn = min(block_n, max(-(-n // 8) * 8, 8))
    nm = -(-m // bm)
    nn = -(-n // bn)
    x1p = jnp.pad(x1.astype(jnp.float32), ((0, 0), (0, nm * bm - m), (0, 0)))
    x2p = jnp.pad(x2.astype(jnp.float32), ((0, 0), (0, nn * bn - n), (0, 0)))
    gam = gammas.astype(jnp.float32).reshape(g)

    out = pl.pallas_call(
        _batched_gram_kernel,
        grid=(g, nm, nn),
        in_specs=[
            pl.BlockSpec((1, bm, d), lambda t, i, j: (t, i, 0)),
            pl.BlockSpec((1, bn, d), lambda t, i, j: (t, j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda t, i, j: (t, i, j)),
        # under shard_map the Grams vary over the mesh axes the inputs do
        out_shape=jax.ShapeDtypeStruct(
            (g, nm * bm, nn * bn), jnp.float32,
            vma=jax.typeof(x1p).vma | jax.typeof(x2p).vma | jax.typeof(gam).vma),
        interpret=interpret,
    )(x1p, x2p, gam)
    return out[:, :m, :n]
