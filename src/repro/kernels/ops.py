"""Jit'd public wrappers for the Pallas kernels.

Dispatch policy (canonically documented in ``repro/serve/__init__.py``):
on TPU backends call the Pallas kernels compiled; elsewhere (this CPU
container) call the pure-jnp oracle, unless ``REPRO_PALLAS_INTERPRET=1``
forces the kernels through interpret mode (used by the test suite to
validate kernel bodies on CPU).
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.gram_matvec import gram_matvec_pallas
from repro.kernels.rbf_gram import rbf_gram_pallas
from repro.kernels.rbf_gram_q8 import rbf_gram_q8_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ensemble_score import ensemble_score_pallas
from repro.kernels.ensemble_score_q8 import ensemble_score_q8_pallas
from repro.kernels.batched_gram import batched_rbf_gram_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _force_interpret() -> bool:
    return os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1"


@partial(jax.jit, static_argnames=("gamma",))
def _rbf_tpu(x1, x2, gamma):
    return rbf_gram_pallas(x1, x2, gamma)


@partial(jax.jit, static_argnames=("gamma",))
def _rbf_ref(x1, x2, gamma):
    return ref.rbf_gram_ref(x1, x2, gamma)


def rbf_gram(x1, x2, gamma: float):
    gamma = float(gamma)
    if _on_tpu():
        return _rbf_tpu(x1, x2, gamma)
    if _force_interpret():
        return rbf_gram_pallas(x1, x2, gamma, interpret=True)
    return _rbf_ref(x1, x2, gamma)


@partial(jax.jit, static_argnames=("gamma",))
def _gmv_tpu(x1, x2, v, gamma):
    return gram_matvec_pallas(x1, x2, v, gamma)


@partial(jax.jit, static_argnames=("gamma",))
def _gmv_ref(x1, x2, v, gamma):
    return ref.gram_matvec_ref(x1, x2, v, gamma)


def gram_matvec(x1, x2, v, gamma: float):
    """Streaming ``K(x1, x2; gamma) @ v`` (the distill CG hot path).

    x1: (m, d); x2: (n, d); v: (n,). Returns (m,) fp32. Neither path
    materializes the full (m, n) Gram: the Pallas kernel reduces each
    VMEM tile immediately, and the CPU oracle is row-chunked.
    """
    gamma = float(gamma)
    if _on_tpu():
        return _gmv_tpu(x1, x2, v, gamma)
    if _force_interpret():
        return gram_matvec_pallas(x1, x2, v, gamma, interpret=True)
    return _gmv_ref(x1, x2, v, gamma)


@partial(jax.jit, static_argnames=("gamma",))
def _q8_tpu(x, q, scale, zero, gamma):
    return rbf_gram_q8_pallas(x, q, scale, zero, gamma)


@partial(jax.jit, static_argnames=("gamma",))
def _q8_ref(x, q, scale, zero, gamma):
    return ref.rbf_gram_q8_ref(x, q, scale, zero, gamma)


def rbf_gram_q8(x, q, scale, zero, gamma: float):
    """Gram tiles straight from int8-quantized supports (the repro.comm
    quantized-scoring hot path).

    x: (m, d) fp32; q: (n, d) int8 per-column affine quantized supports;
    scale, zero: (d,) affine params. Returns (m, n) fp32. The Pallas
    path dequantizes tiles on the fly in VMEM — the fp32 support matrix
    never exists in HBM.
    """
    gamma = float(gamma)
    if _on_tpu():
        return _q8_tpu(x, q, scale, zero, gamma)
    if _force_interpret():
        return rbf_gram_q8_pallas(x, q, scale, zero, gamma, interpret=True)
    return _q8_ref(x, q, scale, zero, gamma)


@jax.jit
def _bgram_tpu(x1, x2, gammas):
    return batched_rbf_gram_pallas(x1, x2, gammas)


@jax.jit
def _bgram_ref(x1, x2, gammas):
    return ref.batched_rbf_gram_ref(x1, x2, gammas)


def batched_rbf_gram(x1, x2, gammas):
    """Per-device RBF Gram matrices (the repro.sim training hot path).

    x1: (g, m, d); x2: (g, n, d); gammas: (g,) per-device bandwidths.
    Returns (g, m, n) fp32. Off-TPU this is the vmap'd jnp oracle — the
    engine's vmap fallback. Callers mask padded rows/cols themselves.
    """
    if _on_tpu():
        return _bgram_tpu(x1, x2, gammas)
    if _force_interpret():
        return batched_rbf_gram_pallas(x1, x2, gammas, interpret=True)
    return _bgram_ref(x1, x2, gammas)


@partial(jax.jit, static_argnames=("causal", "window"))
def _flash_tpu(q, k, v, causal, window):
    return flash_attention_pallas(q, k, v, causal=causal, window=window)


@partial(jax.jit, static_argnames=("causal", "window"))
def _flash_ref(q, k, v, causal, window):
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0):
    if _on_tpu():
        return _flash_tpu(q, k, v, causal, window)
    if _force_interpret():
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      interpret=True)
    return _flash_ref(q, k, v, causal, window)


@partial(jax.jit, static_argnames=("k",))
def _ens_tpu(x, sup, coef, gamma, k):
    return ensemble_score_pallas(x, sup, coef, gamma, k)


@partial(jax.jit, static_argnames=("k",))
def _ens_ref(x, sup, coef, gamma, k):
    return ref.ensemble_score_ref(x, sup, coef, gamma, k)


def ensemble_score(x, sup, coef, gamma, k: int):
    """Fused mean-of-member RBF-SVM scoring (the repro.serve hot path).

    x: (b, d); sup: (T, bn, d) row tiles of every member's supports;
    coef, gamma: (T, bn) dual coefs and per-row bandwidths (zero on the
    tail); k: member count. Returns (b,) fp32. The Pallas path walks
    the real support tiles only and never materializes a Gram tensor
    in HBM.
    """
    k = int(k)
    if _on_tpu():
        return _ens_tpu(x, sup, coef, gamma, k)
    if _force_interpret():
        return ensemble_score_pallas(x, sup, coef, gamma, k, interpret=True)
    return _ens_ref(x, sup, coef, gamma, k)


@jax.jit
def _ens_q8_tpu(x, q, scale, zero, coef, gammas):
    return ensemble_score_q8_pallas(x, q, scale, zero, coef, gammas)


@jax.jit
def _ens_q8_ref(x, q, scale, zero, coef, gammas):
    return ref.ensemble_score_q8_ref(x, q, scale, zero, coef, gammas)


def ensemble_score_q8(x, q, scale, zero, coef, gammas):
    """Fused ensemble scoring straight from int8 wire payloads (the
    repro.comm quantized serve path).

    x: (b, d); q: (k, n_max, d) int8; scale, zero: (k, d) per-member
    affine params; coef: (k, n_max); gammas: (k,). Returns (b,) fp32.
    The Pallas path keeps supports int8 in HBM and dequantizes tiles on
    the fly in VMEM.
    """
    if _on_tpu():
        return _ens_q8_tpu(x, q, scale, zero, coef, gammas)
    if _force_interpret():
        return ensemble_score_q8_pallas(x, q, scale, zero, coef, gammas,
                                        interpret=True)
    return _ens_q8_ref(x, q, scale, zero, coef, gammas)


# ----------------------------------------------------------------------
# kernel registry: every Pallas kernel, its oracle, and its shard specs
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel: implementation + oracle + dispatch + specs.

    ``make_inputs(rng)`` builds a representative positional argument
    tuple accepted by BOTH ``pallas_fn`` (plus ``interpret=True``) and
    ``ref_fn`` — the auto-discovered parity suite in tests/test_kernels
    walks the registry and checks the pair on every entry, so a kernel
    cannot ship without an oracle (unregistered ``*_pallas`` functions
    fail test collection outright).

    ``shard_ranks`` are the sharded-dispatch specs: per argument, the
    rank whose LEADING axis is an independent batch dimension that may
    lay out along the sim mesh's ``devices`` axis (0 = replicate the
    argument). ``out_rank`` is the same for the output — feed both to
    ``sharding.rules.group_shard_specs`` to get the ``shard_map``
    boundary specs the sharded population engine uses.
    """

    name: str
    pallas_fn: Callable
    ref_fn: Callable
    dispatch: Callable
    make_inputs: Callable[[np.random.Generator], tuple]
    shard_ranks: Tuple[int, ...]
    out_rank: int
    tol: float = 1e-5

    def shard_specs(self, mesh):
        """(in_specs, out_specs) for shard_map over the sim mesh."""
        from repro.sharding.rules import group_shard_specs

        specs = group_shard_specs(mesh, self.shard_ranks + (self.out_rank,))
        return specs[:-1], specs[-1]


def _mk_rbf_gram(rng):
    return (rng.normal(size=(48, 12)).astype(np.float32),
            rng.normal(size=(40, 12)).astype(np.float32), 0.4)


def _mk_gram_matvec(rng):
    return (rng.normal(size=(48, 12)).astype(np.float32),
            rng.normal(size=(40, 12)).astype(np.float32),
            rng.normal(size=(40,)).astype(np.float32), 0.4)


def _mk_rbf_gram_q8(rng):
    return (rng.normal(size=(48, 12)).astype(np.float32),
            rng.integers(-127, 128, size=(40, 12)).astype(np.int8),
            rng.uniform(0.005, 0.1, size=12).astype(np.float32),
            rng.normal(size=12).astype(np.float32), 0.4)


def _mk_batched_rbf_gram(rng):
    return (rng.normal(size=(4, 48, 12)).astype(np.float32),
            rng.normal(size=(4, 40, 12)).astype(np.float32),
            rng.uniform(0.1, 1.0, size=4).astype(np.float32))


def _mk_flash_attention(rng):
    # batch of 4: divisible by every sim mesh the CI lanes force
    return tuple(rng.normal(size=(4, 64, 2, 16)).astype(np.float32)
                 for _ in range(3))


def _mk_ensemble_score(rng):
    # four members of 20, 9, 14 and 3 rows in 8-row tiles: members
    # straddle tiles, tiles mix gammas, and the tail is zero
    lengths = (20, 9, 14, 3)
    rows = sum(lengths)
    sup = np.zeros((6 * 8, 12), np.float32)
    coef = np.zeros(6 * 8, np.float32)
    sup[:rows] = rng.normal(size=(rows, 12))
    coef[:rows] = rng.normal(size=rows) / 16
    gamma = np.zeros(6 * 8, np.float32)
    gamma[:rows] = np.repeat(rng.uniform(0.1, 1.0, size=4), lengths)
    return (rng.normal(size=(40, 12)).astype(np.float32), sup.reshape(6, 8, 12),
            coef.reshape(6, 8), gamma.reshape(6, 8), len(lengths))


def _mk_ensemble_score_q8(rng):
    return (rng.normal(size=(40, 12)).astype(np.float32),
            rng.integers(-127, 128, size=(3, 48, 12)).astype(np.int8),
            rng.uniform(0.005, 0.05, size=(3, 12)).astype(np.float32),
            rng.normal(size=(3, 12)).astype(np.float32),
            (rng.normal(size=(3, 48)) / 48).astype(np.float32),
            rng.uniform(0.1, 1.0, size=3).astype(np.float32))


KERNEL_REGISTRY: Dict[str, KernelSpec] = {
    spec.name: spec
    for spec in (
        # rows of x1 are independent -> query-parallel over the mesh
        KernelSpec("rbf_gram", rbf_gram_pallas, ref.rbf_gram_ref, rbf_gram,
                   _mk_rbf_gram, shard_ranks=(2, 0, 0), out_rank=2),
        KernelSpec("gram_matvec", gram_matvec_pallas, ref.gram_matvec_ref,
                   gram_matvec, _mk_gram_matvec,
                   shard_ranks=(2, 0, 0, 0), out_rank=1),
        KernelSpec("rbf_gram_q8", rbf_gram_q8_pallas, ref.rbf_gram_q8_ref,
                   rbf_gram_q8, _mk_rbf_gram_q8,
                   shard_ranks=(2, 0, 0, 0, 0), out_rank=2),
        # leading axis is the per-device group -> the sharded engine's
        # data-parallel layout (sim mesh 'devices' axis)
        KernelSpec("batched_rbf_gram", batched_rbf_gram_pallas,
                   ref.batched_rbf_gram_ref, batched_rbf_gram,
                   _mk_batched_rbf_gram, shard_ranks=(3, 3, 1), out_rank=3),
        KernelSpec("flash_attention", flash_attention_pallas,
                   ref.flash_attention_ref, flash_attention,
                   _mk_flash_attention, shard_ranks=(4, 4, 4), out_rank=4,
                   tol=2e-5),
        # serve kernels: queries shard, the packed ensemble replicates
        KernelSpec("ensemble_score", ensemble_score_pallas,
                   ref.ensemble_score_ref, ensemble_score,
                   _mk_ensemble_score, shard_ranks=(2, 0, 0, 0), out_rank=1,
                   tol=1e-4),
        KernelSpec("ensemble_score_q8", ensemble_score_q8_pallas,
                   ref.ensemble_score_q8_ref, ensemble_score_q8,
                   _mk_ensemble_score_q8,
                   shard_ranks=(2, 0, 0, 0, 0, 0), out_rank=1, tol=1e-4),
    )
}
