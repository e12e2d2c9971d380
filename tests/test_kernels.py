"""Pallas kernel validation: interpret-mode sweeps vs pure-jnp oracles.

The first section is the auto-discovered registry parity suite: it
walks ``kernels.ops.KERNEL_REGISTRY`` and checks every registered
kernel against its oracle, and — at COLLECTION time — cross-checks the
registry against every ``*_pallas`` function found in the package, so
a new kernel shipped without a registered oracle fails the run before
a single test executes. The hand-written sweeps below it stress each
kernel's ragged shapes and edge cases.
"""
import importlib
import pkgutil
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import KERNEL_REGISTRY
from repro.utils.seeds import derive_device_seed


def _discovered_pallas_kernels():
    """name -> module for every ``*_pallas`` callable in repro.kernels."""
    import repro.kernels as pkg

    found = {}
    for info in pkgutil.iter_modules(pkg.__path__):
        mod = importlib.import_module(f"repro.kernels.{info.name}")
        for attr in dir(mod):
            if attr.endswith("_pallas") and callable(getattr(mod, attr)):
                # count a kernel where it is DEFINED, not re-exported
                if getattr(mod, attr).__module__ == mod.__name__:
                    found[attr.removesuffix("_pallas")] = mod.__name__
    return found


def _registry_names():
    """The parametrization source — raises at collection if any Pallas
    kernel is missing from the registry (the 'shipped untested' gap)."""
    discovered = _discovered_pallas_kernels()
    missing = set(discovered) - set(KERNEL_REGISTRY)
    if missing:
        raise RuntimeError(
            f"Pallas kernels without a KERNEL_REGISTRY entry (add one in "
            f"kernels/ops.py with a ref.py oracle): "
            f"{sorted((k, discovered[k]) for k in missing)}"
        )
    stale = set(KERNEL_REGISTRY) - set(discovered)
    if stale:
        raise RuntimeError(f"KERNEL_REGISTRY entries with no *_pallas "
                           f"implementation: {sorted(stale)}")
    return sorted(KERNEL_REGISTRY)


@pytest.mark.parametrize("name", _registry_names())
def test_registry_kernel_matches_oracle(name):
    """Every registered kernel == its ref.py oracle in interpret mode."""
    spec = KERNEL_REGISTRY[name]
    args = spec.make_inputs(np.random.default_rng(zlib.crc32(name.encode())))
    out = spec.pallas_fn(*args, interpret=True)
    want = spec.ref_fn(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=spec.tol)
    assert out.shape == np.asarray(want).shape


@pytest.mark.parametrize("name", _registry_names())
def test_registry_shard_specs_preserve_dispatch(name):
    """The registry's sharded dispatch specs are sound: shard_map-ping
    the public dispatch over the sim mesh with `spec.shard_specs` gives
    the same answer as calling it directly (degenerate 1-shard mesh on
    CPU; the forced multi-device CI lane exercises real splits). The
    mesh is capped at 4 shards so the fixed-size fixture batch axes
    (4 / 40 rows) always divide it, whatever the host exposes."""
    from repro.launch.mesh import make_sim_mesh

    spec = KERNEL_REGISTRY[name]
    args = spec.make_inputs(np.random.default_rng(zlib.crc32(name.encode())))
    mesh = make_sim_mesh(4)
    in_specs, out_specs = spec.shard_specs(mesh)
    arrays = [a for a in args if hasattr(a, "shape")]
    statics = args[len(arrays):]  # trailing python scalars (gamma)
    fn = jax.shard_map(lambda *xs: spec.dispatch(*xs, *statics), mesh=mesh,
                   in_specs=in_specs[: len(arrays)], out_specs=out_specs)
    np.testing.assert_allclose(
        np.asarray(fn(*arrays)), np.asarray(spec.dispatch(*args)),
        atol=spec.tol,
    )
from repro.kernels.batched_gram import batched_rbf_gram_pallas
from repro.kernels.ensemble_score import ensemble_score_pallas
from repro.kernels.gram_matvec import gram_matvec_pallas
from repro.kernels.ensemble_score_q8 import ensemble_score_q8_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rbf_gram import rbf_gram_pallas
from repro.kernels.rbf_gram_q8 import rbf_gram_q8_pallas


@pytest.mark.parametrize("m,n,d", [(32, 32, 8), (50, 70, 16), (128, 128, 32), (200, 130, 4), (1, 300, 64)])
@pytest.mark.parametrize("gamma", [0.1, 1.0])
def test_rbf_gram_shapes(key, m, n, d, gamma):
    k1, k2 = jax.random.split(key)
    x1 = jax.random.normal(k1, (m, d))
    x2 = jax.random.normal(k2, (n, d))
    out = rbf_gram_pallas(x1, x2, gamma, block_m=64, block_n=64, interpret=True)
    want = ref.rbf_gram_ref(x1, x2, gamma)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    assert out.shape == (m, n)


@pytest.mark.parametrize(
    "m,n,d", [(32, 32, 8), (50, 70, 16), (128, 128, 32), (200, 130, 4), (1, 300, 64)]
)
@pytest.mark.parametrize("gamma", [0.1, 1.0])
def test_gram_matvec_sweep(key, m, n, d, gamma):
    """Streaming Gram matvec (distill CG hot path) vs dense-Gram matvec,
    ragged shapes: tiling + padded-v annihilation must be exact."""
    k1, k2, k3 = jax.random.split(key, 3)
    x1 = jax.random.normal(k1, (m, d))
    x2 = jax.random.normal(k2, (n, d))
    v = jax.random.normal(k3, (n,))
    out = gram_matvec_pallas(x1, x2, v, gamma, block_m=64, block_n=64, interpret=True)
    want = ref.rbf_gram_ref(x1, x2, gamma) @ v
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)
    assert out.shape == (m,)


def test_gram_matvec_ref_chunking_invariant(key):
    """The row-chunked CPU oracle is chunk-size independent (it never
    materializes the full Gram; chunking must not change numerics)."""
    k1, k2, k3 = jax.random.split(key, 3)
    x1 = jax.random.normal(k1, (130, 8))
    x2 = jax.random.normal(k2, (77, 8))
    v = jax.random.normal(k3, (77,))
    full = ref.gram_matvec_ref(x1, x2, v, 0.4, row_chunk=1024)
    chunked = ref.gram_matvec_ref(x1, x2, v, 0.4, row_chunk=32)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked), atol=1e-5)
    want = ref.rbf_gram_ref(x1, x2, 0.4) @ v
    np.testing.assert_allclose(np.asarray(full), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rbf_gram_dtypes(key, dtype):
    x1 = jax.random.normal(key, (64, 16)).astype(dtype)
    x2 = jax.random.normal(jax.random.fold_in(key, 1), (64, 16)).astype(dtype)
    out = rbf_gram_pallas(x1, x2, 0.5, interpret=True)
    want = ref.rbf_gram_ref(x1.astype(jnp.float32), x2.astype(jnp.float32), 0.5)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=tol)


def test_rbf_gram_properties(key):
    """K(X,X) symmetric PSD-ish with unit diagonal."""
    x = jax.random.normal(key, (40, 8))
    K = np.asarray(rbf_gram_pallas(x, x, 0.7, interpret=True))
    np.testing.assert_allclose(K, K.T, atol=1e-5)
    # diagonal ~1 up to catastrophic-cancellation noise in ||x||^2+||y||^2-2xy
    np.testing.assert_allclose(np.diag(K), 1.0, atol=1e-4)
    assert (K >= 0).all() and (K <= 1 + 1e-4).all()


@pytest.mark.parametrize(
    "m,n,d", [(16, 16, 4), (50, 70, 16), (128, 128, 8), (1, 300, 32), (200, 33, 5)]
)
@pytest.mark.parametrize("gamma", [0.1, 1.0])
def test_rbf_gram_q8_sweep(key, m, n, d, gamma):
    """int8 on-the-fly-dequant Gram kernel vs its oracle, ragged shapes."""
    rng = np.random.default_rng(derive_device_seed(m, n))
    x = jax.random.normal(key, (m, d))
    q = jnp.asarray(rng.integers(-127, 128, size=(n, d)), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.005, 0.1, size=d), jnp.float32)
    zero = jnp.asarray(rng.normal(0, 1, size=d), jnp.float32)
    out = rbf_gram_q8_pallas(x, q, scale, zero, gamma, block_m=64, block_n=64,
                             interpret=True)
    want = ref.rbf_gram_q8_ref(x, q, scale, zero, gamma)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    assert out.shape == (m, n)


def test_rbf_gram_q8_matches_fp32_kernel_on_dequantized(key):
    """q8 kernel == fp32 kernel fed the materialized dequantized supports
    (the no-fp32-copies claim is a layout change, not a numerics one)."""
    rng = np.random.default_rng(7)
    m, n, d = 40, 60, 12
    x = jax.random.normal(key, (m, d))
    q = rng.integers(-127, 128, size=(n, d)).astype(np.int8)
    scale = rng.uniform(0.01, 0.05, size=d).astype(np.float32)
    zero = rng.normal(0, 1, size=d).astype(np.float32)
    sup = q.astype(np.float32) * scale[None, :] + zero[None, :]
    out = rbf_gram_q8_pallas(x, jnp.asarray(q), jnp.asarray(scale),
                             jnp.asarray(zero), 0.4, interpret=True)
    want = rbf_gram_pallas(x, jnp.asarray(sup), 0.4, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize(
    "g,m,n,d", [(1, 16, 16, 4), (4, 64, 64, 16), (3, 50, 70, 8), (8, 128, 40, 32), (2, 1, 200, 24)]
)
def test_batched_rbf_gram_sweep(key, g, m, n, d):
    """Per-device Gram kernel vs the vmap'd oracle, ragged shapes."""
    ks = jax.random.split(key, 3)
    x1 = jax.random.normal(ks[0], (g, m, d))
    x2 = jax.random.normal(ks[1], (g, n, d))
    gammas = jax.random.uniform(ks[2], (g,), minval=0.05, maxval=2.0)
    out = batched_rbf_gram_pallas(x1, x2, gammas, block_m=64, block_n=64, interpret=True)
    want = ref.batched_rbf_gram_ref(x1, x2, gammas)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    assert out.shape == (g, m, n)


def test_batched_rbf_gram_matches_per_device_unbatched(key):
    """Each slice equals the unbatched kernel with that device's gamma."""
    g, m, n, d = 5, 40, 30, 8
    ks = jax.random.split(key, 3)
    x1 = jax.random.normal(ks[0], (g, m, d))
    x2 = jax.random.normal(ks[1], (g, n, d))
    gammas = jax.random.uniform(ks[2], (g,), minval=0.1, maxval=1.0)
    out = batched_rbf_gram_pallas(x1, x2, gammas, interpret=True)
    for t in range(g):
        want = ref.rbf_gram_ref(x1[t], x2[t], float(gammas[t]))
        np.testing.assert_allclose(np.asarray(out[t]), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize(
    "b,k,n_max,d", [(7, 1, 5, 3), (64, 8, 100, 16), (130, 5, 33, 4), (1, 12, 200, 64), (33, 3, 130, 8)]
)
def test_ensemble_score_sweep(key, b, k, n_max, d):
    """Fused serve kernel vs oracle, with ragged zero-padded supports."""
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (b, d))
    sup = jax.random.normal(ks[1], (k, n_max, d))
    coef = jax.random.normal(ks[2], (k, n_max))
    gammas = jax.random.uniform(ks[3], (k,), minval=0.1, maxval=2.0)
    # ragged members: zero out per-member tails as the packer does
    lengths = np.random.default_rng(0).integers(1, n_max + 1, size=k)
    mask = np.arange(n_max)[None, :] < lengths[:, None]
    sup = sup * mask[:, :, None]
    coef = coef * mask
    out = ensemble_score_pallas(x, sup, coef, gammas, block_b=64, block_n=64, interpret=True)
    want = ref.ensemble_score_ref(x, sup, coef, gammas)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)
    assert out.shape == (b,)


@pytest.mark.parametrize(
    "b,k,n_max,d", [(7, 1, 5, 3), (64, 4, 100, 16), (33, 3, 130, 8), (1, 6, 80, 24)]
)
def test_ensemble_score_q8_sweep(key, b, k, n_max, d):
    """Fused int8 serve kernel vs oracle, ragged zero-padded supports."""
    rng = np.random.default_rng(derive_device_seed(b, k))
    x = jax.random.normal(key, (b, d))
    q = jnp.asarray(rng.integers(-127, 128, size=(k, n_max, d)), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.005, 0.05, size=(k, d)), jnp.float32)
    zero = jnp.asarray(rng.normal(0, 1, size=(k, d)), jnp.float32)
    coef = jnp.asarray(rng.normal(size=(k, n_max)) / n_max, jnp.float32)
    gammas = jnp.asarray(rng.uniform(0.1, 1.0, size=k), jnp.float32)
    # ragged members: zero the per-member coef tails as the packer does
    lengths = rng.integers(1, n_max + 1, size=k)
    coef = coef * (np.arange(n_max)[None, :] < lengths[:, None])
    out = ensemble_score_q8_pallas(x, q, scale, zero, coef, gammas,
                                   block_b=64, block_n=64, interpret=True)
    want = ref.ensemble_score_q8_ref(x, q, scale, zero, coef, gammas)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)
    assert out.shape == (b,)


def test_ensemble_score_q8_matches_fp32_kernel_on_dequantized(key):
    """q8 ensemble kernel == fp32 ensemble kernel fed the materialized
    dequantized supports (layout change, not a numerics change)."""
    rng = np.random.default_rng(3)
    b, k, n_max, d = 40, 3, 50, 8
    x = jax.random.normal(key, (b, d))
    q = rng.integers(-127, 128, size=(k, n_max, d)).astype(np.int8)
    scale = rng.uniform(0.01, 0.04, size=(k, d)).astype(np.float32)
    zero = rng.normal(0, 1, size=(k, d)).astype(np.float32)
    coef = (rng.normal(size=(k, n_max)) / n_max).astype(np.float32)
    gammas = rng.uniform(0.2, 1.0, size=k).astype(np.float32)
    sup = q.astype(np.float32) * scale[:, None, :] + zero[:, None, :]
    out = ensemble_score_q8_pallas(x, jnp.asarray(q), jnp.asarray(scale),
                                   jnp.asarray(zero), jnp.asarray(coef),
                                   jnp.asarray(gammas), interpret=True)
    want = ensemble_score_pallas(x, jnp.asarray(sup), jnp.asarray(coef),
                                 jnp.asarray(gammas), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


def test_ensemble_score_matches_explicit_mean(key):
    """Fused result == mean over per-member padded-gram scores."""
    b, k, n_max, d = 40, 6, 50, 8
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (b, d))
    sup = jax.random.normal(ks[1], (k, n_max, d))
    coef = jax.random.normal(ks[2], (k, n_max))
    gammas = jax.random.uniform(ks[3], (k,), minval=0.2, maxval=1.0)
    out = ensemble_score_pallas(x, sup, coef, gammas, interpret=True)
    member = [ref.rbf_gram_ref(x, sup[t], float(gammas[t])) @ coef[t] for t in range(k)]
    want = jnp.stack(member).mean(axis=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize(
    "B,S,H,K,hd,window,causal",
    [
        (1, 128, 2, 1, 32, 0, True),
        (2, 100, 4, 2, 32, 0, True),   # GQA + padded seq
        (1, 200, 4, 4, 64, 48, True),  # sliding window
        (1, 128, 2, 2, 32, 0, False),  # non-causal (encoder)
        (2, 64, 8, 2, 16, 16, True),   # small window, high rep
    ],
)
def test_flash_attention_sweep(key, B, S, H, K, hd, window, causal):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, block_q=64, block_k=64, interpret=True
    )
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(key, dtype):
    B, S, H, hd = 1, 128, 2, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, H, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, H, hd)).astype(dtype)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=tol
    )
    assert out.dtype == dtype


def test_flash_attention_probability_conservation(key):
    """With v = ones, attention output must be exactly ones."""
    B, S, H, hd = 1, 96, 2, 16
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hd))
    v = jnp.ones((B, S, H, hd))
    out = flash_attention_pallas(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), 1.0, atol=1e-5)
