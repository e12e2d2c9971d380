"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests).

Every contraction runs at ``HIGHEST`` precision, so an oracle is a plain
float32 reference on any backend (see ``repro/serve/__init__.py``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e9
_F32 = jax.lax.Precision.HIGHEST


def rbf_gram_ref(x1, x2, gamma: float):
    """exp(-gamma ||x1_i - x2_j||^2). x1: (m, d), x2: (n, d) -> (m, n)."""
    x1 = x1.astype(jnp.float32)
    x2 = x2.astype(jnp.float32)
    sq1 = jnp.sum(x1 * x1, axis=1)[:, None]
    sq2 = jnp.sum(x2 * x2, axis=1)[None, :]
    cross = jnp.matmul(x1, x2.T, precision=_F32)
    d2 = jnp.maximum(sq1 + sq2 - 2.0 * cross, 0.0)
    return jnp.exp(-gamma * d2)


def batched_rbf_gram_ref(x1, x2, gammas):
    """Per-device Gram matrices with per-device bandwidths (oracle for
    batched_rbf_gram — this vmap IS the CPU fallback path).

    x1: (g, m, d); x2: (g, n, d); gammas: (g,). Returns (g, m, n).
    """
    return jax.vmap(rbf_gram_ref)(
        x1.astype(jnp.float32), x2.astype(jnp.float32), gammas.astype(jnp.float32)
    )


def gram_matvec_ref(x1, x2, v, gamma: float, row_chunk: int = 1024):
    """``K(x1, x2; gamma) @ v`` (oracle for gram_matvec) — row-chunked so
    the full (m, n) Gram never materializes on the CPU path either; the
    peak live tile is (row_chunk, n).

    x1: (m, d); x2: (n, d); v: (n,). Returns (m,).
    """
    m, d = x1.shape
    chunk = min(row_chunk, max(m, 1))
    mp = -(-m // chunk) * chunk
    x1p = jnp.pad(x1.astype(jnp.float32), ((0, mp - m), (0, 0)))
    x2 = x2.astype(jnp.float32)
    v = v.astype(jnp.float32)
    out = jax.lax.map(
        lambda c: jnp.dot(rbf_gram_ref(c, x2, gamma), v, precision=_F32),
        x1p.reshape(mp // chunk, chunk, d),
    )
    return out.reshape(-1)[:m]


def rbf_gram_q8_ref(x, q, scale, zero, gamma: float):
    """Gram between fp32 queries and int8 affine-quantized supports
    (oracle for rbf_gram_q8): dequantize, then the fp32 Gram.

    x: (m, d) fp32; q: (n, d) int8; scale, zero: (d,) per-column affine
    parameters. Returns (m, n).
    """
    s = q.astype(jnp.float32) * scale.astype(jnp.float32)[None, :] + zero.astype(
        jnp.float32
    )[None, :]
    return rbf_gram_ref(x, s, gamma)


def ensemble_score_ref(x, sup, coef, gammas):
    """Mean of member RBF-SVM decision scores (oracle for ensemble_score).

    x: (b, d); sup: (k, n_max, d); coef: (k, n_max); gammas: (k,).
    Returns (b,). Zero-padded support rows contribute nothing because
    their coefficients are zero.
    """
    x = x.astype(jnp.float32)

    def member_scores(s, c, g):
        return jnp.dot(rbf_gram_ref(x, s, g), c, precision=_F32)

    scores = jax.vmap(member_scores)(
        sup.astype(jnp.float32), coef.astype(jnp.float32), gammas.astype(jnp.float32)
    )  # (k, b)
    return jnp.mean(scores, axis=0)


def ensemble_score_q8_ref(x, q, scale, zero, coef, gammas):
    """Mean of member scores from int8 affine-quantized supports
    (oracle for ensemble_score_q8): dequantize per member, then the
    fp32 ensemble oracle.

    x: (b, d); q: (k, n_max, d) int8; scale, zero: (k, d); coef:
    (k, n_max); gammas: (k,). Returns (b,).
    """
    sup = (
        q.astype(jnp.float32) * scale.astype(jnp.float32)[:, None, :]
        + zero.astype(jnp.float32)[:, None, :]
    )
    return ensemble_score_ref(x, sup, coef, gammas)


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """Dense GQA attention oracle.

    q: (B, Sq, H, hd); k, v: (B, Skv, K, hd). Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    rep = H // K
    qg = q.reshape(B, Sq, K, rep, hd)
    logits = jnp.einsum("bskrh,btkh->bkrst", qg, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkrst,btkh->bskrh", probs, v)
    return out.reshape(B, Sq, H, hd)
