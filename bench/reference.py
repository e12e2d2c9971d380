"""The plain reference of one one-shot round, independent of the program.

It imports nothing of ``repro`` and takes nothing the program made: it
splits every device, trains every local RBF-SVM by cyclic SDCA on the
hinge-loss dual, scores the val and test splits, ranks the reports,
builds and evaluates every selected ensemble, the full ensemble and the
pooled ideal, and distills the best cell by conjugate gradient on the
kernel-ridge system, as the paper's round (arXiv:1902.11175, Sec. 3)
and the configuration describe it. Batching is by padding only: no
kernel, no tiling, no bucket policy of the program.

Every contraction goes through ``mm``. At ``"highest"`` it is float32
with full-precision products, what the configuration states. At
``"high"`` it is the three-pass bfloat16 product (hi*hi + hi*lo +
lo*hi of each operand split into two bfloat16 halves, accumulated in
float32), the precision one step below: that is the control, which the
comparison in ``check.py`` has to fail. The split is written out, so the
control reads the same on the TPU and on the CPU.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def _halves(a):
    # reduce_precision, not a round trip through bfloat16: XLA may drop a
    # convert pair as excess precision, which would turn three passes
    # into one on the TPU
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi, jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)


def mm(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` at the named precision."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=_HI)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    ah, al = _halves(a)
    bh, bl = _halves(b)
    return (jnp.einsum(spec, ah, bh, precision=_HI)
            + jnp.einsum(spec, ah, bl, precision=_HI)
            + jnp.einsum(spec, al, bh, precision=_HI))


# ----------------------------------------------------------------------
# seeds and splits (the protocol's definitions)
# ----------------------------------------------------------------------

def device_seed(seed: int, device_id: int) -> int:
    return int(np.random.SeedSequence(
        [seed % 2**64, device_id % 2**64]).generate_state(1)[0])


def split(x: np.ndarray, y: np.ndarray, seed: int, device_id: int) -> Dict[str, tuple]:
    """50/40/10 train/test/val; tiny devices borrow val from test."""
    n = len(y)
    perm = np.random.default_rng(device_seed(seed, device_id)).permutation(n)
    n_tr = max(int(round(0.5 * n)), 1)
    n_te = max(int(round(0.4 * n)), 1)
    tr, te, va = perm[:n_tr], perm[n_tr:n_tr + n_te], perm[n_tr + n_te:]
    if len(va) == 0:
        va, te = (te[-1:], te[:-1]) if len(te) > 1 else (te[:1], te)
    return {"n": n, "train": (x[tr], y[tr]), "test": (x[te], y[te]), "val": (x[va], y[va])}


def gamma_of(x: np.ndarray) -> float:
    """1 / (d * var(x)), the 'scale' bandwidth."""
    return 1.0 / (x.shape[1] * max(float(np.var(x.astype(np.float64))), 1e-8))


# ----------------------------------------------------------------------
# AUC: Mann-Whitney with midranks, for many groups at once
# ----------------------------------------------------------------------

def grouped_auc(group: np.ndarray, y: np.ndarray, s: np.ndarray, n_groups: int) -> np.ndarray:
    """AUC per group id in [0, n_groups); 0.5 where a group lacks a class."""
    group = np.asarray(group, np.int64)
    pos = np.asarray(y) > 0
    s = np.asarray(s, np.float64)
    order = np.lexsort((s, group))
    g, sv, pv = group[order], s[order], pos[order]
    m = len(g)
    idx = np.arange(m)
    gstart = np.searchsorted(g, g, side="left")
    # runs of equal (group, score): midrank = mean position in the run
    new = np.ones(m, bool)
    new[1:] = (g[1:] != g[:-1]) | (sv[1:] != sv[:-1])
    run_id = np.cumsum(new) - 1
    run_first = idx[new]
    run_last = np.append(run_first[1:], m) - 1
    rank = 0.5 * (run_first[run_id] + run_last[run_id]) - gstart + 1.0
    n_pos = np.bincount(g, weights=pv, minlength=n_groups)
    n_all = np.bincount(g, minlength=n_groups)
    n_neg = n_all - n_pos
    rsum = np.bincount(g, weights=rank * pv, minlength=n_groups)
    ok = (n_pos > 0) & (n_neg > 0)
    out = np.full(n_groups, 0.5)
    out[ok] = (rsum[ok] - n_pos[ok] * (n_pos[ok] + 1) / 2) / (n_pos[ok] * n_neg[ok])
    return out


def auc(y: np.ndarray, s: np.ndarray) -> float:
    return float(grouped_auc(np.zeros(len(y), np.int64), y, s, 1)[0])


# ----------------------------------------------------------------------
# RBF SVMs on the device, padded to one shape
# ----------------------------------------------------------------------

def _gram(xa, xb, gam, precision):
    """exp(-gam ||a - b||^2) for batched (g, m, d) x (g, n, d)."""
    sa = jnp.sum(xa * xa, axis=-1)[..., :, None]
    sb = jnp.sum(xb * xb, axis=-1)[..., None, :]
    cross = mm("gmd,gnd->gmn", xa, xb, precision)
    return jnp.exp(-gam[:, None, None] * jnp.maximum(sa + sb - 2.0 * cross, 0.0))


@partial(jax.jit, static_argnames=("epochs", "precision"))
def _fit(x, y, n, gam, lam, epochs, precision):
    """Cyclic SDCA on the hinge dual of every padded device at once.
    Coordinates at or past a device's n stay 0. Returns alpha (g, b)."""
    b = x.shape[1]
    valid = (jnp.arange(b)[None, :] < n[:, None]).astype(jnp.float32)
    K = _gram(x, x, gam, precision) * valid[:, :, None] * valid[:, None, :]
    Ky = K * y[:, None, :]
    nf = n.astype(jnp.float32)
    diag = jnp.diagonal(K, axis1=1, axis2=2)

    def coord(i, a):
        f = mm("gb,gb->g", Ky[:, i, :], a, precision) / (lam * nf)
        step = (1.0 - y[:, i] * f) * lam * nf / jnp.maximum(diag[:, i], 1e-8)
        new = jnp.where(i < n, jnp.clip(a[:, i] + step, 0.0, 1.0), 0.0)
        return a.at[:, i].set(new)

    def epoch(a, _):
        return jax.lax.fori_loop(0, b, coord, a), None

    a, _ = jax.lax.scan(epoch, jnp.zeros_like(y), None, length=epochs)
    return a


@partial(jax.jit, static_argnames=("precision",))
def _score_own(xq, sup, coef, gam, precision):
    """Each device's model on its own queries: (g, q, d) -> (g, q)."""
    return mm("gqb,gb->gq", _gram(xq, sup, gam, precision), coef, precision)


def _pad_stack(arrs: Sequence[np.ndarray], rows: int, fill: float = 0.0) -> np.ndarray:
    out = np.full((len(arrs), rows) + arrs[0].shape[1:], fill, np.float32)
    for i, a in enumerate(arrs):
        out[i, :len(a)] = a
    return out


def _ceil(n: int, q: int) -> int:
    return max(-(-n // q) * q, q)


@dataclasses.dataclass
class LocalModels:
    """Every device's local phase."""

    eligible: np.ndarray               # (m,) bool
    n_train: np.ndarray                # (m,) int
    coef: List[Optional[np.ndarray]]   # (n_train,) float32 per eligible device
    gamma: np.ndarray                  # (m,) float64 (0 where not eligible)
    val_auc: np.ndarray                # (m,) 0.5 where not eligible
    local_auc: np.ndarray              # (m,) own model on own test split
    scores: Dict[int, np.ndarray]      # eligible device -> own val then test scores


def train_local(splits: Sequence[Dict[str, tuple]], min_samples: int, lam: float,
                epochs: int, precision: str) -> LocalModels:
    m = len(splits)
    n_tr = np.array([len(s["train"][1]) for s in splits])
    n_all = np.array([s["n"] for s in splits])
    both = np.array([len(np.unique(s["train"][1])) == 2 for s in splits])
    elig = (n_all >= min_samples) & both
    coef: List[Optional[np.ndarray]] = [None] * m
    gamma = np.zeros(m)
    val_auc = np.full(m, 0.5)
    ids = np.flatnonzero(elig)
    if len(ids):
        b = _ceil(int(n_tr[ids].max()), 64)
        qv = _ceil(max(len(splits[i]["val"][1]) for i in ids), 8)
        qt = _ceil(max(len(splits[i]["test"][1]) for i in ids), 8)
        per = max(1, 2**27 // (b * max(b, qt)))      # devices per padded batch
        per = min(per, len(ids))
        val_s: Dict[int, np.ndarray] = {}
        test_s: Dict[int, np.ndarray] = {}
        for lo in range(0, len(ids), per):
            chunk = ids[lo:lo + per]
            pad = per - len(chunk)
            sel = list(chunk) + [chunk[0]] * pad          # repeat to one shape
            xs = _pad_stack([splits[i]["train"][0] for i in sel], b)
            ys = _pad_stack([splits[i]["train"][1] for i in sel], b, fill=1.0)
            ns = np.array([n_tr[i] for i in sel], np.int32)
            gs = np.array([gamma_of(splits[i]["train"][0]) for i in sel], np.float32)
            alpha = np.asarray(_fit(xs, ys, ns, gs, float(lam), epochs, precision))
            y0 = np.where(np.arange(b)[None, :] < ns[:, None], ys, 0.0)
            c = (alpha * y0 / (lam * ns[:, None])).astype(np.float32)
            sv = np.asarray(_score_own(_pad_stack([splits[i]["val"][0] for i in sel], qv),
                                       xs, c, gs, precision))
            st = np.asarray(_score_own(_pad_stack([splits[i]["test"][0] for i in sel], qt),
                                       xs, c, gs, precision))
            for j, i in enumerate(chunk):
                coef[i] = c[j, :n_tr[i]]
                gamma[i] = gamma_of(splits[i]["train"][0])
                val_s[i] = sv[j, :len(splits[i]["val"][1])]
                test_s[i] = st[j, :len(splits[i]["test"][1])]
        for i in ids:
            val_auc[i] = auc(splits[i]["val"][1], val_s[i])
    # own test scores; constant models (every non-eligible device) tie
    groups, ys, ss = [], [], []
    for i, s in enumerate(splits):
        yt = s["test"][1]
        groups.append(np.full(len(yt), i))
        ys.append(yt)
        ss.append(test_s[i] if elig[i] else np.zeros(len(yt)))
    local = grouped_auc(np.concatenate(groups), np.concatenate(ys), np.concatenate(ss), m)
    scores = {int(i): np.concatenate([val_s[i], test_s[i]]) for i in ids}
    return LocalModels(elig, n_tr, coef, gamma, val_auc, local, scores)


# ----------------------------------------------------------------------
# ensembles: the mean of member scores, over flattened supports
# ----------------------------------------------------------------------

_ROWS, _COLS = 2048, 8192


@partial(jax.jit, static_argnames=("precision",))
def _ens_block(xq, sup, w, gam, precision):
    """sum_j w_j exp(-gam_j ||x - s_j||^2) for one row block; the
    support axis is scanned in (blocks, _COLS) tiles."""
    sq = jnp.sum(xq * xq, axis=1)[:, None]

    def tile(acc, t):
        s, wt, gt = t
        d2 = jnp.maximum(sq + jnp.sum(s * s, axis=1)[None, :]
                         - 2.0 * mm("rd,cd->rc", xq, s, precision), 0.0)
        return acc + mm("rc,c->r", jnp.exp(-gt[None, :] * d2), wt, precision), None

    out, _ = jax.lax.scan(tile, jnp.zeros(xq.shape[0], jnp.float32), (sup, w, gam))
    return out


def ensemble_scores(x: np.ndarray, members: Sequence[Tuple[np.ndarray, np.ndarray, float]],
                    precision: str) -> np.ndarray:
    """Mean member score of (support, coef, gamma) members on rows x."""
    k = len(members)
    sup = np.concatenate([m[0] for m in members]).astype(np.float32)
    w = np.concatenate([m[1] / k for m in members]).astype(np.float32)
    gam = np.concatenate([np.full(len(m[1]), m[2]) for m in members]).astype(np.float32)
    tiles = 1 << (-(-len(w) // _COLS) - 1).bit_length()   # few distinct shapes
    nc = tiles * _COLS
    sup = np.pad(sup, ((0, nc - len(w)), (0, 0))).reshape(tiles, _COLS, -1)
    w = np.pad(w, (0, nc - len(w))).reshape(tiles, _COLS)
    gam = np.pad(gam, (0, nc - len(gam))).reshape(tiles, _COLS)
    sup, w, gam = jnp.asarray(sup), jnp.asarray(w), jnp.asarray(gam)
    outs = []
    for lo in range(0, len(x), _ROWS):
        blk = x[lo:lo + _ROWS]
        xb = np.pad(blk, ((0, _ROWS - len(blk)), (0, 0))).astype(np.float32)
        outs.append(_ens_block(xb, sup, w, gam, precision))
    return np.concatenate([np.asarray(o) for o in outs])[:len(x)]


# ----------------------------------------------------------------------
# kernel-ridge distillation by conjugate gradient
# ----------------------------------------------------------------------

@partial(jax.jit, static_argnames=("precision",))
def _gram_square(x, gam, precision):
    return _gram(x[None], x[None], gam, precision)[0]


@partial(jax.jit, static_argnames=("maxiter", "precision"))
def _cg(K, b, eps, tol, maxiter, precision):
    def dot(u, v):
        return mm("i,i->", u, v, precision)

    def mv(v):
        return mm("ij,j->i", K, v, precision) + eps * v

    stop2 = tol * tol * jnp.maximum(dot(b, b), 1e-30)

    def cond(st):
        return (st[0] < maxiter) & (st[4] > stop2)

    def body(st):
        k, x, r, p, rs = st
        ap = mv(p)
        a = rs / jnp.maximum(dot(p, ap), 1e-30)
        x, r = x + a * p, r - a * ap
        rs_new = dot(r, r)
        return k + 1, x, r, r + rs_new / jnp.maximum(rs, 1e-30) * p, rs_new

    return jax.lax.while_loop(cond, body, (0, jnp.zeros_like(b), b, b, dot(b, b)))[1]


@jax.jit
def _residual(K, alpha, soft, eps):
    r = jnp.einsum("ij,j->i", K, alpha, precision=_HI) + eps * alpha - soft
    return jnp.sqrt(jnp.sum(r * r) / jnp.maximum(jnp.sum(soft * soft), 1e-30))


def kernel_ridge(x: np.ndarray, soft: np.ndarray, gamma: float, eps: float,
                 tol: float, maxiter: int, precision: str) -> np.ndarray:
    K = _gram_square(jnp.asarray(x), jnp.asarray([gamma], jnp.float32), precision)
    return np.asarray(_cg(K, jnp.asarray(soft, jnp.float32), eps, tol, maxiter, precision))


def ridge_residual(x: np.ndarray, alpha: np.ndarray, soft: np.ndarray, gamma: float,
                   eps: float) -> float:
    """||K alpha + eps alpha - soft|| / ||soft||, K at full precision."""
    K = _gram_square(jnp.asarray(x), jnp.asarray([gamma], jnp.float32), "highest")
    return float(_residual(K, jnp.asarray(alpha, jnp.float32),
                           jnp.asarray(soft, jnp.float32), eps))
