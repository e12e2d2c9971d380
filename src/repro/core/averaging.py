"""One-shot parameter averaging — the related-work baseline [8].

The paper argues naive averaging (a) degrades for m > sqrt(N) devices
and (b) is ill-defined for kernel SVMs (disparate dual variable sets) or
heterogeneous deep nets. Both halves are implemented here:

  * ``average_params`` — valid averaging for homogeneous pytrees
    (linear models, same-architecture nets); refuses mismatched trees,
    which IS the paper's infeasibility argument made executable.
  * ``LinearSVM`` + ``train_linear_svm`` — the primal linear model for
    which one-shot averaging [Zhang et al. 2012] is classically defined,
    used by the benchmarks to show ensembles beat averaging on non-IID
    federated splits.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def normalize_weights(weights: Sequence[float], n: Optional[int] = None) -> np.ndarray:
    """Validate member weights and project them onto the simplex.

    Weights must be finite and non-negative, and their sum must be
    bounded away from zero: a negative weight silently flips a member's
    contribution, and a zero/near-zero sum turns the normalizing divide
    into NaN/inf trees (the historic ``average_params`` failure mode —
    it divided blindly). ``fisher`` aggregation feeds empirical Fisher
    masses through here, where all-zero masses are a real input (empty
    validation splits), so the rejection is a ``ValueError`` callers
    can catch and map to a uniform fallback.
    """
    w = np.asarray(weights, np.float64)
    if w.ndim != 1 or (n is not None and len(w) != n):
        raise ValueError(
            f"expected {n if n is not None else 'a 1-D vector of'} weights, "
            f"got shape {w.shape}"
        )
    if len(w) == 0:
        raise ValueError("no weights to normalize")
    if not np.all(np.isfinite(w)):
        raise ValueError(f"weights must be finite, got {w}")
    if np.any(w < 0):
        raise ValueError(f"weights must be non-negative, got {w}")
    s = float(w.sum())
    if s <= 1e-30:
        raise ValueError(
            f"weight sum {s} is zero/near-zero; cannot normalize (all "
            "members carry no weight)"
        )
    return w / s


def average_params(trees: Sequence, weights: Optional[Sequence[float]] = None):
    """Weighted average of homogeneous pytrees (FedAvg-style one-shot).

    Weights are validated through ``normalize_weights``: negative
    weights and zero/near-zero weight sums raise instead of silently
    producing sign-flipped or NaN parameter trees.
    """
    if not trees:
        raise ValueError("no models to average")
    treedefs = {str(jax.tree.structure(t)) for t in trees}
    if len(treedefs) != 1:
        raise ValueError(
            "parameter averaging requires identical model structures; got "
            f"{len(treedefs)} distinct treedefs (the paper's infeasibility "
            "case for kernel SVMs / heterogeneous nets)"
        )
    shapes = [tuple(x.shape for x in jax.tree.leaves(t)) for t in trees]
    if len(set(shapes)) != 1:
        raise ValueError("parameter averaging requires identical leaf shapes")
    if weights is None:
        weights = [1.0 / len(trees)] * len(trees)
    w = normalize_weights(weights, len(trees))
    out = jax.tree.map(lambda x: x * w[0], trees[0])
    for wi, t in zip(w[1:], trees[1:]):
        out = jax.tree.map(lambda a, b, wi=wi: a + wi * b, out, t)
    return out


@dataclasses.dataclass
class LinearSVM:
    w: np.ndarray  # (d,)
    b: float

    def predict(self, x: np.ndarray, chunk: Optional[int] = None) -> np.ndarray:
        """Decision scores w.x + b. ``chunk`` is accepted (and ignored)
        so linear scorers are drop-in for the chunked ensemble predict
        signature — a dense matvec needs no chunking."""
        return x @ self.w + self.b

    @property
    def nbytes(self) -> int:
        # repro: allow[wire-cost-honesty] reason=in-memory model footprint property, not a wire price
        return self.w.nbytes + 8


@dataclasses.dataclass(frozen=True)
class StackedLinear:
    """Packed serve form of a ``LinearSVM`` — the linear mirror of
    ``core.ensemble.StackedEnsemble`` with the same ``score``/``k``/``d``
    surface, so feature-statistics aggregates (``repro.agg``) deploy
    through ``serve.EnsembleScorer`` and the fleet like any ensemble."""

    w: np.ndarray  # (d,) float32
    b: float

    @property
    def k(self) -> int:
        return 1

    @property
    def d(self) -> int:
        return int(self.w.shape[0])

    def score(self, x) -> np.ndarray:
        """Mean member score for one query block. x: (b, d) -> (b,)."""
        return np.asarray(x, np.float32) @ self.w + np.float32(self.b)

    def predict(self, x: np.ndarray, chunk: int = 4096) -> np.ndarray:
        from repro.core.ensemble import chunked_bucket_predict

        return chunked_bucket_predict(self.score, x, chunk)


@partial(jax.jit, static_argnames=("epochs",))
def _pegasos(x, y, n_real, lam: float, epochs: int, key):
    """Pegasos primal SGD for the linear hinge SVM (padded rows masked)."""
    n, d = x.shape

    def step(carry, t):
        w, b = carry
        i = jax.random.randint(jax.random.fold_in(key, t), (), 0, n_real)
        eta = 1.0 / (lam * (t + 1.0))
        margin = y[i] * (x[i] @ w + b)
        viol = margin < 1.0
        gw = lam * w - jnp.where(viol, y[i], 0.0) * x[i]
        gb = -jnp.where(viol, y[i], 0.0)
        return (w - eta * gw, b - eta * 0.01 * gb), None

    w0 = jnp.zeros(d, jnp.float32)
    (w, b), _ = jax.lax.scan(step, (w0, 0.0), jnp.arange(epochs * n, dtype=jnp.float32))
    return w, b


def train_linear_svm(x: np.ndarray, y: np.ndarray, lam: float = 0.01, epochs: int = 5, seed: int = 0) -> LinearSVM:
    n = len(y)
    bucket = max(-(-n // 64) * 64, 64)
    xp = np.zeros((bucket, x.shape[1]), np.float32)
    xp[:n] = x
    yp = np.ones(bucket, np.float32)
    yp[:n] = y
    w, b = _pegasos(jnp.asarray(xp), jnp.asarray(yp), n, lam, epochs, jax.random.PRNGKey(seed))
    return LinearSVM(w=np.asarray(w), b=float(b))


def one_shot_average_linear(models: Sequence[LinearSVM], weights: Optional[Sequence[float]] = None) -> LinearSVM:
    trees = [{"w": jnp.asarray(m.w), "b": jnp.asarray(m.b)} for m in models]
    avg = average_params(trees, weights)
    return LinearSVM(w=np.asarray(avg["w"]), b=float(avg["b"]))
