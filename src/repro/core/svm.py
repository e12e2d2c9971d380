"""RBF-kernel dual SVM — the paper's local model (Section 3, Eq. 2).

Each device solves the dual of the hinge-loss ERM problem with an RBF
kernel via SDCA (stochastic dual coordinate ascent, cyclic order). The
local model is f_t(x) = sum_j coef_j k(x_j, x) with coef = alpha*y/(lam*n),
i.e. support vectors must be shared to communicate the model — exactly
the privacy tension the paper resolves with distillation.

The Gram matrix is the compute hot spot; ``repro.kernels.ops.rbf_gram``
routes to the Pallas TPU kernel on TPU and the jnp oracle elsewhere.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.metrics import roc_auc


# SDCA problems are padded to multiples of this (few distinct compiled
# shapes); the sim engine buckets devices by the same quantum so its
# batched solves are numerically aligned with train_svm's.
SDCA_BUCKET = 64


def default_gamma(x: np.ndarray) -> float:
    """sklearn-style 'scale' heuristic: 1 / (d * var)."""
    v = float(np.var(x))
    return 1.0 / (x.shape[1] * max(v, 1e-8))


def rbf_gram(x1, x2, gamma: float):
    """exp(-gamma ||x1 - x2||^2); routed through the kernels package."""
    from repro.kernels import ops as kops

    return kops.rbf_gram(x1, x2, gamma)


@partial(jax.jit, static_argnames=("epochs",))
def _sdca(K, y, n_real, lam: float, epochs: int = 20):
    """Cyclic SDCA for the hinge-loss dual. Returns alpha in [0, 1]^n.

    K and y are padded to a bucket size (one compilation per bucket, not
    per device); coordinates >= n_real are masked to zero and padded K
    rows/cols are zero so they never touch real coordinates.
    """
    n_pad = y.shape[0]
    Ky = K * y[None, :]  # K_ij y_j

    def coord(i, alpha):
        f_i = jnp.dot(Ky[i], alpha,
                      precision=jax.lax.Precision.HIGHEST) / (lam * n_real)
        grad = 1.0 - y[i] * f_i
        step = grad * lam * n_real / jnp.maximum(K[i, i], 1e-8)
        new = jnp.clip(alpha[i] + step, 0.0, 1.0)
        new = jnp.where(i < n_real, new, 0.0)
        return alpha.at[i].set(new)

    def epoch(alpha, _):
        return jax.lax.fori_loop(0, n_pad, coord, alpha), None

    # built from y so that under shard_map the carry varies over the
    # mesh axes exactly as the loop's output does (scan requires it)
    alpha0 = jnp.zeros_like(y, dtype=jnp.float32)
    alpha, _ = jax.lax.scan(epoch, alpha0, None, length=epochs)
    return alpha


@dataclasses.dataclass
class SVMModel:
    """A trained local model: support vectors + dual coefficients."""

    support_x: np.ndarray  # (n, d)
    coef: np.ndarray  # (n,)  = alpha * y / (lam * n)
    gamma: float

    def predict(self, x: np.ndarray, chunk: int = 8192) -> np.ndarray:
        """Decision scores via the fused k=1 ensemble_score kernel.

        Packs transiently through the canonical packer — protocol models
        predict only a handful of times each, so retaining device copies
        per model would outweigh the repack cost. Hot serving paths hold
        a long-lived ``StackedEnsemble``/``EnsembleScorer`` instead."""
        from repro.core.ensemble import StackedEnsemble

        return StackedEnsemble.from_members([self]).predict(x, chunk=chunk)

    @property
    def nbytes(self) -> int:
        # repro: allow[wire-cost-honesty] reason=in-memory model footprint property, not a wire price
        return self.support_x.nbytes + self.coef.nbytes + 8


@dataclasses.dataclass
class ConstantModel:
    """Paper baseline for data-deficient devices: constant classifier."""

    value: float

    def predict(self, x: np.ndarray) -> np.ndarray:
        return np.full(len(x), self.value, np.float32)

    @property
    def nbytes(self) -> int:
        return 8


def train_svm(
    x: np.ndarray,
    y: np.ndarray,
    lam: float = 0.01,
    gamma: Optional[float] = None,
    epochs: int = 20,
) -> SVMModel:
    if gamma is None:
        gamma = default_gamma(x)
    n = len(y)
    bucket = max(-(-n // SDCA_BUCKET) * SDCA_BUCKET, SDCA_BUCKET)
    xj = jnp.asarray(x, jnp.float32)
    yj = jnp.asarray(y, jnp.float32)
    K = rbf_gram(xj, xj, gamma)
    Kp = jnp.zeros((bucket, bucket), jnp.float32).at[:n, :n].set(K)
    yp = jnp.concatenate([yj, jnp.ones(bucket - n, jnp.float32)])
    alpha = _sdca(Kp, yp, n, lam, epochs)[:n]
    coef = np.asarray(alpha) * np.asarray(y, np.float32) / (lam * n)
    return SVMModel(support_x=np.asarray(x, np.float32), coef=coef.astype(np.float32), gamma=gamma)


def validation_auc(model, x_val: np.ndarray, y_val: np.ndarray) -> float:
    return roc_auc(y_val, model.predict(x_val))
