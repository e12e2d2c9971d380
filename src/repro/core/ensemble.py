"""Ensembles of local models (Section 3): F_k(x) = mean_t f_t(x).

Two representations:
  * ``Ensemble`` — heterogeneous member list (SVMs, constants). SVM-only
    ensembles are packed once into a ``StackedEnsemble`` and scored with
    the fused ``ensemble_score`` kernel; mixed ensembles fall back to
    the per-member mean.
  * ``StackedEnsemble`` — every member's support rows concatenated in
    member order and cut into row tiles: supports (T, bn, d), dual
    coefs (T, bn), and a per-row bandwidth (T, bn) that repeats each
    member's gamma over its own rows. Only the total is padded, up to a
    whole tile, so the kernel reads each member's real supports and not
    k x n_max padded ones. This is the serve-path representation: one
    jit'd fused call per query chunk
    (``repro.kernels.ops.ensemble_score``), no (k, batch, n) Gram tensor
    in HBM.

``Ensemble.predict_padded`` keeps the pre-fusion path (member-padded
arrays built per call + vmap'd padded Gram) as the benchmark baseline
for ``benchmarks/serve_bench.py``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.svm import SVMModel
from repro.obs.trace import span


def chunked_bucket_predict(score_fn, x: np.ndarray, chunk: int) -> np.ndarray:
    """Chunked/streaming evaluation over a host array of queries.

    Each chunk is zero-padded up to a power-of-two bucket before the
    jit'd ``score_fn`` call, so ragged workloads (e.g. per-device test
    splits of hundreds of distinct sizes) compile O(log chunk) shapes
    instead of one per distinct batch size. Shared by the fp32 and int8
    packed-ensemble serve paths — one bucketing policy, one compile
    -shape behavior.
    """
    if len(x) == 0:
        return np.zeros(0, np.float32)
    x = np.asarray(x, np.float32)
    outs = []
    for start in range(0, len(x), chunk):
        xq = x[start : start + chunk]
        b = len(xq)
        bp = max(8, 1 << (b - 1).bit_length())  # next power of two
        if bp != b:
            xq = np.pad(xq, ((0, bp - b), (0, 0)))
        outs.append(np.asarray(score_fn(xq))[:b])
    return np.concatenate(outs)


SUPPORT_TILE = 128  # support rows per kernel tile


@dataclasses.dataclass(frozen=True)
class StackedEnsemble:
    """Packed SVM ensemble in support row tiles: the fused serving
    representation.

    Row r of the flattened (T * bn) rows is a support of one member, in
    member order; ``gamma`` repeats that member's bandwidth on each of
    its rows. The tail past the last real row is zero in all three
    arrays, so it adds nothing to a score. ``bn`` is ``SUPPORT_TILE``,
    or the total rounded up to a multiple of 8 when that is smaller.
    """

    sup: jnp.ndarray    # (T, bn, d) support rows of every member
    coef: jnp.ndarray   # (T, bn) dual coefficients, zero on the tail
    gamma: jnp.ndarray  # (T, bn) each row's member bandwidth, zero on the tail
    k: int              # member count: the score is the row sum over k

    @property
    def d(self) -> int:
        return self.sup.shape[2]

    @classmethod
    def from_members(cls, members: Sequence[SVMModel]) -> "StackedEnsemble":
        if not members:
            raise ValueError("empty ensemble")
        for m in members:
            if not isinstance(m, SVMModel):
                raise TypeError(
                    f"StackedEnsemble requires SVMModel members, got {type(m).__name__}; "
                    "use ensemble_predict_mean for mixed ensembles"
                )
        counts = np.array([len(m.coef) for m in members])
        rows = int(counts.sum())
        bn = min(SUPPORT_TILE, max(-(-rows // 8) * 8, 8))
        tiles = max(-(-rows // bn), 1)
        with span("ensemble.pack", rows=rows, packed_rows=tiles * bn):
            d = members[0].support_x.shape[1]
            sup = np.zeros((tiles * bn, d), np.float32)
            coef = np.zeros(tiles * bn, np.float32)
            gamma = np.zeros(tiles * bn, np.float32)
            np.concatenate([m.support_x for m in members], out=sup[:rows])
            np.concatenate([m.coef for m in members], out=coef[:rows])
            gamma[:rows] = np.repeat(np.array([m.gamma for m in members], np.float32), counts)
            return cls(jnp.asarray(sup.reshape(tiles, bn, d)),
                       jnp.asarray(coef.reshape(tiles, bn)),
                       jnp.asarray(gamma.reshape(tiles, bn)), len(members))

    def score(self, x) -> jnp.ndarray:
        """Fused mean member score for one query block. x: (b, d) -> (b,)."""
        from repro.kernels import ops as kops

        return kops.ensemble_score(jnp.asarray(x, jnp.float32), self.sup, self.coef,
                                   self.gamma, self.k)

    def predict(self, x: np.ndarray, chunk: int = 4096) -> np.ndarray:
        """Chunked scoring with power-of-two bucket padding (see
        ``chunked_bucket_predict``)."""
        return chunked_bucket_predict(self.score, x, chunk)


@dataclasses.dataclass
class Ensemble:
    members: List[SVMModel]
    _stacked: Optional[StackedEnsemble] = dataclasses.field(
        default=None, init=False, repr=False, compare=False
    )
    _qstacked: Optional[object] = dataclasses.field(
        default=None, init=False, repr=False, compare=False
    )

    @property
    def k(self) -> int:
        return len(self.members)

    @property
    def nbytes(self) -> int:
        # repro: allow[wire-cost-honesty] reason=sums member in-memory footprints, not a wire price
        return sum(m.nbytes for m in self.members)

    def stacked(self) -> StackedEnsemble:
        """Pack once, reuse for every subsequent predict/score call.

        Members are treated as immutable after construction (they are
        trained models); mutate ``members`` -> build a new Ensemble.
        """
        if self._stacked is None:
            self._stacked = StackedEnsemble.from_members(self.members)
        return self._stacked

    def predict(self, x: np.ndarray, chunk: int = 4096) -> np.ndarray:
        """Mean of member decision scores via the fused serve path.

        All-``QuantizedSVM`` ensembles (int8 wire payloads) pack once
        into a ``QuantizedStackedEnsemble`` and score through the fused
        ``ensemble_score_q8`` kernel — supports stay int8 end-to-end.
        """
        if not self.members:
            raise ValueError("empty ensemble")
        if any(not isinstance(m, SVMModel) for m in self.members):
            from repro.comm.wire import QuantizedStackedEnsemble, QuantizedSVM

            if all(isinstance(m, QuantizedSVM) for m in self.members):
                if self._qstacked is None:
                    self._qstacked = QuantizedStackedEnsemble.from_members(self.members)
                return self._qstacked.predict(x, chunk=chunk)
            # heterogeneous (e.g. ConstantModel baselines): per-member mean
            return ensemble_predict_mean(self.members, x)
        return self.stacked().predict(x, chunk=chunk)

    def predict_padded(self, x: np.ndarray, chunk: int = 4096) -> np.ndarray:
        """Pre-fusion baseline: pack per call, vmap a full padded Gram.

        Kept (not routed anywhere) as the comparison point for
        ``benchmarks/serve_bench.py``: it builds a (k, n_max, d)
        member-padded support tensor on every call and materializes the
        whole (k, chunk, n_max) Gram before reducing it.
        """
        n_max = max(len(m.coef) for m in self.members)
        sup = np.zeros((self.k, n_max, self.members[0].support_x.shape[1]), np.float32)
        coef = np.zeros((self.k, n_max), np.float32)
        for i, m in enumerate(self.members):  # per call, on purpose
            sup[i, : len(m.coef)] = m.support_x
            coef[i, : len(m.coef)] = m.coef
        sup_j, coef_j = jnp.asarray(sup), jnp.asarray(coef)
        gam_j = jnp.asarray([m.gamma for m in self.members], jnp.float32)

        def member_scores(s, c, g, xq):
            # zero-padded support rows contribute exp(-g*dist)*0 via coef
            x2 = jnp.sum(s * s, axis=1)[None, :]
            q2 = jnp.sum(xq * xq, axis=1)[:, None]
            d2 = jnp.maximum(q2 + x2 - 2.0 * xq @ s.T, 0.0)
            return jnp.exp(-g * d2) @ c  # (nq,)

        outs = []
        for start in range(0, len(x), chunk):
            xq = jnp.asarray(x[start : start + chunk], jnp.float32)
            scores = jax.vmap(member_scores, in_axes=(0, 0, 0, None))(sup_j, coef_j, gam_j, xq)
            outs.append(np.asarray(scores.mean(axis=0)))
        return np.concatenate(outs)


def ensemble_predict_mean(members: Sequence, x: np.ndarray) -> np.ndarray:
    """Reference implementation: plain mean over member.predict (oracle
    for Ensemble.predict in tests; also handles ConstantModel members)."""
    return np.mean([m.predict(x) for m in members], axis=0)
