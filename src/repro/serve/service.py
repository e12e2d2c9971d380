"""EnsembleScorer — the SVM-ensemble scoring service.

Bridges the data plane (packed ``StackedEnsemble`` + fused
``ensemble_score`` kernel, see ``repro.core.ensemble``) to the control
plane (``MicroBatchScheduler``): packing happens ONCE at construction,
and each scheduler batch costs exactly one fused kernel call at a
bucket shape. Dispatch policy per backend is documented in the
``repro.serve`` package docstring.
"""
from __future__ import annotations

from typing import Iterable, Tuple, Union

import numpy as np

from repro.core.ensemble import Ensemble, StackedEnsemble
from repro.core.svm import SVMModel
from repro.obs.trace import span
from repro.serve.scheduler import MicroBatchScheduler, ServeConfig
from repro.utils.metrics import GroupedAUC, streaming_grouped_auc


def _pack(ensemble):
    """Normalize any servable model form to a packed stacked ensemble."""
    from repro.agg import WeightedEnsemble
    from repro.comm.wire import QuantizedStackedEnsemble, QuantizedSVM
    from repro.core.averaging import LinearSVM, StackedLinear

    if isinstance(ensemble, (StackedEnsemble, QuantizedStackedEnsemble, StackedLinear)):
        return ensemble
    if isinstance(ensemble, SVMModel):
        return StackedEnsemble.from_members([ensemble])
    if isinstance(ensemble, QuantizedSVM):
        return QuantizedStackedEnsemble.from_members([ensemble])
    if isinstance(ensemble, LinearSVM):
        # linear aggregates (feature_stats / fused fisher) serve through
        # the packed linear mirror of StackedEnsemble
        return StackedLinear(w=np.asarray(ensemble.w, np.float32), b=float(ensemble.b))
    if isinstance(ensemble, WeightedEnsemble):
        # weighted aggregates serve as their coef-scaled plain ensemble
        return _pack(ensemble.as_ensemble())
    if isinstance(ensemble, Ensemble):
        if ensemble.members and all(
            isinstance(m, QuantizedSVM) for m in ensemble.members
        ):
            return QuantizedStackedEnsemble.from_members(ensemble.members)
        return ensemble.stacked()
    raise TypeError(f"cannot serve {type(ensemble).__name__}")


class EnsembleScorer:
    """score_fn adapter over a packed ensemble (or single student).

    Accepts an ``Ensemble`` (packed here, once), an already-packed
    ``StackedEnsemble``/``QuantizedStackedEnsemble``, or a single model
    — an ``SVMModel`` or int8-wire ``QuantizedSVM``, e.g. the distilled
    student off ``ProtocolResult.student`` — which serves as a k=1
    ensemble through the same fused kernels. Instances are callable
    with a (b, d) batch and return (b,) fp32 mean member scores, which
    is exactly the ``MicroBatchScheduler`` score_fn contract.
    """

    def __init__(self, ensemble: Union[Ensemble, StackedEnsemble, "SVMModel", "QuantizedSVM"]):
        self.stacked = _pack(ensemble)

    @property
    def k(self) -> int:
        return self.stacked.k

    def __call__(self, batch: np.ndarray) -> np.ndarray:
        with span("serve.dispatch"):        # upload and kernel enqueue
            out = self.stacked.score(batch)
        with span("serve.fetch"):           # wait for the device, copy back
            return np.asarray(out)

    def scheduler(self, config: ServeConfig = ServeConfig()) -> MicroBatchScheduler:
        """A micro-batching scheduler serving this ensemble."""
        return MicroBatchScheduler(self, config)

    def evaluate(
        self,
        groups: Iterable[Tuple[object, np.ndarray, np.ndarray]],
        *,
        chunk: int = 4096,
        acc: GroupedAUC = None,
    ) -> GroupedAUC:
        """Streaming per-group AUC over (group, x, y) triples.

        Rows from consecutive groups pack into ``chunk``-sized fused
        kernel calls, and scores fold straight into merge-able
        ``StreamingAUC`` states — no (groups x samples) score matrix.
        Pass ``acc`` to keep folding into an existing accumulator
        (e.g. one per shard, merged at the aggregation barrier).
        """
        return streaming_grouped_auc(self, groups, chunk=chunk, acc=acc)
