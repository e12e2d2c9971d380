"""repro.serve — production serving path for the one-shot global model.

The paper's global model is an ensemble of device-local models scored
as a mean over members (Section 3); per-request that mean is exactly
what a server must compute under heavy traffic. This package is the
request-level half of that story; the math half is the fused
``ensemble_score`` Pallas kernel in ``repro.kernels``.

Modules
-------
scheduler.py  micro-batching request scheduler: bounded queue ->
              dynamic batch assembly padded to bucket sizes (so the
              jit'd scoring call compiles once per bucket, not per
              batch shape) -> single scoring call -> responses
              de-multiplexed in submission order.
cache.py      scored-query LRU cache keyed on raw query bytes; hits
              never enter a batch.
service.py    ``EnsembleScorer`` — adapts a packed ``StackedEnsemble``
              (or an ``Ensemble``) to the scheduler's score_fn
              contract with one jit'd fused kernel call per batch;
              ``EnsembleScorer.evaluate`` streams (group, x, y)
              triples through the merge-able per-group AUC
              accumulators in ``repro.utils.metrics`` (fixed-memory
              eval, composes across shards/micro-batches).

The same scheduler drives both serving workloads in this repo:
  * the SVM-ensemble path (``EnsembleScorer``; benchmarked by
    ``benchmarks/serve_bench.py``);
  * the LM driver ``repro.launch.serve``, which submits token prompts
    as requests and scores a batch with prefill + greedy decode.

Kernel dispatch policy (canonical statement)
--------------------------------------------
All Pallas kernels in this repo — ``rbf_gram``, ``flash_attention``,
and the serve-path ``ensemble_score`` — route through
``repro.kernels.ops`` with one policy:

  * on a TPU backend (``jax.default_backend() == "tpu"``) the compiled
    Pallas kernel runs;
  * anywhere else (e.g. this CPU container) the pure-jnp oracle from
    ``repro.kernels.ref`` runs under ``jax.jit`` — same numerics,
    XLA-compiled, no Pallas lowering required;
  * setting ``REPRO_PALLAS_INTERPRET=1`` overrides the CPU case and
    pushes calls through the Pallas *interpreter* instead, executing
    the real kernel body on CPU. The test suite uses this to validate
    kernel bodies without TPU hardware; it is far slower than the
    oracle and is not a serving configuration.

The SVM kernels, their oracles and the engine's XLA contractions pin
``Precision.HIGHEST``: on a TPU both XLA and Mosaic otherwise contract
f32 in one bf16 pass (about 1e-1 error on a d=32 Gram entry), which
would break kernel/oracle parity and the engine tiers' agreement.

Every module that cares about dispatch (``kernels/ops.py``,
``benchmarks/run.py``) cross-references this docstring rather than
restating the policy.
"""
from repro.serve.cache import LRUCache, query_key
from repro.serve.scheduler import (
    MicroBatchScheduler,
    QueueFullError,
    SchedulerStats,
    ServeConfig,
)
from repro.serve.service import EnsembleScorer

__all__ = [
    "EnsembleScorer",
    "LRUCache",
    "MicroBatchScheduler",
    "QueueFullError",
    "SchedulerStats",
    "ServeConfig",
    "query_key",
]
