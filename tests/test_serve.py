"""repro.serve: scheduler batching invariants, LRU cache, EnsembleScorer."""
import numpy as np
import pytest

from repro.core import Ensemble, ensemble_predict_mean, train_svm
from repro.utils.seeds import derive_device_seed
from repro.serve import (
    EnsembleScorer,
    LRUCache,
    MicroBatchScheduler,
    QueueFullError,
    ServeConfig,
    query_key,
)


def _blob_data(rg, n=60, d=4, sep=2.0):
    y = np.where(rg.random(n) < 0.5, 1.0, -1.0)
    x = rg.normal(0, 1, (n, d)).astype(np.float32) + sep * y[:, None] / np.sqrt(d)
    return x.astype(np.float32), y.astype(np.float32)


def _echo_score(batch):
    """score_fn stub: row sum, so every response is attributable."""
    return batch.sum(axis=tuple(range(1, batch.ndim)))


# ----------------------------------------------------------------------
# scheduler invariants
# ----------------------------------------------------------------------

def test_responses_in_submission_order(rng):
    sched = MicroBatchScheduler(_echo_score, ServeConfig(max_batch=4, buckets=(4,)))
    rows = [rng.normal(0, 1, (3,)).astype(np.float32) for _ in range(11)]
    out = sched.run(rows)
    np.testing.assert_allclose(out, [r.sum() for r in rows], rtol=1e-6)
    assert sched.stats.batches == 3  # 4 + 4 + 3 across two full and one partial


def test_bucket_padding_correctness():
    seen = []

    def spy(batch):
        seen.append(batch.shape[0])
        return _echo_score(batch)

    cfg = ServeConfig(max_batch=8, buckets=(2, 8))
    sched = MicroBatchScheduler(spy, cfg)
    rows = [np.full((2,), float(i), np.float32) for i in range(5)]
    out = sched.run(rows)
    assert seen == [8]  # 5 rows -> smallest covering bucket
    assert sched.stats.padded_rows == 3
    np.testing.assert_allclose(out, [2.0 * i for i in range(5)])
    # exactly-bucket batch pads nothing
    sched2 = MicroBatchScheduler(spy, cfg)
    sched2.run(rows[:2])
    assert seen[-1] == 2 and sched2.stats.padded_rows == 0


def test_bucket_for_picks_smallest_cover():
    cfg = ServeConfig(max_batch=100, buckets=(128, 8, 32))
    assert cfg.bucket_for(1) == 8
    assert cfg.bucket_for(8) == 8
    assert cfg.bucket_for(9) == 32
    assert cfg.bucket_for(100) == 128
    with pytest.raises(ValueError, match="exceeds largest bucket"):
        cfg.bucket_for(129)


def test_config_validation():
    with pytest.raises(ValueError, match="cover max_batch"):
        ServeConfig(max_batch=64, buckets=(8, 32))
    with pytest.raises(ValueError, match="must be >= 1"):
        ServeConfig(max_batch=0)
    with pytest.raises(ValueError, match="max_uncollected"):
        ServeConfig(max_queue=100, max_uncollected=50)


def test_score_fn_failure_requeues_batch():
    """A transient score_fn error must not strand in-flight requests."""
    state = {"fail": True}

    def flaky(batch):
        if state["fail"]:
            state["fail"] = False
            raise RuntimeError("transient device error")
        return _echo_score(batch)

    sched = MicroBatchScheduler(
        flaky, ServeConfig(max_batch=4, buckets=(4,), cache_size=8)
    )
    rows = [np.full(2, float(i), np.float32) for i in range(3)] + [np.full(2, 0.0, np.float32)]
    tickets = sched.submit_many(rows)  # last row duplicates the first
    with pytest.raises(RuntimeError, match="transient"):
        sched.flush()
    sched.flush()  # retry rescores the requeued batch (and its duplicate)
    np.testing.assert_allclose(
        [sched.result(t) for t in tickets], [r.sum() for r in rows]
    )


def test_predict_buckets_chunk_shapes(monkeypatch, rng):
    """Ragged query sizes are padded to power-of-two buckets before the
    jit'd call, bounding recompiles."""
    from repro.kernels import ops as kops

    seen = []
    real = kops.ensemble_score

    def spy(x, sup, coef, gamma, k):
        seen.append(x.shape[0])
        return real(x, sup, coef, gamma, k)

    monkeypatch.setattr(kops, "ensemble_score", spy)
    x, y = _blob_data(np.random.default_rng(0))
    ens = Ensemble([train_svm(x, y)])
    for n in (5, 7, 8, 33, 100):
        assert ens.predict(rng.normal(0, 1, (n, 4)).astype(np.float32)).shape == (n,)
    assert seen == [8, 8, 8, 64, 128]  # 5 ragged sizes -> 3 compiled shapes


def test_submit_many_is_atomic_on_overflow():
    sched = MicroBatchScheduler(_echo_score, ServeConfig(max_batch=2, max_queue=3, buckets=(2,)))
    rows = [np.ones(2, np.float32) * i for i in range(4)]
    with pytest.raises(QueueFullError, match="exceeds remaining"):
        sched.submit_many(rows)
    assert sched.stats.submitted == 0  # nothing stranded in the queue
    assert sched.flush() == 0


def test_bounded_queue_rejects_overflow():
    sched = MicroBatchScheduler(_echo_score, ServeConfig(max_batch=2, max_queue=3, buckets=(2,)))
    for i in range(3):
        sched.submit(np.ones(2, np.float32) * i)
    with pytest.raises(QueueFullError):
        sched.submit(np.ones(2, np.float32))
    sched.flush()
    sched.submit(np.ones(2, np.float32))  # drained queue accepts again


def test_run_empty_request_list():
    sched = MicroBatchScheduler(_echo_score, ServeConfig(max_batch=2, buckets=(2,)))
    out = sched.run([])
    assert out.shape == (0,) and sched.stats.batches == 0


def test_result_is_private_copy_of_bucket_output():
    """Vector responses: a ticket's result must not alias the bucket."""
    sched = MicroBatchScheduler(lambda b: b * 2.0, ServeConfig(max_batch=2, buckets=(2,)))
    rows = [np.arange(3, dtype=np.float32), np.arange(3, dtype=np.float32) + 1]
    r0, r1 = sched.run(rows)
    assert r0.base is None or not np.shares_memory(r0, r1)


def test_result_semantics():
    sched = MicroBatchScheduler(_echo_score, ServeConfig(max_batch=2, buckets=(2,)))
    t = sched.submit(np.ones(3, np.float32))
    with pytest.raises(RuntimeError, match="not scored yet"):
        sched.result(t)
    sched.flush()
    assert sched.result(t) == pytest.approx(3.0)
    with pytest.raises(KeyError):
        sched.result(t)  # one-shot delivery


# ----------------------------------------------------------------------
# cache
# ----------------------------------------------------------------------

def test_cache_hit_skips_scoring():
    calls = []

    def spy(batch):
        calls.append(batch.shape[0])
        return _echo_score(batch)

    sched = MicroBatchScheduler(
        spy, ServeConfig(max_batch=4, buckets=(4,), cache_size=16)
    )
    row = np.arange(3, dtype=np.float32)
    out1 = sched.run([row, row + 1])
    out2 = sched.run([row, row + 1, row + 2])  # two hits, one miss
    assert sched.stats.answered_from_cache == 2
    assert sched.stats.scored_rows == 3  # rows 0,1 then only row 2
    np.testing.assert_allclose(out2[:2], out1)
    np.testing.assert_allclose(out2[2], (row + 2).sum())
    assert len(calls) == 2


def test_lru_eviction_order():
    c = LRUCache(2)
    ka, kb, kc = (query_key(np.array([v], np.float32)) for v in (1.0, 2.0, 3.0))
    c.put(ka, "a")
    c.put(kb, "b")
    assert c.get(ka) == "a"  # refresh a -> b is now LRU
    c.put(kc, "c")
    assert c.get(kb) is None and c.get(ka) == "a" and c.get(kc) == "c"
    assert len(c) == 2


def test_result_mutation_cannot_poison_cache():
    """Vector responses (the LM-path shape): out[i] is a view into the
    bucket output, so cached rows must be copies in both directions."""
    sched = MicroBatchScheduler(
        lambda batch: batch * 2.0, ServeConfig(max_batch=2, buckets=(2,), cache_size=8)
    )
    row = np.arange(3, dtype=np.float32)
    want = row * 2.0
    first = sched.run([row])[0]
    first[:] = -99.0  # caller scribbles on its response view
    second = sched.run([row])[0]  # served from cache
    np.testing.assert_allclose(second, want)
    second[:] = -7.0  # scribble on a cache *hit* too
    np.testing.assert_allclose(sched.run([row])[0], want)
    assert sched.stats.answered_from_cache == 2


def test_submit_copies_caller_buffer():
    """A serving loop legally reuses one buffer across submits."""
    sched = MicroBatchScheduler(_echo_score, ServeConfig(max_batch=4, buckets=(4,)))
    buf = np.zeros(2, np.float32)
    tickets = []
    for i in range(3):
        buf[:] = float(i + 1)
        tickets.append(sched.submit(buf))
    sched.flush()
    np.testing.assert_allclose([sched.result(t) for t in tickets], [2.0, 4.0, 6.0])


def test_intra_flush_duplicates_score_once():
    calls = []

    def spy(batch):
        calls.append(batch.shape[0])
        return batch * 2.0

    sched = MicroBatchScheduler(
        spy, ServeConfig(max_batch=8, buckets=(8,), cache_size=16)
    )
    hot = np.arange(3, dtype=np.float32)
    out = sched.run([hot, hot + 1, hot, hot, hot + 1])
    assert sched.stats.scored_rows == 2 and sched.stats.deduped_in_flight == 3
    assert len(calls) == 1
    np.testing.assert_allclose(out, np.stack([hot, hot + 1, hot, hot, hot + 1]) * 2.0)
    # fanned-out results are private copies too
    out[2][:] = -1.0
    np.testing.assert_allclose(sched.run([hot])[0], hot * 2.0)


def test_abandoned_tickets_are_bounded():
    cfg = ServeConfig(max_batch=2, max_queue=2, buckets=(2,), max_uncollected=3)
    sched = MicroBatchScheduler(_echo_score, cfg)
    tickets = []
    for i in range(6):  # submit+flush without ever collecting
        tickets.append(sched.submit(np.full(2, float(i), np.float32)))
        sched.flush()
    assert sched.stats.evicted_results == 3
    assert len(sched._results) == 3
    with pytest.raises(KeyError):
        sched.result(tickets[0])  # oldest abandoned ticket evicted
    assert sched.result(tickets[-1]) == pytest.approx(10.0)  # recent survives


def test_eviction_skips_collected_and_keeps_done_order_bounded():
    """The completion-order index behind O(evicted) eviction: collected
    tickets leave it immediately (no stale growth), eviction removes the
    oldest-completed *uncollected* tickets, and the stats they earned
    (cache hits, dedupes, batches) survive eviction untouched."""
    cfg = ServeConfig(max_batch=4, max_queue=4, buckets=(4,), max_uncollected=4, cache_size=16)
    sched = MicroBatchScheduler(_echo_score, cfg)
    hot = np.full(2, 9.0, np.float32)
    t_hot = sched.submit(hot)
    abandoned = [sched.submit(np.full(2, float(i), np.float32)) for i in range(3)]
    sched.flush()
    assert sched.result(t_hot) == pytest.approx(18.0)
    assert t_hot not in sched._done  # collected -> out of the done order
    assert len(sched._done) == len(sched._results) == 3
    # next flush completes 2 more (one a cache hit): cap 4 evicts the
    # single oldest-completed abandoned ticket, in completion order
    sched.submit(hot)  # cache hit
    sched.submit(np.full(2, 7.0, np.float32))
    sched.flush()
    assert sched.stats.evicted_results == 1
    assert len(sched._results) == 4 and len(sched._done) == 4
    with pytest.raises(KeyError):
        sched.result(abandoned[0])
    assert sched.result(abandoned[1]) == pytest.approx(2.0)
    # eviction dropped results, not accounting
    assert sched.stats.answered_from_cache == 1
    assert sched.stats.batches == 2
    assert sched.stats.submitted == 6


def test_failed_flush_requeues_duplicates_in_ticket_order():
    """A transient failure re-queues the in-flight batch AND its
    deduped duplicates interleaved back into submission order, so the
    retry replays exactly the original stream."""
    state = {"fail": True}

    def flaky(batch):
        if state["fail"]:
            state["fail"] = False
            raise RuntimeError("transient device error")
        return _echo_score(batch)

    sched = MicroBatchScheduler(
        flaky, ServeConfig(max_batch=8, buckets=(8,), cache_size=16)
    )
    a, b, c = (np.full(2, v, np.float32) for v in (1.0, 2.0, 3.0))
    rows = [a, b, a, c, a]  # tickets 2 and 4 dedupe against 0 in flight
    tickets = sched.submit_many(rows)
    with pytest.raises(RuntimeError, match="transient"):
        sched.flush()
    assert [p.ticket for p in sched._queue] == tickets  # submission order
    assert sched.flush() == 1  # retry: one scoring call, dedupe again
    assert sched.stats.deduped_in_flight == 2
    np.testing.assert_allclose(
        [sched.result(t) for t in tickets], [r.sum() for r in rows]
    )


def test_submit_many_accepts_exact_remaining_capacity():
    """The atomicity boundary: a batch that exactly fills the queue is
    accepted whole; one row more rejects the whole batch."""
    sched = MicroBatchScheduler(_echo_score, ServeConfig(max_batch=2, max_queue=3, buckets=(2,)))
    sched.submit(np.ones(2, np.float32))
    tickets = sched.submit_many([np.full(2, float(i), np.float32) for i in range(2)])
    assert len(tickets) == 2 and sched.stats.submitted == 3
    with pytest.raises(QueueFullError, match="exceeds remaining"):
        sched.submit_many([np.ones(2, np.float32)])
    assert sched.stats.submitted == 3  # rejection enqueued nothing
    sched.flush()
    assert len(sched.submit_many([np.ones(2, np.float32)] * 3)) == 3


def test_cache_disabled_by_default():
    c = LRUCache(0)
    k = query_key(np.zeros(2, np.float32))
    c.put(k, 1.0)
    assert c.get(k) is None and len(c) == 0


def test_disabled_cache_keeps_counters_clean():
    """capacity <= 0 means lookups were never cacheable: neither hits
    nor misses may move, or the exported hit-rate gets polluted."""
    c = LRUCache(0)
    k = query_key(np.zeros(2, np.float32))
    c.put(k, 1.0)
    assert c.get(k) is None
    assert c.hits == 0 and c.misses == 0
    # the scheduler path with caching off leaves them clean too
    sched = MicroBatchScheduler(_echo_score, ServeConfig(max_batch=4, buckets=(4,)))
    sched.run([np.ones(2, np.float32), np.ones(2, np.float32)])
    assert sched.cache.hits == 0 and sched.cache.misses == 0


def test_contains_is_a_stats_free_peek():
    c = LRUCache(2)
    ka, kb, kc = (query_key(np.array([v], np.float32)) for v in (1.0, 2.0, 3.0))
    c.put(ka, "a")
    c.put(kb, "b")
    assert ka in c and kc not in c
    assert c.hits == 0 and c.misses == 0  # no counter bump
    c.put(kc, "c")
    # the peek did not refresh ka's recency: it was still LRU and left
    assert c.get(ka) is None and c.get(kb) == "b" and c.get(kc) == "c"


def test_buckets_normalized_ascending():
    cfg = ServeConfig(max_batch=100, buckets=(128, 8, 32))
    assert cfg.buckets == (8, 32, 128)


# ----------------------------------------------------------------------
# ensemble service end to end
# ----------------------------------------------------------------------

def test_ensemble_scorer_rejects_mixed_members():
    from repro.core import ConstantModel

    x = np.array([[0.0, 1.0], [1.0, 0.0]], np.float32)
    y = np.array([1.0, -1.0], np.float32)
    with pytest.raises(TypeError, match="ConstantModel"):
        EnsembleScorer(Ensemble([ConstantModel(0.5), train_svm(x, y)]))


def test_predict_empty_batch(rng):
    x, y = _blob_data(np.random.default_rng(0))
    m = train_svm(x, y)
    empty = np.zeros((0, x.shape[1]), np.float32)
    assert m.predict(empty).shape == (0,)
    assert Ensemble([m]).predict(empty).shape == (0,)


def test_ensemble_scorer_through_scheduler_matches_oracle(rng):
    members = []
    for i in range(6):
        x, y = _blob_data(np.random.default_rng(i), n=30 + 7 * i)
        members.append(train_svm(x, y, lam=0.02))
    scorer = EnsembleScorer(Ensemble(members))
    assert scorer.k == 6
    sched = scorer.scheduler(ServeConfig(max_batch=16, buckets=(4, 16), cache_size=64))
    queries = [rng.normal(0, 1, (4,)).astype(np.float32) for _ in range(23)]
    got = sched.run(queries)
    want = ensemble_predict_mean(members, np.stack(queries))
    np.testing.assert_allclose(got, want, atol=1e-4)
    # repeat traffic is served from cache without any new scoring call
    before = sched.stats.batches
    got2 = sched.run(queries)
    np.testing.assert_allclose(got2, got, atol=1e-6)
    assert sched.stats.batches == before
    assert sched.stats.answered_from_cache == len(queries)


def test_ensemble_scorer_streaming_evaluate_matches_materialized(rng):
    """EnsembleScorer.evaluate == per-group roc_auc on full score
    arrays, at any chunk size, and partial accumulators merge."""
    from repro.utils.metrics import GroupedAUC, roc_auc

    members = []
    for i in range(4):
        x, y = _blob_data(np.random.default_rng(derive_device_seed(10, i)), n=40)
        members.append(train_svm(x, y, lam=0.02))
    scorer = EnsembleScorer(Ensemble(members))
    local = np.random.default_rng(42)
    groups = []
    for g in range(5):
        m = int(local.integers(3, 60))
        gx = local.normal(0, 1, (m, members[0].support_x.shape[1])).astype(np.float32)
        gy = local.integers(0, 2, m)
        groups.append((g, gx, gy))
    want = {g: roc_auc(gy, scorer(gx)) for g, gx, gy in groups}

    for chunk in (8, 64, 4096):
        got = scorer.evaluate(groups, chunk=chunk).compute()
        assert got.keys() == want.keys()
        for g in want:
            assert abs(got[g] - want[g]) < 1e-9, (chunk, g)

    # shard-style composition: two partial accumulators, merged
    a = scorer.evaluate(groups[:2], chunk=16)
    b = scorer.evaluate(groups[2:], chunk=16, acc=GroupedAUC())
    merged = a.merge(b).compute()
    for g in want:
        assert abs(merged[g] - want[g]) < 1e-9
