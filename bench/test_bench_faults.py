"""CPU tests that a run with the timed path broken underneath reads not
correct, once for each fault a cell can have: an answer altered where
it is produced (a served answer, an evaluation's score, a local fit),
half of a batch left out (a serve batch, a training group, the
server's members, each device's test rows), and a solve that returns
its start (the distilled student). Each drives a whole run of a cell
cut to test size (``bench/tiny.py``), skipping only the look for a chip.
"""
import numpy as np

from bench import tiny


def _answers_altered(orig):
    def f(self, x):
        out = np.asarray(orig(self, x), np.float32).copy()
        out[::3] += 1e-3
        return out
    return f


def _half_batch(orig):
    def f(self, batch):
        kept = batch[: (len(batch) + 1) // 2]
        orig(self, kept)
        for p in batch[len(kept):]:              # answered without being scored
            p.result, p.done = np.float32(0.0), True
            self._done[p.ticket] = None
    return f


def _coefs_altered(orig):
    def f(*args, **kw):
        outs = orig(*args, **kw)
        for o in outs:
            o.model.coef = o.model.coef * np.float32(1.001)
        return outs
    return f


def _half_group_untrained(orig):
    def f(*args, **kw):
        outs = orig(*args, **kw)
        for o in outs[len(outs) // 2:]:          # left out: never trained
            o.model.coef = np.zeros_like(o.model.coef)
        return outs
    return f


def test_serve_answer_altered_fails():
    from repro.serve.service import EnsembleScorer

    with tiny.patched(EnsembleScorer, "__call__", _answers_altered):
        assert _failed(tiny.result("emnist_paper.serve_k50", 5))


def test_serve_half_batch_left_out_fails():
    from repro.serve.scheduler import MicroBatchScheduler

    with tiny.patched(MicroBatchScheduler, "_score_batch", _half_batch):
        assert _failed(tiny.result("emnist_paper.serve_k50", 6))


ROUND = "emnist_paper.round"


def _failed(res):
    return not res["correct"] and any(c["value"] > c["limit"] for c in res["checks"].values())


def test_round_fit_altered_fails():
    import repro.sim.engine as engine

    with tiny.patched(engine, "_train_bucket_group", _coefs_altered):
        assert _failed(tiny.result(ROUND, 7))


def test_round_half_group_left_out_fails():
    """Half of each training batch never trained: the best cell's
    members and every evaluation that holds them are compared."""
    import repro.sim.engine as engine

    with tiny.patched(engine, "_train_bucket_group", _half_group_untrained):
        assert _failed(tiny.result(ROUND, 8))


def _mean_over_half(orig):
    def build(self, members, extras, seed):
        keep = max(1, len(members) // 2)
        return orig(self, list(members)[:keep], list(extras)[:keep], seed)
    return build


def test_round_mean_over_half_the_members_fails():
    """The server's ensemble left half its members out, the mean taken
    over the rest."""
    from repro.agg.strategies import MeanAggregator

    with tiny.patched(MeanAggregator, "build", _mean_over_half):
        assert _failed(tiny.result(ROUND, 9))


def _scores_altered(orig):
    def f(self, x, chunk=4096):
        out = np.asarray(orig(self, x, chunk=chunk), np.float32).copy()
        out[::3] += 1e-3
        return out
    return f


def test_round_evaluation_scores_altered_fails():
    """A score altered where the round's evaluation produces it."""
    from repro.core.ensemble import Ensemble

    with tiny.patched(Ensemble, "predict", _scores_altered):
        assert _failed(tiny.result(ROUND, 10))


def _half_the_rows(orig):
    def fold(score_fn, groups, *, chunk=8192, acc=None):
        return orig(score_fn, ((g, x[::2], y[::2]) for g, x, y in groups),
                    chunk=chunk, acc=acc)
    return fold


def test_round_evaluation_over_half_the_rows_fails():
    """Every evaluation scores half of each device's test rows."""
    import repro.core.protocol as protocol

    with tiny.patched(protocol, "streaming_grouped_auc", _half_the_rows):
        assert _failed(tiny.result(ROUND, 11))


def _student_unchanged(orig):
    def f(*args, **kw):
        student = orig(*args, **kw)
        student.coef = np.zeros_like(student.coef)     # the solve returned its start
        return student
    return f


def test_round_student_left_unsolved_fails():
    import repro.distill.round as distill

    with tiny.patched(distill, "distill_teacher", _student_unchanged):
        res = tiny.result(ROUND, 12)
    assert _failed(res) and res["checks"]["student_residual"]["value"] > 0.5
