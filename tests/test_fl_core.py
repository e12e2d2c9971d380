"""One-shot FL core: SVM solver, ensembles, selection, distillation,
averaging, FedAvg — unit + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (
    ConstantModel,
    DeviceReport,
    Ensemble,
    average_params,
    cv_selection,
    data_selection,
    distill_svm,
    ensemble_predict_mean,
    one_shot_average_linear,
    random_selection,
    run_fedavg,
    train_linear_svm,
    train_svm,
)
from repro.core.svm import _sdca, default_gamma, rbf_gram
from repro.utils.metrics import roc_auc


def _blob_data(rng, n=80, d=4, sep=2.0):
    y = np.where(rng.random(n) < 0.5, 1.0, -1.0)
    x = rng.normal(0, 1, (n, d)).astype(np.float32) + sep * y[:, None] / np.sqrt(d)
    return x.astype(np.float32), y.astype(np.float32)


# ----------------------------------------------------------------------
# SVM
# ----------------------------------------------------------------------

def test_svm_learns_separable_blobs(rng):
    x, y = _blob_data(rng, n=120)
    m = train_svm(x, y, lam=0.01)
    auc = roc_auc(y, m.predict(x))
    assert auc > 0.95
    xt, yt = _blob_data(rng, n=100)
    assert roc_auc(yt, m.predict(xt)) > 0.9


def test_svm_learns_nonlinear_xor(rng):
    """RBF must beat linear on XOR — kernel trick sanity."""
    n = 200
    x = rng.normal(0, 1, (n, 2)).astype(np.float32)
    y = np.sign(x[:, 0] * x[:, 1]).astype(np.float32)
    m = train_svm(x, y, lam=0.005)
    assert roc_auc(y, m.predict(x)) > 0.9
    lin = train_linear_svm(x, y)
    assert roc_auc(y, lin.predict(x)) < 0.7  # linear can't do XOR


def test_sdca_dual_feasibility(rng):
    """0 <= alpha <= 1 box constraint holds; padded coords stay zero."""
    x, y = _blob_data(rng, n=50)
    K = rbf_gram(jnp.asarray(x), jnp.asarray(x), default_gamma(x))
    Kp = jnp.zeros((64, 64)).at[:50, :50].set(K)
    yp = jnp.concatenate([jnp.asarray(y), jnp.ones(14)])
    alpha = np.asarray(_sdca(Kp, yp, 50, 0.01, 10))
    assert (alpha >= 0).all() and (alpha <= 1).all()
    np.testing.assert_allclose(alpha[50:], 0.0)


def test_sdca_improves_dual_objective(rng):
    x, y = _blob_data(rng, n=60)
    gamma = default_gamma(x)
    K = np.asarray(rbf_gram(jnp.asarray(x), jnp.asarray(x), gamma))
    lam, n = 0.01, 60

    def dual_obj(alpha):
        ay = alpha * y
        return -ay @ K @ ay / (2 * lam * n * n) + alpha.mean()

    Kp = jnp.zeros((64, 64)).at[:60, :60].set(jnp.asarray(K))
    yp = jnp.concatenate([jnp.asarray(y), jnp.ones(4)])
    a1 = np.asarray(_sdca(Kp, yp, 60, lam, 1))[:60]
    a20 = np.asarray(_sdca(Kp, yp, 60, lam, 20))[:60]
    assert dual_obj(a20) >= dual_obj(a1) - 1e-6 > dual_obj(np.zeros(60)) - 1e-6


# ----------------------------------------------------------------------
# ensemble (property: batched predict == mean of member predicts)
# ----------------------------------------------------------------------

def test_ensemble_predict_equals_mean_of_members(rng):
    members = []
    for i in range(5):
        x, y = _blob_data(np.random.default_rng(i), n=40 + 10 * i)
        members.append(train_svm(x, y, lam=0.02))
    ens = Ensemble(members)
    xq = rng.normal(0, 1, (64, 4)).astype(np.float32)
    got = ens.predict(xq)
    want = ensemble_predict_mean(members, xq)
    np.testing.assert_allclose(got, want, atol=1e-4)


def _ragged_members(lengths, d=5, seed=0):
    from repro.core.svm import SVMModel

    rng = np.random.default_rng(seed)
    return [SVMModel(support_x=rng.normal(0, 1, (n, d)).astype(np.float32),
                     coef=rng.normal(0, 0.1, n).astype(np.float32),
                     gamma=float(rng.uniform(0.05, 0.5)))
            for n in lengths]


@pytest.mark.parametrize("lengths", [(100, 70, 130, 5), (3, 9, 1), (128,), (64, 64, 1)])
def test_stacked_pack_keeps_member_rows_in_order(lengths):
    """Row tiles hold each member's supports and coefs in member order,
    repeat its gamma over exactly its own rows, and are zero past the
    last real row."""
    from repro.core.ensemble import StackedEnsemble

    members = _ragged_members(lengths)
    st = StackedEnsemble.from_members(members)
    sup = np.asarray(st.sup).reshape(-1, 5)
    coef = np.asarray(st.coef).reshape(-1)
    gamma = np.asarray(st.gamma).reshape(-1)
    assert st.k == len(lengths) and st.d == 5
    start = 0
    for m in members:
        stop = start + len(m.coef)
        np.testing.assert_array_equal(sup[start:stop], m.support_x)
        np.testing.assert_array_equal(coef[start:stop], m.coef)
        np.testing.assert_array_equal(gamma[start:stop], np.float32(m.gamma))
        start = stop
    assert start == sum(lengths)
    assert not sup[start:].any() and not coef[start:].any() and not gamma[start:].any()
    assert len(coef) - start < st.sup.shape[1]  # the tail is under one tile


def test_pack_span_counts_real_and_padded_rows():
    from repro.core.ensemble import StackedEnsemble
    from repro.obs import Tracer, use_tracer

    tracer = Tracer()
    with use_tracer(tracer):
        StackedEnsemble.from_members(_ragged_members((100, 70, 130, 5)))
        StackedEnsemble.from_members(_ragged_members((10, 20)))
    begins = [e["args"] for e in tracer.events
              if e["name"] == "ensemble.pack" and e["ph"] == "B"]
    assert begins == [{"rows": 305, "packed_rows": 384}, {"rows": 30, "packed_rows": 32}]


def test_ensemble_predict_mixed_sizes_equals_mean(rng):
    """Members of very different sizes (one tile shared by several, one
    member over several tiles) score as the plain mean of members."""
    members = _ragged_members((7, 300, 40, 1, 129, 12), d=4, seed=1)
    ens = Ensemble(members)
    xq = rng.normal(0, 1, (70, 4)).astype(np.float32)
    want = ensemble_predict_mean(members, xq)
    np.testing.assert_allclose(ens.predict(xq), want, atol=1e-5)
    np.testing.assert_allclose(ens.predict_padded(xq), want, atol=1e-5)


def test_ensemble_beats_worst_member(rng):
    xs, ys = _blob_data(rng, n=400)
    members = []
    for i in range(6):
        lo, hi = 60 * i, 60 * i + 60
        members.append(train_svm(xs[lo:hi], ys[lo:hi], lam=0.02))
    ens = Ensemble(members)
    aucs = [roc_auc(ys, m.predict(xs)) for m in members]
    assert roc_auc(ys, ens.predict(xs)) >= min(aucs)


# ----------------------------------------------------------------------
# selection (hypothesis)
# ----------------------------------------------------------------------

reports_st = st.lists(
    st.builds(
        DeviceReport,
        device_id=st.integers(0, 10_000),
        n_train=st.integers(0, 500),
        val_auc=st.floats(0.0, 1.0, allow_nan=False),
        eligible=st.booleans(),
    ),
    min_size=0,
    max_size=40,
    unique_by=lambda r: r.device_id,
)


@settings(max_examples=50, deadline=None)
@given(reports=reports_st, k=st.integers(1, 20), baseline=st.floats(0.0, 1.0))
def test_cv_selection_properties(reports, k, baseline):
    ids = cv_selection(reports, k, auc_baseline=baseline)
    assert len(ids) <= k
    by_id = {r.device_id: r for r in reports}
    chosen = [by_id[i] for i in ids]
    # all eligible and above baseline
    assert all(c.eligible and c.val_auc >= baseline for c in chosen)
    # no unchosen eligible device strictly beats a chosen one
    rest = [r for r in reports if r.eligible and r.val_auc >= baseline and r.device_id not in ids]
    if chosen and rest:
        assert max(r.val_auc for r in rest) <= min(c.val_auc for c in chosen) + 1e-12


@settings(max_examples=50, deadline=None)
@given(reports=reports_st, k=st.integers(1, 20), min_train=st.integers(0, 400))
def test_data_selection_properties(reports, k, min_train):
    ids = data_selection(reports, k, min_train=min_train)
    by_id = {r.device_id: r for r in reports}
    chosen = [by_id[i] for i in ids]
    assert len(ids) <= k
    assert all(c.eligible and c.n_train >= min_train for c in chosen)
    rest = [r for r in reports if r.eligible and r.n_train >= min_train and r.device_id not in ids]
    if chosen and rest:
        assert max(r.n_train for r in rest) <= min(c.n_train for c in chosen)


@settings(max_examples=30, deadline=None)
@given(reports=reports_st, k=st.integers(1, 20), seed=st.integers(0, 99))
def test_random_selection_properties(reports, k, seed):
    ids = random_selection(reports, k, seed=seed)
    eligible = {r.device_id for r in reports if r.eligible}
    assert set(ids) <= eligible
    assert len(ids) == min(k, len(eligible))
    assert len(set(ids)) == len(ids)  # no duplicates
    assert random_selection(reports, k, seed=seed) == ids  # deterministic


# ----------------------------------------------------------------------
# distillation
# ----------------------------------------------------------------------

def test_distill_recovers_teacher_on_proxy(rng):
    x, y = _blob_data(rng, n=150)
    teacher = train_svm(x, y, lam=0.01)
    proxy = rng.normal(0, 1, (120, 4)).astype(np.float32) + rng.choice(
        [-1, 1], (120, 1)
    ) * 2.0 / np.sqrt(4)
    student = distill_svm(teacher.predict, proxy, gamma=teacher.gamma)
    # student matches teacher ON THE PROXY almost exactly (Eq. 3 objective)
    np.testing.assert_allclose(student.predict(proxy), teacher.predict(proxy), atol=1e-2)
    # and generalizes: AUC close to teacher on fresh data
    xt, yt = _blob_data(rng, n=200)
    t_auc = roc_auc(yt, teacher.predict(xt))
    s_auc = roc_auc(yt, student.predict(xt))
    assert s_auc > t_auc - 0.05


def test_distill_improves_with_proxy_size(rng):
    """Paper Fig. 3: distilled model approaches ensemble as l grows."""
    xs, ys = _blob_data(rng, n=300)
    members = [train_svm(xs[50 * i : 50 * i + 50], ys[50 * i : 50 * i + 50]) for i in range(5)]
    ens = Ensemble(members)
    xt, yt = _blob_data(rng, n=300)
    ens_auc = roc_auc(yt, ens.predict(xt))
    gaps = []
    for l in (10, 160):
        proxy = _blob_data(rng, n=l)[0]
        student = distill_svm(ens.predict, proxy, gamma=members[0].gamma)
        gaps.append(abs(ens_auc - roc_auc(yt, student.predict(xt))))
    assert gaps[1] <= gaps[0] + 0.02


# ----------------------------------------------------------------------
# averaging + fedavg baselines
# ----------------------------------------------------------------------

def test_average_params_refuses_mismatched_trees():
    t1 = {"w": jnp.ones((2, 2))}
    t2 = {"w": jnp.ones((2, 2)), "b": jnp.zeros(2)}
    with pytest.raises(ValueError, match="identical model structures"):
        average_params([t1, t2])
    t3 = {"w": jnp.ones((3, 2))}
    with pytest.raises(ValueError, match="leaf shapes"):
        average_params([t1, t3])


def test_average_params_weighted():
    t1 = {"w": jnp.zeros(3)}
    t2 = {"w": jnp.ones(3)}
    avg = average_params([t1, t2], weights=[1.0, 3.0])
    np.testing.assert_allclose(np.asarray(avg["w"]), 0.75)


def test_average_params_rejects_degenerate_weights():
    """The historic failure mode: a negative weight silently flips a
    member's sign and a zero-sum turns the normalize into NaN trees.
    Both now raise through normalize_weights."""
    t1, t2 = {"w": jnp.zeros(3)}, {"w": jnp.ones(3)}
    with pytest.raises(ValueError, match="non-negative"):
        average_params([t1, t2], weights=[1.0, -1.0])
    with pytest.raises(ValueError, match="sum"):
        average_params([t1, t2], weights=[0.0, 0.0])
    with pytest.raises(ValueError, match="finite"):
        average_params([t1, t2], weights=[1.0, float("nan")])
    with pytest.raises(ValueError):
        average_params([t1, t2], weights=[1.0])  # wrong length


def test_normalize_weights_projects_to_the_simplex():
    from repro.core.averaging import normalize_weights

    w = normalize_weights([2.0, 6.0])
    assert w.dtype == np.float64
    np.testing.assert_allclose(w, [0.25, 0.75])
    assert w.sum() == pytest.approx(1.0)
    with pytest.raises(ValueError, match="1-D"):
        normalize_weights(np.ones((2, 2)))
    with pytest.raises(ValueError, match="sum"):
        normalize_weights([1e-33, 1e-33])  # near-zero sum, not just exact zero


def test_one_shot_linear_averaging_runs(rng):
    models = []
    for i in range(4):
        x, y = _blob_data(np.random.default_rng(i), n=100)
        models.append(train_linear_svm(x, y))
    avg = one_shot_average_linear(models)
    xt, yt = _blob_data(rng, n=200)
    assert roc_auc(yt, avg.predict(xt)) > 0.8  # IID blobs: averaging fine


def test_fedavg_converges_and_counts_comm(rng):
    datasets = [_blob_data(np.random.default_rng(i), n=80) for i in range(6)]
    xt, yt = _blob_data(rng, n=200)

    def local(params, data, rnd):
        x, y = data
        w, b = params["w"], params["b"]
        for _ in range(3):
            margin = y * (x @ np.asarray(w) + float(b))
            g = -(y * (margin < 1))[:, None] * x
            w = w - 0.05 * (jnp.asarray(g.mean(0)) + 0.01 * w)
        return {"w": w, "b": b}

    def ev(params):
        return roc_auc(yt, xt @ np.asarray(params["w"]) + float(params["b"]))

    res = run_fedavg(
        {"w": jnp.zeros(4), "b": jnp.zeros(())},
        datasets,
        local,
        rounds=8,
        clients_per_round=4,
        eval_fn=ev,
        weights_fn=lambda d: len(d[1]),
    )
    assert res.history[-1] > 0.9
    assert res.comm_bytes == pytest.approx(2 * (4 * 4 + 4) * 8 * 4)  # 2 * bytes * rounds * clients


def test_constant_model_auc_half(rng):
    m = ConstantModel(0.3)
    y = np.array([1, -1, 1, -1.0])
    assert roc_auc(y, m.predict(np.zeros((4, 2)))) == 0.5
