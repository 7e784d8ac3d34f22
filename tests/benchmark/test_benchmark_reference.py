"""benchmark/reference.py: the plain twins against brute-force loops, the
exact AuPR by hand, the plain GBT against the program's own fit, and —
the point of the checks — a sweep whose answer was changed must come out
`correct: false`."""
import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import reference  # noqa: E402


def _toy(seed=0, F=3, N=40, lanes=2, S=2, B=5):
    rng = np.random.default_rng(seed)
    Xb_t = rng.integers(0, B, (F, N))
    pay = rng.normal(size=(lanes * 2, N))
    pay[1::2] = np.abs(pay[1::2]) * (rng.random((lanes, N)) < 0.8)
    slot = rng.integers(0, S + 1, (lanes, N)).astype(np.float32)
    return Xb_t, pay, slot


def test_hist_plain_is_the_sum_by_slot_feature_and_bin():
    F, N, lanes, S, B = 3, 40, 2, 2, 5
    Xb_t, pay, slot = _toy(0, F, N, lanes, S, B)
    got = reference.hist_plain(Xb_t, pay, slot, S, B, derive_count=True)
    want = np.zeros((lanes, S, 3, F, B))
    for k in range(lanes):
        for i in range(N):
            s = int(slot[k, i])
            if s >= S:
                continue          # a dropped row
            for f in range(F):
                b = Xb_t[f, i]
                want[k, s, 0, f, b] += pay[2 * k, i]
                want[k, s, 1, f, b] += pay[2 * k + 1, i]
                want[k, s, 2, f, b] += pay[2 * k + 1, i] > 0
    assert got.shape == (lanes * S * 3, F * B)
    np.testing.assert_allclose(got, want.reshape(got.shape), atol=1e-12)


def test_route_and_route_hist_plain():
    F, N, lanes, S, B = 3, 40, 2, 2, 5
    Xb_t, pay, _ = _toy(1, F, N, lanes, S, B)
    rng = np.random.default_rng(2)
    node = rng.integers(0, S, (lanes, N)).astype(np.float32)
    f_lvl = rng.integers(0, F, (lanes, S))
    t_lvl = rng.integers(0, B, (lanes, S))
    m_lvl = rng.integers(0, 2, (lanes, S))
    new = reference.route_plain(Xb_t, node, f_lvl, t_lvl, m_lvl)
    for k in range(lanes):
        for i in range(N):
            nd = int(node[k, i])
            x = Xb_t[f_lvl[k, nd], i]
            right = x > t_lvl[k, nd] or (x == 0 and m_lvl[k, nd] > 0)
            assert new[k, i] == 2 * nd + right
    assert (Xb_t == 0).any()      # the missing direction was exercised
    hist, new2 = reference.route_hist_plain(Xb_t, pay, node, f_lvl, t_lvl,
                                            m_lvl, S, B)
    assert np.array_equal(new, new2)
    # only the rows that went left, by the node they came from
    left = np.where(new % 2 == 0, node, S)
    np.testing.assert_allclose(
        hist, reference.hist_plain(Xb_t, pay, left, S, B), atol=1e-12)


def test_lookup_plain_gives_zero_out_of_range():
    tbl = np.array([[1.0, 2.0, 3.0]], np.float32)
    idx = np.array([[0, 2, 3, -1, 1]], np.float32)
    assert reference.lookup_plain(tbl, idx).tolist() == [[1, 3, 0, 0, 2]]


def test_binned_sample_holds_every_bin_and_some_missing():
    X = np.random.default_rng(0).normal(size=(4000, 3)).astype(np.float32)
    Xb_t = reference.binned_sample(X, 8, seed=1)
    assert Xb_t.shape == (3, 4000)
    assert set(np.unique(Xb_t)) == set(range(9))     # 0 = missing
    assert 0.002 < (Xb_t == 0).mean() < 0.03
    counts = np.bincount(Xb_t[0], minlength=9)[1:]
    assert counts.min() > 400                        # quantile bins


def test_numpy_au_pr_by_hand():
    # ranked: 1, 0, 1 -> precision at the positives 1/1 and 2/3
    score = np.array([0.9, 0.5, 0.1, 0.99])
    y = np.array([1.0, 0.0, 1.0, 0.0])
    w = np.array([1.0, 1.0, 1.0, 0.0])               # the last row is out
    assert reference.numpy_au_pr(score, y, w) == \
        pytest.approx(0.5 * 1.0 + 0.5 * 2 / 3)
    # rows that share a score count together, whatever order they are in:
    # at 2.0 one of two is positive, at 1.0 two of four
    for tied in ([2.0, 2.0, 1.0, 1.0], [1.0, 1.0, 2.0, 2.0]):
        assert reference.numpy_au_pr(
            np.array(tied), np.array([1.0, 0.0, 1.0, 0.0]),
            np.ones(4)) == pytest.approx(0.5 * 0.5 + 0.5 * 0.5)


@pytest.fixture(scope="module")
def glm_case():
    """A sweep's answer made by hand: fold coefficients from the plain
    reference fit itself, fold metrics their exact AuPR."""
    import jax
    import jax.numpy as jnp
    from benchmark import datagen
    X, y = datagen.device_matrix(6000, 6, "bfloat16", 4)
    yh = np.asarray(y)
    masks = np.ones((3, 6000), np.float32)
    for f in range(3):
        masks[f, f::3] = 0.0
    grids = [{"reg_param": 0.3, "elastic_net_param": 0.0},
             {"reg_param": 1e-3, "elastic_net_param": 0.5}]
    B = np.zeros((3, 2, 6), np.float32)
    b0 = np.zeros((3, 2), np.float32)
    metrics = np.zeros((2, 3))
    for j, g in enumerate(grids):
        for f in range(3):
            with jax.default_matmul_precision("highest"):
                b, c = reference._reference_logistic(
                    X.astype(jnp.float32), y, jnp.asarray(masks[f]),
                    g["reg_param"], g["elastic_net_param"])
            B[f, j], b0[f, j] = np.asarray(b), float(c)
            metrics[j, f] = reference.numpy_au_pr(
                reference._margins(X, B[f, j], b0[f, j]), yh, 1 - masks[f])
    validated = [types.SimpleNamespace(
        grid=g, route="streamed", fold_metrics=list(metrics[j]),
        mean_metric=float(metrics[j].mean())) for j, g in enumerate(grids)]
    return types.SimpleNamespace(validated=validated), B, b0, masks, \
        grids, X, y


def _glm_answer(case, B=None, b0=None, best=None):
    best0, B0, b00, masks, grids, X, y = case
    return reference.glm_sweep_answer(
        best or best0, [(B0 if B is None else B, b00 if b0 is None else b0)],
        masks, grids, X, y, reference_fold=1, reference_rows=6000,
        tol_metric=1e-4, tol_reference=1e-3)


def test_glm_answer_passes_on_its_own_coefficients(glm_case):
    out = _glm_answer(glm_case)
    top = max(glm_case[0].validated, key=lambda v: v.mean_metric)
    assert out["grid"] == top.grid                   # the better point
    assert out["metric_worst_delta"] < 1e-12
    assert out["reference_delta"] < 1e-6
    assert len(out["folds"]) == 3 and out["reference_fold"] == 1


def test_glm_answer_fails_a_metric_that_is_not_its_coefficients(glm_case):
    best = glm_case[0]
    off = [types.SimpleNamespace(**vars(v)) for v in best.validated]
    for v in off:
        v.fold_metrics = [m + 2e-4 for m in v.fold_metrics]
    with pytest.raises(reference.CheckFailure, match="exact AuPR of its own"):
        _glm_answer(glm_case, best=types.SimpleNamespace(validated=off))


def test_glm_answer_fails_coefficients_that_stopped_early(glm_case):
    """Coefficients a third of the way to the optimum (a sweep that
    retired its lanes too soon), with metrics honestly theirs."""
    best, B, b0, masks, grids, X, y = glm_case
    yh = np.asarray(y)
    Bs, b0s = B * 0.3, b0 * 0.3
    Bs[:, :, 0] = 0.0
    v = best.validated[1]
    early = types.SimpleNamespace(
        grid=v.grid, route="streamed", mean_metric=1.0,
        fold_metrics=[reference.numpy_au_pr(
            reference._margins(X, Bs[f, 1], b0s[f, 1]), yh, 1 - masks[f])
            for f in range(3)])
    with pytest.raises(reference.CheckFailure, match="plain reference fit"):
        _glm_answer(glm_case, B=Bs, b0=b0s,
                    best=types.SimpleNamespace(validated=[early]))


def test_glm_answer_needs_the_sweeps_coefficients(glm_case):
    best0, _, _, masks, grids, X, y = glm_case
    with pytest.raises(reference.CheckFailure, match="cannot be read"):
        reference.glm_sweep_answer(
            best0, [], masks, grids, X, y, reference_fold=0,
            reference_rows=100, tol_metric=1.0, tol_reference=1.0)


def test_streamed_fit_spy_keeps_what_the_fit_returned():
    from transmogrifai_tpu.automl.tuning.validators import Validator
    real = Validator._streamed_fit
    Validator._streamed_fit = lambda self, *a, **kw: (
        np.ones((2, 1, 3)), np.zeros((2, 1)), {"kernel": "x"}, None)
    try:
        with reference.StreamedFitSpy() as spy:
            out = Validator._streamed_fit(object())
        assert out[2] == {"kernel": "x"}
        assert len(spy.fits) == 1 and spy.fits[0][0].shape == (2, 1, 3)
        assert Validator._streamed_fit(object())[3] is None   # restored
        assert len(spy.fits) == 1
    finally:
        Validator._streamed_fit = real


@pytest.fixture(scope="module")
def gbt_case():
    from benchmark import datagen
    X, y = datagen.device_matrix(60_000, 8, "float32", 9)
    return X, y


def test_plain_gbt_agrees_with_the_programs_fit(gbt_case):
    """Same rounds, depth, bins, eta, lambda on the same rows: the two
    pick their bin edges and break ties apart, so the trees differ, and
    the held-out AuPR agrees."""
    from transmogrifai_tpu.models.trees import OpXGBoostClassifier
    X, y = gbt_case
    Xn, yn = np.asarray(X), np.asarray(y)
    n = 40_000
    model = OpXGBoostClassifier(num_round=5, max_depth=4, max_bins=16,
                                eta=0.3, reg_lambda=1.0) \
        .fit_arrays(Xn[:n], yn[:n])
    prob = np.asarray(model.predict_arrays(Xn[n:])[2])[:, 1]
    margin = np.asarray(reference.plain_gbt(
        X[:n], y[:n], X[n:], rounds=5, depth=4, bins=16, eta=0.3, lam=1.0))
    ones = np.ones(len(yn) - n)
    a_prog = reference.numpy_au_pr(prob.astype(np.float64), yn[n:], ones)
    a_ref = reference.numpy_au_pr(margin, yn[n:], ones)
    assert a_ref > 0.62                              # it learned
    assert abs(a_prog - a_ref) < 5e-3
    assert np.corrcoef(np.log(prob / (1 - prob)), margin)[0, 1] > 0.9


def test_gbt_answer_fails_a_sweep_that_answers_worse(gbt_case):
    X, y = gbt_case
    masks = np.ones((3, X.shape[0]), np.float32)
    for f in range(3):
        masks[f, f::3] = 0.0
    grid = {"num_round": 3, "max_depth": 3, "max_bins": 8, "eta": 0.3,
            "reg_lambda": 1.0}

    def best(metric):
        return types.SimpleNamespace(validated=[
            types.SimpleNamespace(grid={"reg_param": 0.1},
                                  fold_metrics=[0.0] * 3),
            types.SimpleNamespace(grid=grid, fold_metrics=[metric] * 3)])
    out = reference.gbt_sweep_answer(best(0.5), masks, X, y, fold=2,
                                     train_rows=30_000, tol=1.0)
    assert out["train_rows"] == 30_000 and out["held_rows"] == 20_000
    assert len(out["points"]) == 1                   # the LR point skipped
    ref = out["points"][0]["reference"]
    assert 0.6 < ref < 0.75
    reference.gbt_sweep_answer(best(ref + 1e-3), masks, X, y, fold=2,
                               train_rows=30_000, tol=2e-3)
    with pytest.raises(reference.CheckFailure, match="plain reference GBT"):
        reference.gbt_sweep_answer(best(ref - 0.01), masks, X, y, fold=2,
                                   train_rows=30_000, tol=2e-3)


def _calls(F, n, fo, S, B):
    kw = {"allow_bf16": True, "derive_count": True}

    def rec(kernel, shapes, **static):
        return {"kernel": kernel, "shapes": shapes, "static": static,
                "xb_dtype": "int8", "interpret": True, "available": False}
    return [
        rec("hist_folds", [(F, n), (2 * fo, n), (fo, n)], n_slots=1,
            n_bins=B, **kw),
        rec("route_hist", [(F, n), (2 * fo, n), (fo, n), (fo, S)],
            n_nodes=S, n_bins=B, **kw),
        rec("route", [(F, n), (fo, n), (fo, 2 * S)], n_nodes=2 * S),
        rec("table_lookup", [(fo, 4 * S), (fo, n)]),
        rec("hist_pallas", [(1, fo * n), (2, fo * n), (1, fo * n)],
            n_slots=fo, n_bins=256)]


@pytest.mark.slow
def test_kernel_checks_in_interpret_mode_and_a_kernel_that_lies():
    """Every dispatcher at toy shape, Pallas in interpret mode, against
    the plain twins; a kernel that returns garbage must fail."""
    import jax
    import jax.numpy as jnp
    n, F, fo, bins = 512, 8, 3, 8
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    X = jax.random.normal(k1, (n, F), jnp.float32)
    y = (jax.random.uniform(k2, (n,)) < 0.5).astype(jnp.float32)
    masks = (jax.random.randint(k3, (n,), 0, fo)[None, :]
             != jnp.arange(fo)[:, None]).astype(jnp.float32)
    Xb_t = reference.binned_sample(X, bins, seed=3)
    calls = _calls(F, n, fo, 2, bins + 1)
    res = reference.kernel_checks(calls, Xb_t, y, masks, X[:, 0],
                                  interpret=True, binned_tol=0.05)
    assert [r["kernel"] for r in res] == [c["kernel"] for c in calls]
    hist = [r for r in res if "gh_worst_rel" in r]
    assert len(hist) == 2 and all(r["counts_exact"] for r in hist)
    # bf16 input rounding is really exercised, and stays inside the bound
    assert all(0.0 < r["gh_worst_rel"] <= reference.BF16_HIST_RTOL
               for r in hist)
    from transmogrifai_tpu.ops import pallas_hist as PH
    real = PH.route
    PH.route = lambda *a, **kw: real(*a, **kw) + 1.0
    try:
        with pytest.raises(reference.CheckFailure, match="routing"):
            reference.kernel_checks(
                [c for c in calls if c["kernel"] == "route"], Xb_t, y,
                masks, X[:, 0], interpret=True, binned_tol=0.05)
    finally:
        PH.route = real
