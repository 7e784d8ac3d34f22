"""The regression LR sweep (`CrossValidation(Evaluators.Regression.*)` over
OpLinearRegression on the streamed route) against the plain reference
(benchmark/reference_regression.py) on seeded data: the Gram route's
coefficients against a float64 replay of the documented iteration on
float64 moments, every regression metric of the held-out-once pass against
the exact value of the sweep's own coefficients and against the per-fold
route, on one device and on 4 of conftest's host devices, for float32 and
bfloat16 matrices under a label whose mean lies 5 deviations from zero."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, reference_regression as R
from transmogrifai_tpu.automl.tuning import validators as V
from transmogrifai_tpu.automl.tuning.validators import CrossValidation
from transmogrifai_tpu.evaluators.evaluators import Evaluators
from transmogrifai_tpu.models.glm import OpLinearRegression
from transmogrifai_tpu.ops import glm as G
from transmogrifai_tpu.ops import glm_sweep as GS
from transmogrifai_tpu.ops import metrics_ops as M
from transmogrifai_tpu.parallel.mesh import batch_sharding, make_mesh

N, FOLDS = 8000, 5
GRIDS = [dict(reg_param=r, elastic_net_param=a)
         for r in (0.001, 0.01, 0.1, 0.2) for a in (0.1, 0.5)]
METRICS = ("rmse", "mse", "mae", "r2")


def _data(d: int, dtype):
    """Columns that are neither centred nor of unit scale, every second
    one a sparse 0/1 column, as the sweep sees them (rounded to `dtype`);
    the label 5 of its deviations from zero, its scale not 1."""
    rng = np.random.default_rng(d)
    X = rng.normal(size=(N, d)) * rng.uniform(0.1, 4.0, d) \
        + rng.uniform(-3.0, 3.0, d)
    X[:, 1::2] = rng.random((N, d // 2)) < rng.uniform(0.02, 0.5, d // 2)
    X = np.asarray(jnp.asarray(X, dtype).astype(jnp.float32), np.float64)
    z = ((X - X.mean(0)) / X.std(0)) @ (rng.normal(size=d) / np.sqrt(d))
    y = 1.7 * (z + 0.65 * rng.normal(size=N))
    y = (y + 5.0 * y.std()).astype(np.float32)
    return X, y


@pytest.fixture
def small_routes(monkeypatch):
    """The toy size takes the route the chip takes at 25M rows."""
    monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)


def _sweep(X, y, dtype, metric="rmse", **kw):
    val = CrossValidation(getattr(Evaluators.Regression, metric)(),
                          num_folds=FOLDS, seed=42, sweep_dtype=dtype)
    models = [(OpLinearRegression(max_iter=50, tol=1e-6),
               [dict(g) for g in GRIDS])]
    with reference.StreamedFitSpy() as spy:
        best = val.validate(models, X, y, problem_type="regression", **kw)
    fm = np.asarray([v.fold_metrics for v in best.validated]).T   # [F, G]
    return val, best, fm, spy.fits[0]


def _exact(X, y, held, beta, b0) -> dict:
    r = X @ np.asarray(beta, np.float64) + float(b0) - y
    w = held.astype(np.float64)
    mse = (w * r * r).sum() / w.sum()
    ybar = (w * y).sum() / w.sum()
    return {"rmse": np.sqrt(mse), "mse": mse,
            "mae": (w * np.abs(r)).sum() / w.sum(),
            "r2": 1.0 - (w * r * r).sum() / (w * (y - ybar) ** 2).sum()}


@pytest.mark.parametrize("d, dtype", [(16, jnp.float32), (16, jnp.bfloat16),
                                      (128, jnp.float32),
                                      (128, jnp.bfloat16)])
def test_sweep_matches_the_plain_reference(small_routes, d, dtype):
    X, y = _data(d, dtype)
    val, best, fm, (Braw, b0raw) = _sweep(X.astype(np.float32), y, dtype)
    tele = val.last_streamed_telemetry
    assert {k: tele[k] for k in (
        "route", "kernel", "eval_route", "metric_body", "passes",
        "x_passes", "lanes_at_cap")} == {
        "route": "streamed", "kernel": "gram", "eval_route": "heldout_once",
        "metric_body": "sums", "passes": 1, "x_passes": 4, "lanes_at_cap": 0}
    assert 0 < tele["gram_solve_iters"] < 50
    assert tele["lanes_retired"] == tele["lanes_total"] == FOLDS * len(GRIDS)
    masks = val.fold_masks(y)
    mean, std = X.mean(0), X.std(0)
    xs = (X - mean) / std
    y64 = y.astype(np.float64)
    for f in (0, FOLDS - 1):
        t = masks[f].astype(np.float64)
        m = {"G": (xs * t[:, None]).T @ xs, "sx": t @ xs,
             "c": (t * y64) @ xs, "sy": float(t @ y64), "sw": float(t.sum())}
        for j, g in enumerate(GRIDS):
            doc = R.replay(m, g["reg_param"], g["elastic_net_param"],
                           max_iter=50, tol=1e-6)
            Bs = Braw[f, j] * std
            b0s = b0raw[f, j] + (Braw[f, j] * mean).sum()
            # float32 moments and solves of a label near 10
            assert np.abs(Bs - doc["B"]).max() < 2e-5, (f, g)
            assert abs(b0s - doc["b0"]) < 2e-5, (f, g)
            got = _exact(X, y64, 1.0 - masks[f], Braw[f, j], b0raw[f, j])
            assert abs(fm[f, j] - got["rmse"]) < 2e-6 * got["rmse"], (f, g)
    # the grid discriminates, and the report orders it as the exact values
    assert fm.mean(0).max() - fm.mean(0).min() > 0.05


@pytest.mark.parametrize("metric", METRICS)
def test_heldout_once_matches_per_fold(small_routes, monkeypatch, metric):
    """The same masks handed in with a predicate that says they overlap:
    the per-fold route (all rows scored once a fold, M.regression_metrics
    under vmap) against the one pass of sums, to summation order."""
    X, y = _data(16, jnp.bfloat16)
    X = X.astype(np.float32)
    val, _, once, fits = _sweep(X, y, jnp.bfloat16, metric)
    assert val.last_streamed_telemetry["eval_route"] == "heldout_once"
    masks = val.fold_masks(y)
    monkeypatch.setattr(V, "_held_out_at_most_once", lambda m: False)
    val2, _, per, fits2 = _sweep(X, y, jnp.bfloat16, metric, masks=masks,
                                 w=np.ones(N, np.float32))
    tele = val2.last_streamed_telemetry
    assert tele["eval_route"] == "per_fold" and "metric_body" not in tele
    assert tele["passes"] == FOLDS and tele["x_passes"] == 3 + FOLDS
    np.testing.assert_array_equal(fits[0], fits2[0])
    np.testing.assert_allclose(once, per, rtol=2e-6, atol=0)
    # and both are the exact metric of the sweep's own coefficients
    got = _exact(np.asarray(X, np.float64), y.astype(np.float64),
                 1.0 - masks[1], fits[0][1, 3], fits[1][1, 3])[metric]
    assert abs(once[1, 3] - got) < 3e-6 * abs(got)


def test_mesh_form_is_the_one_device_pass_with_two_psums(small_routes):
    """On 4 host devices, a matrix resident row-sharded: the same fold
    metrics to summation order, the route and the collectives declared;
    the metric program's two psums (the folds' label sums, the residual
    sums) are its only collectives."""
    mesh = make_mesh(n_batch=4, n_model=1, devices=jax.devices()[:4])
    X, y = _data(16, jnp.bfloat16)
    X1 = jnp.asarray(X, jnp.bfloat16)
    _, _, one, fits1 = _sweep(X1, jnp.asarray(y), jnp.bfloat16)
    Xs = jax.device_put(X1, batch_sharding(mesh, 2))
    ys = jax.device_put(y, batch_sharding(mesh, 1))
    val, _, four, fits4 = _sweep(Xs, ys, jnp.bfloat16)
    tele = val.last_streamed_telemetry
    assert tele["eval_route"] == "heldout_once" and tele["shards"] == 4
    assert tele["metric_body"] == "sums" and tele["lanes_at_cap"] == 0
    assert tele["psums"] == 2 and tele["psum_bytes"] == 4 * FOLDS * (2 + 4 * 8)
    np.testing.assert_allclose(fits4[0], fits1[0], rtol=0, atol=2e-5)
    np.testing.assert_allclose(four, one, rtol=5e-6, atol=0)
    S = jax.ShapeDtypeStruct
    text = V._sharded_eval_heldout_fn(mesh, "rmse", None).lower(
        S((N, 16), jnp.bfloat16), S((N,), jnp.float32), S((N,), jnp.float32),
        S((FOLDS, N), jnp.float32), S((FOLDS, 8, 16), jnp.float32),
        S((FOLDS, 8), jnp.float32)).as_text()
    assert "jit__streamed_eval_heldout_sharded" in text
    assert text.count("all_reduce") == 2


@pytest.mark.parametrize("metric, problem, bins, body", [
    ("rmse", "regression", None, "sums"), ("r2", "regression", 4096, "sums"),
    ("au_pr", "binary", 4096, "bins"), ("au_pr", "binary", None, None),
    ("f1", "binary", 4096, None), ("error", "multiclass", 4096, None),
    ("rmse", "binary", 4096, None)])
def test_one_predicate_says_which_metrics_take_the_held_out_pass(
        metric, problem, bins, body):
    assert V.heldout_metric_body(metric, problem, bins) == body


def test_exact_scoring_sees_float32_coefficients():
    """Columns that are not centred: coefficients rounded to bfloat16
    shift every prediction; the exact contraction does not."""
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.normal(size=(512, 16)) + 3.0, jnp.bfloat16)
    B = rng.normal(size=(4, 16)).astype(np.float32)
    b0 = rng.normal(size=4).astype(np.float32)
    want = np.asarray(X.astype(jnp.float32), np.float64) @ B.T.astype(
        np.float64) + b0
    score = jax.jit(GS.sweep_scores_fold, static_argnames=("exact",))
    exact = np.asarray(score(X, jnp.asarray(B), jnp.asarray(b0), exact=True))
    plain = np.asarray(score(X, jnp.asarray(B), jnp.asarray(b0)))
    assert np.abs(exact - want).max() < 1e-5
    assert np.abs(plain - want).max() > 1e-3


@pytest.mark.parametrize("offset", [0.0, 10.0, 1e3])
def test_moment_space_solves_settle_under_any_label_mean(offset):
    """The intercept's step is taken on the centred label: at a label mean
    of 10 the uncentred `b0 sw - sy` cancels to float32 noise of `tol`'s
    own size and no delta settles (the parent ran every such solve to
    max_iter)."""
    rng = np.random.default_rng(0)
    n, d, L = 4000, 12, 6
    xs = rng.normal(size=(n, d))
    y = xs @ rng.normal(size=d) * 0.3 + rng.normal(size=n) + offset
    rep = lambda a: jnp.asarray(np.repeat(np.asarray(a)[None], L, 0),
                                jnp.float32)
    l1 = jnp.asarray([1e-4, 5e-4, 1e-3, 0.01, 0.05, 0.1], jnp.float32)
    l2 = jnp.full(L, 1e-3, jnp.float32)
    mom = (rep(xs.T @ xs), rep(y @ xs), rep(xs.sum(0)), rep(y.sum()),
           rep(float(n)))
    @jax.jit
    def solve(*mom):
        beta0, b00 = G.ridge_gram_solve(*mom, l2)
        return G.prox_newton_gram(*mom, l1, l2, beta0, b00, 50, 1e-6)
    beta, b0, iters, delta = solve(*mom)
    assert int(iters) < 50 and float(delta.max()) <= 1e-6
    m = {"G": xs.T @ xs, "sx": xs.sum(0), "c": y @ xs, "sy": float(y.sum()),
         "sw": float(n)}
    for k in range(L):
        doc = R.replay(m, float(l1[k] + l2[k]), float(l1[k] / (l1[k] + l2[k])),
                       max_iter=50, tol=1e-6)
        assert np.abs(np.asarray(beta[k]) - doc["B"]).max() < 1e-5
        assert abs(float(b0[k]) - doc["b0"]) < 1e-6 * max(1.0, offset) + 1e-5


def test_gram_pass_pads_the_block_not_the_matrix():
    """Past TRI_MAX_D columns the feature tiles pad each row block; the
    moments are those of the matrix's own columns."""
    rng = np.random.default_rng(5)
    n, d, F = 600, 130, 2
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=n).astype(np.float32)
    w = rng.uniform(0.5, 2.0, n).astype(np.float32)
    masks = (rng.random((F, n)) < 0.7).astype(np.float32)
    mean, std = X.mean(0), X.std(0)
    got = GS.sweep_gram_moments(*(jnp.asarray(a) for a in (
        X, y, w, masks, mean, std)))
    ref = R.moments_twin(X, y, w, masks, mean, std)
    assert got[0].shape == (F, 192, 192)
    np.testing.assert_allclose(np.asarray(got[0])[:, :d, :d], ref[0],
                               rtol=0, atol=2e-4 * np.abs(ref[0]).max())
    assert not np.asarray(got[0])[:, d:].any()
    for a, b in zip(got[1:3], ref[1:3]):
        np.testing.assert_allclose(np.asarray(a)[:, :d], b, rtol=0,
                                   atol=2e-4 * np.abs(b).max())


def test_regression_metrics_of_the_pass_are_those_of_the_vmapped_kernel(
        small_routes):
    """M.regression_metrics (the per-fold route's kernel) and the held-out
    pass agree on a label whose mean is 1e4 deviations from zero: both
    take the label's sum of squares about its mean."""
    X, y = _data(16, jnp.float32)
    y = (y - y.mean()) / y.std() + 1e4
    val, _, r2, fits = _sweep(X.astype(np.float32), y.astype(np.float32),
                              jnp.float32, "r2")
    masks = val.fold_masks(y)
    pred = X @ fits[0][0, 0].astype(np.float64) + float(fits[1][0, 0])
    want = float(M.regression_metrics(
        jnp.asarray(pred, jnp.float32), jnp.asarray(y, jnp.float32),
        jnp.asarray(1.0 - masks[0])).r2)
    assert 0.3 < r2[0, 0] < 0.9 and abs(r2[0, 0] - want) < 2e-3
