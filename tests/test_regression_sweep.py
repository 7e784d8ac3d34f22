"""The regression LR sweep (`CrossValidation(Evaluators.Regression.*)` over
OpLinearRegression on the streamed route) against the plain reference
(benchmark/reference_regression.py) on seeded data: the Gram route's
coefficients against a float64 replay of the documented iteration on
float64 moments, every regression metric of the held-out-once pass against
the exact value of the sweep's own coefficients and against the per-fold
route, on one device and on 4 of conftest's host devices, for float32 and
bfloat16 matrices under a label whose mean lies 5 deviations from zero;
and the Gram pass's two bodies (`glm_sweep.gram_pass_body`): the raw
bfloat16 products standardised in moment space against float64 sums and
against the float32 blocks, which float32 matrices, far column means and
feature-tiled widths keep to the last bit."""
import re

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
from transmogrifai_tpu.parallel.mesh import (BATCH_AXIS, batch_sharding,
                                             make_mesh, sharded_along)

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


# -- the Gram pass's two bodies ----------------------------------------------

_blocks = jax.jit(lambda *a: GS._block_moments(*a, None, lambda v: v))


def _table(n, d, F, r, weights, *, dtype=jnp.bfloat16, seed=0):
    """What `transmogrify()` makes of numeric fields with holes, as the
    sweep sees it (rounded to `dtype`): numeric columns of deviations 1/32
    to 16 whose means lie `r` deviations from zero, a tenth of their
    values a shared fill, every second column a 0/1 null indicator at a
    rate of 0.001-0.5; a label of mean 10; weights of ones, of powers of
    two or seeded in 0.5-2; `F` disjoint held-out folds; the columns'
    float32 moments; and the five sums in float64 of the EXACTLY
    standardised rows (`R.moments_twin` standardises in float32 as
    `_block_moments` does: its rounding of an indicator's two values is
    the same in every row)."""
    rng = np.random.default_rng(seed)
    sd = 2.0 ** ((np.arange(d) * 3) % 10 - 5)
    X = rng.normal(size=(n, d)) * sd + r * sd * rng.choice([-1, 1], d)
    X = np.where(rng.random((n, d)) < 0.1, X.mean(0), X)
    X[:, 1::2] = rng.random((n, d // 2)) < np.geomspace(1e-3, 0.5, d // 2)
    X = jnp.asarray(X.astype(np.float32)).astype(dtype)
    Xh = np.asarray(X.astype(jnp.float32), np.float64)
    y = (10.0 + rng.normal(size=n) + Xh[:, 0] / sd[0]).astype(np.float32)
    w = {"ones": np.ones(n), "pow2": 2.0 ** rng.integers(-1, 2, n),
         "seeded": rng.uniform(0.5, 2.0, n)}[weights].astype(np.float32)
    masks = (rng.integers(0, F, n)[None, :]
             != np.arange(F)[:, None]).astype(np.float32)
    mean, std = (np.asarray(v) for v in GS.glm_standardize_stats(
        X, jnp.ones(n, jnp.float32)))
    xs = (Xh - mean.astype(np.float64)) / std.astype(np.float64)
    wf = masks.astype(np.float64) * w.astype(np.float64)
    y64 = y.astype(np.float64)
    ref = (np.einsum("fn,nd,ne->fde", wf, xs, xs, optimize=True),
           (wf * y64) @ xs, wf @ xs, wf @ y64, wf.sum(1))
    return tuple(jnp.asarray(a) for a in (X, y, w, masks, mean, std)), ref


def _off(got, ref):
    """Each sum's worst entry, of its largest."""
    return [float(np.abs(np.asarray(a, np.float64)
                         - np.asarray(b, np.float64)).max()
                  / np.abs(np.asarray(b, np.float64)).max())
            for a, b in zip(got, ref)]


@pytest.mark.parametrize("weights", ["ones", "pow2", "seeded"])
@pytest.mark.parametrize("r", [0.3, 2.8])
def test_raw_body_is_the_float64_sums_and_the_float32_blocks(r, weights):
    """The new body, one part of the weighted rows (fold weights of zeros
    and powers of two: the program's own look at them) and three (any
    others): every sum within 1e-5 of its largest entry of the float64
    sums, and within 2e-5 of today's body, which itself reads 1.1e-5 from
    them under unit weights (its float32 standardisation of an indicator's
    two values is off the same way in every row); a weighted operand
    rounded ONCE to bfloat16 reads 1e-3 on the Gram and 0.1 on the fold's
    column sums."""
    args, ref = _table(2048, 128, 5, r, weights)
    X, y, w, masks, mean, std = args
    assert GS.gram_pass_body(X.dtype, 128, bool(
        GS.raw_moments_guard(mean, std))) == GS.GRAM_PASS_RAW
    got = GS.sweep_gram_moments(*args)
    assert [a.shape for a in got] == [b.shape for b in ref]
    assert max(_off(got, ref)) < 1e-5, _off(got, ref)
    assert max(_off(got, _blocks(*args))) < 2e-5
    low = R.moments_twin(np.asarray(X.astype(jnp.float32)), y, w, masks,
                         mean, std, rounded=True)
    assert _off(low, ref)[0] > 2e-4 and _off(low, ref)[2] > 1e-2


@pytest.mark.parametrize("weights, parts", [("ones", 1), ("pow2", 1),
                                            ("seeded", 3)])
def test_raw_products_and_their_sums_are_exact(weights, parts):
    """Values of ONE significant bit, a small integer label: every product
    and every partial sum is a float32, so the raw pass returns the
    float64 sums to the last bit whatever the order of the rows — nothing
    is rounded on the way to the matrix unit; and the parts the weighted
    rows took are what the weights allow (seeded ones of two bits)."""
    rng = np.random.default_rng(11)
    n, d, F = 2048, 128, 3
    X = rng.choice([0.0, 0.5, 1.0, -2.0, 4.0], size=(n, d))
    y = rng.integers(-4, 5, n).astype(np.float32)
    w = {"ones": np.ones(n), "pow2": 2.0 ** rng.integers(-1, 2, n),
         "seeded": rng.choice([0.75, 1.0, 1.5], n)}[weights] \
        .astype(np.float32)
    masks = (rng.integers(0, F, n)[None, :]
             != np.arange(F)[:, None]).astype(np.float32)
    zero, one = jnp.zeros(d, jnp.float32), jnp.ones(d, jnp.float32)
    wf = masks.astype(np.float64) * w.astype(np.float64)
    ref = (np.einsum("fn,nd,ne->fde", wf, X, X), (wf * y) @ X, wf @ X,
           wf @ y.astype(np.float64), wf.sum(1))
    assert bool(GS._scale_only_weights(jnp.asarray(masks),
                                       jnp.asarray(w))) == (parts == 1)
    for rows in (np.arange(n), rng.permutation(n)):
        got = GS.sweep_gram_moments(
            jnp.asarray(X[rows], jnp.bfloat16), jnp.asarray(y[rows]),
            jnp.asarray(w[rows]), jnp.asarray(masks[:, rows]), zero, one)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a, np.float64), b)


def _far_column(X):
    """Column 0 constant: its mean 1e6 deviations from zero (the column
    moments floor a deviation at 1e-6)."""
    return X.at[:, 0].set(1.0)


@pytest.mark.parametrize("case, dtype, d", [
    ("far_mean", jnp.bfloat16, 128), ("float32", jnp.float32, 128),
    ("feature_tiles", jnp.bfloat16, 130)])
def test_todays_body_keeps_what_the_raw_one_cannot_take(case, dtype, d):
    """A column whose mean is 1e6 of its deviations (under the guard's
    `lax.cond`), a float32 matrix and a feature-tiled width (static):
    `gram_pass_body` names today's body and the five sums are its own, bit
    for bit."""
    args, _ = _table(1024, d, 3, 0.3, "seeded", dtype=dtype)
    if case == "far_mean":
        X = _far_column(args[0])
        args = (X,) + args[1:4] + GS.glm_standardize_stats(X, args[2])
    guard = bool(GS.raw_moments_guard(*args[4:]))
    assert guard == (case != "far_mean")
    assert GS.gram_pass_body(dtype, d, guard) == GS.GRAM_PASS_BODY
    got, want = GS.sweep_gram_moments(*args), _blocks(*args)
    for a, b in zip(got[:4], want[:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(got[4]),
                                  np.maximum(np.asarray(want[4]), GS.EPS))


@pytest.mark.parametrize("weights", ["ones", "seeded"])
def test_mesh_gram_pass_is_the_one_device_pass(weights):
    """`_gram_moments` inside a shard_map over 4 host devices (each
    shard's raw sums standardised, then ONE psum) against the one-device
    program: the five sums to float32 rounding of the psum, and through
    `sweep_glm_squared_gram_sharded` the same coefficients, the same
    verdict of the guard."""
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh(n_batch=4, n_model=1, devices=jax.devices()[:4])
    (X, y, w, masks, mean, std), _ = _table(4096, 16, 3, 2.8, weights)
    one = GS.sweep_gram_moments(X, y, w, masks, mean, std)
    rows = (jax.device_put(X, batch_sharding(mesh, 2)),
            jax.device_put(y, batch_sharding(mesh, 1)),
            jax.device_put(w, batch_sharding(mesh, 1)),
            jax.device_put(masks, sharded_along(mesh, 1, 2)))
    four = jax.jit(GS._build_shard_map(
        lambda *a: GS._gram_moments(*a, axis_name=BATCH_AXIS), mesh,
        in_specs=(P(BATCH_AXIS, None), P(BATCH_AXIS), P(BATCH_AXIS),
                  P(None, BATCH_AXIS), P(None), P(None)),
        out_specs=(P(), P(), P(), P(), P())))(*rows, mean, std)
    assert max(_off(four, one)) < 1e-6, _off(four, one)
    regs = jnp.asarray([0.001, 0.01, 0.1, 0.1], jnp.float32)
    alphas = jnp.asarray([0.0, 0.5, 0.5, 0.0], jnp.float32)
    fit1 = GS.sweep_glm_squared_gram(X, y, w, masks, regs, alphas)
    fit4 = GS.sweep_glm_squared_gram_sharded(mesh, *rows, regs, alphas)
    assert bool(fit1[4]) and bool(fit4[4])
    for a, b in zip(fit4[:2], fit1[:2]):
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=5e-6 * float(jnp.abs(b).max()))
    assert int(fit4[2]) == int(fit1[2]) and int(fit4[3]) == int(fit1[3]) == 0


@pytest.mark.parametrize("case, body", [
    ("near_means", "raw_bf16_moments"), ("far_means", "xla_blocks"),
    ("float32", "xla_blocks")])
def test_either_body_fetches_twice_and_says_which_it_was(small_routes, case,
                                                         body):
    """One `validate()` over OpLinearRegression: the `host_step` spans that
    fetch are `gram_solve` and `metric_fetch`, one each, whichever body
    took the moments (the guard's verdict rides with the solves' two
    counts); `moments_body` on the `gram_pass` span and `gram_moments_body`
    in the telemetry name it, `body` / `gram_body` stay the kind of
    program (an XLA loop over row blocks, which both are); and the fit is
    the float64 replay's either way."""
    from transmogrifai_tpu.utils.metrics import collector
    dtype = jnp.float32 if case == "float32" else jnp.bfloat16
    X, y = _data(16, dtype)
    # `_data`'s own columns lie up to 30 deviations from zero: moved to 2
    # of them (10 where the guard is to refuse them), rounded to `dtype`
    X = X - X.mean(0) + (10.0 if case == "far_means" else 2.0) * X.std(0)
    X = np.asarray(jnp.asarray(X, dtype).astype(jnp.float32), np.float64)
    collector.disable()     # whatever an earlier test file left behind
    collector.enable("gram_pass_span")
    try:
        val, _, _, (Braw, b0raw) = _sweep(X.astype(np.float32), y, dtype)
        steps = [s for s in collector.trace.spans if s.kind == "host_step"]
    finally:
        collector.finish()
        collector.disable()
    names = [s.name for s in steps]
    assert names.count("gram_solve") == names.count("metric_fetch") == 1
    assert sorted(names) == ["gram_pass", "gram_solve", "metric_fetch"]
    gp = next(s.attrs for s in steps if s.name == "gram_pass")
    tele = val.last_streamed_telemetry
    assert gp["moments_body"] == tele["gram_moments_body"] == body
    assert gp["body"] == tele["gram_body"] == GS.GRAM_PASS_BODY
    assert tele["x_passes"] == 4 and tele["lanes_at_cap"] == 0
    mean, std = X.mean(0), X.std(0)
    xs, t = (X - mean) / std, val.fold_masks(y)[0].astype(np.float64)
    y64 = y.astype(np.float64)
    m = {"G": (xs * t[:, None]).T @ xs, "sx": t @ xs, "c": (t * y64) @ xs,
         "sy": float(t @ y64), "sw": float(t.sum())}
    doc = R.replay(m, GRIDS[0]["reg_param"], GRIDS[0]["elastic_net_param"],
                   max_iter=50, tol=1e-6)
    assert np.abs(Braw[0, 0] * std - doc["B"]).max() < 2e-5
    assert abs(b0raw[0, 0] + (Braw[0, 0] * mean).sum() - doc["b0"]) < 2e-5


def _gram_text(dtype, n=4096, d=128, F=3):
    """The lowered text of a fresh trace of `sweep_gram_moments`' body."""
    S, f32 = jax.ShapeDtypeStruct, jnp.float32
    return jax.jit(lambda *a: GS._gram_moments(*a)).lower(
        S((n, d), dtype), S((n,), f32), S((n,), f32), S((F, n), f32),
        S((d,), f32), S((d,), f32)).as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lowered_gram_pass_holds_the_raw_body_only_for_bfloat16(
        monkeypatch, dtype):
    """With a float32 matrix the lowered program does not depend on the
    raw body's existence: it is the text lowered with the predicate forced
    to today's answer, letter for letter (`tools/lowered_digests.py`
    compares the other cells' programs with the parent's the same way). With
    a bfloat16 matrix the forced text is that same body's and the free one
    holds both under the guard's `case`: bfloat16 operands at the default
    precision in the raw branches (the Gram against 1 and against 3 parts
    of the weighted rows, the first-order sums of either), HIGHEST left to
    the float32 branch."""
    free = _gram_text(dtype)
    monkeypatch.setattr(GS, "gram_pass_body",
                        lambda *a, **k: GS.GRAM_PASS_BODY)
    forced = _gram_text(dtype)

    def dots(text):
        lines = [ln for ln in text.splitlines()
                 if "stablehlo.dot_general" in ln]
        return ([ln for ln in lines if "bf16>" in ln],
                [ln for ln in lines if "bf16>" not in ln])
    raw, std = dots(forced)
    assert not raw and "stablehlo.case" not in forced
    assert std and all("precision = [HIGHEST, HIGHEST]" in ln for ln in std)
    if dtype == jnp.float32:
        assert free == forced
        return
    raw, std = dots(free)
    assert free != forced and "stablehlo.case" in free
    assert len(raw) == 4 and all("HIGHEST" not in ln for ln in raw)
    assert std and all("precision = [HIGHEST, HIGHEST]" in ln for ln in std)
    widths = sorted(int(m) for ln in raw for m in
                    re.findall(r"x(\d+)xbf16>\) ->", ln))
    assert widths[-2:] == [3 * 128, 3 * 3 * 128]    # F d, and its 3 parts
