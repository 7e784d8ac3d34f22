"""The wide binary GLM rounds (ops/glm_sweep.py, "streamed wide route"):
through validate() against the plain reference at the rehearsal width and
at 1 024 columns, the properties its one shared curvature matrix stands on,
standardisation folded into the algebra, the same optimum as the narrow
rounds where both run, and the route guard at the benchmark's shape."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from benchmark import datagen_hashed as DH
from benchmark import reference, reference_wide as RW
from transmogrifai_tpu.automl.tuning import validators as V
from transmogrifai_tpu.automl.tuning.validators import CrossValidation
from transmogrifai_tpu.evaluators.evaluators import Evaluators
from transmogrifai_tpu.models.glm import OpLogisticRegression
from transmogrifai_tpu.ops import glm_sweep as GS


def _hashed(rows, buckets, seed=7, dtype="float32"):
    X, y = DH.device_matrix(rows, 8, buckets, dtype, seed,
                            truth_nonzero=max(buckets // 2, 4),
                            truth_scale=3.0, truth_intercept=-2.45)
    return X, y


def _fold_masks(n, folds, seed=3):
    fold = np.random.default_rng(seed).integers(0, folds, n)
    return np.stack([(fold != f).astype(np.float32) for f in range(folds)])


def _wide(X, y, masks, regs, alphas, **kw):
    n = X.shape[0]
    B, b0, info = GS.sweep_glm_wide_streamed_rounds(
        jnp.asarray(X), jnp.asarray(y), jnp.ones(n, jnp.float32),
        jnp.asarray(masks), np.asarray(regs, np.float32),
        np.asarray(alphas, np.float32), **kw)
    return np.asarray(B), np.asarray(b0), info


# -- through validate(), against the plain reference ---------------------------

@pytest.mark.parametrize("buckets,rows", [(32, 3000), (127, 2500)])
def test_validate_takes_the_wide_route_and_holds_the_reference(
        buckets, rows, monkeypatch):
    """d = 264 (8 x 33, the cell's rehearsal shape) and d = 1 024: every
    fold and grid point of a validate() sweep against reference_wide.fit,
    the documented iteration written again with no lanes and no buckets."""
    monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
    X, y = _hashed(rows, buckets)
    d = 8 * (buckets + 1)
    assert X.shape == (rows, d) and d > GS.TRI_MAX_D
    grids = [{"reg_param": r, "elastic_net_param": a}
             for r in (0.01, 0.1) for a in (0.1, 0.5)]
    val = CrossValidation(Evaluators.BinaryClassification.au_pr(),
                          num_folds=3, seed=42)
    with reference.StreamedFitSpy() as spy:
        best = val.validate(
            [(OpLogisticRegression(max_iter=12, tol=1e-6), grids)], X, y)
    tele = val.last_streamed_telemetry
    assert tele["kernel"] == "wide_rounds" and tele["route"] == "streamed"
    assert tele["cols"] == d and tele["padded_cols"] == -(-d // 128) * 128
    assert tele["factorizations"] == 0 and tele["gram_passes"] == 1
    # 12 iterations in rounds of 5, the Gram pass, two passes of moments
    assert tele["x_passes"] == tele["data_passes"] + 3 == 15
    assert all(v.route == "streamed" for v in best.validated)
    (B, b0), = spy.fits
    masks = val.fold_masks(np.zeros(rows))
    ones = np.ones(rows, np.float32)
    for f in range(3):
        for g, grid in enumerate(grids):
            ref = RW.fit(X, np.asarray(y), ones, masks[f],
                         grid["reg_param"], grid["elastic_net_param"],
                         max_iter=12, tol=1e-6)
            scale = 1.0 / ref["inv_std"]
            assert np.abs((B[f, g] - ref["beta"]) * scale).max() < 2e-4, \
                (f, g)
            assert abs(b0[f, g] - ref["b0"]) < 2e-4, (f, g)
    # the fold metric is the exact AuPR of those coefficients
    top = max(best.validated, key=lambda v: v.mean_metric)
    g = grids.index(dict(top.grid))
    for f in range(3):
        m = RW.margins(X, B[f, g], b0[f, g])
        exact = reference.numpy_au_pr(m, np.asarray(y), 1.0 - masks[f])
        assert abs(top.fold_metrics[f] - exact) < 1e-5


def test_bf16_counts_fit_like_float32():
    """A bfloat16 matrix of counts is exact, and the two-part coefficients
    keep its contractions float32-accurate: the same fit as on float32."""
    X, y = _hashed(2048, 32)
    masks = _fold_masks(2048, 2)
    kw = dict(max_iter=10, tol=1e-6)
    B32, b32, _ = _wide(X, y, masks, [0.01, 0.1], [0.5, 0.1], **kw)
    B16, b16, _ = _wide(X.astype(jnp.bfloat16), y, masks, [0.01, 0.1],
                        [0.5, 0.1], **kw)
    assert np.abs(B16 - B32).max() < 5e-5 and np.abs(b16 - b32).max() < 5e-5


# -- the shared curvature matrix -----------------------------------------------

def test_gram_is_additive_over_rows_and_bounds_every_fold():
    """For fixed centre and scale the Gram is linear in the row weights:
    the all-rows Gram less a fold's held-out Gram IS that fold's masked
    (training) Gram. The difference, the held-out Gram, is positive
    semidefinite — which is why the all-rows Gram bounds every fold's
    curvature and one matrix serves the sweep."""
    X, _ = _hashed(1500, 32)
    masks = _fold_masks(1500, 3)
    w = jnp.asarray(np.random.default_rng(5).uniform(0.5, 1.5, 1500)
                    .astype(np.float32))
    mean, std = GS.glm_standardize_stats(X, w)
    inv_std = 1.0 / std
    G_all, lam = GS.wide_gram(X, w, mean, inv_std)
    twin = RW.gram_twin(np.asarray(X), np.asarray(w), mean, inv_std)
    assert np.abs(np.asarray(G_all) - twin).max() \
        <= 1e-5 * np.abs(twin).max()
    top = np.linalg.eigvalsh(twin)[-1]
    assert top <= float(lam) <= 1.06 * top
    for f in range(3):
        m = jnp.asarray(masks[f])
        G_train, _ = GS.wide_gram(X, w * m, mean, inv_std)
        G_held, _ = GS.wide_gram(X, w * (1.0 - m), mean, inv_std)
        # wide_gram centres with sum(w) of ITS weights: add the centre back
        # to compare the raw sums
        def raw(G, ww):
            mu = np.asarray(mean) * np.asarray(inv_std)
            return np.asarray(G) + float(ww.sum()) * np.outer(mu, mu)
        diff = raw(G_all, w) - raw(G_held, w * (1.0 - m))
        assert np.abs(diff - raw(G_train, w * m)).max() \
            <= 1e-4 * np.abs(twin).max()
        held = raw(G_held, w * (1.0 - m))
        assert np.linalg.eigvalsh(0.5 * (held + held.T))[0] \
            >= -1e-4 * np.abs(twin).max()


def test_standardisation_in_the_algebra_equals_a_standardised_copy():
    """Centre and scale applied to coefficients and moments give the fit a
    standardised copy of X gives: X itself is read as it is."""
    X, y = _hashed(2048, 32)
    masks = _fold_masks(2048, 2)
    mean, std = GS.glm_standardize_stats(X, jnp.ones(2048, jnp.float32))
    Xs = (X - mean[None, :]) / std[None, :]
    kw = dict(max_iter=15, tol=1e-6)
    B, b0, _ = _wide(X, y, masks, [0.01, 0.1], [0.5, 0.1],
                     standardize=True, **kw)
    Bc, b0c, _ = _wide(Xs, y, masks, [0.01, 0.1], [0.5, 0.1],
                       standardize=False, **kw)
    Bs = B * np.asarray(std)[None, None, :]
    assert np.abs(Bs - Bc).max() < 1e-4
    b0s = b0 + (B * np.asarray(mean)[None, None, :]).sum(2)
    assert np.abs(b0s - b0c).max() < 1e-4


@pytest.mark.parametrize("d", [64, 128])
def test_wide_and_narrow_rounds_reach_the_same_optimum(d):
    """Where both run (the wide driver takes any width when called
    directly): ridge lanes of the narrow IRLS rounds and of the wide rounds
    end at the same coefficients, and the wide rounds' elastic-net lanes
    satisfy the optimality conditions of the objective."""
    rng = np.random.default_rng(d)
    n = 4000
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, :4] += rng.normal(size=(n, 1)).astype(np.float32)
    beta = np.zeros(d, np.float32)
    beta[:12] = rng.normal(size=12) * 0.6
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ beta - 0.8)))) \
        .astype(np.float32)
    masks = _fold_masks(n, 2)
    regs, alphas = [0.01, 0.1, 0.02], [0.0, 0.0, 0.5]
    Bw, b0w, info = _wide(X, y, masks, regs, alphas, max_iter=600, tol=1e-7)
    assert info["lanes_retired"] == 6
    Bn, b0n, _ = GS.sweep_glm_streamed_rounds(
        jnp.asarray(X), jnp.asarray(y), jnp.ones(n, jnp.float32),
        jnp.asarray(masks), np.asarray(regs, np.float32),
        np.asarray(alphas, np.float32), loss="logistic", max_iter=100,
        tol=1e-7)
    assert np.abs(Bw[:, :2] - Bn[:, :2]).max() < 2e-4
    assert np.abs(b0w[:, :2] - b0n[:, :2]).max() < 2e-4
    # elastic-net lane: 0 in the subdifferential, on the standardised scale
    mean, std = (np.asarray(a) for a in GS.glm_standardize_stats(
        jnp.asarray(X), jnp.ones(n, jnp.float32)))
    Xs = (X - mean) / std
    for f in range(2):
        Bs = Bw[f, 2] * std
        eta = X @ Bw[f, 2] + b0w[f, 2]
        r = (1 / (1 + np.exp(-eta)) - y) * masks[f]
        g = Xs.T @ r / masks[f].sum() + 0.01 * Bs
        on = Bs != 0
        assert on.sum() < d
        assert np.abs(g[on] + 0.01 * np.sign(Bs[on])).max() < 2e-5
        assert np.abs(g[~on]).max() <= 0.01 + 2e-5
        assert abs(r.sum() / masks[f].sum()) < 2e-5


# -- the route guard -----------------------------------------------------------

def test_streamable_at_the_benchmark_shape_without_a_lane_hessian():
    """(786 432 x 4 104, 8 points x 5 folds) takes the streamed route on one
    device; what the wide rounds plan to hold holds no [L, d, d] term; a
    mesh, another loss and a narrow matrix keep the routes they had."""
    val = CrossValidation(Evaluators.BinaryClassification.au_pr(),
                          num_folds=5, seed=42)
    est = OpLogisticRegression(max_iter=50)
    grids = [{"reg_param": r, "elastic_net_param": a}
             for r in (0.001, 0.01, 0.1, 0.2) for a in (0.1, 0.5)]
    X = jax.ShapeDtypeStruct((786_432, 4_104), jnp.bfloat16)
    assert val._streamable(est, grids, "binary", X, 5)
    assert GS.streamed_wide_route_ok(4_104, 40, V.SWEEP_LANE_BUDGET_BYTES)
    assert not GS.streamed_route_ok(4_104, 40, V.SWEEP_LANE_BUDGET_BYTES)
    one_hessian_a_lane = 40 * 4_104 * 4_104 * 4.0
    assert GS.wide_footprint_bytes(4_104, 40) < 0.2 * one_hessian_a_lane
    # eight times the lanes: the planned bytes grow by blocks [rows, lanes]
    # and [lanes, d], not by lanes x d x d
    grown = GS.wide_footprint_bytes(4_104, 320) \
        - GS.wide_footprint_bytes(4_104, 40)
    assert grown < 0.02 * 7 * one_hessian_a_lane
    assert val._wide_rounds("logistic", 4_104)
    assert not val._wide_rounds("logistic", GS.TRI_MAX_D)
    assert not val._wide_rounds("squared_hinge", 4_104)
    val.mesh = object()
    assert not val._wide_rounds("logistic", 4_104)


def test_warm_seed_starts_every_lane_at_the_seed():
    X, y = _hashed(1024, 32)
    masks = _fold_masks(1024, 2)
    B, b0, _ = _wide(X, y, masks, [0.05], [0.1], max_iter=40, tol=1e-6)
    Bs, b0s, info = GS.sweep_glm_wide_streamed_rounds(
        X, y, jnp.ones(1024, jnp.float32), jnp.asarray(masks),
        np.asarray([0.05], np.float32), np.asarray([0.1], np.float32),
        max_iter=1, tol=1e-6, warm_seed=(B[0, 0], float(b0[0, 0])))
    assert info["warm_seeded"]
    # one iteration from fold 0's answer stays beside it
    assert np.abs(Bs[0, 0] - B[0, 0]).max() < 5e-3


def test_telemetry_and_span_name_the_body_of_the_pass(monkeypatch):
    """Which body the rounds' pass over X ran is in the sweep's telemetry
    (`round_kernel`) and on every round's span (`kernel`): the XLA blocks on
    the CPU (tests/test_wide_fused_kernel.py steers the other answer);
    `kernel` in the telemetry stays the route's name."""
    from transmogrifai_tpu.utils.metrics import collector
    monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
    X, y = _hashed(2048, 32, dtype="bfloat16")
    val = CrossValidation(Evaluators.BinaryClassification.au_pr(),
                          num_folds=2, seed=42)
    collector.disable()     # whatever an earlier test file left behind
    collector.enable("wide_round_kernel")
    try:
        val.validate([(OpLogisticRegression(max_iter=6, tol=1e-6),
                       [{"reg_param": 0.1, "elastic_net_param": 0.5}])], X, y)
        spans = [s for s in collector.trace.spans if s.kind == "sweep_round"]
    finally:
        collector.finish()
        collector.disable()
    tele = val.last_streamed_telemetry
    assert tele["kernel"] == "wide_rounds"
    assert tele["round_kernel"] == "xla_blocks"
    assert len(spans) == 2      # 6 iterations in rounds of 5
    assert all(s.name.startswith("glm_wide_round[") for s in spans)
    assert {s.attrs["kernel"] for s in spans} == {"xla_blocks"}
