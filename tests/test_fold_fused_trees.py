"""Fold-fused tree kernels: parity in pallas interpret mode (CPU).

The fold-fused sweep path (ops/trees.fit_gbt_folds + the fold axis on
pallas_hist.hist_pallas / route_pallas / table_lookup_pallas) exists so the
10M-row tree sweep reads the binned matrix once per level for ALL CV folds
(BENCH_NOTES round-4 session 2). Correctness story, strongest first:

  1. kernel-level: fold-fused outputs == per-fold single calls, exactly
     (each fold's contraction rows are disjoint, so fusion must not change
     a single bit);
  2. fused Fo>1 == the same fused program run per fold (Fo=1): the fold
     axis only batches;
  3. fit-level sanity vs the CPU segment-sum path at the metric level
     (different histogram algebra -> near-tie splits may differ, so this
     one is loose by design).

Reference workload: XGBoost hist-method CV (SURVEY §2.9); the mask-fold
protocol is models/trees.mask_fit_scores.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from transmogrifai_tpu.ops import pallas_hist as PH
from transmogrifai_tpu.ops import trees as T


def _data(n=640, f=5, b=7, folds=3, seed=0):
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, b + 1, size=(n, f)).astype(np.int8)  # 0 = missing
    y = (rng.uniform(size=n) < 0.4).astype(np.float32)
    masks = (rng.integers(0, folds, size=n)[None, :]
             != np.arange(folds)[:, None]).astype(np.float32)
    return jnp.asarray(Xb), jnp.asarray(y), jnp.asarray(masks)


def test_hist_fold_axis_matches_single_fold_calls():
    Xb, y, masks = _data()
    n, f = Xb.shape
    folds, B, S = masks.shape[0], 8, 4
    rng = np.random.default_rng(1)
    pay = jnp.asarray(rng.normal(size=(folds * 3, n)).astype(np.float32))
    slot = jnp.asarray(rng.integers(0, S + 1, size=(folds, n))
                       .astype(np.float32))  # S drops the row
    fused = PH.hist_pallas(Xb.T, pay, slot, n_slots=S, n_bins=B,
                           interpret=True)
    for k in range(folds):
        one = PH.hist_pallas(Xb.T, pay[3 * k:3 * k + 3], slot[k:k + 1],
                             n_slots=S, n_bins=B, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(fused[k * S * 3:(k + 1) * S * 3]), np.asarray(one))


def test_route_pallas_matches_xla_route():
    Xb, _, _ = _data(n=514, f=6, b=7)  # ragged: exercises padding
    n, f = Xb.shape
    folds, n_nodes = 3, 4
    rng = np.random.default_rng(2)
    node = rng.integers(0, n_nodes, size=(folds, n))
    f_lvl = jnp.asarray(rng.integers(0, f, size=(folds, n_nodes)), jnp.int32)
    t_lvl = jnp.asarray(rng.integers(0, 8, size=(folds, n_nodes)), jnp.int32)
    m_lvl = jnp.asarray(rng.integers(0, 2, size=(folds, n_nodes)), jnp.int32)
    got = PH.route_pallas(Xb.T, jnp.asarray(node, jnp.float32)[...],
                          f_lvl, t_lvl, m_lvl, n_nodes=n_nodes,
                          interpret=True)
    for k in range(folds):
        want = T._route_level_matmul(Xb, jnp.asarray(node[k], jnp.int32),
                                     f_lvl[k], t_lvl[k], m_lvl[k], n_nodes)
        np.testing.assert_array_equal(np.asarray(got[k]).astype(np.int32),
                                      np.asarray(want))


def test_table_lookup_pallas():
    rng = np.random.default_rng(3)
    folds, M, n = 4, 16, 517
    tbl = jnp.asarray(rng.normal(size=(folds, M)).astype(np.float32))
    idx = rng.integers(0, M, size=(folds, n))
    got = PH.table_lookup_pallas(tbl, jnp.asarray(idx, jnp.float32),
                                 interpret=True)
    want = np.take_along_axis(np.asarray(tbl), idx, axis=1)
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.float32))


@pytest.mark.parametrize("loss,subsample,unit_w", [
    ("logistic", 1.0, True), ("squared", 1.0, True),
    ("logistic", 0.7, True),
    # non-unit row weights exercise base-score/gradient/count semantics
    # beyond the 0/1 fold masks
    ("logistic", 1.0, False)])
def test_fused_folds_equal_fused_single_fold_runs(loss, subsample, unit_w):
    # n=801: ragged vs the 4096 block pad — padded rows must stay inert
    # in every payload channel (h EPS-clamp and count included)
    Xb, y, masks = _data(n=801, f=6, b=7, folds=3, seed=4)
    if unit_w:
        W = masks * 1.0
    else:
        rng = np.random.default_rng(9)
        W = masks * jnp.asarray(
            rng.uniform(0.5, 2.0, size=y.shape[0]).astype(np.float32))
    kw = dict(n_rounds=3, depth=3, n_bins=7, learning_rate=0.3,
              reg_lambda=1.0, loss=loss, subsample=subsample,
              interpret=True)
    fit = functools.partial(T.fit_gbt_folds, Xb, y, key=jax.random.PRNGKey(7),
                            **kw)
    trees, base, margins = fit(W=W)
    for k in range(W.shape[0]):
        _, base1, m1 = fit(W=W[k:k + 1])
        np.testing.assert_array_equal(np.asarray(margins[k]),
                                      np.asarray(m1[0]))
        assert float(base[k]) == float(base1[0])


def test_fused_fit_close_to_cpu_fit_at_metric_level():
    """Loose cross-path check: the CPU fit uses segment-sum histograms
    without sibling subtraction, so individual splits may differ on
    near-ties; weighted train logloss of the fitted margins must agree."""
    Xb, y, masks = _data(n=900, f=6, b=7, folds=2, seed=5)
    W = masks * 1.0
    _, base, margins = T.fit_gbt_folds(
        Xb, y, W, jax.random.PRNGKey(3), n_rounds=4, depth=3, n_bins=7,
        learning_rate=0.3, reg_lambda=1.0, loss="logistic", interpret=True)

    def logloss(m, wv):
        p = 1.0 / (1.0 + np.exp(-np.asarray(m, np.float64)))
        yv = np.asarray(y, np.float64)
        ll = -(yv * np.log(p + 1e-9) + (1 - yv) * np.log(1 - p + 1e-9))
        return float((ll * wv).sum() / wv.sum())

    for k in range(W.shape[0]):
        trees_k, base_k = T.fit_gbt(
            Xb, y, jnp.asarray(W[k]), jax.random.PRNGKey(3), n_rounds=4,
            depth=3, n_bins=7, learning_rate=0.3, reg_lambda=1.0,
            loss="logistic")
        m_cpu = base_k + T.predict_forest_bins(trees_k, Xb, 3)[:, 0]
        wv = np.asarray(W[k], np.float64)
        assert abs(logloss(margins[k], wv) - logloss(m_cpu, wv)) < 0.02


def test_mask_fit_scores_routes_through_fused_hook(monkeypatch):
    """Wiring: when the gate opens, mask_fit_scores hands the booster's
    grid params and per-fold weights to fit_gbt_folds and returns its
    margins unchanged (no re-predict)."""
    from transmogrifai_tpu.models.trees import OpXGBoostClassifier

    Xb, y, masks = _data(n=300, f=5, b=7, folds=3, seed=6)
    est = OpXGBoostClassifier(num_round=4, max_depth=3, eta=0.2,
                              reg_lambda=2.0)
    ctx = (Xb, None, 7)
    seen = {}

    def fake_fit_gbt_folds(Xb_a, y_a, W_a, key, **kw):
        seen.update(kw, W=np.asarray(W_a))
        nodes = jnp.zeros((kw["n_rounds"], W_a.shape[0], 7), jnp.int32)
        stumps = T.Tree(nodes, nodes, jnp.zeros(nodes.shape[:2] + (8, 1)),
                        nodes)
        return stumps, None, jnp.full((W_a.shape[0], y_a.shape[0]), 0.5)

    monkeypatch.setattr(T, "fit_gbt_folds", fake_fit_gbt_folds)
    monkeypatch.setattr(type(est), "_fused_route_ok",
                        lambda self, ctx, y, masks=None, depth=None: True)
    w = jnp.ones_like(y)
    out = est.mask_fit_scores(ctx, y, w * 2.0, masks)
    assert out.shape == (3, 300) and float(out[0, 0]) == 0.5
    assert seen["n_rounds"] == 4 and seen["depth"] == 3
    assert seen["learning_rate"] == pytest.approx(0.2)
    assert seen["reg_lambda"] == pytest.approx(2.0)
    assert seen["loss"] == "logistic"
    np.testing.assert_allclose(seen["W"], np.asarray(masks) * 2.0)


def test_config_fused_lanes_match_per_config_calls():
    """The config-fused sweep's per-lane eta/lambda/gamma/mcw vectors:
    lanes = (config, fold) pairs must reproduce each config's own
    fold-fused fit EXACTLY (each lane's contraction rows are disjoint, so
    batching configs into the fold axis must not change a bit)."""
    Xb, y, masks = _data(n=640, f=5, b=7, folds=2, seed=3)
    w = jnp.ones_like(y)
    key = jax.random.PRNGKey(42)
    configs = [
        dict(learning_rate=0.1, reg_lambda=1.0, min_child_weight=0.0,
             gamma=0.0),
        dict(learning_rate=0.3, reg_lambda=5.0, min_child_weight=2.0,
             gamma=0.1),
        dict(learning_rate=0.05, reg_lambda=0.5, min_child_weight=1.0,
             gamma=0.0),
    ]
    F = masks.shape[0]
    W = masks * w[None, :]
    kw = dict(n_rounds=3, depth=3, n_bins=8, interpret=True)

    W_lanes = jnp.concatenate([W for _ in configs], axis=0)
    lane = {k: jnp.repeat(jnp.asarray([c[k] for c in configs],
                                      jnp.float32), F)
            for k in configs[0]}
    _, base_l, marg_l = T.fit_gbt_folds(Xb, y, W_lanes, key, **kw, **lane)

    for ci, c in enumerate(configs):
        _, base_1, marg_1 = T.fit_gbt_folds(Xb, y, W, key, **kw, **c)
        np.testing.assert_array_equal(
            np.asarray(base_l[ci * F:(ci + 1) * F]), np.asarray(base_1),
            err_msg=f"base config {ci}")
        np.testing.assert_array_equal(
            np.asarray(marg_l[ci * F:(ci + 1) * F]), np.asarray(marg_1),
            err_msg=f"margins config {ci}")


def test_grid_fuse_signature_groups_correctly():
    from transmogrifai_tpu.models.trees import (
        OpGBTClassifier, OpXGBoostClassifier,
    )
    est = OpXGBoostClassifier(num_round=5, max_depth=3, max_bins=16)
    s1 = est.grid_fuse_signature({"eta": 0.1, "reg_lambda": 1.0})
    s2 = est.grid_fuse_signature({"eta": 0.3, "reg_lambda": 5.0})
    s3 = est.grid_fuse_signature({"eta": 0.1, "max_depth": 4})
    assert s1 == s2          # algebra scalars fuse
    assert s1 != s3          # structure (depth) splits
    gbt = OpGBTClassifier(max_iter=3, max_depth=3, max_bins=16)
    g1 = gbt.grid_fuse_signature({"step_size": 0.1})
    g2 = gbt.grid_fuse_signature({"step_size": 0.2})
    g3 = gbt.grid_fuse_signature({"subsampling_rate": 0.8})
    assert g1 == g2
    assert g1 != g3          # subsample draw must match to share a key
