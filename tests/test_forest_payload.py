"""A real-valued payload through the fused histogram passes (PR 46): the
kernels' jnp twins and the interpret-mode kernels under w x y of mean 10
against float64 sums — exact bfloat16 parts hold them to float32 accuracy,
one part fails the same assertion; fit_forest_lanes under a centred label
against the sequential fit_forest and against the benchmark's plain
reference on exact sums; Spark's variance gain against both thresholds, a
node that 0.01 stops and 0.001 splits; the node subset's count, Spark's
ceiling; the predicate's word on the spans and in the telemetry."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.models import trees as MT
from transmogrifai_tpu.ops import pallas_hist as PH
from transmogrifai_tpu.ops import trees as T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import reference  # noqa: E402
from benchmark import reference_forest_reg as RR  # noqa: E402


def _real(n=4096, f=6, bins=8, folds=2, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (10 + 1.7 * (X @ rng.normal(size=f) / np.sqrt(f)
                     + 0.65 * rng.normal(size=n))).astype(np.float32)
    Xb = T.bin_matrix(jnp.asarray(X), T.quantile_edges(jnp.asarray(X), bins))
    fold = rng.integers(0, folds, n)
    masks = (fold[None, :] != np.arange(folds)[:, None]).astype(np.float32)
    return X, Xb, y, masks


def _g_share(got, ref, mass, lanes, slots):
    """Largest |sum of w x y - float64 sum| as a share of the cell's mass,
    and whether the weight and count rows are the exact whole numbers."""
    g = np.asarray(got, np.float64).reshape(lanes, slots, 3, -1)
    r, m = ref.reshape(g.shape), mass.reshape(g.shape)
    share = np.abs(g[:, :, 0] - r[:, :, 0]) / (m[:, :, 0] + 1e-30)
    return float(share[m[:, :, 0] > 0].max()), \
        bool(np.array_equal(g[:, :, 1:], r[:, :, 1:]))


@pytest.mark.parametrize("kernel", ["hist_folds", "route_hist"])
@pytest.mark.parametrize("how", ["twin", "parts3", "parts1"])
def test_histogram_sums_of_a_real_payload_against_float64(kernel, how):
    """w x y with y ~ 10: the jnp twin and the three-part kernel hold every
    cell's sum to float32 accuracy of the float64 sum; the one-part kernel
    (the program before PR 46) is off by ~2^-9 / sqrt(rows a cell)."""
    _, Xb, y, masks = _real(n=2048)
    lanes, S, B, N = 4, 2, 9, 2048
    rng = np.random.default_rng(1)
    w = rng.poisson(1.0, (lanes, N)).astype(np.float32) * masks[
        np.arange(lanes) % 2]
    # over a power of two that brings it into the parts' [-1, 1]
    pay = np.stack([w * y[None, :] / 256.0, w],
                   axis=1).reshape(2 * lanes, N)
    Xb_t = np.asarray(Xb).T
    kw = dict(n_bins=B, allow_bf16=True, derive_count=True,
              interpret=how != "twin",
              payload_parts=1 if how == "parts1" else 3)
    if kernel == "hist_folds":
        slot = rng.integers(0, S + 1, (lanes, N)).astype(np.float32)
        got = PH.hist_folds(jnp.asarray(Xb_t), jnp.asarray(pay),
                            jnp.asarray(slot), n_slots=S, **kw)
        ref = reference.hist_plain(Xb_t, pay, slot, S, B, True)
        mass = reference.hist_plain(Xb_t, np.abs(pay), slot, S, B, True)
    else:
        node = rng.integers(0, S, (lanes, N)).astype(np.float32)
        tabs = [rng.integers(0, hi, (lanes, S)).astype(np.int32)
                for hi in (Xb_t.shape[0], B, 2)]
        got, routed = PH.route_hist(
            jnp.asarray(Xb_t), jnp.asarray(pay), jnp.asarray(node),
            *map(jnp.asarray, tabs), n_nodes=S, **kw)
        ref, want = reference.route_hist_plain(Xb_t, pay, node, *tabs, S, B,
                                               True)
        mass, _ = reference.route_hist_plain(Xb_t, np.abs(pay), node, *tabs,
                                             S, B, True)
        assert np.array_equal(np.asarray(routed), want)
    assert got.shape == ref.shape      # parts are summed: 3 rows a slot
    worst, whole = _g_share(got, ref, mass, lanes, S)
    assert whole
    if how == "parts1":
        assert worst > 1e-4
    else:
        assert worst < 1e-6


def test_payload_rows_and_the_planner_budget_what_the_kernel_issues(
        monkeypatch):
    assert PH.payload_rows(2, 1, True) == 3 == T.forest_payload_rows(
        "indicator")
    assert PH.payload_rows(2, 3, True) == 5 == T.forest_payload_rows(
        "centred_parts")
    from transmogrifai_tpu.utils import platform as PL
    spec = PL.DEVICE_SPECS["TPU v5 lite"]
    monkeypatch.setattr(PL, "device_spec", lambda *a: spec)
    # the regression cell: 10 trees; five rows a slot leave 3 trees a group
    assert PH.plan_forest_group(10_000_000, 64, 33, 5, 10, 6, 3) == 5
    assert PH.plan_forest_group(10_000_000, 64, 33, 5, 10, 6, 5) == 3
    assert PH.plan_fused_hist(64, 33, 15, 6, 5).out_bytes \
        == 15 * 16 * 5 * 2112 * 4 <= PH._FOREST_OUT_BLOCK_BYTES
    assert PH.plan_fused_hist(64, 33, 20, 6, 5).out_bytes \
        > PH._FOREST_OUT_BLOCK_BYTES
    reg = MT.OpRandomForestRegressor(num_trees=10, max_depth=6, max_bins=32)
    clf = MT.OpRandomForestClassifier(num_trees=10, max_depth=6, max_bins=32)
    monkeypatch.setattr(MT, "FOREST_LANE_BACKENDS", ("tpu", "cpu"))
    assert reg.forest_lane_plan(10_000_000, 64, 5)[0] == 3
    assert clf.forest_lane_plan(10_000_000, 64, 5)[0] == 5
    assert MT.forest_payload_body(reg) == "centred_parts" \
        and MT.forest_payload_body(clf) == "indicator"


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["twins", "interpreter"])
def test_lanes_under_a_real_label_against_the_references(interpret):
    """The centred three-part lanes grow the sequential fit_forest's trees
    and obey the plain rule on exact sums: every split the best allowed by
    Spark's variance gain, every leaf the float64 weighted mean to float32
    accuracy. The one-part lanes (payload "indicator" under the same real
    label: the program before PR 46) fail the leaf assertion through the
    kernels."""
    X, Xb, y, masks = _real(n=4096, f=6)
    yd, W = jnp.asarray(y), jnp.asarray(masks)
    n_trees, depth, bins, frac = 2, 3, 8, 0.5
    key = jax.random.PRNGKey(7)
    rw, kf = T.forest_bootstrap(key, 0, 1.0, n_rows=len(y), n_trees=n_trees,
                                group=n_trees)
    kw = dict(depth=depth, n_bins=bins, feature_frac=frac, min_instances=5.0,
              min_info_gain=1e-3, interpret=interpret)
    centre = T.forest_label_centre(yd, jnp.ones_like(yd))
    # eight significant bits; the power of two over 16 x max |y - 10|
    assert float(centre[0]) == 10.0 and float(centre[1]) == 128.0
    votes0 = jnp.zeros(W.shape, jnp.float32)
    votes, grown, subsets = T.fit_forest_lanes(
        Xb, yd, W, rw, kf, votes0, payload="centred_parts", centre=centre,
        **kw)
    _, once, _ = T.fit_forest_lanes(Xb, yd, W, rw, kf, votes0, **kw)
    Xb_t = RR.binned(jnp.asarray(X), np.asarray(
        T.quantile_edges(jnp.asarray(X), bins)))
    assert np.array_equal(np.asarray(Xb_t), np.asarray(Xb).T)
    worst_once = 0.0
    for f in range(W.shape[0]):
        seq = T.fit_forest(Xb, (yd * W[f])[:, None], W[f], key,
                           n_trees=n_trees, depth=depth, n_bins=bins,
                           feature_frac=frac, leaf_mode="mean",
                           min_instances=5.0, min_info_gain=1e-3)
        np.testing.assert_allclose(
            votes[f], T.predict_forest_bins(seq, Xb, depth)[:, 0],
            rtol=2e-6)
        for t in range(n_trees):
            lane = t * W.shape[0] + f
            for name in ("feat", "thresh", "miss"):
                np.testing.assert_array_equal(
                    getattr(grown, name)[lane], getattr(seq, name)[t])
            tree = {k: np.asarray(getattr(grown, k)[lane])
                    for k in ("feat", "thresh", "miss")}
            tree["leaf"] = np.asarray(grown.leaf[lane, :, 0])
            r = RR.split_replay(
                Xb_t, yd, W[f] * rw[t], tree, np.asarray(subsets[t]),
                depth=depth, bins=bins + 1, min_instances=5.0,
                min_info_gain=1e-3)
            assert not r["not_allowed"] and not r["dead_but_allowed"]
            assert r["gain_shortfall"] < 1e-6 and r["splits"] >= 3
            assert r["subset_sizes"] == [3]
            assert r["leaf_worst"] < 2e-6 < 1e-3 < r["leaf_worst_if_bf16"]
            assert r["leaf_worst_if_one_part"] > 1e-4
            tree["leaf"] = np.asarray(once.leaf[lane, :, 0])
            worst_once = max(worst_once, RR.split_replay(
                Xb_t, yd, W[f] * rw[t], tree, np.asarray(subsets[t]),
                depth=depth, bins=bins + 1, min_instances=5.0,
                min_info_gain=1e-3)["leaf_worst"])
    # float32 sums of uncentred values on the twins, bfloat16 products
    # through the kernels: neither is the float64 mean to 2e-6
    assert worst_once > (1e-4 if interpret else 2e-6)


def test_the_variance_gain_meets_both_thresholds_unhalved():
    """One column that moves the label's mean by +-0.07: Spark's variance
    gain of the root is ~0.0049 in label^2 units. minInfoGain 0.001 splits
    it and 0.01 stops it — and half of 0.01 would stop it too, where a
    halved 0.008 would not: the regression lanes compare the threshold as
    the grid states it."""
    rng = np.random.default_rng(3)
    n = 6000
    x = rng.normal(size=(n, 1)).astype(np.float32)
    y = (10 + 0.07 * np.sign(x[:, 0]) + 0.3 * rng.normal(size=n)) \
        .astype(np.float32)
    Xb = T.bin_matrix(jnp.asarray(x), T.quantile_edges(jnp.asarray(x), 8))
    yd = jnp.asarray(y)
    W = jnp.ones((1, n), jnp.float32)
    rw, kf = T.forest_bootstrap(jax.random.PRNGKey(0), 0, 1.0, n_rows=n,
                                n_trees=1, group=1, bootstrap=False)
    centre = T.forest_label_centre(yd, W[0])
    G, H, C = RR.exact_level_sums(
        jnp.asarray(np.asarray(Xb).T), jnp.zeros(n, jnp.int32), W[0],
        RR.payload_rows(W[0], RR.fixed_point(yd), yd)[:1], 1, 9)
    gain = float(RR.variance_gains(G[0] - RR.OFFSET * H, H, C)[0].max())
    assert 0.004 < gain < 0.006

    def root_splits(thr):
        _, tree, _ = T.fit_forest_lanes(
            Xb, yd, W, rw, kf, jnp.zeros((1, n)), depth=1, n_bins=8,
            min_instances=10.0, min_info_gain=thr, payload="centred_parts",
            centre=centre)
        return int(tree.thresh[0, 0]) < 8       # 8 = dead: all rows left
    assert root_splits(0.001) and not root_splits(0.01)
    assert root_splits(0.004) and not root_splits(0.008)
    # the estimator hands the lanes its threshold as it stands
    est = MT.OpRandomForestRegressor(min_info_gain=0.01)
    assert not est.classification and est._one_channel(2, False)


@pytest.mark.parametrize("n_feat,strategy,classification,want", [
    (64, "auto", False, 22), (64, "onethird", True, 22),
    (64, "auto", True, 8), (10, "auto", False, 4), (10, "sqrt", True, 4),
    (9, "sqrt", True, 3), (9, "onethird", False, 3), (7, "0.5", False, 4),
    (64, "log2", True, 6), (5, "all", False, 5)])
def test_the_node_subset_is_sparks_ceiling(n_feat, strategy, classification,
                                           want):
    """round(64 / 3) = 21 and round(sqrt(10)) = 3 where Spark takes 22 and
    4; a fraction that float arithmetic left a hair over a whole count
    (sqrt(9) / 9 x 9) gains no column."""
    frac = MT._feature_frac(strategy, n_feat, classification)
    assert T.features_per_node(frac, n_feat) == want
    mask = T._feature_mask(jax.random.PRNGKey(1), 5, n_feat, frac)
    assert mask.shape == (5, n_feat)
    assert (np.asarray(mask).sum(axis=1) == want).all()


def test_the_native_builder_takes_the_same_ceiling():
    from transmogrifai_tpu.ops import trees_host as TH
    if not TH.available():
        pytest.skip("no native tree builder here")
    rng = np.random.default_rng(0)
    n, f = 400, 10
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X @ rng.normal(size=f)).astype(np.float32)
    edges = TH.quantile_edges_host(X, 8)
    Xb = TH.bin_matrix_host(X, edges)
    w = np.ones(n, np.float32)
    # a root may split only on its subset: over many one-level trees the
    # columns seen are drawn 4 of 10 a node, so every column turns up, and
    # with the subset at one column (frac 0.05 -> ceil 1) the root's choice
    # is as often a weak column as a strong one
    trees = TH.fit_forest_host(Xb, (y * w)[:, None], w, n_trees=200, depth=1,
                               n_bins=8, subsample=1.0, feature_frac=1 / 3,
                               min_instances=1.0, min_info_gain=0.0,
                               bootstrap=True, seed=1)
    assert trees is not None
    assert len(set(np.asarray(trees.feat)[:, 0].tolist())) >= 3


def test_the_spans_and_the_telemetry_carry_the_predicates_word(monkeypatch):
    from transmogrifai_tpu.automl.tuning.validators import CrossValidation
    from transmogrifai_tpu.evaluators.evaluators import Evaluators
    from transmogrifai_tpu.utils.metrics import collector
    X, _, y, _ = _real(n=3000, f=8)
    monkeypatch.setattr(MT, "FOREST_LANE_BACKENDS", ("tpu", "cpu"))
    monkeypatch.setattr(MT, "FOREST_LANE_MIN_ROWS", 0)
    est = MT.OpRandomForestRegressor(num_trees=3, max_depth=3, max_bins=8,
                                     min_instances_per_node=10)
    val = CrossValidation(Evaluators.Regression.rmse(), num_folds=3, seed=42,
                          mesh=None)
    collector.enable("forest_payload_test")
    try:
        best = val.validate(
            [(est, [{"min_info_gain": 0.001}, {"min_info_gain": 0.01}])],
            jnp.asarray(X), jnp.asarray(y), problem_type="regression")
        spans = {}
        for s in collector.trace.spans:
            spans.setdefault(f"{s.kind}:{s.name}", []).append(dict(s.attrs))
    finally:
        collector.finish()
        collector.disable()
    assert [v.route for v in best.validated] == \
        ["mask_folds:forest_lanes"] * 2
    word = MT.forest_payload_body(est)
    tele = val.last_tree_telemetry
    assert (tele["payload_body"], tele["payload_rows"],
            tele["features_per_node"]) == (word, 5, 3)
    assert abs(tele["label_centre"] - 10.0) < 0.1 \
        and tele["payload_scale"] == 128.0
    groups = spans["tree_fused:forest_group"]
    assert len(groups) == 2 and all(
        (g["payload_body"], g["payload_rows"], g["features_per_node"])
        == (word, 5, 3) for g in groups)
    metrics = spans["validate_phase:fold_metrics"]
    assert len(metrics) == 2 and all(
        m["metric"] == "rmse" and m["metric_body"] == "vmapped"
        for m in metrics)
    # the lower mean RMSE wins, and it is a real one
    means = [np.mean(v.fold_metrics) for v in best.validated]
    assert best.best_metric == pytest.approx(min(means))
    assert 0.5 < best.best_metric < float(np.std(y))
