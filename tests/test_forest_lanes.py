"""The forest lane route (ops/trees.fit_forest_lanes, models/trees._ForestBase):
(tree, fold) lanes of the fused passes grow the SAME trees as fit_forest /
grow_tree given the same bootstrap vectors and node subsets, binary and
regression; the minInfoGain scale of the one-channel binary payload; the
bootstrap draws; the gate and what it says when it declines; the route
through validate()."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.models import trees as MT
from transmogrifai_tpu.ops import pallas_hist as PH
from transmogrifai_tpu.ops import trees as T


def _data(n=2400, f=10, bins=8, folds=3, seed=0, regression=False):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    beta = rng.normal(size=f)
    if regression:
        y = (X @ beta + rng.normal(size=n)).astype(np.float32)
    else:
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-X @ beta))) \
            .astype(np.float32)
    Xb = T.bin_matrix(jnp.asarray(X), T.quantile_edges(jnp.asarray(X), bins))
    fold = rng.integers(0, folds, n)
    masks = (fold[None, :] != np.arange(folds)[:, None]).astype(np.float32)
    return X, Xb, jnp.asarray(y), jnp.asarray(masks)


def _lanes(Xb, y, W, key, *, n_trees, group, depth, bins, frac, **kw):
    """The whole forest as lane groups: (votes, trees by tree index)."""
    folds, n = W.shape
    votes = jnp.zeros((folds, n), jnp.float32)
    per_tree = []
    for start in range(0, n_trees, group):
        rw, kf = T.forest_bootstrap(key, start, 1.0, n_rows=n,
                                    n_trees=n_trees, group=group)
        votes, trees, subsets = T.fit_forest_lanes(
            Xb, y, W, rw, kf, votes, depth=depth, n_bins=bins,
            feature_frac=frac, **kw)
        for i in range(min(group, n_trees - start)):
            per_tree.append(jax.tree_util.tree_map(
                lambda a: np.asarray(a)[i * folds:(i + 1) * folds], trees))
    return votes, per_tree


@pytest.mark.parametrize("regression", [False, True],
                         ids=["binary", "regression"])
@pytest.mark.parametrize("sequential", ["fit_forest", "grow_tree"])
def test_lanes_grow_the_sequential_routes_trees(regression, sequential):
    """Decisions equal, leaves to float32 rounding, votes the traversal's
    sums; 3 trees in groups of 2, so the last group carries a dead slot."""
    _, Xb, y, W = _data(regression=regression)
    key = jax.random.PRNGKey(42)
    kw = dict(min_instances=5.0, min_info_gain=5e-4)
    n_trees, depth, bins, frac = 3, 4, 8, 0.3
    votes, lanes = _lanes(Xb, y, W, key, n_trees=n_trees, group=2,
                          depth=depth, bins=bins, frac=frac, **kw)
    for f in range(W.shape[0]):
        w = W[f]
        if sequential == "fit_forest":
            seq = T.fit_forest(Xb, (y * w)[:, None], w, key, n_trees=n_trees,
                               depth=depth, n_bins=bins, feature_frac=frac,
                               leaf_mode="mean", **kw)
            agg = T.predict_forest_bins(seq, Xb, depth)[:, 0]
            np.testing.assert_allclose(votes[f], agg, rtol=5e-5, atol=1e-5)
        for t in range(n_trees):
            if sequential == "fit_forest":
                one = jax.tree_util.tree_map(lambda a: a[t], seq)
            else:   # the same bootstrap vector and node-subset key by hand
                kb, kf = jax.random.split(jax.random.split(key, n_trees)[t])
                rw = T._bootstrap_weights(kb, Xb.shape[0], 1.0)
                one = T.grow_tree(Xb, (y * w * rw)[:, None], w * rw, kf,
                                  depth=depth, n_bins=bins, feature_frac=frac,
                                  leaf_mode="mean", normalize_gain=True, **kw)
            for name in ("feat", "thresh", "miss"):
                np.testing.assert_array_equal(
                    getattr(lanes[t], name)[f], getattr(one, name),
                    err_msg=f"{name} of tree {t}, fold {f}")
            np.testing.assert_allclose(lanes[t].leaf[f], one.leaf,
                                       rtol=1e-5, atol=1e-6)


def test_lanes_through_the_pallas_interpreter_match_the_jnp_twins():
    _, Xb, y, W = _data(n=1024, f=6, folds=2)
    rw, kf = T.forest_bootstrap(jax.random.PRNGKey(1), 0, 1.0, n_rows=1024,
                                n_trees=2, group=2)
    votes = jnp.zeros(W.shape, jnp.float32)
    kw = dict(depth=3, n_bins=8, feature_frac=0.5, min_instances=5.0,
              min_info_gain=5e-4)
    v0, t0, s0 = T.fit_forest_lanes(Xb, y, W, rw, kf, votes, **kw)
    v1, t1, s1 = T.fit_forest_lanes(Xb, y, W, rw, kf, votes, interpret=True,
                                    **kw)
    np.testing.assert_array_equal(t0.feat, t1.feat)
    np.testing.assert_array_equal(t0.thresh, t1.thresh)
    np.testing.assert_array_equal(s0, s1)
    np.testing.assert_allclose(v0, v1, rtol=1e-6, atol=1e-7)


def test_the_min_info_gain_scale_trap():
    """The one-channel binary gain is HALF the two-class Gini gain. A root
    whose two-class gain sits between 1x and 2x the threshold splits under
    the two-class payload and under one channel at half the threshold —
    and would not under one channel at the full one."""
    rng = np.random.default_rng(3)
    n = 4000
    x = rng.normal(size=(n, 1)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5 + 0.1 * np.sign(x[:, 0])) \
        .astype(np.float32)
    Xb = T.bin_matrix(jnp.asarray(x), T.quantile_edges(jnp.asarray(x), 8))
    yd, w = jnp.asarray(y), jnp.ones(n, jnp.float32)
    key = jax.random.PRNGKey(0)

    def root_split(G, thr):
        tree = T.grow_tree(Xb, G, w, key, depth=1, n_bins=8, leaf_mode="mean",
                           normalize_gain=True, min_info_gain=thr)
        return int(tree.thresh[0]) < 8          # 8 = dead: all rows left
    two = jax.nn.one_hot(yd.astype(jnp.int32), 2)
    # the root's two-class gain, by hand at the median split
    left = x[:, 0] < np.median(x[:, 0])
    p, pl, pr = y.mean(), y[left].mean(), y[~left].mean()
    gain2 = 2 * (0.5 * pl ** 2 + 0.5 * pr ** 2 - p ** 2)
    thr = gain2 / 1.5
    assert root_split(two, thr)
    assert root_split(yd[:, None], thr / 2)
    assert not root_split(yd[:, None], thr)
    # the lane route is handed Spark's threshold halved by the model
    W = jnp.ones((1, n), jnp.float32)
    rw = jnp.ones((1, n), jnp.float32)
    votes = jnp.zeros((1, n), jnp.float32)
    for scale, want in ((0.5, True), (1.0, False)):
        _, tree, _ = T.fit_forest_lanes(
            Xb, yd, W, rw, jax.random.split(key, 1), votes, depth=1,
            n_bins=8, min_info_gain=thr * scale)
        assert (int(tree.thresh[0, 0]) < 8) is want


def test_the_model_halves_the_threshold_for_the_one_channel_payload(
        monkeypatch):
    _, Xb, y, W = _data(n=600, f=4, folds=2)
    monkeypatch.setattr(MT, "FOREST_LANE_BACKENDS", ("cpu",))
    monkeypatch.setattr(MT, "FOREST_LANE_MIN_ROWS", 0)
    seen = {}
    real = T.fit_forest_lanes

    def spy(*a, **kw):
        seen.update(kw)
        return real(*a, **kw)
    monkeypatch.setattr(T, "fit_forest_lanes", spy)
    ctx = (Xb, None, 8)
    w = jnp.ones_like(y)
    for cls, scale, body, rows in (
            (MT.OpRandomForestClassifier, 0.5, "indicator", 3),
            (MT.OpRandomForestRegressor, 1.0, "centred_parts", 5)):
        est = cls(num_trees=2, max_depth=2, max_bins=8, min_info_gain=0.01,
                  min_instances_per_node=3)
        out = est.mask_fit_scores(ctx, y, w, W)
        assert out.shape == W.shape
        assert float(seen["min_info_gain"]) == pytest.approx(0.01 * scale)
        assert float(seen["min_instances"]) == 3.0
        # the payload's word reaches the fit as the predicate gives it,
        # and a real label goes with its centre (the 0/1 one with none)
        assert seen["payload"] == body == MT.forest_payload_body(est)
        tele = dict(est.last_lane_telemetry)
        centre = tele.pop("label_centre")
        assert centre is seen["centre"]
        if body == "indicator":
            assert centre is None
        else:   # [the label's mean to 8 bits, the power of two over g]
            assert abs(float(centre[0]) - float(y.mean())) < 0.01
            assert float(centre[1]) == 2.0 ** np.ceil(np.log2(
                16 * float(jnp.abs(y - centre[0]).max())))
        assert tele == dict(
            tree_lanes=4, lane_groups=1, lanes_per_group=4,
            bootstrap_draws=1200, payload_body=body, payload_rows=rows,
            features_per_node=2)


def test_bootstrap_vectors_differ_across_trees_and_fold_lanes_share_them(
        monkeypatch):
    n, folds = 200_000, 3
    key = jax.random.PRNGKey(7)
    rw, kf = T.forest_bootstrap(key, 2, 1.0, n_rows=n, n_trees=5, group=4)
    rw = np.asarray(rw)
    assert rw.shape == (4, n) and not rw[3].any()      # tree 5 of 5: dead
    for t in range(3):
        assert abs(rw[t].mean() - 1.0) < 0.01 and abs(rw[t].var() - 1.0) < 0.02
        # the largest of 200 000 Poisson(1) draws: 7 or so. A CDF summed
        # from the top stuck under 1 in float32 on the chip and drew 24s
        assert rw[t].max() <= 11
    c = np.corrcoef(rw[:3])
    assert np.abs(c - np.eye(3)).max() < 0.01
    # Poisson(1): P(0) = P(1) = 1/e, P(2) = 1/2e
    assert abs((rw[0] == 0).mean() - np.exp(-1)) < 5e-3
    assert abs((rw[0] == 2).mean() - np.exp(-1) / 2) < 5e-3
    # tree t of the forest is the same tree whatever group it falls in,
    # and fit_forest's: split(key, n_trees)[t] -> (bootstrap, nodes)
    rw0, kf0 = T.forest_bootstrap(key, 0, 1.0, n_rows=n, n_trees=5, group=3)
    np.testing.assert_array_equal(np.asarray(rw0)[2], rw[0])
    np.testing.assert_array_equal(np.asarray(kf0)[2], np.asarray(kf)[0])
    kb, _ = jax.random.split(jax.random.split(key, 5)[2])
    np.testing.assert_array_equal(T._bootstrap_weights(kb, n, 1.0), rw[0])
    # no bootstrap: a 0/1 draw at the rate
    sub, _ = T.forest_bootstrap(key, 0, 0.7, n_rows=n, n_trees=1, group=1,
                                bootstrap=False)
    assert set(np.unique(sub)) == {0.0, 1.0} \
        and abs(float(sub.mean()) - 0.7) < 0.01
    # lane (t, f) weighs rw[t] * W[f]: the folds of a tree share its vector
    seen = {}
    real = T._grow_tree_folds

    def spy(Xb_t, G, H, **kw):
        seen["H"], seen["keys"] = H, kw["node_keys"]
        return real(Xb_t, G, H, **kw)
    monkeypatch.setattr(T, "_grow_tree_folds", spy)
    _, Xb, y, W = _data(n=512, f=4, folds=folds)
    rw, kf = T.forest_bootstrap(key, 0, 1.0, n_rows=512, n_trees=2, group=2)

    def lanes(*args):
        """The program traced ONCE around the spy (op by op, untraced, it
        took 40 s of a worker): what the spy saw comes out as results."""
        T.fit_forest_lanes.__wrapped__(*args, depth=2, n_bins=8,
                                       feature_frac=0.5)
        return seen["H"], seen["keys"]
    # tmoglint: disable=TRC001  one call
    seen["H"], seen["keys"] = jax.jit(lanes)(Xb, y, W, rw, kf,
                                             jnp.zeros(W.shape))
    H = np.asarray(seen["H"])[:, :512]
    for t in range(2):
        for f in range(folds):
            np.testing.assert_array_equal(
                H[t * folds + f], np.asarray(rw[t]) * np.asarray(W[f]))
    assert seen["keys"].shape == (2, 2)


def test_the_gate_declines_and_says_why(monkeypatch):
    rf = MT.OpRandomForestClassifier(num_trees=4, max_depth=3, max_bins=8)
    # this backend has no fused kernels by default
    assert rf.forest_lane_plan(10_000_000, 64, 5) == \
        (0, "backend cpu: no fused kernels to run on")
    assert not MT.forest_lane_route_ok(rf, 10_000_000, 64, 5)
    monkeypatch.setattr(MT, "FOREST_LANE_BACKENDS", ("tpu", "cpu"))
    group, why = rf.forest_lane_plan(10_000_000, 64, 5)
    assert group >= 1 and why == "" \
        and MT.forest_lane_route_ok(rf, 10_000_000, 64, 5)
    assert "fold-vmap limit" in rf.forest_lane_plan(2_000_000, 64, 5)[1]
    # a multiclass sweep's K class channels have the route since PR 54
    # (tests/test_forest_multiclass_lanes.py), until K outgrows a group
    assert rf.forest_lane_plan(10_000_000, 64, 5, n_classes=3,
                               multiclass=True)[0] >= 1
    assert MT.forest_lane_route_ok(rf, 10_000_000, 64, 5, multiclass=True)
    wide = rf.copy(max_depth=6, max_bins=32)
    assert "K = 40" in wide.forest_lane_plan(
        10_000_000, 64, 5, n_classes=40, multiclass=True)[1]
    loose = rf.copy(min_instances_per_node=0)
    assert "min_instances_per_node < 1" in loose.forest_lane_plan(
        10_000_000, 64, 5)[1]
    deep = rf.copy(max_depth=12)
    assert "depth 12" in deep.forest_lane_plan(10_000_000, 64, 5)[1]
    # a regressor and a single tree take it where it falls out
    assert MT.forest_lane_route_ok(
        MT.OpRandomForestRegressor(num_trees=4, max_depth=3), 10_000_000,
        64, 5)
    assert MT.OpDecisionTreeClassifier(max_depth=3).forest_lane_plan(
        10_000_000, 64, 5) == (1, "")
    # what declines at run time says so in an event, and falls back
    monkeypatch.setattr(MT, "FOREST_LANE_MIN_ROWS", 0)
    _, Xb, y, W = _data(n=512, f=4, folds=2)
    events = []
    from transmogrifai_tpu.utils.metrics import collector
    monkeypatch.setattr(collector, "event",
                        lambda name, **kw: events.append((name, kw)))
    w = jnp.ones_like(y)
    assert deep._mask_scores_fused((Xb, None, 8), y, w, W, 2, False) is None
    assert wide._mask_scores_fused(
        (jnp.zeros((512, 64), jnp.int8), None, 33), y, w, W, 100,
        True) is None
    devs = jax.devices()
    if len(devs) > 1:    # a mesh: the binned matrix over several devices
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(devs[:2]), ("batch",))
        Xs = jax.device_put(Xb, NamedSharding(mesh, P("batch", None)))
        assert rf._mask_scores_fused((Xs, None, 8), y, w, W, 2, False) is None
        assert "mesh" in events[-1][1]["reason"]
    assert [e[0] for e in events] == \
        ["forest_lane_route_declined"] * len(events)
    assert "depth 12" in events[0][1]["reason"] \
        and "K = 100" in events[1][1]["reason"]


def test_the_planner_sizes_a_group(monkeypatch):
    from transmogrifai_tpu.utils import platform as PL
    spec = PL.DEVICE_SPECS["TPU v5 lite"]
    monkeypatch.setattr(PL, "device_spec", lambda *a: spec)
    # the cell: 20 trees at a most of 6 a group -> 4 groups of 5 (25 lanes)
    assert PH.plan_forest_group(10_000_000, 64, 33, 5, 20, 6) == 5
    assert PH.plan_forest_group(10_000_000, 64, 33, 5, 50, 6) == 6
    assert PH.plan_forest_group(10_000_000, 64, 33, 5, 3, 6) == 3
    # depth 12: the slot-dense output block alone is over the VMEM limit
    assert PH.plan_forest_group(10_000_000, 64, 33, 5, 50, 12) == 0
    # rows count: the lane planes are what grows with them
    assert PH.plan_forest_group(25_000_000, 64, 33, 5, 50, 6) == 1
    plan = PH.plan_fused_hist(64, 33, 25, 6)
    assert plan.out_bytes == 25 * 16 * 3 * 2112 * 4 \
        <= PH._FOREST_OUT_BLOCK_BYTES < 16 << 20


@pytest.mark.parametrize("cls,problem", [
    (MT.OpRandomForestClassifier, "binary"),
    (MT.OpRandomForestRegressor, "regression"),
    (MT.OpDecisionTreeClassifier, "binary")],
    ids=["rf-classifier", "rf-regressor", "decision-tree"])
def test_validate_takes_the_lane_route_and_answers_like_the_sequential_one(
        cls, problem, monkeypatch):
    from transmogrifai_tpu.automl.tuning.validators import CrossValidation
    from transmogrifai_tpu.evaluators.evaluators import Evaluators
    X, _, y, _ = _data(n=3000, f=8, regression=problem == "regression")
    ev = Evaluators.Regression.rmse() if problem == "regression" \
        else Evaluators.BinaryClassification.au_pr()
    params = dict(max_depth=3, max_bins=8, min_info_gain=0.001)
    if cls is not MT.OpDecisionTreeClassifier:
        params["num_trees"] = 3
    grids = [{"min_instances_per_node": 10}, {"min_instances_per_node": 100}]

    def run():
        val = CrossValidation(ev, num_folds=3, seed=42, mesh=None)
        best = val.validate([(cls(**params), grids)], jnp.asarray(X), y,
                            problem_type=problem)
        return best, val
    monkeypatch.setenv("TMOG_NO_HOST_TREES", "1")   # the device trees
    seq, val0 = run()
    assert [v.route for v in seq.validated] == ["mask_folds"] * 2
    assert val0.last_tree_telemetry is None
    monkeypatch.setattr(MT, "FOREST_LANE_BACKENDS", ("tpu", "cpu"))
    monkeypatch.setattr(MT, "FOREST_LANE_MIN_ROWS", 0)
    monkeypatch.delenv("TMOG_NO_HOST_TREES")        # lanes win over host
    lanes, val1 = run()
    assert [v.route for v in lanes.validated] == \
        ["mask_folds:forest_lanes"] * 2
    for a, b in zip(seq.validated, lanes.validated):
        np.testing.assert_allclose(a.fold_metrics, b.fold_metrics,
                                   rtol=2e-5)
    trees = params.get("num_trees", 1)
    tele = dict(val1.last_tree_telemetry)
    want = {
        "model": cls.__name__, "route": "forest_lanes",
        "tree_lanes": 2 * trees * 3, "lane_groups": 2,
        "lanes_per_group": trees * 3, "bootstrap_draws": 2 * trees * 3000}
    if problem == "regression":
        # how the real-valued payload was carried, and two host floats: the
        # label's centre and the power of two its payload was divided by
        assert abs(tele.pop("label_centre") - float(y.mean())) < 0.01
        assert np.log2(tele.pop("payload_scale")) % 1 == 0
        want.update(payload_body="centred_parts", payload_rows=5,
                    features_per_node=3)
    assert tele == want    # a 0/1 label's lanes: the dict of PR 31
