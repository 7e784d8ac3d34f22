"""K class channels a (lane, slot) through the fused passes (PR 54): a
multiclass forest's (tree, fold) lanes grow fit_forest's trees under the
[N, K] one-hot x weight payload — Spark's K-class Gini gain whole, leaves
the weighted class distribution, votes class-major — through the kernels'
jnp twins and the Pallas interpreter; the planner counts what a K-channel
group lays out; the default MultiClassificationModelSelector takes the
route; and the binary, regression and booster programs are the parent's."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.models import trees as MT
from transmogrifai_tpu.ops import pallas_hist as PH
from transmogrifai_tpu.ops import trees as T

WORD = "class_indicators"


def _data(n=2400, f=10, bins=8, folds=3, K=7, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    B = 2.0 * rng.normal(size=(f, K))
    y = np.argmax(X @ B - np.log(np.arange(K) + 1.0)
                  + rng.gumbel(size=(n, K)), axis=1).astype(np.float32)
    Xb = T.bin_matrix(jnp.asarray(X), T.quantile_edges(jnp.asarray(X), bins))
    fold = rng.integers(0, folds, n)
    masks = (fold[None, :] != np.arange(folds)[:, None]).astype(np.float32)
    return X, Xb, jnp.asarray(y), jnp.asarray(masks)


def _lanes(Xb, y, W, key, *, K, n_trees, group, depth, bins, frac, **kw):
    """The whole forest as lane groups: (votes [folds, K, n], trees by
    tree index)."""
    folds, n = W.shape
    votes = jnp.zeros((folds, K, n), jnp.float32)
    per_tree = []
    for start in range(0, n_trees, group):
        rw, kf = T.forest_bootstrap(key, start, 1.0, n_rows=n,
                                    n_trees=n_trees, group=group)
        votes, trees, _ = T.fit_forest_lanes(
            Xb, y, W, rw, kf, votes, depth=depth, n_bins=bins,
            feature_frac=frac, payload=WORD, classes=K, **kw)
        for i in range(min(group, n_trees - start)):
            per_tree.append(jax.tree_util.tree_map(
                lambda a: np.asarray(a)[i * folds:(i + 1) * folds], trees))
    return votes, per_tree


@pytest.mark.parametrize("K", [3, 7])
@pytest.mark.parametrize("frac", [0.3, 1.0], ids=["subsets", "all_columns"])
def test_lanes_grow_fit_forests_trees_under_k_channels(K, frac):
    """Splits equal, leaves to float32, votes the traversal's sums; 3 trees
    in groups of 2, so the last group carries a dead slot. With every
    column a node the dead-level conds are in the program."""
    _, Xb, y, W = _data(K=K)
    key = jax.random.PRNGKey(42)
    kw = dict(min_instances=5.0, min_info_gain=1e-3)
    n_trees, depth, bins = 3, 3, 8
    votes, lanes = _lanes(Xb, y, W, key, K=K, n_trees=n_trees, group=2,
                          depth=depth, bins=bins, frac=frac, **kw)
    assert votes.shape == (W.shape[0], K, W.shape[1])
    for f in range(W.shape[0]):
        w = W[f]
        G = jax.nn.one_hot(y.astype(jnp.int32), K) * w[:, None]
        seq = T.fit_forest(Xb, G, w, key, n_trees=n_trees, depth=depth,
                           n_bins=bins, feature_frac=frac, leaf_mode="mean",
                           **kw)
        agg = T.predict_forest_bins(seq, Xb, depth)            # [n, K]
        np.testing.assert_allclose(votes[f].T, agg, rtol=5e-5, atol=1e-5)
        for t in range(n_trees):
            one = jax.tree_util.tree_map(lambda a: a[t], seq)
            for name in ("feat", "thresh", "miss"):
                np.testing.assert_array_equal(
                    getattr(lanes[t], name)[f], getattr(one, name),
                    err_msg=f"{name} of tree {t}, fold {f}")
            assert lanes[t].leaf[f].shape == (1 << depth, K)
            np.testing.assert_allclose(lanes[t].leaf[f], one.leaf,
                                       rtol=1e-5, atol=1e-6)
            # a leaf with rows is a distribution over the classes
            sums = lanes[t].leaf[f].sum(axis=1)
            assert np.all((np.abs(sums - 1) < 1e-5) | (sums == 0))


def test_lanes_through_the_pallas_interpreter_match_the_jnp_twins():
    K = 7
    _, Xb, y, W = _data(n=1024, f=6, folds=2, K=K)
    rw, kf = T.forest_bootstrap(jax.random.PRNGKey(1), 0, 1.0, n_rows=1024,
                                n_trees=2, group=2)
    votes = jnp.zeros((2, K, 1024), jnp.float32)
    kw = dict(depth=3, n_bins=8, feature_frac=0.5, min_instances=5.0,
              min_info_gain=1e-3, payload=WORD, classes=K)
    v0, t0, s0 = T.fit_forest_lanes(Xb, y, W, rw, kf, votes, **kw)
    v1, t1, s1 = T.fit_forest_lanes(Xb, y, W, rw, kf, votes, interpret=True,
                                    **kw)
    np.testing.assert_array_equal(t0.feat, t1.feat)
    np.testing.assert_array_equal(t0.thresh, t1.thresh)
    np.testing.assert_array_equal(s0, s1)
    np.testing.assert_allclose(t0.leaf, t1.leaf, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(v0, v1, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("kernel", ["hist_folds", "route_hist"])
@pytest.mark.parametrize("K", [2, 7])
def test_interpreted_kernels_at_k_plus_1_rows_equal_the_twins(kernel, K):
    """[class id, weight] in, the K class sums and the count out: whole
    numbers, so the bfloat16 contraction and the float32 segment sums
    agree to the bit; the channels add up to the weight sums."""
    rng = np.random.default_rng(K)
    F, N, B, lanes, S = 5, 4096, 9, 3, 4
    Xb_t = jnp.asarray(rng.integers(0, B, (F, N)), jnp.int8)
    ids = rng.integers(0, K, N).astype(np.float32)
    w = rng.poisson(1.0, (lanes, N)).astype(np.float32)
    pay = jnp.asarray(np.stack(
        [np.broadcast_to(ids, w.shape), w], axis=1).reshape(2 * lanes, N))
    kw = dict(n_bins=B, allow_bf16=True, derive_count=True, classes=K)
    assert PH.payload_rows(2, 1, True, K) == K + 1
    if kernel == "hist_folds":
        slot = jnp.asarray(rng.integers(0, S + 1, (lanes, N)), jnp.float32)
        got = PH.hist_folds(Xb_t, pay, slot, n_slots=S, interpret=True, **kw)
        twin = PH.hist_folds(Xb_t, pay, slot, n_slots=S, **kw)
    else:
        node = jnp.asarray(rng.integers(0, S, (lanes, N)), jnp.float32)
        tables = [jnp.asarray(rng.integers(0, hi, (lanes, S)), jnp.int32)
                  for hi in (F, B, 2)]
        got, n1 = PH.route_hist(Xb_t, pay, node, *tables, n_nodes=S,
                                interpret=True, **kw)
        twin, n0 = PH.route_hist(Xb_t, pay, node, *tables, n_nodes=S, **kw)
        np.testing.assert_array_equal(n0, n1)
    assert got.shape == (lanes * S * (K + 1), F * B)
    np.testing.assert_array_equal(got, twin)
    if kernel == "hist_folds":
        # the class sums add up to the weight sums of the [g, h] form
        h = np.asarray(got).reshape(lanes, S, K + 1, F, B)
        gh = np.asarray(PH.hist_folds(
            Xb_t, jnp.asarray(np.repeat(w, 2, axis=0)), slot, n_slots=S,
            n_bins=B, derive_count=True)).reshape(lanes, S, 3, F, B)
        np.testing.assert_array_equal(h[:, :, :K].sum(axis=2), gh[:, :, 1])
        np.testing.assert_array_equal(h[:, :, K], gh[:, :, 2])
    with pytest.raises(ValueError, match="class channels"):
        PH.payload_rows(2, 3, True, K)


def test_min_info_gain_is_not_halved_for_k_channels():
    """A root whose K-class Gini gain lies between half the threshold and
    the threshold must NOT split (the one-channel trick's 0.5 would split
    it), and splits just under its gain."""
    K = 3
    rng = np.random.default_rng(3)
    n = 6000
    x = rng.normal(size=(n, 1)).astype(np.float32)
    shift = 0.12 * np.sign(x[:, 0])
    u = rng.uniform(size=n)
    y = ((u > 1 / 3 + shift).astype(int) + (u > 2 / 3)).astype(np.float32)
    Xb = T.bin_matrix(jnp.asarray(x), T.quantile_edges(jnp.asarray(x), 8))
    yd = jnp.asarray(y)
    left = x[:, 0] < np.median(x[:, 0])

    def gini_sum(rows):
        share = np.bincount(y[rows].astype(int), minlength=K) / rows.sum()
        return (share ** 2).sum()
    gain = 0.5 * gini_sum(left) + 0.5 * gini_sum(~left) \
        - gini_sum(np.ones(n, bool))
    assert gain > 0.02
    W = jnp.ones((1, n), jnp.float32)
    rw = jnp.ones((1, n), jnp.float32)
    votes = jnp.zeros((1, K, n), jnp.float32)

    def splits(threshold):
        _, tree, _ = T.fit_forest_lanes(
            Xb, yd, W, rw, jax.random.split(jax.random.PRNGKey(0), 1),
            votes, depth=1, n_bins=8, min_info_gain=threshold, payload=WORD,
            classes=K)
        return int(tree.thresh[0, 0]) < 8
    assert splits(gain / 1.2) and not splits(gain * 1.5)
    # the ONE place the threshold is scaled, by the word
    assert T.payload_min_info_gain(WORD, 0.01) == 0.01
    assert T.payload_min_info_gain("centred_parts", 0.01) == 0.01
    assert T.payload_min_info_gain("indicator", 0.01) == 0.005
    est = MT.OpRandomForestClassifier(min_instances_per_node=10)
    assert MT.payload_body(est) == "indicator"
    assert MT.payload_body(est, multiclass=True, n_classes=K) == WORD
    assert MT.payload_body(est, multiclass=True, n_classes=2) == WORD
    assert T.payload_rows(WORD, K) == K + 1 and T.PAYLOAD_PARTS[WORD] == 1
    with pytest.raises(ValueError):
        T.payload_rows(WORD)


@pytest.mark.parametrize("K, at_1m, at_10m, why", [
    (2, 5, 3, ""), (7, 2, 2, ""), (16, 1, 0, "K = 16"), (32, 0, 0, "K = 32")])
def test_plan_forest_group_counts_k(K, at_1m, at_10m, why, monkeypatch):
    """On a described v5e, five folds at depth 6 over 64 x 33 bins, ten
    trees. The output block [lanes x 16 x (K + 1), 2 112] f32 under 12 MB
    admits lanes x (K + 1) <= 93: six trees' lanes at K = 2 (ten trees in
    two groups of 5), two at K = 7, one at K = 16, none at K = 32. The row
    planes a group lays out (five a lane, the votes in and out) under 7/16
    of the chip bind at 10M rows: three trees at K = 2, still two at K = 7,
    none at K = 16 — and where the plan declines, its reason names K."""
    from transmogrifai_tpu.utils import platform
    monkeypatch.setattr(platform, "device_spec",
                        lambda: platform.DEVICE_SPECS["TPU v5 lite"])
    rows = PH.payload_rows(2, 1, True, K)
    for n_rows, trees in ((1_000_000, at_1m), (10_000_000, at_10m)):
        assert PH.plan_forest_group(n_rows, 64, 33, 5, 10, 6, rows,
                                    classes=K) == trees
    assert PH.forest_group_planes(10, 5, 7) == 5 * 16 + 2 * 5 * 8
    assert PH.forest_group_planes(25, 5) == 5 * 32
    monkeypatch.setattr(MT, "FOREST_LANE_BACKENDS", ("tpu", "cpu"))
    est = MT.OpRandomForestClassifier(num_trees=10, max_depth=6,
                                      min_instances_per_node=10)
    group, reason = est.forest_lane_plan(10_000_000, 64, 5, n_classes=K,
                                         multiclass=True)
    assert group == at_10m and (why in reason if why else reason == "")
    assert MT.forest_lane_route_ok(est, 10_000_000, 64, 5, multiclass=True,
                                   n_classes=K) == bool(at_10m)
    # asked without the class count it answers for two
    assert MT.forest_lane_route_ok(est, 10_000_000, 64, 5, multiclass=True)


def test_a_binary_label_as_two_channels_keeps_the_sequential_trees(
        monkeypatch):
    monkeypatch.setattr(MT, "FOREST_LANE_BACKENDS", ("tpu", "cpu"))
    est = MT.OpRandomForestClassifier(min_instances_per_node=0)
    group, reason = est.forest_lane_plan(3_000_000, 8, 3)
    assert group == 0 and "two class channels" in reason


def _sweep(monkeypatch, X, y, est, folds=3):
    from transmogrifai_tpu.automl.selectors import \
        MultiClassificationModelSelector
    from transmogrifai_tpu.automl.tuning.splitters import DataCutter
    from transmogrifai_tpu.utils.metrics import collector
    monkeypatch.setattr(MT, "FOREST_LANE_BACKENDS", ("tpu", "cpu"))
    monkeypatch.setattr(MT, "FOREST_LANE_MIN_ROWS", 0)
    sel = MultiClassificationModelSelector.with_cross_validation(
        splitter=DataCutter(seed=42, reserve_test_fraction=0.0),
        num_folds=folds, seed=42, models_and_parameters=[
            (est, [{"min_info_gain": 0.001}, {"min_info_gain": 0.05}])])
    events = []
    monkeypatch.setattr(collector, "event",
                        lambda name, **kw: events.append((name, kw)))
    collector.enable()
    try:
        best = sel.validator.validate(sel.models, X, y,
                                      problem_type="multiclass")
        spans = [(f"{s.kind}:{s.name}", dict(s.attrs))
                 for s in collector.trace.spans]
    finally:
        collector.disable()
    return sel, best, spans, events


def test_multiclass_selector_takes_the_lane_route(monkeypatch):
    """MultiClassificationModelSelector -> validate() -> mask_folds ->
    fit_forest_lanes on the twins: route, word, rows and classes in the
    telemetry and on every forest_group span, the metric span's body, no
    declined event — and every fold's error equals the error of
    fit_forest's own forest scored by _mask_score's rule."""
    from transmogrifai_tpu.ops import metrics_ops as M
    K, folds = 7, 3
    X, Xb, y, _ = _data(n=3000, f=8, K=K)
    est = MT.OpRandomForestClassifier(num_trees=4, max_depth=4, max_bins=8,
                                      min_instances_per_node=10)
    sel, best, spans, events = _sweep(monkeypatch, X, np.asarray(y), est,
                                      folds)
    val = sel.validator
    assert [v.route for v in best.validated] \
        == ["mask_folds:forest_lanes"] * 2
    assert val.last_tree_telemetry == {
        "model": "OpRandomForestClassifier", "route": "forest_lanes",
        "tree_lanes": 24, "lane_groups": 2, "lanes_per_group": 12,
        "bootstrap_draws": 24000, "payload_body": WORD,
        "payload_rows": K + 1, "features_per_node": 3, "classes": K}
    groups = [a for n, a in spans if n == "tree_fused:forest_group"]
    assert len(groups) == 2 and all(
        (g["payload_body"], g["payload_rows"], g["classes"],
         g["features_per_node"]) == (WORD, K + 1, K, 3) for g in groups)
    metric = [a for n, a in spans if n == "validate_phase:fold_metrics"]
    assert len(metric) == 2 and all(
        (m["metric"], m["metric_body"], m["classes"])
        == ("error", "class_major_confusion", K) for m in metric)
    assert "forest_lane_route_declined" not in [name for name, _ in events]
    # the same folds through fit_forest and _mask_score's rule
    masks = jnp.asarray(val.fold_masks(np.asarray(y)))
    w = jnp.ones(X.shape[0], jnp.float32)
    for v in best.validated:
        est_g = est.copy(**v.grid)
        ctx = est_g._bin(jnp.asarray(X))
        for f in range(folds):
            prob = est_g._mask_score(ctx, y, w * masks[f], K, True)  # [n, K]
            err = M.multiclass_metrics(jnp.argmax(prob, axis=1), y, K,
                                       (1.0 - masks[f]) * w).error
            assert abs(float(err) - v.fold_metrics[f]) < 1e-6


def test_class_major_metrics_equal_the_row_major_ones():
    from transmogrifai_tpu.automl.tuning import validators as V
    from transmogrifai_tpu.ops import metrics_ops as M
    rng = np.random.default_rng(5)
    folds, K, n = 3, 5, 5000
    scores = jnp.asarray(rng.random((folds, K, n)), jnp.float32)
    y = jnp.asarray(rng.integers(0, K, n), jnp.float32)
    w = jnp.ones(n, jnp.float32)
    masks = jnp.asarray(rng.integers(0, 2, (folds, n)), jnp.float32)
    for metric in ("error", "f1", "precision", "recall"):
        got = V._class_major_metrics(scores, y, w, masks, metric=metric,
                                     n_classes=K)
        for f in range(folds):
            want = getattr(M.multiclass_metrics(
                jnp.argmax(scores[f], axis=0), y, K, (1.0 - masks[f]) * w),
                metric)
            assert abs(float(got[f]) - float(want)) < 1e-6
    # more rows than one block, and a tail
    old, V._CLASS_MAJOR_BLOCK = V._CLASS_MAJOR_BLOCK, 2048
    try:
        V._class_major_metrics.clear_cache()
        blocked = V._class_major_metrics(scores, y, w, masks, metric="error",
                                         n_classes=K)
    finally:
        V._CLASS_MAJOR_BLOCK = old
        V._class_major_metrics.clear_cache()
    np.testing.assert_allclose(
        blocked, V._class_major_metrics(scores, y, w, masks, metric="error",
                                        n_classes=K), rtol=0, atol=1e-7)


# -- the other routes' programs are the parent's ---------------------------------

def _digest(fn, *args) -> str:
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _pinned_programs():
    """jaxpr digests of the three accepted lane programs at small shapes,
    on the jnp twins and through the Pallas interpreter."""
    rng = np.random.default_rng(0)
    n, f, folds, bins = 512, 6, 2, 8
    Xb = jnp.asarray(rng.integers(0, bins + 1, (n, f)), jnp.int8)
    y01 = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    yr = jnp.asarray(rng.normal(size=n) + 10, jnp.float32)
    W = jnp.asarray(rng.integers(0, 2, (folds, n)), jnp.float32)
    rw, kf = T.forest_bootstrap(jax.random.PRNGKey(0), 0, 1.0, n_rows=n,
                                n_trees=2, group=2)
    votes = jnp.zeros((folds, n), jnp.float32)
    centre = jnp.asarray([10.0, 64.0], jnp.float32)
    out = {}
    for interp in (False, True):
        kw = dict(depth=3, n_bins=bins, feature_frac=0.5, min_instances=5.0,
                  min_info_gain=1e-3, interpret=interp)
        tag = "interpret" if interp else "twins"
        out[f"rf:{tag}"] = _digest(
            lambda *a: T.fit_forest_lanes(*a, **kw), Xb, y01, W, rw, kf,
            votes)
        out[f"rf-regression:{tag}"] = _digest(
            lambda *a: T.fit_forest_lanes(
                *a[:-1], payload="centred_parts", centre=a[-1], **kw),
            Xb, yr, W, rw, kf, votes, centre)
        for name, loss, yy, more in (
                ("gbt", "logistic", y01, {}),
                ("gbt-regression", "squared", yr,
                 dict(payload="residual_parts", normalize_gain=True))):
            out[f"{name}:{tag}"] = _digest(
                lambda *a: T._fit_gbt_folds_impl(
                    *a, n_rounds=2, depth=3, n_bins=bins, loss=loss,
                    interpret=interp, **more),
                Xb, yy, W, jax.random.PRNGKey(0))
    return out


#: taken on the parent commit (a8d9fa8) by this same function
PARENT_PROGRAMS = {
    "rf:twins": "029c15a8fdffbd1c",
    "rf-regression:twins": "98b264f4317dad8a",
    "gbt:twins": "43ed62470dd5b0cf",
    "gbt-regression:twins": "454cd99e39711049",
    "rf:interpret": "21a357765ff3b904",
    "rf-regression:interpret": "3c75be826867def0",
    "gbt:interpret": "9d490ffb16e72266",
    "gbt-regression:interpret": "8bf6d8da554b201e",
}


def test_binary_regression_and_booster_programs_are_the_parents():
    """sweep-rf, sweep-rf-regression, sweep-gbt and sweep-gbt-regression
    run the programs they ran before the class channels: the jaxprs of
    fit_forest_lanes and of fit_gbt_folds' body at fixed small shapes,
    addresses stripped, digest for digest what the parent commit traces."""
    assert _pinned_programs() == PARENT_PROGRAMS
