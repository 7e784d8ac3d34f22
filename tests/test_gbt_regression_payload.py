"""The regression booster through the fused passes (PR 51): the payload's
word, one for both tree families; a round's residual as three fixed-point
bfloat16 parts over that round's own scale — the interpret-mode kernels and
whole fits against float64 sums and the benchmark's plain reference, the
one-part form failing the same bounds; Spark's split rule (the gain a
weighted row) in every route of the GBT family and in none of XGBoost's;
the logistic program unchanged; the route through RegressionModelSelector,
its spans, telemetry and decline event."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.models import trees as MT
from transmogrifai_tpu.ops import pallas_hist as PH
from transmogrifai_tpu.ops import trees as T
from transmogrifai_tpu.ops import trees_host as TH

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import reference  # noqa: E402
from benchmark import reference_gbt_reg as RG  # noqa: E402

N, F, BINS, FOLDS, DEPTH, ROUNDS = 2048, 6, 8, 2, 3, 3
GBT_KW = dict(n_rounds=ROUNDS, depth=DEPTH, learning_rate=0.1,
              min_instances=10.0, min_info_gain=0.001, normalize_gain=True)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(N, F)).astype(np.float32)
    y = (10 + 1.7 * (X @ rng.normal(size=F) / np.sqrt(F)
                     + 0.65 * rng.normal(size=N))).astype(np.float32)
    Xb = T.bin_matrix(jnp.asarray(X), T.quantile_edges(jnp.asarray(X), BINS))
    fold = rng.integers(0, FOLDS, N)
    masks = (fold[None, :] != np.arange(FOLDS)[:, None]).astype(np.float32)
    return X, Xb, y, masks


@pytest.fixture(scope="module")
def fits(data):
    """The same boosters through the INTERPRETED kernels under each word:
    the bfloat16 contraction as the chip runs it."""
    _, Xb, y, masks = data
    key = jax.random.PRNGKey(42)
    return {word: T.fit_gbt_folds(
        Xb, jnp.asarray(y), jnp.asarray(masks), key, n_bins=BINS,
        loss="squared", interpret=True, payload=word, **GBT_KW)
        for word in ("residual_parts", "gradient")}


def _replays(data, fit, lane=0, **over):
    """reference_gbt_reg.replay_round of every round's tree of one lane,
    the residual rebuilt in float64 from the fit's own earlier trees."""
    _, Xb, y, masks = data
    trees, base, _ = fit
    Xb_t = jnp.asarray(np.asarray(Xb).T)
    kw = dict(depth=DEPTH, bins=BINS + 1, min_instances=10.0,
              min_info_gain=0.001, lam=1.0, step=0.1)
    kw.update(over)
    Fm = np.full(N, float(base[lane]), np.float64)
    out = []
    for r in range(trees.feat.shape[0]):
        tree = {k: np.asarray(getattr(trees, k))[r, lane]
                for k in ("feat", "thresh", "miss")}
        tree["leaf"] = np.asarray(trees.leaf)[r, lane, :, 0]
        out.append(RG.replay_round(Xb_t, y.astype(np.float64) - Fm,
                                   masks[lane], tree, **kw))
        Fm += np.asarray(RG.tree_values(Xb_t, tree, DEPTH), np.float64)
    return out


# -- the word -----------------------------------------------------------------------

@pytest.mark.parametrize("cls,word,rows", [
    (MT.OpGBTRegressor, "residual_parts", 5),
    (MT.OpXGBoostRegressor, "residual_parts", 5),
    (MT.OpGBTClassifier, "gradient", 3),
    (MT.OpXGBoostClassifier, "gradient", 3),
    (MT.OpRandomForestRegressor, "centred_parts", 5),
    (MT.OpRandomForestClassifier, "indicator", 3)])
def test_one_word_for_both_families(cls, word, rows):
    """payload_body is THE predicate: the forests' name for it is the same
    function, its words are PAYLOAD_PARTS' keys and the rows a (lane, slot)
    follow from the parts."""
    assert MT.forest_payload_body is MT.payload_body
    assert T.forest_payload_rows is T.payload_rows
    assert MT.payload_body(cls()) == word and word in T.PAYLOAD_PARTS
    assert T.payload_rows(word) == rows == PH.payload_rows(
        2, T.PAYLOAD_PARTS[word], True)


def test_the_plans_read_the_words_rows():
    """Five rows a (lane, slot) reach the VMEM gate, the lane chunker and
    the roofline span's bytes; three leave all three where they were."""
    wide = dict(n_feat=256, n_bins=257, depth=6)
    three = PH.plan_fused_hist(lanes=5, channels=3, **wide)
    five = PH.plan_fused_hist(lanes=5, channels=5, **wide)
    assert five.out_bytes * 3 == three.out_bytes * 5
    assert PH.plan_lane_chunk(64, 33, 5, 3, 6, channels=5) == 2 \
        and PH.plan_lane_chunk(64, 33, 5, 3, 6, channels=3) == 3
    b3 = PH.fused_fit_bytes(10_000, 64, 5, 6, 10)
    assert b3 == PH.fused_fit_bytes(10_000, 64, 5, 6, 10, payload_rows=3)
    # what a round's scale adds: a read and a write of the gradient plane
    assert PH.fused_fit_bytes(10_000, 64, 5, 6, 10, payload_rows=5) \
        == b3 + 10 * 5 * 2 * 4 * 10_000


# -- the scale ------------------------------------------------------------------------

def test_residual_scale_is_the_power_of_two_over_each_lanes_largest():
    rng = np.random.default_rng(3)
    g = rng.normal(size=(4, 500)).astype(np.float32) \
        * np.asarray([[7.3], [0.011], [1.0], [0.0]], np.float32)
    g[2, 0] = 1.0     # a largest size that IS a power of two goes one up
    g[2, 1:] *= 0.2
    s = np.asarray(T._residual_scale(jnp.asarray(g)), np.float64)
    top = np.abs(g).max(axis=1).astype(np.float64)
    assert np.all(np.log2(s) == np.round(np.log2(s)))        # exact powers
    assert np.all(s[:3] > top[:3]) and np.all(s[:3] <= 2 * top[:3])
    assert s[2] == 2.0 and s[3] == 2.0 ** -126               # zeros stay
    assert np.all(np.abs(g / s[:, None].astype(np.float32)) < 1.0)


@pytest.mark.parametrize("kernel", ["hist_folds", "route_hist"])
@pytest.mark.parametrize("how", ["parts3", "parts1", "stale_scale"])
def test_histogram_sums_of_a_rounds_residual_against_float64(
        data, kernel, how):
    """The booster's call shape (g = w (F - y) / scale, h = w, the count
    derived) through the interpreted kernels: three parts over the round's
    OWN scale hold every cell to 1e-6 of its mass; one part fails that
    bound, and so do three parts over a scale left 2^12 too large."""
    _, Xb, y, masks = data
    lanes, S, B = FOLDS, 2, BINS + 1
    rng = np.random.default_rng(1)
    r = (y - y.mean()) * np.float32(0.01)       # a late round's residual
    g = masks * r[None, :]
    scale = np.asarray(T._residual_scale(jnp.asarray(g)))
    if how == "stale_scale":
        scale = scale * np.float32(4096.0)
    pay = np.stack([g / scale[:, None], masks], axis=1).reshape(2 * lanes, N)
    Xb_t = np.asarray(Xb).T
    static = dict(n_bins=B, allow_bf16=True, derive_count=True,
                  payload_parts=1 if how == "parts1" else 3)
    if kernel == "hist_folds":
        slot = rng.integers(0, S + 1, (lanes, N)).astype(np.float32)
        got = PH.hist_folds(jnp.asarray(Xb_t), jnp.asarray(pay),
                            jnp.asarray(slot), n_slots=S, interpret=True,
                            **static)
        ref = reference.hist_plain(Xb_t, pay, slot, S, B, True)
        mass = reference.hist_plain(Xb_t, np.abs(pay), slot, S, B, True)
    else:
        node = rng.integers(0, S, (lanes, N)).astype(np.float32)
        tables = [rng.integers(0, hi, (lanes, S)).astype(np.int32)
                  for hi in (F, B, 2)]
        got, _ = PH.route_hist(jnp.asarray(Xb_t), jnp.asarray(pay),
                               jnp.asarray(node), *map(jnp.asarray, tables),
                               n_nodes=S, interpret=True, **static)
        ref, _ = reference.route_hist_plain(Xb_t, pay, node, *tables, S, B,
                                            True)
        mass, _ = reference.route_hist_plain(Xb_t, np.abs(pay), node,
                                             *tables, S, B, True)
    a = np.asarray(got, np.float64).reshape(lanes, S, 3, -1)
    r_, m_ = ref.reshape(a.shape), mass.reshape(a.shape)
    assert np.array_equal(a[:, :, 1:], r_[:, :, 1:])     # h and the counts
    share = (np.abs(a[:, :, 0] - r_[:, :, 0])
             / (m_[:, :, 0] + 1e-30))[m_[:, :, 0] > 0].max()
    assert (share < 1e-6) == (how == "parts3"), share


# -- whole fits against the plain reference ----------------------------------------------

@pytest.mark.parametrize("word", ["residual_parts", "gradient"])
def test_every_rounds_tree_against_exact_sums(data, fits, word):
    """The system against benchmark/reference_gbt_reg.py, every round of a
    lane: splits allowed and the exact best, leaves step x G / (H + 1) of
    exact sums to float32's accuracy under three parts; the one-part build
    (the program before this PR) fails the SAME leaf bound."""
    reps = _replays(data, fits[word])
    assert len(reps) == ROUNDS
    worst = max(r["leaf_worst"] for r in reps)
    for r in reps:
        assert not r["not_allowed"] and not r["dead_but_allowed"]
        # what the reference says the wrong builds would read, same nodes
        assert r["leaf_worst_if_one_part"] > 3e-6
        assert r["leaf_worst_if_step_twice"] > 1e-2 \
            and r["leaf_worst_if_no_step"] > 1e-1
        assert r["leaf_worst_if_lambda_0"] > 1e-4
    assert (worst < 3e-7) == (word == "residual_parts"), worst
    if word == "residual_parts":
        assert all(r["gain_shortfall"] < 1e-6 for r in reps)


def test_a_last_round_100x_smaller_keeps_the_bound(data):
    """Rounds shrink the residual; each takes its OWN scale. A label one
    tree fits to a hundredth (a step function of one column's bins, step
    size 1) leaves a last-round residual ~100 x smaller than the first's,
    and its leaves still sit within float32's accuracy of their exact
    sums, as a share of the residual's own size."""
    X, Xb, _, masks = data
    rng = np.random.default_rng(7)
    xb0 = np.asarray(Xb)[:, 0].astype(np.float32)
    y = (xb0 - xb0.mean() + 0.01 * rng.normal(size=N)).astype(np.float32)
    kw = dict(GBT_KW, learning_rate=1.0, n_rounds=2, min_info_gain=0.0)
    fit = T.fit_gbt_folds(Xb, jnp.asarray(y), jnp.asarray(masks),
                          jax.random.PRNGKey(0), n_bins=BINS, loss="squared",
                          interpret=True, payload="residual_parts", **kw)
    d = (X, Xb, y, masks)
    first, last = _replays(d, fit, step=1.0, min_info_gain=0.0)
    assert first["residual_largest"] > 50 * last["residual_largest"]
    assert RG.power_of_two_over(first["residual_largest"]) \
        >= 64 * RG.power_of_two_over(last["residual_largest"])
    for r in (first, last):
        # the margin the residual is taken from is float32: an ulp of it
        # (2.4e-7 at 4) is what a leaf can be off, whatever the round; one
        # part would be off by a share of the residual's own size
        assert r["leaf_worst"] < 5e-7
        assert r["leaf_worst_if_one_part"] > 10 * r["leaf_worst"]
        assert not r["not_allowed"] and not r["dead_but_allowed"]


# -- Spark's split rule in the GBT family ------------------------------------------------

def _dead_nodes(thresh) -> int:
    return int((np.asarray(thresh) >= BINS).sum())


@pytest.mark.parametrize("route", ["fit_gbt", "fit_gbt_folds", "native"])
def test_a_threshold_that_binds_a_weighted_row_prunes(data, route):
    """min_info_gain 0.6 against a gain A ROW stops the deep nodes; the
    same number against the gain summed over ~2 000 rows stops none."""
    _, Xb, y, masks = data
    kw = dict(n_rounds=2, depth=DEPTH, learning_rate=0.1,
              min_instances=10.0, min_info_gain=0.6)
    dead = {}
    for normalize in (True, False):
        if route == "fit_gbt":
            trees, _ = T.fit_gbt(Xb, jnp.asarray(y), jnp.ones(N),
                                 jax.random.PRNGKey(0), n_bins=BINS,
                                 loss="squared", normalize_gain=normalize,
                                 **kw)
        elif route == "fit_gbt_folds":
            trees, _, _ = T.fit_gbt_folds(
                Xb, jnp.asarray(y), jnp.asarray(masks),
                jax.random.PRNGKey(0), n_bins=BINS, loss="squared",
                normalize_gain=normalize, **kw)
        else:
            out = TH.fit_gbt_host(np.asarray(Xb), y, np.ones(N, np.float32),
                                  n_bins=BINS, loss="squared",
                                  normalize_gain=normalize, **kw)
            if out is None:
                pytest.skip("no native builder here")
            trees = out[0]
        dead[normalize] = _dead_nodes(trees.thresh)
    assert dead[False] == 0 < dead[True]


def test_the_gbt_family_hands_every_route_the_rule_and_xgboost_none(data):
    X, Xb, y, _ = data
    assert MT.OpGBTRegressor()._gbt_kw()["normalize_gain"] is True
    assert MT.OpGBTClassifier()._gbt_kw()["normalize_gain"] is True
    for cls in (MT.OpXGBoostRegressor, MT.OpXGBoostClassifier):
        assert "normalize_gain" not in cls()._common()
    # through the estimator: the GBT regressor prunes, XGBoost's does not
    gbt = MT.OpGBTRegressor(max_iter=2, max_depth=DEPTH, max_bins=BINS,
                            min_instances_per_node=10, min_info_gain=0.6)
    assert (gbt.fit_arrays(X, y).thresh_val == np.inf).sum() > 0
    # OpGBTClassifier's default-constructed fit: min_info_gain 0.0, so the
    # rule changes nothing, bit for bit
    yb = (y > np.median(y)).astype(np.float32)
    kw = {k: v for k, v in MT.OpGBTClassifier(
        max_iter=2, max_depth=DEPTH)._gbt_kw().items()
        if k != "normalize_gain"}
    assert kw["min_info_gain"] == 0.0
    a, base_a = T.fit_gbt(Xb, jnp.asarray(yb), jnp.ones(N),
                          jax.random.PRNGKey(1), n_bins=BINS,
                          normalize_gain=True, **kw)
    b, base_b = T.fit_gbt(Xb, jnp.asarray(yb), jnp.ones(N),
                          jax.random.PRNGKey(1), n_bins=BINS, **kw)
    assert float(base_a) == float(base_b)
    for one, other in zip(a, b):
        assert np.array_equal(np.asarray(one), np.asarray(other))


def test_xgboost_regression_trees_are_bit_for_bit_on_the_twins(data):
    """The jnp twins sum float32 as it is and the scale is a power of two:
    the word changes no bit of a CPU fit, so no accepted parity moves."""
    _, Xb, y, masks = data
    kw = dict(n_rounds=2, depth=DEPTH, learning_rate=0.3, reg_lambda=1.0)
    a, b = (T.fit_gbt_folds(Xb, jnp.asarray(y), jnp.asarray(masks),
                            jax.random.PRNGKey(0), n_bins=BINS,
                            loss="squared", payload=w, **kw)
            for w in ("gradient", "residual_parts"))
    for one, other in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b)):
        assert np.array_equal(np.asarray(one), np.asarray(other))


def test_the_logistic_program_is_unchanged(data):
    """The classifier's word is "gradient": its fit's jaxpr is the one a
    caller that passes no word and no rule gets (sweep-gbt's), and holds
    neither the scale's bit cast nor a five-row kernel call; the squared
    loss in parts holds both."""
    _, Xb, y, masks = data
    yb = jnp.asarray((y > np.median(y)).astype(np.float32))

    def text(loss, **kw):
        return str(jax.make_jaxpr(lambda *a: T._fit_gbt_folds_impl(
            *a, n_rounds=2, depth=DEPTH, n_bins=BINS, loss=loss,
            interpret=True, **kw))(Xb, yb, jnp.asarray(masks),
                                   jax.random.PRNGKey(0)))
    word = MT.payload_body(MT.OpXGBoostClassifier())
    plain = text("logistic")
    assert text("logistic", payload=word, normalize_gain=False) == plain
    # F x B = 54 columns; 2 lanes x 1 slot x 3 | 5 rows at the root
    assert "bitcast_convert_type" not in plain and "f32[10,54]" not in plain
    parts = text("squared", payload="residual_parts")
    assert "bitcast_convert_type" in parts and "f32[10,54]" in parts
    assert "f32[6,54]" in plain


# -- the route, its record, its refusal ------------------------------------------------

def test_regression_model_selector_takes_the_parts_route(data, monkeypatch):
    """RegressionModelSelector -> validate() -> mask_folds -> fit_gbt_folds
    with the kernels' jnp twins: the word reaches the fit, the spans and
    the telemetry, and the sweep's own trees pass the benchmark's checks
    against the plain reference."""
    from transmogrifai_tpu.automl.selectors import RegressionModelSelector
    from transmogrifai_tpu.automl.tuning.splitters import DataSplitter
    from transmogrifai_tpu.utils.metrics import collector
    from benchmark import harness
    driver = harness.load_module("drivers", "sweep_gbt_reg")
    X, _, y, _ = data
    monkeypatch.setattr(MT, "FOREST_LANE_BACKENDS", ("tpu", "cpu"))
    monkeypatch.setattr(MT, "FOREST_LANE_MIN_ROWS", 0)
    est = MT.OpGBTRegressor(max_iter=ROUNDS, max_depth=DEPTH, max_bins=BINS,
                            min_instances_per_node=10)
    sel = RegressionModelSelector.with_cross_validation(
        splitter=DataSplitter(seed=42, reserve_test_fraction=0.0),
        num_folds=3, seed=42, models_and_parameters=[
            (est, [{"min_info_gain": 0.001}, {"min_info_gain": 0.5}])])
    collector.enable("gbt_regression_test")
    try:
        with driver.BoosterSpy(0, 64) as spy:
            sel.fit_arrays(X, y)
        spans = [dict(s.attrs) for s in collector.trace.spans
                 if s.kind == "tree_fused" and s.name == "tree_levels"]
    finally:
        collector.finish()
        collector.disable()
    val = sel.validator
    assert val.last_tree_telemetry == {
        "model": "OpGBTRegressor", "route": "fold_fused", "programs": 2,
        "rounds": 2 * ROUNDS, "scale_reductions": 2 * ROUNDS, "lanes": 3,
        "payload_body": "residual_parts", "payload_rows": 5}
    assert len(spy.points) == 2 and all(
        p["said"]["payload"] == "residual_parts"
        and p["said"]["normalize_gain"] is True for p in spy.points)
    assert len(spans) == 2 and all(
        (s["payload_body"], s["payload_rows"], s["rounds"], s["lanes"])
        == ("residual_parts", 5, ROUNDS, 3) for s in spans)
    masks = np.asarray(val.fold_masks(np.zeros(N)))
    into = {}
    best = val.last_best if hasattr(val, "last_best") else None
    if best is None:   # the selector keeps the summary, not the sweep
        best = val.validate(sel.models, jnp.asarray(X), jnp.asarray(y),
                            problem_type="regression")
    RG.gbt_reg_answer(
        best, spy.points[:2], masks, jnp.asarray(X), jnp.asarray(y),
        into=into, fold=0, rounds=ROUNDS, depth=DEPTH, bins=BINS, step=0.1,
        lam=1.0, train_rows=1000, tol_gain=1e-5, tol_leaf=1e-6,
        tol_margin=1e-4, tol_metric=3e-6, tol_plain=0.5)
    assert into["points_grow_different_trees"] is True
    assert any(into["threshold_binds_in_replayed_trees"])
    # the threshold a row: the summed gain would have split dead nodes
    assert max(r["summed_rule_would_split"] for r in into["replay"]) > 0


def test_a_fit_that_cannot_take_the_parts_says_so(data):
    """On this backend no fused kernels run: the sweep's hook declines and
    the event names the word that was asked for; the logistic family, one
    part on every route, has nothing to decline."""
    from transmogrifai_tpu.utils.metrics import collector
    _, Xb, y, masks = data
    seen = []
    orig = collector.event

    def event(name, **kw):
        seen.append((name, kw))
        return orig(name, **kw)
    ctx = (Xb, None, BINS)
    try:
        collector.event = event
        for est in (MT.OpGBTRegressor(max_iter=1, max_depth=2),
                    MT.OpGBTClassifier(max_iter=1, max_depth=2)):
            assert est._mask_scores_fused(
                ctx, jnp.asarray(y), jnp.ones(N), jnp.asarray(masks), 2,
                False) is None
    finally:
        collector.event = orig
    assert [(n, kw["model"], kw["payload_body"]) for n, kw in seen] == [
        ("booster_parts_route_declined", "OpGBTRegressor",
         "residual_parts")]
    assert "no fused kernels" in seen[0][1]["reason"]
