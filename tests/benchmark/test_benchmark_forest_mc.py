"""The multiclass-forest cell `sweep-rf-multiclass` and what it brought to
the benchmark: the cell rehearsed on the CPU traced and untraced with its
metrics printed and its spans found, the refusal of a program that has no
lane route at a class count, the manifest by membership, the configuration
against upstream's DefaultSelectorParams, the work model,
benchmark/reference_forest_mc.py against numpy by hand and against lanes
the program grew — and the named wrong builds (minInfoGain halved, a class
channel left out, one-vs-rest gains, leaves not renormalised, leaves
rounded to bfloat16, a tree missing, 22 columns a node) each refused by a
check."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import datagen_softmax, harness  # noqa: E402
from benchmark import opcount_forest, opcount_forest_mc  # noqa: E402
from benchmark import reference_forest as RF  # noqa: E402
from benchmark import reference_forest_mc as RM  # noqa: E402
from benchmark.reduce_trace import Reduced  # noqa: E402

CELL = "sweep-rf-multiclass"
CONFIG = "multiclass-10m-64-k7-rf"
WORD = "class_indicators"
K = 7


def _load(*parts):
    with open(os.path.join(REPO, "benchmark", *parts)) as f:
        return json.load(f)


def _layer_specs():
    return {f[:-5]: _load("layers", f)
            for f in os.listdir(os.path.join(REPO, "benchmark", "layers"))
            if CELL in _load("layers", f).get("cells", [])}


# -- the cell, rehearsed -------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_cells_metrics(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)   # conftest's 8 virtual devices
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "5400000007", "--seconds", "2",
         "--trace", str(trace), "--rehearse", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    report, line = map(json.loads, r.stdout.strip().splitlines())
    assert line["correct"] is True, report["problems"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    notes = report["notes"]
    assert notes["payload_body_declared"] == WORD
    assert notes["routes"]["cells"] == [
        ["OpRandomForestClassifier", "mask_folds:forest_lanes"]]
    # 2 points x 4 trees x 3 folds, a group a point; 8 columns: 3 a node
    assert notes["forest_lanes"] == {
        "model": "OpRandomForestClassifier", "route": "forest_lanes",
        "tree_lanes": 24, "lane_groups": 2, "lanes_per_group": 12,
        "bootstrap_draws": 2 * 4 * 4096, "payload_body": WORD,
        "payload_rows": K + 1, "features_per_node": 3, "classes": K}
    prog = notes["program"]
    assert prog["groups"] == 2 and prog["hist_calls_classes"] == [K]
    assert prog["calls_said"] == [{"payload": WORD, "classes": K}] * 2
    assert all(g["classes"] == K and g["payload_rows"] == K + 1
               for g in prog["forest_group_spans"])
    span = prog["fold_metrics_spans"][0]
    assert (span["metric"], span["metric_body"], span["classes"]) == (
        "error", "class_major_confusion", K)
    answer = notes["forest_answer"]
    assert answer["bins_identical"] is True and len(answer["replay"]) == 3
    for r_ in answer["replay"]:
        assert not r_["not_allowed"] and not r_["dead_but_allowed"]
        assert r_["gain_shortfall"] < 1e-6 and r_["subset_sizes"] == [3]
        # float32 shares of exact sums; rounded to bfloat16, or left as
        # the class sums themselves, they are not
        assert r_["leaf_worst"] < 2e-6 < 1e-4 < r_["leaf_worst_if_bf16"]
        assert r_["leaf_worst_if_unnormalised"] > 1
        assert r_["leaf_sums_off_one"] < 1e-6
    votes = answer["votes"]
    assert votes["vote_worst"] < 1e-5 and votes["held_rows"] > 1000
    assert votes["metric_delta"] < 1e-6 and \
        answer["every_fold_metric_delta"] < 1e-6
    assert votes["exact_error"] == votes["traversal_error"]
    assert votes["vote_worst_if_a_tree_were_missing"] > 0.1
    assert votes["vote_worst_if_bf16_leaves"] > 1e-4
    assert answer["order"]["misordered"] == []
    assert len(answer["points"]) == 2
    for p in answer["points"]:
        b = p["bootstrap"]
        assert b["trees"] == 4 and b["equal_pairs"] == 0
        assert b["mean_worst"] < 0.1 and b["correlation_worst"] < 0.1
        assert abs(p["sweep"] - p["reference"]) < 0.2
    twins = notes["class_channel_twins"]
    assert {t["kernel"] for t in twins} == {"hist_folds", "route_hist",
                                            "table_lookup"}
    for t in twins:
        assert t["classes"] == K and t["worst_abs"] == 0.0
        assert t.get("rows_a_slot", K + 1) == K + 1
    assert {t["kernel"] for t in notes["kernel_twins"]} == {
        "route", "table_lookup"}
    counters = report["counters"]
    assert counters["rfm_tree_lanes"] == 24 \
        and counters["rfm_lane_groups"] == 2 \
        and counters["rfm_lanes_per_group"] == 12 \
        and counters["rfm_payload_rows"] == K + 1 \
        and counters["rfm_classes"] == K
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"gbt_sweep_s", "setup_s"}
        assert all(m["value"] > 0 for m in metrics.values())
        return
    specs = _layer_specs()
    assert set(specs) == {
        "rfm_tree_device_s", "rfm_hist_kernel_s", "rfm_hist_kernel_roofline",
        "rfm_bootstrap_device_s", "rfm_split_device_s",
        "rfm_metric_device_s", "rfm_host_gap_s", "rfm_fit_host_s",
        "rfm_lane_groups", "rfm_payload_rows"}
    # the kernels, the roofline and what runs beside them need the chip (no
    # Mosaic custom call, no peaks, no HLO op names here); every other
    # metric of the cell is printed
    assert set(metrics) >= (
        set(specs) - {"rfm_hist_kernel_s", "rfm_hist_kernel_roofline",
                      "rfm_split_device_s"}) \
        | {"programs_compiled", "window_compiles"}
    for name, m in metrics.items():
        if name in specs:
            assert m["unit"] == specs[name]["unit"], name
            assert m["value"] >= 0, name
    assert metrics["window_compiles"]["value"] == 0
    assert metrics["rfm_lane_groups"]["value"] == 2
    assert metrics["rfm_payload_rows"]["value"] == K + 1
    assert metrics["rfm_tree_device_s"]["value"] \
        > metrics["rfm_bootstrap_device_s"]["value"] > 0
    assert metrics["rfm_metric_device_s"]["value"] > 0
    # the spans, under the validate root and on its thread
    ctx = types.SimpleNamespace(
        reduced=Reduced.from_file(notes["xplane"]),
        cell={"job_span": "bench.validate"})

    def count(name):
        return harness.load_module("readers", "host_span").read(
            ctx, {"name": name, "stat": "count"})
    assert count(r"^tmog\.validate:CrossValidation$") == 1
    assert count(r"^tmog\.validate_phase:tree_fit$") == 2
    assert count(r"^tmog\.tree_fused:forest_group$") == 2
    assert count(r"^tmog\.validate_phase:fold_metrics$") == 2


def test_a_program_without_the_route_is_refused_before_any_data(
        monkeypatch, tmp_path):
    """What the parent of this cell's PR does: forest_lane_route_ok takes
    no class count (and its plan declines a multiclass forest), so the
    driver fails with BenchFailure before it makes a byte of data; so does
    a program whose word is another, and a class count past a group's
    cap."""
    from transmogrifai_tpu.models import trees as MT
    driver = harness.load_module("drivers", "sweep_forest_mc")

    def no_data(*a, **k):
        raise AssertionError("data was made")
    monkeypatch.setattr(datagen_softmax, "device_matrix", no_data)
    cell, config = _load("workloads", CELL + ".json"), \
        _load("configs", CONFIG + ".json")

    def ctx(**sizes):
        return harness.Ctx(
            cell=cell, config=config, sizes=dict(config["sizes"], **sizes),
            seed=1, seconds=1.0, trace=False, rehearse=False,
            out_dir=str(tmp_path), compile_log=None)
    # this backend runs no fused kernels: the route's question refuses
    with pytest.raises(harness.BenchFailure, match="is False"):
        driver.setup(ctx())
    monkeypatch.setattr(MT, "FOREST_LANE_BACKENDS", ("tpu", "cpu"))
    # the parent's predicate: no n_classes to ask with
    monkeypatch.setattr(
        MT, "forest_lane_route_ok",
        lambda est, n_rows, n_feat, n_folds, multiclass=False: True)
    with pytest.raises(harness.BenchFailure, match="is False"):
        driver.setup(ctx())
    monkeypatch.undo()
    monkeypatch.setattr(datagen_softmax, "device_matrix", no_data)
    monkeypatch.setattr(MT, "FOREST_LANE_BACKENDS", ("tpu", "cpu"))
    with pytest.raises(harness.BenchFailure, match="n_classes=32"):
        driver.setup(ctx(classes=32))
    monkeypatch.setattr(MT, "payload_body", lambda est, *a, **kw: "indicator")
    with pytest.raises(harness.BenchFailure,
                       match="'indicator', not 'class_indicators'"):
        driver.setup(ctx())
    monkeypatch.undo()
    monkeypatch.setattr(MT, "FOREST_LANE_BACKENDS", ("tpu", "cpu"))
    monkeypatch.setattr(datagen_softmax, "device_matrix", no_data)
    with pytest.raises(AssertionError, match="data was made"):
        driver.setup(ctx())       # both answers right: it goes on to data


def test_manifest_lists_the_cell_under_gbt_sweep_s():
    """Membership and order, not position from the end: a later PR appends
    after these entries."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["gbt_sweep_s"]["workloads"][:5] == [
        "sweep-gbt", "sweep-rf", "sweep-rf-regression",
        "sweep-gbt-regression", CELL]
    assert CELL not in e2e["glm_sweep_s"]["workloads"]
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == cells.index("sweep-gbt-regression") + 1
    entry = manifest["workloads"][cells.index(CELL)]
    assert entry["config"] == CONFIG and entry["chips"] == 1
    assert entry["traffic"] == "rfm-closed-1" and len(entry["why"]) <= 200
    assert entry["why"] == _load("workloads", CELL + ".json")["why"]
    conf = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == ["rf_grid", "num_trees"] \
        and conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(conf["why"]) <= 200 and len(conf["source"]) <= 200
    assert conf["source"] == _load("configs", CONFIG + ".json")["source"]
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    specs = _layer_specs()
    assert sorted(m["name"] for m in mine) == sorted(specs)
    for m in mine:
        spec = specs[m["name"]]
        assert m["moves"] == spec["moves"] == "gbt_sweep_s"
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            spec["unit"], spec["better"], spec["source"], spec["layer"])
    roof = specs["rfm_hist_kernel_roofline"]
    assert roof["args"]["opcount"] == "opcount_forest_mc" \
        and roof["args"]["work"] == "forest_sweep" \
        and roof["reader"] == "roofline_of" and roof["unit"] == "%"
    assert len(manifest["per_layer"]) <= 128


def test_the_work_model_is_the_forests_at_k_plus_1_channels():
    """The dense-slot count of opcount_forest at K + 1 channels for 3: the
    operations scale by (K + 1) / 3, the reads do not, and two class
    channels and a count cost what g, h and a count cost."""
    args = (10_000_000, 64, 33, 10, 6)
    f3, b3 = opcount_forest.forest_group(*args)
    f8, b8 = opcount_forest_mc.forest_group(*args, classes=K)
    assert f8 == pytest.approx(f3 * (K + 1) / 3, rel=1e-12)
    assert opcount_forest_mc.forest_group(*args, classes=2) == (f3, b3)
    blocks = 10 * opcount_forest.slot_passes(6) * 64 * 33 * 4
    assert b8 - b3 == pytest.approx((K + 1 - 3) * blocks)
    grids = [dict(num_trees=10, max_depth=6, max_bins=32)] * 2 \
        + [dict(max_iter=5)]
    fs, bs = opcount_forest_mc.forest_sweep(10_000_000, 64, 5, 10, K, grids)
    assert fs == pytest.approx(2 * 5 * f8) and bs == pytest.approx(2 * 5 * b8)


def test_the_configuration_is_upstreams_defaults_cut_as_it_says():
    from transmogrifai_tpu.automl import selectors as S
    from transmogrifai_tpu.models import trees as MT
    from transmogrifai_tpu.ops import trees as T
    config, cell = _load("configs", CONFIG + ".json"), \
        _load("workloads", CELL + ".json")
    D = S.DefaultSelectorParams
    assert {"OpLogisticRegression", "OpRandomForestClassifier"} == set(
        S.MultiClassificationModelSelector.default_model_types)
    assert config["architecture"] is None
    src = config["source_sizes"]
    assert src["max_depth"] == D.MAX_DEPTH and [src["max_bins"]] == D.MAX_BIN
    assert src["min_instances_per_node"] == D.MIN_INSTANCES_PER_NODE
    assert src["min_info_gain"] == D.MIN_INFO_GAIN
    assert [src["num_trees"]] == D.MAX_TREES
    assert [src["subsampling_rate"]] == D.SUBSAMPLE_RATE
    assert src["rf_grid"] == len(D.MAX_DEPTH) * len(D.MIN_INFO_GAIN) \
        * len(D.MIN_INSTANCES_PER_NODE) == 18
    fixed = config["pool"]["rf"]["fixed_grid"]
    trees = fixed["num_trees"]
    assert fixed == {"max_depth": 6, "max_bins": 32, "num_trees": trees,
                     "min_instances_per_node": 10, "subsampling_rate": 1.0,
                     "feature_subset_strategy": "auto", "impurity": "gini"}
    assert fixed["max_depth"] in D.MAX_DEPTH \
        and fixed["min_instances_per_node"] in D.MIN_INSTANCES_PER_NODE
    grid = cell["families"]["rf"]["grid"]["min_info_gain"]
    assert grid[0] == 0.001 and len(grid) == 2 \
        and set(grid) < set(D.MIN_INFO_GAIN)
    assert config["rf_grid"] == 2 and set(config["reduced"]) == {
        "rf_grid", "num_trees"}
    # the tree cells' rows, columns, folds; sweep-mlr-k32's generator
    rf = _load("configs", "binary-10m-64-rf.json")["sizes"]
    assert {k: v for k, v in config["sizes"].items() if k != "classes"} == rf
    assert config["sizes"]["classes"] == K
    assert config["truth_scale"] == _load(
        "configs", "multiclass-25m-64-k32.json")["truth_scale"]
    est = MT.OpRandomForestClassifier(**fixed)
    check = cell["checks"]["forest_answer"]
    want = cell["expect"]["forest_lanes"]
    # the square root of 64 columns
    assert T.features_per_node(MT._feature_frac("auto", 64, True), 64) \
        == check["features_per_node"] == want["features_per_node"] == 8
    assert want["payload_body"] == MT.payload_body(
        est, multiclass=True, n_classes=K) == WORD
    assert want["payload_rows"] == T.payload_rows(WORD, K) == K + 1 \
        and want["classes"] == K
    assert cell["chips"] == 1 and cell["min_jobs"] == 3
    # two trees' fold lanes a group under the output-block cap
    groups = 2 * -(-trees // 2)
    assert (want["tree_lanes"], want["lane_groups"],
            want["lanes_per_group"], want["bootstrap_draws"]) == (
        2 * trees * 5, groups, 10, groups * 2 * 10_000_000)
    reh = config["rehearsal"]
    assert T.features_per_node(
        MT._feature_frac("auto", reh["cols"], True), reh["cols"]) \
        == check["rehearsal"]["features_per_node"] \
        == cell["rehearsal"]["forest_lanes"]["features_per_node"]
    # every tolerance says where it was pinned
    for block in cell["checks"].values():
        assert "my chip runs, PR 54" in block["pinned_from"]


# -- the plain reference ----------------------------------------------------------

def _small(n=3000, f=16, bins=8, seed=5, classes=K):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    B = 3.0 * rng.normal(size=(f, classes)) / np.sqrt(f)
    y = np.argmax(X @ B - np.log(np.arange(classes) + 1.0)
                  + rng.gumbel(size=(n, classes)), axis=1).astype(np.float32)
    edges = RF.quantile_edges(jnp.asarray(X), bins)
    return X, y, edges, RF.binned(jnp.asarray(X), edges)


def test_the_class_histograms_and_the_gini_gain_by_hand():
    import jax.numpy as jnp
    X, y, _, Xb_t = _small(n=2000, f=4)
    Xb = np.asarray(Xb_t)
    rng = np.random.default_rng(1)
    w = rng.poisson(1.0, len(y)).astype(np.float32)
    node = rng.integers(0, 3, len(y))
    pay = RM.class_payload(jnp.asarray(w), jnp.asarray(y), K)
    hist = RF.level_histograms(Xb_t, jnp.asarray(node, jnp.int32), pay, 3, 9)
    ref = np.zeros((3, K + 2, X.shape[1], 9))
    for i in range(len(y)):
        for f in range(X.shape[1]):
            ref[node[i], int(y[i]), f, Xb[f, i]] += w[i]
            ref[node[i], K, f, Xb[f, i]] += w[i]
            ref[node[i], K + 1, f, Xb[f, i]] += w[i] > 0
    assert np.array_equal(hist, ref)     # whole numbers: equal, not close
    gain, c_left, c_right = RM.class_gains(hist)
    k, f, t = 1, 2, 4
    w = w.astype(np.float64)
    rows = node == k
    left = rows & (Xb[f] <= t)
    right = rows & ~left

    def impurity(m):
        share = np.array([w[m & (y == c)].sum() for c in range(K)]) \
            / w[m].sum()
        return 1.0 - (share ** 2).sum()
    by_hand = impurity(rows) \
        - w[left].sum() / w[rows].sum() * impurity(left) \
        - w[right].sum() / w[rows].sum() * impurity(right)
    assert gain[k, f, t] == pytest.approx(by_hand, rel=1e-9)
    assert c_left[k, f, t] == (w[left] > 0).sum() \
        and c_right[k, f, t] == (w[right] > 0).sum()
    # two classes: reference_forest's two-class gain, the same numbers
    two = RM.class_gains(np.stack(
        [hist[:, 0], hist[:, K] - hist[:, 0], hist[:, K], hist[:, K + 1]],
        axis=1))[0]
    old = RF.candidate_gains(np.stack(
        [hist[:, 0], hist[:, K], hist[:, K + 1]], axis=1))[0]
    np.testing.assert_allclose(two, old, rtol=1e-12)
    np.testing.assert_array_equal(two, RM.one_vs_rest_gains(hist, 0))


def test_a_plain_tree_obeys_its_own_rule_and_the_forest_learns():
    import jax.numpy as jnp
    X, y, _, Xb_t = _small()
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.poisson(1.0, len(y)), jnp.float32)
    yd = jnp.asarray(y)
    kw = dict(depth=3, bins=9, classes=K, min_instances=10.0,
              min_info_gain=0.005)
    tree = RM.grow_plain_tree(Xb_t, yd, w, np.random.default_rng(9),
                              features_per_node=4, **kw)
    g = np.random.default_rng(9)
    subsets = np.concatenate([RF.node_subsets(g, 1 << d, 16, 4)
                              for d in range(3)])
    r = RM.split_replay(Xb_t, yd, w, tree, subsets, **kw)
    assert not r["not_allowed"] and not r["dead_but_allowed"]
    assert r["gain_shortfall"] == 0.0 and r["splits_off_best"] == 0
    assert r["leaf_worst"] < 1e-6 and r["subset_sizes"] == [4]
    assert tree["leaf"].shape == (8, K)
    # a worse bin of the same column; a root left unsplit
    worse = dict(tree, thresh=tree["thresh"].copy())
    worse["thresh"][0] = 1 if tree["thresh"][0] != 1 else 2
    r2 = RM.split_replay(Xb_t, yd, w, worse, subsets, **kw)
    assert r2["gain_shortfall"] > 0.05 or r2["not_allowed"]
    dead = dict(tree, thresh=tree["thresh"].copy())
    dead["thresh"][0] = 8
    assert RM.split_replay(Xb_t, yd, w, dead, subsets,
                           **kw)["dead_but_allowed"]
    votes = RM.plain_forest_mc(
        jnp.asarray(X[:2000]), y[:2000], jnp.asarray(X[2000:]), classes=K,
        trees=5, depth=3, bins=8, min_instances=10, min_info_gain=0.001,
        features_per_node=4, seed=3)
    assert votes.shape == (K, 1000)
    np.testing.assert_allclose(votes.sum(axis=0), 5.0, rtol=1e-6)
    prior = np.bincount(y[2000:].astype(int)).max() / 1000
    assert RM.vote_error(votes, y[2000:]) < 1.0 - prior - 0.05


@pytest.mark.parametrize("wrong", [None, "half_gain", "left_out",
                                   "one_vs_rest", "raw_leaves",
                                   "bf16_leaves", "a_third_of_columns"])
def test_the_programs_lanes_replay_and_wrong_builds_do_not(wrong):
    """The system against the reference at a small size: every lane of a
    group the program grew obeys the rule on exact class histograms within
    the cell's own tolerances; a build that halves minInfoGain as the
    binary lanes do, leaves a class channel out of the gain, splits on the
    largest class's one-vs-rest gain, does not renormalise its leaves,
    rounds them to bfloat16, or draws another count of columns a node is
    refused by a check. (A tree missing from the votes: the rehearsal's
    vote_worst_if_a_tree_were_missing.)"""
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.models import trees as MT
    from transmogrifai_tpu.ops import trees as T
    tol = _load("workloads", CELL + ".json")["checks"]["forest_answer"]
    X, y, _, _ = _small(n=4000, f=16)
    Xd, yd = jnp.asarray(X), jnp.asarray(y)
    edges = T.quantile_edges(Xd, 8)
    Xb = T.bin_matrix(Xd, edges)
    Xb_t = RM.binned(Xd, np.asarray(edges))
    folds, trees, depth, thr = 2, 2, 3, 0.004
    fold = np.random.default_rng(0).integers(0, folds, len(y))
    W = jnp.asarray((fold[None] != np.arange(folds)[:, None])
                    .astype(np.float32))
    rw, kf = T.forest_bootstrap(jax.random.PRNGKey(5), 0, 1.0,
                                n_rows=len(y), n_trees=trees, group=trees)
    frac = MT._feature_frac("auto", 16, wrong != "a_third_of_columns")
    want_cols = T.features_per_node(MT._feature_frac("auto", 16, True), 16)

    def grow(min_info_gain, y_fit=yd, classes=K):
        return T.fit_forest_lanes(
            Xb, y_fit, W, rw, kf, jnp.zeros((folds, classes, len(y))),
            depth=depth, n_bins=8, feature_frac=frac, min_instances=10.0,
            min_info_gain=min_info_gain, payload="class_indicators",
            classes=classes)[1:]

    def replay(grown, subsets, t, f, min_info_gain, leaf=None):
        lane = t * folds + f
        tree = {k: np.asarray(getattr(grown, k)[lane])
                for k in ("feat", "thresh", "miss", "leaf")}
        if leaf is not None:
            tree["leaf"] = leaf(tree["leaf"])
        return RM.split_replay(
            Xb_t, yd, W[f] * rw[t], tree, np.asarray(subsets[t]),
            depth=depth, bins=9, classes=K, min_instances=10.0,
            min_info_gain=min_info_gain)
    lanes = [(t, f) for t in range(trees) for f in range(folds)]
    if wrong == "half_gain":
        # a threshold 1.5 x the smallest gain any lane's free growth
        # chose: the rule stops that node, half the threshold splits it
        free, subsets = grow(1e-4)
        thr = 1.5e-4 * min(replay(free, subsets, t, f, 1e-4)[
            "min_gain_margin"] for t, f in lanes)
    if wrong == "left_out":
        # the last class folded into its neighbour: K - 1 channels' gain
        merged = jnp.minimum(yd, K - 2)
        grown, subsets = grow(thr, merged, K - 1)
        grown = grown._replace(leaf=jnp.pad(
            grown.leaf, ((0, 0), (0, 0), (0, 1))))
    elif wrong == "one_vs_rest":
        big = float(np.bincount(y.astype(int)).argmax())
        grown, subsets = grow(thr, (yd != big).astype(jnp.float32), 2)
        grown = grown._replace(leaf=jnp.pad(
            grown.leaf, ((0, 0), (0, 0), (0, K - 2))))
    else:
        grown, subsets = grow(thr * (0.5 if wrong == "half_gain" else 1.0))
    leaf = {"bf16_leaves": RM._as_bf16,
            "raw_leaves": lambda a: a * 100.0}.get(wrong)
    found = {"not_allowed": 0, "leaf": 0, "subsets": 0, "gain": 0,
             "dead": 0}
    for t, f in lanes:
        r = replay(grown, subsets, t, f, thr, leaf)
        found["not_allowed"] += len(r["not_allowed"])
        found["dead"] += len(r["dead_but_allowed"])
        found["leaf"] += r["leaf_worst"] > tol["tol_leaf"]
        found["subsets"] += r["subset_sizes"] != [want_cols]
        found["gain"] += r["gain_shortfall"] > tol["tol_gain"]
        if wrong is None:   # what the wrong builds would have chosen
            assert r["left_out_splits_differ"] + r["ovr_splits_differ"] > 0
            assert r["leaf_worst_if_bf16"] > tol["tol_leaf"]
            assert r["leaf_worst_if_unnormalised"] > 1
    wrong_gain = wrong in ("left_out", "one_vs_rest")
    assert (found["not_allowed"] > 0) is (wrong == "half_gain")
    assert (found["gain"] + found["dead"] > 0) is wrong_gain
    assert (found["leaf"] > 0) is (
        wrong in ("bf16_leaves", "raw_leaves") or wrong_gain)
    assert (found["subsets"] > 0) is (wrong == "a_third_of_columns")
