"""The regression-forest cell `sweep-rf-regression` and what it brought to
the benchmark: the cell rehearsed on the CPU traced and untraced with its
metrics printed and its spans found, the refusal of a program that does not
say how its payload is carried, the manifest by membership, the
configuration against upstream's DefaultSelectorParams, the data against
`sweep-rf`'s matrix, benchmark/reference_forest_reg.py against numpy by
hand and against lanes the program grew — and the named wrong builds (one
bfloat16 part, minInfoGain halved, leaves rounded to bfloat16, a tree
missing, 8 columns a node) each refused by a check."""
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

from benchmark import datagen, datagen_forest_reg, harness  # noqa: E402
from benchmark import reference_forest_reg as RR  # noqa: E402
from benchmark.reduce_trace import Reduced  # noqa: E402

CELL = "sweep-rf-regression"
CONFIG = "regression-10m-64-rf"


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
         "--workload", CELL, "--seed", "4600000007", "--seconds", "2",
         "--trace", str(trace), "--rehearse", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    report, line = map(json.loads, r.stdout.strip().splitlines())
    assert line["correct"] is True, report["problems"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    notes = report["notes"]
    assert notes["payload_body_declared"] == "centred_parts"
    assert notes["routes"]["cells"] == [
        ["OpRandomForestRegressor", "mask_folds:forest_lanes"]]
    # 2 points x 4 trees x 3 folds, a group a point; 8 columns: 3 a node
    assert notes["forest_lanes"] == {
        "model": "OpRandomForestRegressor", "route": "forest_lanes",
        "tree_lanes": 24, "lane_groups": 2, "lanes_per_group": 12,
        "bootstrap_draws": 2 * 4 * 4096, "payload_body": "centred_parts",
        "payload_rows": 5, "features_per_node": 3, "label_centre": 10.0,
        "payload_scale": 128.0}
    prog = notes["program"]
    assert prog["groups"] == 2 and prog["calls_said"] == [
        {"payload": "centred_parts", "centre": [10.0, 128.0]}] * 2
    assert prog["fold_metrics_spans"][0]["metric_body"] == "vmapped"
    answer = notes["forest_answer"]
    assert answer["bins_identical"] is True and len(answer["replay"]) == 3
    for r_ in answer["replay"]:
        assert not r_["not_allowed"] and not r_["dead_but_allowed"]
        assert r_["gain_shortfall"] < 1e-6 and r_["subset_sizes"] == [3]
        # float32 leaves of exact sums; of ONE bfloat16 part, or rounded
        # to bfloat16 themselves, they are not
        assert r_["leaf_worst"] < 2e-6 < 1e-4 < r_["leaf_worst_if_one_part"]
        assert r_["leaf_worst_if_bf16"] > 1e-3
    votes = answer["votes"]
    assert votes["vote_worst"] < 1e-4 and votes["held_rows"] > 1000
    assert votes["metric_delta"] < 1e-6 and \
        answer["every_fold_metric_delta"] < 1e-6
    assert votes["metric_delta_if_a_tree_were_missing"] > 1e-4
    assert votes["metric_delta_if_bf16_leaves"] > 1e-5
    assert votes["vote_worst_if_bf16_leaves"] > 1e-2
    assert answer["order"]["misordered"] == []
    assert len(answer["points"]) == 2
    for p in answer["points"]:
        b = p["bootstrap"]
        assert b["trees"] == 4 and b["equal_pairs"] == 0
        assert b["mean_worst"] < 0.1 and b["correlation_worst"] < 0.1
        assert abs(p["sweep"] - p["reference"]) < 0.2
    twins = notes["real_payload_twins"]
    assert {t["kernel"] for t in twins} == {"hist_folds", "route_hist"}
    for t in twins:
        assert t["payload_parts"] == 3 and t["h_and_counts_exact"]
        assert t["g_worst_share"] < 1e-5 < 1e-4 \
            < t["g_worst_share_if_one_part"]
    assert {t["kernel"] for t in notes["kernel_twins"]} == {
        "route", "table_lookup"}
    counters = report["counters"]
    assert counters["rfr_tree_lanes"] == 24 \
        and counters["rfr_lane_groups"] == 2 \
        and counters["rfr_lanes_per_group"] == 12 \
        and counters["rfr_payload_rows"] == 5
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"gbt_sweep_s", "setup_s"}
        assert all(m["value"] > 0 for m in metrics.values())
        return
    specs = _layer_specs()
    assert set(specs) == {
        "rfr_tree_device_s", "rfr_hist_kernel_s", "rfr_hist_kernel_roofline",
        "rfr_bootstrap_device_s", "rfr_centre_device_s",
        "rfr_metric_device_s", "rfr_host_gap_s", "rfr_fit_host_s",
        "rfr_lane_groups", "rfr_payload_rows"}
    # the kernels and the roofline need the chip (no Mosaic custom call, no
    # peaks here); every other metric of the cell is printed
    assert set(metrics) >= (
        set(specs) - {"rfr_hist_kernel_s", "rfr_hist_kernel_roofline"}) \
        | {"programs_compiled", "window_compiles"}
    for name, m in metrics.items():
        if name in specs:
            assert m["unit"] == specs[name]["unit"], name
            assert m["value"] >= 0, name
    assert metrics["window_compiles"]["value"] == 0
    assert metrics["rfr_lane_groups"]["value"] == 2
    assert metrics["rfr_payload_rows"]["value"] == 5
    assert metrics["rfr_tree_device_s"]["value"] \
        > metrics["rfr_bootstrap_device_s"]["value"] > 0
    assert metrics["rfr_metric_device_s"]["value"] > 0
    assert metrics["rfr_centre_device_s"]["value"] > 0
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


def test_a_program_without_the_predicate_is_refused_before_any_data(
        monkeypatch, tmp_path):
    """What the parent of this cell's PR does: models/trees has no
    forest_payload_body, and the driver fails with BenchFailure before it
    makes a byte of data; so does a program whose word is another."""
    from transmogrifai_tpu.models import trees as MT
    driver = harness.load_module("drivers", "sweep_forest_reg")

    def no_data(*a, **k):
        raise AssertionError("data was made")
    monkeypatch.setattr(datagen_forest_reg, "device_matrix", no_data)
    cell, config = _load("workloads", CELL + ".json"), \
        _load("configs", CONFIG + ".json")
    ctx = harness.Ctx(cell=cell, config=config, sizes=dict(config["sizes"]),
                      seed=1, seconds=1.0, trace=False, rehearse=False,
                      out_dir=str(tmp_path), compile_log=None)
    monkeypatch.setattr(MT, "forest_payload_body", lambda est: "indicator")
    with pytest.raises(harness.BenchFailure,
                       match="rounded ONCE to bfloat16"):
        driver.setup(ctx)
    monkeypatch.delattr(MT, "forest_payload_body")
    with pytest.raises(harness.BenchFailure,
                       match="names None, not 'centred_parts'"):
        driver.setup(ctx)
    monkeypatch.undo()
    monkeypatch.setattr(datagen_forest_reg, "device_matrix", no_data)
    # the word is there; this backend runs no fused kernels: the lane
    # route's own question refuses next, still before any data
    with pytest.raises(harness.BenchFailure,
                       match="declares no forest lane route"):
        driver.setup(ctx)


def test_manifest_lists_the_cell_under_gbt_sweep_s():
    """Membership and order, not position from the end: a later PR appends
    after these entries."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["gbt_sweep_s"]["workloads"][:3] == [
        "sweep-gbt", "sweep-rf", CELL]
    assert CELL not in e2e["glm_sweep_s"]["workloads"]
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == cells.index("sweep-linreg-nulls128") + 1
    entry = manifest["workloads"][cells.index(CELL)]
    assert entry["config"] == CONFIG and entry["chips"] == 1
    assert entry["traffic"] == "rfr-closed-1" and len(entry["why"]) <= 200
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
    # the share of the roofline reads the accepted work model as it stands
    roof = specs["rfr_hist_kernel_roofline"]
    assert roof["args"]["opcount"] == "opcount_forest" \
        and roof["args"]["work"] == "forest_sweep" \
        and roof["reader"] == "roofline_of" and roof["unit"] == "%"


def test_the_configuration_is_upstreams_defaults_cut_as_it_says():
    from transmogrifai_tpu.automl import selectors as S
    from transmogrifai_tpu.models import trees as MT
    from transmogrifai_tpu.ops import trees as T
    config, cell = _load("configs", CONFIG + ".json"), \
        _load("workloads", CELL + ".json")
    D = S.DefaultSelectorParams
    assert {"OpLinearRegression", "OpRandomForestRegressor",
            "OpGBTRegressor"} <= set(
        S.RegressionModelSelector.default_model_types)
    src = config["source_sizes"]
    assert src["max_depth"] == D.MAX_DEPTH and [src["max_bins"]] == D.MAX_BIN
    assert src["min_instances_per_node"] == D.MIN_INSTANCES_PER_NODE
    assert src["min_info_gain"] == D.MIN_INFO_GAIN
    assert [src["num_trees"]] == D.MAX_TREES
    assert [src["subsampling_rate"]] == D.SUBSAMPLE_RATE
    assert src["rf_grid"] == len(D.MAX_DEPTH) * len(D.MIN_INFO_GAIN) \
        * len(D.MIN_INSTANCES_PER_NODE) == 18
    fixed = config["pool"]["rf"]["fixed_grid"]
    assert fixed == {"max_depth": 6, "max_bins": 32, "num_trees": 10,
                     "min_instances_per_node": 10, "subsampling_rate": 1.0,
                     "feature_subset_strategy": "auto",
                     "impurity": "variance"}
    assert fixed["max_depth"] in D.MAX_DEPTH \
        and fixed["min_instances_per_node"] in D.MIN_INSTANCES_PER_NODE
    grid = cell["families"]["rf"]["grid"]
    assert grid == {"min_info_gain": [0.001, 0.1]} \
        and set(grid["min_info_gain"]) < set(D.MIN_INFO_GAIN)
    assert config["rf_grid"] == 2 and set(config["reduced"]) == {
        "rf_grid", "num_trees"}
    # sweep-rf's matrix and folds: the control
    assert config["sizes"] == _load("configs", "binary-10m-64-rf.json")[
        "sizes"]
    est = MT.OpRandomForestRegressor(**fixed)
    assert not est.classification
    check = cell["checks"]["forest_answer"]
    want = cell["expect"]["forest_lanes"]
    # a third of 64 columns, Spark's ceiling
    assert T.features_per_node(MT._feature_frac("auto", 64, False), 64) \
        == check["features_per_node"] == want["features_per_node"] == 22
    assert want["payload_body"] == MT.forest_payload_body(est) \
        and want["payload_rows"] == T.forest_payload_rows(
            want["payload_body"]) == 5
    assert cell["chips"] == 1 and cell["min_jobs"] == 3
    # 10 trees at 3 a group: 4 groups a point, two slots of the 12 dead
    assert (want["tree_lanes"], want["lane_groups"],
            want["lanes_per_group"], want["bootstrap_draws"]) == (
        2 * 10 * 5, 8, 15, 8 * 3 * 10_000_000)
    reh = config["rehearsal"]
    assert T.features_per_node(
        MT._feature_frac("auto", reh["cols"], False), reh["cols"]) \
        == check["rehearsal"]["features_per_node"] \
        == cell["rehearsal"]["forest_lanes"]["features_per_node"]
    # every tolerance says where it was pinned
    for block in cell["checks"].values():
        assert "my chip runs, PR 46" in block["pinned_from"]


def test_the_data_is_sweep_rfs_matrix_under_a_real_label():
    import jax.numpy as jnp
    label = _load("configs", CONFIG + ".json")["label"]
    X, y = datagen_forest_reg.device_matrix(5000, 64, "bfloat16", 4600000123,
                                            **label)
    X0, y0 = datagen.device_matrix(5000, 64, "bfloat16", 4600000123)
    assert X.dtype == jnp.bfloat16 and bool(jnp.array_equal(X, X0))
    assert y.dtype == jnp.float32 and set(np.unique(np.asarray(y0))) == {
        0.0, 1.0}
    m = datagen_forest_reg.label_moments(64, **label)
    yh = np.asarray(y, np.float64)
    assert m["mean"] == 10.0 and 1.9 < m["std"] < 2.2
    assert abs(yh.mean() - m["mean"]) < 0.15 \
        and abs(yh.std() - m["std"]) < 0.1
    assert 4.5 < m["mean"] / m["std"] < 5.3      # deviations from zero
    X1, y1 = datagen_forest_reg.device_matrix(5000, 64, "bfloat16",
                                              4600000124, **label)
    assert not bool(jnp.array_equal(X, X1))


# -- the plain reference ----------------------------------------------------------

def _small(n=3000, f=16, bins=8, seed=5):
    import jax.numpy as jnp
    from benchmark import reference_forest as RF
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    beta = rng.normal(size=f) / np.sqrt(f)
    y = (10 + 1.7 * (X @ beta + 0.65 * rng.normal(size=n))) \
        .astype(np.float32)
    edges = RF.quantile_edges(jnp.asarray(X), bins)
    return X, y, edges, RF.binned(jnp.asarray(X), edges)


def test_the_exact_sums_and_the_variance_gain_by_hand():
    import jax.numpy as jnp
    X, y, _, Xb_t = _small(n=2000, f=4)
    Xb = np.asarray(Xb_t)
    rng = np.random.default_rng(1)
    w = rng.poisson(1.0, len(y)).astype(np.float32)
    node = rng.integers(0, 3, len(y))
    yd, wd = jnp.asarray(y), jnp.asarray(w)
    yq = np.asarray(RR.fixed_point(yd), np.float64) / 2.0 ** RR.SCALE_BITS
    assert np.abs(yq - y).max() <= 2.0 ** -22
    assert np.array_equal(yq[np.abs(y) >= 4], y[np.abs(y) >= 4])
    vq = RR.payload_rows(wd, RR.fixed_point(yd), yd)
    G, H, C = RR.exact_level_sums(Xb_t, jnp.asarray(node, jnp.int32), wd,
                                  vq, 3, 9)
    once = np.asarray((wd * yd).astype(jnp.bfloat16).astype(jnp.float32),
                      np.float64)
    ref = np.zeros((4, 3, X.shape[1], 9))
    for i in range(len(y)):
        for f in range(X.shape[1]):
            ref[:, node[i], f, Xb[f, i]] += (
                w[i] * (yq[i] + RR.OFFSET), once[i] + w[i] * RR.OFFSET,
                w[i], w[i] > 0)
    # whole-number sums put together in float64: equal, not close
    assert np.array_equal(G, ref[:2]) and np.array_equal(H, ref[2]) \
        and np.array_equal(C, ref[3])
    gain, c_left, c_right = RR.variance_gains(G[0] - RR.OFFSET * H, H, C)
    k, f, t = 1, 2, 4
    w = w.astype(np.float64)
    rows = node == k
    left = rows & (Xb[f] <= t)
    right = rows & ~left

    def impurity(m):
        mean = (w[m] * yq[m]).sum() / w[m].sum()
        return (w[m] * (yq[m] - mean) ** 2).sum() / w[m].sum()
    by_hand = impurity(rows) \
        - w[left].sum() / w[rows].sum() * impurity(left) \
        - w[right].sum() / w[rows].sum() * impurity(right)
    assert gain[k, f, t] == pytest.approx(by_hand, rel=1e-9)
    assert c_left[k, f, t] == (w[left] > 0).sum() \
        and c_right[k, f, t] == (w[right] > 0).sum()


def test_a_plain_tree_obeys_its_own_rule_and_the_forest_learns():
    import jax.numpy as jnp
    from benchmark import reference_forest as RF
    X, y, _, Xb_t = _small()
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.poisson(1.0, len(y)), jnp.float32)
    yd = jnp.asarray(y)
    kw = dict(depth=3, bins=9, min_instances=10.0, min_info_gain=0.01)
    tree = RR.grow_plain_tree(Xb_t, yd, w, np.random.default_rng(9),
                              features_per_node=6, **kw)
    g = np.random.default_rng(9)
    subsets = np.concatenate([RF.node_subsets(g, 1 << d, 16, 6)
                              for d in range(3)])
    r = RR.split_replay(Xb_t, yd, w, tree, subsets, **kw)
    assert not r["not_allowed"] and not r["dead_but_allowed"]
    assert r["gain_shortfall"] == 0.0 and r["splits_off_best"] == 0
    assert r["leaf_worst"] < 1e-6 and r["subset_sizes"] == [6]
    # a worse bin of the same column; a root left unsplit
    worse = dict(tree, thresh=tree["thresh"].copy())
    worse["thresh"][0] = 1 if tree["thresh"][0] != 1 else 2
    r2 = RR.split_replay(Xb_t, yd, w, worse, subsets, **kw)
    assert r2["gain_shortfall"] > 0.05 or r2["not_allowed"]
    dead = dict(tree, thresh=tree["thresh"].copy())
    dead["thresh"][0] = 8
    assert RR.split_replay(Xb_t, yd, w, dead, subsets,
                           **kw)["dead_but_allowed"]
    pred = RR.plain_forest_reg(
        jnp.asarray(X[:2000]), y[:2000], jnp.asarray(X[2000:]), trees=5,
        depth=3, bins=8, min_instances=10, min_info_gain=0.001,
        features_per_node=6, seed=3)
    assert RR.rmse(pred, y[2000:]) < 0.9 * y[2000:].std()


@pytest.mark.parametrize("wrong", [None, "one_part", "half_gain",
                                   "bf16_leaves", "eight_columns"])
def test_the_programs_lanes_replay_and_wrong_builds_do_not(wrong):
    """The system against the reference at a small size: every lane of a
    group the program grew obeys the rule on exact sums within the cell's
    own tolerances; a build that hands the kernels ONE bfloat16 part,
    halves minInfoGain as the classifier's lanes do, rounds its leaves to
    bfloat16, or draws the classifier's sqrt(F) columns a node is refused
    by a check. (A tree missing from the votes: the rehearsal's
    metric_delta_if_a_tree_were_missing.)"""
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.models import trees as MT
    from transmogrifai_tpu.ops import trees as T
    tol = _load("workloads", CELL + ".json")["checks"]["forest_answer"]
    X, y, _, _ = _small(n=4000, f=16)
    Xd, yd = jnp.asarray(X), jnp.asarray(y)
    edges = T.quantile_edges(Xd, 8)
    Xb = T.bin_matrix(Xd, edges)
    Xb_t = RR.binned(Xd, np.asarray(edges))
    folds, trees, depth, thr = 2, 2, 3, 0.02
    fold = np.random.default_rng(0).integers(0, folds, len(y))
    W = jnp.asarray((fold[None] != np.arange(folds)[:, None])
                    .astype(np.float32))
    rw, kf = T.forest_bootstrap(jax.random.PRNGKey(5), 0, 1.0,
                                n_rows=len(y), n_trees=trees, group=trees)
    frac = MT._feature_frac("auto", 16, wrong == "eight_columns")
    kw = dict(payload="centred_parts",
              centre=T.forest_label_centre(yd, jnp.ones_like(yd)))
    if wrong == "one_part":         # the parent's lanes, through the kernel
        kw = dict(interpret=True)

    def grow(min_info_gain):
        return T.fit_forest_lanes(
            Xb, yd, W, rw, kf, jnp.zeros(W.shape), depth=depth, n_bins=8,
            feature_frac=frac, min_instances=10.0,
            min_info_gain=min_info_gain, **kw)[1:]
    if wrong == "half_gain":
        # a threshold 1.5 x the smallest gain any lane's free growth
        # chose: the rule stops that node, half the threshold splits it
        free, subsets = grow(1e-4)
        thr = 1.5e-4 * min(RR.split_replay(
            Xb_t, yd, W[f] * rw[t], {
                **{k: np.asarray(getattr(free, k)[t * folds + f])
                   for k in ("feat", "thresh", "miss")},
                "leaf": np.asarray(free.leaf[t * folds + f, :, 0])},
            np.asarray(subsets[t]), depth=depth, bins=9, min_instances=10.0,
            min_info_gain=1e-4)["min_gain_margin"]
            for t in range(trees) for f in range(folds))
    grown, subsets = grow(thr * (0.5 if wrong == "half_gain" else 1.0))
    found = {"not_allowed": 0, "leaf": 0, "subsets": 0, "other": 0}
    for t in range(trees):
        for f in range(folds):
            lane = t * folds + f
            tree = {k: np.asarray(getattr(grown, k)[lane])
                    for k in ("feat", "thresh", "miss")}
            tree["leaf"] = np.asarray(grown.leaf[lane, :, 0])
            if wrong == "bf16_leaves":
                tree["leaf"] = RR._as_bf16(tree["leaf"])
            r = RR.split_replay(
                Xb_t, yd, W[f] * rw[t], tree, np.asarray(subsets[t]),
                depth=depth, bins=9, min_instances=10.0, min_info_gain=thr)
            found["not_allowed"] += len(r["not_allowed"])
            found["leaf"] += r["leaf_worst"] > tol["tol_leaf"]
            found["subsets"] += r["subset_sizes"] != [6]   # ceil(16 / 3)
            found["other"] += len(r["dead_but_allowed"]) \
                + (r["gain_shortfall"] > tol["tol_gain"]
                   and wrong != "one_part")
    assert found["other"] == 0
    assert (found["not_allowed"] > 0) is (wrong == "half_gain")
    assert (found["leaf"] > 0) is (wrong in ("one_part", "bf16_leaves"))
    assert (found["subsets"] > 0) is (wrong == "eight_columns")
