"""The forest cell `sweep-rf` and what it brought to the benchmark: the cell
rehearsed on the CPU traced and untraced with its metrics printed and its
spans found, the refusal of a program that lacks the lane route, the
manifest by membership and order, the configuration against upstream's
DefaultSelectorParams, benchmark/reference_forest.py against numpy by hand
and against trees the program grew (and trees that were changed coming
out not allowed), benchmark/opcount_forest.py by hand."""
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

from benchmark import datagen, harness, opcount_forest  # noqa: E402
from benchmark import reference, reference_forest as RF  # noqa: E402
from benchmark.reduce_trace import Reduced  # noqa: E402

CELL = "sweep-rf"
CONFIG = "binary-10m-64-rf"


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
         "--workload", CELL, "--seed", "3100000007", "--seconds", "3",
         "--trace", str(trace), "--rehearse", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    report, line = map(json.loads, r.stdout.strip().splitlines())
    assert line["correct"] is True, report["problems"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    notes = report["notes"]
    assert notes["routes"]["cells"] == [
        ["OpRandomForestClassifier", "mask_folds:forest_lanes"]]
    # 2 points x 4 trees x 3 folds, a group a point
    assert notes["forest_lanes"] == {
        "model": "OpRandomForestClassifier", "route": "forest_lanes",
        "tree_lanes": 24, "lane_groups": 2, "lanes_per_group": 12,
        "bootstrap_draws": 2 * 4 * 4096}
    answer = notes["forest_answer"]
    assert answer["bins_identical"] is True and len(answer["replay"]) == 2
    for r_ in answer["replay"]:
        assert not r_["not_allowed"] and not r_["dead_but_allowed"]
        assert r_["gain_shortfall"] < 1e-4 and r_["subset_sizes"] == [3]
        # float32 leaves of exact sums; rounded to bfloat16 they are not
        assert r_["leaf_worst"] < 1e-6 < 1e-4 < r_["leaf_worst_if_bf16"]
    votes = answer["votes"]
    assert votes["vote_worst"] < 1e-5 and votes["held_rows"] > 1000
    assert votes["metric_delta"] < 1e-3
    assert votes["metric_delta_if_a_tree_were_missing"] > votes["metric_delta"]
    assert len(answer["points"]) == 2
    for p in answer["points"]:
        b = p["bootstrap"]
        assert b["trees"] == 4 and b["equal_pairs"] == 0
        assert b["mean_worst"] < 0.1 and b["correlation_worst"] < 0.1
        assert abs(p["sweep"] - p["reference"]) < 0.2
    twins = {t["kernel"] for t in notes["kernel_twins"]}
    assert {"hist_folds", "route_hist", "route", "table_lookup"} <= twins
    counters = report["counters"]
    assert counters["rf_tree_lanes"] == 24 and counters["rf_lane_groups"] == 2
    assert counters["rf_lanes_per_group"] == 12
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"gbt_sweep_s", "setup_s"}
        assert all(m["value"] > 0 for m in metrics.values())
        return
    specs = _layer_specs()
    assert set(specs) == {
        "rf_tree_device_s", "rf_hist_kernel_s", "rf_hist_kernel_roofline",
        "rf_bootstrap_device_s", "rf_metric_device_s", "rf_host_gap_s",
        "rf_fit_host_s", "rf_lane_groups"}
    # the kernels and the roofline need the chip (no Mosaic custom call, no
    # peaks here); every other metric is printed
    assert set(metrics) == (
        set(specs) - {"rf_hist_kernel_s", "rf_hist_kernel_roofline"}) \
        | {"programs_compiled", "window_compiles"}
    for name, m in metrics.items():
        if name in specs:
            assert m["unit"] == specs[name]["unit"], name
            assert m["value"] >= 0, name
    assert metrics["window_compiles"]["value"] == 0
    assert metrics["rf_lane_groups"]["value"] == 2
    assert metrics["rf_tree_device_s"]["value"] \
        > metrics["rf_bootstrap_device_s"]["value"] > 0
    assert metrics["rf_metric_device_s"]["value"] > 0
    # the new spans, under the validate root and on its thread
    ctx = types.SimpleNamespace(
        reduced=Reduced.from_file(notes["xplane"]),
        cell={"job_span": "bench.validate"})

    def count(name):
        return harness.load_module("readers", "host_span").read(
            ctx, {"name": name, "stat": "count"})
    assert count(r"^tmog\.validate:CrossValidation$") == 1
    assert count(r"^tmog\.validate_phase:tree_fit$") == 2
    assert count(r"^tmog\.tree_fused:forest_group$") == 2
    assert count(r"^tmog\.tree_fused:forest_bootstrap$") == 2


def test_a_program_without_the_route_is_refused_before_any_data(
        monkeypatch, tmp_path):
    """What the parent of this cell's PR does: models/trees declares no
    forest lane route, and the driver fails with BenchFailure before it
    makes a byte of data."""
    from transmogrifai_tpu.models import trees as MT
    driver = harness.load_module("drivers", "sweep_forest")

    def no_data(*a, **k):
        raise AssertionError("data was made")
    monkeypatch.setattr(datagen, "device_matrix", no_data)
    cell, config = _load("workloads", CELL + ".json"), \
        _load("configs", CONFIG + ".json")
    ctx = harness.Ctx(cell=cell, config=config, sizes=dict(config["sizes"]),
                      seed=1, seconds=1.0, trace=False, rehearse=False,
                      out_dir=str(tmp_path), compile_log=None)
    # this backend runs no fused kernels: declined as on a parent
    with pytest.raises(harness.BenchFailure,
                       match="declares no forest lane route"):
        driver.setup(ctx)
    monkeypatch.delattr(MT, "forest_lane_route_ok")
    with pytest.raises(harness.BenchFailure,
                       match="declares no forest lane route"):
        driver.setup(ctx)


def test_manifest_lists_the_cell_under_gbt_sweep_s():
    """Membership and order, not position from the end: a later PR appends
    after these entries."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["gbt_sweep_s"]["workloads"][:2] == ["sweep-gbt", CELL]
    assert CELL not in e2e["glm_sweep_s"]["workloads"]
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == cells.index("sweep-glm-wide4k") + 1
    entry = manifest["workloads"][cells.index(CELL)]
    assert entry["config"] == CONFIG and entry["chips"] == 1
    assert entry["traffic"] == "rf-closed-1" and len(entry["why"]) <= 200
    conf = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == ["rf_grid", "num_trees"] \
        and conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(conf["why"]) <= 200 and len(conf["source"]) <= 200
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    specs = _layer_specs()
    assert sorted(m["name"] for m in mine) == sorted(specs)
    for m in mine:
        spec = specs[m["name"]]
        assert m["moves"] == spec["moves"] == "gbt_sweep_s"
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            spec["unit"], spec["better"], spec["source"], spec["layer"])


def test_the_configuration_is_upstreams_defaults_cut_as_it_says():
    from transmogrifai_tpu.automl import selectors as S
    from transmogrifai_tpu.models import trees as MT
    config, cell = _load("configs", CONFIG + ".json"), \
        _load("workloads", CELL + ".json")
    D = S.DefaultSelectorParams
    for sel in (S.BinaryClassificationModelSelector,
                S.MultiClassificationModelSelector):
        assert "OpRandomForestClassifier" in sel.default_model_types
    assert "OpRandomForestRegressor" in \
        S.RegressionModelSelector.default_model_types
    src = config["source_sizes"]
    assert src["max_depth"] == D.MAX_DEPTH and [src["max_bins"]] == D.MAX_BIN
    assert src["min_instances_per_node"] == D.MIN_INSTANCES_PER_NODE
    assert src["min_info_gain"] == D.MIN_INFO_GAIN
    assert [src["num_trees"]] == D.MAX_TREES
    assert [src["subsampling_rate"]] == D.SUBSAMPLE_RATE
    assert src["rf_grid"] == len(D.MAX_DEPTH) * len(D.MIN_INFO_GAIN) \
        * len(D.MIN_INSTANCES_PER_NODE) == 18
    fixed = config["pool"]["rf"]["fixed_grid"]
    assert fixed == {"max_depth": 6, "max_bins": 32, "num_trees": 20,
                     "min_info_gain": 0.001, "subsampling_rate": 1.0,
                     "feature_subset_strategy": "auto", "impurity": "gini"}
    assert fixed["max_depth"] in D.MAX_DEPTH \
        and fixed["min_info_gain"] in D.MIN_INFO_GAIN
    grid = cell["families"]["rf"]["grid"]
    assert grid == {"min_instances_per_node": D.MIN_INSTANCES_PER_NODE}
    assert config["rf_grid"] == 2 and set(config["reduced"]) == {
        "rf_grid", "num_trees"}
    assert config["sizes"] == _load("configs", "binary-10m-64.json")["sizes"]
    # the declared defaults of the estimator are the source's other values
    est = MT.OpRandomForestClassifier(**fixed)
    assert est.get_param("impurity") == "gini"
    # sqrt(64) = 8 columns a node, the check's own figure
    frac = MT._feature_frac("auto", 64, True)
    check = cell["checks"]["forest_answer"]
    assert max(1, int(round(frac * 64))) == check["features_per_node"] == 8
    assert cell["chips"] == 1 and cell["min_jobs"] == 3
    assert cell["expect"]["forest_lanes"] == {
        "tree_lanes": 2 * 20 * 5, "lane_groups": 8, "lanes_per_group": 25,
        "bootstrap_draws": 8 * 5 * 10_000_000}
    reh = config["rehearsal"]
    assert max(1, int(round(MT._feature_frac("auto", reh["cols"], True)
                            * reh["cols"]))) \
        == check["rehearsal"]["features_per_node"]


# -- the plain reference ----------------------------------------------------------

def _small(n=3000, f=6, bins=8, seed=5):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    beta = rng.normal(size=f)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-X @ beta))).astype(np.float32)
    edges = RF.quantile_edges(jnp.asarray(X), bins)
    return X, y, edges, RF.binned(jnp.asarray(X), edges)


def test_the_pieces_against_numpy_by_hand():
    import jax.numpy as jnp
    X, y, edges, Xb_t = _small()
    Xb = np.asarray(Xb_t)
    want = 1 + (X.T[:, :, None] >= edges[:, None, :]).sum(axis=2)
    assert np.array_equal(Xb, want) and Xb.min() >= 1 and Xb.max() == 8
    rng = np.random.default_rng(1)
    w = rng.poisson(1.0, len(y)).astype(np.float32)
    node = rng.integers(0, 4, len(y))
    pay = np.stack([w * y, w, (w > 0).astype(np.float32)])
    got = RF.level_histograms(Xb_t, jnp.asarray(node, jnp.int32),
                              jnp.asarray(pay), 4, 9)
    ref = np.zeros((4, 3, X.shape[1], 9))
    for i in range(len(y)):
        for f in range(X.shape[1]):
            ref[node[i], :, f, Xb[f, i]] += pay[:, i]
    assert np.array_equal(got, ref)              # integers: exact
    # the Gini gain of one candidate, from the definition
    gain, c_left, c_right = RF.candidate_gains(got)
    k, f, t = 2, 3, 4
    rows = node == k
    left = rows & (Xb[f] <= t)
    right = rows & ~left

    def impurity(m):
        p1 = (w[m] * y[m]).sum() / w[m].sum()
        return 1 - p1 ** 2 - (1 - p1) ** 2
    by_hand = impurity(rows) \
        - w[left].sum() / w[rows].sum() * impurity(left) \
        - w[right].sum() / w[rows].sum() * impurity(right)
    assert gain[k, f, t] == pytest.approx(by_hand, rel=1e-5)
    assert c_left[k, f, t] == (w[left] > 0).sum() \
        and c_right[k, f, t] == (w[right] > 0).sum()
    sub = RF.node_subsets(np.random.default_rng(0), 63, 64, 8)
    assert sub.shape == (63, 64) and (sub.sum(axis=1) == 8).all() \
        and len({s.tobytes() for s in sub}) == 63
    assert np.array_equal(RF._as_bf16(np.array([1.0, 0.3, 1 / 3])),
                          np.array([1.0, 0.30078125, 0.333984375]))


def test_a_plain_tree_obeys_its_own_rule_and_a_changed_one_does_not():
    import jax.numpy as jnp
    X, y, edges, Xb_t = _small()
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.poisson(1.0, len(y)), jnp.float32)
    yd = jnp.asarray(y)
    kw = dict(depth=3, bins=9, min_instances=10.0, min_info_gain=0.002)
    # the tree and the subsets it drew, from the same generator state
    tree = RF.grow_plain_tree(Xb_t, yd, w, np.random.default_rng(9),
                              features_per_node=2, **kw)
    g = np.random.default_rng(9)
    subsets = np.concatenate([RF.node_subsets(g, 1 << d, 6, 2)
                              for d in range(3)])
    r = RF.split_replay(Xb_t, yd, w, tree, subsets, **kw)
    assert not r["not_allowed"] and not r["dead_but_allowed"]
    assert r["gain_shortfall"] == 0.0 and r["leaf_worst"] < 1e-7
    assert r["subset_sizes"] == [2] and r["nodes"] == 7
    # a split on a column outside its node's subset
    bad = dict(tree, feat=tree["feat"].copy())
    bad["feat"][0] = int(np.flatnonzero(~subsets[0])[0])
    assert RF.split_replay(Xb_t, yd, w, bad, subsets, **kw)["not_allowed"]
    # a worse bin of the same column
    worse = dict(tree, thresh=tree["thresh"].copy())
    worse["thresh"][0] = 1 if tree["thresh"][0] != 1 else 2
    r2 = RF.split_replay(Xb_t, yd, w, worse, subsets, **kw)
    assert r2["gain_shortfall"] > 0.05 or r2["not_allowed"]
    # a root left unsplit that had an allowed candidate
    dead = dict(tree, thresh=tree["thresh"].copy())
    dead["thresh"][0] = 8
    assert RF.split_replay(Xb_t, yd, w, dead, subsets,
                           **kw)["dead_but_allowed"]
    # leaves rounded to bfloat16
    r3 = RF.split_replay(Xb_t, yd, w,
                         dict(tree, leaf=RF._as_bf16(tree["leaf"])),
                         subsets, **kw)
    assert r3["leaf_worst"] > 1e-4
    # the forest learns: held-out AuPR above the positive rate
    votes = RF.plain_forest(jnp.asarray(X[:2000]), y[:2000],
                            jnp.asarray(X[2000:]), trees=5, depth=3, bins=8,
                            min_instances=10, min_info_gain=0.001,
                            features_per_node=2, seed=3)
    assert reference.numpy_au_pr(votes, y[2000:], np.ones(1000)) \
        > y[2000:].mean() + 0.15


@pytest.mark.parametrize("wrong", [None, "half_scale", "one_subset_a_tree",
                                   "no_bootstrap"])
def test_the_programs_lanes_replay_and_wrong_builds_do_not(wrong):
    """The system against the reference at a small size: every lane of a
    group the program grew obeys the rule on exact histograms; a build
    that compares the one-channel gain with the two-class threshold, draws
    one subset a tree, or drops the bootstrap is refused by a check."""
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import trees as T
    X, y, _, _ = _small(n=4000, f=8)
    Xd = jnp.asarray(X)
    edges = T.quantile_edges(Xd, 8)
    Xb = T.bin_matrix(Xd, edges)
    Xb_t = RF.binned(Xd, np.asarray(edges))
    assert np.array_equal(np.asarray(Xb_t), np.asarray(Xb).T)
    folds, trees, depth, thr = 2, 3, 3, 0.004
    fold = np.random.default_rng(0).integers(0, folds, len(y))
    W = jnp.asarray((fold[None] != np.arange(folds)[:, None])
                    .astype(np.float32))
    rw, kf = T.forest_bootstrap(jax.random.PRNGKey(5), 0, 1.0,
                                n_rows=len(y), n_trees=trees, group=trees,
                                bootstrap=wrong != "no_bootstrap")
    if wrong == "one_subset_a_tree":   # every level from the same key
        orig = T._feature_mask
        T._feature_mask = lambda k, n, F, frac: jnp.broadcast_to(
            orig(jax.random.PRNGKey(0), 1, F, frac), (n, F))
    try:
        _, grown, subsets = T.fit_forest_lanes.__wrapped__(
            Xb, jnp.asarray(y), W, rw, kf, jnp.zeros(W.shape), depth=depth,
            n_bins=8, feature_frac=3 / 8, min_instances=10.0,
            min_info_gain=thr * (1.0 if wrong == "half_scale" else 0.5))
    finally:
        if wrong == "one_subset_a_tree":
            T._feature_mask = orig
    found = {"dead_but_allowed": 0, "few_subsets": 0, "other": 0}
    for t in range(trees):
        for f in range(folds):
            lane = t * folds + f
            tree = {k: np.asarray(getattr(grown, k)[lane])
                    for k in ("feat", "thresh", "miss")}
            tree["leaf"] = np.asarray(grown.leaf[lane, :, 0])
            r = RF.split_replay(
                Xb_t, jnp.asarray(y), W[f] * rw[t], tree,
                np.asarray(subsets[t]), depth=depth, bins=9,
                min_instances=10.0, min_info_gain=thr)
            found["dead_but_allowed"] += len(r["dead_but_allowed"])
            found["few_subsets"] += 2 * r["distinct_subsets"] <= r["nodes"]
            found["other"] += len(r["not_allowed"]) \
                + (r["gain_shortfall"] > 1e-4) + (r["leaf_worst"] > 1e-6) \
                + (r["subset_sizes"] != [3])
    stats = [(float(v.mean()), float(v.var())) for v in np.asarray(rw)]
    if wrong == "no_bootstrap":
        with pytest.raises(reference.CheckFailure, match="variance"):
            RF.bootstrap_answer(stats, np.asarray(rw), rate=1.0,
                                rows=len(y), tol_moment=0.1, tol_corr=0.1)
    else:
        RF.bootstrap_answer(stats, np.asarray(rw), rate=1.0, rows=len(y),
                            tol_moment=0.1, tol_corr=0.1)
    assert found["other"] == 0
    assert (found["dead_but_allowed"] > 0) is (wrong == "half_scale")
    assert (found["few_subsets"] > 0) is (wrong == "one_subset_a_tree")


# -- the work model ------------------------------------------------------------------

def test_the_work_model_counts_the_issued_contraction():
    assert opcount_forest.slot_passes(6) == 32 \
        and opcount_forest.slot_passes(1) == 1
    rows, F, B, lanes = 10_000_000, 64, 33, 25
    padded = 10_002_432
    assert padded % 4096 == 0 and padded - rows < 4096
    flops, byts = opcount_forest.forest_group(rows, F, B, lanes, 6)
    assert flops == 2.0 * lanes * 32 * 3 * F * B * padded
    assert byts == 6 * (padded * F + lanes * padded * 12) \
        + 5 * lanes * padded * 4 + lanes * 32 * 3 * F * B * 4
    grids = [{"num_trees": 20, "max_depth": 6, "max_bins": 32},
             {"num_trees": 20, "max_depth": 6, "max_bins": 32},
             {"num_round": 10, "max_depth": 6, "max_bins": 32}]
    fs, bs = opcount_forest.forest_sweep(rows, F, 5, lanes, grids)
    assert (fs, bs) == (8 * flops, 8 * byts)
    # 21 trees at 5 a group: a fifth group, its dead slots counted
    f21, _ = opcount_forest.forest_sweep(
        rows, F, 5, lanes, [dict(grids[0], num_trees=21)])
    assert f21 == 5 * flops
    # a tree-lane at the bf16 peak: 32 slot-passes x 3 rows, ~20.6 ms
    peaks = _load("peaks.json")["devices"]["TPU v5 lite"]
    assert flops / lanes / peaks["bf16_flops"] == pytest.approx(0.0206,
                                                                rel=0.01)
    # what sweep-gbt's share counts: two channels, live rows, one slot
    from benchmark import opcount
    f_gbt, _ = opcount.tree_hist(rows, F, 5, 1, 1, 6, B)
    assert f_gbt == 6 * 2.0 * rows * 5 * 2 * F * B
