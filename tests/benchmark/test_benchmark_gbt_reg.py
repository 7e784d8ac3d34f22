"""The regression booster cell `sweep-gbt-regression` and what it brought to
the benchmark: the cell rehearsed on the CPU with its metrics printed and
its spans found, the refusal of a program that does not say how a round's
residual is carried, the manifest by membership, the configuration against
upstream's DefaultSelectorParams, benchmark/reference_gbt_reg.py against
numpy by hand and against fits the program grew — and the named wrong
builds (one bfloat16 part, the gain summed over the node, step_size applied
twice or not at all, another reg_lambda) each refused by a check."""
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

from benchmark import datagen_forest_reg, harness, opcount  # noqa: E402
from benchmark import opcount_gbt_reg  # noqa: E402
from benchmark import reference  # noqa: E402
from benchmark import reference_gbt_reg as RG  # noqa: E402
from benchmark.reduce_trace import Reduced  # noqa: E402

CELL = "sweep-gbt-regression"
CONFIG = "regression-10m-64-gbt"
LAYERS = {
    "gbr_tree_device_s", "gbr_hist_kernel_s", "gbr_hist_kernel_roofline",
    "gbr_residual_device_s", "gbr_metric_device_s", "gbr_host_gap_s",
    "gbr_fit_host_s", "gbr_payload_rows", "gbr_tree_rounds"}


def _load(*parts):
    with open(os.path.join(REPO, "benchmark", *parts)) as f:
        return json.load(f)


def _layer_specs():
    return {f[:-5]: _load("layers", f)
            for f in os.listdir(os.path.join(REPO, "benchmark", "layers"))
            if CELL in _load("layers", f).get("cells", [])}


# -- the cell, rehearsed -------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """ONE traced rehearsal (a run's set-up, window and checks are the same
    traced or not; the untraced line's two metrics ride in its notes). The
    traced loop ends after its two jobs: the 5 s are room for a loaded
    machine, not time spent."""
    out = tmp_path_factory.mktemp("gbr")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)   # conftest's 8 virtual devices
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "5100000007", "--seconds", "5",
         "--trace", "1", "--rehearse", "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    report, line = map(json.loads, r.stdout.strip().splitlines())
    return report, line


def test_rehearsal_is_correct_and_says_what_ran(rehearsal):
    report, line = rehearsal
    assert line["correct"] is True, report["problems"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    notes = report["notes"]
    assert notes["payload_body_declared"] == "residual_parts"
    assert notes["declined"] == []
    assert notes["routes"]["cells"] == [["OpGBTRegressor", "mask_folds"]]
    assert notes["routes"]["kernel_spans"] == ["tree_sweep_fold_fused"]
    # 2 points x 3 rounds, 3 fold lanes a program
    assert notes["booster"] == {
        "model": "OpGBTRegressor", "route": "fold_fused", "programs": 2,
        "rounds": 6, "scale_reductions": 6, "lanes": 3,
        "payload_body": "residual_parts", "payload_rows": 5}
    prog = notes["program"]
    assert prog["fits"] == 2 and all(
        s["payload"] == "residual_parts" and s["normalize_gain"] is True
        and s["loss"] == "squared" and s["reg_lambda"] is None
        for s in prog["calls_said"])
    assert all(s["payload_rows"] == 5 and s["rounds"] == 3
               for s in prog["tree_levels_spans"])
    assert prog["fold_metrics_spans"][0]["metric_body"] == "vmapped"
    assert set(notes["traced_end_to_end"]) == {"gbt_sweep_s", "setup_s"}


def test_rehearsal_holds_the_sweep_and_reads_the_wrong_builds(rehearsal):
    answer = rehearsal[0]["notes"]["gbt_answer"]
    assert answer["bins_identical"] is True
    assert answer["points_grow_different_trees"] is True
    # the first tree and the last round's tree of each of the two points
    assert [(r["point"], r["round"]) for r in answer["replay"]] == [
        (0, 0), (0, 2), (1, 0), (1, 2)]
    assert any(answer["threshold_binds_in_replayed_trees"][2:])
    for r in answer["replay"]:
        assert not r["not_allowed"] and not r["dead_but_allowed"]
        assert r["gain_shortfall"] < 1e-6 and r["leaf_worst"] < 5e-7
        # beside it, the named wrong builds on the same nodes and leaves
        assert r["leaf_worst_if_one_part"] > 3e-6
        assert r["leaf_worst_if_step_twice"] > 1e-2 < \
            r["leaf_worst_if_no_step"]
        assert r["leaf_worst_if_lambda_0"] > 1e-5
        assert r["leaf_worst_if_bf16"] > 1e-5
        assert r["scale"] == RG.power_of_two_over(r["residual_largest"])
    # the gain summed over the node would have split what 0.2 a row stops
    assert sum(r["summed_rule_would_split"] for r in answer["replay"]
               if r["point"] == 1) > 0
    assert answer["every_fold_metric_delta"] < 3e-6
    for p in answer["points"]:
        assert p["margin_worst"] < 1e-4
        assert abs(p["sweep_fold_rmse"][0] - p["plain_program_rmse"]) < 0.3
        # Spark's own boosting rule on the same rows: the departure's size
        assert p["plain_spark_rmse"] < p["plain_program_rmse"]


def test_rehearsal_replays_the_kernels_under_a_rounds_residual(rehearsal):
    notes = rehearsal[0]["notes"]
    twins = notes["residual_twins"]
    assert {(t["kernel"], t["residual"]) for t in twins} == {
        (k, r) for k in ("hist_folds", "route_hist")
        for r in ("round_1", "after_the_last_round")}
    for t in twins:
        assert t["payload_parts"] == 3 and t["h_and_counts_exact"]
        assert t["g_worst_share"] < 1e-6 < 1e-5 \
            < t["g_worst_share_if_two_parts"]
        assert t["g_worst_share_if_one_part"] > 1e-4
        assert all(np.log2(s) == round(np.log2(s)) for s in t["scales"])
    assert {t["kernel"] for t in notes["kernel_twins"]} == {
        "route", "table_lookup"}


def test_rehearsal_prints_the_cells_metrics(rehearsal):
    report, line = rehearsal
    counters, metrics = report["counters"], line["metrics"]
    assert counters["gbr_payload_rows"] == 5 \
        and counters["gbr_tree_rounds"] == 6
    specs = _layer_specs()
    assert set(specs) == LAYERS
    # the kernels, the roofline and what runs beside them need the chip (no
    # Mosaic custom call, no peaks here); every other metric is printed
    assert set(metrics) >= (LAYERS - {
        "gbr_hist_kernel_s", "gbr_hist_kernel_roofline",
        "gbr_residual_device_s"}) | {"programs_compiled", "window_compiles"}
    for name, m in metrics.items():
        if name in specs:
            assert m["unit"] == specs[name]["unit"], name
            assert m["value"] >= 0, name
    assert metrics["window_compiles"]["value"] == 0
    assert metrics["gbr_payload_rows"]["value"] == 5
    assert metrics["gbr_tree_rounds"]["value"] == 6
    assert metrics["gbr_tree_device_s"]["value"] \
        > metrics["gbr_metric_device_s"]["value"] > 0
    # the spans, under the validate root and on its thread
    ctx = types.SimpleNamespace(
        reduced=Reduced.from_file(report["notes"]["xplane"]),
        cell={"job_span": "bench.validate"})

    def count(name):
        return harness.load_module("readers", "host_span").read(
            ctx, {"name": name, "stat": "count"})
    assert count(r"^tmog\.validate:CrossValidation$") == 1
    assert count(r"^tmog\.validate_phase:tree_fit$") == 2
    assert count(r"^tmog\.tree_fused:tree_levels$") == 2
    assert count(r"^tmog\.validate_phase:fold_metrics$") == 2


def test_a_program_without_the_word_is_refused_before_any_data(
        monkeypatch, tmp_path):
    """What the parent of this cell's PR does: models/trees has no
    payload_body, and the driver fails with BenchFailure before it makes a
    byte of data; so does a program whose word is another."""
    from transmogrifai_tpu.models import trees as MT
    driver = harness.load_module("drivers", "sweep_gbt_reg")

    def no_data(*a, **k):
        raise AssertionError("data was made")
    monkeypatch.setattr(datagen_forest_reg, "device_matrix", no_data)
    cell, config = _load("workloads", CELL + ".json"), \
        _load("configs", CONFIG + ".json")
    ctx = harness.Ctx(cell=cell, config=config, sizes=dict(config["sizes"]),
                      seed=1, seconds=1.0, trace=False, rehearse=False,
                      out_dir=str(tmp_path), compile_log=None)
    monkeypatch.setattr(MT, "payload_body", lambda est: "gradient")
    with pytest.raises(harness.BenchFailure,
                       match="rounded ONCE to bfloat16"):
        driver.setup(ctx)
    monkeypatch.delattr(MT, "payload_body")
    with pytest.raises(harness.BenchFailure,
                       match="names None, not 'residual_parts'"):
        driver.setup(ctx)


# -- the manifest and the configuration ---------------------------------------------------

def test_manifest_lists_the_cell_under_gbt_sweep_s():
    """Membership and order, not position from the end: a later PR appends
    after these entries."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["gbt_sweep_s"]["workloads"]
    assert e2e["gbt_sweep_s"]["workloads"].index(CELL) \
        > e2e["gbt_sweep_s"]["workloads"].index("sweep-rf-regression")
    assert CELL not in e2e["glm_sweep_s"]["workloads"]
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > cells.index("sweep-rf-regression")
    entry = manifest["workloads"][cells.index(CELL)]
    assert entry["config"] == CONFIG and entry["chips"] == 1
    assert entry["traffic"] == "gbr-closed-1" and len(entry["why"]) <= 200
    assert entry["why"] == _load("workloads", CELL + ".json")["why"]
    conf = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == ["gbt_grid"] \
        and conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(conf["why"]) <= 200 and len(conf["source"]) <= 200
    assert conf["source"] == _load("configs", CONFIG + ".json")["source"]
    sources = [c["source"] for c in manifest["configs"]]
    assert sources.count(conf["source"]) == 1
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    specs = _layer_specs()
    assert sorted(m["name"] for m in mine) == sorted(specs) == sorted(LAYERS)
    for m in mine:
        spec = specs[m["name"]]
        assert m["moves"] == spec["moves"] == "gbt_sweep_s"
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            spec["unit"], spec["better"], spec["source"], spec["layer"])
    layers = {m["layer"] for m in manifest["per_layer"]
              if m.get("workloads") != [CELL]}
    assert {m["layer"] for m in mine} <= layers     # no new layer name


def test_the_roofline_counts_sweep_gbts_work_under_the_other_key():
    """opcount_gbt_reg reads a point's rounds under `max_iter` and calls
    opcount.tree_hist: the same count opcount.tree_sweep makes of the same
    point under `num_round`, none of its arithmetic copied."""
    roof = _layer_specs()["gbr_hist_kernel_roofline"]
    assert roof["args"]["opcount"] == "opcount_gbt_reg" \
        and roof["args"]["work"] == "booster_sweep" \
        and roof["reader"] == "roofline_of" and roof["unit"] == "%"
    grid = {"max_iter": 10, "max_depth": 6, "max_bins": 32}
    mine = opcount_gbt_reg.booster_sweep(10_000_000, 64, 5, [grid] * 2)
    theirs = opcount.tree_sweep(10_000_000, 64, 5,
                                [dict(grid, num_round=10)] * 2)
    assert mine == theirs and mine[0] > 0
    assert opcount_gbt_reg.booster_sweep(1000, 8, 3, [{"num_trees": 5}]) \
        == (0.0, 0.0)


def test_the_configuration_is_upstreams_defaults_cut_as_it_says():
    from transmogrifai_tpu.automl import selectors as S
    from transmogrifai_tpu.models import trees as MT
    from transmogrifai_tpu.ops import trees as T
    config, cell = _load("configs", CONFIG + ".json"), \
        _load("workloads", CELL + ".json")
    D = S.DefaultSelectorParams
    assert "OpGBTRegressor" in S.RegressionModelSelector.default_model_types
    src = config["source_sizes"]
    assert src["max_depth"] == D.MAX_DEPTH and [src["max_bins"]] == D.MAX_BIN
    assert src["min_instances_per_node"] == D.MIN_INSTANCES_PER_NODE
    assert src["min_info_gain"] == D.MIN_INFO_GAIN
    assert src["gbt_grid"] == len(D.MAX_DEPTH) * len(D.MIN_INFO_GAIN) \
        * len(D.MIN_INSTANCES_PER_NODE) == 18
    defaults = MT.OpGBTRegressor()
    assert src["max_iter"] == defaults.get_param("max_iter") == 20
    assert src["step_size"] == defaults.get_param("step_size") == 0.1
    fixed = config["pool"]["gbt"]["fixed_grid"]
    assert fixed["max_depth"] in D.MAX_DEPTH \
        and fixed["min_instances_per_node"] in D.MIN_INSTANCES_PER_NODE \
        and fixed["max_bins"] == 32 and fixed["step_size"] == 0.1 \
        and fixed["subsampling_rate"] == 1.0
    assert fixed["max_iter"] == src["max_iter"]      # upstream's, uncut
    grid = cell["families"]["gbt"]["grid"]
    assert list(grid) == ["min_info_gain"] and len(
        grid["min_info_gain"]) == 2 == config["gbt_grid"]
    assert set(grid["min_info_gain"]) < set(D.MIN_INFO_GAIN)
    assert set(config["reduced"]) == {"gbt_grid"}
    # sweep-rf-regression's matrix, label and folds: the control
    control = _load("configs", "regression-10m-64-rf.json")
    assert config["sizes"] == control["sizes"] \
        and config["label"] == control["label"]
    est = MT.OpGBTRegressor(**fixed)
    want = cell["expect"]["booster"]
    assert want["payload_body"] == MT.payload_body(est) \
        and want["payload_rows"] == T.payload_rows(want["payload_body"]) == 5
    points = len(grid["min_info_gain"])
    assert (want["programs"], want["rounds"], want["scale_reductions"],
            want["lanes"]) == (points, points * fixed["max_iter"],
                               points * fixed["max_iter"],
                               config["sizes"]["folds"])
    assert cell["expect"]["kernel_spans"] == {
        "tree_sweep_fold_fused": points}
    assert cell["chips"] == 1 and cell["min_jobs"] == 3
    # the rule the checks hold is the one the estimator hands its fits
    assert est._gbt_kw()["normalize_gain"] is True
    assert cell["checks"]["gbt_answer"]["reg_lambda"] == 1.0
    for word in ("a weighted row", "reg_lambda 1", "weighted mean"):
        assert word in config["guarantees"], word
    for key in ("first_tree", "pseudo_residual", "leaves", "pool", "rows",
                "folds", "label"):
        assert key in config["assumed"], key
    # every tolerance says where it was pinned
    for block in cell["checks"].values():
        assert "my chip runs, PR 51" in block["pinned_from"]


# -- the plain reference ----------------------------------------------------------

def test_the_split_rule_against_numpy_by_hand():
    """One node, one feature, three bins: G = (3, -1, 2), H = C = (4, 2, 4),
    lambda 1: the candidate that leaves bin 0 alone scores 9 / 5 + 1 / 7 -
    16 / 11, a weighted row of 10."""
    G = np.asarray([[[3.0, -1.0, 2.0]]])
    H = C = np.asarray([[[4.0, 2.0, 4.0]]])
    gain, cl, cr = RG.newton_gains(G, H, C, 1.0)
    want = 9 / 5 + 1 / 7 - 16 / 11
    assert gain[0, 0, 0] == pytest.approx(want / 10)
    assert (cl[0, 0, 0], cr[0, 0, 0]) == (4.0, 6.0)
    summed, _, _ = RG.newton_gains(G, H, C, 1.0, RG.RULE_SUMMED)
    assert summed[0, 0, 0] == pytest.approx(want)
    assert gain[0, 0, 2] == pytest.approx(0.0)     # nothing goes right
    # Spark's leaves: lambda 0, and a side of no weight scores nothing
    bare, _, _ = RG.newton_gains(G, H * 0 + [[[4.0, 0.0, 4.0]]], C, 0.0)
    assert np.isfinite(bare).all()
    assert RG.power_of_two_over(10.8) == 16.0 \
        and RG.power_of_two_over(8.0) == 16.0 \
        and RG.power_of_two_over(0.03) == 0.03125
    x = np.float32(0.3141592)
    assert abs(RG.two_parts(x) - x) <= 2.0 ** -16
    assert RG.two_parts(np.float32(0.5)) == 0.5


@pytest.fixture(scope="module")
def grown():
    """A small table and what the PROGRAM grows on it through the kernels'
    jnp twins, as gbt_reg_answer takes it: two thresholds, three fold
    lanes, the sweep's metric the exact RMSE of its own margins."""
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import trees as T
    n, f, bins, folds = 2048, 6, 8, 3
    rng = np.random.default_rng(11)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (10 + 1.7 * (X @ rng.normal(size=f) / np.sqrt(f)
                     + 0.65 * rng.normal(size=n))).astype(np.float32)
    Xd = jnp.asarray(X)
    edges = T.quantile_edges(Xd, bins)
    Xb = T.bin_matrix(Xd, edges)
    fold = rng.integers(0, folds, n)
    masks = (fold[None, :] != np.arange(folds)[:, None]).astype(np.float32)

    def points(thresholds=(0.001, 0.9), leaf_times=1.0, **over):
        kw = dict(n_rounds=2, depth=3, learning_rate=0.1, min_instances=10.0,
                  normalize_gain=True, payload="residual_parts")
        kw.update(over)
        out, validated = [], []
        for thr in thresholds:
            trees, base, margins = T.fit_gbt_folds(
                Xb, jnp.asarray(y), jnp.asarray(masks),
                jax.random.PRNGKey(42), n_bins=bins, loss="squared",
                min_info_gain=thr, **kw)
            tr = {k: np.asarray(getattr(trees, k))
                  for k in ("feat", "thresh", "miss", "leaf")}
            tr["leaf"] = tr["leaf"][..., 0] * np.float32(leaf_times)
            out.append({"Xb": Xb, "edges": np.asarray(edges, np.float32),
                        "margins_fold": margins[0],
                        "base": np.asarray(base, np.float64),
                        "min_instances": 10.0, "min_info_gain": thr,
                        "trees": tr})
            m = np.asarray(margins, np.float64)
            validated.append(types.SimpleNamespace(
                grid={"min_info_gain": thr}, fold_metrics=[
                    RG.rmse(m[k][masks[k] == 0], y[masks[k] == 0])
                    for k in range(folds)]))
        means = [np.mean(v.fold_metrics) for v in validated]
        best = types.SimpleNamespace(
            validated=validated,
            best_grid=validated[int(np.argmin(means))].grid)
        return best, out

    def answer(best, pts, **over):
        kw = dict(fold=0, rounds=2, depth=3, bins=bins, step=0.1, lam=1.0,
                  train_rows=1000, tol_gain=1e-5, tol_leaf=1e-6,
                  tol_margin=1e-4, tol_metric=3e-6, tol_plain=0.5)
        kw.update(over)
        into = {}
        RG.gbt_reg_answer(best, pts, masks, Xd, jnp.asarray(y), into=into,
                          **kw)
        return into
    return points, answer


def test_the_reference_passes_what_the_program_grows(grown):
    points, answer = grown
    into = answer(*points())
    assert into["points_grow_different_trees"] is True
    assert any(into["threshold_binds_in_replayed_trees"])
    assert into["every_fold_metric_delta"] < 1e-6
    assert all(r["leaf_worst"] < 1e-6 for r in into["replay"])


@pytest.mark.parametrize("build,refused", [
    ("gain_summed_over_the_node", "grew the SAME trees"),
    ("step_size_twice", r"a leaf is .* off step x G"),
    ("step_size_not_at_all", r"a leaf is .* off step x G"),
    ("reg_lambda_0", r"a leaf is .* off step x G"),
    ("one_bfloat16_part", r"a leaf is .* off step x G"),
])
def test_each_named_wrong_build_is_refused(grown, build, refused):
    """Builds of the PROGRAM, not of the reference: the booster grown under
    the other rule or through the interpreted kernels in one part, or its
    leaves as a build that misapplies step_size would have left them."""
    points, answer = grown
    over = {
        "gain_summed_over_the_node": dict(normalize_gain=False),
        "step_size_twice": dict(leaf_times=0.1),
        "step_size_not_at_all": dict(leaf_times=10.0),
        "reg_lambda_0": dict(reg_lambda=0.0),
        "one_bfloat16_part": dict(payload="gradient", interpret=True),
    }[build]
    with pytest.raises(reference.CheckFailure, match=refused):
        answer(*points(**over))
