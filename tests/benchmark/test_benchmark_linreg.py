"""The regression cell `sweep-linreg-nulls128` and what it brought to the
benchmark: benchmark/datagen_regression.py against datagen_nulls block for
block and the label's moments, benchmark/reference_regression.py piece by
piece against closed forms, ONE rehearsal of the cell on the CPU in which
every named wrong build reads past the bound that refuses it, the refusal
of a program that does not declare the held-out route for a regression
metric, and the manifest's entries by membership."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import datagen_nulls as DN  # noqa: E402
from benchmark import datagen_regression as DR  # noqa: E402
from benchmark import harness, opcount, opcount_gram  # noqa: E402
from benchmark import reference_regression as R  # noqa: E402

CELL = "sweep-linreg-nulls128"
CONFIG = "regression-25m-64-nulls"
LABEL = dict(mu=10.0, sigma=1.7, truth_scale=1.0, noise=0.65)


def _load(*parts):
    with open(os.path.join(REPO, "benchmark", *parts)) as f:
        return json.load(f)


# -- the generator ------------------------------------------------------------------

def test_the_matrix_is_datagen_nulls_block_for_block():
    """Two blocks of 65 536 rows x 8 fields: X and the fills are
    datagen_nulls.device_matrix's for the seed, bit for bit, whatever the
    label; the label is real-valued, seeded, and a block's rows come from
    that block's draws alone."""
    import jax.numpy as jnp
    rows, fields, seed = 2 * DN.BLOCK_ROWS, 8, 4_300_000_019
    block = DN._block_rows(rows)
    X, y, fills = DR.device_matrix(rows, fields, "bfloat16", seed, **LABEL)
    Xn, yn, fills_n = DN.device_matrix(rows, fields, "bfloat16", seed,
                                       truth_scale=2.5, truth_intercept=-1.5)
    assert X.shape == (rows, 2 * fields) and X.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(X.astype(jnp.float32)),
                          np.asarray(Xn.astype(jnp.float32)))
    assert np.array_equal(fills, fills_n)
    y = np.asarray(y)
    assert y.dtype == np.float32 and len(np.unique(y)) > rows // 2
    assert set(np.unique(np.asarray(yn))) == {0.0, 1.0}
    X2, y2, _ = DR.device_matrix(rows, fields, "bfloat16", seed, **LABEL)
    assert np.array_equal(np.asarray(y2), y)
    _, y3, _ = DR.device_matrix(rows, fields, "bfloat16", seed + 1, **LABEL)
    assert not np.array_equal(np.asarray(y3), y)
    # the label is the documented function of the block's own rows
    V = np.asarray(X.astype(jnp.float32), np.float64)
    pop = DN.population(fields)
    z = ((V - pop["mean"]) / pop["std"]) @ DN.truth(fields, 1.0)
    noise = (y - LABEL["mu"]) / LABEL["sigma"] - z
    assert abs(noise.std() - LABEL["noise"]) < 0.03
    assert abs(np.corrcoef(noise[:block], noise[block:])[0, 1]) < 0.05
    assert abs(np.corrcoef(noise, z)[0, 1]) < 0.05


def test_the_labels_moments_are_the_configurations():
    """Population, closed form: the mean is MU, 5.2 deviations from zero,
    SIGMA is not 1, the truth's R2 is 0.674; a sample agrees."""
    config = _load("configs", CONFIG + ".json")
    assert config["label"] == LABEL
    pop = DR.label_moments(64, **LABEL)
    assert pop["mean"] == 10.0 and 4.5 < pop["mean"] / pop["std"] < 5.5
    assert 0.6 < pop["r2_of_truth"] < 0.8
    norm2 = float((DN.truth(64, 1.0) ** 2).sum())
    assert pop["std"] == pytest.approx(1.7 * np.sqrt(norm2 + 0.65 ** 2))
    assert pop["r2_of_truth"] == pytest.approx(0.674, abs=1e-3)
    _, y, _ = DR.device_matrix(16384, 64, "bfloat16", 7, **LABEL)
    y = np.asarray(y, np.float64)
    assert abs(y.mean() - pop["mean"]) < 0.1
    assert abs(y.std() - pop["std"]) < 0.1


# -- the reference, piece by piece ----------------------------------------------------

@pytest.fixture(scope="module")
def small():
    """2 048 rows x 4 fields of the generator as a device matrix, their
    float64 standardised copy, and the moments of a two-thirds fold."""
    import jax.numpy as jnp
    X, y, _ = DR.device_matrix(2048, 4, "bfloat16", 11, **LABEL)
    y = np.asarray(y)
    t = (np.arange(2048) % 3 != 0).astype(np.float32)
    Xh = np.asarray(X.astype(jnp.float32), np.float64)
    xs = (Xh - Xh.mean(0)) / Xh.std(0)
    mean, std = R.column_moments(X)
    return X, y, t, xs, R.fold_moments(X, y, t, mean, 1.0 / std)


def test_fold_moments_against_numpy(small):
    X, y, t, xs, m = small
    t64, y64 = t.astype(np.float64), y.astype(np.float64)
    np.testing.assert_allclose(m["G"], (xs * t64[:, None]).T @ xs,
                               rtol=0, atol=2e-5 * t64.sum())
    np.testing.assert_allclose(m["sx"], t64 @ xs, atol=2e-5 * t64.sum())
    np.testing.assert_allclose(m["c"], (t64 * y64) @ xs,
                               atol=2e-5 * t64.sum())
    assert m["sy"] == pytest.approx(t64 @ y64, rel=1e-6)
    assert m["sw"] == t64.sum()
    # a sample, and operands rounded once to bfloat16
    mean, std = R.column_moments(X)
    part = R.fold_moments(X, y, t, mean, 1.0 / std, rows=1000)
    assert part["sw"] == t64[:1000].sum()
    low = R.fold_moments(X, y, t, mean, 1.0 / std, rounded=True)
    off = np.abs(low["G"] - m["G"]).max() / np.abs(m["G"]).max()
    assert 1e-4 < off < 2e-2


def test_ridge_is_the_normal_equations(small):
    """With a column of ones appended and no penalty on it, the normal
    equations of the weighted ridge give `ridge`'s coefficients and
    intercept; the jitter is what the documented seed adds."""
    _, y, t, xs, m = small
    l2 = 0.07
    Z = np.concatenate([xs, np.ones((len(xs), 1))], axis=1)
    T = t.astype(np.float64)
    A = (Z * T[:, None]).T @ Z / T.sum() + l2 * np.diag([1.0] * 8 + [0.0])
    sol = np.linalg.solve(A, (Z * T[:, None]).T @ y / T.sum())
    B, b0 = R.ridge(m, l2)
    np.testing.assert_allclose(B, sol[:8], atol=2e-6)
    assert b0 == pytest.approx(sol[8], abs=2e-6)
    assert R.kkt_residual(m, B, b0, l2, 0.0) < 5e-6
    Bn, b0n = R.ridge(m, l2, fit_intercept=False)
    assert b0n == 0.0 and np.abs(Bn - B).max() > 1e-3   # the label's mean


def test_soft_threshold_solves_an_orthogonal_design():
    """G = sw I, sx = 0: the elastic-net optimum is soft(c / sw, l1) / (1 +
    l2) coordinate by coordinate; `fista` finds it, `replay` lands on it in
    one step from its ridge seed (there the diagonal IS the Hessian), and
    `kkt_residual` reads zero there and the shortfall elsewhere."""
    sw, d = 100.0, 6
    c = sw * np.array([0.5, -0.3, 0.05, -0.02, 0.2, 0.0])
    m = {"G": sw * np.eye(d), "sx": np.zeros(d), "c": c, "sy": sw * 3.0,
         "sw": sw}
    reg, alpha = 0.2, 0.5                                   # l1 = l2 = 0.1
    want = R.soft(c / sw, 0.1) / 1.1
    assert (want == 0).sum() == 3
    opt = R.fista(m, reg, alpha)
    np.testing.assert_allclose(opt["B"], want, atol=1e-10)
    assert opt["b0"] == pytest.approx(3.0)
    assert R.kkt_residual(m, opt["B"], opt["b0"], reg, alpha) < 1e-10
    doc = R.replay(m, reg, alpha, max_iter=50, tol=1e-9)
    np.testing.assert_allclose(doc["B"], want, atol=2e-6)   # the 1e-6 jitter
    assert doc["iters"] <= 3 and doc["b0"] == pytest.approx(3.0)
    np.testing.assert_allclose(doc["seed"][0], c / sw / (1.1 + 1e-6))
    # by hand: a non-zero coefficient off by 0.01 of gradient, a zero one
    # whose gradient is 0.03 over l1, an intercept 0.2 off
    off = want.copy()
    off[0] += 0.01 / 1.1
    assert R.kkt_residual(m, off, 3.0, reg, alpha) == pytest.approx(0.01)
    m2 = dict(m, c=c + sw * np.array([0, 0, 0, 0, 0, 0.13]))
    assert R.kkt_residual(m2, want, 3.0, reg, alpha) == pytest.approx(0.03)
    assert R.kkt_residual(m, want, 3.2, reg, alpha) == pytest.approx(0.2)


def test_replay_stops_where_documented_and_fista_at_the_optimum(small):
    _, _, _, _, m = small
    doc = R.replay(m, 0.6, 0.5, max_iter=50, tol=1e-6)
    assert doc["deltas"][-1] <= 1e-6 < doc["deltas"][-2]
    assert doc["iters"] == len(doc["deltas"]) < 50
    opt = R.fista(m, 0.6, 0.5)
    assert R.kkt_residual(m, opt["B"], opt["b0"], 0.6, 0.5) < 1e-9
    assert (opt["B"] == 0).any() and (opt["B"] != 0).any()
    # the documented fixed point lies near the optimum, not on it (the
    # threshold takes the Hessian's diagonal), and on it where l1 is 0
    assert 1e-7 < np.abs(doc["B"] - opt["B"]).max() < 0.05
    one = R.replay(m, 0.6, 0.5, max_iter=1, tol=1e-6)
    assert one["iters"] == 1
    assert np.abs(one["B"] - doc["B"]).max() > 10 * doc["deltas"][-1]
    r0, ridge = R.replay(m, 0.05, 0.0, max_iter=50, tol=1e-6), \
        R.ridge(m, 0.05)
    assert np.abs(r0["B"] - ridge[0]).max() < 1e-5
    # columns left out, columns rescaled
    keep = np.arange(8) % 2 == 0
    assert R.restrict(m, keep)["G"].shape == (4, 4)
    s = np.arange(1.0, 9.0)
    np.testing.assert_allclose(R.rescale(m, s)["G"][2, 5],
                               m["G"][2, 5] * 3.0 * 6.0)


def test_residual_sums_are_the_exact_metrics(small):
    import jax.numpy as jnp
    X, y, t, _, _ = small
    Xh = np.asarray(X.astype(jnp.float32), np.float64)
    rng = np.random.default_rng(0)
    beta, b0 = rng.normal(size=(3, 8)) * 0.2, np.array([9.0, 10.0, 11.0])
    v = 1.0 - t
    mets = R.metrics_from_sums(R.residual_sums(X, y, v, beta, b0, pivot=9.5))
    y64 = y.astype(np.float64)
    for k in range(3):
        r = Xh @ beta[k] + b0[k] - y64
        mse = (v * r * r).sum() / v.sum()
        assert mets["mse"][k] == pytest.approx(mse, rel=1e-6)
        assert mets["rmse"][k] == pytest.approx(np.sqrt(mse), rel=1e-6)
        assert mets["mae"][k] == pytest.approx(
            (v * np.abs(r)).sum() / v.sum(), rel=1e-6)
        ybar = (v * y64).sum() / v.sum()
        assert mets["r2"][k] == pytest.approx(
            1 - (v * r * r).sum() / (v * (y64 - ybar) ** 2).sum(), abs=1e-6)


def test_the_twin_is_the_programs_gram_pass():
    """`glm_sweep.sweep_gram_moments` (640 rows x 128 columns with
    deviations from 0.03 to 16, 3 folds, seeded row weights) against the
    float64 twin written from its docstring: to float32 summation; the
    twin with operands rounded once to bfloat16 is 1e-3 off."""
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import glm_sweep as GS
    rng = np.random.default_rng(5)
    n, d, F = 640, 128, 3
    scale = 2.0 ** ((np.arange(d) * 3) % 10 - 5)
    X = jnp.asarray((rng.normal(size=(n, d)) * scale + 0.3 * scale)
                    .astype(np.float32)).astype(jnp.bfloat16)
    Xh = np.asarray(X.astype(jnp.float32))
    y = (10.0 + rng.normal(size=n)).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    fold = rng.integers(0, F, size=n)
    masks = (fold[None, :] != np.arange(F)[:, None]).astype(np.float32)
    mean, std = Xh.mean(0), Xh.std(0)
    assert std.min() < 0.04 and std.max() > 15
    got = GS.sweep_gram_moments(X, jnp.asarray(y), jnp.asarray(w),
                                jnp.asarray(masks), jnp.asarray(mean),
                                jnp.asarray(std))
    ref = R.moments_twin(Xh, y, w, masks, mean, std)
    low = R.moments_twin(Xh, y, w, masks, mean, std, rounded=True)
    for a, r in zip(got, ref):
        assert a.shape == r.shape
        assert np.abs(np.asarray(a) - r).max() <= 2e-6 * np.abs(r).max()
    assert np.abs(low[0] - ref[0]).max() > 1e-4 * np.abs(ref[0]).max()
    # the twin itself, one fold by hand
    xs = ((Xh - mean) / std).astype(np.float64)
    wf = masks[1] * w.astype(np.float64)
    np.testing.assert_allclose(ref[0][1], (xs * wf[:, None]).T @ xs,
                               rtol=1e-12, atol=1e-9)
    np.testing.assert_allclose(ref[1][1], (wf * y) @ xs, rtol=1e-12,
                               atol=1e-9)
    assert ref[4][1] == pytest.approx(wf.sum())


def test_misordered_pairs_by_hand():
    order = {"mean_rmse_exact": [1.0, 1.1, 1.10001, 1.3],
             "mean_rmse_sweep": [1.0, 1.2, 1.1, 1.15]}
    # (1, 3) is turned over; (1, 2) too, but lies closer than 1e-3
    assert R.misordered(order, 1e-3) == [(1, 3)]
    assert R.misordered(order, 1e-6) == [(1, 2), (1, 3)]


# -- the cell, rehearsed ONCE -----------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    out = tmp_path_factory.mktemp("linreg")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)   # conftest's 8 virtual devices
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "4300000007", "--seconds", "2",
         "--trace", "1", "--rehearse", "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=400)
    assert r.returncode == 0, r.stderr[-3000:]
    report, line = map(json.loads, r.stdout.strip().splitlines())
    return report, line


def test_rehearsal_prints_the_cells_metrics(rehearsal):
    report, line = rehearsal
    assert line["correct"] is True, report["problems"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    notes = report["notes"]
    assert notes["routes"]["cells"] == [["OpLinearRegression", "streamed"]]
    assert notes["metric_body_declared"] == "sums"
    prog = notes["program"]
    tele = prog["telemetry"]
    expect = _load("workloads", CELL + ".json")["expect"]["telemetry"]
    assert {k: tele[k] for k in expect} == expect
    assert 0 < tele["gram_solve_iters"] < 50
    assert tele["lanes_retired"] == tele["lanes_total"] == 24
    assert [s["body"] for s in prog["gram_pass_spans"]] == ["xla_blocks"]
    assert prog["gram_solve_spans"][0]["iters"] == tele["gram_solve_iters"]
    assert prog["gram_temp_bytes"] >= 0
    # the program's counters and spans, under the cell's names
    listed = {f[:-5] for f in os.listdir(os.path.join(REPO, "benchmark",
                                                      "layers"))
              if CELL in _load("layers", f).get("cells", [])}
    assert {"lin_x_passes", "lin_gram_temp_bytes", "lin_fit_host_s",
            "lin_eval_host_s", "lin_host_fetches", "programs_compiled",
            "window_compiles"} <= set(line["metrics"])
    assert set(line["metrics"]) <= listed | {"programs_compiled",
                                             "window_compiles"}
    assert line["metrics"]["lin_x_passes"]["value"] == 4
    assert line["metrics"]["lin_host_fetches"]["value"] == 2
    assert line["metrics"]["window_compiles"]["value"] == 0


def test_rehearsal_refuses_every_named_wrong_build(rehearsal):
    """Each named wrong build, read by the reference beside the sweep in
    the same run, lies past a bound of the cell file that the sweep's own
    reading is inside (the rehearsal's bounds here, the chip's pinned ones
    there)."""
    notes = rehearsal[0]["notes"]
    cell = _load("workloads", CELL + ".json")
    tol = {k: dict(c, **c.get("rehearsal", {}))
           for k, c in cell["checks"].items()}
    ans, c = notes["linreg_answer"], tol["linreg_answer"]
    assert ans["metric_worst_delta"] <= c["tol_metric"]
    assert ans["replay_delta_worst"] <= c["tol_replay"]
    assert ans["kkt_worst"] <= c["tol_kkt"]
    assert ans["coefficients_worst"] <= c["tol_coefficients"]
    assert ans["mse_delta_worst"] <= c["tol_mse"]
    assert ans["order"]["misordered"] == []
    assert set(ans["points"]) == {"best", "least_regularised", "largest_l1"}
    assert ans["points"]["largest_l1"]["grid"] == {
        "reg_param": 0.2, "elastic_net_param": 0.5}
    # coefficients rounded to bfloat16 in the scoring
    assert ans["metric"]["bf16_coefficients_delta_least"] > c["tol_metric"]
    # the fit's wrong builds: refused where ANY point reads past the bound
    wrong = ans["wrong_worst"]
    assert set(wrong) == {
        "once_rounded_operands", "std_not_applied", "indicators_left_out",
        "intercept_dropped", "ridge_for_elastic_net", "one_prox_iteration"}
    for name, reads in wrong.items():
        # (on the chip a one-iteration solve is the intercept's to refuse:
        # the coefficients settle in one step there)
        assert reads["replay_delta"] > c["tol_replay"] \
            or reads["intercept_delta"] > c["tol_intercept"], name
    assert wrong["one_prox_iteration"]["intercept_delta"] \
        > c["tol_intercept"] >= ans["intercept_delta_worst"]
    # the solve's length is held by a count: the program's iterations may
    # not be under the longest float64 replay's (a one-iteration solve: 1)
    iters = notes["program"]["telemetry"]["gram_solve_iters"]
    assert iters >= ans["replay_iters_max"] > 1
    for point in ans["points"].values():
        assert point["replay_deltas"][-1] <= 1e-6
        assert point["optimum_kkt"] < 1e-8
    # operands rounded once, sums accumulated in bfloat16
    twin, c = notes["moments_twin"], tol["moments_twin"]
    assert twin["worst"] <= c["tol"] < twin["once_rounded_operands"]
    assert c["tol"] < twin["bf16_accumulation"]


def test_a_program_without_the_regression_held_out_route_is_refused(
        monkeypatch):
    """Asked BEFORE any data is made: a program whose validators name no
    body for a regression metric on the held-out pass (the parent: no such
    function at all) is refused there."""
    from transmogrifai_tpu.automl.tuning import validators as V
    driver = harness.load_module("drivers", "sweep_linreg")
    cell = _load("workloads", CELL + ".json")
    config = _load("configs", CONFIG + ".json")

    def forbidden(*a, **kw):
        raise AssertionError("data was made")
    monkeypatch.setattr(DR, "device_matrix", forbidden)

    def ctx():
        return harness.Ctx(
            cell=cell, config=config, sizes=dict(config["sizes"]), seed=1,
            seconds=1.0, trace=False, rehearse=False, out_dir="/nonexistent",
            compile_log=None)
    monkeypatch.delattr(V, "heldout_metric_body")
    c = ctx()
    with pytest.raises(harness.BenchFailure, match="heldout_metric_body"):
        driver.setup(c)
    assert c.notes["metric_body_declared"] is None
    monkeypatch.setattr(V, "heldout_metric_body",
                        lambda m, p, b: None, raising=False)
    with pytest.raises(harness.BenchFailure, match="once a fold"):
        driver.setup(ctx())


# -- the files ------------------------------------------------------------------------

def test_manifest_lists_the_cell_under_glm_sweep_s():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = _load("workloads", CELL + ".json")
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    assert entry["traffic"] == cell["traffic"] == "linreg-closed-1"
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == [] and len(config["source"]) <= 200
    assert config["source"] == _load("configs", CONFIG + ".json")["source"]
    e2e = next(m for m in manifest["end_to_end"]
               if m["name"] == "glm_sweep_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.02
    mine = {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine == {
        "lin_gram_device_s", "lin_gram_roofline", "lin_solve_device_s",
        "lin_standardize_device_s", "lin_metric_device_s",
        "lin_fold_assign_device_s", "lin_x_passes", "lin_gram_temp_bytes",
        "lin_fit_host_s", "lin_eval_host_s", "lin_host_fetches",
        "lin_host_gap_s"}       # membership, not position (PERF.md §7 (h))
    for m in manifest["per_layer"]:
        if m["name"] in mine:
            assert m["moves"] == "glm_sweep_s" and m["workloads"] == [CELL]
            layer = _load("layers", m["name"] + ".json")
            assert layer["cells"] == [CELL] and layer["layer"] == m["layer"]


def test_the_configuration_is_upstreams_defaults():
    config = _load("configs", CONFIG + ".json")
    cell = _load("workloads", CELL + ".json")
    assert config["reduced"] == {}
    assert "RegressionModelSelector" in config["source"]
    sz, nulls = config["sizes"], _load("configs", "binary-25m-64-nulls.json")
    assert sz == nulls["sizes"]             # one table, two labels
    assert config["pool"]["lr"]["estimator"].endswith(":OpLinearRegression")
    assert config["pool"]["lr"]["params"] == {
        "max_iter": 50, "tol": 1e-6, "standardization": True,
        "fit_intercept": True}
    grid = cell["families"]["lr"]["grid"]
    assert grid == {"reg_param": [0.001, 0.01, 0.1, 0.2],
                    "elastic_net_param": [0.1, 0.5]}    # the grid WHOLE
    assert config["glm_grid"] == 8 and config["source_sizes"]["folds"] == 3
    for key in ("rows", "folds", "fields", "label", "mu", "sigma",
                "truth_scale", "solver", "pool"):
        assert key in config["assumed"]
    for check in cell["checks"].values():
        assert "pinned_from" in check
    assert cell["expect"]["telemetry"]["eval_route"] == "heldout_once"
    assert cell["expect"]["gram_temp_share"] == 0.05


def test_the_roofline_counts_every_rows_outer_product_once():
    """opcount_gram.gram_pass at the cell's sizes: 2 x rows x 129^2
    operations whatever the folds, one read of X, y, w and the masks; on a
    v5e the HBM roof binds it; a product a (row, fold) at six passes is 30
    times the counted operations."""
    flops, byts = opcount_gram.gram_pass(25_000_000, 128, 5, 2)
    assert flops == 2 * 25e6 * 129 ** 2
    assert byts == 25e6 * (128 * 2 + 4 * 7)
    assert opcount_gram.gram_pass(25_000_000, 128, 10, 2)[0] == flops
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    least, roof = opcount.least_seconds(flops, byts, peaks)
    assert roof == "bytes" and 0.008 < least < 0.010
    layer = _load("layers", "lin_gram_roofline.json")
    assert layer["reader"] == "roofline_of"
    assert layer["args"]["opcount"] == "opcount_gram"
    assert layer["args"]["work"] == "gram_pass"


def test_the_regression_reference_imports_nothing_of_the_program():
    for name in ("reference_regression.py", "datagen_regression.py",
                 "opcount_gram.py"):
        with open(os.path.join(REPO, "benchmark", name)) as f:
            src = f.read()
        assert "transmogrifai_tpu" not in src.split('"""', 2)[2], name
