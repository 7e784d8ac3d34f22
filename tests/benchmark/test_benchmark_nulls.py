"""The null-tracked cell `sweep-glm-nulls128` and what it brought to the
benchmark: benchmark/datagen_nulls.py against the repo's own
NumericVectorizer on the regenerated raw table, benchmark/reference_nulls.py
piece by piece against closed forms and against the kernel it twins, ONE
rehearsal of the cell on the CPU in which every named wrong build reads
past the bound that refuses it, the refusal of a program whose rounds are
not the fused body, and the manifest's entries."""
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
from benchmark import harness, opcount  # noqa: E402
from benchmark import reference_nulls as RN  # noqa: E402

CELL = "sweep-glm-nulls128"
CONFIG = "binary-25m-64-nulls"


def _load(*parts):
    with open(os.path.join(REPO, "benchmark", *parts)) as f:
        return json.load(f)


# -- the generator and the program's vectoriser -------------------------------------

def test_the_generator_is_what_the_repos_vectoriser_makes_of_the_raw_table():
    """4 096 x 8 fields: the device matrix equals, bit for bit after the
    bfloat16 cast, NumericVectorizer().fit_columns(raw).transform_block(raw)
    with the device's fills, which are the fitted model's (float64 nanmean)
    to float32 rounding; the layout is value, indicator, value ...; a second
    call with the seed repeats, another seed does not; any row range of the
    raw table can be made again."""
    import jax.numpy as jnp
    from transmogrifai_tpu.automl.vectorizers.numeric import (
        NumericVectorizer, NumericVectorizerModel)
    from transmogrifai_tpu.data.dataset import Column
    rows, fields, seed = 4096, 8, 3_700_000_019
    kw = dict(truth_scale=2.5, truth_intercept=-1.5)
    X, y, fills = DN.device_matrix(rows, fields, "bfloat16", seed, **kw)
    raw = DN.raw_rows(rows, fields, seed, 0, rows)
    assert X.shape == (rows, 2 * fields) and raw.shape == (rows, fields)
    cols = [Column(kind="float", data=raw[:, j]) for j in range(fields)]
    fitted = NumericVectorizer().fit_columns(*cols)
    pop = DN.population(fields)
    assert np.abs(fitted.fills - fills).max() <= 1e-6 * pop["scale"].max()
    np.testing.assert_allclose(fills, RN.fills_of(raw), rtol=0,
                               atol=1e-6 * pop["scale"].max())
    dev = np.asarray(X.astype(jnp.float32))
    model = NumericVectorizerModel(fills=fills, track_nulls=True)
    for made in (model.transform_block(cols), RN.impute_indicate(raw, fills)):
        assert np.array_equal(RN.as_bf16(made), dev)
    assert set(np.unique(dev[:, 1::2])) == {0.0, 1.0}
    assert np.array_equal(dev[:, 1::2] == 1.0, np.isnan(raw))
    # the rates and scales the configuration states
    assert pop["missing"].min() == pytest.approx(0.001)
    assert pop["missing"].max() == pytest.approx(0.5)
    assert set(np.log2(DN.population(64)["scale"])) == set(range(-4, 5))
    ratio = np.abs(DN.population(64)["loc"]) / DN.population(64)["scale"]
    assert 0.25 <= ratio.min() and ratio.max() <= 2.0
    miss = np.isnan(DN.raw_rows(rows, fields, seed, 0, rows)).mean(0)
    assert np.abs(miss - pop["missing"]).max() < 0.03
    # seeded, and any range again
    X2, y2, _ = DN.device_matrix(rows, fields, "bfloat16", seed, **kw)
    assert np.array_equal(np.asarray(X2.astype(jnp.float32)), dev)
    assert np.array_equal(np.asarray(y2), np.asarray(y))
    X3, _, _ = DN.device_matrix(rows, fields, "bfloat16", seed + 1, **kw)
    assert not np.array_equal(np.asarray(X3.astype(jnp.float32)), dev)
    part = DN.raw_rows(rows, fields, seed, 1000, 1300)
    assert np.array_equal(part, raw[1000:1300], equal_nan=True)
    assert 0.15 < float(np.asarray(y).mean()) < 0.45


def test_blocks_divide_the_rows():
    assert DN._block_rows(25_000_000) == 62_500
    assert DN._block_rows(4096) == 4096
    n = 3 * DN.BLOCK_ROWS + 6
    assert n % DN._block_rows(n) == 0 and DN._block_rows(n) <= DN.BLOCK_ROWS


# -- the reference, piece by piece ------------------------------------------------

def test_impute_indicate_and_fills_by_hand():
    raw = np.array([[1.0, np.nan], [3.0, 4.0], [np.nan, 8.0]])
    assert RN.fills_of(raw).tolist() == [2.0, 6.0]
    assert RN.impute_indicate(raw, [2.0, 6.0]).tolist() == [
        [1.0, 0.0, 6.0, 1.0], [3.0, 0.0, 4.0, 0.0], [2.0, 1.0, 8.0, 0.0]]


def test_kkt_residual_by_hand():
    """reg 0.2, alpha 0.5: l1 = l2 = 0.1. A non-zero coefficient asks g +
    l2 B + l1 sign(B) = 0, a zero one |g| <= l1, the intercept g0 = 0."""
    B = np.array([0.5, 0.0, 0.0, -1.0])
    g = np.array([-0.15, 0.08, 0.25, 0.2])      # exact, inside, 0.15 over,
    assert RN.kkt_residual(g, 0.0, B, 0.2, 0.5) == pytest.approx(0.15)
    assert RN.kkt_residual(g[:2], 0.0, B[:2], 0.2, 0.5) == pytest.approx(0.0)
    assert RN.kkt_residual(g[:2], -0.3, B[:2], 0.2, 0.5) == pytest.approx(0.3)
    assert RN.kkt_residual(g[3:], 0.0, B[3:], 0.2, 0.5) == pytest.approx(0.0)


@pytest.fixture(scope="module")
def small():
    """2 048 rows x 4 fields of the generator, as a device matrix."""
    X, y, _ = DN.device_matrix(2048, 4, "bfloat16", 11, truth_scale=2.5,
                               truth_intercept=-1.5)
    t = (np.arange(2048) % 3 != 0).astype(np.float32)
    return X, np.asarray(y), t


def test_moments_gradient_margins_logloss_against_numpy(small):
    import jax.numpy as jnp
    X, y, t = small
    Xh = np.asarray(X.astype(jnp.float32), np.float64)
    mean, std = RN.moments(X)
    np.testing.assert_allclose(mean, Xh.mean(0), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(std, Xh.std(0), rtol=1e-5)
    rng = np.random.default_rng(0)
    B, b0 = rng.normal(size=(2, 8)) * 0.3, np.array([0.1, -0.4])
    g, g0 = RN.gradient(X, y, t, mean, 1.0 / std, B, b0)
    xs = (Xh - Xh.mean(0)) / Xh.std(0)
    for k in range(2):
        r = (1.0 / (1.0 + np.exp(-(xs @ B[k] + b0[k]))) - y) * t
        np.testing.assert_allclose(g[k], r @ xs / t.sum(), atol=2e-6)
        assert g0[k] == pytest.approx(r.sum() / t.sum(), abs=2e-6)
    beta = rng.normal(size=8)
    m = RN.margins(X, beta, 0.25)
    np.testing.assert_allclose(m, Xh @ beta + 0.25, rtol=1e-5, atol=1e-5)
    w = rng.uniform(size=2048)
    assert RN.logloss(m, y, w) == pytest.approx(
        ((np.log1p(np.exp(m.astype(np.float64))) - y * m) * w).sum()
        / w.sum())


def test_fit_reaches_the_optimum_and_the_replay_its_documented_fixed_point(
        small):
    """`fit`'s coefficients satisfy the optimality conditions (residual
    under 2e-6) with an exact zero where the l1 ball holds one; the
    documented Newton replay stops at a delta under tol, near the optimum
    but not on it where l1 is large (the diagonal threshold), and on it to
    float32 where l1 is 0."""
    X, y, t = small
    mean, std = RN.moments(X)
    xs = RN.standardised(X, mean, 1.0 / std, 2048)
    out = RN.fit(xs, y, t, 0.2, 0.5)
    g, g0 = RN.gradient(X, y, t, mean, 1.0 / std, out["B"][None, :],
                        np.array([out["b0"]]))
    assert RN.kkt_residual(g[0], g0[0], out["B"], 0.2, 0.5) < 2e-6
    assert (out["B"] == 0).any() and (out["B"] != 0).any()
    assert out["iters"] < RN.FIT_ITERS
    rep = RN.newton_replay(xs, y, t, 0.2, 0.5, max_iter=50, tol=1e-6)
    assert rep["deltas"][-1] <= 1e-6 < rep["deltas"][-2]
    assert len(rep["deltas"]) == len(rep["B"]) == len(rep["b0"]) < 50
    assert np.abs(rep["B"][-1] - out["B"]).max() < 0.02
    ridge = RN.fit(xs, y, t, 0.01, 0.0)
    rep0 = RN.newton_replay(xs, y, t, 0.01, 0.0, max_iter=50, tol=1e-6)
    assert np.abs(rep0["B"][-1] - ridge["B"]).max() < 2e-5
    assert abs(rep0["b0"][-1] - ridge["b0"]) < 2e-5


def test_the_twin_is_the_kernels_sums_at_128_columns():
    """pallas_glm.glm_moments in cols_minor tiles (interpret mode, 640 rows
    x 128 columns with standard deviations from 0.03 to 16, 8 lanes of
    which 2 are inert) against the float64 twin written from its
    docstrings, operand roundings and all: to float32 summation and the
    rare rounding tie a last digit of a margin decides (1e-4 of each sum's
    largest entry; the intercept's sums 1e-6)."""
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import glm_sweep as GS
    from transmogrifai_tpu.ops import pallas_glm as PG
    rng = np.random.default_rng(5)
    n, d, Lb, live, F = 640, 128, 8, 6, 3
    scale = 2.0 ** ((np.arange(d) * 3) % 10 - 5)
    X = jnp.asarray((rng.normal(size=(n, d)) * scale + 0.3 * scale)
                    .astype(np.float32)).astype(jnp.bfloat16)
    Xh = np.asarray(X.astype(jnp.float32))
    y = (rng.uniform(size=n) < 0.4).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    fold = rng.integers(0, F, size=n)
    masks = (fold[None, :] != np.arange(F)[:, None]).astype(np.float32)
    sel = np.zeros((F, Lb), np.float32)
    sel[rng.integers(0, F, size=live), np.arange(live)] = 1.0
    B = (rng.normal(size=(Lb, d)) * 0.1).astype(np.float32)
    B[live:] = 0.0
    Bt = jnp.asarray(B).astype(jnp.bfloat16)
    b0 = rng.normal(size=Lb).astype(np.float32)
    mean, std = Xh.mean(0), Xh.std(0)
    assert std.min() < 0.04 and std.max() > 15
    assert GS.glm_x_tile(d) == "cols_minor"
    got = PG.glm_moments(
        X, PG.dense_rows(jnp.asarray(y)), PG.dense_rows(jnp.asarray(w)),
        jnp.asarray(masks), jnp.asarray(sel), Bt, jnp.asarray(b0),
        jnp.asarray(mean), jnp.asarray(std), loss="logistic",
        x_tile="cols_minor", interpret=True)
    args = (y, w, masks, sel, np.asarray(Bt.astype(jnp.float32)), b0, mean,
            std)
    ref = RN.moments_twin(Xh, *args)
    for a, r, tol in zip(got, ref, (1e-4, 1e-4, 1e-6, 1e-6)):
        assert a.shape == r.shape
        assert np.abs(np.asarray(a) - r).max() <= tol * np.abs(r).max()
    assert all((np.asarray(v)[live:] == 0).all() for v in got)
    # the twin itself, one lane by hand
    xs = RN.as_bf16((Xh - mean) / std).astype(np.float64)
    k = 2
    p = 1.0 / (1.0 + np.exp(-(xs @ args[4][k].astype(np.float64) + b0[k])))
    wl = (masks.T * w[:, None]) @ sel[:, k]
    low = lambda v: RN.as_bf16(v).astype(np.float64)     # noqa: E731
    np.testing.assert_allclose(ref[0][k], low((p - y) * wl) @ xs, rtol=1e-9,
                               atol=1e-9)
    np.testing.assert_allclose(
        ref[1][k], low(xs * (np.maximum(p * (1 - p), 1e-6) * wl)[:, None]).T
        @ xs, rtol=1e-9, atol=1e-9)
    assert ref[2][k] == pytest.approx(((p - y) * wl).sum(), rel=1e-12)


# -- the cell, rehearsed ONCE ------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    out = tmp_path_factory.mktemp("nulls")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)   # conftest's 8 virtual devices
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3700000007", "--seconds", "2",
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
    assert notes["routes"]["cells"] == [["OpLogisticRegression", "streamed"]]
    # the CPU has no Mosaic: the blocks run, and the cell says so (on the
    # chip the same readings must name the fused body, or the run fails)
    body = notes["round_body"]
    assert notes["round_body_declared"] == body["telemetry"] == "xla_blocks"
    assert body["round_spans"] == [["xla_blocks", "rows_minor"]]
    assert body["fit_span"] == {"cols": 16, "lanes": 24, "bucket": 32,
                                "standardize": True}
    assert body["round_temp_bytes"] > 0
    # the program's counters and spans, under the cell's names
    listed = {f[:-5] for f in os.listdir(os.path.join(REPO, "benchmark",
                                                      "layers"))
              if CELL in _load("layers", f).get("cells", [])}
    assert {"nul_padded_lane_passes", "nul_round_temp_bytes",
            "nul_fit_host_s", "nul_host_fetches", "programs_compiled",
            "window_compiles"} <= set(line["metrics"])
    assert set(line["metrics"]) <= listed | {"programs_compiled",
                                             "window_compiles"}
    assert line["metrics"]["nul_round_temp_bytes"]["value"] \
        == body["round_temp_bytes"]
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
    ans, c = notes["nulls_answer"], tol["nulls_answer"]
    assert len(ans["folds"]) == 3
    assert ans["metric_worst_delta"] <= c["tol_metric"]
    assert ans["kkt_worst"] <= c["tol_kkt"]
    assert ans["coefficients_worst"] <= c["tol_coefficients"]
    assert ans["logloss_delta_worst"] <= c["tol_logloss"]
    # indicator columns left out of the margins: the metric pass and the fit
    assert ans["indicators_left_out_metric_delta"] > c["tol_metric"]
    for point in ans["points"].values():
        wrong = point["wrong"]
        for name in ("indicators_left_out", "std_not_applied"):
            assert wrong[name]["kkt"] > c["tol_kkt"], name
            assert wrong[name]["coefficients"] > c["tol_coefficients"], name
        # one Newton iteration fewer moves the coefficients by the sweep's
        # last delta, under its own tol: the same answer at this tolerance,
        # which no bound refuses (PERF.md §7); it is read and reported
        fewer = wrong["one_newton_iteration_fewer"]
        assert abs(fewer["coefficients"] - point["coefficients"]) < 1e-5
        assert abs(fewer["kkt"] - point["kkt"]) < 1e-5
        assert "half_the_newton_iterations" in wrong
        assert point["newton_replay_deltas"][-1] <= 1e-6
    # fill 0 in place of the mean
    tie, c = notes["vectoriser_tie"], tol["vectoriser_tie"]
    assert tie["reference_vs_device"] == tie["program_vs_device"] == 0
    assert tie["fills_worst_sd"] <= c["tol_fills_sd"]
    assert tie["fill_zero_vs_device"] > 0
    assert tie["fill_zero_worst_sd"] > c["tol_fills_sd"]
    # sums accumulated in bfloat16
    twin, c = notes["moments_twin"], tol["moments_twin"]
    assert twin["worst"] <= c["tol"] < twin["bf16_accumulation"]


def test_a_program_whose_rounds_are_not_the_fused_body_is_refused(
        monkeypatch):
    """Asked BEFORE any data is made: on this backend (the CPU)
    glm_round_kernel(128, bfloat16, 64) names the blocks, and off
    --rehearse the driver refuses the run there."""
    driver = harness.load_module("drivers", "sweep_nulls")
    cell = _load("workloads", CELL + ".json")
    config = _load("configs", CONFIG + ".json")

    def forbidden(*a, **kw):
        raise AssertionError("data was made")
    monkeypatch.setattr(DN, "device_matrix", forbidden)
    ctx = harness.Ctx(cell=cell, config=config, sizes=dict(config["sizes"]),
                      seed=1, seconds=1.0, trace=False, rehearse=False,
                      out_dir="/nonexistent", compile_log=None)
    with pytest.raises(harness.BenchFailure, match="glm_round_kernel"):
        driver.setup(ctx)
    assert ctx.notes["round_body_declared"] == "xla_blocks"


# -- the files --------------------------------------------------------------------

def test_manifest_lists_the_cell_under_glm_sweep_s():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = _load("workloads", CELL + ".json")
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    assert entry["traffic"] == cell["traffic"] == "glm-nulls-closed-1"
    e2e = next(m for m in manifest["end_to_end"]
               if m["name"] == "glm_sweep_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.02
    mine = {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine == {
        "nul_rounds_device_s", "nul_moments_kernel_s", "nul_rounds_roofline",
        "nul_standardize_device_s", "nul_metric_device_s", "nul_host_gap_s",
        "nul_padded_lane_passes", "nul_round_temp_bytes", "nul_fit_host_s",
        "nul_host_fetches"}     # membership, not position (PERF.md §7 (h))


def test_the_configuration_is_upstreams_defaults():
    config = _load("configs", CONFIG + ".json")
    cell = _load("workloads", CELL + ".json")
    assert config["reduced"] == {} and "TrackNulls" in config["source"]
    sz = config["sizes"]
    assert sz["cols"] == 2 * sz["raw_cols"] == 128 and sz["folds"] == 5
    assert sz["rows"] == 25_000_000 and sz["dtype"] == "bfloat16"
    assert config["pool"]["lr"]["params"] == {
        "max_iter": 50, "tol": 1e-6, "standardization": True,
        "fit_intercept": True}
    grid = cell["families"]["lr"]["grid"]
    assert grid == {"reg_param": [0.001, 0.01, 0.1, 0.2],
                    "elastic_net_param": [0.1, 0.5]}    # the LR grid WHOLE
    assert config["glm_grid"] == 8
    for key in ("rows", "missing", "values", "fill", "label"):
        assert key in config["assumed"]
    for check in cell["checks"].values():
        assert "pinned_from" in check


def test_the_roofline_counts_the_full_gram_at_128_columns():
    """opcount.glm_sweep with this cell's counters: a 64-lane pass at 128
    columns is 8 x the Gram work of sweep-glm's 32-lane pass at 64."""
    f128, b128 = opcount.glm_sweep(25_000_000, 128, 64, 1, 2)
    f64, _ = opcount.glm_sweep(25_000_000, 64, 32, 1, 2)
    assert f128 == 64 * 25e6 * (4 * 128 + 2 * 128 * 128)
    assert b128 == 25e6 * 128 * 2
    assert 7.5 < f128 / f64 < 8.0


def test_the_nulls_reference_imports_nothing_of_the_program():
    for name in ("reference_nulls.py", "datagen_nulls.py"):
        with open(os.path.join(REPO, "benchmark", name)) as f:
            src = f.read()
        assert "transmogrifai_tpu" not in src.split('"""', 2)[2], name
