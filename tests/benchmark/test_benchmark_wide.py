"""The wide cell `sweep-glm-wide4k` and what it brought to the benchmark:
the cell rehearsed on the CPU traced and untraced with its metrics printed
and its spans found, benchmark/datagen_hashed.py against its own closed-form
moments, benchmark/reference_wide.py against a numpy loop and against the
optimality conditions of the objective it states, a sweep whose answer was
changed coming out not correct, benchmark/opcount_wide.py by hand, and the
refusal of a program that lacks the wide route."""
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import datagen_hashed as DH  # noqa: E402
from benchmark import harness, opcount, opcount_wide  # noqa: E402
from benchmark import reference, reference_wide as RW  # noqa: E402
from benchmark.reduce_trace import Reduced  # noqa: E402

CELL = "sweep-glm-wide4k"
CONFIG = "hashed-text-786k-4104"


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
         "--workload", CELL, "--seed", "2900000007", "--seconds", "3",
         "--trace", str(trace), "--rehearse", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=400)
    assert r.returncode == 0, r.stderr[-3000:]
    report, line = map(json.loads, r.stdout.strip().splitlines())
    assert line["correct"] is True, report["problems"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    notes = report["notes"]
    assert notes["routes"]["cells"] == [["OpLogisticRegression", "streamed"]]
    answer = notes["wide_answer"]
    assert len(answer["folds"]) == 3 and answer["reference_iters"] == 50
    assert answer["metric_worst_delta"] < 1e-5
    assert answer["coefficient_delta"] < 1e-4
    assert answer["logloss_delta"] < 1e-5
    assert answer["objective_delta"] < 1e-6
    # each named wrong computation is further off than the sweep, in the
    # comparison that is there to refuse it
    wrong = answer["wrong"]
    assert wrong["one_iteration_fewer"]["coefficients"] \
        > 20 * answer["coefficient_delta"]
    assert wrong["bf16_coefficients"]["coefficients"] \
        > 20 * answer["coefficient_delta"]
    assert wrong["standardisation_dropped"]["logloss_delta"] > 1e-2
    assert notes["gram_twin"]["worst"] < 1e-5 \
        < 1e-3 < notes["gram_twin"]["bf16_accumulation"]
    # 8 grid points x 3 folds = 24 lanes in a bucket of 32 (no lane retires
    # before max_iter 50 here), one Gram, two passes of moments
    counters = report["counters"]
    assert counters["wglm_x_passes"] == 53
    assert counters["wglm_data_passes"] == 50
    assert counters["wglm_gram_passes"] == 1
    assert counters["wglm_factorizations"] == 0
    assert counters["wglm_padded_cols"] == 384 and counters["cols"] == 264
    assert counters["wglm_padded_lane_passes"] \
        >= counters["wglm_lane_passes"] > 0
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"glm_sweep_s", "setup_s"}
        assert all(m["value"] > 0 for m in metrics.values())
        return
    specs = _layer_specs()
    assert set(specs) == {
        "wglm_rounds_device_s", "wglm_gram_device_s", "wglm_metric_device_s",
        "wglm_host_gap_s", "wglm_rounds_roofline", "wglm_gram_roofline",
        "wglm_x_passes", "wglm_fit_host_s", "wglm_host_fetches"}
    # the rooflines need the chip's peaks; every other metric is printed
    assert set(metrics) == (
        set(specs) - {"wglm_rounds_roofline", "wglm_gram_roofline"}) \
        | {"programs_compiled", "window_compiles"}
    for name, m in metrics.items():
        if name in specs:
            assert m["unit"] == specs[name]["unit"], name
            assert m["value"] >= 0, name
    assert metrics["window_compiles"]["value"] == 0
    assert metrics["wglm_x_passes"]["value"] == 53
    # 10 rounds of 5 iterations + 3 folds x one chunk of 8 grid points
    assert metrics["wglm_host_fetches"]["value"] == 13
    assert metrics["wglm_rounds_device_s"]["value"] \
        > metrics["wglm_gram_device_s"]["value"] > 0
    assert metrics["wglm_metric_device_s"]["value"] > 0
    # the new spans, under the validate root and on its thread
    ctx = types.SimpleNamespace(
        reduced=Reduced.from_file(notes["xplane"]),
        cell={"job_span": "bench.validate"})

    def count(name):
        return harness.load_module("readers", "host_span").read(
            ctx, {"name": name, "stat": "count"})
    assert count(r"^tmog\.validate:CrossValidation$") == 1
    assert count(r"^tmog\.sweep_fit:glm_streamed:OpLogisticRegression$") == 1
    assert count(r"^tmog\.host_step:gram_factor$") == 1
    assert count(r"^tmog\.sweep_round:glm_wide_round\[\d+\]$") == 10
    assert count(r"^tmog\.host_step:round_prep$") == 10
    assert count(r"^tmog\.host_step:round_fetch$") == 10
    assert count(r"^tmog\.host_step:metric_fetch$") == 3


def test_a_program_without_the_route_is_refused_before_any_data(
        monkeypatch, tmp_path):
    """What the parent of this cell's PR does: ops/glm_sweep declares no
    wide route, and the driver fails with BenchFailure before it makes a
    byte of data."""
    from transmogrifai_tpu.ops import glm_sweep as GS
    driver = harness.load_module("drivers", "sweep_wide")
    monkeypatch.delattr(GS, "streamed_wide_route_ok")

    def no_data(*a, **k):
        raise AssertionError("data was made")
    monkeypatch.setattr(DH, "device_matrix", no_data)
    cell, config = _load("workloads", CELL + ".json"), \
        _load("configs", CONFIG + ".json")
    ctx = harness.Ctx(cell=cell, config=config, sizes=dict(config["sizes"]),
                      seed=1, seconds=1.0, trace=False, rehearse=False,
                      out_dir=str(tmp_path), compile_log=None)
    with pytest.raises(harness.BenchFailure,
                       match="declares no wide streamed route"):
        driver.setup(ctx)


def test_manifest_lists_the_cell_under_glm_sweep_s():
    """Membership and order, not position from the end: a later PR appends
    after these entries."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    listed = e2e["glm_sweep_s"]["workloads"]
    assert listed[:3] == ["sweep-glm", "sweep-mlr-k32", CELL]
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == cells.index("sweep-mlr-k32") + 1
    entry = manifest["workloads"][cells.index(CELL)]
    assert entry["config"] == CONFIG and entry["chips"] == 1
    assert entry["traffic"] == "glm-wide-closed-1"
    conf = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == [] \
        and conf["file"] == f"benchmark/configs/{CONFIG}.json"
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(mine) == sorted(_layer_specs())


def test_the_configuration_is_upstreams_defaults():
    config, cell = _load("configs", CONFIG + ".json"), \
        _load("workloads", CELL + ".json")
    sz = config["sizes"]
    assert sz["cols"] == sz["text_columns"] * (sz["buckets"] + 1) == 4104
    assert sz["rows"] == 24 * 32768 and sz["buckets"] == 512
    assert config["reduced"] == {} and config["glm_grid"] == 8
    assert config["pool"]["lr"]["params"] == {
        "max_iter": 50, "tol": 1e-6, "standardization": True,
        "fit_intercept": True}
    grid = cell["families"]["lr"]["grid"]
    assert grid == {"reg_param": [0.001, 0.01, 0.1, 0.2],
                    "elastic_net_param": [0.1, 0.5]}
    assert cell["chips"] == 1 and cell["min_jobs"] == 3
    # the matrix alone is over the floor of a quarter of the chip
    assert sz["rows"] * sz["cols"] * 2 > 0.25 * 16.9e9
    reh = config["rehearsal"]
    assert reh["cols"] == sz["text_columns"] * (reh["buckets"] + 1) == 264


# -- the generator ----------------------------------------------------------------

def test_the_generator_is_seeded_exact_in_bf16_and_has_its_moments():
    kw = dict(truth_nonzero=16, truth_scale=3.0, truth_intercept=-2.45)
    X, y = DH.device_matrix(8192, 8, 32, "bfloat16", 2 ** 31 + 5, **kw)
    X2, y2 = DH.device_matrix(8192, 8, 32, "bfloat16", 2 ** 31 + 5, **kw)
    X3, _ = DH.device_matrix(8192, 8, 32, "float32", 7, **kw)
    Xh = np.asarray(X, np.float32)
    assert X.shape == (8192, 264) and str(X.dtype) == "bfloat16"
    assert np.array_equal(Xh, np.asarray(X2, np.float32))
    assert np.array_equal(np.asarray(y), np.asarray(y2))
    assert not np.array_equal(Xh, np.asarray(X3))
    # whole counts no larger than 255: exact in bfloat16
    assert np.array_equal(Xh, np.round(Xh)) and 0 <= Xh.min() \
        and Xh.max() <= 255
    # a null column: zero counts, indicator one
    blocks = Xh.reshape(8192, 8, 33)
    null = blocks[:, :, 32] == 1.0
    assert 0.08 < null.mean() < 0.12
    assert blocks[:, :, :32][null].sum() == 0
    mean, std = DH.population_moments(8, 32)
    heavy = mean > 0.5
    assert np.abs(Xh.mean(0)[heavy] / mean[heavy] - 1).max() < 0.1
    assert np.abs(Xh.std(0)[heavy] / std[heavy] - 1).max() < 0.15
    assert 0.1 < float(np.asarray(y).mean()) < 0.35
    q = DH.bucket_masses(8, 32)
    assert q.shape == (8, 32) and np.allclose(q.sum(1), 1.0)
    assert not np.array_equal(q[0], q[1])       # a hash space a column
    beta = DH.truth(8, 32, 16, 3.0)
    assert (beta != 0).sum() == 24 and (beta.reshape(8, 33)[:, 32] != 0).all()


# -- the reference ---------------------------------------------------------------

def _toy(n=400, d=5, seed=0):
    rng = np.random.default_rng(seed)
    X = (rng.poisson(1.0, size=(n, d)) * [1.0, 2.0, 1.0, 3.0, 1.0]) \
        .astype(np.float32)
    X[:, 1] += X[:, 0]
    z = (X - X.mean(0)) / X.std(0) @ rng.normal(size=d) - 0.7
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(np.float32)
    w = rng.uniform(0.5, 2.0, n).astype(np.float32)
    t = w * (rng.random(n) < 0.8)
    return X, y, w, t.astype(np.float32)


def _numpy_fit(X, y, w, t, reg, alpha, iters):
    """The documented iteration as a float64 loop."""
    X, w, t = (a.astype(np.float64) for a in (X, w, t))
    n, d = X.shape
    W, T = w.sum(), t.sum()
    mean = (X * w[:, None]).sum(0) / W
    std = np.sqrt((((X - mean) ** 2) * w[:, None]).sum(0) / W)
    Xs = (X - mean) / std
    Gs = np.zeros((d, d))
    for i in range(n):
        Gs += w[i] * np.outer(Xs[i], Xs[i])
    v = np.full(d, d ** -0.5)
    for _ in range(RW.POWER_ITERS):
        v = Gs @ v
        v /= np.linalg.norm(v)
    lam = RW.LAM_MARGIN * v @ Gs @ v
    l1, l2, kappa = reg * alpha, reg * (1 - alpha), 0.25 / T
    step = 1.0 / (kappa * lam + l2)
    B, b0 = np.zeros(d), 0.0
    for _ in range(iters):
        r = (1 / (1 + np.exp(-(Xs @ B + b0))) - y) * t
        g = Xs.T @ r / T
        z, v, th = B.copy(), B.copy(), 1.0
        for _ in range(RW.INNER_STEPS):
            u = v - step * (g + kappa * Gs @ (v - B) + l2 * v)
            zn = np.sign(u) * np.maximum(np.abs(u) - step * l1, 0.0)
            thn = 0.5 * (1 + np.sqrt(1 + 4 * th * th))
            v, z, th = zn + (th - 1) / thn * (zn - z), zn, thn
        B, b0 = z, b0 - 4 * r.sum() / W
    return B / std, b0 - (B / std * mean).sum()


def test_reference_fit_is_the_documented_iteration():
    import jax.numpy as jnp
    X, y, w, t = _toy()
    out = RW.fit(jnp.asarray(X), y, w, t, 0.05, 0.5, max_iter=9, tol=0.0)
    nB, nb0 = _numpy_fit(X, y, w, t, 0.05, 0.5, 9)
    assert out["iters"] == 9
    # float32 at `highest` against float64: 9 iterations on 400 rows
    assert np.abs(out["beta"] - nB).max() < 2e-5
    assert abs(out["b0"] - nb0) < 2e-5
    # and one iteration fewer is another answer
    fewer = RW.fit(jnp.asarray(X), y, w, t, 0.05, 0.5, max_iter=9, tol=0.0,
                   iterations=8)
    assert fewer["iters"] == 8
    assert np.abs(fewer["beta"] - nB).max() > 1e-4


def test_reference_fit_converges_to_the_objectives_optimum():
    """Run long, the iteration stops where 0 is in the subdifferential of
    the STATED objective: training-weighted mean log-loss + reg (alpha |B|_1
    + (1 - alpha) / 2 |B|^2) on the scale standardised by ALL rows, the
    intercept free."""
    import jax.numpy as jnp
    X, y, w, t = _toy(seed=1)
    reg, alpha = 0.08, 0.5
    out = RW.fit(jnp.asarray(X), y, w, t, reg, alpha, max_iter=3000,
                 tol=1e-9)
    X64, w64, t64 = (a.astype(np.float64) for a in (X, w, t))
    mean = (X64 * w64[:, None]).sum(0) / w64.sum()
    std = np.sqrt((((X64 - mean) ** 2) * w64[:, None]).sum(0) / w64.sum())
    Bs = out["beta"] * std
    r = (1 / (1 + np.exp(-(X64 @ out["beta"] + out["b0"]))) - y) * t64 \
        / t64.sum()
    g = ((X64 - mean) / std).T @ r + reg * (1 - alpha) * Bs
    on = Bs != 0
    assert 0 < on.sum() <= len(Bs)
    assert np.abs(g[on] + reg * alpha * np.sign(Bs[on])).max() < 2e-5
    assert (np.abs(g[~on]) <= reg * alpha + 2e-5).all()
    assert abs(r.sum()) < 2e-5                  # the intercept unpenalised
    # its objective is the lowest: 4 iterations are NOT there yet
    def obj(o):
        m = RW.margins(jnp.asarray(X), o["beta"], o["b0"])
        return RW.objective(m, y, t, o["beta"], o["inv_std"], reg, alpha)
    early = RW.fit(jnp.asarray(X), y, w, t, reg, alpha, max_iter=4, tol=0.0)
    assert obj(early) > obj(out) + 1e-7
    assert np.abs(early["beta"] - out["beta"]).max() > 1e-4


def test_margins_logloss_objective_and_gram_twin_by_hand():
    import jax.numpy as jnp
    X = jnp.asarray([[1.0, 0.0], [0.0, 2.0], [3.0, 1.0]], jnp.bfloat16)
    beta, b0 = np.float32([0.5, -1.0]), 0.25
    m = RW.margins(X, beta, b0)
    assert np.allclose(m, [0.75, -1.75, 0.75])
    y, w = np.float32([1, 0, 0]), np.float32([1.0, 2.0, 0.0])
    want = (np.log1p(np.exp(-0.75)) + 2 * np.log1p(np.exp(-1.75))) / 3
    assert RW.logloss(m, y, w) == pytest.approx(want)
    inv_std = np.float32([2.0, 0.5])            # standardised: 0.25, -2
    assert RW.objective(m, y, w, beta, inv_std, 0.1, 0.5) == pytest.approx(
        want + 0.1 * (0.5 * 2.25 + 0.25 * (0.0625 + 4.0)))
    G = RW.gram_twin(np.asarray(X, np.float32), w, [1.0, 1.0], inv_std)
    raw = np.array([[1.0, 0.0], [0.0, 8.0]]) - 3.0
    assert np.allclose(G, raw * np.outer(inv_std, inv_std))


# -- a sweep whose answer was changed -----------------------------------------------

FIT = {"max_iter": 15, "tol": 1e-6, "fit_intercept": True,
       "standardize": True}
TOLS = dict(tol_metric=1e-5, tol_coefficients=2e-4, tol_logloss=1e-5,
            tol_objective=1e-6)


@pytest.fixture(scope="module")
def wide_case():
    """A sweep's answer made by hand: fold coefficients from the plain
    reference itself, fold metrics their exact AuPR."""
    n = 3000
    X, y = DH.device_matrix(n, 8, 32, "bfloat16", 11, truth_nonzero=16,
                            truth_scale=3.0, truth_intercept=-2.45)
    yh = np.asarray(y)
    masks = np.ones((3, n), np.float32)
    for f in range(3):
        masks[f, f::3] = 0.0
    grids = [{"reg_param": 0.2, "elastic_net_param": 0.5},
             {"reg_param": 0.01, "elastic_net_param": 0.1}]
    d = X.shape[1]
    B = np.zeros((3, 2, d), np.float32)
    b0 = np.zeros((3, 2), np.float32)
    au = np.zeros((2, 3))
    ones = np.ones(n, np.float32)
    for j, g in enumerate(grids):
        for f in range(3):
            out = RW.fit(X, yh, ones, masks[f], g["reg_param"],
                         g["elastic_net_param"], **FIT)
            B[f, j], b0[f, j] = out["beta"], out["b0"]
            au[j, f] = _au_pr(X, yh, B[f, j], b0[f, j], masks[f])
    validated = [types.SimpleNamespace(
        grid=g, route="streamed", fold_metrics=list(au[j]),
        mean_metric=float(au[j].mean())) for j, g in enumerate(grids)]
    return types.SimpleNamespace(validated=validated), B, b0, masks, \
        grids, X, y


def _au_pr(X, yh, beta, b0, mask):
    return reference.numpy_au_pr(RW.margins(X, beta, b0), yh, 1.0 - mask)


def _answer(case, B=None, b0=None, best=None, **tols):
    best0, B0, b00, masks, grids, X, y = case
    return RW.wide_sweep_answer(
        best or best0, [(B0 if B is None else B, b00 if b0 is None else b0)],
        masks, grids, X, y, fit_params=FIT, reference_fold=1,
        **dict(TOLS, **tols))


def test_answer_passes_on_its_own_coefficients(wide_case):
    out = _answer(wide_case)
    top = max(wide_case[0].validated, key=lambda v: v.mean_metric)
    assert out["grid"] == top.grid              # the HIGHER AuPR wins
    assert out["metric_worst_delta"] < 1e-12
    assert out["coefficient_delta"] < 1e-6 and out["logloss_delta"] < 1e-7
    assert out["objective_delta"] < 1e-7
    assert len(out["folds"]) == 3 and out["reference_fold"] == 1
    assert set(out["wrong"]) == {"one_iteration_fewer",
                                 "standardisation_dropped",
                                 "bf16_coefficients"}
    # every named wrong computation is refused by at least one bound
    for name, w in out["wrong"].items():
        assert (w["coefficients"] > TOLS["tol_coefficients"]
                or w["logloss_delta"] > TOLS["tol_logloss"]
                or w["objective_delta"] > TOLS["tol_objective"]), name


def test_answer_fails_a_metric_that_is_not_its_coefficients(wide_case):
    off = [types.SimpleNamespace(**vars(v)) for v in wide_case[0].validated]
    for v in off:
        v.fold_metrics = [m + 5e-4 for m in v.fold_metrics]
    with pytest.raises(reference.CheckFailure, match="exact AuPR of its own"):
        _answer(wide_case, best=types.SimpleNamespace(validated=off))


def test_answer_fails_coefficients_rounded_to_bfloat16(wide_case):
    """Coefficients a sweep rounded to bfloat16 on their way out, with
    metrics honestly theirs."""
    best, B, b0, masks, grids, X, y = wide_case
    yh = np.asarray(y)
    Bl = RW._as_bf16(B)
    low = [types.SimpleNamespace(
        grid=g, route="streamed",
        fold_metrics=[_au_pr(X, yh, Bl[f, j], b0[f, j], masks[f])
                      for f in range(3)]) for j, g in enumerate(grids)]
    for v in low:
        v.mean_metric = float(np.mean(v.fold_metrics))
    with pytest.raises(reference.CheckFailure, match="plain reference fit"):
        _answer(wide_case, B=Bl, best=types.SimpleNamespace(validated=low))


def test_answer_fails_coefficients_that_stopped_early(wide_case):
    """Coefficients of 14 iterations where 15 were asked, with metrics
    honestly theirs."""
    best, B, b0, masks, grids, X, y = wide_case
    yh = np.asarray(y)
    top = max(best.validated, key=lambda v: v.mean_metric)
    j = grids.index(top.grid)
    Bs, b0s = B.copy(), b0.copy()
    ones = np.ones(len(yh), np.float32)
    for f in range(3):
        out = RW.fit(X, yh, ones, masks[f], grids[j]["reg_param"],
                     grids[j]["elastic_net_param"], iterations=14, **FIT)
        Bs[f, j], b0s[f, j] = out["beta"], out["b0"]
    early = types.SimpleNamespace(
        grid=grids[j], route="streamed", mean_metric=1.0,
        fold_metrics=[_au_pr(X, yh, Bs[f, j], b0s[f, j], masks[f])
                      for f in range(3)])
    with pytest.raises(reference.CheckFailure, match="plain reference fit"):
        _answer(wide_case, B=Bs, b0=b0s,
                best=types.SimpleNamespace(validated=[early]))


def test_answer_needs_the_sweeps_coefficients(wide_case):
    best0, B, b0, masks, grids, X, y = wide_case
    with pytest.raises(reference.CheckFailure, match="cannot be read"):
        RW.wide_sweep_answer(
            best0, [], masks, grids, X, y, fit_params=FIT, reference_fold=0,
            **TOLS)
    with pytest.raises(reference.CheckFailure, match="of shape"):
        _answer(wide_case, B=B[..., :200])      # a column axis too short


# -- the work models ---------------------------------------------------------------

def test_wide_opcounts_by_hand():
    flops, byts = opcount_wide.wide_rounds(
        rows=1000, cols=8, padded_lane_passes=320, x_passes=13,
        gram_passes=1, inner_steps=16, itemsize=2)
    assert flops == (4 * 1000 * 8 + 2 * 8 * 8 * 16) * 320
    assert byts == 12 * 1000 * 8 * 2
    flops, byts = opcount_wide.wide_gram(rows=1000, cols=8, gram_passes=1,
                                         itemsize=2)
    assert flops == 2 * 1000 * 8 * 8 and byts == 1000 * 8 * 2 + 8 * 8 * 4
    # the cell as configured: 64 padded lanes x 50 iterations, 53 reads of X
    peaks = _load("peaks.json")["devices"]["TPU v5 lite"]
    flops, byts = opcount_wide.wide_rounds(
        rows=786_432, cols=4_104, padded_lane_passes=3_200, x_passes=53,
        gram_passes=1, inner_steps=16, itemsize=2)
    assert flops == pytest.approx(4.3037e13, rel=1e-4)
    assert byts == pytest.approx(3.3566e11, rel=1e-4)
    least, roof = opcount.least_seconds(flops, byts, peaks)
    assert roof == "bytes" and least == pytest.approx(0.40984, rel=1e-4)
    flops, byts = opcount_wide.wide_gram(
        rows=786_432, cols=4_104, gram_passes=1, itemsize=2)
    least, roof = opcount.least_seconds(flops, byts, peaks)
    assert flops == pytest.approx(2.6491e13, rel=1e-4)
    assert roof == "flops" and least == pytest.approx(0.13447, rel=1e-4)


def test_the_wide_reference_imports_nothing_of_the_program():
    for name in ("reference_wide.py", "datagen_hashed.py", "opcount_wide.py"):
        with open(os.path.join(REPO, "benchmark", name)) as f:
            src = f.read()
        assert not re.search(r"^\s*(from|import)\s+transmogrifai_tpu", src,
                             re.M), name
