"""The multiclass cell `sweep-mlr-k32` and what it brought to the benchmark:
the cell rehearsed on the CPU traced and untraced with its metrics printed
and its host gap split, benchmark/reference_softmax.py against brute-force
numpy and against the objective it states, a sweep whose answer was
changed coming out not correct, benchmark/opcount_softmax.py by hand, and
the refusal of a program that lacks the streamed multiclass route."""
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

from benchmark import harness, opcount_softmax  # noqa: E402
from benchmark import reference, reference_softmax as RS  # noqa: E402
from benchmark.reduce_trace import Reduced  # noqa: E402

CELL = "sweep-mlr-k32"
ROOT = r"^tmog\.validate:"
TOP_LEVEL = r"^tmog\.(validate_phase|sweep_fit|sweep_eval):"


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
         "--workload", CELL, "--seed", "2500000007", "--seconds", "3",
         "--trace", str(trace), "--rehearse", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    report, line = map(json.loads, r.stdout.strip().splitlines())
    assert line["correct"] is True, report["problems"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    notes = report["notes"]
    assert notes["routes"]["cells"] == [["OpLogisticRegression", "streamed"]]
    answer = notes["mlr_answer"]
    assert len(answer["folds"]) == 3
    # float32 logits on both sides at this size: the same predictions
    assert answer["metric_worst_delta"] < 1e-6
    assert answer["coefficient_delta"] < 0.05
    assert answer["logloss_delta"] < 1e-3
    assert notes["confusion_twin"]["lanes"] == 3
    # 3 grid points x 3 folds = 9 lanes in a bucket of 16, 50 iterations
    assert report["counters"]["mlr_padded_lane_passes"] == 800
    assert report["counters"]["mlr_lane_passes"] == 450
    assert report["counters"]["mlr_gram_passes"] == 3
    assert report["counters"]["mlr_data_passes"] == 53
    assert report["counters"]["classes"] == 5
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"glm_sweep_s", "setup_s"}
        assert all(m["value"] > 0 for m in metrics.values())
        return
    specs = _layer_specs()
    assert set(specs) == {
        "mlr_host_gap_s", "mlr_rounds_device_s", "mlr_metric_device_s",
        "mlr_rounds_roofline", "mlr_padded_lane_passes", "mlr_fit_host_s",
        "mlr_eval_host_s", "mlr_host_fetches"}
    # the roofline needs the chip's peaks; every other metric is printed
    assert set(metrics) == (set(specs) - {"mlr_rounds_roofline"}) \
        | {"programs_compiled", "window_compiles"}
    for name, m in metrics.items():
        if name in specs:
            assert m["unit"] == specs[name]["unit"], name
            assert m["value"] >= 0, name
    assert metrics["window_compiles"]["value"] == 0
    assert metrics["mlr_padded_lane_passes"]["value"] == 800
    # 10 rounds of 5 iterations + 3 folds x one chunk of 3 grid points
    assert metrics["mlr_host_fetches"]["value"] == 13
    assert metrics["mlr_rounds_device_s"]["value"] \
        > metrics["mlr_metric_device_s"]["value"] > 0
    # the same trace, read once more: the top-level phases (label_classes,
    # fold_assign, device_place, bookkeeping, record, winner have no metric
    # of their own in this cell) and the rest add up to the host gap
    ctx = types.SimpleNamespace(
        reduced=Reduced.from_file(notes["xplane"]),
        cell={"job_span": "bench.validate"})

    def read(name, stat):
        return harness.load_module("readers", "host_span").read(
            ctx, {"name": name, "stat": stat})
    phases, uncovered = read(TOP_LEVEL, "exposed_s"), read(ROOT,
                                                           "uncovered_s")
    assert phases + uncovered == pytest.approx(
        metrics["mlr_host_gap_s"]["value"], rel=1e-6)
    listed = metrics["mlr_fit_host_s"]["value"] \
        + metrics["mlr_eval_host_s"]["value"]
    others = read(r"^tmog\.validate_phase:", "exposed_s")
    assert listed + others == pytest.approx(phases, rel=1e-6)
    assert 0 < listed <= phases
    assert read(r"^tmog\.validate_phase:label_classes$", "count") == 1
    assert read(r"^tmog\.host_step:gram_factor$", "count") == 1
    assert read(r"^tmog\.sweep_round:mlr_round\[16\]$", "count") == 10


def test_a_program_without_the_route_is_refused_before_any_data(
        monkeypatch, tmp_path):
    """What the parent of this cell's PR does: OpLogisticRegression
    declares no streamed multiclass route, and the driver fails with
    BenchFailure before it makes a byte of data."""
    from benchmark import datagen_softmax
    from transmogrifai_tpu.models.glm import OpLogisticRegression
    driver = harness.load_module("drivers", "sweep_mlr")
    monkeypatch.delattr(OpLogisticRegression, "streamed_multiclass_loss")

    def no_data(*a, **k):
        raise AssertionError("data was made")
    monkeypatch.setattr(datagen_softmax, "device_matrix", no_data)
    cell, config = _load("workloads", CELL + ".json"), \
        _load("configs", "multiclass-25m-64-k32.json")
    ctx = harness.Ctx(cell=cell, config=config, sizes=dict(config["sizes"]),
                      seed=1, seconds=1.0, trace=False, rehearse=False,
                      out_dir=str(tmp_path), compile_log=None)
    with pytest.raises(harness.BenchFailure,
                       match="declares no streamed multiclass route"):
        driver.setup(ctx)


def test_manifest_lists_the_cell_under_glm_sweep_s():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["glm_sweep_s"]["workloads"] == ["sweep-glm", CELL]
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == "multiclass-25m-64-k32"
    listed = [m["name"] for m in manifest["per_layer"]
              if m.get("workloads") == [CELL]]
    assert sorted(listed) == sorted(_layer_specs())
    config = _load("configs", "multiclass-25m-64-k32.json")
    assert config["pool"]["lr"]["params"] == {
        "max_iter": 50, "tol": 1e-6, "standardization": True,
        "fit_intercept": True}
    assert config["sizes"]["classes"] == 32 and config["sizes"]["cols"] == 64


# -- the data -----------------------------------------------------------------

def test_datagen_is_seeded_chunked_and_skewed():
    from benchmark import datagen_softmax as DS
    B, b = DS.truth(8, 5, 3.0)
    assert B.shape == (8, 5) and b[0] == 0 and b[4] == pytest.approx(
        -np.log(5))
    X, y = DS.device_matrix(3000, 8, 5, "bfloat16", 2 ** 31 + 5, 3.0)
    X2, y2 = DS.device_matrix(3000, 8, 5, "bfloat16", 2 ** 31 + 5, 3.0)
    X3, _ = DS.device_matrix(3000, 8, 5, "bfloat16", 7, 3.0)
    assert X.shape == (3000, 8) and str(X.dtype) == "bfloat16"
    assert np.array_equal(np.asarray(X, np.float32),
                          np.asarray(X2, np.float32))
    assert np.array_equal(np.asarray(y), np.asarray(y2))
    assert not np.array_equal(np.asarray(X, np.float32),
                              np.asarray(X3, np.float32))
    counts = np.bincount(np.asarray(y).astype(int), minlength=5)
    assert counts.min() > 0 and counts[0] > counts[4]   # skewed priors


# -- the reference ---------------------------------------------------------------

def _toy(n=300, d=3, K=4, seed=0):
    rng = np.random.default_rng(seed)
    X = (rng.normal(size=(n, d)) * [1.0, 2.0, 0.5] + [0.0, 1.0, -2.0]) \
        .astype(np.float32)
    y = (X @ rng.normal(size=(d, K)) + rng.gumbel(size=(n, K))).argmax(1) \
        .astype(np.float32)
    w = (rng.random(n) < 0.8).astype(np.float32) * rng.uniform(0.5, 2, n) \
        .astype(np.float32)
    return X, y, w


def _numpy_fit(X, y, w, reg, alpha, K, iters):
    """The documented iteration as a float64 loop over rows and classes."""
    X, w = X.astype(np.float64), w.astype(np.float64)
    n, d = X.shape
    mean = (X * w[:, None]).sum(0) / w.sum()
    std = np.sqrt((((X - mean) ** 2) * w[:, None]).sum(0) / w.sum())
    Xs = (X - mean) / std
    l1, l2, coef = reg * alpha, reg * (1 - alpha), 0.5 * (1 - 1 / K)
    A = np.zeros((d, d))
    for i in range(n):
        A += coef * w[i] * np.outer(Xs[i], Xs[i]) / w.sum()
    A += (l2 + 1e-6) * np.eye(d)
    B, b0 = np.zeros((d, K)), np.zeros(K)
    for _ in range(iters):
        G, g0 = l2 * B, np.zeros(K)
        for i in range(n):
            z = Xs[i] @ B + b0
            p = np.exp(z - z.max())
            p /= p.sum()
            p[int(y[i])] -= 1.0
            G += w[i] * np.outer(Xs[i], p) / w.sum()
            g0 += w[i] * p / w.sum()
        Bn = B - np.linalg.inv(A) @ G
        B = np.sign(Bn) * np.maximum(np.abs(Bn) - l1 / np.diag(A)[:, None],
                                     0.0)
        b0 = b0 - g0 / coef
    B = B / std[:, None]
    return B, b0 - (B * mean[:, None]).sum(0)


def test_reference_fit_is_the_documented_iteration():
    X, y, w = _toy()
    B, b0 = RS.fit(X, y, w, 0.05, 0.3, 4, max_iter=7, tol=0.0)
    nB, nb0 = _numpy_fit(X, y, w, 0.05, 0.3, 4, 7)
    # float32 at `highest` against float64: 7 steps on 300 rows
    assert np.abs(B - nB).max() < 2e-5 and np.abs(b0 - nb0).max() < 2e-5
    assert (B == 0).any()           # the threshold bit at this reg


def test_reference_fit_converges_to_the_objectives_optimum():
    """Run long, the iteration stops where the STATED objective's gradient
    vanishes (alpha 0: smooth, so the condition is plain): weighted mean
    log-loss + reg / 2 |B|^2 on the standardised scale, intercepts free."""
    X, y, w = _toy(seed=1)
    reg, K = 0.02, 4
    B, b0 = RS.fit(X, y, w, reg, 0.0, K, max_iter=4000, tol=1e-9)
    X64, w64 = X.astype(np.float64), w.astype(np.float64)
    mean = (X64 * w64[:, None]).sum(0) / w64.sum()
    std = np.sqrt((((X64 - mean) ** 2) * w64[:, None]).sum(0) / w64.sum())
    Bs = B * std[:, None]                      # standardised scale
    z = X64 @ B + b0
    P = np.exp(z - z.max(1, keepdims=True))
    P /= P.sum(1, keepdims=True)
    P[np.arange(len(y)), y.astype(int)] -= 1.0
    R = P * w64[:, None] / w64.sum()
    grad_B = ((X64 - mean) / std).T @ R + reg * Bs
    assert np.abs(grad_B).max() < 2e-5
    assert np.abs(R.sum(0)).max() < 2e-5       # intercepts unpenalised
    # and 50 steps are NOT there yet: which iterate is part of the answer
    B50, _ = RS.fit(X, y, w, reg, 0.0, K, max_iter=50, tol=1e-9)
    assert np.abs(B50 - B).max() > 1e-3


def test_scores_confusion_and_metrics_by_hand():
    import jax.numpy as jnp
    X = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0], [-1.0, 0.0],
                     [0.0, -1.0]], jnp.bfloat16)
    B = np.float32([[2.0, 0.0, -1.0], [0.0, 2.0, -1.0]])
    b0 = np.float32([0.0, 0.0, 0.5])
    y = np.float32([0, 1, 2, 2, 0])
    pred, loss = RS.scores(X, y, B, b0, chunk=2)     # overlapping chunks
    z = np.asarray(X, np.float32) @ B + b0
    assert list(pred) == list(z.argmax(1)) == [0, 1, 0, 2, 2]
    want = np.log(np.exp(z).sum(1)) - z[np.arange(5), y.astype(int)]
    assert np.abs(loss - want).max() < 1e-6
    w = np.float32([1.0, 2.0, 0.0, 1.0, 0.5])
    conf = RS.confusion_plain(pred, y, w, 3)
    assert conf.tolist() == [[1.0, 0.0, 0.5], [0.0, 2.0, 0.0],
                             [0.0, 0.0, 1.0]]
    m = RS.metrics_plain(conf)
    assert m["error"] == pytest.approx(0.5 / 4.5)
    # classes 0, 1, 2: precision 1, 1, 2/3; recall 2/3, 1, 1; shares
    # 1.5, 2, 1 of 4.5
    assert m["precision"] == pytest.approx((1.5 + 2 + 2 / 3) / 4.5)
    assert m["recall"] == pytest.approx((1.5 * 2 / 3 + 2 + 1) / 4.5)
    assert m["f1"] == pytest.approx((1.5 * 0.8 + 2 + 0.8) / 4.5)
    # a label or a prediction outside the classes counts nowhere
    assert RS.confusion_plain([0, 5], [3, 0], [1, 1], 3).sum() == 0


# -- a sweep whose answer was changed -----------------------------------------------

FIT = {"max_iter": 20, "tol": 1e-6, "fit_intercept": True,
       "standardize": True}


@pytest.fixture(scope="module")
def mlr_case():
    """A sweep's answer made by hand: fold coefficients from the plain
    reference itself, fold metrics their exact error."""
    import jax.numpy as jnp
    from benchmark import datagen_softmax as DS
    n, d, K = 6000, 6, 4
    X, y = DS.device_matrix(n, d, K, "bfloat16", 11, 3.0)
    yh = np.asarray(y)
    masks = np.ones((3, n), np.float32)
    for f in range(3):
        masks[f, f::3] = 0.0
    grids = [{"reg_param": 0.3, "elastic_net_param": 0.1},
             {"reg_param": 1e-3, "elastic_net_param": 0.1}]
    B = np.zeros((3, 2, d, K), np.float32)
    b0 = np.zeros((3, 2, K), np.float32)
    errs = np.zeros((2, 3))
    for j, g in enumerate(grids):
        for f in range(3):
            B[f, j], b0[f, j] = RS.fit(
                X.astype(jnp.float32), yh, masks[f], g["reg_param"],
                g["elastic_net_param"], K, **FIT)
            errs[j, f] = _error(X, yh, B[f, j], b0[f, j], masks[f], K)
    validated = [types.SimpleNamespace(
        grid=g, route="streamed", fold_metrics=list(errs[j]),
        mean_metric=float(errs[j].mean())) for j, g in enumerate(grids)]
    return types.SimpleNamespace(validated=validated), B, b0, masks, \
        grids, X, y, K


def _error(X, yh, B, b0, mask, K):
    pred, _ = RS.scores(X, yh, B, b0)
    return RS.metrics_plain(RS.confusion_plain(pred, yh, 1 - mask, K))[
        "error"]


def _answer(case, B=None, b0=None, best=None):
    best0, B0, b00, masks, grids, X, y, K = case
    return RS.mlr_sweep_answer(
        best or best0, [(B0 if B is None else B, b00 if b0 is None else b0)],
        masks, grids, X, y, n_classes=K, fit_params=FIT, reference_fold=1,
        reference_rows=6000, tol_metric=2e-4, tol_coefficients=1e-3,
        tol_logloss=1e-4)


def test_answer_passes_on_its_own_coefficients(mlr_case):
    out = _answer(mlr_case)
    top = min(mlr_case[0].validated, key=lambda v: v.mean_metric)
    assert out["grid"] == top.grid              # the LOWER error wins
    assert out["metric_worst_delta"] < 1e-12
    assert out["coefficient_delta"] < 1e-6 and out["logloss_delta"] < 1e-6
    assert len(out["folds"]) == 3 and out["reference_fold"] == 1
    assert out["bf16_coefficients_delta"] >= 0


def test_answer_fails_a_metric_that_is_not_its_coefficients(mlr_case):
    """One row in 2 000 predicted otherwise than reported is 5e-4."""
    off = [types.SimpleNamespace(**vars(v)) for v in mlr_case[0].validated]
    for v in off:
        v.fold_metrics = [m + 5e-4 for m in v.fold_metrics]
    with pytest.raises(reference.CheckFailure, match="exact error of its own"):
        _answer(mlr_case, best=types.SimpleNamespace(validated=off))


def test_answer_fails_a_sweep_that_dropped_its_intercepts(mlr_case):
    """Metrics reported with the intercepts, coefficients handed on without
    them: the skewed priors make that a different classifier."""
    _, B, b0, *_ = mlr_case
    with pytest.raises(reference.CheckFailure, match="exact error of its own"):
        _answer(mlr_case, b0=np.zeros_like(b0))


def test_answer_fails_coefficients_that_stopped_early(mlr_case):
    """Coefficients of 10 steps where 20 were asked (a sweep that retired
    its lanes too soon), with metrics honestly theirs."""
    import jax.numpy as jnp
    best, B, b0, masks, grids, X, y, K = mlr_case
    yh = np.asarray(y)
    j = 1
    Bs, b0s = B.copy(), b0.copy()
    for f in range(3):
        Bs[f, j], b0s[f, j] = RS.fit(
            X.astype(jnp.float32), yh, masks[f], grids[j]["reg_param"],
            grids[j]["elastic_net_param"], K, **dict(FIT, max_iter=10))
    early = types.SimpleNamespace(
        grid=grids[j], route="streamed", mean_metric=0.0,
        fold_metrics=[_error(X, yh, Bs[f, j], b0s[f, j], masks[f], K)
                      for f in range(3)])
    with pytest.raises(reference.CheckFailure, match="plain reference fit"):
        _answer(mlr_case, B=Bs, b0=b0s,
                best=types.SimpleNamespace(validated=[early]))


def test_answer_needs_the_sweeps_coefficients(mlr_case):
    best0, B, b0, masks, grids, X, y, K = mlr_case
    with pytest.raises(reference.CheckFailure, match="cannot be read"):
        RS.mlr_sweep_answer(
            best0, [], masks, grids, X, y, n_classes=K, fit_params=FIT,
            reference_fold=0, reference_rows=100, tol_metric=1.0,
            tol_coefficients=1.0, tol_logloss=1.0)
    with pytest.raises(reference.CheckFailure, match="of shape"):
        _answer(mlr_case, B=B[..., :3])         # a class axis too short


# -- the work model ---------------------------------------------------------------

def test_mlr_sweep_opcount_by_hand():
    flops, byts = opcount_softmax.mlr_sweep(
        rows=1000, cols=8, classes=5, padded_lane_passes=800,
        gram_passes=3, data_passes=53, itemsize=2)
    assert flops == 4 * 1000 * 8 * 5 * 800 + 2 * 1000 * 8 * 8 * 3
    assert byts == 53 * 1000 * 8 * 2
    # the cell as configured: 16 padded lanes x 50 iterations, 5 Grams
    flops, byts = opcount_softmax.mlr_sweep(
        rows=25_000_000, cols=64, classes=32, padded_lane_passes=800,
        gram_passes=5, data_passes=53, itemsize=2)
    assert flops == pytest.approx(1.64864e14)
    assert byts == pytest.approx(1.696e11)


def test_the_softmax_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark", "reference_softmax.py")) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+transmogrifai_tpu", src,
                         re.M)
