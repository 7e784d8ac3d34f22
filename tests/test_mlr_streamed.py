"""The streamed multinomial-LR sweep (ops/glm_sweep.sweep_mlr_streamed_rounds
through CrossValidation.validate) at a small size on the CPU: 4 096 x 8,
5 classes, 3 folds, 3 grid points, the row floor lowered as the benchmark's
rehearsal lowers it. Held to the plain float32 reference
(benchmark/reference_softmax.py, which imports nothing of the program), to
the exact error of its own coefficients, and to the vmapped fit_softmax
route. Every tolerance carries its reason.
"""
import copy
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import reference_softmax as RS  # noqa: E402
from transmogrifai_tpu.automl import CrossValidation  # noqa: E402
from transmogrifai_tpu.automl.tuning import validators as V  # noqa: E402
from transmogrifai_tpu.evaluators.evaluators import Evaluators  # noqa: E402
from transmogrifai_tpu.models.glm import OpLogisticRegression  # noqa: E402
from transmogrifai_tpu.ops import glm as G  # noqa: E402
from transmogrifai_tpu.ops import glm_sweep as GS  # noqa: E402
from transmogrifai_tpu.ops import metrics_ops as M  # noqa: E402

N, D, K, FOLDS = 4096, 8, 5, 3
REGS = (0.001, 0.01, 0.1)


def _data(seed=0, skip=None):
    """Standard-normal X and a label drawn from a softmax model with skewed
    priors; `skip` relabels one class away, so that max + 1 still counts it."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, D)).astype(np.float32)
    logits = X @ rng.normal(size=(D, K)).astype(np.float32) \
        - np.log(np.arange(K) + 1.0)
    y = (logits + rng.gumbel(size=logits.shape)).argmax(1)
    if skip is not None:
        y[y == skip] = skip - 1
    return X, y.astype(np.float32)


def _sweep(X, y, alpha, *, standardization, max_iter=50, tol=1e-6,
           min_rows=0, metric="error"):
    """validate() over the LR grid; returns (best, validator, the (B, b0)
    the streamed fit handed to its metric pass or None, the fold masks)."""
    seen = []
    orig = V.Validator._streamed_fit

    def spy(self, *a, **k):
        out = orig(self, *a, **k)
        seen.append((np.asarray(out[0]), np.asarray(out[1])))
        return out
    grids = [{"reg_param": r, "elastic_net_param": alpha} for r in REGS]
    cv = CrossValidation(getattr(Evaluators.MultiClassification, metric)(),
                         num_folds=FOLDS, seed=42)
    old = V.STREAMED_SWEEP_MIN_ROWS
    V.STREAMED_SWEEP_MIN_ROWS = min_rows
    V.Validator._streamed_fit = spy
    try:
        best = cv.validate(
            [(OpLogisticRegression(max_iter=max_iter, tol=tol,
                                   standardization=standardization), grids)],
            X, y, problem_type="multiclass")
    finally:
        V.Validator._streamed_fit = orig
        V.STREAMED_SWEEP_MIN_ROWS = old
    return best, cv, (seen[0] if seen else None), cv.fold_masks(y)


@pytest.mark.parametrize("alpha", [0.0, 0.1, 0.5])
def test_coefficients_match_the_plain_reference(alpha):
    """Every fold and grid point against the reference fitted on that
    fold's training rows, no standardization: the same iteration in
    float32 on both sides, so only the order of float32 sums differs
    (4 096-row reductions, 50 steps): 2e-4 against coefficients of order 1.
    """
    X, y = _data()
    best, cv, (B, b0), masks = _sweep(X, y, alpha, standardization=False)
    assert {v.route for v in best.validated} == {"streamed"}
    assert B.shape == (FOLDS, len(REGS), D, K)
    assert b0.shape == (FOLDS, len(REGS), K)
    for f in range(FOLDS):
        for g, reg in enumerate(REGS):
            rB, rb0 = RS.fit(X, y, masks[f], reg, alpha, K, max_iter=50,
                             tol=1e-6, standardize=False)
            assert np.abs(B[f, g] - rB).max() < 2e-4, (f, reg)
            assert np.abs(b0[f, g] - rb0).max() < 2e-4, (f, reg)
    if alpha == 0.5:    # the L1 part really thresholds at the largest reg
        assert (B[:, -1] == 0.0).any()
        assert not (B[:, 0] == 0.0).all()


def test_standardized_coefficients_match_the_reference():
    """Columns of unequal scale and mean 1. With standardization the route
    uses the column moments of ALL rows, once, where the reference (like
    fit_softmax) uses each fold's training rows': the moments differ by
    O(1 / sqrt(rows)) = 1.6 %, and so do the penalty's scale and the
    coefficients (0.019 at worst here). 3e-2 admits that and nothing
    coarser: a sweep that skipped the standardization is 2.5 away."""
    X, y = _data()
    X = X * np.linspace(0.5, 2.0, D).astype(np.float32) + 1.0
    best, cv, (B, b0), masks = _sweep(X, y, 0.1, standardization=True)
    worst = 0.0
    for f in range(FOLDS):
        for g, reg in enumerate(REGS):
            rB, rb0 = RS.fit(X, y, masks[f], reg, 0.1, K, max_iter=50,
                             tol=1e-6, standardize=True)
            worst = max(worst, np.abs(B[f, g] - rB).max(),
                        np.abs(b0[f, g] - rb0).max())
    assert worst < 3e-2


@pytest.mark.parametrize("metric", ["error", "precision", "recall", "f1"])
def test_fold_metrics_are_the_exact_ones_of_the_coefficients(metric):
    """The in-sweep metric is a count over float32 logits of the same
    coefficients the reference scores: only a row whose two largest logits
    tie to the last float32 bit may be predicted differently. One such row
    of a 1 365-row fold moves the error by 7e-4; none is expected: 1e-6 is
    float32 rounding of the ratio."""
    X, y = _data(seed=3)
    best, cv, (B, b0), masks = _sweep(X, y, 0.1, standardization=True,
                                      metric=metric)
    for g, v in enumerate(best.validated):
        for f in range(FOLDS):
            pred, _ = RS.scores(jnp.asarray(X), y, B[f, g], b0[f, g])
            exact = RS.metrics_plain(RS.confusion_plain(
                pred, y, 1.0 - masks[f], K))[metric]
            assert abs(v.fold_metrics[f] - exact) < 1e-6, (g, f)


def test_streamed_equals_the_vmapped_route():
    """max_iter 30 (the vmapped route's cap), tol 0, no standardization:
    both routes run fit_softmax's 30 steps from zero on the same weights,
    so the coefficients agree to float32 summation order (1e-4) and the
    fold errors to a row or two of 1 365 (2e-3)."""
    X, y = _data()
    best_s, _, (B, b0), masks = _sweep(X, y, 0.1, standardization=False,
                                       max_iter=30, tol=0.0)
    best_v, _, none, _ = _sweep(X, y, 0.1, standardization=False,
                                max_iter=30, tol=0.0, min_rows=10 ** 9)
    assert none is None
    assert {v.route for v in best_v.validated} == {"vmapped"}
    assert best_s.best_grid == best_v.best_grid
    for a, b in zip(best_s.validated, best_v.validated):
        assert np.abs(np.subtract(a.fold_metrics, b.fold_metrics)).max() \
            < 2e-3
    Y = np.eye(K, dtype=np.float32)[y.astype(int)]
    for f in range(FOLDS):
        vB, vb0 = G.fit_softmax(jnp.asarray(X), jnp.asarray(Y),
                                jnp.asarray(masks[f]), 0.01, 0.1,
                                max_iter=30, standardize=False)
        assert np.abs(B[f, 1] - np.asarray(vB)).max() < 1e-4
        assert np.abs(b0[f, 1] - np.asarray(vb0)).max() < 1e-4


def test_a_label_that_skips_a_class():
    """Classes are max + 1: a class no row carries keeps its column. Its
    intercept only ever falls; the route, the counts and the reference
    agree as with every class present."""
    X, y = _data(skip=3)
    assert 3.0 not in set(y) and V.label_classes(y) == K
    assert V.label_classes(jnp.asarray(y)) == K     # the device reduction
    best, cv, (B, b0), masks = _sweep(X, y, 0.1, standardization=False)
    assert {v.route for v in best.validated} == {"streamed"}
    assert cv.last_streamed_telemetry["classes"] == K
    assert np.isfinite(B).all() and np.isfinite(b0).all()
    assert (b0[:, :, 3] < b0[:, :, [0, 1, 2, 4]].min(axis=2)).all()
    rB, rb0 = RS.fit(X, y, masks[0], REGS[1], 0.1, K, max_iter=50,
                     tol=1e-6, standardize=False)
    assert np.abs(B[0, 1] - rB).max() < 2e-4
    pred, _ = RS.scores(jnp.asarray(X), y, B[0, 1], b0[0, 1])
    exact = RS.metrics_plain(RS.confusion_plain(pred, y, 1.0 - masks[0], K))
    assert abs(best.validated[1].fold_metrics[0] - exact["error"]) < 1e-6


def test_round_state_resumes_bit_identically():
    X, y = _data(seed=5)
    cv = CrossValidation(Evaluators.MultiClassification.error(),
                         num_folds=FOLDS, seed=42)
    args = (jnp.asarray(X), jnp.asarray(y), jnp.ones(N, jnp.float32),
            cv.device_fold_masks(y), np.float32(REGS),
            np.full(3, 0.1, np.float32))
    kw = dict(n_classes=K, max_iter=12, tol=1e-6, round_iters=2)
    snaps = []
    B, b0, info = GS.sweep_mlr_streamed_rounds(
        *args, on_round=lambda st: snaps.append(copy.deepcopy(st)), **kw)
    assert len(snaps) == info["glm_rounds"] == 6
    B2, b02, info2 = GS.sweep_mlr_streamed_rounds(
        *args, state=copy.deepcopy(snaps[2]), **kw)
    assert np.array_equal(B, B2) and np.array_equal(b0, b02)
    assert info2["glm_rounds"] == 6
    assert info2["padded_lane_passes"] == info["padded_lane_passes"]


def test_validator_round_checkpoint_resumes(monkeypatch, tmp_path):
    """Killed after two rounds, the sweep resumes from the round file and
    reports bit-identical fold metrics."""
    monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
    X, y = _data(seed=6)
    grids = [{"reg_param": r, "elastic_net_param": 0.1} for r in REGS]
    orig = GS.sweep_mlr_streamed_rounds
    resumed = []

    class Boom(RuntimeError):
        pass

    def dying(*a, **k):
        inner = k["on_round"]

        def bomb(st):
            inner(st)
            if st["rounds"] >= 2:
                raise Boom()
        return orig(*a, **dict(k, on_round=bomb))

    def resuming(*a, **k):
        resumed.append(copy.deepcopy(k.get("state")))
        return orig(*a, **k)

    def run(path, fn):
        monkeypatch.setattr(GS, "sweep_mlr_streamed_rounds", fn)
        cv = CrossValidation(Evaluators.MultiClassification.error(),
                             num_folds=FOLDS, seed=42)
        cv.checkpoint_path = path
        return cv.validate([(OpLogisticRegression(max_iter=20), grids)],
                           X, y, problem_type="multiclass")
    with pytest.raises(Boom):
        run(str(tmp_path / "ck.jsonl"), dying)
    again = run(str(tmp_path / "ck.jsonl"), resuming)
    clean = run(None, orig)
    assert resumed[0] is not None and resumed[0]["rounds"] == 2
    assert resumed[0]["B"].shape == (FOLDS * len(REGS), D, K)
    for a, b in zip(again.validated, clean.validated):
        assert a.fold_metrics == b.fold_metrics


def test_retirement_and_telemetry():
    """A lane stops at its own delta <= tol and keeps its coefficients;
    the counters say what ran: lane passes, padded passes of the bucket
    ladder, data passes (iterations + the Gram pass + two for the
    moments), one Gram per fold."""
    X, y = _data(seed=7)
    cv = CrossValidation(Evaluators.MultiClassification.error(),
                         num_folds=FOLDS, seed=42)
    args = (jnp.asarray(X), jnp.asarray(y), jnp.ones(N, jnp.float32),
            cv.device_fold_masks(y))
    regs = np.float32([0.01, 30.0])      # the second shrinks B to 0 at once
    B, b0, info = GS.sweep_mlr_streamed_rounds(
        *args, regs, np.float32([0.9, 0.9]), n_classes=K, max_iter=40,
        tol=1e-3, round_iters=5)
    assert info["kernel"] == "mlr_rounds" and info["classes"] == K
    assert info["gram_passes"] == FOLDS
    assert info["lanes_total"] == 2 * FOLDS
    assert info["lanes_retired"] + info["lanes_at_cap"] == 2 * FOLDS
    assert info["lanes_retired"] >= FOLDS           # the heavy-reg lanes
    assert info["active_per_round"][0] == 2 * FOLDS
    assert info["active_per_round"][-1] < 2 * FOLDS
    assert info["bucket_sizes"][0] == GS.bucket_lanes(2 * FOLDS)
    iters = sum(info["iters_per_round"])
    assert info["data_passes"] == iters + 3
    assert info["lane_passes"] == sum(
        a * i for a, i in zip(info["active_per_round"],
                              info["iters_per_round"]))
    assert info["padded_lane_passes"] == sum(
        b * i for b, i in zip(info["bucket_sizes"],
                              info["iters_per_round"]))
    assert (B[:, 1] == 0.0).all()                   # thresholded away
    # a retired lane is frozen: a longer budget leaves it where it stopped
    B2, _, _ = GS.sweep_mlr_streamed_rounds(
        *args, regs, np.float32([0.9, 0.9]), n_classes=K, max_iter=60,
        tol=1e-3, round_iters=5)
    assert np.array_equal(B[:, 1], B2[:, 1])


def test_route_choice(monkeypatch):
    """Multiclass takes the streamed route where binary does: at the row
    floor, on one device, for an estimator that declares it."""
    X, y = _data()
    est = OpLogisticRegression()
    grids = [{"reg_param": 0.1}]
    cv = CrossValidation(Evaluators.MultiClassification.error(),
                         num_folds=FOLDS)
    assert not cv._streamable(est, grids, "multiclass", X, FOLDS, K)
    monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", N)
    assert cv._streamable(est, grids, "multiclass", X, FOLDS, K)
    assert cv._streamable(est, grids, "binary", X, FOLDS)
    monkeypatch.setattr(OpLogisticRegression, "streamed_multiclass_loss",
                        None)
    assert not cv._streamable(est, grids, "multiclass", X, FOLDS, K)
    monkeypatch.undo()
    monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", N)
    cv.mesh = object()          # any mesh keeps the vmapped route
    assert not cv._streamable(est, grids, "multiclass", X, FOLDS, K)
    assert not GS.streamed_mlr_route_ok(GS.TRI_MAX_D + 1, 15, K, 12e9)
    assert not GS.streamed_mlr_route_ok(64, 15, 10 ** 6, 12e9)
    assert GS.streamed_mlr_route_ok(64, 15, 32, 12e9)


def test_confusion_lanes_equals_multiclass_metrics():
    """The lane-batched count is multiclass_metrics' confusion on the same
    predictions, lane by lane, under non-unit weights (float32 sums of
    2 000 weights: 1e-4), and the metrics from it are the same numbers."""
    rng = np.random.default_rng(1)
    n, lanes = 2000, 4
    pred = rng.integers(0, K, size=(lanes, n))
    y = rng.integers(0, K, size=n)
    w = rng.uniform(0.0, 2.0, n).astype(np.float32)
    conf = np.asarray(M.confusion_lanes(jnp.asarray(pred), jnp.asarray(y),
                                        jnp.asarray(w), K))
    for g in range(lanes):
        assert np.abs(conf[g] - RS.confusion_plain(pred[g], y, w, K)).max() \
            < 1e-4 * w.sum() / K
        got = M.multiclass_metrics_from_confusion(jnp.asarray(conf[g]))
        ref = M.multiclass_metrics(jnp.asarray(pred[g]), jnp.asarray(y), K,
                                   jnp.asarray(w))
        for name in ("precision", "recall", "f1", "error"):
            assert abs(float(getattr(got, name))
                       - float(getattr(ref, name))) < 1e-6


def test_blocks_cover_every_row_once():
    """_mlr_blocks: the last block starts early; `fresh` gives each row to
    exactly one block."""
    n, c = 1000, 384
    XT = jnp.arange(n, dtype=jnp.float32)[None, :]
    nb, take = GS._mlr_blocks(n, c, XT, jnp.ones(n))
    assert nb == 3
    seen = np.zeros(n)
    for i in range(nb):
        xT, fresh, ones = take(i)
        np.add.at(seen, np.asarray(xT[0]).astype(int), np.asarray(fresh))
    assert (seen == 1).all()
