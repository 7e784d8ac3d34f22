"""Convergence-aware GLM sweep (docs/performance.md "Convergence-aware GLM
sweep"): the squared-loss sufficient-statistics Gram fast path must agree
with the per-lane ops/glm solvers (ridge closed form AND elastic-net
proximal Newton), the IRLS retirement round driver must freeze lanes at
coefficients matching run-to-max_iter within tol, the bucket ladder must
reuse compiled round programs, and the sharded round driver must match the
single-device one on a CPU mesh."""
import copy

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.automl.tuning import validators as V
from transmogrifai_tpu.automl.tuning.validators import CrossValidation
from transmogrifai_tpu.evaluators.evaluators import Evaluators
from transmogrifai_tpu.models.glm import (
    OpLinearRegression, OpLinearSVC, OpLogisticRegression,
)
from transmogrifai_tpu.ops import glm_sweep as GS
from transmogrifai_tpu.ops.glm import fit_linear, fit_linear_svc, fit_logistic
from transmogrifai_tpu.ops.glm_sweep import (
    bucket_lanes,
    sweep_glm_round,
    sweep_glm_squared_gram,
    sweep_glm_streamed_rounds,
)


def _binary(n=2000, d=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    beta = np.linspace(1.5, -1.5, d)
    p = 1 / (1 + np.exp(-(X @ beta + 0.3)))
    y = (rng.uniform(size=n) < p).astype(np.float32)
    return X, y


def _regression(n=2000, d=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    beta = np.linspace(1.0, -1.0, d)
    y = (X @ beta + 0.3 + 0.2 * rng.normal(size=n)).astype(np.float32)
    return X, y


def _multiclass(n=1500, d=5, k=3, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    logits = X @ rng.normal(size=(d, k)).astype(np.float32)
    y = (logits + rng.gumbel(size=logits.shape)).argmax(1)
    return X, y.astype(np.float32)


def _masks(y, folds=2, seed=1):
    rng = np.random.default_rng(seed)
    fold = rng.integers(0, folds, size=len(y))
    return np.stack([(fold != k).astype(np.float32) for k in range(folds)])


def _assert_lanes_match(B, b0, fit_lane, atol):
    """Every (fold, grid) lane of a sweep's B [F, G, d], b0 [F, G] against
    `fit_lane(f, g)`, the per-lane solver of ops/glm.py on that fold's
    weights."""
    for f in range(B.shape[0]):
        for g in range(B.shape[1]):
            beta_ref, b0_ref = fit_lane(f, g)
            assert np.allclose(B[f, g], np.asarray(beta_ref),
                               atol=atol), (f, g)
            assert abs(float(b0[f, g]) - float(b0_ref)) < atol, (f, g)


class TestGramFastPath:
    """(a) Gram fast path vs ops/glm per-lane solvers for ridge and
    elastic-net squared loss."""

    @pytest.mark.parametrize("standardize", [False, True])
    def test_ridge_and_elastic_net_match_per_lane(self, standardize):
        X, y = _regression()
        masks = _masks(y, folds=3)
        w = np.ones_like(y)
        regs = np.array([0.001, 0.05, 0.5], np.float32)
        alphas = np.array([0.0, 0.5, 0.25], np.float32)
        B, b0, *_ = sweep_glm_squared_gram(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), jnp.asarray(regs), jnp.asarray(alphas),
            50, 1e-6, standardize=standardize)
        B = np.asarray(B)
        b0 = np.asarray(b0)
        # global-weight standardization differs from the per-lane solver's
        # fold-weight standardization at O(1/sqrt(n)) only
        atol = 0.05 if standardize else 3e-3
        for f in range(masks.shape[0]):
            for g in range(len(regs)):
                beta_ref, b0_ref = fit_linear(
                    jnp.asarray(X), jnp.asarray(y),
                    jnp.asarray(masks[f] * w), jnp.asarray(regs[g]),
                    jnp.asarray(alphas[g]), max_iter=50,
                    standardize=standardize)
                assert np.allclose(B[f, g], np.asarray(beta_ref),
                                   atol=atol), (f, g)
                assert abs(b0[f, g] - float(b0_ref)) < atol, (f, g)

    def test_no_intercept(self):
        X, y = _regression(n=1500, d=5, seed=3)
        masks = _masks(y, folds=2, seed=2)
        w = np.ones_like(y)
        B, b0, *_ = sweep_glm_squared_gram(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), jnp.asarray([0.01], np.float32),
            jnp.asarray([0.25], np.float32), 50, 1e-6,
            fit_intercept=False, standardize=False)
        assert np.allclose(np.asarray(b0), 0.0)
        beta_ref, _ = fit_linear(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(masks[0] * w),
            jnp.asarray(0.01), jnp.asarray(0.25), max_iter=50,
            fit_intercept=False, standardize=False)
        assert np.allclose(np.asarray(B)[0, 0], np.asarray(beta_ref),
                           atol=3e-3)

    def test_nonuniform_weights(self):
        X, y = _regression(n=1800, d=5, seed=7)
        rng = np.random.default_rng(11)
        w = rng.uniform(0.25, 3.0, size=len(y)).astype(np.float32)
        masks = _masks(y, folds=2, seed=5)
        B, b0, *_ = sweep_glm_squared_gram(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), jnp.asarray([0.05], np.float32),
            jnp.asarray([0.5], np.float32), 50, 1e-6, standardize=False)
        beta_ref, b0_ref = fit_linear(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(masks[1] * w),
            jnp.asarray(0.05), jnp.asarray(0.5), max_iter=50,
            standardize=False)
        assert np.allclose(np.asarray(B)[1, 0], np.asarray(beta_ref),
                           atol=3e-3)
        assert abs(float(b0[1, 0]) - float(b0_ref)) < 3e-3

    def test_single_pass_telemetry(self, monkeypatch):
        """Acceptance gate: a squared-loss sweep through the validator
        executes exactly ONE streaming pass over X for the whole
        fold x grid, asserted via the pass-counter telemetry AND by
        counting Gram-kernel invocations."""
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        calls = []
        orig = GS.sweep_glm_squared_gram

        def counting(*a, **k):
            calls.append(1)
            return orig(*a, **k)

        monkeypatch.setattr(GS, "sweep_glm_squared_gram", counting)
        X, y = _regression(n=1500)
        val = CrossValidation(Evaluators.Regression.rmse(), num_folds=3,
                              seed=3)
        best = val.validate(
            [(OpLinearRegression(max_iter=25, standardization=False),
              [{"reg_param": 0.001}, {"reg_param": 0.05},
               {"reg_param": 0.5, "elastic_net_param": 0.5}])],
            X, y, problem_type="regression")
        assert np.isfinite(best.best_metric)
        info = val.last_streamed_telemetry
        assert info is not None and info["kernel"] == "gram"
        assert info["data_passes"] == 1
        assert info["glm_rounds"] == 1
        assert info["lanes_retired"] == info["lanes_total"] == 9
        assert len(calls) == 1  # one kernel dispatch = one X pass
        assert best.validated[0].route == "streamed"


class TestRoundDriver:
    """(b) retirement: a retired lane's coefficients match letting it keep
    iterating, within tol; active-lane counts shrink monotonically."""

    def test_matches_per_lane_logistic(self):
        X, y = _binary()
        masks = _masks(y, folds=2)
        w = np.ones_like(y)
        regs = np.array([0.005, 0.05, 0.3], np.float32)
        alphas = np.array([0.0, 0.25, 0.5], np.float32)
        Br, b0r, info = sweep_glm_streamed_rounds(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), regs, alphas, loss="logistic",
            max_iter=30, tol=1e-6, standardize=False, round_iters=3)
        _assert_lanes_match(
            Br, b0r, lambda f, g: fit_logistic(
                jnp.asarray(X), jnp.asarray(y), jnp.asarray(masks[f] * w),
                jnp.asarray(regs[g]), jnp.asarray(alphas[g]), max_iter=30,
                tol=1e-6, standardize=False),
            atol=5e-3)
        assert info["lanes_retired"] == info["lanes_total"] == 6
        assert info["data_passes"] == sum(info["iters_per_round"])

    def test_retired_lane_matches_run_to_max_iter(self):
        """Once a lane retires (K=1 rounds force the earliest possible
        retirement), its frozen coefficients match the same lane iterated
        in one uninterrupted round to max_iter — within tol-scale."""
        X, y = _binary(n=1800, d=5, seed=4)
        masks = _masks(y, folds=2, seed=3)
        w = np.ones_like(y)
        regs = np.array([0.002, 0.1, 0.8], np.float32)
        alphas = np.zeros(3, np.float32)
        kw = dict(loss="logistic", max_iter=40, tol=1e-6,
                  standardize=False, warm_start=False)
        B1, b01, i1 = sweep_glm_streamed_rounds(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), regs, alphas, round_iters=1, **kw)
        B2, b02, i2 = sweep_glm_streamed_rounds(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), regs, alphas, round_iters=40, **kw)
        assert i2["glm_rounds"] == 1
        assert i1["glm_rounds"] > 1
        assert np.allclose(B1, B2, atol=2e-3)
        assert np.allclose(b01, b02, atol=2e-3)
        # monotone shrink of active lanes across the retirement rounds
        act = i1["active_per_round"]
        assert all(a >= b for a, b in zip(act, act[1:]))
        # retirement saved lane-passes vs lock-step-to-the-slowest
        assert i1["lane_passes"] <= i1["lanes_total"] * max(
            sum(i1["iters_per_round"]), 1)

    def test_squared_hinge_matches_per_lane_svc(self):
        X, y = _binary(n=2200, d=6, seed=9)
        masks = _masks(y, folds=2, seed=8)
        w = np.ones_like(y)
        regs = np.array([0.01, 0.2], np.float32)
        B, b0, info = sweep_glm_streamed_rounds(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), regs, np.zeros(2, np.float32),
            loss="squared_hinge", max_iter=30, tol=1e-6,
            standardize=False, round_iters=4)
        for f in range(2):
            for g in range(2):
                beta_ref, b0_ref = fit_linear_svc(
                    jnp.asarray(X), jnp.asarray(y),
                    jnp.asarray(masks[f] * w), jnp.asarray(regs[g]),
                    max_iter=30, standardize=False)
                assert np.allclose(B[f, g], np.asarray(beta_ref),
                                   atol=5e-3), (f, g)
                assert abs(float(b0[f, g]) - float(b0_ref)) < 5e-3

    def test_warm_start_parity_and_telemetry(self):
        """Pathwise warm starts change the iteration path, never the
        answer (convex losses): seeded and unseeded drivers agree within
        tol-scale; the seed round fits only folds x 1 lanes."""
        X, y = _binary(n=1600, d=5, seed=6)
        masks = _masks(y, folds=2, seed=7)
        w = np.ones_like(y)
        regs = np.array([0.001, 0.03, 0.5], np.float32)
        alphas = np.zeros(3, np.float32)
        kw = dict(loss="logistic", max_iter=40, tol=1e-6,
                  standardize=False, round_iters=4)
        Bw, b0w, iw = sweep_glm_streamed_rounds(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), regs, alphas, warm_start=True, **kw)
        Bc, b0c, ic = sweep_glm_streamed_rounds(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), regs, alphas, warm_start=False, **kw)
        assert iw["warm_start"] and not ic["warm_start"]
        assert iw["active_per_round"][0] == masks.shape[0]  # seed lanes
        assert np.allclose(Bw, Bc, atol=5e-3)
        assert np.allclose(b0w, b0c, atol=5e-3)

    def test_max_iter_caps_every_lane(self):
        X, y = _binary(n=1200, d=4, seed=2)
        masks = _masks(y, folds=2, seed=2)
        w = np.ones_like(y)
        B, b0, info = sweep_glm_streamed_rounds(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), np.asarray([0.01], np.float32),
            np.asarray([0.0], np.float32), loss="logistic", max_iter=1,
            tol=1e-9, standardize=False, round_iters=5)
        assert info["data_passes"] == 1  # one round of exactly one iter
        assert info["lanes_at_cap"] == info["lanes_total"]
        assert np.isfinite(B).all()

    def test_standardize_matches_per_lane(self):
        """The rounds standardize once with the global weights, the
        per-lane solver with its fold's: means and stds differ at
        O(1/sqrt(n)), and the coefficients by that times the penalty."""
        X, y = _binary(n=2400, d=5, seed=12)
        X = X * 2.0 + 1.0
        masks = _masks(y, folds=2, seed=4)
        w = np.ones_like(y)
        Br, b0r, _ = sweep_glm_streamed_rounds(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), np.asarray([0.02], np.float32),
            np.asarray([0.0], np.float32), loss="logistic", max_iter=30,
            tol=1e-6, standardize=True, round_iters=4)
        _assert_lanes_match(
            Br, b0r, lambda f, g: fit_logistic(
                jnp.asarray(X), jnp.asarray(y), jnp.asarray(masks[f] * w),
                jnp.asarray(0.02), jnp.asarray(0.0), max_iter=30, tol=1e-6,
                standardize=True),
            atol=5e-3)


class TestBucketLadder:
    """(c) compaction pads to a power-of-two ladder and reuses compiled
    round programs across rounds and sweeps."""

    def test_bucket_lanes_ladder(self):
        assert bucket_lanes(1) == GS._BUCKET_MIN
        assert bucket_lanes(GS._BUCKET_MIN) == GS._BUCKET_MIN
        assert bucket_lanes(9) == 16
        assert bucket_lanes(17) == 32
        assert bucket_lanes(240) == 256

    def test_round_program_cache_reuse(self):
        """Two sweeps with different lane counts in the SAME bucket (and
        every round of each) share one compiled round program, asserted
        via the jit cache size."""
        if not hasattr(sweep_glm_round, "_cache_size"):
            pytest.skip("jit cache introspection unavailable")
        X, y = _binary(n=900, d=4, seed=5)
        masks = _masks(y, folds=2, seed=6)
        w = np.ones_like(y)

        def run(n_grid):
            regs = np.linspace(0.01, 0.5, n_grid).astype(np.float32)
            return sweep_glm_streamed_rounds(
                jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
                jnp.asarray(masks), regs, np.zeros(n_grid, np.float32),
                loss="logistic", max_iter=25, tol=1e-6,
                standardize=False, round_iters=2, warm_start=False)

        before = sweep_glm_round._cache_size()
        _, _, i1 = run(5)   # 10 lanes -> bucket 16, several rounds
        after_first = sweep_glm_round._cache_size()
        assert after_first - before <= 2  # ladder may shrink 16 -> 8
        _, _, i2 = run(8)   # 16 lanes -> same 16-bucket programs
        assert sweep_glm_round._cache_size() == after_first
        for info in (i1, i2):
            assert all(b in (8, 16) for b in info["bucket_sizes"])
            assert all(b & (b - 1) == 0 for b in info["bucket_sizes"])

    def test_traced_tol_max_iter_share_executable(self):
        """tol/max_iter are traced scalars of the round program —
        retuning them must NOT recompile."""
        if not hasattr(sweep_glm_round, "_cache_size"):
            pytest.skip("jit cache introspection unavailable")
        X, y = _binary(n=700, d=4, seed=8)
        masks = _masks(y, folds=2, seed=9)
        w = np.ones_like(y)

        def run(mi, tl):
            return sweep_glm_streamed_rounds(
                jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
                jnp.asarray(masks), np.asarray([0.05], np.float32),
                np.asarray([0.0], np.float32), loss="logistic",
                max_iter=mi, tol=tl, standardize=False)

        run(10, 1e-5)
        size_after_first = sweep_glm_round._cache_size()
        run(17, 1e-4)
        run(23, 1e-7)
        assert sweep_glm_round._cache_size() == size_after_first


class TestRoundCheckpoint:
    """Round-granular persistence: resume at the last retirement boundary
    reproduces the uninterrupted run bit for bit."""

    def test_driver_state_resume_bit_identical(self):
        X, y = _binary(n=1400, d=5, seed=10)
        masks = _masks(y, folds=2, seed=11)
        w = np.ones_like(y)
        regs = np.array([0.005, 0.08, 0.4], np.float32)
        alphas = np.zeros(3, np.float32)
        kw = dict(loss="logistic", max_iter=30, tol=1e-6,
                  standardize=False, round_iters=2, warm_start=True)
        snapshots = []
        B_full, b0_full, info_full = sweep_glm_streamed_rounds(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), regs, alphas,
            on_round=lambda st: snapshots.append(copy.deepcopy(st)), **kw)
        assert len(snapshots) == info_full["glm_rounds"]
        # resume from the state after the SECOND round boundary
        B_res, b0_res, info_res = sweep_glm_streamed_rounds(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), regs, alphas,
            state=copy.deepcopy(snapshots[1]), **kw)
        assert np.array_equal(B_full, B_res)
        assert np.array_equal(b0_full, b0_res)
        assert info_res["glm_rounds"] == info_full["glm_rounds"]

    @pytest.mark.parametrize("family", ["binary", "multinomial"])
    def test_interrupted_after_round_1_reports_the_same_info(self, family):
        """Both host drivers run the ONE retirement loop: killed at the
        first round boundary and resumed from that state, a sweep hands
        back the uninterrupted sweep's coefficients and its `info`, key
        by key."""
        if family == "binary":
            X, y = _binary(n=1400, d=5, seed=10)
            driver, kw = sweep_glm_streamed_rounds, dict(
                loss="logistic", warm_start=True)
        else:
            X, y = _multiclass(seed=10)
            driver, kw = GS.sweep_mlr_streamed_rounds, dict(n_classes=3)
        args = (jnp.asarray(X), jnp.asarray(y),
                jnp.ones(len(y), jnp.float32),
                jnp.asarray(_masks(y, folds=2, seed=11)),
                np.array([0.005, 0.08, 0.4], np.float32),
                np.full(3, 0.1, np.float32))
        kw.update(max_iter=12, tol=1e-6, standardize=True, round_iters=2)
        B_full, b0_full, info_full = driver(*args, **kw)
        assert info_full["glm_rounds"] > 2

        class Killed(RuntimeError):
            pass
        saved = []

        def kill(st):
            saved.append(copy.deepcopy(st))
            raise Killed()
        with pytest.raises(Killed):
            driver(*args, on_round=kill, **kw)
        assert saved[0]["rounds"] == 1
        B_res, b0_res, info_res = driver(*args, state=saved[0], **kw)
        assert np.array_equal(B_full, B_res)
        assert np.array_equal(b0_full, b0_res)
        assert info_res.keys() == info_full.keys()
        for key in info_full:
            assert info_res[key] == info_full[key], key

    def test_roundcheckpoint_file_roundtrip(self, tmp_path):
        from transmogrifai_tpu.automl.tuning.checkpoint import (
            RoundCheckpoint)
        rc = RoundCheckpoint(str(tmp_path / "sweep.jsonl.glm_rounds.npz"))
        st = GS._new_round_state(6, 4)
        st["B"][:] = 1.5
        st["rounds"] = 2
        st["active_per_round"] = [6, 3]
        st["warmed"] = True
        rc.save("k1", st)
        assert rc.load("other-key") is None  # mismatched key ignored
        got = rc.load("k1")
        assert got is not None
        assert np.array_equal(got["B"], st["B"])
        assert got["rounds"] == 2 and got["warmed"] is True
        assert got["active_per_round"] == [6, 3]
        rc.clear()
        assert rc.load("k1") is None

    def test_validator_round_checkpoint_resume(self, monkeypatch, tmp_path):
        """A streamed sweep killed mid-rounds resumes at the last
        retirement boundary: the resumed run executes FEWER rounds and
        lands on the same winner as a clean run."""
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        X, y = _binary(n=1400)
        ev = Evaluators.BinaryClassification.au_pr()
        grids = [{"reg_param": 0.002}, {"reg_param": 0.05},
                 {"reg_param": 0.4}]
        est = lambda: OpLogisticRegression(max_iter=30)

        class _Boom(RuntimeError):
            pass

        orig = GS.sweep_glm_streamed_rounds
        seen_states = []

        def dying(*a, **k):
            inner = k.get("on_round")

            def bomb(st):
                if inner is not None:
                    inner(st)
                seen_states.append(copy.deepcopy(st))
                if st["rounds"] >= 2:
                    raise _Boom()
            k["on_round"] = bomb
            return orig(*a, **k)

        monkeypatch.setattr(GS, "sweep_glm_streamed_rounds", dying)
        val = CrossValidation(ev, num_folds=2, seed=5)
        val.checkpoint_path = str(tmp_path / "ck.jsonl")
        with pytest.raises(_Boom):
            val.validate([(est(), [dict(g) for g in grids])], X, y)
        interrupted_rounds = seen_states[-1]["rounds"]
        # resume: the round file must exist and seed the next attempt
        resumed = []

        def resuming(*a, **k):
            # snapshot NOW: the driver mutates the state dict in place
            resumed.append(copy.deepcopy(k.get("state")))
            return orig(*a, **k)

        monkeypatch.setattr(GS, "sweep_glm_streamed_rounds", resuming)
        val2 = CrossValidation(ev, num_folds=2, seed=5)
        val2.checkpoint_path = val.checkpoint_path
        b2 = val2.validate([(est(), [dict(g) for g in grids])], X, y)
        assert resumed and resumed[0] is not None
        assert resumed[0]["rounds"] == interrupted_rounds
        # clean reference run
        val3 = CrossValidation(ev, num_folds=2, seed=5)
        b3 = val3.validate([(est(), [dict(g) for g in grids])], X, y)
        assert b2.best_grid == b3.best_grid
        for a, b in zip(b2.validated, b3.validated):
            assert np.allclose(a.fold_metrics, b.fold_metrics, atol=5e-3)


class TestShardedRounds:
    """(d) sharded round driver / Gram path match single-device on a
    2-device CPU mesh."""

    def _mesh(self):
        from transmogrifai_tpu.parallel.mesh import make_mesh
        return make_mesh(n_batch=2, n_model=1)

    def _put(self, mesh, X, y, w, masks):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        row = NamedSharding(mesh, P("batch", None))
        vec = NamedSharding(mesh, P("batch"))
        mrow = NamedSharding(mesh, P(None, "batch"))
        return (jax.device_put(X, row), jax.device_put(y, vec),
                jax.device_put(w, vec), jax.device_put(masks, mrow))

    def test_sharded_round_driver_matches_single(self):
        mesh = self._mesh()
        n = 2048  # multiple of the 2-way batch axis
        X, y = _binary(n=n, d=5, seed=14)
        w = np.ones_like(y)
        masks = _masks(y, folds=2, seed=13)
        regs = np.array([0.01, 0.2], np.float32)
        alphas = np.array([0.0, 0.5], np.float32)
        kw = dict(loss="logistic", max_iter=25, tol=1e-6,
                  standardize=True, round_iters=3)
        B1, b01, i1 = sweep_glm_streamed_rounds(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), regs, alphas, **kw)
        Xd, yd, wd, md = self._put(mesh, X, y, w, masks)
        B2, b02, i2 = sweep_glm_streamed_rounds(
            Xd, yd, wd, md, regs, alphas, mesh=mesh, **kw)
        assert np.allclose(B1, B2, atol=3e-3)
        assert np.allclose(b01, b02, atol=3e-3)
        assert i1["lanes_retired"] == i2["lanes_retired"]

    def test_sharded_gram_matches_single(self):
        import jax
        mesh = self._mesh()
        X, y = _regression(n=2048, d=5, seed=15)
        X = X * 2.0 + 3.0  # exercise the psum'd standardization too
        w = np.ones_like(y)
        masks = _masks(y, folds=2, seed=15)
        regs = np.array([0.01, 0.3], np.float32)
        alphas = np.array([0.0, 0.5], np.float32)
        B1, b01, *_ = sweep_glm_squared_gram(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), jnp.asarray(regs), jnp.asarray(alphas),
            50, 1e-6, standardize=True)
        Xd, yd, wd, md = self._put(mesh, X, y, w, masks)
        B2, b02, *_ = GS.sweep_glm_squared_gram_sharded(
            mesh, Xd, yd, wd, md, jnp.asarray(regs), jnp.asarray(alphas),
            50, 1e-6, standardize=True)
        assert np.allclose(np.asarray(B1), np.asarray(B2), atol=3e-3)
        assert np.allclose(np.asarray(b01), np.asarray(b02), atol=3e-3)

    def test_validator_mesh_routes_match(self, monkeypatch):
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        mesh = self._mesh()
        X, y = _regression(n=1000, d=5, seed=16)  # odd n: pads
        ev = Evaluators.Regression.rmse()
        grids = [{"reg_param": 0.001}, {"reg_param": 0.1}]
        vm = CrossValidation(ev, num_folds=2, seed=3, mesh=mesh)
        bm = vm.validate([(OpLinearRegression(max_iter=25), grids)], X, y,
                         problem_type="regression")
        assert vm.last_streamed_telemetry["kernel"] == "gram"
        vp = CrossValidation(ev, num_folds=2, seed=3)
        bp = vp.validate([(OpLinearRegression(max_iter=25), grids)], X, y,
                         problem_type="regression")
        assert bm.best_grid == bp.best_grid
        for a, b in zip(bp.validated, bm.validated):
            assert np.allclose(a.fold_metrics, b.fold_metrics, atol=5e-3)


class TestValidatorRouting:
    def test_logistic_routes_rounds_and_matches_vmapped(self, monkeypatch):
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        X, y = _binary(n=1800)
        ev = Evaluators.BinaryClassification.au_pr()
        grids = [{"reg_param": 0.001}, {"reg_param": 0.05},
                 {"reg_param": 0.5}]
        vs = CrossValidation(ev, num_folds=3, seed=7)
        bs = vs.validate([(OpLogisticRegression(max_iter=20),
                           [dict(g) for g in grids])], X, y)
        info = vs.last_streamed_telemetry
        assert info["kernel"] == "rounds"
        assert info["lanes_total"] == 9
        assert sum(info["iters_per_round"]) == info["data_passes"]
        # monotone active-lane shrink over the post-seed rounds
        act = info["active_per_round"][1:] if info.get("warm_start") \
            else info["active_per_round"]
        assert all(a >= b for a, b in zip(act, act[1:]))
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 10**12)
        vv = CrossValidation(ev, num_folds=3, seed=7)
        bv = vv.validate([(OpLogisticRegression(max_iter=20),
                           [dict(g) for g in grids])], X, y)
        assert bs.best_grid == bv.best_grid
        for a, b in zip(bv.validated, bs.validated):
            assert np.allclose(a.fold_metrics, b.fold_metrics, atol=5e-3)

    @pytest.mark.parametrize("est,evaluator,problem_type,data,kernel", [
        (OpLogisticRegression, Evaluators.BinaryClassification.au_pr,
         "binary", _binary, "rounds"),
        (OpLinearSVC, Evaluators.BinaryClassification.au_roc,
         "binary", _binary, "rounds"),
        (OpLinearRegression, Evaluators.Regression.rmse,
         "regression", _regression, "gram"),
        (OpLogisticRegression, Evaluators.MultiClassification.error,
         "multiclass", _multiclass, "mlr_rounds"),
    ], ids=["logistic", "squared_hinge", "squared", "softmax"])
    def test_one_streamed_kernel_a_loss(self, monkeypatch, est, evaluator,
                                        problem_type, data, kernel):
        """_streamed_fit dispatches on the loss alone."""
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        X, y = data(n=1200)
        val = CrossValidation(evaluator(), num_folds=2, seed=3)
        best = val.validate([(est(max_iter=15),
                              [{"reg_param": 0.01}, {"reg_param": 0.1}])],
                            X, y, problem_type=problem_type)
        assert np.isfinite(best.best_metric)
        assert {v.route for v in best.validated} == {"streamed"}
        assert val.last_streamed_telemetry["kernel"] == kernel

    def test_collector_records_sweep_convergence(self, monkeypatch):
        from transmogrifai_tpu.utils.metrics import collector
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        X, y = _binary(n=900)
        collector.enable("test_sweep_conv")
        try:
            val = CrossValidation(
                Evaluators.BinaryClassification.au_pr(), num_folds=2,
                seed=4)
            val.validate([(OpLogisticRegression(max_iter=15),
                           [{"reg_param": 0.01}, {"reg_param": 0.2}])],
                         X, y)
            recs = collector.current.sweep_metrics
            assert recs and recs[-1].kernel == "rounds"
            assert recs[-1].lanes_total == 4
            out = collector.current.to_json()
            assert "sweep_metrics" in out
        finally:
            collector.disable()


class TestBenchFlopModel:
    """Satellite: the stale streamed FLOP model (compressed-triangle 2nT,
    hard-coded 15 iterations) is gone — executed FLOPs come from the
    sweep's measured lane-passes."""

    def test_streamed_model_uses_measured_lane_passes(self):
        import bench
        cfg = dict(n_rows=1000, n_cols=8, glm_grid=4, folds=2)
        n, d = 1000, 8
        per_lane_pass = 4 * n * d + 2 * n * d * d
        got = bench.glm_flops_estimate(cfg, "streamed",
                                       {"lane_passes": 7})
        assert got == per_lane_pass * 7
        # executed work (the padded bucket) outranks the logical count
        got_pad = bench.glm_flops_estimate(
            cfg, "streamed", {"lane_passes": 7, "padded_lane_passes": 16})
        assert got_pad == per_lane_pass * 16
        # fallback without telemetry: 15 iterations x all lanes, but on
        # the FULL symmetric einsum model (not the retired triangle)
        got_fb = bench.glm_flops_estimate(cfg, "streamed", None)
        assert got_fb == per_lane_pass * 15 * 4 * 2
        T = d * (d + 1) // 2
        stale = (4 * n * d + 2 * n * T) * 15 * 4 * 2
        assert got_fb != stale

    def test_vmapped_model_unchanged(self):
        import bench
        cfg = dict(n_rows=500, n_cols=4, glm_grid=3, folds=2)
        n, d = 500, 4
        per_iter_lane = 4 * n * d + 2 * n * d * d + n * d
        assert bench.glm_flops_estimate(cfg, "vmapped") == \
            per_iter_lane * 15 * 6


# -- the intercept steps with the coefficients ----------------------------------

# sweep-glm-nulls128's grid: (reg, elastic-net) -> l1 = reg a, l2 = reg (1 - a)
NULLS_GRID = [(r, a) for r in (0.001, 0.01, 0.1, 0.2) for a in (0.1, 0.5)]


def _null_tracked_standardised(n=24000, raw=16, seed=0):
    """A null-tracked table as transmogrify() makes it, standardised, float64:
    `raw` fields at scales 2^-4 .. 2^4, each missing at a rate from 0.001 to
    0.5 and filled with its observed mean, each followed by its 0/1 null
    indicator — the columns whose curvature-weighted means lie far from
    zero; a logistic label over all of them; a fifth of the rows held out
    (training weights `t`)."""
    rng = np.random.default_rng(seed)
    rates = rng.permutation(np.logspace(-3, np.log10(0.5), raw))
    scale = 2.0 ** ((np.arange(raw) * 5) % 9 - 4)
    loc = scale * rng.uniform(0.25, 2.0, raw) * rng.choice([-1, 1], raw)
    v = loc + scale * rng.normal(size=(n, raw))
    miss = rng.uniform(size=(n, raw)) < rates
    v = np.where(miss, np.where(miss, 0, v).sum(0) / (~miss).sum(0), v)
    X = np.empty((n, 2 * raw))
    X[:, 0::2], X[:, 1::2] = v, miss
    xs = (X - X.mean(0)) / X.std(0)
    beta = rng.normal(size=2 * raw) * 2.5 / np.sqrt(2 * raw)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(xs @ beta - 1.5)))) \
        .astype(np.float64)
    return xs, y, (rng.integers(0, 5, n) != 0).astype(np.float64)


def _pass_sums(xs, y, t, B, b0):
    """One pass's sums in numpy, in the table's dtype: gA, hA, g0A, h0A and
    cA = sum_rows S xs', the Hessian's border."""
    p = 1 / (1 + np.exp(-(xs @ B + b0)))
    r, s = (p - y) * t, np.maximum(p * (1 - p), 1e-6) * t
    return r @ xs, (xs * s[:, None]).T @ xs, r.sum(), s.sum(), s @ xs


def _soft(z, thr):
    return np.sign(z) * np.maximum(np.abs(z) - thr, 0)


def _documented_iteration(xs, y, t, l1, l2, tol, bordered=False,
                          max_iter=400):
    """The rounds' iteration as it was documented before the intercept
    stepped with the coefficients, plain numpy: B by H^-1 g and the soft
    threshold over the Hessian's diagonal, the intercept by g0 / h0, each
    as if the other stood still. `bordered`: the joint [d + 1, d + 1]
    Newton step and THEN the threshold, the remedy ROADMAP.md once named.
    (B, b0, iterations, the intercept's gradient where it stopped, the
    iteration at which the rounds' tol 1e-6 would have stopped it)."""
    d, T = xs.shape[1], t.sum()
    B, b0, at_rounds_tol = np.zeros(d), 0.0, None
    for it in range(1, max_iter + 1):
        gA, hA, g0A, h0A, cA = _pass_sums(xs, y, t, B, b0)
        g = gA / T + l2 * B
        H = hA / T + (l2 + 1e-6) * np.eye(d)
        thr = l1 / np.maximum(np.diag(H), 1e-12)
        g0, h0 = g0A / T, max(h0A / T, 1e-12)
        if bordered:
            c = cA / T
            step = np.linalg.solve(
                np.block([[H, c[:, None]], [c[None, :], np.array([[h0]])]]),
                np.append(g, g0))
            B_new, b0_new = _soft(B - step[:-1], thr), b0 - step[-1]
        else:
            B_new, b0_new = _soft(B - np.linalg.solve(H, g), thr), b0 - g0 / h0
        delta = np.abs(B_new - B).max() + abs(b0_new - b0)
        B, b0 = B_new, b0_new
        if at_rounds_tol is None and delta <= 1e-6:
            at_rounds_tol = it
        if delta <= tol:
            break
    return B, b0, it, _pass_sums(xs, y, t, B, b0)[2] / T, at_rounds_tol


def _update_args(xs, y, t, B, b0, l1, l2):
    """`_newton_prox_update`'s arguments for one lane, from numpy sums in
    the table's dtype."""
    dt, d = xs.dtype, xs.shape[1]
    gA, hA, g0A, h0A, cA = _pass_sums(xs, y, t, B[0], b0[0])
    return (jnp.asarray(B), jnp.asarray(b0), jnp.asarray(gA[None]),
            jnp.asarray(hA[None]), jnp.asarray([g0A], dt),
            jnp.asarray([h0A], dt), jnp.asarray(cA[None]),
            jnp.asarray([t.sum()], dt), jnp.asarray([l1], dt),
            jnp.asarray([l2], dt), jnp.eye(d, dtype=dt), lambda h: h)


def _programs_iteration(xs, y, t, l1, l2, tol, max_iter=100):
    """The same passes (numpy, the table's dtype) around the program's own
    update, from zero, stopped as the rounds stop."""
    B, b0 = np.zeros((1, xs.shape[1]), xs.dtype), np.zeros(1, xs.dtype)
    for it in range(1, max_iter + 1):
        B, b0, delta = (np.asarray(v) for v in GS._newton_prox_update(
            *_update_args(xs, y, t, B, b0, l1, l2), True))
        assert B.dtype == b0.dtype == xs.dtype
        if delta[0] <= tol:
            break
    return B[0], b0[0], it, _pass_sums(xs, y, t, B[0], b0[0])[2] / t.sum()


def _older_update(B, b0, gA, hA, g0A, h0A, wsum_l, l1, l2, eye, assemble,
                  fit_intercept):
    """`_newton_prox_update` as it stood before this class's subject, to
    the letter (jnp, so that its roundings are the program's)."""
    g = gA / wsum_l[:, None] + l2[:, None] * B
    H = assemble(hA) / wsum_l[:, None, None]
    H = H + (l2[:, None, None] + 1e-6) * eye[None]
    step = jnp.linalg.solve(H, g[..., None])[..., 0]
    B_new = B - step
    hdiag = jnp.maximum(jnp.diagonal(H, axis1=1, axis2=2), GS.EPS)
    B_new = (jnp.sign(B_new)
             * jnp.maximum(jnp.abs(B_new) - l1[:, None] / hdiag, 0.0))
    b0_new = b0 - (g0A / wsum_l) / jnp.maximum(h0A / wsum_l, GS.EPS) \
        if fit_intercept else b0
    delta = jnp.abs(B_new - B).max(axis=1) + jnp.abs(b0_new - b0)
    return B_new, b0_new, delta


class TestInterceptStepsWithTheCoefficients:
    """`_newton_prox_update` solves the iteration's own quadratic model for
    B and the intercept together (block Gauss-Seidel, from the pass's sixth
    sum): the documented iteration's fixed point, in a third of its
    passes where the columns' curvature-weighted means are far from zero."""

    @pytest.fixture(scope="class")
    def table(self):
        return _null_tracked_standardised()

    @pytest.fixture(scope="class")
    def documented(self, table):
        """By grid point: the documented iteration's count to the rounds'
        tol, and its fixed point (run on to 1e-11, float64)."""
        out = {}
        for reg, a in NULLS_GRID:
            l1, l2 = reg * a, reg * (1 - a)
            B, b0, _, g0, iters = _documented_iteration(*table, l1, l2,
                                                        1e-11)
            assert abs(g0) <= 1e-9
            out[reg, a] = iters, B, b0
        return out

    @pytest.mark.parametrize("dtype", [np.float64, np.float32],
                             ids=["float64", "float32"])
    @pytest.mark.parametrize("reg,a", NULLS_GRID)
    def test_same_fixed_point_in_fewer_passes(self, table, documented, reg,
                                              a, dtype):
        """At each of the cell's eight grid points, in float64 and with
        every sum and the update in float32: stopped at the rounds' tol,
        B and the intercept lie within 2e-6 of the documented iteration's
        fixed point, the intercept's gradient is zero there (1e-6), and at
        reg 0.001 and 0.01 it took at most half the documented count."""
        doc_iters, B_doc, b0_doc = documented[reg, a]
        xs, y, t = (v.astype(dtype) for v in table)
        with jax.enable_x64(dtype == np.float64):
            B, b0, iters, g0 = _programs_iteration(
                xs, y, t, reg * a, reg * (1 - a), 1e-6)
        assert np.abs(B - B_doc).max() + abs(b0 - b0_doc) <= 2e-6
        assert abs(g0) <= 1e-6
        assert iters <= doc_iters
        if reg <= 0.01:
            assert doc_iters >= 19 and 2 * iters <= doc_iters, (
                iters, doc_iters)
        assert ((B != 0) == (B_doc != 0)).all()

    def test_bordered_step_then_threshold_is_another_fixed_point(self, table,
                                                                 documented):
        """The joint [d + 1, d + 1] Newton step followed by the soft
        threshold (ROADMAP.md's older text) stops as soon, but where the
        intercept's gradient is NOT zero: the threshold moves B after the
        joint solve and the intercept never hears of it. `mesh_answer`
        holds that gradient under 3e-6; here it reads over 1e-4 at every
        point that keeps a coefficient, and the intercept is off the
        documented one."""
        for reg, a in NULLS_GRID[:-1]:
            _, b0, iters, g0, _ = _documented_iteration(
                *table, reg * a, reg * (1 - a), 1e-9, bordered=True)
            assert iters <= 12
            assert abs(g0) > 1e-4, (reg, a, g0)
            assert abs(b0 - documented[reg, a][2]) > 1e-4

    @pytest.mark.parametrize("dtype", [np.float64, np.float32],
                             ids=["float64", "float32"])
    def test_older_step_where_nothing_is_coupled(self, table, dtype):
        """Without an intercept, and in a lane whose coefficients the
        penalty holds at zero, the update is the older one bit for bit: B
        and the intercept's plain Newton step. (A lane that KEEPS
        coefficients steps elsewhere: the case above.)"""
        xs, y, t = (v.astype(dtype) for v in table)
        rng = np.random.default_rng(3)
        B = (rng.normal(size=(1, xs.shape[1])) * 0.1).astype(dtype)
        b0 = np.asarray([-1.2], dtype)
        with jax.enable_x64(dtype == np.float64):
            args = _update_args(xs, y, t, B, b0, 1e-3, 9e-3)
            got = GS._newton_prox_update(*args, False)
            old = _older_update(*args[:6], *args[7:], False)
            for a, b in zip(got, old):
                assert np.array_equal(np.asarray(a), np.asarray(b))
            assert np.array_equal(np.asarray(got[1]), b0)
            assert float(got[2][0]) > 1e-3
            # the penalty past every gradient: B stays at zero
            zero = _update_args(xs, y, t, np.zeros_like(B), b0, 10.0, 0.0)
            got = GS._newton_prox_update(*zero, True)
            old = _older_update(*zero[:6], *zero[7:], True)
            assert not np.asarray(got[0]).any()
            for a, b in zip(got, old):
                assert np.array_equal(np.asarray(a), np.asarray(b))
            assert abs(float(got[1][0]) - float(b0[0])) > 1e-2
            kept = GS._newton_prox_update(*args, True)
            assert not np.array_equal(
                np.asarray(kept[1]),
                np.asarray(_older_update(*args[:6], *args[7:], True)[1]))

    def test_no_lane_at_the_cap_and_the_telemetry_says_which_update(self,
                                                                    table):
        """The whole grid through the round driver (three folds, float32
        blocks on the CPU): every lane retires at tol, none at max_iter, in
        at most half the passes the documented iteration's slowest lane
        took; `intercept_sweeps` names the update on every round's span and
        in the telemetry (the constant, 0 where no intercept is fitted)."""
        xs, y, _ = table
        X = jnp.asarray(xs, jnp.float32)
        masks = _masks(y, folds=3)
        regs, alphas = (np.float32(v) for v in zip(*NULLS_GRID))
        from transmogrifai_tpu.utils.metrics import collector
        collector.disable()
        collector.enable("intercept_sweeps")
        try:
            st = GS._new_round_state(3 * len(regs), X.shape[1])
            _, _, info = sweep_glm_streamed_rounds(
                X, jnp.asarray(y, jnp.float32), jnp.ones(len(y), jnp.float32),
                jnp.asarray(masks), regs, alphas, loss="logistic",
                max_iter=50, tol=1e-6, standardize=False, state=st)
            spans = [s for s in collector.trace.spans
                     if s.kind == "sweep_round"]
        finally:
            collector.finish()
            collector.disable()
        assert info["lanes_at_cap"] == 0
        assert info["lanes_retired"] == info["lanes_total"] == 24
        assert int(st["iters"].max()) <= 12 and info["data_passes"] <= 13
        assert info["intercept_sweeps"] == GS.INTERCEPT_SWEEPS == 2
        assert spans and all(s.attrs["intercept_sweeps"] == 2 for s in spans)
        _, _, plain = sweep_glm_streamed_rounds(
            X, jnp.asarray(y, jnp.float32), jnp.ones(len(y), jnp.float32),
            jnp.asarray(masks), regs[:2], alphas[:2], loss="logistic",
            max_iter=50, tol=1e-6, standardize=False, fit_intercept=False)
        assert plain["intercept_sweeps"] == 0
