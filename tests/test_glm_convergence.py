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
