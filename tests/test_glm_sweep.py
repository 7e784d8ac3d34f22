"""Streaming lane-batched GLM sweep (ops/glm_sweep.py) must agree with the
per-lane vmapped path — same fold masks, same grids, near-identical fold
metrics and the same winner (the streamed kernel is an alternative
factorization of the same Newton solve, OpValidator.scala:270 workload)."""
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
from transmogrifai_tpu.ops.glm import fit_logistic


def _binary(n=3000, d=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    beta = np.linspace(1.5, -1.5, d)
    p = 1 / (1 + np.exp(-(X @ beta + 0.3)))
    y = (rng.uniform(size=n) < p).astype(np.float32)
    return X, y


def _masks(y, folds=3, seed=1):
    rng = np.random.default_rng(seed)
    fold = rng.integers(0, folds, size=len(y))
    return np.stack([(fold != k).astype(np.float32) for k in range(folds)])


def _streamed(X, y, w, masks, regs, alphas, *, loss, max_iter, standardize,
              mesh=None):
    """(B [F, G, d], b0 [F, G]) from THE streamed kernel of the loss, the
    one Validator._streamed_fit takes: the Gram fast path for `squared`,
    the retirement rounds (cold start: no pathwise seed, so a lane's
    iterates are the per-lane solver's) for the IRLS losses. Arrays may be
    host arrays or already placed on `mesh`."""
    regs, alphas = jnp.asarray(regs), jnp.asarray(alphas)
    if loss == "squared":
        B, b0, *_ = GS.sweep_glm_squared_gram(
            X, y, w, masks, regs, alphas, max_iter, standardize=standardize)
    else:
        B, b0, _ = GS.sweep_glm_streamed_rounds(
            X, y, w, masks, regs, alphas, loss=loss, max_iter=max_iter,
            standardize=standardize, warm_start=False, mesh=mesh)
    return np.asarray(B), np.asarray(b0)


class TestKernelParity:
    def test_streamed_matches_per_lane_logistic(self):
        X, y = _binary()
        masks = _masks(y)
        w = np.ones_like(y)
        regs = np.array([0.001, 0.01, 0.1], np.float32)
        alphas = np.array([0.0, 0.25, 0.5], np.float32)
        B, b0 = _streamed(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), jnp.asarray(regs), jnp.asarray(alphas),
            loss="logistic", max_iter=25, standardize=False)
        B = np.asarray(B)
        b0 = np.asarray(b0)
        for f in range(masks.shape[0]):
            for g in range(len(regs)):
                beta_ref, b0_ref = fit_logistic(
                    jnp.asarray(X), jnp.asarray(y),
                    jnp.asarray(masks[f] * w),
                    jnp.asarray(regs[g]), jnp.asarray(alphas[g]),
                    max_iter=25, standardize=False)
                assert np.allclose(B[f, g], np.asarray(beta_ref),
                                   atol=2e-3), (f, g)
                assert abs(b0[f, g] - float(b0_ref)) < 2e-3, (f, g)

    def test_streamed_standardize_close(self):
        """Global-weight standardization differs from per-lane fold
        standardization only at O(1/sqrt(n)) — betas must still land
        within statistical tolerance."""
        X, y = _binary(n=4000)
        masks = _masks(y)
        w = np.ones_like(y)
        regs = np.array([0.01], np.float32)
        alphas = np.array([0.0], np.float32)
        B, b0 = _streamed(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), jnp.asarray(regs), jnp.asarray(alphas),
            loss="logistic", max_iter=25, standardize=True)
        beta_ref, b0_ref = fit_logistic(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(masks[0] * w),
            jnp.asarray(0.01), jnp.asarray(0.0), max_iter=25,
            standardize=True)
        assert np.allclose(np.asarray(B)[0, 0], np.asarray(beta_ref),
                           atol=0.05)

    def test_streamed_squared_and_hinge(self):
        X, y = _binary(n=2500)
        masks = _masks(y, folds=2)
        w = np.ones_like(y)
        regs = np.array([0.01, 0.1], np.float32)
        alphas = np.zeros(2, np.float32)
        for loss in ("squared", "squared_hinge"):
            B, b0 = _streamed(
                jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
                jnp.asarray(masks), jnp.asarray(regs), jnp.asarray(alphas),
                loss=loss, max_iter=20, standardize=False)
            assert np.isfinite(np.asarray(B)).all()
            assert np.isfinite(np.asarray(b0)).all()

    def test_streamed_tiled_wide_matches_per_lane(self):
        """Feature-tiled Gram path (d > TRI_MAX_D): same Newton math at
        tile-pair granularity, so wide transmogrified matrices (the r2
        wide bench is d=567) use the one-pass kernel too. Parity vs the
        per-lane logistic solver at d=600 (tiled, non-multiple of the
        64-tile so column padding is exercised). 600 columns on 600
        training rows leave the reg 0.01 lanes nearly separable and the
        intercept coupled to the coefficients: the sweep, whose intercept
        steps with them, is at its fixed point in 17 iterations; the
        per-lane solver, which alternates, needs 65-75 to the same one, so
        it is given them (at 20 its intercept is still 0.012 short)."""
        from transmogrifai_tpu.ops.glm_sweep import TRI_MAX_D
        rng = np.random.default_rng(11)
        n, d = 1200, 600
        assert d > TRI_MAX_D
        X = rng.normal(size=(n, d)).astype(np.float32)
        beta = np.zeros(d, np.float32)
        beta[:10] = np.linspace(1.0, -1.0, 10)
        p = 1 / (1 + np.exp(-(X @ beta)))
        y = (rng.uniform(size=n) < p).astype(np.float32)
        masks = _masks(y, folds=2)
        w = np.ones_like(y)
        regs = np.array([0.01, 0.3], np.float32)
        alphas = np.zeros(2, np.float32)
        B, b0 = _streamed(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), jnp.asarray(regs), jnp.asarray(alphas),
            loss="logistic", max_iter=20, standardize=False)
        B = np.asarray(B)
        assert B.shape == (2, 2, d)
        for f in range(2):
            for g in range(2):
                beta_ref, b0_ref = fit_logistic(
                    jnp.asarray(X), jnp.asarray(y),
                    jnp.asarray(masks[f] * w), jnp.asarray(regs[g]),
                    jnp.asarray(0.0), max_iter=200, standardize=False)
                assert np.allclose(B[f, g], np.asarray(beta_ref),
                                   atol=1e-4), (f, g)
                assert abs(float(b0[f, g]) - float(b0_ref)) < 1e-4

    def test_streamed_hinge_matches_per_lane_svc(self):
        """Streamed squared_hinge must reproduce fit_linear_svc per lane —
        same loss scaling (0.5*gap^2), so the same effective L2 for a
        given reg_param above and below STREAMED_SWEEP_MIN_ROWS."""
        from transmogrifai_tpu.ops.glm import fit_linear_svc
        X, y = _binary(n=3000)
        masks = _masks(y, folds=2)
        w = np.ones_like(y)
        regs = np.array([0.01, 0.1, 1.0], np.float32)
        alphas = np.zeros(3, np.float32)
        B, b0 = _streamed(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), jnp.asarray(regs), jnp.asarray(alphas),
            loss="squared_hinge", max_iter=30, standardize=False)
        B = np.asarray(B)
        b0 = np.asarray(b0)
        for f in range(masks.shape[0]):
            for g in range(len(regs)):
                beta_ref, b0_ref = fit_linear_svc(
                    jnp.asarray(X), jnp.asarray(y),
                    jnp.asarray(masks[f] * w), jnp.asarray(regs[g]),
                    max_iter=30, standardize=False)
                assert np.allclose(B[f, g], np.asarray(beta_ref),
                                   atol=5e-3), (f, g, B[f, g],
                                                np.asarray(beta_ref))
                assert abs(b0[f, g] - float(b0_ref)) < 5e-3, (f, g)


class TestValidatorRouting:
    def test_streamed_and_vmapped_agree_end_to_end(self, monkeypatch):
        """Force the streamed route at small n: winner and fold metrics
        match the vmapped path."""
        X, y = _binary(n=2000)
        w = None
        ev = Evaluators.BinaryClassification.au_pr()
        models = lambda: [(OpLogisticRegression(max_iter=20),
                           [{"reg_param": 0.001}, {"reg_param": 0.05},
                            {"reg_param": 0.5}])]
        val = CrossValidation(ev, num_folds=3, seed=7)
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 10**12)
        best_vmapped = val.validate(models(), X, y)
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        val2 = CrossValidation(ev, num_folds=3, seed=7)
        best_streamed = val2.validate(models(), X, y)
        assert best_streamed.best_grid == best_vmapped.best_grid
        for a, b in zip(best_vmapped.validated, best_streamed.validated):
            assert a.grid == b.grid
            assert np.allclose(a.fold_metrics, b.fold_metrics, atol=5e-3), \
                (a.grid, a.fold_metrics, b.fold_metrics)

    def test_streamed_svc_and_regression_route(self, monkeypatch):
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        X, y = _binary(n=1500)
        ev = Evaluators.BinaryClassification.au_roc()
        val = CrossValidation(ev, num_folds=2, seed=3)
        best = val.validate([(OpLinearSVC(max_iter=15),
                              [{"reg_param": 0.01}, {"reg_param": 0.1}])],
                            X, y)
        assert np.isfinite(best.best_metric)
        # regression
        rng = np.random.default_rng(2)
        yr = (X @ np.linspace(1, -1, X.shape[1])
              + 0.1 * rng.normal(size=len(X))).astype(np.float32)
        evr = Evaluators.Regression.rmse()
        valr = CrossValidation(evr, num_folds=2, seed=3)
        bestr = valr.validate([(OpLinearRegression(max_iter=15),
                                [{"reg_param": 0.001}, {"reg_param": 0.1}])],
                              X, yr, problem_type="regression")
        assert np.isfinite(bestr.best_metric)

    def test_streamed_checkpoint_cells(self, monkeypatch, tmp_path):
        """Resume skips finished cells on the streamed path too."""
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        X, y = _binary(n=1200)
        ev = Evaluators.BinaryClassification.au_pr()
        grids = [{"reg_param": 0.001}, {"reg_param": 0.1}]
        val = CrossValidation(ev, num_folds=2, seed=5)
        val.checkpoint_path = str(tmp_path / "ck.jsonl")
        b1 = val.validate([(OpLogisticRegression(max_iter=15), grids)], X, y)
        val2 = CrossValidation(ev, num_folds=2, seed=5)
        val2.checkpoint_path = val.checkpoint_path
        b2 = val2.validate([(OpLogisticRegression(max_iter=15), grids)], X, y)
        assert b1.best_grid == b2.best_grid
        for a, b in zip(b1.validated, b2.validated):
            assert a.fold_metrics == b.fold_metrics

    def test_constant_off_axis_override_honored(self, monkeypatch):
        """A constant non-axis grid key (e.g. max_iter) must bind on the
        streamed path exactly as the vmapped path binds it (review r2
        finding: the streamed fit read estimator defaults instead)."""
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        X, y = _binary(n=1500)
        ev = Evaluators.BinaryClassification.au_pr()
        # max_iter=1 must visibly under-converge vs default 50
        grids = [{"reg_param": 0.01, "max_iter": 1}]
        val = CrossValidation(ev, num_folds=2, seed=4)
        b1 = val.validate([(OpLogisticRegression(), grids)], X, y)
        val2 = CrossValidation(ev, num_folds=2, seed=4)
        b2 = val2.validate([(OpLogisticRegression(),
                             [{"reg_param": 0.01, "max_iter": 50}])], X, y)
        # 1-iteration Newton and 50-iteration fits differ measurably
        assert not np.allclose(b1.validated[0].fold_metrics,
                               b2.validated[0].fold_metrics, atol=1e-6)


class TestStreamedProperties:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_nonuniform_sample_weights_match_per_lane(self, seed):
        """Sample weights compose with fold masks identically on both
        routes (balancing weights enter the sweep this way)."""
        X, y = _binary(n=1800, d=6, seed=seed)
        rng = np.random.default_rng(seed + 100)
        w = rng.uniform(0.25, 3.0, size=len(y)).astype(np.float32)
        masks = _masks(y, folds=2, seed=seed)
        regs = np.array([0.01], np.float32)
        alphas = np.array([0.25], np.float32)
        B, b0 = _streamed(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), jnp.asarray(regs), jnp.asarray(alphas),
            loss="logistic", max_iter=25, standardize=False)
        for f in range(2):
            beta_ref, b0_ref = fit_logistic(
                jnp.asarray(X), jnp.asarray(y),
                jnp.asarray(masks[f] * w), jnp.asarray(0.01),
                jnp.asarray(0.25), max_iter=25, standardize=False)
            assert np.allclose(np.asarray(B)[f, 0], np.asarray(beta_ref),
                               atol=3e-3), seed
            assert abs(float(b0[f, 0]) - float(b0_ref)) < 3e-3

    def test_row_block_boundary_sizes(self, monkeypatch):
        """n exactly at, one under, and one over the scan block size."""
        monkeypatch.setattr(GS, "_ROW_BLOCK", 512)
        # the unjitted round: no program traced under the patched block
        # stays in sweep_glm_round's cache for a later test of this process
        monkeypatch.setattr(GS, "sweep_glm_round",
                            GS.sweep_glm_round.__wrapped__)
        for n in (511, 512, 513, 1024, 1030):
            X, y = _binary(n=n, d=4, seed=3)
            w = np.ones_like(y)
            masks = _masks(y, folds=2, seed=4)
            B, b0 = _streamed(
                jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
                jnp.asarray(masks), np.array([0.01], np.float32),
                np.array([0.0], np.float32),
                loss="logistic", max_iter=15, standardize=False)
            beta_ref, _ = fit_logistic(
                jnp.asarray(X), jnp.asarray(y), jnp.asarray(masks[0] * w),
                jnp.asarray(0.01), jnp.asarray(0.0), max_iter=15,
                standardize=False)
            assert np.allclose(np.asarray(B)[0, 0], np.asarray(beta_ref),
                               atol=3e-3), n


class TestShardedStreamed:
    def _mesh(self):
        from transmogrifai_tpu.parallel.mesh import make_mesh
        return make_mesh(n_batch=4, n_model=1)

    def test_sharded_matches_unsharded(self):
        """shard_map row-sharded streamed sweep == single-device streamed
        sweep (psum'd accumulators are the only difference)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._mesh()
        n = 4096  # multiple of the 4-way batch axis
        X, y = _binary(n=n, d=6, seed=5)
        w = np.ones_like(y)
        masks = _masks(y, folds=2, seed=6)
        regs = np.array([0.01, 0.1], np.float32)
        alphas = np.array([0.0, 0.5], np.float32)

        B1, b01 = _streamed(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), jnp.asarray(regs), jnp.asarray(alphas),
            loss="logistic", max_iter=20, standardize=False)

        row = NamedSharding(mesh, P("batch", None))
        vec = NamedSharding(mesh, P("batch"))
        mrow = NamedSharding(mesh, P(None, "batch"))
        B2, b02 = _streamed(
            jax.device_put(X, row), jax.device_put(y, vec),
            jax.device_put(w, vec), jax.device_put(masks, mrow),
            regs, alphas, loss="logistic", max_iter=20, standardize=False,
            mesh=mesh)
        assert np.allclose(np.asarray(B1), np.asarray(B2), atol=2e-3)
        assert np.allclose(np.asarray(b01), np.asarray(b02), atol=2e-3)

    def test_sharded_standardize(self):
        """One-pass psum'd standardization lands within f32 tolerance of
        the single-device two-pass."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._mesh()
        X, y = _binary(n=2048, d=5, seed=9)
        X = X * 3.0 + 1.5  # non-trivial mean/std
        w = np.ones_like(y)
        masks = _masks(y, folds=2, seed=2)
        regs = np.array([0.05], np.float32)
        alphas = np.array([0.0], np.float32)
        B1, b01 = _streamed(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), jnp.asarray(regs), jnp.asarray(alphas),
            loss="logistic", max_iter=25, standardize=True)
        row = NamedSharding(mesh, P("batch", None))
        vec = NamedSharding(mesh, P("batch"))
        mrow = NamedSharding(mesh, P(None, "batch"))
        B2, b02 = _streamed(
            jax.device_put(X, row), jax.device_put(y, vec),
            jax.device_put(w, vec), jax.device_put(masks, mrow),
            regs, alphas, loss="logistic", max_iter=25, standardize=True,
            mesh=mesh)
        assert np.allclose(np.asarray(B1), np.asarray(B2), atol=5e-3)

    def test_validator_mesh_routes_streamed(self, monkeypatch):
        """Validator(mesh=...) + large-n gate routes through the sharded
        streamed kernel and agrees with the meshless route."""
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        mesh = self._mesh()
        X, y = _binary(n=1000, d=5, seed=12)  # NOT a multiple of 4: pads
        ev = Evaluators.BinaryClassification.au_pr()
        grids = [{"reg_param": 0.001}, {"reg_param": 0.1}]
        v_mesh = CrossValidation(ev, num_folds=2, seed=3, mesh=mesh)
        best_m = v_mesh.validate(
            [(OpLogisticRegression(max_iter=20), grids)], X, y)
        v_plain = CrossValidation(ev, num_folds=2, seed=3)
        best_p = v_plain.validate(
            [(OpLogisticRegression(max_iter=20), grids)], X, y)
        assert best_m.best_grid == best_p.best_grid
        for a, b in zip(best_p.validated, best_m.validated):
            assert np.allclose(a.fold_metrics, b.fold_metrics, atol=5e-3)

    def test_sharded_standardize_large_mean(self):
        """Epoch-timestamp-scale means must not destroy the variance
        (two-pass psum'd moments; the one-pass form cancels in f32)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._mesh()
        X, y = _binary(n=2048, d=4, seed=13)
        X = X + np.float32(1.6e9)  # large mean, unit variance
        w = np.ones_like(y)
        masks = _masks(y, folds=2, seed=1)
        row = NamedSharding(mesh, P("batch", None))
        vec = NamedSharding(mesh, P("batch"))
        mrow = NamedSharding(mesh, P(None, "batch"))
        B, b0 = _streamed(
            jax.device_put(X, row), jax.device_put(y, vec),
            jax.device_put(w, vec), jax.device_put(masks, mrow),
            np.array([0.05], np.float32), np.array([0.0], np.float32),
            loss="logistic", max_iter=20, standardize=True, mesh=mesh)
        assert np.isfinite(np.asarray(B)).all()
        assert np.abs(np.asarray(B)).max() < 100.0  # no exploded scales


class TestHeldoutOnceEval:
    """The binned in-sweep rank metric scores every row ONCE and bins it
    into its one held-out fold when the folds' held-out sets are disjoint
    (validators._streamed_eval_heldout); the fold-by-fold loop over the
    whole matrix stays for every other case and is its reference."""

    @staticmethod
    def _sweep(monkeypatch, val, grids, X, y, w, masks, force_loop):
        """(fold metrics [G, F], telemetry, B [F, G, d], b0 [F, G]) of a
        streamed sweep under external `masks`; force_loop makes the
        validator see them as overlapping."""
        fits = []
        orig = V.Validator._streamed_fit

        def spy(self, *a, **k):
            out = orig(self, *a, **k)
            fits.append((np.asarray(out[0]), np.asarray(out[1])))
            return out
        monkeypatch.setattr(V.Validator, "_streamed_fit", spy)
        if force_loop:
            monkeypatch.setattr(V, "_held_out_at_most_once",
                                lambda masks: False)
        best = val.validate([(OpLogisticRegression(max_iter=6), grids)],
                            X, y, w=w, masks=masks)
        assert {v.route for v in best.validated} == {"streamed"}
        return (np.array([v.fold_metrics for v in best.validated]),
                val.last_streamed_telemetry, *fits[-1])

    @pytest.mark.parametrize("n_grid", [3, 11])
    @pytest.mark.parametrize("folds", [1, 3, 5])
    @pytest.mark.parametrize("metric", ["au_pr", "au_roc"])
    def test_one_pass_equals_the_fold_by_fold_loop(self, monkeypatch,
                                                   metric, folds, n_grid):
        from transmogrifai_tpu.automl.tuning.validators import (
            TrainValidationSplit)
        from transmogrifai_tpu.ops import metrics_ops as M
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        monkeypatch.setattr(V, "BINNED_RANK_METRIC_MIN_ROWS", 0)
        X, y = _binary(n=2500, d=6, seed=folds)
        w = np.random.default_rng(9).uniform(0.5, 2.0, len(y)) \
            .astype(np.float32)
        ev = getattr(Evaluators.BinaryClassification, metric)()
        # one split holds a quarter of the rows out and the rest by none
        make = (lambda: TrainValidationSplit(ev, train_ratio=0.75, seed=3)) \
            if folds == 1 else \
            (lambda: CrossValidation(ev, num_folds=folds, seed=3))
        masks = make().fold_masks(y)
        assert masks.shape == (folds, len(y))
        grids = [{"reg_param": float(r)}
                 for r in np.geomspace(1e-4, 1.0, n_grid)]
        once, tele, B, b0 = self._sweep(monkeypatch, make(), grids, X, y, w,
                                        masks, force_loop=False)
        loop, tele_loop, _, _ = self._sweep(monkeypatch, make(), grids, X,
                                            y, w, masks, force_loop=True)
        chunks = -(-n_grid // V.Validator._STREAMED_EVAL_CHUNK)
        assert (tele["eval_route"], tele["passes"]) \
            == ("heldout_once", chunks)
        assert (tele_loop["eval_route"], tele_loop["passes"]) \
            == ("per_fold", folds * chunks)
        assert once.shape == (n_grid, folds)
        np.testing.assert_allclose(once, loop, rtol=0, atol=1e-6)
        # ... and the exact sorted metric of the sweep's own coefficients
        # within the binned contract (ops/metrics_ops.au_pr_binned)
        exact_fn = getattr(M, metric)
        exact = np.array([[float(exact_fn(
            jnp.asarray(X @ B[f, g] + b0[f, g]), jnp.asarray(y),
            jnp.asarray((1.0 - masks[f]) * w))) for f in range(folds)]
            for g in range(n_grid)])
        np.testing.assert_allclose(once, exact, rtol=0, atol=2e-3)

    def test_overlapping_external_masks_keep_the_loop(self, monkeypatch):
        """A row held out by two folds breaks the one-pass route's
        premise: the validator sees it on the masks and runs the loop,
        whose values are those of the same masks forced through it."""
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        monkeypatch.setattr(V, "BINNED_RANK_METRIC_MIN_ROWS", 0)
        X, y = _binary(n=1500, d=6, seed=4)
        masks = _masks(y, folds=3)
        assert V._held_out_at_most_once(masks)
        assert V._held_out_at_most_once(jnp.asarray(masks))
        masks[1, np.flatnonzero(masks[0] == 0)[0]] = 0.0
        assert not V._held_out_at_most_once(masks)
        assert not V._held_out_at_most_once(jnp.asarray(masks))
        ev = Evaluators.BinaryClassification.au_pr()
        grids = [{"reg_param": 0.01}, {"reg_param": 0.1}]
        seen, tele, _, _ = self._sweep(
            monkeypatch, CrossValidation(ev, num_folds=3, seed=3), grids,
            X, y, None, masks, force_loop=False)
        assert (tele["eval_route"], tele["passes"]) == ("per_fold", 3)
        forced, _, _, _ = self._sweep(
            monkeypatch, CrossValidation(ev, num_folds=3, seed=3), grids,
            X, y, None, masks, force_loop=True)
        np.testing.assert_array_equal(seen, forced)
