"""Tree-family kernels + estimators.

Mirrors the reference suites OpRandomForest*/OpGBT*/OpDecisionTree*/
OpXGBoost*Test.scala (core/src/test/.../impl/{classification,regression}/):
fitted model emits Prediction(pred, rawPrediction, probability); quality
checks on separable/nonlinear synthetic data; save/load round-trip.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.ops import trees as T


def _xor_data(n=2000, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.uniform(-1, 1, size=(n, 4)).astype(np.float32)
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0)).astype(np.float32)
    return X, y


def _blob_data(n=1500, k=3, seed=1):
    rng = np.random.default_rng(seed)
    centers = rng.normal(scale=4.0, size=(k, 5))
    y = rng.integers(0, k, size=n)
    X = centers[y] + rng.normal(size=(n, 5))
    return X.astype(np.float32), y.astype(np.float32)


def _piecewise(n=3000, seed=2):
    rng = np.random.default_rng(seed)
    X = rng.uniform(0, 1, size=(n, 3)).astype(np.float32)
    y = (np.where(X[:, 0] < 0.3, 1.0, 0.0) + 2.0 * (X[:, 1] > 0.6)
         + 0.1 * rng.normal(size=n)).astype(np.float32)
    return X, y


class TestBinning:
    def test_quantile_edges_monotone(self):
        X = np.random.default_rng(0).normal(size=(500, 3)).astype(np.float32)
        edges = np.asarray(T.quantile_edges(jnp.asarray(X), 16))
        assert edges.shape == (3, 15)
        assert (np.diff(edges, axis=1) >= 0).all()

    def test_bin_matrix_range_and_threshold_semantics(self):
        X = np.random.default_rng(1).normal(size=(400, 2)).astype(np.float32)
        edges = T.quantile_edges(jnp.asarray(X), 8)
        Xb = np.asarray(T.bin_matrix(jnp.asarray(X), edges))
        # present values occupy [1, n_bins]; bin 0 is reserved for missing
        assert Xb.min() >= 1 and Xb.max() <= 8
        # bin > t  <=>  x >= edges[t-1] (equality on an edge goes right)
        e = np.asarray(edges)
        t = 3
        assert ((Xb[:, 0] > t) == (X[:, 0] >= e[0, t - 1])).all()

    def test_bin_matrix_missing_bin(self):
        X = np.random.default_rng(2).normal(size=(300, 2)).astype(np.float32)
        X[::7, 0] = np.nan
        edges = T.quantile_edges(jnp.asarray(X), 8)
        Xb = np.asarray(T.bin_matrix(jnp.asarray(X), edges))
        nan = np.isnan(X[:, 0])
        assert (Xb[nan, 0] == 0).all()
        assert (Xb[~nan, 0] >= 1).all()
        # NaN rows are excluded from the quantile sketch: edges of the
        # NaN-carrying column are finite
        assert np.isfinite(np.asarray(edges)[0]).all()

    def test_constant_feature_is_harmless(self):
        X = np.ones((100, 2), np.float32)
        X[:, 1] = np.arange(100)
        edges = T.quantile_edges(jnp.asarray(X), 8)
        Xb = np.asarray(T.bin_matrix(jnp.asarray(X), edges))
        assert (Xb[:, 0] == Xb[0, 0]).all()


class TestGrowTree:
    def test_single_split_recovers_step(self):
        # y = 1[x0 > 0.5]: a depth-1 tree must find feature 0, cut ~0.5
        rng = np.random.default_rng(3)
        X = rng.uniform(0, 1, size=(1000, 3)).astype(np.float32)
        y = (X[:, 0] > 0.5).astype(np.float32)
        edges = T.quantile_edges(jnp.asarray(X), 32)
        Xb = T.bin_matrix(jnp.asarray(X), edges)
        tree = T.grow_tree(Xb, jnp.asarray(y[:, None]),
                           jnp.ones(1000, jnp.float32),
                           jnp.zeros(2, dtype=jnp.uint32),
                           depth=1, n_bins=32, leaf_mode="mean")
        assert int(tree.feat[0]) == 0
        tv = float(np.asarray(T.thresholds_to_values(
            tree.feat, tree.thresh, edges))[0])
        assert 0.4 < tv < 0.6
        leaves = np.asarray(tree.leaf)[:, 0]
        assert leaves[0] < 0.05 and leaves[1] > 0.95

    def test_no_split_when_pure(self):
        X = np.random.default_rng(4).normal(size=(200, 2)).astype(np.float32)
        y = np.ones(200, np.float32)  # pure node: zero gain everywhere
        edges = T.quantile_edges(jnp.asarray(X), 8)
        Xb = T.bin_matrix(jnp.asarray(X), edges)
        tree = T.grow_tree(Xb, jnp.asarray(y[:, None]),
                           jnp.ones(200, jnp.float32),
                           jnp.zeros(2, dtype=jnp.uint32),
                           depth=2, n_bins=8, leaf_mode="mean",
                           min_info_gain=1e-6)
        # dead splits encode thresh = n_bins (all rows left; bin 0 is the
        # missing slot so live bins are [1, n_bins])
        assert (np.asarray(tree.thresh) == 8).all()
        # every populated leaf predicts the pure value
        assert np.allclose(np.asarray(tree.leaf)[0, 0], 1.0, atol=1e-5)

    def test_min_instances_respected(self):
        rng = np.random.default_rng(5)
        X = rng.uniform(0, 1, size=(100, 1)).astype(np.float32)
        y = (X[:, 0] > 0.97).astype(np.float32)  # only ~3 positives
        edges = T.quantile_edges(jnp.asarray(X), 64)
        Xb = T.bin_matrix(jnp.asarray(X), edges)
        tree = T.grow_tree(Xb, jnp.asarray(y[:, None]),
                           jnp.ones(100, jnp.float32),
                           jnp.zeros(2, dtype=jnp.uint32),
                           depth=1, n_bins=64, leaf_mode="mean",
                           min_instances=10.0)
        n_right = int((np.asarray(Xb)[:, 0] > int(tree.thresh[0])).sum())
        assert n_right >= 10 or int(tree.thresh[0]) == 64


class TestEstimators:
    def test_gbt_classifier_solves_xor(self):
        from transmogrifai_tpu.models.trees import OpGBTClassifier
        X, y = _xor_data()
        m = OpGBTClassifier(max_iter=30, max_depth=3, step_size=0.3)
        model = m.fit_arrays(X, y)
        pred, raw, prob = model.predict_arrays(X)
        assert raw.shape == (len(y), 2) and prob.shape == (len(y), 2)
        assert (pred == y).mean() > 0.95
        assert np.allclose(prob.sum(axis=1), 1.0, atol=1e-5)

    def test_xgb_classifier_binary_quality(self):
        from transmogrifai_tpu.models.trees import OpXGBoostClassifier
        X, y = _xor_data(seed=7)
        m = OpXGBoostClassifier(num_round=40, max_depth=3, eta=0.3,
                                max_bins=64)
        model = m.fit_arrays(X, y)
        pred, _, prob = model.predict_arrays(X)
        assert (pred == y).mean() > 0.95

    def test_xgb_multiclass_softprob(self):
        from transmogrifai_tpu.models.trees import OpXGBoostClassifier
        X, y = _blob_data()
        m = OpXGBoostClassifier(num_round=15, max_depth=3, eta=0.5,
                                max_bins=32)
        model = m.fit_arrays(X, y)
        pred, raw, prob = model.predict_arrays(X)
        assert prob.shape == (len(y), 3)
        assert np.allclose(prob.sum(axis=1), 1.0, atol=1e-5)
        assert (pred == y).mean() > 0.9

    def test_random_forest_multiclass(self):
        from transmogrifai_tpu.models.trees import OpRandomForestClassifier
        X, y = _blob_data(seed=11)
        m = OpRandomForestClassifier(num_trees=20, max_depth=5)
        model = m.fit_arrays(X, y)
        pred, _, prob = model.predict_arrays(X)
        assert prob.shape[1] == 3
        assert (pred == y).mean() > 0.9

    def test_decision_tree_classifier(self):
        # axis-aligned AND target (greedy trees cannot break symmetric XOR;
        # boosting/bagging handle that — see the GBT/XGB tests above)
        from transmogrifai_tpu.models.trees import OpDecisionTreeClassifier
        rng = np.random.default_rng(13)
        X = rng.uniform(-1, 1, size=(2000, 4)).astype(np.float32)
        y = ((X[:, 0] > 0) & (X[:, 1] > 0)).astype(np.float32)
        m = OpDecisionTreeClassifier(max_depth=4)
        model = m.fit_arrays(X, y)
        pred, _, _ = model.predict_arrays(X)
        assert (pred == y).mean() > 0.95
        assert model.feat.shape[0] == 1  # single tree

    def test_gbt_regressor_piecewise(self):
        from transmogrifai_tpu.models.trees import OpGBTRegressor
        X, y = _piecewise()
        m = OpGBTRegressor(max_iter=40, max_depth=3, step_size=0.3,
                           max_bins=128)
        model = m.fit_arrays(X, y)
        pred, raw, prob = model.predict_arrays(X)
        assert raw is None and prob is None
        rmse = float(np.sqrt(np.mean((pred - y) ** 2)))
        assert rmse < 0.2

    def test_random_forest_regressor(self):
        from transmogrifai_tpu.models.trees import OpRandomForestRegressor
        X, y = _piecewise(seed=17)
        m = OpRandomForestRegressor(num_trees=30, max_depth=6)
        model = m.fit_arrays(X, y)
        pred, _, _ = model.predict_arrays(X)
        rmse = float(np.sqrt(np.mean((pred - y) ** 2)))
        assert rmse < 0.3

    def test_xgb_regressor(self):
        from transmogrifai_tpu.models.trees import OpXGBoostRegressor
        X, y = _piecewise(seed=19)
        m = OpXGBoostRegressor(num_round=50, max_depth=3, eta=0.3,
                               max_bins=64)
        model = m.fit_arrays(X, y)
        pred, _, _ = model.predict_arrays(X)
        rmse = float(np.sqrt(np.mean((pred - y) ** 2)))
        assert rmse < 0.2

    def test_decision_tree_regressor(self):
        from transmogrifai_tpu.models.trees import OpDecisionTreeRegressor
        X, y = _piecewise(seed=23)
        m = OpDecisionTreeRegressor(max_depth=4)
        model = m.fit_arrays(X, y)
        pred, _, _ = model.predict_arrays(X)
        rmse = float(np.sqrt(np.mean((pred - y) ** 2)))
        assert rmse < 0.35

    def test_sample_weights_shift_model(self):
        from transmogrifai_tpu.models.trees import OpGBTClassifier
        X, y = _xor_data(seed=29)
        w_pos = np.where(y > 0, 10.0, 0.1).astype(np.float32)
        m = OpGBTClassifier(max_iter=10, max_depth=3)
        p_w = m.fit_arrays(X, y, w_pos).predict_arrays(X)[2][:, 1].mean()
        p_u = m.fit_arrays(X, y).predict_arrays(X)[2][:, 1].mean()
        assert p_w > p_u + 0.1  # upweighting positives raises P(y=1)


class TestServingParity:
    def test_binned_and_raw_traversal_agree_on_onehot(self):
        # regression: one-hot values sit exactly on their bin edge; serving
        # must use x >= thresh to match `bin > t` (right-side binning)
        import jax
        rng = np.random.default_rng(43)
        X = np.concatenate([
            rng.uniform(0, 1, size=(800, 2)),
            (rng.uniform(size=(800, 2)) < 0.4).astype(np.float64),
        ], axis=1).astype(np.float32)
        y = ((X[:, 2] > 0.5) | (X[:, 0] > 0.7)).astype(np.float32)
        edges = T.quantile_edges(jnp.asarray(X), 32)
        Xb = T.bin_matrix(jnp.asarray(X), edges)
        trees, base = T.fit_gbt(Xb, jnp.asarray(y),
                                jnp.ones(800, jnp.float32),
                                jax.random.PRNGKey(0), n_rounds=5, depth=3,
                                n_bins=32, learning_rate=0.3,
                                loss="logistic")
        binned = float(base) + np.asarray(
            T.predict_forest_bins(trees, Xb, 3))[:, 0]
        tv = np.asarray(T.thresholds_to_values(trees.feat, trees.thresh,
                                               edges))
        raw = float(base) + T.np_predict_ensemble(
            np.asarray(trees.feat), tv, np.asarray(trees.leaf), X, 3)[:, 0]
        assert np.allclose(binned, raw, atol=1e-5)

    def test_nan_features_agree_between_binned_and_raw(self):
        # NaN occupies the dedicated bin 0 and routes by each node's
        # LEARNED default direction (Tree.miss); raw serving applies the
        # same bit on isnan rows — train and serve must agree when a NaN
        # escapes imputation
        import jax
        rng = np.random.default_rng(7)
        X = rng.normal(size=(600, 3)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        X[rng.uniform(size=600) < 0.15, 0] = np.nan
        X[rng.uniform(size=600) < 0.1, 2] = np.nan
        # quantile_edges sees the raw NaN matrix, same as models/trees._bin
        edges = T.quantile_edges(jnp.asarray(X), 16)
        assert np.isfinite(np.asarray(edges)[:, -1]).all()  # not NaN-poisoned
        Xb = T.bin_matrix(jnp.asarray(X), edges)
        trees, base = T.fit_gbt(Xb, jnp.asarray(y),
                                jnp.ones(600, jnp.float32),
                                jax.random.PRNGKey(1), n_rounds=4, depth=3,
                                n_bins=16, learning_rate=0.3,
                                loss="logistic")
        binned = float(base) + np.asarray(
            T.predict_forest_bins(trees, Xb, 3))[:, 0]
        tv = np.asarray(T.thresholds_to_values(trees.feat, trees.thresh,
                                               edges))
        raw = float(base) + T.np_predict_ensemble(
            np.asarray(trees.feat), tv, np.asarray(trees.leaf), X, 3,
            miss=np.asarray(trees.miss))[:, 0]
        assert np.isfinite(binned).all()
        assert np.allclose(binned, raw, atol=1e-5)
        # the missing mass is informative here (y depends on x0 which is
        # NaN-ed at random): some node learns default-right across rounds
        # (5/28 at this seed), proving the direction is actually used —
        # if learning regressed to always-left this catches it
        assert (np.asarray(trees.miss) > 0).any()


class TestPersistence:
    def test_tree_model_save_load_round_trip(self, tmp_path):
        from transmogrifai_tpu.models.trees import OpXGBoostClassifier
        from transmogrifai_tpu.stages.registry import (
            pack_args, unpack_args, build_stage)
        X, y = _xor_data(seed=31)
        model = OpXGBoostClassifier(num_round=5, max_depth=3).fit_arrays(X, y)
        store = {}
        packed = pack_args(model.save_args(), store, model.uid)
        restored = build_stage(type(model).__name__,
                               unpack_args(packed, store))
        p1 = model.predict_arrays(X)[2]
        p2 = restored.predict_arrays(X)[2]
        assert np.allclose(p1, p2, atol=1e-6)

    def test_softmax_model_round_trip(self):
        from transmogrifai_tpu.models.trees import OpXGBoostClassifier
        X, y = _blob_data(seed=37)
        model = OpXGBoostClassifier(num_round=3, max_depth=2).fit_arrays(X, y)
        args = model.save_args()
        cls = type(model)
        restored = cls.from_save_args(args)
        assert np.allclose(model.predict_arrays(X)[2],
                           restored.predict_arrays(X)[2], atol=1e-6)


class TestSelectorIntegration:
    def test_binary_selector_with_trees(self):
        from transmogrifai_tpu.automl.selectors import (
            BinaryClassificationModelSelector)
        from transmogrifai_tpu.models.trees import OpGBTClassifier
        from transmogrifai_tpu.models.glm import OpLogisticRegression
        from transmogrifai_tpu.stages.params import param_grid
        X, y = _xor_data(n=600, seed=41)
        sel = BinaryClassificationModelSelector.with_train_validation_split(
            models_and_parameters=[
                (OpLogisticRegression(), param_grid(reg_param=[0.01])),
                (OpGBTClassifier(), param_grid(max_iter=[10], max_depth=[3])),
            ])
        best = sel.fit_arrays(X, y)
        # XOR is not linearly separable: trees must win the sweep
        assert best.summary.best_model_type == "OpGBTClassifier"


class TestHistogramPaths:
    """The TPU matmul-histogram path must agree with the segment-sum path
    (they are alternative lowerings of the same reduction; grow_tree picks
    by backend, so CPU tests exercise the matmul path explicitly here)."""

    def _inputs(self, n=1000, f=6, b=8, n_nodes=4, k=2, seed=3):
        rng = np.random.default_rng(seed)
        Xb = jnp.asarray(rng.integers(0, b, size=(n, f)), jnp.int32)
        G = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
        H = jnp.asarray(rng.uniform(0.1, 1.0, size=n), jnp.float32)
        cu = jnp.asarray(H > 0, jnp.float32)
        node = jnp.asarray(rng.integers(0, n_nodes, size=n), jnp.int32)
        return Xb, G, H, cu, node, n_nodes, b

    def test_matmul_matches_segment(self):
        args = self._inputs()
        out_m = T._histograms_matmul(*args)
        out_s = T._histograms_segment(*args)
        for m, s in zip(out_m, out_s):
            assert np.allclose(np.asarray(m), np.asarray(s), atol=1e-3)

    def test_matmul_chunked_with_padding(self, monkeypatch):
        # force several chunks + a ragged tail (n=1000 with chunk=256)
        monkeypatch.setattr(T, "_HIST_CHUNK", 256)
        args = self._inputs(n=1000)
        out_m = T._histograms_matmul(*args)
        out_s = T._histograms_segment(*args)
        for m, s in zip(out_m, out_s):
            assert np.allclose(np.asarray(m), np.asarray(s), atol=1e-3)

    def test_grow_tree_matmul_path_matches(self, monkeypatch):
        """Full tree growth with the matmul histograms (as on TPU) produces
        the same splits and near-identical leaves as the segment path."""
        X, y = _xor_data(n=800, seed=7)
        edges = T.quantile_edges(jnp.asarray(X), 16)
        Xb = T.bin_matrix(jnp.asarray(X), edges)
        G = jnp.asarray((0.5 - y)[:, None], jnp.float32)
        H = jnp.full((len(y),), 0.25, jnp.float32)
        key = __import__("jax").random.PRNGKey(0)

        real_backend = T.jax.default_backend

        def grow(force_tpu):
            monkeypatch.setattr(
                T.jax, "default_backend",
                (lambda: "tpu") if force_tpu else real_backend)
            # bypass the jit cache with a jit of its own around the wrapped
            # fn (op by op, untraced, the two growths took 5 minutes of a
            # loaded worker): the backend is read when it is traced
            return jax.jit(functools.partial(
                T.grow_tree.__wrapped__, depth=3, n_bins=16, reg_lambda=1.0,
                leaf_mode="newton"))(Xb, G, H, key)

        t_mat = grow(True)
        t_seg = grow(False)
        assert np.array_equal(np.asarray(t_mat.feat), np.asarray(t_seg.feat))
        assert np.array_equal(np.asarray(t_mat.thresh),
                              np.asarray(t_seg.thresh))
        assert np.allclose(np.asarray(t_mat.leaf), np.asarray(t_seg.leaf),
                           atol=1e-4)

    def test_tpu_gather_free_paths_match(self, monkeypatch):
        """bin_matrix (edge counting), routing and prediction one-hot
        contractions — selected when backend=='tpu' — must equal the
        gather-based CPU lowerings exactly."""
        import jax
        X, y = _xor_data(n=700, seed=11)
        real_backend = T.jax.default_backend
        edges = T.quantile_edges(jnp.asarray(X), 16)

        monkeypatch.setattr(T.jax, "default_backend", lambda: "tpu")
        Xb_t = T.bin_matrix(jnp.asarray(X), edges)
        monkeypatch.setattr(T.jax, "default_backend", real_backend)
        Xb_c = T.bin_matrix(jnp.asarray(X), edges)
        assert np.array_equal(np.asarray(Xb_t), np.asarray(Xb_c))

        G = (0.5 - y)[:, None]
        H = jnp.full((len(y),), 0.25, jnp.float32)
        tree = T.grow_tree(Xb_c, jnp.asarray(G), H, __import__("jax").random.PRNGKey(3),
                           depth=4, n_bins=16, reg_lambda=1.0,
                           leaf_mode="newton")
        # routing parity
        node = jnp.asarray(np.random.default_rng(0).integers(0, 4, len(y)),
                           jnp.int32)
        f_lvl = tree.feat[3:7]
        t_lvl = tree.thresh[3:7]
        m_lvl = tree.miss[3:7]
        routed = T._route_level_matmul(Xb_c, node, f_lvl, t_lvl, m_lvl, 4)
        rows = jnp.arange(len(y))
        xb = Xb_c[rows, f_lvl[node]]
        expect = 2 * node + ((xb > t_lvl[node])
                             | ((xb == 0)
                                & (m_lvl[node] > 0))).astype(jnp.int32)
        assert np.array_equal(np.asarray(routed), np.asarray(expect))
        # prediction parity
        out_m = T._predict_bins_matmul(tree, Xb_c, 4)
        out_g = T.predict_bins(tree, Xb_c, 4)
        assert np.allclose(np.asarray(out_m), np.asarray(out_g), atol=1e-6)

    def test_route_chunk_padding(self, monkeypatch):
        monkeypatch.setattr(T, "_ROUTE_CHUNK", 128)
        X, y = _xor_data(n=500, seed=13)
        edges = T.quantile_edges(jnp.asarray(X), 8)
        Xb = T.bin_matrix(jnp.asarray(X), edges)
        node = jnp.asarray(np.random.default_rng(1).integers(0, 2, len(y)),
                           jnp.int32)
        f_lvl = jnp.asarray([1, 2], jnp.int32)
        t_lvl = jnp.asarray([3, 5], jnp.int32)
        m_lvl = jnp.asarray([0, 1], jnp.int32)
        routed = T._route_level_matmul(Xb, node, f_lvl, t_lvl, m_lvl, 2)
        rows = jnp.arange(len(y))
        xb = Xb[rows, f_lvl[node]]
        expect = 2 * node + ((xb > t_lvl[node])
                             | ((xb == 0)
                                & (m_lvl[node] > 0))).astype(jnp.int32)
        assert np.array_equal(np.asarray(routed), np.asarray(expect))

    def test_chunked_scan_boundary_full_fit(self, monkeypatch):
        """Full GBT fit through the forced-TPU path at N just past the
        histogram chunk boundary (chunked scan + sibling subtraction +
        one-hot routing all active) == the segment path's splits."""
        real_backend = T.jax.default_backend
        N, F, B = T._HIST_CHUNK + 1234, 8, 16
        rng = np.random.default_rng(21)
        X = rng.normal(size=(N, F)).astype(np.float32)
        y = (rng.uniform(size=N)
             < 1 / (1 + np.exp(-X @ np.linspace(1, -1, F)))).astype(
                 np.float32)
        w = jnp.ones(N, jnp.float32)
        edges = T.quantile_edges(jnp.asarray(X), B)
        Xb = T.bin_matrix(jnp.asarray(X), edges)
        key = __import__("jax").random.PRNGKey(2)

        def fit():
            # a jit of its own: traced anew under either backend
            return jax.jit(functools.partial(
                T.fit_gbt.__wrapped__, n_rounds=2, depth=4, n_bins=B,
                learning_rate=0.3, loss="logistic"))(
                    Xb, jnp.asarray(y), w, key)

        monkeypatch.setattr(T.jax, "default_backend", lambda: "tpu")
        trees_t, base_t = fit()
        pred_t = np.asarray(T.predict_forest_bins(trees_t, Xb, 4))
        monkeypatch.setattr(T.jax, "default_backend", real_backend)
        trees_c, base_c = fit()
        pred_c = np.asarray(T.predict_forest_bins(trees_c, Xb, 4))
        assert np.array_equal(np.asarray(trees_t.feat),
                              np.asarray(trees_c.feat))
        assert np.allclose(pred_t, pred_c, atol=5e-3)
