"""Fold-fused tree growth at each level's own slot count + mesh lanes.

The fused tree fit (ops/trees.fit_gbt_folds) unrolls a tree over its
depth: level d splits 1 << d nodes and its fused route+histogram pass
(pallas_hist.route_hist) is traced at exactly that slot count — a level
with one live node never pays for the deepest level's 2^(depth-2).
Contracts pinned here:

  1. the multi-lane fused fit is DECISION/MARGIN BIT-EXACT with the
     per-fold single-lane fit across a parity zoo (depths 1-6,
     colsample_bylevel, alpha/max_delta_step, per-lane scalar vectors,
     squared loss, subsample, non-unit weights): the fold axis only
     batches;
  2. route_hist is traced with n_nodes == 1 << d at every fused level,
     single-device and under the sharded driver, and the `tree_fused`
     span's slot_passes is the sum of exactly those ints;
  3. one executable per (shape, depth): a re-sweep at the same
     (shape, depth) costs 0 true compiles and a depth change costs
     exactly 1 (RecompileTracker);
  4. the mesh route: fit_gbt_folds_sharded (shard_map over the batch
     axis, psum-merged per-level histograms) matches the single-device
     fused fit on the 2-device CPU mesh, and mask_fit_scores_grid takes
     it instead of falling back per-fold;
  5. uint8 binning for 128..255 bins is decision-identical to int32.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from transmogrifai_tpu.ops import pallas_hist as PH
from transmogrifai_tpu.ops import trees as T
from transmogrifai_tpu.parallel.mesh import make_mesh
from transmogrifai_tpu.utils.metrics import collector


def _data(n=700, f=6, b=7, folds=3, seed=0, unit_w=True):
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, b + 1, size=(n, f)).astype(np.int8)  # 0 = missing
    y = (rng.uniform(size=n) < 0.4).astype(np.float32)
    masks = (rng.integers(0, folds, size=n)[None, :]
             != np.arange(folds)[:, None]).astype(np.float32)
    W = masks if unit_w else masks * rng.uniform(
        0.5, 2.0, size=n).astype(np.float32)[None, :]
    return jnp.asarray(Xb), jnp.asarray(y), jnp.asarray(W)


def _fit_lanes_and_each(Xb, y, W, key, **kw):
    """The fused fit over every lane of W, and each lane alone through
    the same program at Fo == 1 (per-lane [Fo] vectors sliced along)."""
    fused = T.fit_gbt_folds(Xb, y, W, key, **kw)
    singles = []
    for k in range(W.shape[0]):
        kw_k = {n: v[k:k + 1] if getattr(v, "ndim", 0) == 1 else v
                for n, v in kw.items()}
        singles.append(T.fit_gbt_folds(Xb, y, W[k:k + 1], key, **kw_k))
    return fused, singles


def _assert_lanes_equal_singles(fused, singles, msg=""):
    trees, base, margins = fused
    for k, single in enumerate(singles):
        lane = (T.Tree(*(getattr(trees, fld)[:, k:k + 1]
                         for fld in T.Tree._fields)),
                base[k:k + 1], margins[k:k + 1])
        _assert_fit_equal(lane, single, f"{msg} lane={k}")


def _assert_fit_equal(a, b, msg=""):
    ta, ba, ma = a
    tb, bb, mb = b
    for fld in ("feat", "thresh", "miss", "leaf"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ta, fld)), np.asarray(getattr(tb, fld)),
            err_msg=f"{msg} tree.{fld}")
    np.testing.assert_array_equal(np.asarray(ba), np.asarray(bb),
                                  err_msg=f"{msg} base")
    np.testing.assert_array_equal(np.asarray(ma), np.asarray(mb),
                                  err_msg=f"{msg} margins")


class TestLaneParityZoo:
    """Fused lanes vs each lane alone: every tree decision and every
    margin bit-exact (each lane's contraction rows are disjoint)."""

    @pytest.mark.parametrize("depth", [1, 2, 3, 4, 5, 6])
    def test_depths(self, depth):
        Xb, y, W = _data()
        kw = dict(n_rounds=2, depth=depth, n_bins=7, learning_rate=0.3,
                  reg_lambda=1.0, loss="logistic")
        fused, singles = _fit_lanes_and_each(Xb, y, W,
                                             jax.random.PRNGKey(7), **kw)
        _assert_lanes_equal_singles(fused, singles, f"depth={depth}")

    @pytest.mark.parametrize("kw", [
        dict(colsample_bylevel=0.5),
        dict(alpha=0.4, max_delta_step=0.7),
        dict(colsample_bylevel=0.6, alpha=0.2, min_child_weight=1.0,
             gamma=0.05),
        dict(loss="squared"),
        dict(subsample=0.7),
        dict(feature_frac=0.6, colsample_bylevel=0.7),
    ], ids=["bylevel", "alpha_mds", "bylevel_alpha_mcw_gamma", "squared",
            "subsample", "bytree_bylevel"])
    def test_param_tail(self, kw):
        Xb, y, W = _data(n=640, seed=3, unit_w=False)
        base = dict(n_rounds=3, depth=3, n_bins=7, learning_rate=0.2,
                    reg_lambda=1.5, loss="logistic")
        base.update(kw)
        fused, singles = _fit_lanes_and_each(
            Xb, y, W, jax.random.PRNGKey(11), **base)
        _assert_lanes_equal_singles(fused, singles, str(kw))

    def test_per_lane_scalar_vectors(self):
        """The config-fused sweep's per-lane eta/lambda/mcw/gamma vectors
        give each lane what its own scalars give it alone."""
        Xb, y, W = _data(folds=3, seed=5)
        kw = dict(
            n_rounds=3, depth=4, n_bins=7, loss="logistic",
            learning_rate=jnp.asarray([0.1, 0.2, 0.3], jnp.float32),
            reg_lambda=jnp.asarray([1.0, 2.0, 0.5], jnp.float32),
            min_child_weight=jnp.asarray([0.0, 1.0, 0.0], jnp.float32),
            gamma=jnp.asarray([0.0, 0.05, 0.0], jnp.float32))
        fused, singles = _fit_lanes_and_each(
            Xb, y, W, jax.random.PRNGKey(42), **kw)
        _assert_lanes_equal_singles(fused, singles, "lane vectors")


@pytest.fixture
def route_hist_slots(monkeypatch):
    """The n_nodes of every pallas_hist.route_hist call traced while the
    fixture is live, in call order."""
    seen = []
    real = PH.route_hist

    def spy(*a, n_nodes, **k):
        seen.append(n_nodes)
        return real(*a, n_nodes=n_nodes, **k)

    monkeypatch.setattr(PH, "route_hist", spy)
    return seen


#: depth -> (the fit's arrays, the n_nodes route_hist was traced with): ONE
#: trace a depth for the tests that read the level widths, at a row count
#: no other test fits at (the jit cache cannot hold the program, so the fit
#: really traces) — a second test of a depth runs the cached program
_LEVEL_TRACES = {}


def _level_trace(depth):
    if depth not in _LEVEL_TRACES:
        seen, real = [], PH.route_hist

        def spy(*a, n_nodes, **k):
            seen.append(n_nodes)
            return real(*a, n_nodes=n_nodes, **k)
        data = _data(n=300 + depth, folds=2, seed=depth)
        PH.route_hist = spy
        try:
            T.fit_gbt_folds(*data, jax.random.PRNGKey(0), n_rounds=2,
                            depth=depth, n_bins=7)
        finally:
            PH.route_hist = real
        _LEVEL_TRACES[depth] = (data, seen)
    return _LEVEL_TRACES[depth]


def _one_tree_of(seen, depth):
    """The per-level slot counts of ONE tree out of a spy log: the round
    scan may trace its body more than once, every trace a whole tree."""
    per_tree = depth - 1
    assert seen and len(seen) % per_tree == 0, seen
    trees = [seen[i:i + per_tree] for i in range(0, len(seen), per_tree)]
    assert all(t == trees[0] for t in trees), seen
    return trees[0]


class TestLevelSlotCounts:
    """Each fused level runs at its OWN slot count — the test that fails
    if a form padded to the deepest level's slots comes back."""

    @pytest.mark.parametrize("depth", [3, 4, 5, 6])
    def test_route_hist_traced_at_level_width(self, depth):
        _, seen = _level_trace(depth)
        assert _one_tree_of(seen, depth) == \
            [1 << d for d in range(depth - 1)]

    @pytest.mark.parametrize("depth,slot_passes", [(6, 31), (4, 7)])
    def test_span_slot_passes_is_what_route_hist_saw(
            self, depth, slot_passes):
        from transmogrifai_tpu.models.trees import _TreeEstimator
        # the fit the level-width test traced: its program, its spy log
        (Xb, y, W), seen = _level_trace(depth)
        c = collector
        c.enable("tree_levels_span")
        try:
            _TreeEstimator._timed_fused_fit(
                "tree_sweep_fold_fused", Xb, W.shape[0], depth, 2,
                lambda: T.fit_gbt_folds(Xb, y, W, jax.random.PRNGKey(0),
                                        n_rounds=2, depth=depth,
                                        n_bins=7))
            c.finish()
        finally:
            c.disable()
        sp, = [s for s in c.trace.spans if s.kind == "tree_fused"]
        assert sp.name == "tree_levels"
        assert sp.attrs["lanes"] == 2 and sp.attrs["depth"] == depth
        assert sp.attrs["slot_passes"] == slot_passes
        assert sp.attrs["slot_passes"] == sum(_one_tree_of(seen, depth))

    @pytest.mark.parametrize("depth,node_rows", [(1, 24), (3, 40),
                                                 (6, 144)])
    def test_span_route_node_rows_is_what_the_kernels_laid_out(
            self, depth, node_rows, monkeypatch):
        """The span's counter and the kernels read ONE function: every
        node axis a tree's routing passes and its leaf lookup were traced
        with (interpret mode: the real kernels), summed."""
        from transmogrifai_tpu.models.trees import _TreeEstimator
        laid = {}
        real = PH.node_rows

        def spy(n, itemsize=4):
            # keyed: a second trace of the round body lays out the same
            laid[(n, itemsize)] = real(n, itemsize)
            return laid[(n, itemsize)]

        Xb, y, W = _data(n=320 + depth, folds=2, seed=depth)
        # the kernels' wrappers are jits of their own: a level another
        # test already traced at this shape would not size itself again
        for fn in (PH.route_pallas, PH._route_hist_pallas_jit,
                   PH.table_lookup_pallas):
            fn.clear_cache()
        c = collector
        c.enable("tree_levels_node_rows")
        try:
            # the span reads the counter before the spy goes in
            with monkeypatch.context() as m:
                _TreeEstimator._timed_fused_fit(
                    "tree_sweep_fold_fused", Xb, W.shape[0], depth, 1,
                    lambda: (m.setattr(PH, "node_rows", spy),
                             T.fit_gbt_folds(
                                 Xb, y, W, jax.random.PRNGKey(0),
                                 n_rounds=1, depth=depth, n_bins=7,
                                 interpret=True))[1])
            c.finish()
        finally:
            c.disable()
        sp, = [s for s in c.trace.spans if s.kind == "tree_fused"]
        assert sp.attrs["route_node_rows"] == node_rows \
            == PH.route_node_rows(depth)
        # levels 0..depth-1 route at 1 << d nodes (f32 rows), the lookup
        # reads 1 << depth leaves (bf16 rows)
        assert sorted(laid) == sorted(
            [(1 << d, 4) for d in range(depth)] + [(1 << depth, 2)])
        assert sum(laid.values()) == node_rows

    def test_plan_route_resident_follows_the_node_axis(self, monkeypatch):
        """plan_fused_hist budgets the routing half at the level's node
        rows, one lane group, and the flagship shape still fits the
        v5e's VMEM."""
        from transmogrifai_tpu.utils import platform as P
        monkeypatch.setattr(P, "device_spec",
                            lambda kind=None: P.DEVICE_SPECS["TPU v5 lite"])
        for (f, b, lanes, depth) in [(64, 33, 10, 6), (64, 33, 10, 3),
                                     (64, 33, 1, 12), (300, 257, 5, 6)]:
            plan = PH.plan_fused_hist(f, b, lanes, depth)
            cols = f * b
            onehot = cols * plan.blk * (4 + 2)
            minor = (f + lanes * 3 + lanes) * plan.blk * 8
            route_b = plan.vmem_bytes - plan.out_bytes - onehot - minor
            rows = PH.route_group_rows(plan.n_slots, lanes)
            assert route_b == 2 * rows * plan.blk * 4
            assert rows <= max(PH._ROUTE_GROUP_ROWS,
                               PH.node_rows(plan.n_slots))
            assert rows % PH.node_rows(plan.n_slots) == 0
        # depth 6: 16 nodes x 10 lanes = 160 rows where one lane's
        # one-hot alone was 128
        assert PH.route_group_rows(16, 10) == 160
        assert PH.route_group_rows(1, 10) == 80
        assert PH.route_group_rows(2048, 10) == 2048
        assert PH.fused_hist_fits(64, 33, 10, 6)
        assert PH.plan_fused_hist(64, 33, 10, 6).blk == 2048

    def test_sharded_fit_traces_the_same_level_widths(
            self, monkeypatch, route_hist_slots):
        depth = 5
        Xb, y, W = _data(n=352, folds=2, seed=12)
        kw = dict(n_rounds=1, depth=depth, n_bins=7)
        key = jax.random.PRNGKey(2)
        T.fit_gbt_folds(Xb, y, W, key, **kw)
        single = _one_tree_of(list(route_hist_slots), depth)
        del route_hist_slots[:]
        # a private program dict: a cached shard_map program would not
        # trace again
        monkeypatch.setattr(T, "_SHARDED_FIT_CACHE", {})
        T.fit_gbt_folds_sharded(Xb, y, W, key,
                                mesh=make_mesh(n_batch=2, n_model=1), **kw)
        assert _one_tree_of(route_hist_slots, depth) == single \
            == [1, 2, 4, 8]


class TestProgramCount:
    """One executable per (shape, depth), however many levels it unrolls."""

    def _run(self, Xb, y, W, depth):
        out = T.fit_gbt_folds(Xb, y, W, jax.random.PRNGKey(1),
                              n_rounds=2, depth=depth, n_bins=7)
        jax.block_until_ready(out)
        return out

    def test_resweep_zero_depth_change_one(self):
        Xb, y, W = _data(n=512, seed=9)
        # warm: both depths' helper programs (array placement etc.) and
        # depth 3's fit executable
        self._run(Xb, y, W, 3)
        c = collector
        c.enable("tree_levels_compiles")
        try:
            with c.trace_span("resweep", kind="sweep_fit"):
                self._run(Xb, y, W, 3)
            with c.trace_span("deeper", kind="sweep_fit"):
                self._run(Xb, y, W, 4)
            c.finish()
        finally:
            c.disable()
        by = {s.name: s for s in c.trace.spans}
        assert int(by["resweep"].attrs.get("compiles", 0)) == 0, \
            "re-sweep at the same (shape, depth) must hit the jit cache"
        assert int(by["deeper"].attrs.get("compiles", 0)) == 1, \
            "a depth change must cost exactly ONE fresh executable"


class TestShardedLanes:
    """Mesh-sharded (fold x config) lanes: psum-merged histograms.

    The strongest pin is BIT-EXACT: a 1-round squared-loss fit with
    base_score=0.0 has integer gradient/hessian payloads (g = -w*y,
    h = w with 0/1 weights), so every histogram cell is an integer sum
    < 2^24 — exact in f32 under ANY summation order, including the
    cross-shard psum. Trees and margins must then match the
    single-device fused fit bit for bit, isolating the psum plumbing
    from the separate (documented) near-tie effect: with real-valued
    payloads, psum reordering perturbs gains at the ulp level and an
    argmax between near-equal split candidates may flip — exactly why
    the validator keys mesh checkpoints separately (_sweep_path)."""

    def _int_kw(self):
        return dict(n_rounds=1, depth=3, n_bins=7, learning_rate=0.5,
                    reg_lambda=1.0, loss="squared", base_score=0.0)

    @pytest.fixture(scope="class")
    def sharded(self):
        """One matrix and one mesh for the class: the sharded form takes
        its algebra scalars as lane vectors, so the integer-payload fits
        of two tests are ONE sharded program at one shape."""
        return _data(n=640, folds=2, seed=1), \
            make_mesh(n_batch=2, n_model=1)

    def test_sharded_bit_exact_on_integer_payloads(self, sharded):
        (Xb, y, W), mesh = sharded
        key = jax.random.PRNGKey(3)
        un = T.fit_gbt_folds(Xb, y, W, key, **self._int_kw())
        sh = T.fit_gbt_folds_sharded(Xb, y, W, key, mesh=mesh,
                                     **self._int_kw())
        _assert_fit_equal(un, sh, "sharded integer payloads")
        # trees replicate: every shard grew from the same psum'd hists
        assert np.asarray(sh[0].feat).shape == (1, 2, 7)

    def test_sharded_per_lane_vectors_bit_exact(self, sharded):
        (Xb, y, W), mesh = sharded
        key = jax.random.PRNGKey(5)
        kw = dict(self._int_kw(),
                  learning_rate=jnp.asarray([0.1, 0.3], jnp.float32),
                  reg_lambda=jnp.asarray([1.0, 4.0], jnp.float32))
        un = T.fit_gbt_folds(Xb, y, W, key, **kw)
        sh = T.fit_gbt_folds_sharded(Xb, y, W, key, mesh=mesh, **kw)
        _assert_fit_equal(un, sh, "sharded lane vectors")

    def test_sharded_matches_single_device_logistic(self, sharded):
        """Multi-round logistic: real-valued payloads, so parity is
        allclose on a seed verified tie-free (see class docstring)."""
        (Xb, y, W), mesh = sharded
        key = jax.random.PRNGKey(3)
        kw = dict(n_rounds=3, depth=3, n_bins=7, learning_rate=0.3,
                  reg_lambda=1.0, loss="logistic")
        _, b1, m1 = T.fit_gbt_folds(Xb, y, W, key, **kw)
        _, b2, m2 = T.fit_gbt_folds_sharded(Xb, y, W, key, mesh=mesh, **kw)
        np.testing.assert_allclose(np.asarray(b2), np.asarray(b1),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(m2), np.asarray(m1),
                                   rtol=1e-4, atol=1e-5)

    def test_sharded_rejects_subsample(self):
        Xb, y, W = _data(n=512, folds=2)
        mesh = make_mesh(n_batch=2, n_model=1)
        with pytest.raises(ValueError, match="subsample"):
            T.fit_gbt_folds_sharded(Xb, y, W, jax.random.PRNGKey(0),
                                    mesh=mesh, n_rounds=1, depth=2,
                                    n_bins=7, subsample=0.8)


class TestGridMeshRoute:
    """mask_fit_scores_grid no longer falls back per-fold on a mesh."""

    def _est(self, **kw):
        from transmogrifai_tpu.models.trees import OpXGBoostClassifier
        return OpXGBoostClassifier(num_round=3, max_depth=3, max_bins=15,
                                   **kw)

    def _arrays(self, n=600, d=5, seed=0):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        masks = (rng.integers(0, 2, size=n)[None, :]
                 != np.arange(2)[:, None]).astype(np.float32)
        return X, jnp.asarray(y), jnp.asarray(masks)

    def test_grid_route_sharded_matches_meshless(self):
        est = self._est()
        X, y, masks = self._arrays()
        w = jnp.ones_like(y)
        grids = [{"eta": 0.1, "reg_lambda": 1.0},
                 {"eta": 0.3, "reg_lambda": 4.0}]
        mesh = make_mesh(n_batch=2, n_model=1)
        # mesh context: the device binning path (a host-tagged native
        # context never reaches the fused kernels)
        ctx = est.mask_sweep_context(jnp.asarray(X), mesh=mesh)
        sharded = est.mask_fit_scores_grid(ctx, y, w, masks, grids,
                                           mesh=mesh)
        assert sharded is not None, "mesh grid sweep must not fall back"
        assert est._last_grid_route == "grid_fused_sharded"
        # meshless reference: the same lanes through the single-device
        # fused program (the gate is TPU-only, so call the kernel direct)
        Xb, edges, n_bins = ctx
        F = masks.shape[0]
        W_lanes = jnp.stack([masks * w[None, :] for _ in grids],
                            axis=0).transpose(1, 0, 2).reshape(
                                len(grids) * F, y.shape[0])
        lane = dict(
            learning_rate=jnp.tile(jnp.asarray([0.1, 0.3], jnp.float32), F),
            reg_lambda=jnp.tile(jnp.asarray([1.0, 4.0], jnp.float32), F),
            min_child_weight=jnp.tile(jnp.asarray([1.0, 1.0], jnp.float32),
                                      F),
            gamma=jnp.zeros(len(grids) * F, jnp.float32))
        kw = est._common()
        shared = {k: v for k, v in kw.items() if k not in est._LANE_KEYS}
        _, _, ref = T.fit_gbt_folds(Xb, y, W_lanes, est._key(),
                                    n_bins=n_bins, loss="logistic",
                                    **shared, **lane)
        ref = ref.reshape(F, len(grids), y.shape[0]).transpose(1, 0, 2)
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_shard_kill_switch_and_subsample_gate(self, monkeypatch):
        est = self._est()
        X, y, masks = self._arrays(n=400)
        w = jnp.ones_like(y)
        grids = [{"eta": 0.1}, {"eta": 0.3}]
        mesh = make_mesh(n_batch=2, n_model=1)
        ctx = est.mask_sweep_context(jnp.asarray(X), mesh=mesh)
        monkeypatch.setenv("TMOG_TREE_SHARD", "0")
        assert est.mask_fit_scores_grid(ctx, y, w, masks, grids,
                                        mesh=mesh) is None
        monkeypatch.delenv("TMOG_TREE_SHARD")
        sub = self._est(subsample=0.8)
        assert sub.mask_fit_scores_grid(ctx, y, w, masks, grids,
                                        mesh=mesh) is None


class TestUint8Bins:
    """128..255 bins now bin to uint8 end-to-end (2x+ less Xb traffic)."""

    def test_bin_dtype_tiers(self):
        rng = np.random.default_rng(0)
        X = jnp.asarray(rng.normal(size=(400, 4)).astype(np.float32))
        for n_bins, want in ((100, jnp.int8), (127, jnp.int8),
                             (128, jnp.uint8), (200, jnp.uint8),
                             (255, jnp.uint8), (300, jnp.int32)):
            edges = T.quantile_edges(X, n_bins)
            Xb = T.bin_matrix(X, edges)
            assert Xb.dtype == jnp.dtype(want), (n_bins, Xb.dtype)
            assert int(jnp.max(Xb)) <= n_bins

    def test_host_bin_dtype(self):
        from transmogrifai_tpu.ops import trees_host as TH
        rng = np.random.default_rng(1)
        X = rng.normal(size=(300, 3)).astype(np.float32)
        Xb, edges, _ = TH.bin_context(X, 200)
        assert Xb.dtype == np.uint8
        assert Xb.max() <= 200
        # device twin agrees bin-for-bin at the shared dtype tier
        Xb_d = np.asarray(T.bin_matrix(jnp.asarray(X), jnp.asarray(edges)))
        np.testing.assert_array_equal(Xb_d.astype(np.int32),
                                      Xb.astype(np.int32))

    def test_uint8_fit_parity_with_int32(self):
        """Same bins, narrow vs wide dtype: identical trees + margins."""
        rng = np.random.default_rng(2)
        n = 500
        X = jnp.asarray(rng.normal(size=(n, 5)).astype(np.float32))
        y = jnp.asarray((rng.uniform(size=n) < 0.5).astype(np.float32))
        W = jnp.asarray((rng.integers(0, 2, size=(2, n)) > 0)
                        .astype(np.float32))
        edges = T.quantile_edges(X, 200)
        Xb8 = T.bin_matrix(X, edges)
        assert Xb8.dtype == jnp.uint8
        kw = dict(n_rounds=2, depth=3, n_bins=200)
        key = jax.random.PRNGKey(8)
        out8 = T.fit_gbt_folds(Xb8, y, W, key, **kw)
        out32 = T.fit_gbt_folds(Xb8.astype(jnp.int32), y, W, key, **kw)
        _assert_fit_equal(out8, out32, "uint8 vs int32")

    def test_stream_bin_matrix_uint8(self):
        from transmogrifai_tpu.parallel.tileplane import ArraySource
        rng = np.random.default_rng(3)
        X = rng.normal(size=(700, 4)).astype(np.float32)
        edges = np.asarray(T.quantile_edges(jnp.asarray(X), 150))
        got = T.stream_bin_matrix(ArraySource(X), edges, tile_rows=256)
        assert got.dtype == np.uint8
        want = np.asarray(T.bin_matrix(jnp.asarray(X), jnp.asarray(edges)))
        np.testing.assert_array_equal(got, want)


def test_fused_folds_still_equal_single_fold_runs_in_interpret_mode():
    """The PR 1 contract (each lane's contraction rows are disjoint)
    through the interpret-mode pallas kernels, every level at its own
    slot count."""
    Xb, y, W = _data(n=513, f=5, b=7, folds=2, seed=8)
    kw = dict(n_rounds=2, depth=3, n_bins=7, interpret=True)
    fit = functools.partial(T.fit_gbt_folds, Xb, y,
                            key=jax.random.PRNGKey(7), **kw)
    _, base, margins = fit(W=W)
    for k in range(W.shape[0]):
        _, base1, m1 = fit(W=W[k:k + 1])
        np.testing.assert_array_equal(np.asarray(margins[k]),
                                      np.asarray(m1[0]))
        assert float(base[k]) == float(base1[0])
