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
  5. uint8 binning for 128..255 bins is decision-identical to int32;
  6. a level at which no lane has a node left to split ends the tree
     (PR 52): the remaining passes sit under a cond and are not run, a
     dead node's all-left child holds the node's own sums whether its
     pass ran or not — so contract 1 holds when one lane's level is dead
     and another's is live — the passes run are counted off the trees,
     and a fit whose nodes or levels draw feature subsets keeps the
     unconditional loop.
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


# -- a dead level ends the tree ----------------------------------------------

DEAD_ROUNDS, DEAD_DEPTH, DEAD_BINS = 5, 4, 7
DEAD_KW = dict(n_rounds=DEAD_ROUNDS, depth=DEAD_DEPTH, n_bins=DEAD_BINS,
               learning_rate=0.5, loss="squared", normalize_gain=True,
               payload="residual_parts")
#: min_info_gain -> the live nodes of every (round, lane, level) the twins
#: grow on _reg_data, and so the level passes the program runs. 0.2: round
#: 0's last level dead in every lane (levels >= 3), round 1's levels >= 1,
#: the ROOT of every lane from round 2 on. 0.002: in round 4 lane 0's
#: levels 2 and 3 are dead while lanes 1 and 2 split there. 0.0: every
#: lane splits at every level of every round.
DEAD_CASES = {
    0.2: dict(run=4, dead_root_from=2),
    0.002: dict(run=20, lane_dead_where_others_live=(4, 0, 2)),
    0.0: dict(run=20, nothing_dead=True),
}


def _reg_data(n=700, f=6, b=DEAD_BINS, folds=3, seed=0):
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, b + 1, size=(n, f)).astype(np.int8)
    y = (Xb[:, 0] * 0.5 + (Xb[:, 1] > 3) * 1.0
         + 0.2 * rng.normal(size=n)).astype(np.float32)
    masks = (rng.integers(0, folds, size=n)[None, :]
             != np.arange(folds)[:, None]).astype(np.float32)
    return jnp.asarray(Xb), jnp.asarray(y), jnp.asarray(masks)


def _live_by_level(trees, depth=DEAD_DEPTH, bins=DEAD_BINS):
    """[rounds, lanes, depth] live nodes a level, off the split tables."""
    dead = np.asarray((trees.feat == 0) & (trees.thresh == bins)
                      & (trees.miss == 0))
    return np.stack([(~dead[..., (1 << d) - 1:(2 << d) - 1]).sum(-1)
                     for d in range(depth)], axis=-1)


@pytest.fixture(scope="module")
def dead_fits():
    """min_info_gain -> (fused, singles) of the squared-loss booster."""
    data = _reg_data()
    return {mig: _fit_lanes_and_each(*data, jax.random.PRNGKey(7),
                                     min_info_gain=mig, **DEAD_KW)
            for mig in DEAD_CASES}


@pytest.fixture(scope="module")
def every_pass_fits():
    """The same boosters through the loop that runs every pass (the rule
    switched off around a private jit: what the parent commit ran)."""
    data = _reg_data()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(T, "dead_levels_end_tree", lambda *a, **k: False)
        fit = jax.jit(functools.partial(T._fit_gbt_folds_impl, **DEAD_KW))
        return {mig: jax.block_until_ready(
            fit(*data, jax.random.PRNGKey(7), min_info_gain=mig))
            for mig in DEAD_CASES}


class TestDeadLevels:
    """A level with no live node in any lane ends the tree."""

    @pytest.mark.parametrize("mig", list(DEAD_CASES))
    def test_lanes_equal_singles_bit_for_bit(self, dead_fits, mig):
        fused, singles = dead_fits[mig]
        assert len(fused) == 3          # (trees, base, margins), as ever
        _assert_lanes_equal_singles(fused, singles, f"mig={mig}")
        live = _live_by_level(fused[0])
        case = DEAD_CASES[mig]
        if "dead_root_from" in case:
            r0 = case["dead_root_from"]
            assert (live[:r0, :, 0] == 1).all() and (live[r0:] == 0).all()
            # levels >= 3 of round 0 and levels >= 1 of round 1
            assert (live[0, :, 3] == 0).all() and (live[0, :, 2] > 0).all()
            assert (live[1, :, 1:] == 0).all()
        if "lane_dead_where_others_live" in case:
            r, lane, lvl = case["lane_dead_where_others_live"]
            others = [k for k in range(live.shape[1]) if k != lane]
            assert live[r, lane, lvl] == 0 and (live[r, others, lvl] > 0).all()
            # alone, that lane's tree ended there: its own program skipped
            # the passes the fused one ran
            alone = T.level_passes_run(singles[lane][0], depth=DEAD_DEPTH,
                                       n_bins=DEAD_BINS)
            assert int(alone) < DEAD_ROUNDS * DEAD_DEPTH == case["run"]
        if case.get("nothing_dead"):
            assert (live > 0).all()     # a dead node or two, no dead level

    @pytest.mark.parametrize("mig", list(DEAD_CASES))
    def test_passes_run_are_counted_off_the_trees(self, dead_fits, mig):
        trees = dead_fits[mig][0][0]
        run = T.level_passes_run(trees, depth=DEAD_DEPTH, n_bins=DEAD_BINS)
        assert run.dtype == jnp.int32 and int(run) == DEAD_CASES[mig]["run"]
        # a round runs the levels before its first level dead in EVERY lane
        live = _live_by_level(trees).sum(axis=1) > 0        # [rounds, depth]
        assert int(run) == int(np.cumprod(live, axis=1).sum())

    @pytest.mark.parametrize("mig", list(DEAD_CASES))
    def test_against_the_loop_that_runs_every_pass(
            self, dead_fits, every_pass_fits, mig):
        """Split arrays, base and margins bit for bit; a dead node's leaf
        within one float32 re-summation (the unconditional loop sums the
        same rows again in another pass's order) — and where nothing is
        dead, every array equal."""
        (trees, base, margins), _ = dead_fits[mig]
        ref_trees, ref_base, ref_margins = every_pass_fits[mig]
        for fld in ("feat", "thresh", "miss"):
            np.testing.assert_array_equal(np.asarray(getattr(trees, fld)),
                                          np.asarray(getattr(ref_trees, fld)))
        np.testing.assert_array_equal(np.asarray(base), np.asarray(ref_base))
        if DEAD_CASES[mig].get("nothing_dead"):
            np.testing.assert_array_equal(np.asarray(trees.leaf),
                                          np.asarray(ref_trees.leaf))
            np.testing.assert_array_equal(np.asarray(margins),
                                          np.asarray(ref_margins))
        else:
            np.testing.assert_allclose(np.asarray(trees.leaf),
                                       np.asarray(ref_trees.leaf),
                                       rtol=0, atol=1e-6)
            np.testing.assert_allclose(np.asarray(margins),
                                       np.asarray(ref_margins),
                                       rtol=0, atol=1e-5)

    @pytest.mark.parametrize("mig", [0.2])
    def test_against_the_sequential_fit_a_fold(self, dead_fits, mig):
        """fit_gbt on each fold's weights: the split arrays equal, the
        leaves within 1e-6. (At 0.002 a node without missing rows ties its
        two missing directions and the routes' histogram algebra breaks
        the tie differently, as it did before the rule: not held here.)"""
        Xb, y, W = _reg_data()
        trees = dead_fits[mig][0][0]
        seq_kw = {k: v for k, v in DEAD_KW.items() if k != "payload"}
        for k in range(W.shape[0]):
            seq, _ = T.fit_gbt(Xb, y, W[k], jax.random.PRNGKey(7),
                               min_info_gain=mig, **seq_kw)
            for fld in ("feat", "thresh", "miss"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(trees, fld))[:, k],
                    np.asarray(getattr(seq, fld)), err_msg=f"{fld} lane={k}")
            np.testing.assert_allclose(np.asarray(trees.leaf)[:, k],
                                       np.asarray(seq.leaf),
                                       rtol=0, atol=1e-6)

    def test_a_dead_lane_beside_live_ones_by_gamma(self):
        """Per-lane gamma vectors: lane 1's root is dead in every round
        (its own program runs no level pass at all), lanes 0 and 2 grow
        full trees — each lane still what it is alone."""
        Xb, y, W = _reg_data(seed=2)
        kw = dict(DEAD_KW, min_info_gain=0.0, n_rounds=3,
                  gamma=jnp.asarray([0.0, 1e9, 0.0], jnp.float32))
        fused, singles = _fit_lanes_and_each(Xb, y, W,
                                             jax.random.PRNGKey(3), **kw)
        _assert_lanes_equal_singles(fused, singles, "gamma lanes")
        live = _live_by_level(fused[0])
        assert (live[:, 1] == 0).all() and (live[:, [0, 2], 0] == 1).all()
        count = functools.partial(T.level_passes_run, depth=DEAD_DEPTH,
                                  n_bins=DEAD_BINS)
        assert int(count(singles[1][0])) == 0
        assert int(count(fused[0])) == 3 * DEAD_DEPTH

    def test_half_one_is_what_holds_a_dead_node_beside_a_live_lane(
            self, monkeypatch):
        """A pass that sums in another order than the one before it (the
        twins do not: here every fused pass's sums are off by a rounding's
        worth) moves no bit of a dead node: its left child is the held
        histogram whether the pass ran (beside a live lane) or not
        (alone). Without _held_by_dead_nodes the dead lane's leaves would
        differ between the two programs."""
        ok = jnp.asarray([[True, False]])
        out = np.asarray(T._held_by_dead_nodes(
            ok, jnp.ones((1, 2, 3, 2, 2)), jnp.full((1, 2, 3, 2, 2), 7.0)))
        assert (out[0, 0] == 1.0).all() and (out[0, 1] == 7.0).all()

        real = PH.route_hist

        def another_order(*a, **k):
            hist, node = real(*a, **k)
            return hist * (1.0 + 2.0 ** -18), node
        monkeypatch.setattr(PH, "route_hist", another_order)
        Xb, y, W = _reg_data(seed=2)
        kw = dict(DEAD_KW, min_info_gain=0.0, n_rounds=2)
        fit = jax.jit(functools.partial(T._fit_gbt_folds_impl, **kw))
        gamma = jnp.asarray([0.0, 1e9], jnp.float32)
        key = jax.random.PRNGKey(3)
        trees, base, margins = fit(Xb, y, W[:2], key, gamma=gamma)
        alone = fit(Xb, y, W[1:2], key, gamma=gamma[1:])
        assert (_live_by_level(trees)[:, 1] == 0).all()
        _assert_fit_equal(
            (T.Tree(*(getattr(trees, f)[:, 1:2] for f in T.Tree._fields)),
             base[1:2], margins[1:2]), alone, "the dead lane")

    def test_interpret_mode_lanes_equal_singles_with_dead_levels(self):
        """The bfloat16 contraction in three parts as the chip issues it,
        at the smallest shape: the passes of a dead level are skipped in a
        lane's own program and run in the fused one."""
        Xb, y, W = _reg_data(n=513, f=5, folds=2, seed=8)
        kw = dict(DEAD_KW, n_rounds=3, depth=3, min_info_gain=0.05,
                  interpret=True)
        fused, singles = _fit_lanes_and_each(Xb, y, W,
                                             jax.random.PRNGKey(7), **kw)
        _assert_lanes_equal_singles(fused, singles, "interpret")
        run = int(T.level_passes_run(fused[0], depth=3, n_bins=DEAD_BINS))
        assert 0 < run < 3 * 3


def _tables(levels_live, depth, bins=DEAD_BINS):
    """A [1, lanes, 2^depth - 1] Tree whose level d of lane k has
    levels_live[k][d] live nodes (the first ones), the rest dead."""
    lanes = len(levels_live)
    feat = np.zeros((1, lanes, (1 << depth) - 1), np.int32)
    thresh = np.full_like(feat, bins)
    for k, per_level in enumerate(levels_live):
        for d, n_live in enumerate(per_level):
            lo = (1 << d) - 1
            feat[0, k, lo:lo + n_live] = 1
            thresh[0, k, lo:lo + n_live] = 2
    return T.Tree(jnp.asarray(feat), jnp.asarray(thresh),
                  jnp.zeros((1, lanes, 1 << depth, 1)),
                  jnp.zeros_like(jnp.asarray(feat)))


@pytest.mark.parametrize("levels_live,want", [
    ([[0, 0, 0, 0]], 0),                    # a lone leaf: no pass of 4
    ([[1, 2, 4, 8]], 4),                    # a full tree: every pass
    ([[1, 2, 0, 0]], 2),                    # first dead level k -> k
    ([[1, 0, 0, 0]], 1),
    ([[1, 0, 0, 0], [1, 1, 1, 0]], 3),      # one live lane keeps the level
    ([[0, 0, 0, 0], [1, 2, 4, 8]], 4),
    ([[1, 0, 3, 0]], 1),    # nothing runs past the first dead level
], ids=["lone_leaf", "full_tree", "dead_from_2", "dead_from_1",
        "one_live_lane", "dead_lane_beside_full", "first_dead_level_ends"])
def test_level_passes_run_on_hand_built_tables(levels_live, want):
    tree = _tables(levels_live, 4)
    assert int(T.level_passes_run(tree, depth=4, n_bins=DEAD_BINS)) == want
    # a live split on feature 0 with every row left but default-right
    # missing is no dead table
    odd = tree._replace(miss=tree.miss.at[0, 0, 0].set(1))
    assert int(T.level_passes_run(odd, depth=4, n_bins=DEAD_BINS)) \
        == max(want, 1)


class TestWhereTheRuleEngages:
    """Decided by what the code can see: no per-node and no per-level
    feature draw. Elsewhere the program holds no cond."""

    def _booster_text(self, **kw):
        Xb, y, W = _reg_data(n=256)
        return str(jax.make_jaxpr(lambda *a: T._fit_gbt_folds_impl(
            *a, n_rounds=2, depth=3, n_bins=DEAD_BINS, loss="squared",
            **kw))(Xb, y, W, jax.random.PRNGKey(0)))

    def test_a_booster_puts_every_level_pass_under_a_cond(self):
        assert T.dead_levels_end_tree(1.0, 1.0)
        assert self._booster_text().count(" cond[") == 3    # depth, a round

    def test_a_level_draw_keeps_the_unconditional_loop(self):
        assert not T.dead_levels_end_tree(0.5, 1.0)
        assert " cond[" not in self._booster_text(colsample_bylevel=0.5)
        # a per-tree subset is the same candidates at parent and child
        assert self._booster_text(feature_frac=0.5).count(" cond[") == 3

    def test_forest_lanes_hold_no_cond(self):
        assert not T.dead_levels_end_tree(1.0, 22 / 64)
        Xb, y, W = _reg_data(n=256, folds=2)
        rw, node_keys = T.forest_bootstrap(
            jax.random.PRNGKey(0), 0, 1.0, n_rows=256, n_trees=2, group=2)
        text = str(jax.make_jaxpr(functools.partial(
            T.fit_forest_lanes, depth=3, n_bins=DEAD_BINS,
            feature_frac=0.5))(Xb, y, W, rw, node_keys,
                               jnp.zeros((2, 256), jnp.float32)))
        assert "cond[" not in text


def test_the_sweep_reports_passes_run_of_passes_planned(monkeypatch):
    """RegressionModelSelector -> mask_folds -> fit_gbt_folds on the twins:
    the estimator's last_lane_telemetry holds the passes planned and run as
    ints once the validator has fetched them, one `tree_levels_skipped`
    event a grid point carries both, and last_tree_telemetry is the dict
    it was."""
    from transmogrifai_tpu.automl.selectors import RegressionModelSelector
    from transmogrifai_tpu.automl.tuning.splitters import DataSplitter
    from transmogrifai_tpu.models import trees as MT
    rng = np.random.default_rng(0)
    n, f, rounds, depth = 2048, 6, 3, 3
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (10 + 1.7 * (X @ rng.normal(size=f) / np.sqrt(f)
                     + 0.65 * rng.normal(size=n))).astype(np.float32)
    monkeypatch.setattr(MT, "FOREST_LANE_BACKENDS", ("tpu", "cpu"))
    monkeypatch.setattr(MT, "FOREST_LANE_MIN_ROWS", 0)
    est = MT.OpGBTRegressor(max_iter=rounds, max_depth=depth, max_bins=8,
                            min_instances_per_node=10)
    sel = RegressionModelSelector.with_cross_validation(
        splitter=DataSplitter(seed=42, reserve_test_fraction=0.0),
        num_folds=3, seed=42, models_and_parameters=[
            (est, [{"min_info_gain": 0.001}, {"min_info_gain": 1.5}])])
    events, lanes_seen = [], []
    monkeypatch.setattr(
        collector, "event",
        lambda name, **kw: events.append(dict(kw, event=name)))
    real = MT._TreeEstimator._count_booster_fit

    def count(self, *a, **k):
        real(self, *a, **k)
        lanes_seen.append(self.last_lane_telemetry)
    monkeypatch.setattr(MT._TreeEstimator, "_count_booster_fit", count)
    sel.fit_arrays(X, y)
    assert sel.validator.last_tree_telemetry == {
        "model": "OpGBTRegressor", "route": "fold_fused", "programs": 2,
        "rounds": 2 * rounds, "scale_reductions": 2 * rounds, "lanes": 3,
        "payload_body": "residual_parts", "payload_rows": 5}
    sent = [e for e in events if e["event"] == "tree_levels_skipped"]
    assert len(sent) == len(lanes_seen) == 2
    for e, tele in zip(sent, lanes_seen):
        assert e["model"] == "OpGBTRegressor" and e["lanes"] == 3
        assert e["rounds"] == rounds
        assert e["planned"] == tele["level_passes_planned"] == rounds * depth
        assert type(tele["level_passes_run"]) is int
        assert e["run"] == tele["level_passes_run"]
    # the loose point grows full trees; 1.5 a weighted row kills the deep
    # levels of the toy matrix's trees
    assert sent[0]["run"] == rounds * depth > sent[1]["run"]
