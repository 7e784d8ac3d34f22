"""Pallas gradient-histogram kernel vs the segment-sum reference, in
interpreter mode (the kernel's logic, layouts and accumulation across grid
steps — compiled-TPU execution is exercised by the bench)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.ops import trees as T
from transmogrifai_tpu.ops import pallas_hist as PH


def _inputs(n, f=6, b=8, n_nodes=4, k=1, seed=0):
    rng = np.random.default_rng(seed)
    Xb = jnp.asarray(rng.integers(0, b, size=(n, f)), jnp.int8)
    G = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    H = jnp.asarray(rng.uniform(0.1, 1.0, size=n), jnp.float32)
    cu = jnp.asarray(H > 0, jnp.float32)
    node = jnp.asarray(rng.integers(0, n_nodes, size=n), jnp.int32)
    return Xb, G, H, cu, node, n_nodes, b


@pytest.mark.parametrize("n", [PH._BLK, 4 * PH._BLK])
def test_kernel_matches_segment(n):
    Xb, G, H, cu, node, n_nodes, B = _inputs(n)
    K = G.shape[1]
    C = K + 2
    pay = jnp.concatenate([G.T, H[None], cu[None]], axis=0)
    hist = PH.hist_pallas(Xb.T, pay, node[None].astype(jnp.float32),
                          n_slots=n_nodes, n_bins=B, interpret=True)
    hist = np.asarray(hist).reshape(n_nodes, C, Xb.shape[1], B)
    hg, hh, hc = T._histograms_segment(Xb, G, H, cu, node, n_nodes, B)
    assert np.allclose(hist[:, :K].transpose(0, 2, 3, 1), np.asarray(hg),
                       atol=1e-4)
    assert np.allclose(hist[:, K], np.asarray(hh), atol=1e-4)
    assert np.allclose(hist[:, K + 1], np.asarray(hc), atol=1e-4)


def test_out_of_range_slot_drops_rows():
    """slot == n_slots (padding / subtraction encoding) contributes 0."""
    Xb, G, H, cu, node, n_nodes, B = _inputs(2 * PH._BLK, seed=3)
    pay = jnp.concatenate([G.T, H[None], cu[None]], axis=0)
    dropped = jnp.full_like(node, n_nodes)
    hist = PH.hist_pallas(Xb.T, pay, dropped[None].astype(jnp.float32),
                          n_slots=n_nodes, n_bins=B, interpret=True)
    assert np.allclose(np.asarray(hist), 0.0)


def test_histograms_pallas_wrapper_shapes(monkeypatch):
    """trees._histograms_pallas transposes/reshapes consistently with the
    XLA paths (interpret mode, forced availability). With the tree
    consumers' bf16 contraction inputs forced OFF the values must match
    the segment path near-exactly; with them on (the default,
    PH._HIST_BF16) the g/h channels carry ~0.4% relative quantization
    while the unit-count channel stays exact."""
    monkeypatch.setattr(PH, "available", lambda: True)
    import functools
    real = PH.hist_pallas
    monkeypatch.setattr(
        PH, "hist_pallas",
        functools.partial(real, interpret=True))
    Xb, G, H, cu, node, n_nodes, B = _inputs(2 * PH._BLK, k=2, seed=5)
    out_s = T._histograms_segment(Xb, G, H, cu, node, n_nodes, B)
    prev = PH._HIST_BF16
    try:
        PH.set_hist_bf16(False)
        out_p = T._histograms_pallas(Xb, G, H, cu, node, n_nodes, B)
        for a, b_ in zip(out_p, out_s):
            assert a.shape == b_.shape
            assert np.allclose(np.asarray(a), np.asarray(b_), atol=1e-4)
        PH.set_hist_bf16(True)
        out_b = T._histograms_pallas(Xb, G, H, cu, node, n_nodes, B)
        # the bf16 leg must actually quantize: bitwise equality with the
        # f32 leg would mean the flag did not reach the kernel
        assert any(np.any(np.asarray(a) != np.asarray(p))
                   for a, p in zip(out_b[:2], out_p[:2]))
        for a, b_ in zip(out_b, out_s):
            assert a.shape == b_.shape
            ref = np.asarray(b_)
            assert np.allclose(np.asarray(a), ref,
                               atol=0.02 * (np.abs(ref).max() + 1.0))
        np.testing.assert_array_equal(np.asarray(out_b[2]),
                                      np.asarray(out_s[2]))  # counts exact
    finally:
        PH.set_hist_bf16(prev)


class TestBinnedLanes:
    """Lane-batched binned rank metrics vs the per-lane scatter path."""

    def _lanes(self, L=3, n=1500, seed=7):
        rng = np.random.default_rng(seed)
        scores = jnp.asarray(rng.normal(size=(L, n)), jnp.float32)
        y = jnp.asarray((rng.uniform(size=n) < 0.4), jnp.float32)
        w = jnp.asarray(rng.uniform(0.2, 1.0, size=(L, n)), jnp.float32)
        return scores, y, w

    def test_cpu_route_matches_scatter(self):
        from transmogrifai_tpu.ops import metrics_ops as M
        scores, y, w = self._lanes()
        tps, fps = M.binned_cum_counts_lanes(scores, y, w, 256)
        for l in range(scores.shape[0]):
            t1, f1 = M._binned_cum_counts(scores[l], y, w[l], 256)
            assert np.allclose(np.asarray(tps[l]), np.asarray(t1), atol=1e-3)
            assert np.allclose(np.asarray(fps[l]), np.asarray(f1), atol=1e-3)

    def test_pallas_route_matches_scatter(self):
        from transmogrifai_tpu.ops import metrics_ops as M
        scores, y, w = self._lanes(L=4, n=1100)  # forces tail padding
        tps, fps = M._binned_cum_counts_lanes_pallas(scores, y, w, 128,
                                                     interpret=True)
        for l in range(scores.shape[0]):
            t1, f1 = M._binned_cum_counts(scores[l], y, w[l], 128)
            assert np.allclose(np.asarray(tps[l]), np.asarray(t1), atol=1e-3)
            assert np.allclose(np.asarray(fps[l]), np.asarray(f1), atol=1e-3)

    def test_au_pr_lanes_matches_scalar(self):
        from transmogrifai_tpu.ops import metrics_ops as M
        scores, y, w = self._lanes(L=2, n=900, seed=9)
        vals = np.asarray(M.au_pr_binned_lanes(scores, y, w, 512))
        for l in range(2):
            ref = float(M.au_pr_binned(scores[l], y, w[l], 512))
            assert abs(vals[l] - ref) < 1e-4

    def test_au_roc_lanes_matches_scalar(self):
        from transmogrifai_tpu.ops import metrics_ops as M
        scores, y, w = self._lanes(L=2, n=900, seed=11)
        vals = np.asarray(M.au_roc_binned_lanes(scores, y, w, 512))
        for l in range(2):
            ref = float(M.au_roc_binned(scores[l], y, w[l], 512))
            assert abs(vals[l] - ref) < 1e-4


class TestHeldoutOnceCounts:
    """The k-fold rank metric's one-pass counts
    (metrics_ops.heldout_cum_counts_lanes): a row goes into the histograms
    of the ONE fold that holds it out, Gc grid points at a time."""

    @staticmethod
    def _rows(n, n_folds, Gc, seed):
        rng = np.random.default_rng(seed)
        scores = rng.normal(size=(Gc, n)).astype(np.float32)
        y = (rng.uniform(size=n) < 0.4).astype(np.float32)
        w = rng.uniform(0.2, 2.0, size=n).astype(np.float32)
        # fold n_folds: held out by no fold, the row must vanish
        fold_of = rng.integers(0, n_folds + 1, size=n).astype(np.int32)
        return scores, y, w, fold_of

    @staticmethod
    def _numpy_counts(idx, y, w, fold_of, n_folds, n_bins):
        """[n_folds, Gc, 2, n_bins] weighted (positive, negative)
        histograms, float64, row by row in numpy."""
        Gc, n = idx.shape
        out = np.zeros((n_folds, Gc, 2, n_bins))
        keep = fold_of < n_folds
        for g in range(Gc):
            for c, wv in enumerate((w * y, w * (1.0 - y))):
                np.add.at(out[:, g, c], (fold_of[keep], idx[g, keep]),
                          wv[keep])
        return out

    @pytest.mark.parametrize("layout", ["grid_as_features", "grid_in_slot"])
    @pytest.mark.parametrize("n_folds,Gc,n", [(5, 6, 1100), (3, 8, 2048),
                                               (1, 3, 777)])
    def test_one_hist_call_matches_numpy(self, layout, n_folds, Gc, n):
        """ONE hist_pallas call (interpret mode) over a ragged N with
        dropped rows, in the form the sweep uses (grid points as the
        kernel's features, the fold as its slot) and in the flattened form
        with the slot `fold_of * Gc + g` (n_slots = F x Gc): same counts,
        numpy's."""
        from transmogrifai_tpu.ops import metrics_ops as M
        n_bins = 128
        scores, y, w, fold_of = self._rows(n, n_folds, Gc, seed=n)
        idx = np.asarray(M._bin_idx(jnp.asarray(scores), n_bins))
        ref = self._numpy_counts(idx, y, w, fold_of, n_folds, n_bins)
        pay = np.stack([w * y, w * (1.0 - y)])
        if layout == "grid_as_features":
            hist = PH.hist_pallas(
                jnp.asarray(idx), jnp.asarray(pay),
                jnp.asarray(fold_of, jnp.float32)[None, :],
                n_slots=n_folds, n_bins=n_bins, interpret=True)
            got = np.asarray(hist).reshape(n_folds, 2, Gc, n_bins) \
                .transpose(0, 2, 1, 3)
        else:
            S = n_folds * Gc
            slot = np.where(fold_of[None, :] < n_folds,
                            fold_of[None, :] * Gc + np.arange(Gc)[:, None],
                            S)
            hist = PH.hist_pallas(
                jnp.asarray(idx.reshape(1, -1)),
                jnp.asarray(np.tile(pay, (1, Gc))),
                jnp.asarray(slot.reshape(1, -1), jnp.float32),
                n_slots=S, n_bins=n_bins, interpret=True)
            got = np.asarray(hist).reshape(n_folds, Gc, 2, n_bins)
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-3)
        assert abs(got.sum() - w[fold_of < n_folds].sum() * Gc) < 1e-1

    @pytest.mark.parametrize("n_folds,Gc,n", [(5, 6, 1100), (1, 3, 777)])
    def test_pallas_route_matches_jnp_twin_and_lanes_route(self, n_folds,
                                                            Gc, n):
        """The dispatcher's two routes agree, and both with the
        (fold x grid)-lane route fed whole-matrix weights that are zero
        outside the row's own fold — what the fold-by-fold loop bins."""
        from transmogrifai_tpu.ops import metrics_ops as M
        scores, y, w, fold_of = map(jnp.asarray,
                                    self._rows(n, n_folds, Gc, seed=n + 1))
        tp, fp = M._heldout_cum_counts_lanes_pallas(
            scores, y, w, fold_of, n_folds, 256, interpret=True)
        tj, fj = M.heldout_cum_counts_lanes(scores, y, w, fold_of, n_folds,
                                            256)    # CPU: the jnp twin
        assert tp.shape == fp.shape == (n_folds, Gc, 256)
        np.testing.assert_allclose(np.asarray(tp), np.asarray(tj),
                                   rtol=0, atol=1e-3)
        np.testing.assert_allclose(np.asarray(fp), np.asarray(fj),
                                   rtol=0, atol=1e-3)
        for f in range(n_folds):
            wl = jnp.broadcast_to((w * (fold_of == f))[None, :], (Gc, n))
            tl, fl = M.binned_cum_counts_lanes(scores, y, wl, 256)
            np.testing.assert_allclose(np.asarray(tj[f]), np.asarray(tl),
                                       rtol=0, atol=1e-3)
            np.testing.assert_allclose(np.asarray(fj[f]), np.asarray(fl),
                                       rtol=0, atol=1e-3)
        for fn, lanes in ((M.au_pr_heldout_lanes, M.au_pr_binned_lanes),
                          (M.au_roc_heldout_lanes, M.au_roc_binned_lanes)):
            vals = np.asarray(fn(scores, y, w, fold_of, n_folds, 256))
            assert vals.shape == (n_folds, Gc)
            for f in range(n_folds):
                wl = jnp.broadcast_to((w * (fold_of == f))[None, :],
                                      (Gc, n))
                np.testing.assert_allclose(
                    vals[f], np.asarray(lanes(scores, y, wl, 256)),
                    rtol=0, atol=1e-6)


def test_set_pallas_enabled_toggles_and_clears_caches():
    from transmogrifai_tpu.ops import trees as T2
    orig = T2.pallas_enabled()
    try:
        T2.set_pallas_enabled(False)
        assert not T2.pallas_enabled()
        T2.set_pallas_enabled(False)  # idempotent
        T2.set_pallas_enabled(True)
        assert T2.pallas_enabled()
    finally:
        T2.set_pallas_enabled(orig)


def test_lanes_4096_bins_block_sizing():
    """The production rank-metric shape (4096 bins): block_rows shrinks
    the one-level body's tile; hist_pallas runs the two-level body there
    (ops/pallas_rank_hist.py), and results still match the scatter path."""
    from transmogrifai_tpu.ops import metrics_ops as M
    from transmogrifai_tpu.ops import pallas_rank_hist as RH
    assert PH.block_rows(4096) < PH._BLK
    assert RH.hist_body(4096, False) == "two_level"
    rng = np.random.default_rng(17)
    L, n = 3, 700
    scores = jnp.asarray(rng.normal(size=(L, n)), jnp.float32)
    y = jnp.asarray((rng.uniform(size=n) < 0.5), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(L, n)), jnp.float32)
    idx = M._bin_idx(scores, 4096)
    pos = w * y[None, :]
    neg = w * (1.0 - y[None, :])
    lane = jnp.broadcast_to(jnp.arange(L, dtype=jnp.float32)[:, None],
                            (L, n))
    flat = lambda a: a.reshape(1, L * n)
    hist = PH.hist_pallas(flat(idx),
                          jnp.concatenate([flat(pos), flat(neg)], axis=0),
                          flat(lane), n_slots=L, n_bins=4096,
                          interpret=True)
    hist = np.asarray(hist).reshape(L, 2, 4096)
    for l in range(L):
        t1, f1 = M._binned_cum_counts(scores[l], y, w[l], 4096)
        assert np.allclose(np.cumsum(hist[l, 0][::-1]), np.asarray(t1),
                           atol=1e-3)
        assert np.allclose(np.cumsum(hist[l, 1][::-1]), np.asarray(f1),
                           atol=1e-3)


class TestFusedVmemGuard:
    """ADVICE r4 (medium): the fold-fused histogram's VMEM-resident output
    block [n_folds*n_slots*C, F*B] scales with folds x slots x F x bins,
    but only the one-hot tile was budgeted — XGB-shaped configs compiled
    to a Mosaic failure with no library fallback."""

    def test_sweep_shapes_fit(self):
        # the BASELINE sweep shape (64 feat, 33 bins, 5 folds, depth 6)
        # must keep the fused route on any generation's budget
        assert PH.fused_hist_fits(64, 33, 5, 6) or PH._vmem_limit() < (
            100 << 20)  # CPU test host reports the conservative limit

    def test_xgb_default_shape_rejected(self, monkeypatch):
        # 300 features x 257 bins x 5 folds x depth 6: output block alone
        # is ~74MB; with the one-hot tile it exceeds even v5e+ VMEM
        monkeypatch.setattr(PH, "_vmem_limit", lambda: 100 << 20)
        assert not PH.fused_hist_fits(300, 257, 5, 6)

    def test_baseline_shape_fits_on_v5e_budget(self, monkeypatch):
        monkeypatch.setattr(PH, "_vmem_limit", lambda: 100 << 20)
        assert PH.fused_hist_fits(64, 33, 5, 6)
        assert not PH.fused_hist_fits(2048, 257, 5, 6)

    def test_route_gate_consults_footprint(self, monkeypatch):
        # _fused_route_ok must return False for an over-budget shape even
        # when every other condition passes
        from transmogrifai_tpu.models import trees as MT
        est = MT.OpXGBoostClassifier()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(PH, "available", lambda: True)
        monkeypatch.setattr(est, "_VMAP_FOLD_MAX_ROWS", 0)
        Xb = jnp.zeros((8, 300), jnp.int8)
        y = jnp.zeros(8, jnp.float32)
        masks = jnp.ones((5, 8), jnp.float32)
        ctx = (Xb, jnp.zeros((300, 256)), 256)
        monkeypatch.setattr(PH, "_vmem_limit", lambda: 100 << 20)
        assert not est._fused_route_ok(ctx, y, masks, depth=6)
        # a sweep-sized shape on the same gate stays on the fused route
        ctx_small = (jnp.zeros((8, 64), jnp.int8), jnp.zeros((64, 32)), 32)
        assert est._fused_route_ok(ctx_small, y, masks, depth=6)
