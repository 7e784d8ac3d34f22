"""Tree-family predictors: decision tree, random forest, GBT, XGBoost-class.

Reference wrappers: core/.../impl/classification/{OpDecisionTreeClassifier,
OpRandomForestClassifier, OpGBTClassifier, OpXGBoostClassifier}.scala and
core/.../impl/regression/{OpDecisionTreeRegressor, OpRandomForestRegressor,
OpGBTRegressor, OpXGBoostRegressor}.scala. Param names mirror the Spark/
XGBoost params the reference grids over (DefaultSelectorParams.scala:35-56).

All training runs through ops/trees histogram kernels — quantile binning +
level-wise growth as one XLA program per ensemble (scan over trees/rounds).
The reference reached C++ (libxgboost via JNI + Rabit allreduce) for exactly
this workload; here the same histogram build is a segment-sum whose
cross-chip reduction is an XLA psum over ICI.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import trees as T
from ..stages.params import Param
from .base import PredictionModel, PredictorEstimator, stable_sigmoid


def _softmax_np(raw: np.ndarray) -> np.ndarray:
    m = raw.max(axis=1, keepdims=True)
    e = np.exp(raw - m)
    return e / e.sum(axis=1, keepdims=True)


class TreeEnsembleModel(PredictionModel):
    """Fitted tree ensemble. Serving traverses raw-value thresholds in numpy
    (the Spark-free local-scoring path); `feat`/`thresh_val`/`leaf` carry a
    leading [n_trees] axis (flattened rounds x classes for softmax boosting).

    mode: 'classify_mean'  — payload K=n_classes distributions, averaged
          'margin'         — payload K=1 logistic margins, summed + base
          'regress_mean'   — payload K=1 values, averaged
          'regress_sum'    — payload K=1 boosting steps, summed + base
          'softmax'        — n_trees = rounds*n_classes, per-class margin sum
    """

    def __init__(self, feat: np.ndarray, thresh_val: np.ndarray,
                 leaf: np.ndarray, depth: int, mode: str,
                 base: float = 0.0, n_classes: int = 2,
                 miss: Optional[np.ndarray] = None,
                 operation_name: str = "treeEnsemble",
                 uid: Optional[str] = None):
        super().__init__(operation_name, uid=uid)
        self.feat = np.asarray(feat, np.int32)
        self.thresh_val = np.asarray(thresh_val, np.float32)
        self.leaf = np.asarray(leaf, np.float32)
        # models saved before missing-direction learning default NaN left
        self.miss = (np.zeros_like(self.feat) if miss is None
                     else np.asarray(miss, np.int32))
        self.depth = int(depth)
        self.mode = mode
        self.base = float(base)
        self.n_classes = int(n_classes)

    def predict_arrays(self, X):
        X = np.asarray(X, np.float32)
        agg = T.np_predict_ensemble(self.feat, self.thresh_val, self.leaf,
                                    X, self.depth,
                                    miss=self.miss)         # [N, K] sums
        n_trees = self.feat.shape[0]
        if self.mode == "classify_mean":
            prob = agg / n_trees
            prob = np.clip(prob, 0.0, None)
            prob = prob / np.maximum(prob.sum(axis=1, keepdims=True), 1e-12)
            pred = prob.argmax(axis=1).astype(np.float32)
            return pred, agg, prob
        if self.mode == "margin":
            margin = agg[:, 0] + self.base
            p1 = stable_sigmoid(margin)
            prob = np.stack([1.0 - p1, p1], axis=1)
            raw = np.stack([-margin, margin], axis=1)
            return (p1 >= 0.5).astype(np.float32), raw, prob
        if self.mode == "regress_mean":
            return (agg[:, 0] / n_trees).astype(np.float32), None, None
        # regress_sum
        return (agg[:, 0] + self.base).astype(np.float32), None, None

    def save_args(self) -> Dict[str, Any]:
        d = super().save_args()
        d.update(feat=self.feat, thresh_val=self.thresh_val, leaf=self.leaf,
                 miss=self.miss, depth=self.depth, mode=self.mode,
                 base=self.base, n_classes=self.n_classes)
        return d


class SoftmaxEnsembleModel(PredictionModel):
    """Multiclass boosted ensemble: trees grouped [rounds, n_classes]."""

    def __init__(self, feat: np.ndarray, thresh_val: np.ndarray,
                 leaf: np.ndarray, depth: int, n_classes: int,
                 miss: Optional[np.ndarray] = None,
                 operation_name: str = "xgbSoftmax",
                 uid: Optional[str] = None):
        super().__init__(operation_name, uid=uid)
        self.feat = np.asarray(feat, np.int32)          # [R*C, I]
        self.thresh_val = np.asarray(thresh_val, np.float32)
        self.leaf = np.asarray(leaf, np.float32)        # [R*C, L, 1]
        self.miss = (np.zeros_like(self.feat) if miss is None
                     else np.asarray(miss, np.int32))
        self.depth = int(depth)
        self.n_classes = int(n_classes)

    def predict_arrays(self, X):
        X = np.asarray(X, np.float32)
        n = X.shape[0]
        C = self.n_classes
        margins = np.zeros((n, C), np.float32)
        for c in range(C):
            margins[:, c] = T.np_predict_ensemble(
                self.feat[c::C], self.thresh_val[c::C], self.leaf[c::C],
                X, self.depth, miss=self.miss[c::C])[:, 0]
        prob = _softmax_np(margins)
        pred = prob.argmax(axis=1).astype(np.float32)
        return pred, margins, prob

    def save_args(self) -> Dict[str, Any]:
        d = super().save_args()
        d.update(feat=self.feat, thresh_val=self.thresh_val, leaf=self.leaf,
                 miss=self.miss, depth=self.depth, n_classes=self.n_classes)
        return d


# -- estimator machinery ----------------------------------------------------

class _TreeEstimator(PredictorEstimator):
    """Shared: quantile-bin on device, grow, freeze raw-value thresholds."""

    supports_grid_vmap = False
    # validator fast path: folds enter as weight masks over one binned matrix
    # (Validator._validate_mask_folds) — no per-fold host slicing. Bin edges
    # then come from the full feature columns (labels never participate).
    supports_mask_folds = True

    def _bin(self, X, n_valid: int = None):
        """(binned matrix, edges, n_bins).

        Keeps X's dtype (bf16 sweeps stay bf16 — no full-size f32 copy;
        quantile_edges casts only its row sample). NaN gets the dedicated
        bin 0 and routes by each node's learned direction (Tree.miss) —
        never folded into the value bins. `n_valid`: number of REAL rows
        when the caller padded X to a mesh multiple
        (validators._device_arrays repeats the last row) — the quantile
        sketch uses only the real rows so mesh and meshless runs grow
        from IDENTICAL bin edges; padded rows still bin (real values,
        zero weight — inert in every histogram)."""
        n_bins = int(self.get_param("max_bins"))
        Xd = jnp.asarray(X)
        Xq = Xd if n_valid is None or n_valid >= Xd.shape[0] \
            else Xd[:n_valid]
        edges = T.quantile_edges(Xq, n_bins)
        Xb = T.bin_matrix(Xd, edges)
        return Xb, edges, n_bins

    # -- host (C++) route ---------------------------------------------------
    # On the CPU backend, tree fits go through native/trees.cpp: the XLA
    # kernels' dense 2^depth-node levels are the right shape for the MXU
    # but pure waste for deep trees at host scale (the reference's default
    # RF grid reaches maxDepth=12 -> 4096-node levels; measured 11.8s for
    # one warm 50-tree fit on 900 Titanic rows vs 0.04s native). Same
    # role as libxgboost's C++ behind the reference's OpXGBoost*.
    @staticmethod
    def _host_route() -> bool:
        # same truthiness convention as TMOG_NO_PALLAS (pallas_hist.py)
        if os.environ.get("TMOG_NO_HOST_TREES", "").strip().lower() \
                not in ("", "0", "false"):
            return False
        import jax as _jax
        if _jax.default_backend() != "cpu":
            return False
        from ..ops import trees_host as TH
        return TH.available()

    def _bin_host(self, X, n_valid: int = None):
        from ..ops import trees_host as TH
        n_bins = int(self.get_param("max_bins"))
        Xn = np.asarray(X, np.float32)
        Xq = Xn if n_valid is None or n_valid >= Xn.shape[0] \
            else Xn[:n_valid]
        edges = TH.quantile_edges_host(Xq, n_bins)
        return TH.bin_matrix_host(Xn, edges), edges, n_bins

    # -- mask-fold sweep protocol ------------------------------------------
    def mask_sweep_context(self, X, n_valid: int = None, mesh=None):
        """Binned context shared by every (grid, fold) fit — host-tagged
        when the native route is taken. A mesh run must stay on the
        device path even on the CPU backend (the virtual-device parity
        story: sharded and single-device sweeps go through the SAME
        kernels; the native builder's near-tie choices differ)."""
        if mesh is None and self._host_route() and not self._fused_sweep_here(
                int(n_valid or X.shape[0])):
            return ("host",) + self._bin_host(X, n_valid=n_valid)
        return self._bin(X, n_valid=n_valid)

    # Above this row count the fold axis stops being vmapped: XLA lays the
    # vmapped traversal's [folds, n] node-index arrays out fold-minor and
    # pads the fold axis to the 128-lane tile (5 -> 128 = 25.6x HBM; the
    # 10M-row bench config needed 20.9G and failed to compile). One fold of
    # 10M rows already saturates the MXU, so large-N folds run sequentially
    # through the SAME cached per-fold executable.
    _VMAP_FOLD_MAX_ROWS = 2_000_000
    # the fold-vmapped branch must never reach the pallas histogram path
    # (pallas_call does not sit under a batch axis here) — enforced against
    # the kernel-selection threshold, and not via `assert` (stripped by -O)
    if _VMAP_FOLD_MAX_ROWS >= T._PALLAS_MIN_ROWS:
        raise RuntimeError(
            "_VMAP_FOLD_MAX_ROWS must stay below ops.trees._PALLAS_MIN_ROWS")

    def mask_fit_scores(self, ctx, y, w, masks, n_classes: int = 2,
                        multiclass: bool = False):
        """[F, n] margins (binary/regression) or [F, n, c] class scores:
        one fit+predict per fold per grid point, entirely on device against
        the shared binned matrix. `multiclass` (the validator's problem
        type, NOT n_classes — a multiclass sweep over 2-class data must
        still return [F, n, c]) picks the score shape. Folds are vmapped
        below _VMAP_FOLD_MAX_ROWS and loop over one compiled program above
        it (see the constant's rationale). A host-tagged context (CPU
        backend + native builder) runs the per-fold loop in C++ instead."""
        if isinstance(ctx, tuple) and len(ctx) == 4 and ctx[0] == "host":
            host_ctx = ctx[1:]
            yn = np.asarray(y, np.float32)
            wn = np.asarray(w, np.float32)
            mn = np.asarray(masks, np.float32)
            return np.stack([
                self._mask_score_host(host_ctx, yn, wn * mn[f], n_classes,
                                      multiclass)
                for f in range(mn.shape[0])])
        fused = self._mask_scores_fused(ctx, y, w, masks, n_classes,
                                        multiclass)
        if fused is not None:
            return fused

        def one(m):
            return self._mask_score(ctx, y, w * m, n_classes, multiclass)
        if y.shape[0] <= self._VMAP_FOLD_MAX_ROWS:
            return jax.vmap(one)(masks)
        return jnp.stack([one(masks[f]) for f in range(masks.shape[0])])

    def _mask_scores_fused(self, ctx, y, w, masks, n_classes, multiclass):
        """All-folds-in-one-program fast path; None = not applicable
        (family hook — the GBT/XGB boosters implement it)."""
        return None

    # -- config-fused sweep (grid points batched into the fold axis) ------
    #: fit_gbt_folds args that may vary PER LANE (pure algebra scalars);
    #: every other kw must match across a fused group
    _LANE_KEYS = ("learning_rate", "reg_lambda", "min_child_weight",
                  "gamma")
    _LANE_DEFAULTS = {"learning_rate": 0.1, "reg_lambda": 1.0,
                      "min_child_weight": 0.0, "gamma": 0.0}

    def _sweep_kw(self):
        """The kw dict this family passes to fit_gbt_folds (hook)."""
        return None

    def grid_fuse_signature(self, grid):
        """Hashable structural signature: grid points with EQUAL
        signatures fit in one fold-fused device program (they differ only
        in per-lane algebra scalars). None = this grid point cannot
        fuse. Used by the validator to batch the sweep."""
        est_g = self.copy(**grid)
        kw = est_g._sweep_kw()
        if kw is None:
            return None
        items = tuple(sorted(
            (k, v) for k, v in kw.items() if k not in self._LANE_KEYS))
        # seed from the GRID-APPLIED copy: a swept seed must split the
        # group (one key drives the shared subsample/colsample draws)
        return items + (("loss", getattr(self, "_loss", "logistic")),
                        ("seed", int(est_g.get_param("seed"))
                         if est_g.has_param("seed") else 0))

    def mask_fit_scores_grid(self, ctx, y, w, masks, grids,
                             n_classes: int = 2, multiclass: bool = False,
                             mesh=None):
        """[G, F, n] margins for a GROUP of same-signature grid points in
        as few device programs as fit VMEM/HBM, or None (validator falls
        back to per-config mask_fit_scores). The lanes axis is
        (config, fold) pairs over the SHARED binned matrix: one histogram
        one-hot pass serves every config and fold, and the contraction M
        dim grows from folds*3 toward the MXU's 128 rows (the measured
        headroom in docs/performance.md's roofline table).

        `mesh` (the validator's sweep mesh, None off-mesh): when the
        batch axis spans >1 devices the group runs through
        T.fit_gbt_folds_sharded — rows shard over the mesh, per-level
        histograms psum-merge (DrJAX MapReduce shape), split algebra and
        trees replicate — instead of the old unconditional fallback to
        the sequential per-fold path. Gated by _sharded_route_ok
        (TMOG_TREE_SHARD kill switch, subsample == 1.0)."""
        if isinstance(ctx, tuple) and len(ctx) == 4 and ctx[0] == "host":
            return None   # host-tagged sweep: the C++ builder path
        regression = (getattr(self, "_regression", False)
                      or getattr(self, "_loss", "logistic") == "squared")
        if multiclass and not regression:
            return None
        if len(grids) < 2:
            return None
        kws = [self.copy(**g)._sweep_kw() for g in grids]
        if any(k is None for k in kws):
            return None
        sigs = {self.grid_fuse_signature(g) for g in grids}
        if len(sigs) != 1 or None in sigs:
            return None
        depth = kws[0]["depth"]
        from ..parallel.mesh import mesh_batch_count
        n_shards = mesh_batch_count(mesh)
        if n_shards > 1:
            if not self._sharded_route_ok(kws[0]):
                return None
        elif not self._fused_route_ok(ctx, y, masks, depth):
            return None
        from ..ops import pallas_hist
        Xb, edges, n_bins = ctx
        F = masks.shape[0]
        n = y.shape[0]
        G = len(grids)
        # chunk size from the one chunker (ops/pallas_hist
        # plan_lane_chunk): the fused kernel's VMEM residents scale with
        # lane count, HBM carries 4 lane-sized f32 planes (W, g, h,
        # margins), and Mosaic's layout search explodes when the out
        # block nears the scoped-VMEM boundary (r5 session 2: 20+ min
        # compiles at a 16MB out block) — the chunker gates all three,
        # INCLUDING at chunk == 1 (a single config's fold lanes that
        # clear the VMEM gate can still bust the HBM/out-block caps;
        # ADVICE round 5), where 0 falls back per-config. On a mesh the
        # lane row-planes shard, so the HBM lane budget scales with the
        # shard count (the chunker's lane-shard budget).
        sharded = n_shards > 1
        self.last_lane_telemetry = None
        # the word of how a round's g enters the contraction: its rows a
        # (lane, slot) size the plan; the sharded form issues one part
        word = payload_body(self)
        if sharded and T.PAYLOAD_PARTS[word] != 1:
            word = self._decline_parts(
                "rows sharded over a mesh: fit_gbt_folds_sharded takes the "
                "payload in one part")
        rows = T.payload_rows(word)
        chunk = pallas_hist.plan_lane_chunk(
            Xb.shape[1], n_bins + 1, F, G, depth, channels=rows,
            n_shards=n_shards)
        if chunk == 0:
            return None

        self._last_grid_route = "grid_fused_sharded" if sharded \
            else "grid_fused"
        label = "tree_sweep_grid_fused_sharded" if sharded \
            else "tree_sweep_grid_fused"
        span = "tree_shard_merge" if sharded else "tree_levels"
        loss = "squared" if regression else "logistic"
        outs = []
        for lo in range(0, G, chunk):
            sub = kws[lo:lo + chunk]
            g_here = len(sub)
            # per-config w (scale_pos_weight may vary across the grid)
            Ws = []
            for gi in range(lo, lo + g_here):
                est_g = self.copy(**grids[gi])
                w_g = est_g._apply_spw(y, w, n_classes, multiclass) \
                    if hasattr(est_g, "_apply_spw") else w
                Ws.append(masks * w_g[None, :])
            # FOLD-MAJOR lanes (fold slow, config fast): all configs of a
            # fold sit adjacent in the batched kernel's lane axis, and
            # the 5 folds share one residency of the binned matrix per
            # program — lane = f * g_here + config
            W_lanes = jnp.stack(Ws, axis=0).transpose(1, 0, 2) \
                .reshape(g_here * F, n)                    # [F*g, n]
            lane_vec = {
                key: jnp.tile(jnp.asarray(
                    [float(k.get(key, self._LANE_DEFAULTS[key]))
                     for k in sub], jnp.float32), F)
                for key in self._LANE_KEYS}
            shared = {k: v for k, v in sub[0].items()
                      if k not in self._LANE_KEYS}
            # the signature pins one seed per group; honor the grid's
            key = self.copy(**grids[lo])._key()
            if sharded:
                def fit(W_lanes=W_lanes, key=key, shared=shared,
                        lane_vec=lane_vec):
                    return T.fit_gbt_folds_sharded(
                        Xb, y, W_lanes, key, mesh=mesh, n_bins=n_bins,
                        loss=loss, **shared, **lane_vec)
            else:
                def fit(W_lanes=W_lanes, key=key, shared=shared,
                        lane_vec=lane_vec):
                    return T.fit_gbt_folds(
                        Xb, y, W_lanes, key, n_bins=n_bins, loss=loss,
                        payload=word, **shared, **lane_vec)
            trees, _, margins = self._timed_fused_fit(
                label, Xb, g_here * F, depth, shared["n_rounds"], fit,
                span=span, payload=word)
            self._count_booster_fit(word, g_here * F, shared, n_bins, trees)
            outs.append(margins.reshape(F, g_here, n).transpose(1, 0, 2))
        return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]

    # (backend, label, shape signature) tuples whose fused program has
    # already run once this process — the first run's wall includes jit
    # trace + Mosaic compile (documented 20+ min at sweep shapes), so its
    # span is marked cold and readers must compare warm spans only. Keyed
    # by backend: after force_cpu re-scopes the platform, a shape warmed
    # on one backend must NOT be misclassified warm on the other (a fresh
    # backend means fresh executables, and a mislabeled cold span's
    # compile wall would pollute warm-span GB/s claims).
    _WARM_FUSED_SHAPES: set = set()

    @staticmethod
    def _timed_fused_fit(label, Xb, lanes, depth, n_rounds, call,
                         span="tree_levels", payload="gradient"):
        """Run one fused-sweep fit; when stage metrics are being
        collected, time it to completion and record a kernel-roofline
        span (analytic HBM bytes from the single traffic model in
        ops/pallas_hist) so BENCH_*.json can report achieved GB/s and
        %-of-roof without a hand-run roofline script. The first span per
        (backend, label, shape) carries cold=True: its wall contains the
        compile, not just the kernel, and would wildly understate
        achieved GB/s. Every fit also runs inside a named `tree_fused`
        trace span — "tree_levels", or "tree_shard_merge" on a mesh — so
        a Perfetto view shows which merge form ran and the
        RecompileTracker books the fit's compiles to it
        (docs/observability.md). Its `slot_passes` is the sum of the
        slot counts the level loop hands route_hist for one tree
        (ops/trees.fused_level_slots): 31 at depth 6; `route_node_rows`
        the node rows a lane the routing and lookup kernels lay out for
        one tree (pallas_hist.route_node_rows: 144 at depth 6, 896 when
        every table was padded to 128); `payload_body` the word of how a
        round's g enters the bfloat16 contraction (payload_body(est)),
        `payload_rows` the rows a (lane, slot) it implies (3 | 5) and
        `rounds` the boosting rounds of the fit. The span is there
        with collection off too (its profiler annotation costs nothing
        then); the fence and the kernel record are not: they change
        what a timed sweep measures."""
        from ..ops import pallas_hist
        from ..utils.metrics import collector
        rows = T.payload_rows(payload)
        cm = collector.trace_span(
            span, kind="tree_fused", lanes=int(lanes), depth=int(depth),
            slot_passes=sum(T.fused_level_slots(int(depth))),
            route_node_rows=pallas_hist.route_node_rows(int(depth)),
            payload_body=payload, payload_rows=rows, rounds=int(n_rounds))
        if not collector.enabled:
            with cm:
                return call()
        import time
        sig = (jax.default_backend(), label,
               Xb.shape, str(Xb.dtype), lanes, depth, n_rounds)
        cold = sig not in _TreeEstimator._WARM_FUSED_SHAPES
        t0 = time.perf_counter()
        with cm:
            out = call()
            jax.block_until_ready(out)
        collector.kernel(
            label, time.perf_counter() - t0,
            pallas_hist.fused_fit_bytes(
                Xb.shape[0], Xb.shape[1], lanes, depth, n_rounds,
                xb_itemsize=Xb.dtype.itemsize, payload_rows=rows),
            cold=cold,
            # shape attrs ride into the kernel span of the trace export,
            # so a Perfetto view names the program's sweep geometry
            attrs=dict(lanes=int(lanes), depth=int(depth),
                       n_rounds=int(n_rounds), n_rows=int(Xb.shape[0]),
                       payload_rows=rows))
        _TreeEstimator._WARM_FUSED_SHAPES.add(sig)
        return out

    #: what the booster fits of the last sweep call counted (programs,
    #: rounds, scale reductions, the widest program's lanes, the payload
    #: word and its rows): the validator sums it into last_tree_telemetry
    last_lane_telemetry: Optional[Dict[str, Any]] = None

    def _count_booster_fit(self, word, lanes, kw, n_bins, trees) -> None:
        """One fold-fused booster program (the fit's own `kw`, the `trees`
        it returned) into last_lane_telemetry (a grid-fused group runs
        several: they add up). `level_passes_run`,
        the fused and routing passes the fit ran of the rounds x depth
        planned (ops/trees.level_passes_run: a level with no node left to
        split in any lane ends the tree), stays a DEVICE scalar here, read
        off the trees behind the fit in the queue: whoever fetches the
        fit's scores fetches it after them (the validator's
        _count_tree_lanes, which then holds an int), so the fit waits for
        nothing."""
        tele = self.last_lane_telemetry or dict(
            route="fold_fused", programs=0, rounds=0, scale_reductions=0,
            lanes=0, level_passes_planned=0, level_passes_run=0)
        parts = T.PAYLOAD_PARTS[word]
        n_rounds, depth = int(kw["n_rounds"]), int(kw["depth"])
        planned = ran = n_rounds * depth
        if T.dead_levels_end_tree(float(kw.get("colsample_bylevel", 1.0))):
            ran = T.level_passes_run(trees, depth=depth, n_bins=n_bins)
        tele.update(
            programs=tele["programs"] + 1,
            rounds=tele["rounds"] + n_rounds,
            # one max-reduction over [lanes, N] a round, only in parts
            scale_reductions=tele["scale_reductions"]
            + (n_rounds if parts != 1 else 0),
            lanes=max(tele["lanes"], int(lanes)),
            payload_body=word, payload_rows=T.payload_rows(word),
            level_passes_planned=tele["level_passes_planned"] + planned,
            # no eager add on the device for the one program of a point
            level_passes_run=tele["level_passes_run"] + ran
            if tele["programs"] else ran)
        self.last_lane_telemetry = tele

    def _decline_parts(self, reason: str) -> str:
        """This fit carries the payload in ONE bfloat16 part where the
        estimator's word (payload_body) asks for three: say so with a
        `booster_parts_route_declined` event, as forest_lane_route_declined
        does for the forests, and return the word that runs."""
        from ..utils.metrics import collector
        collector.event("booster_parts_route_declined",
                        model=type(self).__name__,
                        payload_body=payload_body(self), reason=reason)
        return "gradient"

    def _sharded_route_ok(self, kw) -> bool:
        """Gate for the mesh-sharded fused sweep (mask_fit_scores_grid
        with a >1-device batch axis). TMOG_TREE_SHARD=0 is the kill
        switch; row subsample must stay 1.0 (per-shard uniform draws are
        index-local — every shard would draw the same bits for its local
        rows, matching neither the single-device mask nor independence).
        Unlike _fused_route_ok there is no TPU/pallas requirement: on
        CPU meshes the jnp twin dispatchers run the identical call
        shape, which is what makes the route parity-testable in CI."""
        if os.environ.get("TMOG_TREE_SHARD", "").strip().lower() \
                in ("0", "false", "off"):
            return False
        return float(kw.get("subsample", 1.0)) >= 1.0

    @staticmethod
    def _one_device(Xb) -> bool:
        """Does the binned matrix live on a single device? The pallas
        kernels are placed only then: under plain GSPMD a pallas_call is
        not partitioned (each device would gather and process the whole
        matrix), so a mesh sweep keeps the chunked XLA histograms, which
        GSPMD does partition."""
        try:
            return len(Xb.sharding.device_set) <= 1
        except AttributeError:  # host array
            return True

    def _fused_route_ok(self, ctx, y, masks=None, depth=None):
        return not self._fused_route_why(ctx, y, masks, depth)

    def _fused_route_why(self, ctx, y, masks=None, depth=None) -> str:
        """Shared gate for the fold-fused booster path ("" = taken, else
        why not): fused kernels to run on (a live pallas TPU; the
        backends and the row floor are FOREST_LANE_BACKENDS /
        FOREST_LANE_MIN_ROWS, the fused lane routes' hand overrides) on a
        single device above the fold-vmap row limit. Mesh-sharded
        contexts keep the per-fold path HERE (single-config fits);
        the GRID sweep has its own mesh route — mask_fit_scores_grid
        dispatches to fit_gbt_folds_sharded under _sharded_route_ok.
        When the caller supplies the sweep shape (masks + tree depth),
        the fused kernel's VMEM footprint is checked too — its output
        block scales with folds x slots x F x bins, and an over-budget
        shape is a Mosaic compile failure, so those fall back to the
        sequential per-fold path. The footprint is that of the rows a
        (lane, slot) the estimator's payload word implies."""
        from ..ops import pallas_hist
        Xb, _, n_bins = ctx
        why = fused_lanes_why(int(y.shape[0]), self._VMAP_FOLD_MAX_ROWS)
        if why:
            return why
        if not self._one_device(Xb):
            return "the binned matrix is laid over a mesh"
        if masks is not None and depth is not None:
            # fit_gbt_folds histograms with B = n_bins + 1 slots per bin axis
            rows = T.payload_rows(payload_body(self))
            if not pallas_hist.fused_hist_fits(
                    Xb.shape[1], n_bins + 1, masks.shape[0], depth,
                    channels=rows):
                return (f"depth {depth} at {rows} rows a (lane, slot): the "
                        f"fused output block outgrows VMEM")
        return ""

    def _fused_sweep_here(self, n_rows: int) -> bool:
        """Would a sweep of this many rows take the boosters' fold-fused
        route on this backend (_sweep_kw: the booster families)? Then it
        runs on the device context whatever the backend — a CPU rehearsal
        takes the kernels' jnp twins, not the native builder."""
        return self._sweep_kw() is not None and not fused_lanes_why(
            n_rows, self._VMAP_FOLD_MAX_ROWS)

    def _fold_fused_scores(self, ctx, y, w, masks, kw, loss):
        """The boosters' fold-fused fit (ops/trees.fit_gbt_folds: every
        fold a lane of one program) under the estimator's payload word,
        or None where _fused_route_why declines — with a
        `booster_parts_route_declined` event where the word asked for
        three parts and the sequential fits will issue one."""
        word = payload_body(self)
        self.last_lane_telemetry = None
        if not self._fused_route_ok(ctx, y, masks, kw["depth"]):
            if T.PAYLOAD_PARTS[word] != 1:
                self._decline_parts(
                    self._fused_route_why(ctx, y, masks, kw["depth"]))
            return None
        Xb, edges, n_bins = ctx
        lanes = int(masks.shape[0])
        trees, _, margins = self._timed_fused_fit(
            "tree_sweep_fold_fused", Xb, lanes, kw["depth"], kw["n_rounds"],
            lambda: T.fit_gbt_folds(
                Xb, y, masks * w[None, :], self._key(), n_bins=n_bins,
                loss=loss, payload=word, **kw),
            payload=word)
        self._count_booster_fit(word, lanes, kw, n_bins, trees)
        return margins

    def _decline_sequential(self) -> None:
        """A single fit on the device grows one tree a program through
        grow_tree, whose kernels take the payload in one part."""
        if T.PAYLOAD_PARTS[payload_body(self)] != 1:
            self._decline_parts("the sequential fit_gbt: one tree a "
                                "program, its payload in one part")

    def _mask_score(self, ctx, y, w, n_classes, multiclass):
        raise NotImplementedError

    def _mask_score_host(self, ctx, y, w, n_classes, multiclass):
        raise NotImplementedError

    def _host_fallback(self, ctx, y, w, n_classes, multiclass):
        """Device-path retry for _mask_score_host when the native library
        vanishes mid-flight (shared by every family)."""
        Xb, edges, n_bins = ctx
        return np.asarray(self._mask_score(
            (jnp.asarray(Xb), jnp.asarray(edges), n_bins),
            jnp.asarray(y), jnp.asarray(w), n_classes, multiclass))

    def _freeze(self, trees: T.Tree, edges) -> Dict[str, np.ndarray]:
        feat = np.asarray(trees.feat)
        thresh = np.asarray(trees.thresh)
        tv = np.asarray(T.thresholds_to_values(
            jnp.asarray(feat), jnp.asarray(thresh), edges))
        leaf = np.asarray(trees.leaf)
        miss = np.asarray(trees.miss)
        # stack any leading (rounds, classes) axes into one tree axis
        feat = feat.reshape(-1, feat.shape[-1])
        tv = tv.reshape(-1, tv.shape[-1])
        leaf = leaf.reshape(-1, leaf.shape[-2], leaf.shape[-1])
        miss = miss.reshape(-1, miss.shape[-1])
        return dict(feat=feat, thresh_val=tv, leaf=leaf, miss=miss)

    def _key(self):
        return jax.random.PRNGKey(int(self.get_param("seed")))

    def _w(self, y, w):
        return (np.ones_like(y, np.float32) if w is None
                else np.asarray(w, np.float32))


def _feature_frac(strategy: str, n_feat: int, classification: bool) -> float:
    """Spark featureSubsetStrategy -> fraction (RandomForest.scala defaults);
    ops/trees.features_per_node turns it into Spark's count, its ceiling."""
    if strategy == "all":
        return 1.0
    if strategy == "auto":
        return (np.sqrt(n_feat) / n_feat) if classification else (1.0 / 3.0)
    if strategy == "sqrt":
        return np.sqrt(n_feat) / n_feat
    if strategy == "log2":
        return max(np.log2(max(n_feat, 2)) / n_feat, 1.0 / n_feat)
    if strategy == "onethird":
        return 1.0 / 3.0
    try:
        return float(strategy)
    except ValueError:
        return 1.0


#: Where forest sweeps take the lane route: the backends that have fused
#: kernels to run it on, and the row floor (the fold-vmap limit: under it
#: folds are vmapped over single-lane trees). Hand overrides for tests
#: and the benchmark's CPU rehearsal, where the kernels' jnp twins run the
#: same call shapes; nothing else sets them.
FOREST_LANE_BACKENDS = ("tpu",)
FOREST_LANE_MIN_ROWS = _TreeEstimator._VMAP_FOLD_MAX_ROWS


def fused_lanes_why(n_rows: int, min_rows: Optional[int] = None) -> str:
    """"" where a sweep of `n_rows` rows has fused kernels to run its
    lanes on here, else why not: the backend and the row floor, the first
    two questions of both fused lane routes (the forests'
    forest_lane_plan, the boosters' _fused_route_why, which hands over
    its estimator's own fold-vmap limit: the lower floor holds)."""
    from ..ops import pallas_hist
    floor = FOREST_LANE_MIN_ROWS if min_rows is None \
        else min(min_rows, FOREST_LANE_MIN_ROWS)
    backend = jax.default_backend()
    if backend not in FOREST_LANE_BACKENDS or (
            backend == "tpu" and not pallas_hist.available()):
        return f"backend {backend}: no fused kernels to run on"
    if n_rows <= floor:
        return f"{n_rows} rows: at or under the fold-vmap limit {floor}"
    return ""


def forest_lane_route_ok(est, n_rows: int, n_feat: int, n_folds: int,
                         multiclass: bool = False,
                         n_classes: int = 2) -> bool:
    """Does `est`'s mask-fold sweep of an [n_rows, n_feat] matrix run as
    (tree, fold) lanes of the fused passes here? Shapes and the class
    count only: a caller (the benchmark) asks before it makes any data."""
    plan = getattr(est, "forest_lane_plan", None)
    return plan is not None and plan(n_rows, n_feat, n_folds,
                                     n_classes=n_classes,
                                     multiclass=multiclass)[0] > 0


def payload_body(est, multiclass: bool = False, n_classes: int = 2) -> str:
    """How `est`'s fused passes carry its payload g into the kernels'
    bfloat16 contraction — a key of ops/trees.PAYLOAD_PARTS. A forest's g
    is weight x label: "indicator" for a class label (0/1: g exact in one
    part, three rows a (lane, slot)), "centred_parts" for a real-valued
    one (the label less its weighted mean, g as three exact parts, five
    rows), "class_indicators" where the label's classes go as K channels
    (a multiclass sweep: K + 1 rows, T.payload_rows(word, K)). A
    booster's g is its loss's gradient x weight: "gradient" for
    the logistic loss (|g| < 1, one part, three rows — what the boosters
    always issued), "residual_parts" for the squared loss (w (F - y),
    each round over that round's own scale, three exact parts, five
    rows). THE word of both families: the plans (forest_lane_plan,
    _fused_route_why, plan_lane_chunk), the fits, the `tree_fused` spans,
    `last_tree_telemetry` and the benchmark (which asks before it makes
    any data) all read it here."""
    if isinstance(est, _ForestBase):
        if not est.classification:
            return "centred_parts"
        return "indicator" if est._one_channel(n_classes, multiclass) \
            else "class_indicators"
    squared = (getattr(est, "_regression", False)
               or getattr(est, "_loss", "logistic") == "squared")
    return "residual_parts" if squared else "gradient"


#: the name the forest cells' driver and tests ask the word under
forest_payload_body = payload_body


class _ForestBase(_TreeEstimator):
    classification = True

    def _forest_cfg(self, n_feat: int) -> Dict[str, Any]:
        return dict(
            n_trees=int(self.get_param("num_trees")),
            subsample=float(self.get_param("subsampling_rate")),
            feature_frac=float(_feature_frac(
                str(self.get_param("feature_subset_strategy")), n_feat,
                self.classification)),
            bootstrap=True)

    def _one_channel(self, n_classes: int, multiclass: bool) -> bool:
        """Does the sweep's payload fit ONE channel? A regression target
        does; a binary label does too: the variance gain of [w y] is half
        the two-class Gini gain of [w (1 - y), w y] at every candidate, so
        the same trees grow once minInfoGain is halved with it, and the
        class-1 mean IS the leaf's distribution. It holds while every
        tree scores every row: a tree counts 0 for a row on a
        training-empty leaf, which the two-channel vote renormalises away
        and no row can reach where a split needs a row a side
        (min_instances_per_node >= 1)."""
        if not self.classification:
            return True
        return (not multiclass and n_classes == 2
                and float(self.get_param("min_instances_per_node")) >= 1.0)

    def _mask_score(self, ctx, y, w, n_classes, multiclass):
        Xb, edges, n_bins = ctx
        cfg = self._forest_cfg(Xb.shape[1])
        depth = int(self.get_param("max_depth"))
        one = self._one_channel(n_classes, multiclass)
        min_info_gain = T.payload_min_info_gain(
            payload_body(self, multiclass, n_classes),
            float(self.get_param("min_info_gain")))
        if one:
            G = (y * w)[:, None]
        else:
            G = jax.nn.one_hot(y.astype(jnp.int32), n_classes,
                               dtype=jnp.float32) * w[:, None]
        trees = T.fit_forest(
            Xb, G, w, self._key(), depth=depth, n_bins=n_bins,
            min_instances=float(self.get_param("min_instances_per_node")),
            min_info_gain=min_info_gain,
            leaf_mode="mean", allow_pallas=self._one_device(Xb), **cfg)
        agg = T.predict_forest_bins(trees, Xb, depth)  # [n, K]
        if one:
            return T.forest_vote_scores(
                agg[:, 0], n_trees=cfg["n_trees"],
                classification=self.classification)
        prob = jnp.clip(agg / cfg["n_trees"], 0.0, None)
        prob = prob / jnp.maximum(prob.sum(axis=1, keepdims=True), 1e-12)
        if multiclass:
            return prob  # [n, c] class scores (argmax = predicted class)
        p1 = jnp.clip(prob[:, 1], 1e-7, 1.0 - 1e-7)
        return jnp.log(p1 / (1.0 - p1))  # margin for the binary metrics

    # -- lane route: (tree, fold) lanes of the fused passes -----------------
    def forest_lane_plan(self, n_rows: int, n_feat: int, n_folds: int,
                         n_classes: int = 2, multiclass: bool = False):
        """(trees a lane group, "") where this estimator's mask-fold sweep
        takes the lane route (ops/trees.fit_forest_lanes) at this shape
        on this backend, (0, why not) where it keeps the sequential
        trees. THE gate of the route: mask_sweep_context, the fused hook
        and the benchmark's predicate (forest_lane_route_ok) all ask it."""
        from ..ops import pallas_hist
        why = fused_lanes_why(n_rows)
        if why:
            return 0, why
        body = payload_body(self, multiclass, n_classes)
        by_class = body == "class_indicators"
        if by_class and not multiclass:
            return 0, ("a binary label as two class channels "
                       "(min_instances_per_node < 1): its margin is read "
                       "from one channel's votes")
        cfg = self._forest_cfg(n_feat)
        depth = int(self.get_param("max_depth"))
        rows = T.payload_rows(body, n_classes)
        group = pallas_hist.plan_forest_group(
            n_rows, n_feat, int(self.get_param("max_bins")) + 1, n_folds,
            cfg["n_trees"], depth, rows,
            classes=n_classes if by_class else 0)
        if group == 0:
            return 0, (f"depth {depth}, K = {n_classes} class channels "
                       f"({rows} rows a (lane, slot), {n_folds} folds): "
                       f"plan_forest_group admits no tree's fold lanes — "
                       f"the output block of the deepest level, or the "
                       f"group's row planes in HBM" if by_class else
                       f"depth {depth}: plan_forest_group refuses the "
                       f"slot-dense output block of its deepest level")
        return group, ""

    def mask_sweep_context(self, X, n_valid: int = None, mesh=None):
        """The lane route runs on the device context whatever the backend
        (a CPU rehearsal takes the kernels' jnp twins, not the native
        builder). Asked for one fold, the most the plan can admit: a
        sweep the hook then declines runs the device trees."""
        if mesh is None and self.forest_lane_plan(
                int(n_valid or X.shape[0]), int(X.shape[1]), 1)[0]:
            return self._bin(X, n_valid=n_valid)
        return super().mask_sweep_context(X, n_valid=n_valid, mesh=mesh)

    def _mask_scores_fused(self, ctx, y, w, masks, n_classes, multiclass):
        """Every fold's forest as lane groups of the fused passes; None
        (with a `forest_lane_route_declined` event that says why) where
        forest_lane_plan declines or the matrix is laid over a mesh."""
        from ..ops import pallas_hist
        from ..utils.metrics import collector
        Xb, edges, n_bins = ctx
        n, n_feat, folds = int(Xb.shape[0]), int(Xb.shape[1]), \
            int(masks.shape[0])
        group, why = self.forest_lane_plan(n, n_feat, folds, n_classes,
                                           multiclass)
        if group and not self._one_device(Xb):
            group, why = 0, "the binned matrix is laid over a mesh"
        if not group:
            collector.event("forest_lane_route_declined",
                            model=type(self).__name__, reason=why)
            return None
        cfg = self._forest_cfg(n_feat)
        depth = int(self.get_param("max_depth"))
        n_trees = cfg["n_trees"]
        body = payload_body(self, multiclass, n_classes)
        classes = n_classes if body == "class_indicators" else 0
        # what only K class channels say: a 0/1 or a real-valued label's
        # spans, calls and telemetry stay what they were
        by_class = {"classes": classes} if classes else {}
        rows = T.payload_rows(body, n_classes)
        min_info_gain = T.payload_min_info_gain(
            body, float(self.get_param("min_info_gain")))
        key = self._key()
        W = masks * w[None, :]
        votes = jnp.zeros((folds, classes, n) if classes else (folds, n),
                          jnp.float32)
        groups = -(-n_trees // group)
        per_node = T.features_per_node(cfg["feature_frac"], n_feat)
        # the label's [centre, scale]: device scalars the lanes shift and
        # divide by and the sums and leaves get back; the fit fetches
        # neither
        centre = T.forest_label_centre(y, w) \
            if body == "centred_parts" else None
        for gi in range(groups):
            with collector.trace_span(
                    "forest_bootstrap", kind="tree_fused", trees=group,
                    rows=n, draws=group * n):
                rw, node_keys = T.forest_bootstrap(
                    key, gi * group, cfg["subsample"], n_rows=n,
                    n_trees=n_trees, group=group,
                    bootstrap=cfg["bootstrap"])
            with collector.trace_span(
                    "forest_group", kind="tree_fused",
                    lanes=group * folds, trees=group, folds=folds,
                    depth=depth,
                    slot_passes=sum(T.fused_level_slots(depth)),
                    route_node_rows=pallas_hist.route_node_rows(depth),
                    payload_body=body, payload_rows=rows,
                    features_per_node=per_node, **by_class):
                votes, _, _ = T.fit_forest_lanes(
                    Xb, y, W, rw, node_keys, votes, depth=depth,
                    n_bins=n_bins, feature_frac=cfg["feature_frac"],
                    min_instances=float(
                        self.get_param("min_instances_per_node")),
                    min_info_gain=min_info_gain, payload=body,
                    centre=centre, **by_class)
        self.last_lane_telemetry = dict(
            tree_lanes=n_trees * folds, lane_groups=groups,
            lanes_per_group=group * folds,
            bootstrap_draws=groups * group * n, payload_body=body,
            payload_rows=rows, features_per_node=per_node,
            label_centre=centre, **by_class)
        if classes:
            return T.ClassMajorScores(
                T.forest_class_scores(votes, n_trees=n_trees))
        return T.forest_vote_scores(votes, n_trees=n_trees,
                                    classification=self.classification)

    def _mask_score_host(self, ctx, y, w, n_classes, multiclass):
        """Numpy/native twin of _mask_score (CPU sweeps)."""
        from ..ops import trees_host as TH
        Xb, edges, n_bins = ctx
        cfg = self._forest_cfg(Xb.shape[1])
        depth = int(self.get_param("max_depth"))
        if self.classification:
            G = np.eye(n_classes, dtype=np.float32)[y.astype(int)] \
                * w[:, None]
        else:
            G = (y * w)[:, None]
        trees = TH.fit_forest_host(
            Xb, G, w, n_trees=cfg["n_trees"], depth=depth, n_bins=n_bins,
            subsample=cfg["subsample"], feature_frac=cfg["feature_frac"],
            min_instances=float(self.get_param("min_instances_per_node")),
            min_info_gain=float(self.get_param("min_info_gain")),
            bootstrap=cfg["bootstrap"], seed=int(self.get_param("seed")))
        if trees is None:  # library vanished mid-flight: device fallback
            return self._host_fallback(ctx, y, w, n_classes, multiclass)
        agg = TH.predict_bins_host(trees, Xb, depth)
        if not self.classification:
            return agg[:, 0] / cfg["n_trees"]
        prob = np.clip(agg / cfg["n_trees"], 0.0, None)
        prob = prob / np.maximum(prob.sum(axis=1, keepdims=True), 1e-12)
        if multiclass:
            return prob
        p1 = np.clip(prob[:, 1], 1e-7, 1.0 - 1e-7)
        return np.log(p1 / (1.0 - p1))

    @classmethod
    def _declare_params(cls):
        return [
            Param("num_trees", "ensemble size", 50),
            Param("max_depth", "tree depth", 5),
            Param("max_bins", "histogram bins", 32),
            Param("min_instances_per_node", "min rows per child", 1),
            Param("min_info_gain", "min impurity decrease", 0.0),
            Param("subsampling_rate", "bootstrap rate", 1.0),
            Param("feature_subset_strategy", "auto|all|sqrt|log2|onethird",
                  "auto"),
            Param("impurity", "gini|entropy|variance (variance-equivalent "
                  "gain used)", "gini"),
            Param("seed", "rng seed", 42),
        ]

    def _fit_forest(self, X, y, w, G, leaf_mode):
        frac = _feature_frac(str(self.get_param("feature_subset_strategy")),
                             X.shape[1], self.classification)
        if self._host_route():
            from ..ops import trees_host as TH
            Xb, edges, n_bins = self._bin_host(X)
            trees = TH.fit_forest_host(
                Xb, np.asarray(G, np.float32), np.asarray(w, np.float32),
                n_trees=int(self.get_param("num_trees")),
                depth=int(self.get_param("max_depth")), n_bins=n_bins,
                subsample=float(self.get_param("subsampling_rate")),
                feature_frac=float(frac),
                min_instances=float(self.get_param("min_instances_per_node")),
                min_info_gain=float(self.get_param("min_info_gain")),
                bootstrap=True, seed=int(self.get_param("seed")))
            if trees is not None:
                return self._freeze(trees, jnp.asarray(edges))
        Xb, edges, n_bins = self._bin(X)
        trees = T.fit_forest(
            Xb, jnp.asarray(G), jnp.asarray(w), self._key(),
            n_trees=int(self.get_param("num_trees")),
            depth=int(self.get_param("max_depth")), n_bins=n_bins,
            subsample=float(self.get_param("subsampling_rate")),
            feature_frac=float(frac),
            min_instances=float(self.get_param("min_instances_per_node")),
            min_info_gain=float(self.get_param("min_info_gain")),
            leaf_mode=leaf_mode)
        return self._freeze(trees, edges)


class OpRandomForestClassifier(_ForestBase):
    """Reference OpRandomForestClassifier (impl/classification/, 159 LoC)."""

    problem_types = ("binary", "multiclass")
    classification = True

    def __init__(self, uid: Optional[str] = None, **params):
        super().__init__("randomForestClassifier", uid=uid, **params)

    def fit_arrays(self, X, y, w=None):
        w = self._w(y, w)
        n_classes = max(int(np.max(y)) + 1 if y.size else 2, 2)
        G = np.eye(n_classes, dtype=np.float32)[y.astype(int)] * w[:, None]
        frozen = self._fit_forest(X, y, w, G, leaf_mode="mean")
        return TreeEnsembleModel(depth=int(self.get_param("max_depth")),
                                 mode="classify_mean", n_classes=n_classes,
                                 operation_name=self.operation_name, **frozen)


class OpRandomForestRegressor(_ForestBase):
    """Reference OpRandomForestRegressor (impl/regression/, 133 LoC)."""

    problem_types = ("regression",)
    classification = False
    produces_probabilities = False

    def __init__(self, uid: Optional[str] = None, **params):
        super().__init__("randomForestRegressor", uid=uid, **params)

    def fit_arrays(self, X, y, w=None):
        w = self._w(y, w)
        G = (np.asarray(y, np.float32) * w)[:, None]
        frozen = self._fit_forest(X, y, w, G, leaf_mode="mean")
        return TreeEnsembleModel(depth=int(self.get_param("max_depth")),
                                 mode="regress_mean",
                                 operation_name=self.operation_name, **frozen)


def _single_tree_params():
    return [p for p in _ForestBase._declare_params()
            if p.name not in ("num_trees", "subsampling_rate",
                              "feature_subset_strategy")]


class OpDecisionTreeClassifier(OpRandomForestClassifier):
    """Reference OpDecisionTreeClassifier (120 LoC): single tree, all
    features, no bagging."""

    def _forest_cfg(self, n_feat: int) -> Dict[str, Any]:
        return dict(n_trees=1, subsample=1.0, feature_frac=1.0,
                    bootstrap=False)

    @classmethod
    def _declare_params(cls):
        return _single_tree_params()

    def __init__(self, uid: Optional[str] = None, **params):
        PredictorEstimator.__init__(self, "decisionTreeClassifier", uid=uid,
                                    **params)

    def _fit_forest(self, X, y, w, G, leaf_mode):
        if self._host_route():
            from ..ops import trees_host as TH
            Xb, edges, n_bins = self._bin_host(X)
            trees = TH.fit_forest_host(
                Xb, np.asarray(G, np.float32), np.asarray(w, np.float32),
                n_trees=1, depth=int(self.get_param("max_depth")),
                n_bins=n_bins, subsample=1.0, feature_frac=1.0,
                bootstrap=False,
                min_instances=float(self.get_param("min_instances_per_node")),
                min_info_gain=float(self.get_param("min_info_gain")),
                seed=int(self.get_param("seed")))
            if trees is not None:
                return self._freeze(trees, jnp.asarray(edges))
        Xb, edges, n_bins = self._bin(X)
        trees = T.fit_forest(
            Xb, jnp.asarray(G), jnp.asarray(w), self._key(),
            n_trees=1, depth=int(self.get_param("max_depth")), n_bins=n_bins,
            subsample=1.0, feature_frac=1.0, bootstrap=False,
            min_instances=float(self.get_param("min_instances_per_node")),
            min_info_gain=float(self.get_param("min_info_gain")),
            leaf_mode=leaf_mode)
        return self._freeze(trees, edges)


class OpDecisionTreeRegressor(OpRandomForestRegressor):
    """Reference OpDecisionTreeRegressor (119 LoC)."""

    _fit_forest = OpDecisionTreeClassifier._fit_forest
    _forest_cfg = OpDecisionTreeClassifier._forest_cfg

    @classmethod
    def _declare_params(cls):
        return _single_tree_params()

    def __init__(self, uid: Optional[str] = None, **params):
        PredictorEstimator.__init__(self, "decisionTreeRegressor", uid=uid,
                                    **params)


class _GBTBase(_TreeEstimator):
    @classmethod
    def _declare_params(cls):
        return [
            Param("max_iter", "boosting rounds", 20),
            Param("max_depth", "tree depth", 5),
            Param("max_bins", "histogram bins", 32),
            Param("step_size", "learning rate", 0.1),
            Param("min_instances_per_node", "min rows per child", 1),
            Param("min_info_gain", "min gain to split", 0.0),
            Param("subsampling_rate", "row subsample per round", 1.0),
            Param("seed", "rng seed", 42),
        ]

    _loss = "logistic"  # subclass override; used by the mask-fold sweep

    def _gbt_kw(self):
        """What every route of this family hands its fit (fit_gbt,
        fit_gbt_folds and its sharded form, the native builder). The
        SPLIT rule is Spark's, as the forests hold it: `normalize_gain`
        compares min_info_gain with the gain a weighted row, so upstream's
        grid (0.001 / 0.01 / 0.1) means here what it means there — not
        with the gain summed over a node's rows, XGBoost's rule and
        _XGBBase's. The BOOSTING rule stays this library's (see
        OpGBTRegressor): base score the weighted label mean, Newton
        leaves under reg_lambda 1.0 (the fits' default; none is passed),
        step_size on every tree."""
        return dict(
            n_rounds=int(self.get_param("max_iter")),
            depth=int(self.get_param("max_depth")),
            learning_rate=float(self.get_param("step_size")),
            min_instances=float(self.get_param("min_instances_per_node")),
            min_info_gain=float(self.get_param("min_info_gain")),
            subsample=float(self.get_param("subsampling_rate")),
            normalize_gain=True)

    _sweep_kw = _gbt_kw  # config-fused sweep hook

    def _fit_gbt(self, X, y, w, loss):
        kw = self._gbt_kw()
        if self._host_route():
            from ..ops import trees_host as TH
            Xb, edges, n_bins = self._bin_host(X)
            out = TH.fit_gbt_host(Xb, np.asarray(y, np.float32),
                                  np.asarray(w, np.float32), n_bins=n_bins,
                                  seed=int(self.get_param("seed")),
                                  loss=loss, **kw)
            if out is not None:
                trees, base = out
                return self._freeze(trees, jnp.asarray(edges)), float(base)
        Xb, edges, n_bins = self._bin(X)
        self._decline_sequential()
        trees, base = T.fit_gbt(
            Xb, jnp.asarray(y, jnp.float32), jnp.asarray(w), self._key(),
            n_bins=n_bins, loss=loss, **kw)
        return self._freeze(trees, edges), float(base)

    def _mask_score(self, ctx, y, w, n_classes, multiclass):
        Xb, edges, n_bins = ctx
        kw = self._gbt_kw()
        trees, base = T.fit_gbt(Xb, y, w, self._key(), n_bins=n_bins,
                                loss=self._loss,
                                allow_pallas=self._one_device(Xb), **kw)
        return base + T.predict_forest_bins(trees, Xb, kw["depth"])[:, 0]

    def _mask_scores_fused(self, ctx, y, w, masks, n_classes, multiclass):
        kw = self._gbt_kw()
        return self._fold_fused_scores(ctx, y, w, masks, kw, self._loss)

    def _mask_score_host(self, ctx, y, w, n_classes, multiclass):
        from ..ops import trees_host as TH
        Xb, edges, n_bins = ctx
        kw = self._gbt_kw()
        out = TH.fit_gbt_host(Xb, y, w, n_bins=n_bins,
                              seed=int(self.get_param("seed")),
                              loss=self._loss, **kw)
        if out is None:
            return self._host_fallback(ctx, y, w, n_classes, multiclass)
        trees, base = out
        return base + TH.predict_bins_host(trees, Xb, kw["depth"])[:, 0]


class OpGBTClassifier(_GBTBase):
    """Reference OpGBTClassifier (147 LoC). Binary only — matching Spark's
    GBTClassifier; multiclass boosting lives in OpXGBoostClassifier.

    The SPLIT rule is Spark's: `min_info_gain` is compared with the gain a
    weighted row of the node (_gbt_kw, `normalize_gain`), `min_instances_
    per_node` with the rows a side. The BOOSTING rule is this library's,
    not Spark's GradientBoostedTrees.boost: Spark fits regression trees
    with plain-mean leaves to the LogLoss pseudo-residual of labels in
    {-1, +1}, its first tree to the label itself at weight 1; here the
    margin starts at the prior's logit and every round is a second-order
    (Newton) step on the logistic loss, leaf -G / (H + 1) x step_size,
    the gain G^2 / (H + 1) of the same sums. Its gradient p - y is under
    1 in size and goes to the fused passes in one bfloat16 part
    (payload_body: "gradient")."""

    problem_types = ("binary",)

    def __init__(self, uid: Optional[str] = None, **params):
        super().__init__("gbtClassifier", uid=uid, **params)

    def fit_arrays(self, X, y, w=None):
        frozen, base = self._fit_gbt(X, y, self._w(y, w), loss="logistic")
        return TreeEnsembleModel(depth=int(self.get_param("max_depth")),
                                 mode="margin", base=base,
                                 operation_name=self.operation_name, **frozen)


class OpGBTRegressor(_GBTBase):
    """Reference OpGBTRegressor (145 LoC), squared loss.

    The SPLIT rule is Spark's: `min_info_gain` is compared with the gain a
    weighted row of the node (_gbt_kw, `normalize_gain`) on every route —
    fit_gbt, fit_gbt_folds and its sharded form, the native builder — so
    upstream's grid (0.001 / 0.01 / 0.1) means here what it means there.
    The BOOSTING rule is this library's and departs from Spark 2.3's
    GradientBoostedTrees.boost in three stated ways
    (benchmark/configs/regression-10m-64-gbt.json `assumed` has their
    sizes on the benchmark's data): (1) F starts at the weighted label
    mean and the first tree is a round like the others, at step_size —
    Spark fits its first tree to the label itself at weight 1; (2) a
    round fits the residual y - F — Spark the pseudo-residual 2 (y - F),
    twice the step a round and 4 x the gain its threshold sees; (3) a leaf
    is the Newton step G / (H + reg_lambda) with reg_lambda 1.0 (the
    fits' default; none is passed) — Spark's the plain mean G / H.

    In the fold-fused sweep (fit_gbt_folds, one chip) a round's residual
    reaches the kernels' bfloat16 contraction as three exact parts over
    that round's own scale (payload_body: "residual_parts", five rows a
    (lane, slot)); a fit that cannot take them — a mesh, rows under the
    fold-vmap limit, the sequential fit_gbt — issues one part and says so
    with a `booster_parts_route_declined` event."""

    problem_types = ("regression",)
    produces_probabilities = False
    _loss = "squared"

    def __init__(self, uid: Optional[str] = None, **params):
        super().__init__("gbtRegressor", uid=uid, **params)

    def fit_arrays(self, X, y, w=None):
        frozen, base = self._fit_gbt(X, y, self._w(y, w), loss="squared")
        return TreeEnsembleModel(depth=int(self.get_param("max_depth")),
                                 mode="regress_sum", base=base,
                                 operation_name=self.operation_name, **frozen)


class _XGBBase(_TreeEstimator):
    @classmethod
    def _declare_params(cls):
        # the real-ML tail of the reference's 41 setters
        # (OpXGBoostClassifier.scala): alpha/scale_pos_weight/
        # max_delta_step/colsample_bylevel/base_score change fitted
        # models; the remaining setters are JNI/tracker plumbing with no
        # TPU referent
        return [
            Param("num_round", "boosting rounds", 100),
            Param("eta", "learning rate", 0.3),
            Param("max_depth", "tree depth", 6),
            Param("max_bins", "histogram bins", 256),
            Param("min_child_weight", "min hessian per child", 1.0),
            Param("reg_lambda", "L2 on leaves", 1.0),
            Param("alpha", "L1 on leaf weights (soft-threshold)", 0.0),
            Param("gamma", "complexity penalty per split", 0.0),
            Param("subsample", "row subsample per round", 1.0),
            Param("colsample_bytree", "feature subsample per tree", 1.0),
            Param("colsample_bylevel", "feature subsample per level", 1.0),
            Param("scale_pos_weight", "positive-class weight multiplier "
                  "(binary; xgboost imbalance control)", 1.0),
            Param("max_delta_step", "cap on each leaf's raw newton step "
                  "(imbalanced-logistic stabilizer)", 0.0),
            Param("base_score", "initial prediction (None = weighted "
                  "label mean, a better-calibrated prior than xgboost's "
                  "fixed 0.5)", None),
            Param("seed", "rng seed", 42),
        ]

    def _common(self):
        base_score = self.get_param("base_score")
        return dict(
            n_rounds=int(self.get_param("num_round")),
            depth=int(self.get_param("max_depth")),
            learning_rate=float(self.get_param("eta")),
            reg_lambda=float(self.get_param("reg_lambda")),
            min_child_weight=float(self.get_param("min_child_weight")),
            gamma=float(self.get_param("gamma")),
            subsample=float(self.get_param("subsample")),
            feature_frac=float(self.get_param("colsample_bytree")),
            alpha=float(self.get_param("alpha")),
            max_delta_step=float(self.get_param("max_delta_step")),
            colsample_bylevel=float(self.get_param("colsample_bylevel")),
            base_score=None if base_score is None else float(base_score))

    _sweep_kw = _common  # config-fused sweep hook

    _HOST_UNSUPPORTED = ("alpha", "max_delta_step", "colsample_bylevel",
                         "base_score")

    def _split_host_kw(self, kw):
        """(host-safe kw, True if the host/native builder can run them).

        The C++ builder implements the core surface; the round-5 tail
        lives in the XLA/pallas kernels only — non-default values force
        the device route rather than silently ignoring the params."""
        host_kw = {k: v for k, v in kw.items()
                   if k not in self._HOST_UNSUPPORTED}
        ok = (kw.get("alpha", 0.0) == 0.0
              and kw.get("max_delta_step", 0.0) == 0.0
              and kw.get("colsample_bylevel", 1.0) == 1.0
              and kw.get("base_score") is None)
        return host_kw, ok

    def _apply_spw(self, y, w, n_classes=2, multiclass=False):
        """scale_pos_weight: multiply positive-class weights — for the
        logistic objective this is exactly xgboost's g/h scaling of
        positive instances, and it reaches every route (device, fused,
        native host) because all take row weights."""
        spw = float(self.get_param("scale_pos_weight"))
        if spw == 1.0 or self._regression or multiclass or n_classes > 2:
            return w
        if isinstance(w, np.ndarray):
            yn = np.asarray(y)
            return (w * np.where(yn == 1, spw, 1.0)).astype(np.float32)
        return w * jnp.where(y == 1, spw, 1.0).astype(jnp.float32)

    def _check_multiclass_params(self, multiclass_fit: bool) -> None:
        if multiclass_fit and self.get_param("base_score") is not None:
            # softmax boosting has no scalar prior slot; dropping the
            # param silently would break the never-ignore contract
            raise ValueError(
                "base_score is only supported for binary/regression "
                "xgboost fits (softmax margins start at 0, matching "
                "xgboost multi:softprob)")

    def mask_fit_scores(self, ctx, y, w, masks, n_classes: int = 2,
                        multiclass: bool = False):
        self._check_multiclass_params(multiclass and not self._regression)
        w = self._apply_spw(y, w, n_classes, multiclass)
        if isinstance(ctx, tuple) and len(ctx) == 4 and ctx[0] == "host":
            _, host_ok = self._split_host_kw(self._common())
            if not host_ok:
                # round-5 tail params live in the XLA kernels only; untag
                # the context ONCE so the sweep converts the binned
                # matrix a single time instead of per (grid point, fold)
                import jax.numpy as jnp
                Xb, edges, n_bins = ctx[1:]
                ctx = (jnp.asarray(Xb), jnp.asarray(edges), n_bins)
        return super().mask_fit_scores(ctx, y, w, masks, n_classes,
                                       multiclass)

    _regression = False

    def _mask_score_host(self, ctx, y, w, n_classes, multiclass):
        from ..ops import trees_host as TH
        Xb, edges, n_bins = ctx
        kw = self._common()
        host_kw, host_ok = self._split_host_kw(kw)
        if not host_ok:  # round-5 param tail: XLA kernels only
            return self._host_fallback(ctx, y, w, n_classes, multiclass)
        depth = kw["depth"]
        seed = int(self.get_param("seed"))
        if self._regression or not multiclass:
            loss = "squared" if self._regression else "logistic"
            out = TH.fit_gbt_host(Xb, y, w, n_bins=n_bins, seed=seed,
                                  loss=loss, **host_kw)
            if out is None:
                return self._host_fallback(ctx, y, w, n_classes, multiclass)
            trees, base = out
            return base + TH.predict_bins_host(trees, Xb, depth)[:, 0]
        trees = TH.fit_gbt_softmax_host(
            Xb, y, w, n_bins=n_bins, n_classes=n_classes, seed=seed,
            **host_kw)
        if trees is None:
            return self._host_fallback(ctx, y, w, n_classes, multiclass)
        # per-class margin = sum over rounds of that class's trees
        margins = np.zeros((Xb.shape[0], n_classes), np.float32)
        for c in range(n_classes):
            sub = T.Tree(feat=trees.feat[:, c], thresh=trees.thresh[:, c],
                         leaf=trees.leaf[:, c], miss=trees.miss[:, c])
            margins[:, c] = TH.predict_bins_host(sub, Xb, depth)[:, 0]
        return margins

    def _mask_scores_fused(self, ctx, y, w, masks, n_classes, multiclass):
        if multiclass and not self._regression:
            return None   # softmax boosting keeps the per-fold path
        return self._fold_fused_scores(
            ctx, y, w, masks, self._common(),
            "squared" if self._regression else "logistic")

    def _mask_score(self, ctx, y, w, n_classes, multiclass):
        Xb, edges, n_bins = ctx
        kw = self._common()
        depth = kw["depth"]
        if self._regression or not multiclass:
            loss = "squared" if self._regression else "logistic"
            trees, base = T.fit_gbt(Xb, y, w, self._key(), n_bins=n_bins,
                                    loss=loss,
                                    allow_pallas=self._one_device(Xb), **kw)
            return base + T.predict_forest_bins(trees, Xb, depth)[:, 0]
        self._check_multiclass_params(True)
        soft_kw = {k: v for k, v in kw.items() if k != "base_score"}
        trees = T.fit_gbt_softmax(Xb, y, w, self._key(), n_bins=n_bins,
                                  n_classes=n_classes, **soft_kw)

        # trees carry leading [rounds, classes] axes with K=1 payloads;
        # per-class margin = sum over rounds (mirrors the training step)
        def per_round(carry, tree_c):
            step = jax.vmap(
                lambda t: T.predict_bins(t, Xb, depth)[:, 0])(tree_c)
            return carry + step.T, None

        init = jnp.zeros((Xb.shape[0], n_classes), jnp.float32)
        margins, _ = jax.lax.scan(per_round, init, trees)
        return margins  # [n, c]


class OpXGBoostClassifier(_XGBBase):
    """Reference OpXGBoostClassifier (375 LoC, JNI -> libxgboost): binary
    logistic or multiclass softprob, histogram algorithm."""

    problem_types = ("binary", "multiclass")

    def __init__(self, uid: Optional[str] = None, **params):
        super().__init__("xgbClassifier", uid=uid, **params)

    def fit_arrays(self, X, y, w=None):
        n_classes = max(int(np.max(y)) + 1 if y.size else 2, 2)
        w = self._apply_spw(y, self._w(y, w), n_classes)
        kw = self._common()
        host_kw, host_ok = self._split_host_kw(kw)
        depth = kw["depth"]
        if self._host_route() and host_ok:
            from ..ops import trees_host as TH
            Xb, edges, n_bins = self._bin_host(X)
            seed = int(self.get_param("seed"))
            yn = np.asarray(y, np.float32)
            if n_classes <= 2:
                out = TH.fit_gbt_host(Xb, yn, w, n_bins=n_bins, seed=seed,
                                      loss="logistic", **host_kw)
                if out is not None:
                    trees, base = out
                    frozen = self._freeze(trees, jnp.asarray(edges))
                    return TreeEnsembleModel(
                        depth=depth, mode="margin", base=float(base),
                        operation_name=self.operation_name, **frozen)
            else:
                trees = TH.fit_gbt_softmax_host(
                    Xb, yn, w, n_bins=n_bins, n_classes=n_classes,
                    seed=seed, **host_kw)
                if trees is not None:
                    frozen = self._freeze(trees, jnp.asarray(edges))
                    return SoftmaxEnsembleModel(
                        depth=depth, n_classes=n_classes,
                        operation_name=self.operation_name, **frozen)
        Xb, edges, n_bins = self._bin(X)
        if n_classes <= 2:
            trees, base = T.fit_gbt(
                Xb, jnp.asarray(y, jnp.float32), jnp.asarray(w), self._key(),
                n_bins=n_bins, loss="logistic", **kw)
            frozen = self._freeze(trees, edges)
            return TreeEnsembleModel(depth=depth, mode="margin",
                                     base=float(base),
                                     operation_name=self.operation_name,
                                     **frozen)
        self._check_multiclass_params(True)
        soft_kw = {k: v for k, v in kw.items() if k != "base_score"}
        trees = T.fit_gbt_softmax(
            Xb, jnp.asarray(y, jnp.float32), jnp.asarray(w), self._key(),
            n_bins=n_bins, n_classes=n_classes, **soft_kw)
        frozen = self._freeze(trees, edges)
        return SoftmaxEnsembleModel(depth=depth, n_classes=n_classes,
                                    operation_name=self.operation_name,
                                    **frozen)


class OpXGBoostRegressor(_XGBBase):
    """Reference OpXGBoostRegressor (346 LoC): squared-error objective."""

    problem_types = ("regression",)
    produces_probabilities = False
    _regression = True

    def __init__(self, uid: Optional[str] = None, **params):
        super().__init__("xgbRegressor", uid=uid, **params)

    def fit_arrays(self, X, y, w=None):
        w = self._w(y, w)
        kw = self._common()
        host_kw, host_ok = self._split_host_kw(kw)
        if self._host_route() and host_ok:
            from ..ops import trees_host as TH
            Xb, edges, n_bins = self._bin_host(X)
            out = TH.fit_gbt_host(Xb, np.asarray(y, np.float32), w,
                                  n_bins=n_bins,
                                  seed=int(self.get_param("seed")),
                                  loss="squared", **host_kw)
            if out is not None:
                trees, base = out
                frozen = self._freeze(trees, jnp.asarray(edges))
                return TreeEnsembleModel(
                    depth=kw["depth"], mode="regress_sum", base=float(base),
                    operation_name=self.operation_name, **frozen)
        Xb, edges, n_bins = self._bin(X)
        self._decline_sequential()
        trees, base = T.fit_gbt(
            Xb, jnp.asarray(y, jnp.float32), jnp.asarray(w), self._key(),
            n_bins=n_bins, loss="squared", **kw)
        frozen = self._freeze(trees, edges)
        return TreeEnsembleModel(depth=kw["depth"], mode="regress_sum",
                                 base=float(base),
                                 operation_name=self.operation_name, **frozen)
