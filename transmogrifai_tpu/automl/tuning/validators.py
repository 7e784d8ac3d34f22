"""Validators: cross-validation and train/validation split.

Reference: core/.../impl/tuning/{OpValidator.scala:94, OpCrossValidation.scala:41,
OpTrainValidationSplit.scala:34}. The reference evaluates every
(model x ParamMap) per fold on an 8-thread pool (OpValidator.scala:318) with
physical per-fold datasets (MLUtils.kFold).

TPU-first redesign: folds are *weight masks* over the in-HBM feature matrix —
no data movement between folds. For GLM-family estimators the whole
(fold x grid) sweep is ONE jitted program: `vmap` over fold masks and
hyperparameter leaves, fit by fixed-iteration Newton, score with one matmul,
evaluate with mask-weighted metric kernels. Non-vmappable estimators (trees,
naive Bayes) fall back to a per-(fold, grid) loop over sliced arrays.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...evaluators.evaluators import Evaluator
from ...models.base import PredictionModel, PredictorEstimator
from ...models.prediction import make_prediction_column
from ...ops import metrics_ops as M
from ...ops.trees import ClassMajorScores
from ...stages.params import ParamMap
from ...utils.metrics import collector
from .folds import (
    assign_fold_masks, assign_fold_masks_sharded, device_fold_route,
    fold_key, sharded_fold_route,
)


def _phase(name: str, **attrs: Any):
    """One top-level phase of validate(): a `validate_phase` span directly
    beneath the `validate` root. The phases (with the sweep_fit /
    sweep_eval spans, which keep their kinds) are disjoint, so a
    profiler trace splits a sweep's host seconds between them
    (docs/observability.md); spans nested inside a phase are `host_step`."""
    return collector.trace_span(name, kind="validate_phase", **attrs)


def _host_bytes(*arrays: Any) -> int:
    """Bytes of the arguments that live on the host: what placing them
    moves to the device."""
    return int(sum(a.nbytes for a in arrays if isinstance(a, np.ndarray)))


@dataclass
class ValidatedModel:
    """Validation record for one (estimator, grid point) — reference
    ModelEvaluation entries in ModelSelectorSummary."""

    model_name: str
    model_uid: str
    grid: ParamMap
    metric_name: str
    fold_metrics: List[float]
    # which sweep kernel produced these metrics ("streamed" | "vmapped" |
    # "mask_folds" | "sequential") — callers attributing timings/FLOPs
    # (bench.py MFU accounting) read it off the validation result
    route: str = ""

    @property
    def mean_metric(self) -> float:
        vals = [v for v in self.fold_metrics if np.isfinite(v)]
        return float(np.mean(vals)) if vals else float("nan")


@dataclass
class BestEstimator:
    """Winner of validation (reference OpValidator.wrapBestEstimator:147)."""

    name: str
    estimator: PredictorEstimator  # configured with the best grid
    best_grid: ParamMap
    best_metric: float
    validated: List[ValidatedModel] = field(default_factory=list)


# In-sweep AuPR/AuROC switch from exact sorts to O(n) histogram kernels above
# this many rows (the winner's final metrics remain exact); see
# ops/metrics_ops.au_pr_binned for the approximation contract.
BINNED_RANK_METRIC_MIN_ROWS = 2_000_000
RANK_METRIC_BINS = 4096

# HBM budget the auto grid-chunker assumes for one sweep call. Each vmapped
# lane (fold x grid point) materializes one [n, d] X-scaled product for the
# Gram matmul, so lanes are capped at budget / (n * d * itemsize).
SWEEP_LANE_BUDGET_BYTES = 12e9


def _metric_fn(problem_type: str, metric: str, n_classes: int = 2,
               rank_bins: Optional[int] = None) -> Callable:
    """Pure-jax (scores, labels, weights, margin_threshold) -> scalar used
    inside the vmapped sweep. Binary scores are margins (monotone in
    probability, so rank metrics match); thresholded metrics use the margin
    equivalent of the evaluator's probability threshold (logit for
    probabilistic models). The threshold is a traced scalar so distinct
    evaluator thresholds do NOT trigger sweep-kernel recompiles. Multiclass
    scores are [n, c] logits; argmax is invariant to softmax, so class
    metrics come straight from the confusion matmul
    (OpMultiClassificationEvaluator.scala:58)."""
    if problem_type == "binary":
        if metric == "au_pr":
            if rank_bins:
                return lambda s, y, w, thr: M.au_pr_binned(s, y, w, rank_bins)
            return lambda s, y, w, thr: M.au_pr(s, y, w)
        if metric == "au_roc":
            if rank_bins:
                return lambda s, y, w, thr: M.au_roc_binned(s, y, w, rank_bins)
            return lambda s, y, w, thr: M.au_roc(s, y, w)
        def bin_m(s, y, w, thr, _m=metric):
            return getattr(M.binary_metrics(s, y, w, threshold=thr), _m)
        return bin_m
    if problem_type == "multiclass":
        def multi_m(s, y, w, thr, _m=metric, _k=n_classes):
            pred = jnp.argmax(s, axis=1)
            return getattr(M.multiclass_metrics(pred, y, _k, w), _m)
        return multi_m
    if problem_type == "regression":
        def reg_m(p, y, w, thr, _m=metric):
            return getattr(M.regression_metrics(p, y, w), _m)
        return reg_m
    raise ValueError(f"No vmapped metric for problem type {problem_type}")


# Rows above which GLM sweeps route through the streaming lane-batched
# kernel (ops/glm_sweep.py): one X pass per Newton iteration for ALL
# (fold x grid) lanes instead of one per lane. Below it, the per-lane
# vmapped program is simpler and compile-cheaper. Read at each sweep:
# tests and bench.py's vmapped-retry path reassign it to pin a route.
STREAMED_SWEEP_MIN_ROWS = 200_000


def grid_fuse_on() -> bool:
    """TMOG_GRID_FUSE: the config-fused tree route, opt-in (its widest
    Mosaic compiles took 20+ minutes, r5). A whitelist of 1 / true / on,
    so "yes" stays off."""
    return os.environ.get("TMOG_GRID_FUSE", "").strip().lower() \
        in ("1", "true", "on")


def grid_fuse_max_failures() -> int:
    """Consecutive config-fused route failures tolerated before the
    sweep raises (ADVICE r5): the per-config fallback is the correctness
    baseline, but a fused route that dies on EVERY group is a broken
    kernel/driver that must surface, not a warning stream to scroll
    past. Read per sweep, like every other TMOG_GRID_FUSE_* knob."""
    return int(os.environ.get("TMOG_GRID_FUSE_MAX_FAILURES", "3"))

def _lanes_metric_fn(metric: str, problem_type: str, rank_bins,
                     unit_payload: bool = False):
    """(scores [L, n], labels [n], w_lanes [L, n]) -> [L] metric values
    when the metric has a lane-batched binned kernel, else None: the guard
    of the binned COUNTS for every sweep path (streamed eval, tree fold
    metrics). Which metrics take the streamed sweep's one held-out pass is
    `heldout_metric_body`'s to say, and a binned rank metric is one kind
    of them. `unit_payload`: validate()'s word that weights x labels are
    zeros and ones (Validator._unit_payload), handed on to the kernel."""
    if not (rank_bins and problem_type == "binary"):
        return None
    lanes = {"au_pr": M.au_pr_binned_lanes,
             "au_roc": M.au_roc_binned_lanes}.get(metric)
    if lanes is None:
        return None
    return lambda s, y, wl: lanes(s, y, wl, rank_bins,
                                  unit_payload=unit_payload)


def heldout_metric_body(metric: str, problem_type: str, rank_bins
                        ) -> Optional[str]:
    """How the one-pass held-out route (`eval_route` "heldout_once":
    `_eval_heldout_core`) takes this metric, or None where it cannot: the
    route is open to any metric that is a sum or a count over held-out
    rows — "bins" for a binned rank metric (the cumulative class counts a
    score bin, `_lanes_metric_fn`), "sums" for the regression metrics
    (weighted sums of the residual and of the label). THE predicate: the
    route choice and the telemetry's `metric_body` read it, and the metric
    program (`_eval_heldout_core`) branches on the same names."""
    if problem_type == "regression" \
            and metric in M.RegressionMetrics._fields:
        return "sums"
    if _lanes_metric_fn(metric, problem_type, rank_bins) is not None:
        return "bins"
    return None


def _held_out_at_most_once(masks) -> bool:
    """Does every row have validation weight (mask != 1) in at most one of
    the [F, n] fold masks? Host masks are numpy's to count; device masks
    are reduced on the device and ONE scalar comes back."""
    if masks.shape[0] == 1:
        return True
    xp = jnp if isinstance(masks, jax.Array) else np
    return int(xp.max(xp.sum(masks != 1.0, axis=0))) <= 1


def label_classes(y) -> int:
    """Classes of a label vector of ids 0..K-1: max + 1. A device array is
    reduced on the device and ONE scalar comes back; a host array is
    numpy's to reduce."""
    return int(jnp.max(y) if isinstance(y, jax.Array) else np.max(y)) + 1


def _streamed_confusion(X, y, vw, Bc, b0c, n_classes: int):
    """[chunk, K, K] weighted confusion counts of one fold's grid chunk of
    multinomial coefficients Bc [chunk, d, K], b0c [chunk, K]: a loop over
    row blocks, each the block's logits, their argmax per lane and
    M.confusion_lanes of it. Nothing [n, K] is resident."""
    from ...ops import glm_sweep as GS
    n = X.shape[0]
    c = GS._mlr_row_block(Bc.shape[0] * n_classes, n)
    nb, take = GS._mlr_blocks(n, c, X.T, y, vw)

    def body(i, conf):
        xT, fresh, y_blk, w_blk = take(i)
        pred = jnp.argmax(GS.sweep_logits_fold_t(xT, Bc, b0c), axis=1)
        return conf + M.confusion_lanes(pred, y_blk, w_blk * fresh,
                                        n_classes)

    return jax.lax.fori_loop(0, nb, body, jnp.zeros(
        (Bc.shape[0], n_classes, n_classes), jnp.float32))


_CLASS_MAJOR_BLOCK = 1 << 19


@partial(jax.jit, static_argnames=("metric", "n_classes"))
def _class_major_metrics(scores, y, w, masks, *, metric: str,
                         n_classes: int):
    """[folds] class metric of class-major scores [folds, K, n]
    (ops/trees.ClassMajorScores: a forest's lane route) on each fold's
    held-out rows: the argmax over the class axis, then the weighted
    confusion count [folds, K, K] summed a block of rows at a time —
    nothing [folds, K, n] is built beside the scores — and every class
    metric read off it (M.multiclass_metrics_from_confusion, as the
    streamed multinomial sweep's). Unit weights count exactly."""
    pred = jnp.argmax(scores, axis=1)                          # [folds, n]
    vw = (1.0 - masks) * w[None, :]
    folds, n = pred.shape
    classes = jnp.arange(n_classes, dtype=jnp.int32)

    def count(start, size):
        p = jax.lax.dynamic_slice_in_dim(pred, start, size, axis=1)
        P = (p[:, None, :] == classes[None, :, None]).astype(jnp.float32)
        Y = (jax.lax.dynamic_slice_in_dim(y, start, size).astype(jnp.int32)[
            None, :] == classes[:, None]).astype(jnp.float32)  # [K, size]
        A = Y[None] * jax.lax.dynamic_slice_in_dim(
            vw, start, size, axis=1)[:, None, :]               # [folds, K, c]
        return jnp.einsum("ftc,fpc->ftp", A, P,
                          precision=jax.lax.Precision.HIGHEST)

    c = min(_CLASS_MAJOR_BLOCK, n)
    nb, tail = divmod(n, c)
    conf = jax.lax.fori_loop(
        0, nb, lambda i, acc: acc + count(i * c, c),
        jnp.zeros((folds, n_classes, n_classes), jnp.float32))
    if tail:
        conf = conf + count(nb * c, tail)
    return jax.vmap(lambda cf: getattr(
        M.multiclass_metrics_from_confusion(cf), metric))(conf)


@partial(jax.jit,
         static_argnames=("metric", "problem_type", "n_classes",
                          "rank_bins", "chunk", "use_lanes",
                          "unit_payload"))
def _streamed_eval(X, y, vw, Bc, b0c, thr, *, metric, problem_type,
                   n_classes=2, rank_bins=None, chunk=8, use_lanes=True,
                   unit_payload=False):
    """Metrics for one fold's grid chunk of streamed-sweep coefficients
    (the `per_fold` route: every row scored once a FOLD, all but the
    fold's own at weight zero — what is left for overlapping masks, a mesh
    that spans processes, exact rank metrics and thresholded ones):
    scores in one MXU contraction (a regression sweep's at float32
    coefficients, `sweep_scores_fold(exact=True)`, as its held-out pass);
    binned rank metrics go through the
    lane-batched kernel (one pallas histogram for the whole chunk on TPU
    instead of per-lane scatter-adds), everything else vmaps. Mesh
    callers pass use_lanes=False (a pallas_call must not consume
    row-sharded operands; GSPMD partitions the vmapped kernels instead).
    Multiclass coefficients ([chunk, d, K]) take the lane-batched
    confusion count: one [K, K] count per lane gives every class metric."""
    if problem_type == "multiclass":
        conf = _streamed_confusion(X, y, vw, Bc, b0c, n_classes)
        return jax.vmap(lambda cf: getattr(
            M.multiclass_metrics_from_confusion(cf), metric))(conf)
    from ...ops.glm_sweep import sweep_scores_fold
    # a regression metric is not invariant to coefficients rounded to X's
    # dtype (a bias of sum(delta_j mean_j) in every prediction): exact
    s = sweep_scores_fold(X, Bc, b0c,
                          exact=problem_type == "regression")  # [n, chunk]
    lanes_fn = _lanes_metric_fn(metric, problem_type, rank_bins,
                                unit_payload) if use_lanes else None
    if lanes_fn is not None:
        wl = jnp.broadcast_to(vw[None, :], (s.shape[1], vw.shape[0]))
        return lanes_fn(s.T, y, wl)
    mfn = _metric_fn(problem_type, metric, n_classes, rank_bins)
    return jax.vmap(lambda col: mfn(col, y, vw, thr), in_axes=1)(s)


def _heldout_fold_of(masks):
    """fold_of [n] int32: the fold that holds the row out (an entry != 1
    of the [F, n] masks, whose held-out sets are disjoint), F for none."""
    out = masks != 1.0
    return jnp.where(jnp.any(out, axis=0), jnp.argmax(out, axis=0),
                     masks.shape[0]).astype(jnp.int32)


def _heldout_scores(X, masks, Bc, b0c):
    """([Gc, n] f32 margins of every row under the grid chunk fitted by the
    fold that holds the row out, fold_of [n] int32 with F for "held out by
    none"): X is read ONCE, in row blocks, each ONE contraction against all
    F x Gc coefficient columns (sweep_scores_fold's products: X's dtype,
    float32 accumulation), the row's own fold's Gc scores selected from
    the block. Nothing [n, F x Gc] is resident. Bc [F, Gc, d], b0c [F, Gc];
    masks [F, n] whose held-out sets (entries != 1) are disjoint."""
    from ...ops import glm_sweep as GS
    F, Gc, d = Bc.shape
    n = X.shape[0]
    fold_of = _heldout_fold_of(masks)
    Ball = Bc.reshape(F * Gc, d).astype(X.dtype)
    c = GS._mlr_row_block(F * Gc, n)
    nb, take = GS._mlr_blocks(n, c, X.T, fold_of)

    def body(i, scores):
        xT, _, f_blk = take(i)
        s = (jnp.matmul(Ball, xT, preferred_element_type=jnp.float32)
             + b0c.reshape(F * Gc, 1)).reshape(F, Gc, c)
        own = s[0]
        for f in range(1, F):
            own = jnp.where(f_blk[None, :] == f, s[f], own)
        # the last block starts early: rows written twice, same values
        return jax.lax.dynamic_update_slice_in_dim(
            scores, own, jnp.minimum(i * c, n - c), axis=1)

    return jax.lax.fori_loop(0, nb, body,
                             jnp.zeros((Gc, n), jnp.float32)), fold_of


def _heldout_regression(X, y, vw, masks, Bc, b0c, metric, allreduce):
    """[F, Gc] of a regression metric (a field of M.RegressionMetrics) from
    ONE pass over X: every row predicted under the grid chunk fitted by
    the fold that holds it out, and a fold and grid point the weighted
    sums of the residual r — sum w r^2, sum w |r| — beside the fold's sum
    w, sum w (y - p), sum w (y - p)^2 about the fold's own held-out label
    mean p (read first, from y and w alone: a label far from zero then
    cancels nothing in R2's total sum of squares, as the two-pass
    M.regression_metrics). No histogram, nothing [Gc, n] resident: a row
    block's sums land in their own row of a [blocks, F, 4, Gc] array,
    which is added up at the end (a running float32 sum over hundreds of
    blocks would lose what the metric must resolve). `allreduce` sums
    over the mesh axis: the label's [F, 2], then the [F, 4, Gc] sums.

    The contraction sees the coefficients at float32 precision, as their
    exact parts of X's dtype (`ops/parts.float32_parts`; a float32
    matrix contracts at HIGHEST): rounded to bfloat16 they shift EVERY
    prediction by sum(delta_j mean_j) on columns that are not centred,
    which adds to the squared error in full."""
    from ...ops import glm_sweep as GS
    from ...ops import parts as P
    f32 = jnp.float32
    F, Gc, d = Bc.shape
    n = X.shape[0]
    fold_of = _heldout_fold_of(masks)
    held = [fold_of == f for f in range(F)]
    label = allreduce(jnp.stack(
        [jnp.stack([jnp.where(h, vw, 0.0).sum(),
                    jnp.where(h, vw * y, 0.0).sum()]) for h in held]))
    sw = jnp.maximum(label[:, 0], M.EPS)                        # [F]
    pivot = jnp.append(label[:, 1] / sw, 0.0)                   # [F + 1]
    parts = P.stacked_parts(Bc.reshape(F * Gc, d), X.dtype)
    precision = jax.lax.Precision.HIGHEST \
        if X.dtype == jnp.float32 else None
    c = GS._mlr_row_block(parts.shape[0], n)
    nb, take = GS._mlr_blocks(n, c, fold_of, y, vw)
    x_block = GS.x_row_blocks(X, c)

    def body(i, sums):
        f_blk, fresh, y_blk, w_blk = take(i)
        s = (P.slab_sum(jnp.matmul(x_block(i), parts.T, precision=precision,
                                   preferred_element_type=f32), F * Gc,
                        axis=1) + b0c.reshape(1, F * Gc)).reshape(c, F, Gc)
        own = s[:, 0]
        for f in range(1, F):
            own = jnp.where(f_blk[:, None] == f, s[:, f], own)
        r = own - y_blk[:, None]                                # [c, Gc]
        yc = jnp.broadcast_to((y_blk - pivot[f_blk])[:, None], r.shape)
        q = jnp.stack([r * r, jnp.abs(r), yc, yc * yc], axis=1)
        wf = jnp.stack([jnp.where(f_blk == f, w_blk * fresh, 0.0)
                        for f in range(F)], axis=1)             # [c, F]
        part = jnp.einsum("cf,ckg->fkg", wf, q,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=f32)
        return jax.lax.dynamic_update_slice_in_dim(
            sums, part[None], i, axis=0)

    s_r2, s_abs, s_y, s_y2 = jnp.moveaxis(allreduce(jax.lax.fori_loop(
        0, nb, body, jnp.zeros((nb, F, 4, Gc), f32)).sum(0)), 1, 0)
    sw = sw[:, None]
    mse = s_r2 / sw
    ss_tot = s_y2 - s_y * s_y / sw
    return {"mse": mse, "rmse": jnp.sqrt(mse), "mae": s_abs / sw,
            "r2": 1.0 - s_r2 / jnp.maximum(ss_tot, M.EPS)}[metric]


def _eval_heldout_core(X, y, w, masks, Bc, b0c, *, metric, rank_bins,
                       unit_payload=False, axis_name=None):
    """The held-out-once metric pass over the rows at hand: all of them,
    or under `axis_name` a chip's own, whose sums are added over that mesh
    axis before the metric is taken from them. One body a kind of metric
    (`heldout_metric_body`): a regression metric (`rank_bins` is None) its
    weighted sums (`_heldout_regression`: one psum of the folds' label
    sums, one of the [F, 4, Gc] residual sums); a binned rank metric the
    [F, Gc, bins] counts (ONE psum of both classes' counts)."""
    if metric in M.RegressionMetrics._fields:
        return _heldout_regression(
            X, y, (1.0 - jnp.min(masks, axis=0)) * w, masks, Bc, b0c, metric,
            (lambda v: v) if axis_name is None
            else (lambda v: jax.lax.psum(v, axis_name)))
    scores, fold_of = _heldout_scores(X, masks, Bc, b0c)
    vw = (1.0 - jnp.min(masks, axis=0)) * w
    counts = M.heldout_cum_counts_lanes(scores, y, vw, fold_of, Bc.shape[0],
                                        rank_bins, unit_payload=unit_payload)
    counts = counts if axis_name is None else jax.lax.psum(counts, axis_name)
    return M.RANK_METRIC_FROM_COUNTS[metric](*counts)


@partial(jax.jit, static_argnames=("metric", "rank_bins", "unit_payload"))
def _streamed_eval_heldout(X, y, w, masks, Bc, b0c, unit_payload=False, *,
                           metric, rank_bins):
    """[F, Gc] binned rank metrics of EVERY fold's grid chunk in one pass
    over X, for folds whose held-out sets are disjoint (k-fold, a single
    split): _streamed_eval called fold by fold scores and bins all n rows
    F times, all but a row's own fold with weight zero. Values equal the
    per-fold route's up to float32 summation order. The program's name
    keeps `streamed_eval`: traces and the benchmark find the metric pass
    by it. `unit_payload` (static; the mesh form takes it in the same
    place): validate()'s word that w and the masks hold zeros and ones."""
    return _eval_heldout_core(X, y, w, masks, Bc, b0c, metric=metric,
                              rank_bins=rank_bins, unit_payload=unit_payload)


@lru_cache(maxsize=None)
def _sharded_eval_heldout_fn(mesh, metric, rank_bins):
    """_streamed_eval_heldout on a mesh, with its arguments (`unit_payload`
    static, last): every chip scores and bins its OWN rows once (the Pallas
    histogram kernel sees local rows), the [F, Gc, bins] counts are summed
    over the batch axis in one psum and every chip takes the same metrics
    from the sum."""
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import BATCH_AXIS, build_shard_map

    def _streamed_eval_heldout_sharded(X, y, w, masks, Bc, b0c,
                                       unit_payload=False):
        def local(X, y, w, masks, Bc, b0c):
            return _eval_heldout_core(
                X, y, w, masks, Bc, b0c, metric=metric, rank_bins=rank_bins,
                unit_payload=unit_payload, axis_name=BATCH_AXIS)

        return build_shard_map(
            local, mesh,
            in_specs=(P(BATCH_AXIS, None), P(BATCH_AXIS), P(BATCH_AXIS),
                      P(None, BATCH_AXIS), P(None, None, None),
                      P(None, None)),
            out_specs=P(None, None))(X, y, w, masks, Bc, b0c)

    return jax.jit(_streamed_eval_heldout_sharded,
                   static_argnames=("unit_payload",))


# the metric programs' executables bake the lanes-kernel (pallas) choice
# in; the kill switch clears them on toggle
from ...ops import pallas_hist as _pallas_hist  # noqa: E402
_pallas_hist.register_cache_consumer(_streamed_eval)
_pallas_hist.register_cache_consumer(_streamed_eval_heldout)
# (the mesh form's programs hang off an lru_cache: the kill switch drops it)
_sharded_eval_heldout_fn.clear_cache = _sharded_eval_heldout_fn.cache_clear
_pallas_hist.register_cache_consumer(_sharded_eval_heldout_fn)


@partial(jax.jit,
         static_argnames=("fit_one", "metric", "problem_type", "n_classes",
                          "rank_bins"))
def _sweep(X, y, w, fold_masks, regs, alphas, margin_threshold, *, fit_one,
           metric, problem_type, n_classes=2, rank_bins=None):
    """The sweep kernel: metrics[F, G] for F fold masks x G grid points.

    One XLA program: on a row-sharded X every Gram-matrix reduction inside
    fit_one becomes an ICI psum; fold/grid axes are embarrassingly parallel
    (vmap) and can additionally be laid out on the `model` mesh axis.
    Multiclass fit_one returns (B [d, c], b0 [c]) and the same `X @ beta + b0`
    scoring broadcasts to [n, c] logits.
    """
    mfn = _metric_fn(problem_type, metric, n_classes, rank_bins)

    def one(mask, reg, alpha):
        beta, b0 = fit_one(X, y, mask * w, reg, alpha)
        # keep a bf16 X bf16 in the scoring dot too (beta is f32 solver
        # state; plain X @ beta would materialize a full f32 copy of X)
        score = jnp.matmul(X, beta.astype(X.dtype),
                           preferred_element_type=jnp.float32) + b0
        return mfn(score, y, (1.0 - mask) * w, margin_threshold)

    per_grid = jax.vmap(lambda m: jax.vmap(partial(one, m))(regs, alphas))
    return per_grid(fold_masks)


class Validator:
    """Base validator (reference OpValidator.scala:94)."""

    def __init__(self, evaluator: Evaluator, seed: int = 42,
                 stratify: bool = False, parallelism: int = 8,
                 grid_chunk: Optional[int] = None,
                 sweep_dtype: Optional[Any] = None,
                 mask_fold_trees: bool = True,
                 mesh: Optional[Any] = None):
        self.evaluator = evaluator
        self.seed = int(seed)
        self.stratify = bool(stratify)
        # kept for API parity; device vmap replaces the thread pool
        self.parallelism = int(parallelism)
        # optional sweep checkpoint (resume skips finished model x grid cells)
        self.checkpoint_path: Optional[str] = None
        # round/pass telemetry of the LAST streamed GLM sweep (bench.py's
        # executed-FLOP accounting reads it; also mirrored into
        # utils/metrics.collector.sweep_convergence when collection is on)
        self.last_streamed_telemetry: Optional[Dict[str, Any]] = None
        # what the last sweep's tree family counted: a forest's lanes or a
        # booster's fold-fused fits (None: neither ran) — _count_tree_lanes
        self.last_tree_telemetry: Optional[Dict[str, Any]] = None
        self._external_mask_tag = ""  # set per validate() call
        # are the folds' held-out sets disjoint? (set per validate() call;
        # the streamed sweep's one-pass metric route needs it)
        self._heldout_once = False
        # is every weight x label the metric kernels will see 0 or 1? (set
        # per validate() call: no sample weights handed in, the fold masks
        # made here; the binned rank metrics' histogram then takes one
        # bfloat16 part of its payload and not three)
        self._unit_payload = False
        # grid points swept per XLA call (None = auto from the HBM budget);
        # checkpoints land after every chunk, so a preempted vmapped sweep
        # resumes mid-grid
        self.grid_chunk = grid_chunk
        # on-device dtype of the sweep's feature matrix; jnp.bfloat16 halves
        # HBM per lane (solvers keep f32 state — ops/glm._solver_dtype)
        self.sweep_dtype = sweep_dtype
        # trees: fit every fold as a weight mask over ONE device-binned
        # matrix (no host slicing). NB quantile bin edges then come from the
        # full column (features only, never labels) rather than per-fold
        # train rows — set False to force physically split refits
        self.mask_fold_trees = bool(mask_fold_trees)
        # optional jax.sharding.Mesh (parallel/mesh.py axes): the sweep's
        # feature matrix/labels/weights shard rows over the `batch` axis,
        # fold masks shard their row dim — every Gram/histogram reduction
        # inside the jitted sweep then becomes an ICI psum inserted by
        # GSPMD; program text is unchanged (SURVEY §2.9 translation of
        # Spark partitioning). Rows pad to the axis size with zero weights,
        # which every kernel treats as absent. A matrix that already lives
        # row-sharded on the device needs no mesh here: validate() reads
        # the mesh from where X lives (parallel/mesh.resident_row_mesh).
        self.mesh = mesh
        # the mesh X of the current validate() call lives row-sharded on
        # (None: a host array, one device) — _sweep_mesh, _resident
        self._resident_mesh = None
        # overflow flag of the last fold program that ran on a mesh, a
        # device scalar nothing fetches (True: a run outgrew its padding
        # and the replicated sort answered); None before one has run
        self.last_fold_overflow = None

    @property
    def _sweep_mesh(self):
        """The mesh the current validate() call sweeps on: `mesh`, or the
        resident matrix's own."""
        return self.mesh if self.mesh is not None else self._resident_mesh

    @property
    def _resident(self) -> bool:
        """Does X already live row-sharded on the sweep's mesh?"""
        return self._resident_mesh is not None \
            and self._resident_mesh == self._sweep_mesh

    # -- folds -------------------------------------------------------------
    def _fold_spec(self) -> Dict[str, Any]:
        """Static arguments of folds.assign_fold_masks that say how this
        validator holds rows out: `n_folds`, `val_fraction`."""
        raise NotImplementedError

    def device_fold_masks(self, y, mesh: Optional[Any] = None) -> jax.Array:
        """[F, n] float32 train-membership masks (1=train, 0=validation) on
        the device: one dispatch of folds.assign_fold_masks, a function of
        (seed, rows, folds or ratio, and `y` when stratified) alone —
        the same for a host `y` and a device `y`, on any backend and, with
        `mesh`, sharded on rows over it (folds.assign_fold_masks_sharded:
        no chip holds the whole block; the program's overflow flag is kept
        as `last_fold_overflow`)."""
        spec = dict(n=len(y), stratify=self.stratify, **self._fold_spec())
        y = jnp.asarray(y, jnp.float32) if self.stratify else None
        if mesh is None:
            return assign_fold_masks(fold_key(self.seed), y, **spec)
        if y is not None:
            from ...parallel.mesh import batch_sharding
            y = jax.device_put(y, batch_sharding(mesh, 1))
        masks, self.last_fold_overflow = assign_fold_masks_sharded(
            mesh, fold_key(self.seed), y, **spec)
        return masks

    def fold_masks(self, y) -> np.ndarray:
        """The same masks on the host: what validate() ran on, bit for
        bit, for callers that index them with numpy."""
        return np.asarray(self.device_fold_masks(y))

    # -- validation --------------------------------------------------------
    def validate(self, models: Sequence[Tuple[PredictorEstimator, List[ParamMap]]],
                 X: np.ndarray, y: np.ndarray,
                 w: Optional[np.ndarray] = None,
                 problem_type: str = "binary",
                 masks: Optional[np.ndarray] = None) -> BestEstimator:
        """`masks` overrides self.fold_masks(y) — the workflow-level CV
        (leakage-free in-fold DAG refits, OpValidator.applyDAG:228) feeds
        one fold-fitted matrix at a time with that fold's single mask, so
        its inner (model x grid) sweep rides the same device routes."""
        from ...parallel.mesh import (
            batch_sharding, mesh_batch_count, resident_row_mesh,
        )
        n_folds = int(masks.shape[0] if masks is not None
                      else getattr(self, "num_folds", 1))
        # where X lives decides where the sweep runs: a matrix row-sharded
        # over a mesh sweeps on that mesh, nothing of it through the host
        self._resident_mesh = resident = resident_row_mesh(X)
        shards = mesh_batch_count(self._sweep_mesh)
        with collector.trace_span(
                type(self).__name__, kind="validate", rows=len(y),
                folds=n_folds, models=len(models),
                grid_points=sum(max(len(g), 1) for _, g in models),
                shards=shards):
            n_classes = 2
            if problem_type == "multiclass":
                # before the fold program is dispatched: the scalar's
                # fetch then waits for this reduction alone
                with _phase("label_classes"):
                    n_classes = label_classes(y)
            if masks is not None:
                how = {"route": "external"}
            elif self._resident:
                how = sharded_fold_route(resident, len(y), self.stratify)
            else:
                how = device_fold_route(len(y), self.stratify)
            with _phase("fold_assign", rows=len(y), folds=n_folds,
                        stratify=self.stratify,
                        shards=shards if self._resident else 1, **how):
                # unit weights under 0/1 masks (device_fold_masks') and the
                # binary route's 0/1 labels; masks handed in may hold anything
                self._unit_payload = w is None and masks is None
                if w is None:
                    w = jnp.ones(len(y), jnp.float32, device=batch_sharding(
                        resident, 1) if self._resident else None)
                if masks is None:
                    masks = self.device_fold_masks(
                        y, mesh=resident if self._resident else None)
                    self._external_mask_tag = ""
                    # k folds or one split: a row is held out at most once
                    self._heldout_once = True
                else:
                    self._heldout_once = _held_out_at_most_once(masks)
                    # checkpoint cells must be keyed by WHICH masks ran:
                    # external per-fold masks can share a data fingerprint
                    # across calls
                    import hashlib
                    self._external_mask_tag = hashlib.sha1(
                        np.ascontiguousarray(masks, np.float32).tobytes()
                    ).hexdigest()[:12]
            metric = self.evaluator.default_metric
            larger = self.evaluator.is_larger_better()

            # a user-supplied metric (Evaluators.custom) has no device kernel:
            # every candidate goes through the sequential per-fold route, which
            # is the only one that calls evaluator.evaluate on host columns
            device_metric = getattr(self.evaluator, "device_metric", True)

            validated: List[ValidatedModel] = []
            for est, grids in models:
                if not grids:
                    grids = [dict()]
                if not device_metric:
                    validated.extend(self._validate_sequential(
                        est, grids, X, y, w, masks))
                elif self._streamable(est, grids, problem_type, X,
                                      masks.shape[0], n_classes):
                    validated.extend(self._validate_streamed(
                        est, grids, X, y, w, masks, metric, problem_type,
                        n_classes))
                elif self._vmappable(est, grids, problem_type):
                    validated.extend(self._validate_vmapped(
                        est, grids, X, y, w, masks, metric, problem_type,
                        n_classes))
                elif (self.mask_fold_trees
                      and getattr(est, "supports_mask_folds", False)
                      and problem_type in getattr(est, "problem_types", ())):
                    validated.extend(self._validate_mask_folds(
                        est, grids, X, y, w, masks, metric, problem_type,
                        n_classes))
                else:
                    validated.extend(self._validate_sequential(
                        est, grids, X, y, w, masks))

            with _phase("winner"):
                if not validated:
                    raise ValueError("No models to validate")
                key = (lambda v: v.mean_metric if np.isfinite(v.mean_metric)
                       else (-np.inf if larger else np.inf))
                best = max(validated, key=key) if larger \
                    else min(validated, key=key)
                winner = next(e for e, _ in models
                              if e.uid == best.model_uid).copy(**best.grid)
                return BestEstimator(name=best.model_name, estimator=winner,
                                     best_grid=best.grid,
                                     best_metric=best.mean_metric,
                                     validated=validated)

    # -- vmapped GLM path --------------------------------------------------
    @staticmethod
    def _constant_off_axis(est: PredictorEstimator, grids: List[ParamMap],
                           axes) -> bool:
        """Every non-axis grid key must be constant across the grid (those
        become static jit args via copy)."""
        others = {k for g in grids for k in g if k not in axes}
        for k in others:
            vals = {repr(g.get(k, est.get_param(k))) for g in grids}
            if len(vals) > 1:
                return False
        return True

    @staticmethod
    def _vmappable(est: PredictorEstimator, grids: List[ParamMap],
                   problem_type: str) -> bool:
        if not getattr(est, "supports_grid_vmap", False):
            return False
        if problem_type == "multiclass":
            if not getattr(est, "supports_multiclass_vmap", False):
                return False
        elif problem_type not in ("binary", "regression"):
            return False
        _, axes = est.batched_fit_fn()
        return Validator._constant_off_axis(est, grids, axes)

    def _streamable(self, est: PredictorEstimator, grids: List[ParamMap],
                    problem_type: str, X, n_folds: int,
                    n_classes: int = 2) -> bool:
        """Large binary/regression GLM sweeps route through the streaming
        lane-batched kernel (ops/glm_sweep.py) — under a mesh, its
        shard_map variant (per-shard row scans, psum'd accumulators).
        (How the route then takes its metric — one held-out pass or one a
        fold — is not decided here: `heldout_metric_body`, a property of
        the metric, read in `_validate_streamed`.)
        Past TRI_MAX_D features the kernel switches internally to
        feature-tiled Gram accumulation, so width no longer excludes the
        route; the remaining guard is the per-iteration [L, d, d]
        Hessian-assembly + batched-solve footprint against the sweep HBM
        budget (lanes L = folds x grid points).

        A multiclass sweep takes the route at the same row floor when the
        estimator declares `streamed_multiclass_loss` and the sweep runs on
        ONE device: the multinomial rounds (ops/glm_sweep.sweep_mlr_round)
        have no shard_map or RowSource form yet, so a mesh keeps the
        vmapped route (ROADMAP R2); the guard is then the rounds' own
        [bucket x K, rows] block footprint."""
        multiclass = problem_type == "multiclass"
        if multiclass:
            if getattr(est, "streamed_multiclass_loss", None) is None \
                    or self._sweep_mesh is not None:
                return False
        elif getattr(est, "streamed_loss", None) is None \
                or problem_type not in ("binary", "regression"):
            return False
        # an assigned across-time warm seed (retrain refit) is only
        # consumable by the streamed rounds kernel — a seeded refit
        # takes this route regardless of scale, else the seed would be
        # silently dropped (and warm_seeded honestly reported False)
        if X.shape[0] < STREAMED_SWEEP_MIN_ROWS and (
                multiclass or getattr(self, "warm_seed", None) is None):
            return False
        from ...ops import glm_sweep as GS
        lanes = n_folds * max(len(grids), 1)
        d = X.shape[1]
        if multiclass:
            ok = GS.streamed_mlr_route_ok(d, lanes, n_classes,
                                          SWEEP_LANE_BUDGET_BYTES)
        elif self._wide_rounds(est.streamed_loss, d):
            ok = GS.streamed_wide_route_ok(d, lanes, SWEEP_LANE_BUDGET_BYTES)
        else:
            ok = GS.streamed_route_ok(d, lanes, SWEEP_LANE_BUDGET_BYTES)
        if not ok:
            return False
        _, axes = est.batched_fit_fn()
        return self._constant_off_axis(est, grids, axes)

    def _wide_rounds(self, loss: str, d: int) -> bool:
        """Does a binary sweep of this loss over d columns take the wide
        rounds (ops/glm_sweep.sweep_glm_wide_round)? Chosen from what is
        observed: logistic loss, more columns than the narrow Gram einsum
        holds, one device (a mesh keeps the feature-tiled rounds, which
        have a shard_map form and stop at 1 792 columns)."""
        from ...ops.glm_sweep import TRI_MAX_D
        return loss == "logistic" and d > TRI_MAX_D \
            and self._sweep_mesh is None

    # -- shared helpers for the device-sweep paths --------------------------
    def _margin_threshold(self, est) -> float:
        """Thresholded metrics: probability threshold t maps to margin
        logit(t) for probabilistic models; margin models cut at 0 (their
        decision rule)."""
        thr = float(getattr(self.evaluator, "threshold", 0.5))
        if getattr(est, "produces_probabilities", True) and 0.0 < thr < 1.0:
            return float(np.log(thr / (1.0 - thr)))
        return 0.0

    def _rank_bins(self, n_rows: int) -> Optional[int]:
        return RANK_METRIC_BINS if n_rows >= BINNED_RANK_METRIC_MIN_ROWS \
            else None

    def _auto_grid_chunk(self, n: int, d: int, n_folds: int,
                         itemsize: int, n_grids: int) -> int:
        if self.grid_chunk is not None:
            return max(1, int(self.grid_chunk))
        lane_bytes = max(n * d * itemsize, 1)
        if self._sweep_mesh is not None:
            # rows shard: per-chip lane cost shrinks
            from ...parallel.mesh import mesh_batch_count
            lane_bytes = max(
                lane_bytes // mesh_batch_count(self._sweep_mesh), 1)
        lanes = max(int(SWEEP_LANE_BUDGET_BYTES / lane_bytes), 1)
        # cap: total vmap lanes also scale XLA compile time — past ~8 grid
        # points per program the compile cost outweighs the dispatch savings
        return int(np.clip(lanes // max(n_folds, 1), 1, min(n_grids, 8)))

    def _device_arrays(self, X, y, w, masks, dtype):
        """Place sweep arrays on device. A matrix that already lives
        row-sharded on the sweep's mesh stays where it is (route
        `resident_sharded`: y, w and the masks join it in its layout,
        nothing is fetched, padded or copied through the host); host
        arrays with a mesh (`host_put`) pad rows to the batch axis (zero
        weight = inert everywhere: fits see mask*w, metrics see
        (1-mask)*w) and shard across it."""
        mesh = self._sweep_mesh
        route = "one_device" if mesh is None else \
            "resident_sharded" if self._resident else "host_put"
        with _phase("device_place", h2d_bytes=_host_bytes(X, y, w, masks),
                    route=route):
            if mesh is None:
                return (jnp.asarray(X, dtype), jnp.asarray(y, jnp.float32),
                        jnp.asarray(w, jnp.float32),
                        jnp.asarray(masks, jnp.float32))
            from ...parallel.mesh import (
                BATCH_AXIS, batch_sharding, mesh_is_multiprocess,
                pad_rows_to_multiple, sharded_along,
            )
            if self._resident:
                # device_put of an array already in the layout is the
                # array itself; a host y or w goes straight to its shards
                def put(a, sharding):
                    return jax.device_put(
                        jnp.asarray(a, jnp.float32)
                        if isinstance(a, jax.Array)
                        else np.asarray(a, np.float32), sharding)
                rows = batch_sharding(mesh, 1)
                return (jnp.asarray(X, dtype), put(y, rows), put(w, rows),
                        put(masks, sharded_along(mesh, 1, 2)))
            if mesh_is_multiprocess(mesh):
                # SPMD pod sweep: X/y/w/masks hold THIS PROCESS's rows; each
                # block lands as the process's batch-axis stripe of a global
                # array (same pad semantics as the single-host branch below:
                # X repeats its last row, weights pad 0 = inert, masks pad 1)
                from ...parallel import multihost as MH
                layout = MH.row_layout(np.asarray(X).shape[0], mesh)
                return (
                    MH.host_local_block(
                        np.asarray(np.asarray(X), jnp.dtype(dtype)),
                        mesh, layout, pad_value=None),
                    MH.host_local_block(np.asarray(y, np.float32),
                                        mesh, layout),
                    MH.host_local_block(np.asarray(w, np.float32),
                                        mesh, layout),
                    MH.host_local_block(np.asarray(masks, np.float32),
                                        mesh, layout, pad_value=1.0,
                                        axis=1),
                )
            nb = mesh.shape[BATCH_AXIS]
            # X pads by repeating the last real row (pad_value=None): tree
            # quantile binning is unweighted, so synthetic values would shift
            # bin edges. Labels/weights pad with zeros — inert in every
            # weighted reduction; masks pad with 1s (irrelevant under w=0).
            X, _ = pad_rows_to_multiple(np.asarray(X), nb, pad_value=None)
            y, _ = pad_rows_to_multiple(np.asarray(y, np.float32), nb)
            w, _ = pad_rows_to_multiple(np.asarray(w, np.float32), nb)
            masks = pad_rows_to_multiple(
                np.asarray(masks, np.float32).T, nb, pad_value=1.0)[0].T
            # device_put host arrays DIRECTLY with the sharding: jnp.asarray
            # first would commit the whole matrix to device 0 before resharding
            # — an OOM at exactly the >1-chip scale the mesh exists for
            put = jax.device_put
            return (
                put(np.asarray(X, jnp.dtype(dtype)),
                    batch_sharding(mesh, 2)),
                put(np.asarray(y, np.float32), batch_sharding(mesh, 1)),
                put(np.asarray(w, np.float32), batch_sharding(mesh, 1)),
                put(np.asarray(masks, np.float32),
                    sharded_along(mesh, 1, 2)),
            )

    def _sweep_path(self, base: str) -> str:
        """Checkpoint path tag: a mesh run pads rows (shifting tree bin
        edges and f32 reduction orders), so its metrics must not be
        replayed into a differently-sharded resume; externally supplied
        fold masks (workflow-level CV calls validate() once per fold,
        possibly on identical matrices when the in-fold DAG has no
        estimators) must not replay one fold's cells into another."""
        if self._external_mask_tag:
            base = f"{base}:masks{self._external_mask_tag}"
        if self._sweep_mesh is None:
            return base
        from ...parallel.mesh import BATCH_AXIS
        return f"{base}:mesh{self._sweep_mesh.shape.get(BATCH_AXIS, 1)}"

    def _cell_bookkeeping(self, est, grids, X, y, metric, n_folds,
                          path: str = ""):
        """(checkpoint, per-grid keys, finished results) — cell-level
        records shared across resumes of the SAME sweep path. `path` names
        the compute path and its statistically relevant knobs (mask-fold
        vs physically-split binning, sweep dtype): metrics from one path
        must never be replayed into another, since they can legitimately
        differ enough to flip the winner."""
        with _phase("bookkeeping"):
            from .checkpoint import data_fingerprint, sweep_key
            ckpt = self._checkpoint()
            if ckpt is None:
                return None, [None] * len(grids), {}
            data_fp = data_fingerprint(X, y)
            base_params = est.param_values() if hasattr(est, "param_values") \
                else None
            # a custom metric is an arbitrary function: its identity must be
            # part of the cell key, or editing the function silently replays
            # the OLD function's cached fold metrics (the name alone is not a
            # fingerprint the way built-in metric names are)
            metric_key = getattr(self.evaluator, "metric_key", metric)
            keys = [sweep_key(type(est).__name__, g, n_folds,
                              self.seed, self.stratify, metric_key,
                              data_fp=data_fp, base_params=base_params,
                              path=path)
                    for g in grids]
            results = {}
            for gi, key in enumerate(keys):
                done = ckpt.get(key)
                if done is not None:
                    results[gi] = [float(v) for v in done["fold_metrics"]]
            return ckpt, keys, results

    def _validate_vmapped(self, est, grids, X, y, w, masks, metric,
                          problem_type, n_classes=2) -> List[ValidatedModel]:
        """GLM-family sweep: ONE jitted program per grid chunk (vmap over
        folds x chunk). Chunking bounds the per-call HBM footprint — each
        lane materializes an [n, d] product for the Gram matmul — and gives
        the checkpoint mid-grid granularity (VERDICT r1 weak #9: the
        flagship vmapped sweep previously restarted from zero)."""
        base = est.copy(**{k: v for k, v in grids[0].items()})
        if problem_type == "multiclass":
            fit_one, _ = base.batched_fit_fn(n_classes=n_classes)
        else:
            fit_one, _ = base.batched_fit_fn()
        regs, alphas = self._grid_axis_arrays(est, grids)
        margin_thr = self._margin_threshold(est)

        dtype = self.sweep_dtype or jnp.float32
        ckpt, keys, results = self._cell_bookkeeping(
            est, grids, X, y, metric, masks.shape[0],
            path=self._sweep_path(f"vmapped:{jnp.dtype(dtype).name}"))
        pending = [gi for gi in range(len(grids)) if gi not in results]
        if pending:
            Xd, yd, wd, md = self._device_arrays(X, y, w, masks, dtype)
            thr_d = jnp.asarray(margin_thr, jnp.float32)
            rank_bins = self._rank_bins(X.shape[0])
            chunk = self._auto_grid_chunk(
                X.shape[0], X.shape[1], masks.shape[0],
                jnp.dtype(dtype).itemsize, len(pending))
            for start in range(0, len(pending), chunk):
                idx = pending[start:start + chunk]
                # pad the tail chunk so every call shares one compiled shape
                padded = idx + [idx[-1]] * (chunk - len(idx))
                with collector.trace_span(
                        f"glm_vmapped:{type(est).__name__}",
                        kind="sweep_fit", folds=int(masks.shape[0]),
                        chunk=chunk):
                    out = _sweep(Xd, yd, wd, md,
                                 jnp.asarray(regs[padded]),
                                 jnp.asarray(alphas[padded]), thr_d,
                                 fit_one=fit_one, metric=metric,
                                 problem_type=problem_type,
                                 n_classes=n_classes, rank_bins=rank_bins)
                    out = np.asarray(out)  # [F, chunk]
                with _phase("record", cells=len(idx)):
                    for j, gi in enumerate(idx):
                        fm = [float(v) for v in out[:, j]]
                        results[gi] = fm
                        if ckpt is not None:
                            ckpt.record(keys[gi], type(est).__name__,
                                        grids[gi], fm, metric)
                        self._cell_event(est, gi, fm, "vmapped")
        return [
            ValidatedModel(model_name=type(est).__name__, model_uid=est.uid,
                           grid=g, metric_name=metric,
                           fold_metrics=results[gi], route="vmapped")
            for gi, g in enumerate(grids)
        ]

    @staticmethod
    def _grid_axis_arrays(est, grids) -> Tuple[np.ndarray, np.ndarray]:
        """Per-grid (regs, alphas) along the estimator's sweep axes —
        shared by the vmapped and streamed paths."""
        _, axes = est.batched_fit_fn()
        regs = np.array([g.get(axes[0], est.get_param(axes[0]))
                         for g in grids], np.float32)
        second = axes[1] if len(axes) > 1 else None
        alphas = np.array([g.get(second, est.get_param(second)) if second
                           else 0.0 for g in grids], np.float32)
        return regs, alphas

    # -- streamed GLM path --------------------------------------------------
    _STREAMED_EVAL_CHUNK = 8

    def _round_checkpoint(self, keys, pending, fit_kwargs):
        """(RoundCheckpoint, key, resumable state) for the round driver —
        keyed by the pending cells' sweep keys (which already fold in the
        data fingerprint, masks, base params and compute path) plus the
        solver knobs, so state from a different sweep is never replayed."""
        if self.checkpoint_path is None or keys[0] is None:
            return None, None, None
        import hashlib
        import json as _json

        from .checkpoint import RoundCheckpoint
        # the third element was the value of an environment variable that
        # is gone; "" is what it read unset, so a round checkpoint written
        # before it went still resumes
        payload = _json.dumps(
            [[keys[gi] for gi in pending],
             {k: repr(v) for k, v in sorted(fit_kwargs.items())},
             ""], sort_keys=True)
        rkey = hashlib.sha256(payload.encode()).hexdigest()[:24]
        rc = RoundCheckpoint(self.checkpoint_path + ".glm_rounds.npz")
        return rc, rkey, rc.load(rkey)

    @staticmethod
    def _cell_event(est, gi, fm, route):
        """One `sweep_cell_landed` event per finished (model x grid) cell
        (all fold metrics exist) — the resumable unit of the sweep
        checkpoint, streamed so `tail -f events.jsonl` shows sweep
        progress cell by cell."""
        finite = [v for v in fm if np.isfinite(v)]
        collector.event(
            "sweep_cell_landed", model=type(est).__name__,
            grid_index=int(gi), route=route, n_folds=len(fm),
            mean_metric=float(np.mean(finite)) if finite else None)

    def _count_tree_lanes(self, est, lanes):
        """Sum what one tree fit of the sweep counted into
        last_tree_telemetry — the one place for both families, told apart
        by the dict's `route`. A forest grid point's lanes (no `route`:
        "forest_lanes"): tree_lanes, lane_groups, bootstrap_draws add up
        over a sweep's points; lanes_per_group is the widest; how a
        real-valued payload was carried — payload_body, payload_rows,
        features_per_node, label_centre and payload_scale, the last two
        fetched here — is the last point's, as are a class label's K
        channels (payload_body class_indicators, payload_rows K + 1,
        `classes`). A booster's fold-fused fits
        ("fold_fused": _TreeEstimator._count_booster_fit): programs,
        rounds and scale_reductions add up, lanes is the widest
        program's, payload_body and payload_rows the last fit's. The
        level passes its fits ran of those planned are fetched here, behind
        the cell's own fetch like a forest's centre, left as ints in the
        estimator's dict and sent as one `tree_levels_skipped` event; they
        stay out of last_tree_telemetry, which accepted tests hold to
        equality."""
        if lanes.get("route") == "fold_fused":
            tele = self.last_tree_telemetry or {
                "model": type(est).__name__, "route": "fold_fused",
                "programs": 0, "rounds": 0, "scale_reductions": 0,
                "lanes": 0}
            for key in ("programs", "rounds", "scale_reductions"):
                tele[key] += int(lanes[key])
            tele["lanes"] = max(tele["lanes"], int(lanes["lanes"]))
            for key in ("payload_body", "payload_rows"):
                tele[key] = lanes[key]
            self.last_tree_telemetry = tele
            lanes["level_passes_run"] = int(lanes["level_passes_run"])
            collector.event(
                "tree_levels_skipped", model=type(est).__name__,
                lanes=int(lanes["lanes"]), rounds=int(lanes["rounds"]),
                planned=int(lanes["level_passes_planned"]),
                run=lanes["level_passes_run"])
            return
        tele = self.last_tree_telemetry or {
            "model": type(est).__name__, "route": "forest_lanes",
            "tree_lanes": 0, "lane_groups": 0, "lanes_per_group": 0,
            "bootstrap_draws": 0}
        for key in ("tree_lanes", "lane_groups", "bootstrap_draws"):
            tele[key] += int(lanes[key])
        tele["lanes_per_group"] = max(tele["lanes_per_group"],
                                      int(lanes["lanes_per_group"]))
        if lanes["payload_body"] != "indicator":
            # a 0/1 label's three-row lanes report what they always did
            # (the accepted sweep-rf test holds that dict to equality);
            # their word is on the forest_group spans
            for key in ("payload_body", "payload_rows",
                        "features_per_node"):
                tele[key] = lanes[key]
            if lanes["label_centre"] is not None:   # a real-valued label's
                tele["label_centre"], tele["payload_scale"] = map(
                    float, lanes["label_centre"])
            if "classes" in lanes:                  # K class channels'
                tele["classes"] = int(lanes["classes"])
        self.last_tree_telemetry = tele

    def _record_sweep_telemetry(self, est, info):
        self.last_streamed_telemetry = dict(info,
                                            model=type(est).__name__)
        if collector.enabled:
            collector.sweep_convergence(
                family=type(est).__name__, kernel=info["kernel"],
                rounds=info.get("glm_rounds", 0),
                data_passes=info.get("data_passes", 0),
                lane_passes=info.get("lane_passes", 0),
                lanes_total=info.get("lanes_total", 0),
                lanes_retired=info.get("lanes_retired", 0),
                active_per_round=info.get("active_per_round", ()),
                iters_per_round=info.get("iters_per_round", ()),
                bucket_sizes=info.get("bucket_sizes", ()))

    def _streamed_fit(self, est, fit_kwargs, Xd, yd, wd, md, regs_p,
                      alphas_p, keys, pending):
        """Fit every pending (fold x grid) lane through THE streamed
        kernel of the loss (docs/performance.md "Convergence-aware GLM
        sweep"): softmax -> the multinomial rounds; squared ->
        sufficient-statistics Gram fast path (ONE streaming pass for the
        whole sweep); every other loss -> the host-driven round loop with
        per-lane retirement and bucket-ladder compaction (the rounds
        checkpoint when a checkpoint path is set). Returns (B [F, Gp, d]
        jnp RAW units, b0, telemetry info dict, round-checkpoint or None — the
        CALLER clears it only after the cells land in the JSONL
        checkpoint, so a preemption during metric evaluation still
        resumes from the fully-retired round state instead of
        refitting)."""
        from ...ops import glm_sweep as GS

        loss = fit_kwargs["loss"]
        F = int(md.shape[0])
        L = F * len(pending)

        def round_hooks():
            """(round checkpoint, resumable state, on_round) of a round
            driver: one event per retirement boundary — the tail of
            events.jsonl IS the live convergence picture of a multi-hour
            sweep (GLM round retired / checkpoint saved)."""
            rc, rkey, state = self._round_checkpoint(keys, pending,
                                                     fit_kwargs)

            def on_round(st):
                if rc is not None:
                    rc.save(rkey, st)
                    collector.event("round_checkpoint_written",
                                    path=rc.path, rounds=int(st["rounds"]))
                collector.event(
                    "glm_round_retired", rounds=int(st["rounds"]),
                    lanes_retired=int(st["retired"].sum()),
                    lanes_active=int((~st["retired"]).sum()),
                    lane_passes=int(st["lane_passes"]))
            return rc, state, on_round

        if loss == "softmax":
            # the multinomial rounds: the only streamed multiclass kernel
            # (_streamable keeps a mesh off this route)
            rc, state, on_round = round_hooks()
            fk = {k: v for k, v in fit_kwargs.items() if k != "loss"}
            B, b0, info = GS.sweep_mlr_streamed_rounds(
                Xd, yd, wd, md, np.asarray(regs_p), np.asarray(alphas_p),
                state=state, on_round=on_round, **fk)
            return jnp.asarray(B), jnp.asarray(b0), info, rc
        if loss == "squared":
            fk = {k: v for k, v in fit_kwargs.items() if k != "loss"}
            mi, tl = fk.pop("max_iter"), fk.pop("tol")
            mesh = self._sweep_mesh
            d = int(Xd.shape[1])
            # the pass's programs are dispatched inside `gram_pass`; the
            # host then waits in `gram_solve` for the solves' two counts
            # and the column moments' word on which body took the moments
            # (`glm_sweep.gram_pass_body`), the fit's only fetch
            with collector.trace_span(
                    "gram_pass", kind="host_step", folds=F, cols=d,
                    body=GS.GRAM_PASS_BODY, x_tile=GS.glm_x_tile(d),
                    moments_body=GS.gram_pass_body(Xd.dtype, d)) as gp:
                gram = GS.sweep_glm_squared_gram if mesh is None \
                    else partial(GS.sweep_glm_squared_gram_sharded, mesh)
                B, b0, *counts = gram(Xd, yd, wd, md, regs_p, alphas_p, mi,
                                      tl, **fk)
            with collector.trace_span("gram_solve", kind="host_step",
                                      lanes=L) as sp:
                giters, gcap, raw = (int(v) for v in jax.device_get(counts))
                body = GS.gram_pass_body(Xd.dtype, d, bool(raw))
                if sp is not None:
                    sp.attrs.update(iters=giters, lanes_at_cap=gcap)
                    gp.attrs.update(moments_body=body)
            info = {"route": "streamed", "kernel": "gram",
                    "gram_body": GS.GRAM_PASS_BODY,
                    "gram_moments_body": body,
                    "glm_rounds": 1, "data_passes": 1, "lane_passes": F,
                    "padded_lane_passes": F,  # the Gram pass never pads
                    "lanes_total": L, "lanes_retired": L - gcap,
                    "lanes_at_cap": gcap, "gram_solve_iters": giters,
                    # whole reads of X by the fit: the column moments'
                    # two passes (`_psum_moments`) and the Gram pass
                    # (`x_passes`, once the metric's are known)
                    "fit_x_passes": 1 + 2 * bool(fk["standardize"])}
            return B, b0, info, None
        rc, state, on_round = round_hooks()
        # across-time warm seed (retrain refit): the previous champion's
        # raw coefficients, threaded selector -> validator
        # (ModelSelector.fit_arrays). The sweep ignores a seed whose
        # dimension disagrees with this vectorization.
        seed = getattr(self, "warm_seed", None)
        seed_t = None
        if isinstance(seed, dict) and seed.get("beta") is not None:
            seed_t = (np.asarray(seed["beta"], np.float32),
                      float(seed.get("intercept", 0.0)))
        if self._wide_rounds(loss, int(Xd.shape[1])):
            # thousands of columns: the bound-optimisation rounds, which
            # never form a [d, d] array a lane
            fk = {k: v for k, v in fit_kwargs.items() if k != "loss"}
            B, b0, info = GS.sweep_glm_wide_streamed_rounds(
                Xd, yd, wd, md, np.asarray(regs_p), np.asarray(alphas_p),
                state=state, on_round=on_round, warm_seed=seed_t, **fk)
            return jnp.asarray(B), jnp.asarray(b0), info, rc
        # the IRLS rounds; _residual_curvature refuses a loss it does not
        # know
        B, b0, info = GS.sweep_glm_streamed_rounds(
            Xd, yd, wd, md, np.asarray(regs_p), np.asarray(alphas_p),
            mesh=self._sweep_mesh, state=state, on_round=on_round,
            warm_seed=seed_t, **fit_kwargs)
        return jnp.asarray(B), jnp.asarray(b0), info, rc

    def _validate_streamed(self, est, grids, X, y, w, masks, metric,
                           problem_type, n_classes=2
                           ) -> List[ValidatedModel]:
        """Streamed convergence-aware sweep: every pending (fold x grid)
        cell fits through _streamed_fit (Gram fast path / retirement round
        driver / multinomial rounds); the metric then takes ONE pass over
        X a grid chunk where it is a sum or a count over held-out rows and
        no row is held out twice (`heldout_metric_body`: binned AuPR /
        AuROC of a binary sweep, RMSE / MSE / MAE / R2 of a regression
        sweep; `eval_route` heldout_once, one fetch a sweep), and
        otherwise runs per fold in grid chunks of one scoring matmul each
        (multiclass: one block-scanned confusion count each)."""
        multiclass = problem_type == "multiclass"
        regs, alphas = self._grid_axis_arrays(est, grids)
        # constant off-axis grid keys (admitted by _constant_off_axis) must
        # bind exactly as on the vmapped path: est.copy(**grids[0])
        base = est.copy(**{k: v for k, v in grids[0].items()})
        margin_thr = self._margin_threshold(est)
        dtype = self.sweep_dtype or jnp.float32
        # stale telemetry must never survive into a sweep that runs no fit
        # (fully checkpoint-resumed): bench would pair a previous sweep's
        # lane_passes with this sweep's near-zero wall
        self.last_streamed_telemetry = None
        ckpt, keys, results = self._cell_bookkeeping(
            est, grids, X, y, metric, masks.shape[0],
            path=self._sweep_path(f"streamed:{jnp.dtype(dtype).name}"))
        pending = [gi for gi in range(len(grids)) if gi not in results]
        if pending:
            Xd, yd, wd, md = self._device_arrays(X, y, w, masks, dtype)
            fit_kwargs = dict(
                loss=est.streamed_multiclass_loss if multiclass
                else est.streamed_loss,
                max_iter=int(base.get_param("max_iter")),
                tol=float(base.get_param("tol")),
                fit_intercept=bool(base.get_param("fit_intercept"))
                if base.has_param("fit_intercept") else True,
                standardize=bool(base.get_param("standardization"))
                if base.has_param("standardization") else True)
            if multiclass:
                fit_kwargs["n_classes"] = int(n_classes)
            from ...parallel.mesh import mesh_batch_count, mesh_is_multiprocess
            mesh = self._sweep_mesh
            shards = mesh_batch_count(mesh)
            from ...ops.glm_sweep import bucket_lanes, wide_padded_cols
            d = int(X.shape[1])
            lanes = int(masks.shape[0]) * len(pending)
            fit_attrs = dict(folds=int(masks.shape[0]), grids=len(pending),
                             classes=int(n_classes), shards=shards, cols=d,
                             lanes=lanes, bucket=bucket_lanes(lanes),
                             standardize=fit_kwargs["standardize"])
            if not multiclass and self._wide_rounds(fit_kwargs["loss"], d):
                fit_attrs.update(padded_cols=wide_padded_cols(d))
            with collector.trace_span(
                    f"glm_streamed:{type(est).__name__}", kind="sweep_fit",
                    **fit_attrs) as sp:
                B, b0, sweep_info, round_ckpt = self._streamed_fit(
                    est, fit_kwargs, Xd, yd, wd, md,
                    jnp.asarray(regs[pending]), jnp.asarray(alphas[pending]),
                    keys, pending)
                if sp is not None:
                    sp.attrs["kernel"] = sweep_info.get("kernel")
            rank_bins = self._rank_bins(X.shape[0])
            thr_d = jnp.asarray(margin_thr, jnp.float32)
            F = int(masks.shape[0])
            chunk = min(self._STREAMED_EVAL_CHUNK, len(pending))
            # (cells, the same padded: every call shares one compiled shape)
            chunks = []
            for s in range(0, len(pending), chunk):
                idx = list(range(s, min(s + chunk, len(pending))))
                chunks.append(
                    (idx, jnp.asarray(idx + [idx[-1]] * (chunk - len(idx)))))
            # disjoint held-out sets and a metric that is a sum or a count
            # over held-out rows (`heldout_metric_body`): every row is
            # scored ONCE for all folds, on a mesh by the chip that holds
            # it; otherwise (and across processes) fold by fold over the
            # whole matrix
            body = heldout_metric_body(metric, problem_type, rank_bins)
            binned_lanes = body == "bins"
            unit = self._unit_payload
            heldout_once = (
                self._heldout_once and not mesh_is_multiprocess(mesh)
                and body is not None)
            if heldout_once and mesh is not None:
                eval_fn = _sharded_eval_heldout_fn(mesh, metric, rank_bins)
                # a chunk: one psum of both classes' [F, Gc, bins] counts,
                # or of the folds' [F, 2] label sums and then of the
                # [F, 4, Gc] residual sums
                eval_psums = len(chunks) * (1 if binned_lanes else 2)
                eval_psum_bytes = len(chunks) * 4 * (
                    2 * F * chunk * rank_bins if binned_lanes
                    else F * (2 + 4 * chunk))
            else:
                eval_fn = partial(_streamed_eval_heldout, metric=metric,
                                  rank_bins=rank_bins)
                eval_psums = eval_psum_bytes = 0
            eval_info = {
                "eval_route": "heldout_once" if heldout_once else "per_fold",
                "passes": len(chunks) * (1 if heldout_once else F),
                "shards": shards}
            if heldout_once:
                eval_info["metric_body"] = body
            if binned_lanes and (heldout_once or mesh is None):
                # the lane-batched counts run: which histogram body, and
                # the parts it takes the weights in
                eval_info.update(M.rank_hist_kernel(rank_bins, unit))
            if "fit_x_passes" in sweep_info:
                # the Gram route: whole reads of X in the SWEEP, the fit's
                # and the metric's (the wide rounds' `x_passes` are their
                # fit's alone)
                eval_info["x_passes"] = \
                    sweep_info["fit_x_passes"] + eval_info["passes"]
            # the layout the sweep ran on, and the collectives it declares
            # (the rounds' own and the metric pass's; a per_fold pass on a
            # mesh leaves its collectives to GSPMD, uncounted)
            self._record_sweep_telemetry(est, dict(
                sweep_info, **eval_info,
                rows_per_shard=int(Xd.shape[0]) // shards,
                psums=int(sweep_info.get("psums", 0)) + eval_psums,
                psum_bytes=int(sweep_info.get("psum_bytes", 0))
                + eval_psum_bytes))
            out = np.empty((F, len(pending)), np.float64)
            with collector.trace_span(
                    f"glm_streamed_eval:{type(est).__name__}",
                    kind="sweep_eval", cells=len(pending),
                    classes=int(n_classes), **eval_info):
                if heldout_once:
                    # (`unit` by position: what wraps a metric program
                    # hands its positional arguments on)
                    vals = [eval_fn(Xd, yd, wd, md, B[:, padded],
                                    b0[:, padded], unit)
                            for _, padded in chunks]
                    # the chunks' [F, Gc] values wait on the device for
                    # ONE fetch a sweep; the tail's padding comes last
                    with collector.trace_span("metric_fetch",
                                              kind="host_step"):
                        out[:] = np.asarray(jnp.concatenate(
                            vals, axis=1))[:, :len(pending)]
                else:
                    for f in range(F):
                        vw = (1.0 - md[f]) * wd
                        for idx, padded in chunks:
                            vals = _streamed_eval(
                                Xd, yd, vw, B[f, padded], b0[f, padded],
                                thr_d, metric=metric,
                                problem_type=problem_type,
                                n_classes=n_classes, rank_bins=rank_bins,
                                chunk=chunk, use_lanes=mesh is None,
                                unit_payload=unit)
                            with collector.trace_span("metric_fetch",
                                                      kind="host_step"):
                                out[f, idx] = np.asarray(vals)[:len(idx)]
            with _phase("record", cells=len(pending)):
                for j, gi in enumerate(pending):
                    fm = [float(v) for v in out[:, j]]
                    results[gi] = fm
                    if ckpt is not None:
                        ckpt.record(keys[gi], type(est).__name__,
                                    grids[gi], fm, metric)
                    self._cell_event(est, gi, fm, "streamed")
                if round_ckpt is not None:
                    # only NOW are all cells in the JSONL checkpoint: a
                    # preemption during the evaluation above resumes from
                    # the fully-retired round state instead of refitting
                    round_ckpt.clear()
        return [
            ValidatedModel(model_name=type(est).__name__, model_uid=est.uid,
                           grid=g, metric_name=metric,
                           fold_metrics=results[gi], route="streamed")
            for gi, g in enumerate(grids)
        ]

    # -- mask-fold tree path ------------------------------------------------
    def _validate_mask_folds(self, est, grids, X, y, w, masks, metric,
                             problem_type, n_classes=2
                             ) -> List[ValidatedModel]:
        """Tree-family sweep with folds as weight masks: the feature matrix
        is quantile-binned ONCE on device, then every (grid, fold) fit runs
        against it with the fold's training mask as sample weights — no host
        slicing, no per-fold data movement (VERDICT r1: the sequential
        fallback re-sliced X per fold, 'exactly the Spark-era shape'). The
        fold axis is vmapped; grids stay sequential because tree params
        (depth, rounds) are XLA-static."""
        self.last_tree_telemetry = None
        margin_thr = self._margin_threshold(est)
        ckpt, keys, results = self._cell_bookkeeping(
            est, grids, X, y, metric, masks.shape[0],
            path=self._sweep_path(
                "mask_folds:host" if (self._sweep_mesh is None
                                      and est._host_route())
                else "mask_folds"))
        pending = [gi for gi in range(len(grids)) if gi not in results]
        fused_gis: Dict[int, str] = {}   # cell -> fused route label
        # ("mask_folds:grid_fused" / ":grid_fused_sharded" on a mesh) —
        # route attribution for bench/MFU readers
        # consecutive fused-route failure escalation: one sweep-level
        # warning on first failure, silent per-config fallback while the
        # streak stays short, a raise once it reaches the cap
        fuse_fail_streak = 0
        fuse_failures = 0
        fuse_max_failures = grid_fuse_max_failures()
        if pending:
            # trees only read X through quantile binning, so the bf16 sweep
            # dtype is safe here too and halves the resident matrix
            Xd, yd, wd, md = self._device_arrays(
                X, y, w, masks, self.sweep_dtype or jnp.float32)
            rank_bins = self._rank_bins(X.shape[0])
            mfn = _metric_fn(problem_type, metric, n_classes, rank_bins)
            thr_d = jnp.asarray(margin_thr, jnp.float32)
            # mesh runs keep the vmapped metric (pallas must not consume
            # row-sharded operands)
            lanes_fn = _lanes_metric_fn(
                metric, problem_type, rank_bins, self._unit_payload) \
                if self._sweep_mesh is None else None
            # what the metric program of this route is, on its span: the
            # lane-batched binned counts ("bins", with the kernel's own
            # words) or the metric function vmapped over the folds' whole
            # score rows ("vmapped": every regression and class metric —
            # the tree route has no one-pass body of sums)
            hist_attrs = dict(metric=metric, metric_body="vmapped") \
                if lanes_fn is None else dict(
                    M.rank_hist_kernel(rank_bins, self._unit_payload),
                    metric=metric, metric_body="bins")

            @jax.jit
            def fold_metrics(scores, y_, w_, m_, t_):
                if lanes_fn is not None:
                    # scores [F, n]: all folds through ONE lane-batched
                    # binned-counts kernel (pallas on TPU; a fold-vmapped
                    # scatter-add would serialize there)
                    return lanes_fn(scores, y_, (1.0 - m_) * w_[None, :])

                def per_fold(s, m):
                    return mfn(s, y_, (1.0 - m) * w_, t_)
                return jax.vmap(per_fold)(scores, m_)

            # the binned context depends on max_bins, which may itself be a
            # grid axis — group grids by value and bin once per GROUP,
            # releasing each multi-GB [n, d] binned matrix before the next
            # (three live contexts at the 10M config would eat the HBM
            # budget the lane chunker assumes)
            def bins_of(gi):
                g = grids[gi]
                if "max_bins" in g:
                    return g["max_bins"]
                return est.get_param("max_bins") \
                    if est.has_param("max_bins") else None

            groups: Dict[Any, List[int]] = {}
            for gi in pending:
                groups.setdefault(bins_of(gi), []).append(gi)
            multicls = problem_type == "multiclass"

            def depth_of(gi):
                g = grids[gi]
                if "max_depth" in g:
                    return int(g["max_depth"])
                return int(est.get_param("max_depth")) \
                    if est.has_param("max_depth") else 0
            fuse_on = grid_fuse_on()
            for bins, group in sorted(groups.items(),
                                      key=lambda kv: str(kv[0])):
                # n_valid: mesh runs pad rows (repeat-last) — the quantile
                # sketch must see only the real rows so mesh and meshless
                # sweeps grow from identical bin edges
                with _phase("tree_bin", bins=int(bins or 0),
                            configs=len(group)):
                    ctx = est.copy(**grids[group[0]]).mask_sweep_context(
                        Xd, n_valid=X.shape[0], mesh=self._sweep_mesh)

                def record(gi, scores_f, route=None):
                    # a forest's lane route hands a class label's scores
                    # over class-major: their own metric program
                    major = isinstance(scores_f, ClassMajorScores)
                    attrs = dict(hist_attrs, classes=int(n_classes)) \
                        if multicls else hist_attrs
                    if major:
                        attrs["metric_body"] = "class_major_confusion"
                    with _phase("fold_metrics", lanes=int(md.shape[0]),
                                depth=depth_of(gi), **attrs):
                        out = np.asarray(
                            _class_major_metrics(
                                scores_f.scores, yd, wd, md, metric=metric,
                                n_classes=int(n_classes)) if major
                            else fold_metrics(scores_f, yd, wd, md, thr_d))
                    with _phase("record", cells=1):
                        fm = [float(v) for v in out]
                        results[gi] = fm
                        if ckpt is not None:
                            ckpt.record(keys[gi], type(est).__name__,
                                        grids[gi], fm, metric)
                        self._cell_event(est, gi, fm, route or "mask_folds")

                # config fusion: grid points whose structural signature
                # matches fit ONE fold-fused device program (lanes =
                # configs x folds) — one histogram pass serves them all
                sig_of = getattr(est, "grid_fuse_signature", lambda g: None)
                sig_groups: Dict[Any, List[int]] = {}
                for gi in group:
                    sig = sig_of(grids[gi])
                    key = ("solo", gi) if sig is None else ("fuse", sig)
                    sig_groups.setdefault(key, []).append(gi)
                for key, gis in sig_groups.items():
                    fused = None
                    # the widened-M hist programs are bitwise-correct
                    # (ops-level parity suite) but their Mosaic compiles
                    # ran 20+ minutes at the 2M x 20-lane shape on first
                    # hardware contact, hence the opt-in above
                    if key[0] == "fuse" and len(gis) > 1 and fuse_on:
                        try:
                            with _phase("tree_fit",
                                        lanes=int(md.shape[0]) * len(gis),
                                        depth=depth_of(gis[0])):
                                fused = est.mask_fit_scores_grid(
                                    ctx, yd, wd, md,
                                    [grids[gi] for gi in gis],
                                    n_classes=n_classes,
                                    multiclass=multicls,
                                    mesh=self._sweep_mesh)
                        except Exception as e:  # never lose the sweep to
                            # the fast path: per-config route is the
                            # correctness baseline — but a route that
                            # fails REPEATEDLY is a broken kernel, not a
                            # per-config nuisance: count the streak, warn
                            # once at sweep level, raise at the cap
                            fuse_fail_streak += 1
                            fuse_failures += 1
                            collector.event(
                                "fused_route_fallback",
                                model=type(est).__name__,
                                error_type=type(e).__name__,
                                streak=fuse_fail_streak,
                                configs=len(gis))
                            if fuse_fail_streak >= fuse_max_failures:
                                raise RuntimeError(
                                    f"config-fused sweep route failed "
                                    f"{fuse_fail_streak} consecutive "
                                    f"times (last: {type(e).__name__}: "
                                    f"{e}); the fused kernel path is "
                                    f"dead — fix it or unset "
                                    f"TMOG_GRID_FUSE") from e
                            import logging
                            logger = logging.getLogger(__name__)
                            if fuse_failures == 1:
                                logger.warning(
                                    "config-fused sweep failed (%s); "
                                    "falling back per-config (further "
                                    "failures logged at DEBUG; raising "
                                    "after %d consecutive)", e,
                                    fuse_max_failures)
                            else:
                                logger.debug(
                                    "config-fused sweep failure %d: %s",
                                    fuse_failures, e)
                            fused = None
                    if fused is not None:
                        fuse_fail_streak = 0
                        # the estimator stamps which fused form ran
                        # (sharded on a mesh) right before returning
                        grid_route = "mask_folds:" + getattr(
                            est, "_last_grid_route", "grid_fused")
                        for k, gi in enumerate(gis):
                            record(gi, fused[k], route=grid_route)
                            fused_gis[gi] = grid_route
                        if est.last_lane_telemetry:
                            self._count_tree_lanes(
                                est, est.last_lane_telemetry)
                        continue
                    for gi in gis:
                        est_g = est.copy(**grids[gi])
                        with _phase("tree_fit", lanes=int(md.shape[0]),
                                    depth=depth_of(gi)):
                            scores = est_g.mask_fit_scores(
                                ctx, yd, wd, md, n_classes=n_classes,
                                multiclass=multicls)
                        # a forest that ran as (tree, fold) lanes of the
                        # fused passes says so, and what it counted
                        lanes = getattr(est_g, "last_lane_telemetry", None)
                        if lanes and "route" not in lanes:
                            fused_gis[gi] = "mask_folds:forest_lanes"
                        record(gi, scores, route=fused_gis.get(gi))
                        if lanes:   # after the cell's own fetch: the
                            # centre's waits for nothing
                            self._count_tree_lanes(est, lanes)
                del ctx  # free the binned matrix before the next group
            if fuse_failures:
                import logging
                logging.getLogger(__name__).warning(
                    "config-fused sweep: %d group(s) fell back to the "
                    "per-config route this sweep", fuse_failures)
        return [
            ValidatedModel(model_name=type(est).__name__, model_uid=est.uid,
                           grid=g, metric_name=metric,
                           fold_metrics=results[gi],
                           route=fused_gis.get(gi, "mask_folds"))
            for gi, g in enumerate(grids)
        ]

    # -- sequential fallback ----------------------------------------------
    def _checkpoint(self):
        if self.checkpoint_path is None:
            return None
        from .checkpoint import SweepCheckpoint
        return SweepCheckpoint(self.checkpoint_path)

    def _validate_sequential(self, est, grids, X, y, w, masks
                             ) -> List[ValidatedModel]:
        metric = self.evaluator.default_metric
        # the one route that indexes rows on the host
        w, masks = np.asarray(w), np.asarray(masks)
        ckpt, keys, results = self._cell_bookkeeping(
            est, grids, X, y, metric, masks.shape[0],
            path=self._sweep_path(
                "sequential:host"
                if getattr(est, "_host_route", lambda: False)()
                else "sequential"))
        for gi, g in enumerate(grids):
            if gi in results:
                continue
            est_g = est.copy(**g)
            fold_vals: List[float] = []
            with collector.trace_span(
                    f"sequential:{type(est).__name__}", kind="sweep_fit",
                    folds=int(masks.shape[0]), grid_index=gi):
                for f in range(masks.shape[0]):
                    tr = masks[f] > 0
                    va = ~tr
                    model = est_g.fit_arrays(X[tr], y[tr], w[tr])
                    pred, raw, prob = model.predict_arrays(X[va])
                    col = make_prediction_column(pred, raw, prob)
                    fold_vals.append(
                        self.evaluator.evaluate(y[va], col, w[va]))
            with _phase("record", cells=1):
                results[gi] = fold_vals
                if ckpt is not None:
                    ckpt.record(keys[gi], type(est).__name__, g, fold_vals,
                                metric)
                self._cell_event(est, gi, fold_vals, "sequential")
        return [
            ValidatedModel(model_name=type(est).__name__, model_uid=est.uid,
                           grid=g, metric_name=metric,
                           fold_metrics=results[gi], route="sequential")
            for gi, g in enumerate(grids)
        ]


class CrossValidation(Validator):
    """k-fold CV (reference OpCrossValidation.scala:41; NumFolds default 3)."""

    def __init__(self, evaluator: Evaluator, num_folds: int = 3,
                 seed: int = 42, stratify: bool = False, parallelism: int = 8,
                 **kwargs):
        super().__init__(evaluator, seed=seed, stratify=stratify,
                         parallelism=parallelism, **kwargs)
        if num_folds < 2:
            raise ValueError("num_folds must be >= 2")
        self.num_folds = int(num_folds)

    def _fold_spec(self) -> Dict[str, Any]:
        return {"n_folds": self.num_folds}


class TrainValidationSplit(Validator):
    """Single split (reference OpTrainValidationSplit.scala:34;
    TrainRatio default 0.75)."""

    def __init__(self, evaluator: Evaluator, train_ratio: float = 0.75,
                 seed: int = 42, stratify: bool = False, parallelism: int = 8,
                 **kwargs):
        super().__init__(evaluator, seed=seed, stratify=stratify,
                         parallelism=parallelism, **kwargs)
        if not 0.0 < train_ratio < 1.0:
            raise ValueError("train_ratio must be in (0, 1)")
        self.train_ratio = float(train_ratio)

    def _fold_spec(self) -> Dict[str, Any]:
        return {"n_folds": 1, "val_fraction": 1.0 - self.train_ratio}
