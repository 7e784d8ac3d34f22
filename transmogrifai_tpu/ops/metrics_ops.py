"""Classification/regression metric kernels (pure jnp, mask-aware).

Reference: core/.../evaluators/ — OpBinaryClassificationEvaluator.scala:56
(Precision/Recall/F1/AuROC/AuPR/Error/TP-TN-FP-FN + threshold curves),
OpMultiClassificationEvaluator.scala:58, OpRegressionEvaluator.scala:61.

AuROC/AuPR are sort-based with exact tie handling (metrics evaluated only at
threshold boundaries), matching Spark MLlib's BinaryClassificationMetrics
semantics. All functions accept a weight vector so padded rows (device
sharding) and fold masks (CV) cost nothing.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

EPS = 1e-12


def _sorted_cum_counts(scores: jax.Array, labels: jax.Array,
                       w: Optional[jax.Array] = None):
    """Sort by score desc; cumulative weighted TP/FP; tie-boundary mask.
    One multi-operand sort carries the weighted labels along with the
    scores (no argsort + gathers)."""
    if w is None:
        w = jnp.ones_like(scores)
    neg, pos_w, neg_w = jax.lax.sort(
        (-scores, labels * w, (1.0 - labels) * w), num_keys=1)
    tps = jnp.cumsum(pos_w)
    fps = jnp.cumsum(neg_w)
    # boundary i is valid if score[i] != score[i+1] (last of a tie group)
    nxt = jnp.concatenate([neg[1:], jnp.array([jnp.inf], neg.dtype)])
    boundary = (neg != nxt)
    # zero-weight rows (padding) sort to a tie group; ensure they are inert:
    # their ww=0 contributes nothing to cumsums. They may create spurious
    # boundaries but with unchanged cumulative counts => zero-area segments.
    return tps, fps, boundary


def _previous_boundary(values: jax.Array, boundary: jax.Array) -> jax.Array:
    """values at the boundary BEFORE each position (0 before the first), for
    non-decreasing non-negative `values` (cumulative counts and what is
    proportional to them): a running maximum over the boundary points,
    shifted by one. It replaces a carry through a scalar loop of one step a
    row, which at a million rows cost seconds on the chip and filled its
    profiler's trace."""
    seen = jax.lax.cummax(jnp.where(boundary, values, 0.0))
    return jnp.concatenate([jnp.zeros(1, values.dtype), seen[:-1]])


@jax.jit
def au_roc(scores: jax.Array, labels: jax.Array,
           w: Optional[jax.Array] = None) -> jax.Array:
    """Area under ROC (trapezoid over tie-boundary points)."""
    tps, fps, boundary = _sorted_cum_counts(scores, labels, w)
    P = tps[-1]
    N = fps[-1]
    tpr = tps / jnp.maximum(P, EPS)
    fpr = fps / jnp.maximum(N, EPS)
    # from (0, 0): sum over boundary points of
    # (fpr_i - fpr_prev) * (tpr_i + tpr_prev) / 2, prev = the boundary before
    pf = _previous_boundary(fpr, boundary)
    pt = _previous_boundary(tpr, boundary)
    return jnp.where(boundary, (fpr - pf) * (tpr + pt) * 0.5, 0.0).sum()


@jax.jit
def au_pr(scores: jax.Array, labels: jax.Array,
          w: Optional[jax.Array] = None) -> jax.Array:
    """Area under precision-recall (step interpolation / average precision)."""
    tps, fps, boundary = _sorted_cum_counts(scores, labels, w)
    P = jnp.maximum(tps[-1], EPS)
    recall = tps / P
    precision = tps / jnp.maximum(tps + fps, EPS)
    pr = _previous_boundary(recall, boundary)
    return jnp.where(boundary, (recall - pr) * precision, 0.0).sum()


def _bin_idx(scores: jax.Array, n_bins: int) -> jax.Array:
    """Shared score->bucket rule for every binned-counts route (scores pass
    through a sigmoid — monotone, so ranking is unchanged whether the
    caller supplies margins or probabilities)."""
    p = jax.nn.sigmoid(scores.astype(jnp.float32))
    return jnp.clip((p * n_bins).astype(jnp.int32), 0, n_bins - 1)


def _binned_cum_counts(scores: jax.Array, labels: jax.Array,
                       w: Optional[jax.Array], n_bins: int):
    """Weighted TP/FP cumulative counts over a score histogram.

    Scores land in `n_bins` equal-width buckets (_bin_idx); one
    scatter-add replaces the O(n log n) sort of `_sorted_cum_counts`.
    Cumulative counts run from the high-score end, so bucket k's entry is
    the (TP, FP) at threshold k/n_bins."""
    if w is None:
        w = jnp.ones_like(scores)
    idx = _bin_idx(scores, n_bins)
    pos = jnp.zeros(n_bins, jnp.float32).at[idx].add(labels * w)
    neg = jnp.zeros(n_bins, jnp.float32).at[idx].add((1.0 - labels) * w)
    tps = jnp.cumsum(pos[::-1])
    fps = jnp.cumsum(neg[::-1])
    return tps, fps


def _pallas_route() -> bool:
    """Do the lane-batched counts take the pallas histogram here? (A TPU
    backend with the kernels enabled; everywhere else the scatter twins.)"""
    if jax.default_backend() != "tpu":
        return False
    from . import pallas_hist
    return pallas_hist.available()


def rank_hist_kernel(n_bins: int, unit_payload: bool = False
                     ) -> Dict[str, object]:
    """What the lane-batched counts below run at `n_bins`, for the spans
    that record it: `hist_body`, the histogram body pallas_hist.hist_pallas
    chooses for a rank-metric call ("two_level" | "one_level":
    pallas_rank_hist.hist_body, the function that makes the choice) or
    "scatter" where the jnp twins run, and `payload_parts`, the bfloat16
    parts the two-level body takes the weights in (pallas_rank_hist.
    payload_parts: 1 where the caller vouches by `unit_payload` that
    weights x labels are zeros and ones, else 3)."""
    from . import pallas_rank_hist
    return {"hist_body": pallas_rank_hist.hist_body(n_bins, False)
            if _pallas_route() else "scatter",
            "payload_parts": pallas_rank_hist.payload_parts(unit_payload)}


def binned_cum_counts_lanes(scores: jax.Array, labels: jax.Array,
                            w_lanes: jax.Array, n_bins: int, *,
                            unit_payload: bool = False
                            ) -> Tuple[jax.Array, jax.Array]:
    """Per-lane weighted TP/FP cumulative counts: scores [L, n] (one lane
    per fold/grid cell over the SAME rows), labels [n], w_lanes [L, n].

    TPU route: ONE pallas histogram call for all lanes — the lane id is
    the kernel's slot axis (ops/pallas_hist.py), so the [L, n] scatter-add
    the vmapped path would lower to (TPU serializes scatters) becomes MXU
    one-hot contractions over VMEM tiles: at the rank metrics' 4 096 bins
    the two-level body (ops/pallas_rank_hist.py), which takes the weights
    as three bfloat16 parts, or as one where `unit_payload` vouches that
    w_lanes x labels holds zeros and ones only. CPU/fallback: vmap of the
    scatter path. Identical results.
    """
    if _pallas_route():
        return _binned_cum_counts_lanes_pallas(scores, labels, w_lanes,
                                               n_bins,
                                               unit_payload=unit_payload)
    return _binned_cum_counts_lanes_jnp(scores, labels, w_lanes, n_bins)


def _binned_cum_counts_lanes_jnp(scores, labels, w_lanes, n_bins):
    """vmap of the scatter path — the CPU route and the pallas route's
    reference."""
    return jax.vmap(
        lambda s, wl: _binned_cum_counts(s, labels, wl, n_bins)
    )(scores, w_lanes)


def _binned_cum_counts_lanes_pallas(scores, labels, w_lanes, n_bins,
                                    interpret: bool = False,
                                    unit_payload: bool = False):
    from . import pallas_hist
    L, n = scores.shape
    idx = _bin_idx(scores, n_bins)
    pos_w = w_lanes * labels[None, :]
    neg_w = w_lanes * (1.0 - labels[None, :])
    lane = jnp.broadcast_to(
        jnp.arange(L, dtype=jnp.float32)[:, None], (L, n))
    total = L * n
    flat = lambda a: a.reshape(1, total)
    pay = jnp.concatenate([flat(pos_w), flat(neg_w)], axis=0)
    # ragged totals pad inside the kernel call (dropped-slot rows)
    hist = pallas_hist.hist_pallas(flat(idx), pay, flat(lane), n_slots=L,
                                   n_bins=n_bins, interpret=interpret,
                                   unit_payload=unit_payload)  # [L*2, bins]
    hist = hist.reshape(L, 2, n_bins)
    tps = jnp.cumsum(hist[:, 0, ::-1], axis=1)
    fps = jnp.cumsum(hist[:, 1, ::-1], axis=1)
    return tps, fps


def heldout_cum_counts_lanes(scores: jax.Array, labels: jax.Array,
                             w: jax.Array, fold_of: jax.Array,
                             n_folds: int, n_bins: int, *,
                             unit_payload: bool = False
                             ) -> Tuple[jax.Array, jax.Array]:
    """Weighted TP/FP cumulative counts [n_folds, Gc, n_bins] of a k-fold
    sweep in ONE pass over the rows: a row is held out by at most one fold,
    so it is binned once, into the histograms of that fold alone.

    scores [Gc, n]: each row's margins under ITS OWN held-out fold's Gc
    grid points; labels [n]; w [n] held-out weights; fold_of [n] int32,
    the fold that holds the row out (n_folds: none does, the row is
    dropped). n x Gc elements are binned, where binned_cum_counts_lanes
    over (fold x grid) lanes of whole-matrix weights bins n_folds times as
    many, all but one of every n_folds with weight zero. Same bins
    (_bin_idx), weights and products as that route, same dispatch: on the
    TPU ONE pallas histogram call in the tree histograms' own form — the
    Gc grid points are its features, the fold is its slot, so the weights
    and the fold are read once a ROW (ops/pallas_hist.py; at 4 096 bins
    its two-level body, ops/pallas_rank_hist.py, where the slot-and-class
    rows are built once a row block for all Gc). `unit_payload`: the
    caller vouches that w x labels holds zeros and ones only, and the
    kernel takes one bfloat16 part of it instead of three."""
    if _pallas_route():
        return _heldout_cum_counts_lanes_pallas(
            scores, labels, w, fold_of, n_folds, n_bins,
            unit_payload=unit_payload)
    return _heldout_cum_counts_lanes_jnp(scores, labels, w, fold_of,
                                         n_folds, n_bins)


def _cum_from_high(hist: jax.Array) -> jax.Array:
    return jnp.cumsum(hist[..., ::-1], axis=-1)


def _heldout_cum_counts_lanes_jnp(scores, labels, w, fold_of, n_folds,
                                  n_bins):
    """One scatter-add a class over (fold, grid point, bin) cells — the
    CPU route and the pallas route's reference."""
    Gc = scores.shape[0]
    idx = _bin_idx(scores, n_bins)
    lane = jnp.arange(Gc)[:, None]

    def hist(wv):   # fold n_folds is the spill plane, cut away
        return jnp.zeros((n_folds + 1, Gc, n_bins), jnp.float32) \
            .at[fold_of[None, :], lane, idx] \
            .add(jnp.broadcast_to(wv[None, :], idx.shape))[:n_folds]
    return (_cum_from_high(hist(w * labels)),
            _cum_from_high(hist(w * (1.0 - labels))))


def _heldout_cum_counts_lanes_pallas(scores, labels, w, fold_of, n_folds,
                                     n_bins, interpret: bool = False,
                                     unit_payload: bool = False):
    from . import pallas_hist
    Gc = scores.shape[0]
    hist = pallas_hist.hist_pallas(
        _bin_idx(scores, n_bins), jnp.stack([w * labels, w * (1.0 - labels)]),
        fold_of.astype(jnp.float32)[None, :], n_slots=n_folds,
        n_bins=n_bins, interpret=interpret,
        unit_payload=unit_payload)               # [n_folds * 2, Gc * bins]
    hist = hist.reshape(n_folds, 2, Gc, n_bins)
    return _cum_from_high(hist[:, 0]), _cum_from_high(hist[:, 1])


def _au_pr_from_counts(tps: jax.Array, fps: jax.Array) -> jax.Array:
    """Average precision from cumulative counts; bins on the LAST axis
    (shared by the scalar and lane-batched routes)."""
    P = jnp.maximum(tps[..., -1:], EPS)
    recall = tps / P
    precision = tps / jnp.maximum(tps + fps, EPS)
    dr = jnp.diff(recall, axis=-1, prepend=0.0)
    return (dr * precision).sum(axis=-1)


def _au_roc_from_counts(tps: jax.Array, fps: jax.Array) -> jax.Array:
    """Trapezoid AuROC from cumulative counts; bins on the LAST axis."""
    P = jnp.maximum(tps[..., -1:], EPS)
    N = jnp.maximum(fps[..., -1:], EPS)
    tpr = tps / P
    fpr = fps / N
    dfpr = jnp.diff(fpr, axis=-1, prepend=0.0)
    tpr_prev = jnp.concatenate(
        [jnp.zeros(tpr.shape[:-1] + (1,), tpr.dtype), tpr[..., :-1]],
        axis=-1)
    return (dfpr * (tpr + tpr_prev) * 0.5).sum(axis=-1)


def au_pr_binned_lanes(scores: jax.Array, labels: jax.Array,
                       w_lanes: jax.Array, n_bins: int, *,
                       unit_payload: bool = False) -> jax.Array:
    """[L] average-precision values from per-lane binned counts (same
    approximation contract as au_pr_binned)."""
    return _au_pr_from_counts(*binned_cum_counts_lanes(
        scores, labels, w_lanes, n_bins, unit_payload=unit_payload))


def au_roc_binned_lanes(scores: jax.Array, labels: jax.Array,
                        w_lanes: jax.Array, n_bins: int, *,
                        unit_payload: bool = False) -> jax.Array:
    """[L] AuROC values from per-lane binned counts."""
    return _au_roc_from_counts(*binned_cum_counts_lanes(
        scores, labels, w_lanes, n_bins, unit_payload=unit_payload))


def au_pr_heldout_lanes(scores: jax.Array, labels: jax.Array, w: jax.Array,
                        fold_of: jax.Array, n_folds: int, n_bins: int, *,
                        unit_payload: bool = False) -> jax.Array:
    """[n_folds, Gc] average-precision values, every row binned once
    (heldout_cum_counts_lanes; au_pr_binned's approximation contract)."""
    return _au_pr_from_counts(*heldout_cum_counts_lanes(
        scores, labels, w, fold_of, n_folds, n_bins,
        unit_payload=unit_payload))


def au_roc_heldout_lanes(scores: jax.Array, labels: jax.Array,
                         w: jax.Array, fold_of: jax.Array, n_folds: int,
                         n_bins: int, *,
                         unit_payload: bool = False) -> jax.Array:
    """[n_folds, Gc] AuROC values, every row binned once."""
    return _au_roc_from_counts(*heldout_cum_counts_lanes(
        scores, labels, w, fold_of, n_folds, n_bins,
        unit_payload=unit_payload))


#: (tps, fps) cumulative counts, bins last -> the rank metric: what the
#: *_heldout_lanes and *_binned_lanes functions end in, for a caller that
#: sums the counts of several row sets first (a mesh: every chip bins its
#: own rows, validators._eval_heldout_core)
RANK_METRIC_FROM_COUNTS = {"au_pr": _au_pr_from_counts,
                           "au_roc": _au_roc_from_counts}


def au_pr_binned(scores: jax.Array, labels: jax.Array,
                 w: Optional[jax.Array] = None,
                 n_bins: int = 4096) -> jax.Array:
    """Histogram-approximate AuPR (average precision over bin boundaries).

    O(n) scatter-add instead of an O(n log n) device sort — the in-sweep
    ranking metric for very large n (the model selector's final winner is
    still scored with the exact `au_pr`). Approximation error is the score
    mass sharing a 1/n_bins-wide bucket: ~1e-4 at the default 4096 bins for
    smooth score distributions (the reference's threshold curves likewise
    bin at numBins=100, OpBinaryClassificationEvaluator.scala:68)."""
    tps, fps = _binned_cum_counts(scores, labels, w, n_bins)
    return _au_pr_from_counts(tps, fps)


def au_roc_binned(scores: jax.Array, labels: jax.Array,
                  w: Optional[jax.Array] = None,
                  n_bins: int = 4096) -> jax.Array:
    """Histogram-approximate AuROC (trapezoid over bin boundaries); see
    au_pr_binned for the approximation contract."""
    tps, fps = _binned_cum_counts(scores, labels, w, n_bins)
    return _au_roc_from_counts(tps, fps)


class BinaryMetrics(NamedTuple):
    au_roc: jax.Array
    au_pr: jax.Array
    precision: jax.Array
    recall: jax.Array
    f1: jax.Array
    error: jax.Array
    tp: jax.Array
    tn: jax.Array
    fp: jax.Array
    fn: jax.Array


@jax.jit
def binary_metrics(scores: jax.Array, labels: jax.Array,
                   w: Optional[jax.Array] = None,
                   threshold: float = 0.5) -> BinaryMetrics:
    scores = jnp.asarray(scores)
    labels = jnp.asarray(labels)
    if w is None:
        w = jnp.ones_like(scores)
    pred = (scores >= threshold).astype(scores.dtype)
    tp = (w * pred * labels).sum()
    fp = (w * pred * (1 - labels)).sum()
    tn = (w * (1 - pred) * (1 - labels)).sum()
    fn = (w * (1 - pred) * labels).sum()
    precision = tp / jnp.maximum(tp + fp, EPS)
    recall = tp / jnp.maximum(tp + fn, EPS)
    f1 = 2 * precision * recall / jnp.maximum(precision + recall, EPS)
    error = (fp + fn) / jnp.maximum(tp + tn + fp + fn, EPS)
    return BinaryMetrics(
        au_roc=au_roc(scores, labels, w), au_pr=au_pr(scores, labels, w),
        precision=precision, recall=recall, f1=f1, error=error,
        tp=tp, tn=tn, fp=fp, fn=fn)


@partial(jax.jit, static_argnames=("num_bins",))
def threshold_curves(scores: jax.Array, labels: jax.Array,
                     w: Optional[jax.Array] = None,
                     num_bins: int = 100) -> Dict[str, jax.Array]:
    """Precision/recall/F1 at evenly spaced thresholds (numBins=100,
    reference OpBinaryClassificationEvaluator threshold metrics)."""
    scores = jnp.asarray(scores)
    labels = jnp.asarray(labels)
    if w is None:
        w = jnp.ones_like(scores)
    thresholds = jnp.linspace(0.0, 1.0, num_bins)

    def at(th):
        pred = (scores >= th).astype(scores.dtype)
        tp = (w * pred * labels).sum()
        fp = (w * pred * (1 - labels)).sum()
        fn = (w * (1 - pred) * labels).sum()
        prec = tp / jnp.maximum(tp + fp, EPS)
        rec = tp / jnp.maximum(tp + fn, EPS)
        f1 = 2 * prec * rec / jnp.maximum(prec + rec, EPS)
        return prec, rec, f1

    prec, rec, f1 = jax.vmap(at)(thresholds)
    return {"thresholds": thresholds, "precision": prec, "recall": rec, "f1": f1}


class MultiMetrics(NamedTuple):
    precision: jax.Array  # weighted
    recall: jax.Array
    f1: jax.Array
    error: jax.Array


def multiclass_metrics_from_confusion(conf: jax.Array) -> MultiMetrics:
    """Weighted precision/recall/F1/error from one weighted confusion
    count conf[true, pred]."""
    tp = jnp.diag(conf)
    per_pred = conf.sum(axis=0)
    per_true = conf.sum(axis=1)
    prec_c = tp / jnp.maximum(per_pred, EPS)
    rec_c = tp / jnp.maximum(per_true, EPS)
    f1_c = 2 * prec_c * rec_c / jnp.maximum(prec_c + rec_c, EPS)
    weights = per_true / jnp.maximum(per_true.sum(), EPS)
    precision = (prec_c * weights).sum()
    recall = (rec_c * weights).sum()
    f1 = (f1_c * weights).sum()
    error = 1.0 - tp.sum() / jnp.maximum(conf.sum(), EPS)
    return MultiMetrics(precision=precision, recall=recall, f1=f1, error=error)


@partial(jax.jit, static_argnames=("n_classes",))
def multiclass_metrics(pred: jax.Array, labels: jax.Array, n_classes: int,
                       w: Optional[jax.Array] = None) -> MultiMetrics:
    """Weighted precision/recall/F1/error from predicted & true class ids."""
    pred = jnp.asarray(pred)
    labels = jnp.asarray(labels)
    if w is None:
        w = jnp.ones(pred.shape, jnp.float32)
    P = jax.nn.one_hot(pred.astype(jnp.int32), n_classes, dtype=w.dtype)
    Y = jax.nn.one_hot(labels.astype(jnp.int32), n_classes, dtype=w.dtype) * w[:, None]
    # [true, pred], row-weighted once via Y
    return multiclass_metrics_from_confusion(Y.T @ P)


def confusion_lanes(pred: jax.Array, labels: jax.Array, w: jax.Array,
                    n_classes: int) -> jax.Array:
    """[G, K, K] weighted confusion counts conf[g, true, pred] of G lanes
    of predicted class ids pred [G, c] over the same rows (labels [c],
    weights [c]): the lane-batched count the streamed multiclass metric
    pass sums block by block. Equal to multiclass_metrics' confusion on
    the same predictions: the one-hots are exact in any precision, and
    `highest` keeps the weights' f32 mantissas on the MXU; counts of unit
    weights are exact integers up to 2**24 a cell. A label or prediction
    outside 0..K-1 counts nowhere (jax.nn.one_hot's rule)."""
    classes = jnp.arange(n_classes, dtype=jnp.int32)
    P = (pred.astype(jnp.int32)[:, None, :] == classes[None, :, None]) \
        .astype(jnp.float32)                                  # [G, K, c]
    Y = (labels.astype(jnp.int32)[None, :] == classes[:, None]) \
        .astype(jnp.float32) * w[None, :]                     # [K, c]
    return jnp.einsum("tc,gpc->gtp", Y, P,
                      precision=jax.lax.Precision.HIGHEST)


class ThresholdMetrics(NamedTuple):
    """Top-N per-threshold correctness counts (reference
    OpMultiClassificationEvaluator.scala:295 ThresholdMetrics). For each
    (top-N, threshold) cell over n rows:
    correct   — true-class score in the top N AND >= threshold;
    incorrect — top predicted score >= threshold AND (true class not in
                top N OR its score < threshold);
    no_prediction — top predicted score < threshold.
    The three [len(top_ns), T] count arrays sum to n in every cell."""

    top_ns: Tuple[int, ...]
    thresholds: jax.Array            # [T]
    correct_counts: jax.Array        # [len(top_ns), T] int32
    incorrect_counts: jax.Array      # [len(top_ns), T] int32
    no_prediction_counts: jax.Array  # [len(top_ns), T] int32

    def to_json(self) -> Dict[str, object]:
        import numpy as _np
        return {
            "top_ns": list(self.top_ns),
            "thresholds": _np.asarray(self.thresholds).tolist(),
            "correct_counts": {
                str(t): _np.asarray(self.correct_counts[i]).tolist()
                for i, t in enumerate(self.top_ns)},
            "incorrect_counts": {
                str(t): _np.asarray(self.incorrect_counts[i]).tolist()
                for i, t in enumerate(self.top_ns)},
            "no_prediction_counts": {
                str(t): _np.asarray(self.no_prediction_counts[i]).tolist()
                for i, t in enumerate(self.top_ns)},
        }


@partial(jax.jit, static_argnames=("top_ns",))
def _threshold_metrics_kernel(probs: jax.Array, labels: jax.Array,
                              thresholds: jax.Array, top_ns: Tuple[int, ...]
                              ) -> Tuple[jax.Array, jax.Array]:
    """One pass over [n, C] probabilities — no sort, no gather.

    Reference computeMetrics (OpMultiClassificationEvaluator.scala:188)
    sorts each row's scores; here the true class's rank comes from two
    fused comparisons (scores strictly greater + equal-score ties at lower
    index, matching the stable descending sort), the true-class score from
    a one-hot contraction, and each per-threshold fill range from an
    indexWhere-equivalent first-True argmax. Everything lowers to
    elementwise compares + reductions on the MXU/VPU."""
    n, C = probs.shape
    T = thresholds.shape[0]
    lbl = labels.astype(jnp.int32)
    valid = (lbl >= 0) & (lbl < C)          # scores.lift(label) semantics
    onehot = jax.nn.one_hot(jnp.where(valid, lbl, 0), C, dtype=probs.dtype)
    s_true = jnp.where(valid, (probs * onehot).sum(1), 0.0)
    s_top = probs.max(1)
    # rank of the true class under a STABLE descending sort (scala sortBy):
    # strictly-greater scores, plus equal scores at a lower class index
    idx = jnp.arange(C)[None, :]
    gt = (probs > s_true[:, None]).sum(1)
    ties_before = ((probs == s_true[:, None])
                   & (idx < lbl[:, None])).sum(1)
    rank = gt + ties_before
    # indexWhere(_ > score): first threshold index exceeding the score,
    # T when none does (argmax of a boolean row finds the first True)
    def cutoff(score):
        over = thresholds[None, :] > score[:, None]      # [n, T]
        return jnp.where(over.any(1), jnp.argmax(over, 1), T)
    c_true = cutoff(s_true)[:, None]                     # [n, 1]
    c_top = cutoff(s_top)[:, None]
    k = jnp.arange(T)[None, :]                           # [1, T]
    before_true = k < c_true                             # arrayFill(0, cTrue)
    before_top = k < c_top
    correct_rows, incorrect_rows = [], []
    for t in top_ns:
        in_topn = (valid & (rank < t))[:, None]          # [n, 1]
        corr = in_topn & before_true
        incorr = jnp.where(in_topn, (~before_true) & before_top, before_top)
        correct_rows.append(corr.sum(0, dtype=jnp.int32))
        incorrect_rows.append(incorr.sum(0, dtype=jnp.int32))
    return jnp.stack(correct_rows), jnp.stack(incorrect_rows)


def multiclass_threshold_metrics(probs: jax.Array, labels: jax.Array,
                                 top_ns: Tuple[int, ...] = (1, 3),
                                 thresholds: Optional[jax.Array] = None
                                 ) -> ThresholdMetrics:
    """Top-N threshold metrics for multiclass probabilities (reference
    calculateThresholdMetrics, OpMultiClassificationEvaluator.scala:154;
    default thresholds 0.00..1.00 step 0.01 as in the reference)."""
    probs = jnp.asarray(probs)
    if thresholds is None:
        thresholds = jnp.arange(101, dtype=jnp.float32) / 100.0
    else:
        thresholds = jnp.asarray(thresholds, jnp.float32)
    top_ns = tuple(int(t) for t in top_ns)
    if not top_ns or any(t <= 0 for t in top_ns):
        raise ValueError("top_ns must be non-empty positive ints")
    correct, incorrect = _threshold_metrics_kernel(
        probs, jnp.asarray(labels), thresholds, top_ns)
    n = probs.shape[0]
    return ThresholdMetrics(
        top_ns=top_ns, thresholds=thresholds,
        correct_counts=correct, incorrect_counts=incorrect,
        no_prediction_counts=n - correct - incorrect)


class RegressionMetrics(NamedTuple):
    rmse: jax.Array
    mse: jax.Array
    mae: jax.Array
    r2: jax.Array


@jax.jit
def regression_metrics(pred: jax.Array, labels: jax.Array,
                       w: Optional[jax.Array] = None) -> RegressionMetrics:
    pred = jnp.asarray(pred)
    labels = jnp.asarray(labels)
    if w is None:
        w = jnp.ones_like(pred)
    tot = jnp.maximum(w.sum(), EPS)
    err = pred - labels
    mse = (w * err * err).sum() / tot
    mae = (w * jnp.abs(err)).sum() / tot
    ybar = (w * labels).sum() / tot
    ss_tot = (w * (labels - ybar) ** 2).sum()
    ss_res = (w * err * err).sum()
    r2 = 1.0 - ss_res / jnp.maximum(ss_tot, EPS)
    return RegressionMetrics(rmse=jnp.sqrt(mse), mse=mse, mae=mae, r2=r2)
