"""Host (C++) tree training — the small-N/deep-tree twin of ops/trees.py.

The XLA kernels are shaped for the device regime (N >> 2^depth: dense
per-level histograms -> MXU contractions). On the CPU backend at
Titanic-like scale with the reference's default grids (maxDepth up to 12)
the dense design pays for thousands of empty nodes; this module routes
those fits through native/trees.cpp — an occupancy-aware level-wise
builder, the same role libxgboost's C++ plays behind the reference's
OpXGBoost* (SURVEY 2.9) — and returns arrays in exactly the Tree layout
ops/trees.py produces, so freezing/serving/persistence are unchanged.

Binning here is a numpy twin of quantile_edges/bin_matrix (same strided
sample, same right-side searchsorted with the shifted missing bin 0), so a
native fit and an XLA fit grow from identical binned matrices.

Everything degrades gracefully: `available()` is False when the native
library cannot build, and callers keep the XLA path.
"""
from __future__ import annotations

import ctypes
import os
from typing import Optional, Tuple

import numpy as np

from . import trees as T

_lib: Optional[ctypes.CDLL] = None
_tried = False

_i32p = ctypes.POINTER(ctypes.c_int32)
_f32p = ctypes.POINTER(ctypes.c_float)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("TMOG_DISABLE_NATIVE") or \
            os.environ.get("TMOG_DISABLE_NATIVE_TREES"):
        return None
    try:
        from ..native.build import build
        path = build()
        if path is None:
            return None
        lib = ctypes.CDLL(path)
        lib.tmog_gbt_fit.restype = ctypes.c_int
        lib.tmog_gbt_softmax_fit.restype = ctypes.c_int
        lib.tmog_rf_fit.restype = ctypes.c_int
        lib.tmog_debug_group_sweeps.restype = ctypes.c_int64
        lib.tmog_predict_bins.restype = ctypes.c_int
        lib.tmog_predict_raw.restype = ctypes.c_int
    except (OSError, AttributeError):
        return None
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


# -- numpy binning twin ------------------------------------------------------

def quantile_edges_host(X: np.ndarray, n_bins: int) -> np.ndarray:
    """Numpy twin of ops/trees.quantile_edges: [d, n_bins-1] f32 edges over
    present values, strided sample above the same _QUANTILE_SAMPLE cap."""
    n = X.shape[0]
    if n > T._QUANTILE_SAMPLE:
        stride = -(-n // T._QUANTILE_SAMPLE)
        X = X[::stride]
    X = np.asarray(X, np.float32)
    # host-only quantile math: f64 keeps the edge interpolation exact and the
    # returned edges are cast to f32 below, so no f64 reaches the device
    # tmoglint: disable=TPU003  host precision, result cast to f32
    qs = np.arange(1, n_bins, dtype=np.float64) / n_bins
    with np.errstate(invalid="ignore"):
        # tmoglint: disable=TPU003  host precision, result cast to f32
        edges = np.nanquantile(X.astype(np.float64), qs, axis=0)
    return np.asarray(edges.T, np.float32)


def bin_matrix_host(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Numpy twin of ops/trees.bin_matrix: NaN -> 0, present -> 1 +
    right-side searchsorted. uint8 when the bins fit (<= 255 value bins —
    the Xb stream is the native builder's dominant memory traffic at big
    N, and trees.cpp reads 1-byte bins as uint8_t), int32 otherwise."""
    X = np.asarray(X, np.float32)
    n, d = X.shape
    n_bins = edges.shape[1] + 1
    dtype = np.uint8 if n_bins <= 255 else np.int32
    out = np.empty((n, d), dtype)
    for f in range(d):
        col = X[:, f]
        missing = np.isnan(col)
        b = np.searchsorted(edges[f], np.where(missing, -np.inf, col),
                            side="right") + 1
        out[:, f] = np.where(missing, 0, b)
    return out


def bin_context(X: np.ndarray, n_bins: int
                ) -> Tuple[np.ndarray, np.ndarray, int]:
    """(Xb uint8|int32, edges, n_bins) — host twin of _TreeEstimator._bin."""
    X = np.asarray(X, np.float32)
    edges = quantile_edges_host(X, n_bins)
    return bin_matrix_host(X, edges), edges, n_bins


# -- native drivers ----------------------------------------------------------

def _c(arr: np.ndarray, ptr):
    return arr.ctypes.data_as(ptr)


def _xb_native(Xb: np.ndarray):
    """(contiguous array, void pointer, itemsize) for the bin matrix —
    uint8/int8 pass through (itemsize 1), everything else widens to
    int32."""
    if Xb.dtype in (np.uint8, np.int8):
        Xb = np.ascontiguousarray(Xb)
        return Xb, Xb.ctypes.data_as(ctypes.c_void_p), 1
    Xb = np.ascontiguousarray(Xb, np.int32)
    return Xb, Xb.ctypes.data_as(ctypes.c_void_p), 4


def fit_gbt_host(Xb: np.ndarray, y: np.ndarray, w: np.ndarray, *,
                 n_rounds: int, depth: int, n_bins: int,
                 learning_rate: float = 0.1, reg_lambda: float = 1.0,
                 min_child_weight: float = 0.0, min_instances: float = 1.0,
                 min_info_gain: float = 0.0, gamma: float = 0.0,
                 subsample: float = 1.0, feature_frac: float = 1.0,
                 seed: int = 42, loss: str = "logistic",
                 normalize_gain: bool = False):
    """Native fit_gbt twin (`normalize_gain` as there: Spark's minInfoGain
    a weighted row). Returns (Tree-of-ndarrays [R, ...], base) or None
    when the library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    Xb, xb_ptr, itemsize = _xb_native(np.asarray(Xb))
    N, F = Xb.shape
    B = n_bins + 1
    M, L = (1 << depth) - 1, 1 << depth
    y32 = np.ascontiguousarray(y, np.float32)
    w32 = np.ascontiguousarray(w, np.float32)
    feat = np.zeros((n_rounds, M), np.int32)
    thresh = np.zeros((n_rounds, M), np.int32)
    miss = np.zeros((n_rounds, M), np.int32)
    leaf = np.zeros((n_rounds, L), np.float32)
    base = ctypes.c_float(0.0)
    rc = lib.tmog_gbt_fit(
        xb_ptr, ctypes.c_int64(N), ctypes.c_int32(F),
        ctypes.c_int32(B), ctypes.c_int32(itemsize),
        _c(y32, _f32p), _c(w32, _f32p),
        ctypes.c_int32(0 if loss == "logistic" else 1),
        ctypes.c_int32(n_rounds), ctypes.c_int32(depth),
        ctypes.c_double(learning_rate), ctypes.c_double(reg_lambda),
        ctypes.c_double(min_child_weight), ctypes.c_double(min_instances),
        ctypes.c_double(min_info_gain), ctypes.c_double(gamma),
        ctypes.c_double(subsample), ctypes.c_double(feature_frac),
        ctypes.c_uint64(seed & (2**64 - 1)),
        ctypes.c_int32(1 if normalize_gain else 0),
        _c(feat, _i32p), _c(thresh, _i32p), _c(miss, _i32p),
        _c(leaf, _f32p), ctypes.byref(base))
    if rc != 0:
        return None
    tree = T.Tree(feat=feat, thresh=thresh, leaf=leaf[:, :, None], miss=miss)
    return tree, float(base.value)


def fit_gbt_softmax_host(Xb: np.ndarray, y: np.ndarray, w: np.ndarray, *,
                         n_rounds: int, depth: int, n_bins: int,
                         n_classes: int, learning_rate: float = 0.1,
                         reg_lambda: float = 1.0,
                         min_child_weight: float = 0.0, gamma: float = 0.0,
                         subsample: float = 1.0, feature_frac: float = 1.0,
                         seed: int = 42):
    """Native fit_gbt_softmax twin: Tree arrays with leading
    [n_rounds, n_classes] axes, or None."""
    lib = _load()
    if lib is None:
        return None
    Xb, xb_ptr, itemsize = _xb_native(np.asarray(Xb))
    N, F = Xb.shape
    B = n_bins + 1
    M, L = (1 << depth) - 1, 1 << depth
    RC = n_rounds * n_classes
    y32 = np.ascontiguousarray(y, np.float32)
    w32 = np.ascontiguousarray(w, np.float32)
    feat = np.zeros((RC, M), np.int32)
    thresh = np.zeros((RC, M), np.int32)
    miss = np.zeros((RC, M), np.int32)
    leaf = np.zeros((RC, L), np.float32)
    rc = lib.tmog_gbt_softmax_fit(
        xb_ptr, ctypes.c_int64(N), ctypes.c_int32(F),
        ctypes.c_int32(B), ctypes.c_int32(itemsize),
        _c(y32, _f32p), _c(w32, _f32p),
        ctypes.c_int32(n_classes), ctypes.c_int32(n_rounds),
        ctypes.c_int32(depth), ctypes.c_double(learning_rate),
        ctypes.c_double(reg_lambda), ctypes.c_double(min_child_weight),
        ctypes.c_double(gamma), ctypes.c_double(subsample),
        ctypes.c_double(feature_frac), ctypes.c_uint64(seed & (2**64 - 1)),
        _c(feat, _i32p), _c(thresh, _i32p), _c(miss, _i32p),
        _c(leaf, _f32p))
    if rc != 0:
        return None
    shape = (n_rounds, n_classes)
    return T.Tree(feat=feat.reshape(shape + (M,)),
                  thresh=thresh.reshape(shape + (M,)),
                  leaf=leaf.reshape(shape + (L, 1)),
                  miss=miss.reshape(shape + (M,)))


def fit_forest_host(Xb: np.ndarray, G: np.ndarray, H: np.ndarray, *,
                    n_trees: int, depth: int, n_bins: int,
                    subsample: float = 1.0, feature_frac: float = 1.0,
                    reg_lambda: float = 0.0, min_instances: float = 1.0,
                    min_info_gain: float = 0.0, bootstrap: bool = True,
                    seed: int = 42):
    """Native fit_forest twin (mean leaves): stacked Tree or None."""
    lib = _load()
    if lib is None:
        return None
    Xb, xb_ptr, itemsize = _xb_native(np.asarray(Xb))
    N, F = Xb.shape
    B = n_bins + 1
    G = np.ascontiguousarray(G, np.float32)
    K = G.shape[1]
    H32 = np.ascontiguousarray(H, np.float32)
    M, L = (1 << depth) - 1, 1 << depth
    feat = np.zeros((n_trees, M), np.int32)
    thresh = np.zeros((n_trees, M), np.int32)
    miss = np.zeros((n_trees, M), np.int32)
    leaf = np.zeros((n_trees, L, K), np.float32)
    rc = lib.tmog_rf_fit(
        xb_ptr, ctypes.c_int64(N), ctypes.c_int32(F),
        ctypes.c_int32(B), ctypes.c_int32(itemsize),
        _c(G, _f32p), _c(H32, _f32p), ctypes.c_int32(K),
        ctypes.c_int32(n_trees), ctypes.c_int32(depth),
        ctypes.c_double(reg_lambda), ctypes.c_double(min_instances),
        ctypes.c_double(min_info_gain), ctypes.c_double(subsample),
        ctypes.c_double(feature_frac), ctypes.c_int32(1 if bootstrap else 0),
        ctypes.c_uint64(seed & (2**64 - 1)),
        _c(feat, _i32p), _c(thresh, _i32p), _c(miss, _i32p),
        _c(leaf, _f32p))
    if rc != 0:
        return None
    return T.Tree(feat=feat, thresh=thresh, leaf=leaf, miss=miss)


def predict_bins_host(trees: T.Tree, Xb: np.ndarray, depth: int
                      ) -> np.ndarray:
    """Sum of tree payloads on binned rows (mirrors predict_forest_bins).
    trees may carry any leading batch axes. Native row-major traversal
    when the library is loaded (each row's bins stay in cache across the
    ensemble); numpy gather fallback otherwise."""
    feat = np.ascontiguousarray(np.asarray(trees.feat), np.int32)
    thresh = np.ascontiguousarray(np.asarray(trees.thresh), np.int32)
    miss = np.ascontiguousarray(np.asarray(trees.miss), np.int32)
    leaf = np.ascontiguousarray(np.asarray(trees.leaf), np.float32)
    M = feat.shape[-1]
    K = leaf.shape[-1]
    feat = feat.reshape(-1, M)
    thresh = thresh.reshape(-1, M)
    miss = miss.reshape(-1, M)
    leaf = leaf.reshape(-1, leaf.shape[-2], K)
    N = Xb.shape[0]
    out = np.zeros((N, K), np.float32)

    lib = _load()
    if lib is not None:
        Xbc, xb_ptr, itemsize = _xb_native(np.asarray(Xb))
        rc = lib.tmog_predict_bins(
            xb_ptr, ctypes.c_int64(N), ctypes.c_int32(Xbc.shape[1]),
            ctypes.c_int32(itemsize), _c(feat, _i32p), _c(thresh, _i32p),
            _c(miss, _i32p), _c(leaf, _f32p),
            ctypes.c_int32(feat.shape[0]), ctypes.c_int32(depth),
            ctypes.c_int32(K), _c(out, _f32p))
        if rc == 0:
            return out

    rows = np.arange(N)
    for t in range(feat.shape[0]):
        rel = np.zeros(N, np.int64)
        for d in range(depth):
            gi = (1 << d) - 1 + rel
            f = feat[t, gi]
            b = Xb[rows, f]
            right = (b > thresh[t, gi]) | ((b == 0) & (miss[t, gi] > 0))
            rel = 2 * rel + right
        out += leaf[t, rel]
    return out


def predict_raw_native(feat: np.ndarray, thresh_val: np.ndarray,
                       leaf: np.ndarray, X: np.ndarray, depth: int,
                       miss: np.ndarray) -> Optional[np.ndarray]:
    """Native raw-value ensemble traversal (serving twin of
    ops/trees.np_predict_ensemble); None when the library is absent."""
    lib = _load()
    if lib is None:
        return None
    X = np.ascontiguousarray(X, np.float32)
    feat = np.ascontiguousarray(feat, np.int32)
    tv = np.ascontiguousarray(thresh_val, np.float32)
    miss = np.ascontiguousarray(miss, np.int32)
    leaf = np.ascontiguousarray(leaf, np.float32)
    N, F = X.shape
    T_, K = feat.shape[0], leaf.shape[-1]
    out = np.zeros((N, K), np.float32)
    rc = lib.tmog_predict_raw(
        _c(X, _f32p), ctypes.c_int64(N), ctypes.c_int32(F),
        _c(feat, _i32p), _c(tv, _f32p), _c(miss, _i32p), _c(leaf, _f32p),
        ctypes.c_int32(T_), ctypes.c_int32(depth), ctypes.c_int32(K),
        _c(out, _f32p))
    return out if rc == 0 else None
