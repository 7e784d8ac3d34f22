"""Histogram decision-tree kernels as XLA programs.

TPU-native replacement for the reference's only native-compute path —
XGBoost4J's C++ libxgboost (reference shim
core/src/main/scala/ml/dmlc/xgboost4j/scala/spark/XGBoostParams.scala) and
Spark MLlib's tree learners behind OpRandomForest*/OpGBT*/OpDecisionTree*
(core/.../impl/classification/, core/.../impl/regression/).

Design (TPU-first, not a port):
- Features are quantile-binned once to int8 (uint8 up to 255 bins, int32
  past that; `quantile_edges` / `bin_matrix`);
  all growth happens on the binned matrix, which is the XGBoost `hist`
  algorithm shape and keeps every per-level pass a dense, static-shape
  gather/segment-sum that XLA tiles well.
- Trees are complete binary trees of static depth in heap layout: internal
  node arrays `feat`/`thresh`/`miss` of length 2^depth - 1, leaf payloads
  [2^depth, K]. Bins are shifted: 0 is the dedicated missing bin, present
  values occupy [1, n_bins] (int8 holds up to 127 quantile bins, uint8 up
  to 255 — the XGBoost 256-bin default at 1 byte/cell), and
  every node learns the default direction for missing rows (`miss`,
  XGBoost's sparsity-aware split). A node that fails its split test is
  encoded as (feat=0, thresh=n_bins, miss=0): `bin > thresh` is then
  never true, so all rows fall left — traversal stays branchless and
  data-independent (no dynamic shapes under jit, reference-free control
  flow for lax.scan).
- Multi-output payloads unify every leaf statistic the reference needs:
  K=1 Newton leaves (-G/(H+lambda)) give XGBoost/GBT boosting steps;
  K=n_classes mean leaves (G/H with G=onehot·w, H=w) give RF/DT class
  distributions whose variance-reduction gain IS the Gini gain; K=1 mean
  leaves give regression-tree variance reduction (Spark `impurity`).
- Per-level gradient histograms are one reduction over (node, feature,
  bin) cells with three lowerings: a fused `segment_sum` on CPU/GPU, a
  chunked one-hot MXU contraction on TPU, and a pallas kernel (VMEM
  one-hot tiles) above _PALLAS_MIN_ROWS. Levels past the root compute
  left children only and derive siblings by subtraction. Under pjit row
  sharding the partial histograms all-reduce over ICI exactly where
  XGBoost used Rabit allreduce.
- TPU serializes data-dependent gathers, so routing, traversal, leaf
  lookup and digitize all lower as one-hot contractions / fused compares
  there (CPU keeps the gather forms; results agree up to f32 rounding).
- Row parallelism = whole-array ops over N; tree/round loops are lax.scan;
  the class axis of softmax boosting is vmapped.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-12


class Tree(NamedTuple):
    """One complete binary tree (heap layout). Leading axes may batch trees.

    Missing values occupy the dedicated bin 0 (bin_matrix); present values
    bin to [1, n_bins]. Each node carries a learned default direction for
    missing rows (XGBoost's sparsity-aware split: both directions are
    scored during growth and the better one recorded)."""
    feat: jax.Array    # int32 [..., 2^depth - 1] split feature id
    thresh: jax.Array  # int32 [..., 2^depth - 1] go right iff bin > thresh
    leaf: jax.Array    # f32   [..., 2^depth, K] leaf payload
    miss: jax.Array    # int32 [..., 2^depth - 1] 1 = missing goes right


# -- binning ----------------------------------------------------------------

_QUANTILE_SAMPLE = 131_072


def quantile_edges(X: jax.Array, n_bins: int) -> jax.Array:
    """Per-feature quantile bin edges over PRESENT values.

    X: [n, d] -> edges [d, n_bins - 1], ascending per feature. Constant
    features produce repeated edges (empty bins; zero split gain — harmless).
    Rows are strided-sampled above _QUANTILE_SAMPLE — the XGBoost `hist`
    approximation — so the sort stays cheap at 10M+ rows. NaN rows are
    excluded from the sketch (nanquantile), matching XGBoost: missing
    values get the dedicated bin 0 in bin_matrix, not a quantile slot; an
    all-NaN feature yields NaN edges, which bin every present value to 1
    and can never win a split.
    """
    n = X.shape[0]
    if n > _QUANTILE_SAMPLE:
        stride = -(-n // _QUANTILE_SAMPLE)  # ceil
        X = X[::stride]
    # cast only the (<=131K-row) sample to f32 — a bf16 sweep matrix must
    # not be copied whole
    X = jnp.asarray(X, jnp.float32)
    qs = jnp.arange(1, n_bins, dtype=jnp.float32) / n_bins
    edges = jnp.nanquantile(X, qs, axis=0)       # [n_bins-1, d]
    return jnp.asarray(edges.T, jnp.float32)     # [d, n_bins-1]


# Rows per chunk of the binning map — bounds the f32 canonicalized copy and
# the [chunk, d, B-1] digitize-compare broadcast to O(chunk * d * B) instead
# of O(n * d * B) (the 10M-row bench OOM'd binning: four live [10M, 64]
# copies).
_BIN_CHUNK = 1 << 18


def bin_dtype(n_bins: int):
    """Narrowest integer dtype holding shifted bins [0, n_bins] (bin 0 =
    missing, so the max stored value is n_bins itself): int8 up to 127
    quantile bins, uint8 up to 255 — the XGBoost 256-bin default stays at
    1 byte/cell, 4x less Xb traffic than the old int32 fall-through —
    and int32 beyond. Shared by the resident, streamed and host binning
    paths so the three can never disagree on width."""
    if n_bins <= 127:
        return jnp.int8
    return jnp.uint8 if n_bins <= 255 else jnp.int32


def _bin_block(xb, edges):
    """Digitize ONE row block against `edges` — THE binning rule, shared
    by the resident `bin_matrix` map and the streamed tile emission
    (`stream_bin_matrix`), so the two paths cannot drift.

    TPU: digitize by counting edges <= x (identical to right-side
    searchsorted) — a fused broadcast-compare+reduce instead of the
    binary-search gathers searchsorted lowers to (TPU serializes
    data-dependent gathers); CPU keeps the O(log B) search. The backend
    branch resolves at trace time."""
    n_bins = edges.shape[1] + 1
    out_dtype = bin_dtype(n_bins)
    xf = jnp.asarray(xb, jnp.float32)
    missing = jnp.isnan(xf)
    if jax.default_backend() == "tpu":
        # NaN >= edge is False, so the count is 0 for missing rows
        # before the shift; the where picks bin 0 for them explicitly
        bins = (xf[:, :, None] >= edges[None, :, :]).sum(axis=2) + 1
    else:
        xs = jnp.where(missing, -jnp.inf, xf)
        bins = jax.vmap(
            lambda col, e: jnp.searchsorted(e, col, side="right"),
            in_axes=(1, 0), out_axes=1)(xs, edges) + 1
    return jnp.where(missing, 0, bins).astype(out_dtype)


def bin_matrix(X: jax.Array, edges: jax.Array) -> jax.Array:
    """Digitize with a dedicated missing bin: NaN -> 0, present values ->
    1 + #edges below-or-equal (searchsorted right, shifted).

    X [n, d], edges [d, n_bins-1] -> int8 / uint8 / int32 (bin_dtype)
    [n, d] in [0, n_bins]. For present values `bin > t` is equivalent to
    `x >= edges[t-1]` for t in [1, n_bins-1] (right-side search counts
    edges <= x, so equality on an edge goes right) — the raw serving
    traversal compares with >=, which matters for discrete columns
    (one-hot indicators sit exactly on their edge). Missing rows route by
    each node's learned default direction (Tree.miss), never by the
    comparison. Row blocks are processed by a lax.map so the f32
    temporaries never exceed O(_BIN_CHUNK * d); 1-byte output (int8 up
    to 127 bins, uint8 to 255) keeps the resident binned matrix at n*d
    bytes (640MB at the 10M config) through the XGBoost 256-bin default.
    """
    N, d = X.shape

    def one_block(xb):
        return _bin_block(xb, edges)
    chunk = min(_BIN_CHUNK, N)
    nchunks = -(-N // chunk)
    pad = nchunks * chunk - N
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
    out = jax.lax.map(one_block, X.reshape(nchunks, chunk, d))
    return out.reshape(nchunks * chunk, d)[:N]


def thresholds_to_values(feat: jax.Array, thresh: jax.Array,
                         edges: jax.Array) -> jax.Array:
    """Map bin thresholds to raw-value thresholds for serving on unbinned X.

    The raw rule for PRESENT values is `x >= value` (matching `bin > t`
    under shifted right-side binning: bin = 1 + #edges <= x, so bin > t
    iff x >= edges[t-1] for t in [1, n_bins-1]). t == 0 sends every
    present value right (-inf); dead nodes (thresh == n_bins, all-left)
    become +inf. Missing rows ignore the value and follow Tree.miss.
    """
    n_bins = edges.shape[1] + 1
    ti = jnp.clip(thresh - 1, 0, n_bins - 2)
    tv = edges[feat, ti]
    tv = jnp.where(thresh <= 0, -jnp.inf, tv)
    return jnp.where(thresh >= n_bins, jnp.inf, tv)


# -- streamed binning (tileplane) --------------------------------------------

def _x_source_with_dummies(source):
    """Wrap an x-only RowSource into the (x, y, w) chunk shape the stats
    engine's streamed driver expects (zero labels, unit weights)."""
    from ..parallel.tileplane import IterSource

    def factory():
        for chunk in source.chunks():
            x = np.asarray(chunk[0], np.float32)
            n = x.shape[0]
            yield (x, np.zeros(n, np.float32), np.ones(n, np.float32))

    return IterSource(factory, n_rows=source.n_rows)


def stream_quantile_edges(source, n_bins: int, *, hist_bins: int = 1024,
                          tile_rows: Optional[int] = None,
                          prefetch: Optional[int] = None) -> np.ndarray:
    """Per-feature quantile bin edges from a STREAMED source — the
    larger-than-HBM replacement for `quantile_edges`.

    Two statistics-engine passes over the source (both double-buffered
    via the tileplane): one for per-column min/max, one for fixed-range
    `hist_bins` histograms between them; the edges are then the inverse
    CDF of each column's histogram (linear interpolation inside the
    crossing bin — the XGBoost-hist sketch with uniform bins instead of
    a merged quantile sketch). Edge error is bounded by one histogram
    bin width, so `hist_bins >> n_bins` (default 1024 vs <= 127 tree
    bins) keeps streamed splits within a sliver of the resident sketch.
    NaN rows are excluded exactly like the resident path; an all-NaN
    column yields NaN edges (bins every present value to 1, never wins
    a split); a constant column yields repeated edges. Returns
    [d, n_bins - 1] float32."""
    from . import stats_engine as SE

    wrapped = _x_source_with_dummies(source)
    st, _ = SE.stream_stats(wrapped, tile_rows=tile_rows,
                            prefetch=prefetch)
    # host-only sketch finalize on [d]-vectors; device tiles stay f32
    f8 = np.float64  # tmoglint: disable=TPU003  host-only precision
    cnt = np.asarray(st.cnt, f8)
    lo = np.asarray(st.minv, f8)
    hi = np.asarray(st.maxv, f8)
    d = cnt.shape[0]
    ok = cnt > 0
    lo_r = np.where(ok, lo, 0.0).astype(np.float32)
    hi_r = np.where(ok, hi, 1.0).astype(np.float32)
    st2, _ = SE.stream_stats(_x_source_with_dummies(source),
                             tile_rows=tile_rows, lo=lo_r, hi=hi_r,
                             bins=int(hist_bins), prefetch=prefetch)
    hist = np.asarray(st2.hist, f8).reshape(d, hist_bins + 1)[:, :hist_bins]

    edges = np.full((d, n_bins - 1), np.nan, np.float32)
    qs = np.arange(1, n_bins, dtype=f8) / n_bins
    for j in range(d):
        total = hist[j].sum()
        if not ok[j] or total <= 0:
            continue  # all-NaN column: NaN edges, like nanquantile
        if hi[j] <= lo[j]:
            edges[j] = lo[j]  # constant feature: repeated edges
            continue
        bounds = lo[j] + (hi[j] - lo[j]) \
            * np.arange(1, hist_bins + 1, dtype=f8) / hist_bins
        cum = np.cumsum(hist[j])
        edges[j] = np.interp(qs * total,
                             np.concatenate(([0.0], cum)),
                             np.concatenate(([lo[j]], bounds))
                             ).astype(np.float32)
    return edges


def stream_bin_matrix(source, edges, *, tile_rows: Optional[int] = None,
                      sink=None, prefetch: Optional[int] = None):
    """Second streamed pass: emit the binned matrix tile-by-tile.

    Each fixed-shape tile runs the SAME `_bin_block` rule as the
    resident `bin_matrix` (exact parity by construction) under the
    double-buffered tileplane; the 1-byte (bin_dtype) output tiles are
    fetched with a one-tile lag (D2H of tile k overlaps tile k+1's
    compute) and handed to `sink(np_tile, n_valid)` — or, when `sink` is
    None, assembled into the full [n, d] host matrix, which at n*d bytes is
    the one artifact of the flow SMALL enough to keep (the 10M-row
    bench's binned matrix is 640MB vs 2.5GB of f32 X). TMOG_TILEPLANE=0
    degrades to run_tileplane's synchronous single-thread loop."""
    from ..parallel import tileplane as TP

    edges_j = jnp.asarray(edges, jnp.float32)
    d = int(edges_j.shape[0])
    c = int(tile_rows) if tile_rows else TP.tile_rows_for(4 * d,
                                                          source.n_rows)
    n_bins = int(np.asarray(edges).shape[1]) + 1
    out_dtype = np.dtype(bin_dtype(n_bins))
    parts: list = []
    full = None
    cursor = 0
    if sink is not None:
        out_sink = sink
    elif source.n_rows is not None:
        # known row count: write tiles straight into the final [n, d]
        # matrix — collecting tiles then concatenating would transiently
        # DOUBLE the peak host memory of the one artifact this flow keeps
        full = np.empty((int(source.n_rows), d), out_dtype)

        def out_sink(tile, n_valid):
            nonlocal cursor
            full[cursor:cursor + n_valid] = tile
            cursor += n_valid
    else:
        def out_sink(tile, n_valid):  # unknown length: concat at the end
            parts.append(tile)

    def step(carry, xt):
        return carry, _bin_tile_jit(xt, edges_j)

    # TMOG_TILEPLANE=0 degrades inside run_tileplane to the synchronous
    # single-thread loop — same tiles, same rule, no producer thread
    TP.run_tileplane(source, step, jnp.zeros((), jnp.int32),
                     tile_rows=c, label="tree_bin", sink=out_sink,
                     prefetch=prefetch)
    if sink is not None:
        return None
    if full is not None:
        return full[:cursor]
    return np.concatenate(parts, axis=0) if parts else \
        np.zeros((0, d), out_dtype)


@jax.jit
def _bin_tile_jit(x, edges):
    """One streamed tile's binned output (fixed shape: one executable
    for every tile of the pass)."""
    return _bin_block(x, edges)


# -- single-tree growth -----------------------------------------------------

def _soft_l1(G, alpha):
    """XGBoost's L1 soft-threshold on leaf gradient sums: shrink |G| by
    alpha, zero inside the dead zone (ThresholdL1 in xgboost's
    split_evaluator; OpXGBoostClassifier.setAlpha on the reference
    wrapper). alpha == 0 is the identity."""
    if isinstance(alpha, float) and alpha == 0.0:
        return G
    return jnp.sign(G) * jnp.maximum(jnp.abs(G) - alpha, 0.0)


def _split_scores(GL, HL, CL, Gt, Ht, Ct, Gm, Hm, Cm, reg_lambda,
                  min_child_weight, min_instances, min_info_gain, gamma,
                  alpha, normalize_gain):
    """Gain + validity for every (node, feature, bin, missing-direction)
    split candidate — XGBoost's sparsity-aware split search.

    GL/HL/CL: cumulative left sums [nodes, F, B(, K)] over the shifted bin
    axis, so slot 0 (the missing bin) is inside every prefix; Gt/Ht/Ct
    totals; Gm/Hm/Cm the per-(node, feature) missing-bin mass. Direction 0
    keeps missing in the left prefix (default-left); direction 1 moves the
    missing mass right (left' = GL - Gm). Gain is the multi-output
    sum-of-squares improvement sum_k GL_k^2/(HL+l) + GR_k^2/(HR+l) -
    Gt_k^2/(Ht+l); for mean-mode payloads (H = weight) this is total
    variance reduction, i.e. n x the Spark impurity gain —
    `normalize_gain` divides by Ht to compare against Spark's per-row
    minInfoGain; `gamma` is XGBoost's complexity penalty.

    Returns gain [nodes, F, B, 2] with -inf at invalid candidates.
    """
    def score(G, H):
        Ga = _soft_l1(G, alpha)
        return (Ga * Ga).sum(-1) / (H + reg_lambda + EPS)

    parent = score(Gt, Ht)[:, None, None]
    norm = jnp.maximum(Ht, 1.0)[:, None, None] if normalize_gain else 1.0

    def one_direction(GLd, HLd, CLd):
        GR = Gt[:, None, None, :] - GLd
        HR = Ht[:, None, None] - HLd
        CR = Ct[:, None, None] - CLd
        gain = score(GLd, HLd) + score(GR, HR) - parent
        ok = ((HLd >= min_child_weight) & (HR >= min_child_weight)
              & (CLd >= min_instances) & (CR >= min_instances)
              & (gain / norm > min_info_gain) & (gain > 2.0 * gamma))
        return jnp.where(ok, gain, -jnp.inf)

    g_left = one_direction(GL, HL, CL)
    g_right = one_direction(GL - Gm[:, :, None, :], HL - Hm[:, :, None],
                            CL - Cm[:, :, None])
    return jnp.stack([g_left, g_right], axis=-1)


def features_per_node(feature_frac: float, n_feat: int) -> int:
    """Columns a node's subset holds: Spark's count, the CEILING of the
    fraction of the columns (DecisionTreeMetadata: `auto` / `onethird` of
    64 columns are 22, sqrt of 64 is 8), at least one. The native builder
    (native/trees.cpp) takes the same ceiling. The 1e-9 keeps a fraction
    that float arithmetic left a hair over a whole count (sqrt(9) / 9 x 9)
    from gaining a column."""
    return min(max(1, math.ceil(feature_frac * n_feat - 1e-9)), n_feat)


def _feature_mask(key: jax.Array, n_nodes: int, n_feat: int,
                  feature_frac: float) -> jax.Array:
    """Per-node random feature subset mask [n_nodes, F] (RF column sampling,
    Spark featureSubsetStrategy applied per node)."""
    k = features_per_node(feature_frac, n_feat)
    if k >= n_feat:
        return jnp.ones((n_nodes, n_feat), bool)
    scores = jax.random.uniform(key, (n_nodes, n_feat))
    kth = jnp.sort(scores, axis=1)[:, k - 1:k]
    return scores <= kth


def _level_feature_mask(key: jax.Array, n_feat: int, frac: float,
                        within: Optional[jax.Array],
                        within_count: Optional[int] = None) -> jax.Array:
    """[F] bool level subset (XGBoost colsample_bylevel), sampled FROM the
    colsample_bytree subset when one is active — xgboost nests the two
    draws ('columns are subsampled from the set of columns chosen for the
    current tree'), so their intersection is never empty. `within` [F]
    bool (or None) restricts the draw; `within_count` is its static
    population (the bytree k), so the level keeps frac * bytree_k
    features. Excluded features score -inf; the k-th-largest threshold
    then only ever admits allowed features."""
    pool = within_count if within_count is not None else n_feat
    k = max(1, int(round(frac * pool)))
    if k >= n_feat and within is None:
        return jnp.ones((n_feat,), bool)
    scores = jax.random.uniform(key, (1, n_feat))
    if within is not None:
        scores = jnp.where(within[None, :], scores, -jnp.inf)
    kth = jnp.sort(scores, axis=1, descending=True)[:, k - 1:k]
    return (scores >= kth)[0] & jnp.isfinite(scores[0])


def _histograms_segment(Xb, G, H, count_unit, node, n_nodes: int, B: int):
    """One fused segment-sum over node*F*B ids (CPU/GPU path; under row
    sharding the partial sums all-reduce — the Rabit-allreduce slot)."""
    N, F = Xb.shape
    K = G.shape[1]
    ids = (node[:, None] * (F * B)
           + jnp.arange(F, dtype=jnp.int32)[None, :] * B + Xb)  # [N, F]
    ids_f = ids.reshape(-1)
    seg = n_nodes * F * B
    hg = jax.ops.segment_sum(
        jnp.broadcast_to(G[:, None, :], (N, F, K)).reshape(-1, K),
        ids_f, num_segments=seg).reshape(n_nodes, F, B, K)
    hh = jax.ops.segment_sum(
        jnp.broadcast_to(H[:, None], (N, F)).reshape(-1),
        ids_f, num_segments=seg).reshape(n_nodes, F, B)
    hc = jax.ops.segment_sum(
        jnp.broadcast_to(count_unit[:, None], (N, F)).reshape(-1),
        ids_f, num_segments=seg).reshape(n_nodes, F, B)
    return hg, hh, hc


# Rows per chunk of the matmul-histogram scan. Bounds the on-device
# temporaries (combined one-hot [chunk, F*B] + Q [chunk, nodes*C]) that the
# unchunked design materialized at full N — the round-2 bench OOM at the
# 10M-row config with 5 fold lanes vmapped on top.
_HIST_CHUNK = 65_536

# Above this many rows the level histograms go through the pallas kernel
# (ops/pallas_hist.py): the one-hot tiles then live only in VMEM instead
# of costing ~1GB of HBM write+read per 64K-row chunk. MUST stay above
# models/trees._VMAP_FOLD_MAX_ROWS so a pallas_call never sits under the
# fold vmap (models/trees.py asserts the ordering at import).
#
# Accumulation-width limit: all histogram channels (G/H/count) accumulate
# in f32, whose integer ladder ends at 2^24 (~16.7M). Per-NODE unit-weight
# counts are exact below that; past ~16M rows in a single node the
# empty-leaf zeroing (Cl >= 0.5) and min_child_weight comparisons can
# drift by ulps. The BASELINE 10M-row config sits safely inside the
# window; scaling a single unsharded fit past ~16M rows/node requires
# splitting counts into two channels or a widened final reduce. (Under
# pjit row sharding each shard accumulates its local rows only, so the
# per-shard bound is rows/shard, and the psum is exact far longer.)
_PALLAS_MIN_ROWS = 4_000_000

def pallas_enabled() -> bool:
    """The single pallas switch lives in ops/pallas_hist (env default
    TMOG_NO_PALLAS); these are convenience delegates."""
    from . import pallas_hist
    return pallas_hist.enabled()


def set_pallas_enabled(enabled: bool) -> None:
    """Runtime pallas kill switch (e.g. the bench's retry after a Mosaic
    compile failure on untested hardware). Flipping it clears every
    registered pallas-consuming jit cache (tree fits here, the streamed
    metric evaluator in the validator) so already-compiled executables
    cannot pin the previous choice — the flag is read at trace time and
    is not part of the jit key."""
    from . import pallas_hist
    pallas_hist.set_enabled(enabled)


def _histograms_pallas(Xb, G, H, count_unit, node, n_nodes: int, B: int):
    """Level histograms via the VMEM-resident pallas kernel (transposed
    operands — see ops/pallas_hist.py for the layout rationale). The
    unit-count channel is derived IN VMEM from the hessian plane
    (count_unit = (H > 0) by construction in every caller), saving one
    full-N f32 HBM stream per level."""
    from . import pallas_hist
    N, F = Xb.shape
    K = G.shape[1]
    C = K + 2
    pay = jnp.concatenate([G.T, H[None, :]], axis=0)         # [K+1, N]
    hist = pallas_hist.hist_pallas(
        Xb.T, pay, node[None, :].astype(jnp.float32),
        n_slots=n_nodes, n_bins=B, allow_bf16=True,
        derive_count=True)                                   # [nC, F*B]
    hist = hist.reshape(n_nodes, C, F, B)
    return (hist[:, :K].transpose(0, 2, 3, 1), hist[:, K], hist[:, K + 1])


def _histograms_matmul(Xb, G, H, count_unit, node, n_nodes: int, B: int):
    """Histograms as dense MXU contractions (TPU path — scatter-free).

    One combined one-hot over the (feature, bin) axis: oh[i, f*B+b] =
    (Xb[i, f] == b), so the whole level histogram is ONE contraction
    Q^T @ oh -> [n_nodes*C, F*B] per row chunk (Q folds the node one-hot
    with the K+2 payload channels). F*B ~ 2048 columns keeps the MXU tiles
    square-ish, and the chunked lax.scan caps HBM temporaries at
    O(_HIST_CHUNK * F * B) regardless of N. Under the fold-vmapped sweep
    the one-hot depends only on Xb (unbatched), so XLA shares it across
    fold lanes and batches the Q contraction.
    """
    N, F = Xb.shape
    K = G.shape[1]
    C = K + 2
    FB = F * B
    P = jnp.concatenate([G, H[:, None], count_unit[:, None]], axis=1)

    chunk = min(_HIST_CHUNK, N)
    nchunks = -(-N // chunk)
    pad = nchunks * chunk - N
    if pad:
        # zero-payload padding is inert: P rows are 0, so whatever one-hot
        # cell a padded row lands in receives +0
        Xb = jnp.pad(Xb, ((0, pad), (0, 0)))
        P = jnp.pad(P, ((0, pad), (0, 0)))
        node = jnp.pad(node, ((0, pad),))

    offs = (jnp.arange(F, dtype=jnp.int32) * B)[None, :]
    cols = jnp.arange(FB, dtype=jnp.int32)[None, :]

    def body(acc, sl):
        xb_c, p_c, node_c = sl
        oh = (jnp.repeat(xb_c.astype(jnp.int32) + offs, B, axis=1)
              == cols).astype(jnp.float32)                       # [c, F*B]
        node_oh = jax.nn.one_hot(node_c, n_nodes, dtype=jnp.float32)
        Q = (node_oh[:, :, None] * p_c[:, None, :]).reshape(chunk,
                                                            n_nodes * C)
        acc = acc + jax.lax.dot_general(
            Q, oh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [nC, F*B]
        return acc, None

    xs = (Xb.reshape(nchunks, chunk, F), P.reshape(nchunks, chunk, C),
          node.reshape(nchunks, chunk))
    acc0 = jnp.zeros((n_nodes * C, FB), jnp.float32)
    hist, _ = jax.lax.scan(body, acc0, xs)
    hist = hist.reshape(n_nodes, C, F, B)
    hg = hist[:, :K].transpose(0, 2, 3, 1)                       # [n,F,B,K]
    hh = hist[:, K]                                              # [n,F,B]
    hc = hist[:, K + 1]
    return hg, hh, hc


# Rows per chunk of the one-hot routing/prediction maps (bounds the
# [chunk, F] selection products).
_ROUTE_CHUNK = 1 << 20


def _onehot_route_step(xf, rel, f_lvl, t_lvl, m_lvl, n_nodes: int):
    """One gather-free routing step:
    rel' = 2*rel + ((bin > t(rel)) | (bin == 0 & miss(rel))).

    TPU serializes data-dependent row gathers, so the per-row feature
    select becomes a one-hot contraction: sel = onehot(rel) @ FS with
    FS[n, f] = (f_lvl[n] == f); the selected bin is then a masked row sum
    (exact: bin 0 contributes 0, so a missing row's masked sum is 0 —
    precisely the missing-bin value). Exact for bin values (< 2^24,
    f32-representable). Shared by training routing (_route_level_matmul)
    and prediction (_predict_bins_matmul)."""
    F = xf.shape[1]
    rel_oh = jax.nn.one_hot(rel, n_nodes, dtype=jnp.float32)
    FS = (f_lvl[:, None] == jnp.arange(F)[None, :]).astype(jnp.float32)
    sel = jnp.matmul(rel_oh, FS, preferred_element_type=jnp.float32)
    xb_sel = (xf * sel).sum(axis=1)
    tm = jnp.stack([t_lvl.astype(jnp.float32),
                    m_lvl.astype(jnp.float32)], axis=1)          # [n, 2]
    tm_sel = jnp.matmul(rel_oh, tm,
                        preferred_element_type=jnp.float32)      # [N, 2]
    right = (xb_sel > tm_sel[:, 0]) | ((xb_sel == 0.0)
                                       & (tm_sel[:, 1] > 0.5))
    return 2 * rel + right.astype(jnp.int32)


def _route_level_matmul(Xb, node, f_lvl, t_lvl, m_lvl, n_nodes: int):
    """Gather-free level routing over row chunks (see _onehot_route_step)."""
    N, F = Xb.shape

    def one_block(sl):
        xb_blk, node_blk = sl
        return _onehot_route_step(xb_blk.astype(jnp.float32), node_blk,
                                  f_lvl, t_lvl, m_lvl, n_nodes)

    chunk = min(_ROUTE_CHUNK, N)
    nchunks = -(-N // chunk)
    pad = nchunks * chunk - N
    if pad:
        Xb = jnp.pad(Xb, ((0, pad), (0, 0)))
        node = jnp.pad(node, ((0, pad),))
    out = jax.lax.map(one_block, (Xb.reshape(nchunks, chunk, F),
                                  node.reshape(nchunks, chunk)))
    return out.reshape(-1)[:N]


@functools.partial(
    jax.jit,
    static_argnames=("depth", "n_bins", "leaf_mode", "feature_frac",
                     "normalize_gain", "allow_pallas", "alpha",
                     "max_delta_step", "level_feature_frac",
                     "feature_mask_count"))
def grow_tree(Xb: jax.Array, G: jax.Array, H: jax.Array,
              key: jax.Array, *, depth: int, n_bins: int,
              reg_lambda: float = 0.0, min_child_weight: float = 0.0,
              min_instances: float = 1.0, min_info_gain: float = 0.0,
              gamma: float = 0.0, leaf_mode: str = "newton",
              feature_frac: float = 1.0, learning_rate: float = 1.0,
              normalize_gain: bool = True,
              feature_mask: Optional[jax.Array] = None,
              allow_pallas: bool = True, alpha: float = 0.0,
              max_delta_step: float = 0.0,
              level_feature_frac: float = 1.0,
              feature_mask_count: Optional[int] = None) -> Tree:
    """Grow one depth-`depth` tree level-wise on binned features.

    Xb: int8/int32 [N, F] bins; G: f32 [N, K] per-row gradient payload (weights
    folded in); H: f32 [N] per-row hessian/weight (0 = row excluded, which
    is how bootstrap, fold masks and padding enter). Rows, features and bins
    are all machine axes; the level loop is a static Python unroll.

    `feature_frac` < 1 resamples a feature subset at every node (Spark RF
    featureSubsetStrategy semantics); `feature_mask` [F] bool fixes one
    subset for the whole tree (XGBoost colsample_bytree semantics).

    Bins arrive shifted (bin_matrix): 0 = missing, present in [1, n_bins],
    so histograms carry n_bins + 1 slots and every split learns the
    missing default direction (sparsity-aware search, _split_scores).
    """
    N, F = Xb.shape
    K = G.shape[1]
    B = n_bins + 1   # histogram slots: missing bin 0 + n_bins value bins
    count_unit = jnp.asarray(H > 0, jnp.float32)
    # TPU: histograms as MXU matmuls (scatter lowers poorly there) — via
    # the VMEM-resident pallas kernel at large N, the chunked XLA scan
    # otherwise; CPU/GPU: one fused segment-sum. Results agree up to f32
    # rounding (the TPU path derives right-child histograms by sibling
    # subtraction, so near-tie splits can differ across backends).
    use_matmul = jax.default_backend() == "tpu"
    use_pallas = False
    if use_matmul and allow_pallas and N >= _PALLAS_MIN_ROWS:
        from . import pallas_hist
        use_pallas = pallas_hist.available()  # honors the kill switch
    if use_matmul and N > _HIST_CHUNK:
        # pad rows ONCE to the histogram chunk multiple (zero payload =
        # inert) so the per-level histogram calls never re-copy the arrays
        pad = -(-N // _HIST_CHUNK) * _HIST_CHUNK - N
        if pad:
            Xb = jnp.pad(Xb, ((0, pad), (0, 0)))
            G = jnp.pad(G, ((0, pad), (0, 0)))
            H = jnp.pad(H, ((0, pad),))
            count_unit = jnp.pad(count_unit, ((0, pad),))
            N += pad
    rows = jnp.arange(N)

    node = jnp.zeros(N, jnp.int32)   # in-level relative node id
    feats, threshs, misses = [], [], []
    last = None                      # split state for the leaf pass
    prev = None                      # previous level's raw histograms

    def _interleave(left, right, n_nodes):
        # children [2p] = left[p], [2p+1] = right[p]
        return jnp.stack([left, right], axis=1).reshape(
            (n_nodes,) + left.shape[1:])

    for d in range(depth):
        n_nodes = 1 << d
        if use_matmul and d > 0:
            # histogram subtraction (the XGBoost sibling trick): compute
            # LEFT children only — rows in right children carry the
            # out-of-range slot (dropped by one_hot / the pallas kernel)
            # — and derive right = parent - left from the previous
            # level's raw histograms. Halves the one-hot contraction
            # FLOPs of every level past the root.
            n_half = n_nodes // 2
            slots = jnp.where(node % 2 == 0, node // 2, n_half)
            fn = _histograms_pallas if use_pallas else _histograms_matmul
            hgl, hhl, hcl = fn(Xb, G, H, count_unit, slots, n_half, B)
            pg, ph, pc = prev
            hg = _interleave(hgl, pg - hgl, n_nodes)
            hh = _interleave(hhl, ph - hhl, n_nodes)
            hc = _interleave(hcl, pc - hcl, n_nodes)
        elif use_pallas:
            hg, hh, hc = _histograms_pallas(Xb, G, H, count_unit, node,
                                            n_nodes, B)
        elif use_matmul:
            hg, hh, hc = _histograms_matmul(Xb, G, H, count_unit, node,
                                            n_nodes, B)
        else:
            hg, hh, hc = _histograms_segment(Xb, G, H, count_unit, node,
                                             n_nodes, B)
        prev = (hg, hh, hc)

        GL = jnp.cumsum(hg, axis=2)
        HL = jnp.cumsum(hh, axis=2)
        CL = jnp.cumsum(hc, axis=2)
        Gt, Ht, Ct = GL[:, 0, -1, :], HL[:, 0, -1], CL[:, 0, -1]
        Gm, Hm, Cm = hg[:, :, 0, :], hh[:, :, 0], hc[:, :, 0]

        gain = _split_scores(GL, HL, CL, Gt, Ht, Ct, Gm, Hm, Cm,
                             reg_lambda, min_child_weight, min_instances,
                             min_info_gain, gamma, alpha, normalize_gain)
        if feature_mask is not None:
            gain = jnp.where(feature_mask[None, :, None, None],
                             gain, -jnp.inf)
        if level_feature_frac < 1.0:  # XGBoost colsample_bylevel: one
            key, sub = jax.random.split(key)  # fresh subset per level,
            # nested inside the bytree subset when one is active
            fml = _level_feature_mask(sub, F, level_feature_frac,
                                      feature_mask, feature_mask_count)
            gain = jnp.where(fml[None, :, None, None], gain, -jnp.inf)
        if feature_frac < 1.0:
            key, sub = jax.random.split(key)
            fm = _feature_mask(sub, n_nodes, F, feature_frac)
            gain = jnp.where(fm[:, :, None, None], gain, -jnp.inf)

        flat = gain.reshape(n_nodes, F * B * 2)
        best = jnp.argmax(flat, axis=1)
        best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
        ok = jnp.isfinite(best_gain)
        f_lvl = jnp.where(ok, (best // (B * 2)).astype(jnp.int32), 0)
        t_lvl = jnp.where(ok, ((best // 2) % B).astype(jnp.int32), B - 1)
        m_lvl = jnp.where(ok, (best % 2).astype(jnp.int32), 0)
        feats.append(f_lvl)
        threshs.append(t_lvl)
        misses.append(m_lvl)
        last = (GL, HL, CL, Gt, Ht, Ct, Gm, Hm, Cm, f_lvl, t_lvl, m_lvl)

        if use_pallas:
            # exact-equal decisions to _route_level_matmul (the selected
            # bin is a single one-hot term, f32-exact), one VMEM-resident
            # Xb pass instead of HBM selection products
            from . import pallas_hist
            node = pallas_hist.route_pallas(
                Xb.T, node[None].astype(jnp.float32), f_lvl[None],
                t_lvl[None], m_lvl[None],
                n_nodes=n_nodes)[0].astype(jnp.int32)
        elif use_matmul:
            node = _route_level_matmul(Xb, node, f_lvl, t_lvl, m_lvl,
                                       n_nodes)
        else:
            xb = Xb[rows, f_lvl[node]]
            right = (xb > t_lvl[node]) | ((xb == 0) & (m_lvl[node] > 0))
            node = 2 * node + right.astype(jnp.int32)

    # -- leaves -------------------------------------------------------------
    # Leaf sums come for free from the LAST level's cumulative histograms:
    # left child of node n = GL[n, f_n, t_n] (everything at or below the
    # chosen threshold), right child = Gt[n] - left. A dead node
    # (t = B-1) sends its whole mass left and 0 right — exactly the
    # all-rows-left traversal encoding. This removes the full-N
    # segment-sum (a scatter XLA serializes on TPU) from the leaf pass.
    n_leaves = 1 << depth
    if depth == 0:
        Gl = G.sum(axis=0, keepdims=True)                        # [1, K]
        Hl = H.sum()[None]
        Cl = count_unit.sum()[None]
    else:
        GL, HL, CL, Gt, Ht, Ct, Gm, Hm, Cm, f_lvl, t_lvl, m_lvl = last
        n_nodes = n_leaves // 2
        nid = jnp.arange(n_nodes)
        # default-right splits move the missing-bin mass out of the prefix
        mr = m_lvl.astype(jnp.float32)
        Gleft = (GL[nid, f_lvl, t_lvl, :]
                 - mr[:, None] * Gm[nid, f_lvl, :])              # [n, K]
        Hleft = HL[nid, f_lvl, t_lvl] - mr * Hm[nid, f_lvl]      # [n]
        Cleft = CL[nid, f_lvl, t_lvl] - mr * Cm[nid, f_lvl]
        Gl = _interleave(Gleft, Gt - Gleft, n_leaves)
        Hl = _interleave(Hleft, Ht - Hleft, n_leaves)
        Cl = _interleave(Cleft, Ct - Cleft, n_leaves)
    if leaf_mode == "newton":
        leaf = -_soft_l1(Gl, alpha) / (Hl + reg_lambda + EPS)[:, None]
        if max_delta_step > 0.0:  # XGBoost max_delta_step: cap the raw
            # (pre-learning-rate) newton step — the imbalanced-logistic
            # stabilizer (xgboost doc: 'Maximum delta step we allow each
            # leaf output to be')
            leaf = jnp.clip(leaf, -max_delta_step, max_delta_step)
    else:  # mean
        leaf = Gl / (Hl + EPS)[:, None]
    # training-empty leaves predict exactly 0: the count histogram is
    # integer-exact, while sibling-subtracted G/H can leave f32 noise
    # whose ratio would be an arbitrary payload for a serving row routed
    # into an empty (min_instances=0) child
    leaf = jnp.where(Cl[:, None] >= 0.5, leaf, 0.0)
    return Tree(jnp.concatenate(feats), jnp.concatenate(threshs),
                learning_rate * leaf, jnp.concatenate(misses))


def predict_bins(tree: Tree, Xb: jax.Array, depth: int) -> jax.Array:
    """Traverse one tree on binned rows: Xb [N, F] -> leaf payload [N, K].

    CPU: data-dependent gathers (fast there). TPU: gather-free — per-level
    one-hot routing exactly as _route_level_matmul, and the leaf payload
    lookup as onehot(leaf) @ leaf-table, all inside one chunked lax.map."""
    if jax.default_backend() != "tpu":
        N = Xb.shape[0]
        rows = jnp.arange(N)
        rel = jnp.zeros(N, jnp.int32)
        for d in range(depth):
            idx = (1 << d) - 1 + rel
            f = tree.feat[idx]
            t = tree.thresh[idx]
            xb = Xb[rows, f]
            right = (xb > t) | ((xb == 0) & (tree.miss[idx] > 0))
            rel = 2 * rel + right.astype(jnp.int32)
        return tree.leaf[rel]
    return _predict_bins_matmul(tree, Xb, depth)


def _predict_bins_matmul(tree: Tree, Xb: jax.Array, depth: int) -> jax.Array:
    N, F = Xb.shape
    K = tree.leaf.shape[-1]
    n_leaves = 1 << depth

    def one_block(xb_blk):
        c = xb_blk.shape[0]
        xf = xb_blk.astype(jnp.float32)
        rel = jnp.zeros(c, jnp.int32)
        for d in range(depth):
            lo = (1 << d) - 1
            rel = _onehot_route_step(xf, rel, tree.feat[lo: lo + (1 << d)],
                                     tree.thresh[lo: lo + (1 << d)],
                                     tree.miss[lo: lo + (1 << d)], 1 << d)
        leaf_oh = jax.nn.one_hot(rel, n_leaves, dtype=jnp.float32)
        return jnp.matmul(leaf_oh, tree.leaf.astype(jnp.float32),
                          preferred_element_type=jnp.float32)   # [c, K]

    chunk = min(_ROUTE_CHUNK, N)
    nchunks = -(-N // chunk)
    pad = nchunks * chunk - N
    if pad:
        Xb = jnp.pad(Xb, ((0, pad), (0, 0)))
    out = jax.lax.map(one_block, Xb.reshape(nchunks, chunk, F))
    return out.reshape(-1, K)[:N]


def predict_forest_bins(trees: Tree, Xb: jax.Array, depth: int) -> jax.Array:
    """Sum of payloads over a stacked batch of trees: [N, K]."""
    def one(carry, tree):
        return carry + predict_bins(tree, Xb, depth), None
    K = trees.leaf.shape[-1]
    init = jnp.zeros((Xb.shape[0], K), trees.leaf.dtype)
    out, _ = jax.lax.scan(one, init, trees)
    return out


# -- random forest ----------------------------------------------------------

# Terms of the Poisson inverse CDF below: the tail past them is under
# 1e-13 at rate 1 and under 1e-7 at rate 4, far finer than the 2^-23 steps
# of a float32 uniform
_POISSON_TERMS = 24


def _bootstrap_weights(key: jax.Array, n_rows: int, subsample,
                       bootstrap: bool = True) -> jax.Array:
    """One tree's row weights [n_rows] f32: Poisson(subsample) counts
    (Spark's with-replacement bagging) or, without bootstrap, a 0/1 draw
    at rate `subsample`. ONE uniform a row and the inverse CDF as an
    unrolled compare-and-count: one elementwise pass, where
    jax.random.poisson loops a rejection sampler over the whole vector.
    The count is sum_k [1 - u <= P(X > k)], the survival function summed
    from its SMALLEST term up: a CDF summed from the top sticks a few
    ulps under 1 in float32, and the largest uniforms then count every
    term (the chip drew 24s, PERF.md §6 PR 31). The one draw of both
    forest routes (fit_forest, forest_bootstrap): a seed grows the same
    trees on either."""
    u = jax.random.uniform(key, (n_rows,))
    if not bootstrap:
        return (u < subsample).astype(jnp.float32)
    lam = jnp.asarray(subsample, jnp.float32)
    pmf = [jnp.exp(-lam)]
    for k in range(1, _POISSON_TERMS + 1):
        pmf.append(pmf[-1] * lam / k)
    v = 1.0 - u                       # (0, 1], exact: u is k * 2^-23
    tail = jnp.zeros((), jnp.float32)
    count = jnp.zeros((n_rows,), jnp.float32)
    for k in range(_POISSON_TERMS, 0, -1):
        tail = tail + pmf[k]          # P(X > k - 1)
        count = count + (v <= tail)
    return count


@functools.partial(
    jax.jit,
    static_argnames=("n_trees", "depth", "n_bins", "leaf_mode",
                     "feature_frac", "bootstrap", "allow_pallas"))
def fit_forest(Xb: jax.Array, G: jax.Array, H: jax.Array, key: jax.Array, *,
               n_trees: int, depth: int, n_bins: int,
               subsample: float = 1.0, feature_frac: float = 1.0,
               reg_lambda: float = 0.0, min_instances: float = 1.0,
               min_info_gain: float = 0.0, leaf_mode: str = "mean",
               bootstrap: bool = True, allow_pallas: bool = True) -> Tree:
    """Random forest: scan of independent trees with Poisson bootstrap row
    weights (Spark's with-replacement bagging) and per-node feature subsets.

    Returns stacked Tree arrays with a leading [n_trees] axis; the ensemble
    prediction is the payload MEAN (class distribution / regression value).
    """
    def one(_, k):
        kb, kf = jax.random.split(k)
        rw = _bootstrap_weights(kb, Xb.shape[0], subsample, bootstrap)
        tree = grow_tree(Xb, G * rw[:, None], H * rw, kf, depth=depth,
                         n_bins=n_bins, reg_lambda=reg_lambda,
                         min_instances=min_instances,
                         min_info_gain=min_info_gain, leaf_mode=leaf_mode,
                         feature_frac=feature_frac, normalize_gain=True,
                         allow_pallas=allow_pallas)
        return None, tree
    _, trees = jax.lax.scan(one, None, jax.random.split(key, n_trees))
    return trees


# -- gradient boosting ------------------------------------------------------

def _logistic_grad(margin, y, w):
    p = jax.nn.sigmoid(margin)
    return w * (p - y), jnp.maximum(w * p * (1.0 - p), EPS)


def _squared_grad(pred, y, w):
    return w * (pred - y), w


@functools.partial(
    jax.jit,
    static_argnames=("n_rounds", "depth", "n_bins", "loss", "subsample",
                     "feature_frac", "alpha", "max_delta_step",
                     "colsample_bylevel", "base_score", "allow_pallas",
                     "normalize_gain"))
def fit_gbt(Xb: jax.Array, y: jax.Array, w: jax.Array, key: jax.Array, *,
            n_rounds: int, depth: int, n_bins: int,
            learning_rate: float = 0.1, reg_lambda: float = 1.0,
            min_child_weight: float = 0.0, min_instances: float = 1.0,
            min_info_gain: float = 0.0, gamma: float = 0.0,
            subsample: float = 1.0, feature_frac: float = 1.0,
            loss: str = "logistic", alpha: float = 0.0,
            max_delta_step: float = 0.0, colsample_bylevel: float = 1.0,
            base_score: Optional[float] = None,
            allow_pallas: bool = True,
            normalize_gain: bool = False) -> Tuple[Tree, jax.Array]:
    """Second-order boosted trees (XGBoost `hist` equivalent, one XLA program).

    loss='logistic' -> binary margins; loss='squared' -> regression. Returns
    (stacked trees, base_score). Prediction = base + sum of tree payloads.
    `base_score`: None derives the prior from the weighted label mean
    (better-calibrated start); a float pins the initial margin exactly the
    XGBoost way (probability for logistic, raw value for squared —
    OpXGBoostClassifier.setBaseScore on the reference wrapper).
    `allow_pallas=False` keeps every level on the chunked XLA histograms:
    callers pass it when Xb is laid out over several devices — a
    pallas_call under plain GSPMD is not partitioned, each device would
    gather and histogram the whole matrix.
    `normalize_gain`: compare `min_info_gain` with the gain a weighted row
    (Spark's minInfoGain: the GBT family, models/trees._GBTBase) and not
    with the gain summed over the node's rows (XGBoost's: the default).
    The payload g goes to grow_tree as it is, in one part: the three-part
    form is fit_gbt_folds' (models/trees.payload_body).
    """
    grad_fn = _logistic_grad if loss == "logistic" else _squared_grad
    wsum = w.sum() + EPS
    if base_score is not None:
        if loss == "logistic":
            p0 = min(max(float(base_score), 1e-6), 1 - 1e-6)
            base = jnp.asarray(np.log(p0 / (1 - p0)), jnp.float32)
        else:
            base = jnp.asarray(float(base_score), jnp.float32)
    elif loss == "logistic":
        p0 = jnp.clip((w * y).sum() / wsum, 1e-6, 1 - 1e-6)
        base = jnp.log(p0 / (1 - p0))
    else:
        base = (w * y).sum() / wsum

    def one(carry, k):
        margin, = carry
        ks, kc, kf = jax.random.split(k, 3)
        g, h = grad_fn(margin, y, w)
        if subsample < 1.0:
            rw = (jax.random.uniform(ks, y.shape) < subsample
                  ).astype(jnp.float32)
            g, h = g * rw, h * rw
        fm = (_feature_mask(kc, 1, Xb.shape[1], feature_frac)[0]
              if feature_frac < 1.0 else None)  # colsample_bytree
        tree = grow_tree(Xb, g[:, None], h, kf, depth=depth, n_bins=n_bins,
                         reg_lambda=reg_lambda,
                         min_child_weight=min_child_weight,
                         min_instances=min_instances,
                         min_info_gain=min_info_gain, gamma=gamma,
                         leaf_mode="newton", feature_mask=fm,
                         learning_rate=learning_rate,
                         normalize_gain=normalize_gain,
                         allow_pallas=allow_pallas,
                         alpha=alpha, max_delta_step=max_delta_step,
                         level_feature_frac=colsample_bylevel,
                         feature_mask_count=(
                             max(1, int(round(feature_frac * Xb.shape[1])))
                             if feature_frac < 1.0 else None))
        margin = margin + predict_bins(tree, Xb, depth)[:, 0]
        return (margin,), tree

    init = jnp.full(y.shape, base, jnp.float32)
    (_,), trees = jax.lax.scan(one, (init,), jax.random.split(key, n_rounds))
    return trees, base


# -- fold-fused growth ------------------------------------------------------
def _allreduce(v, axis_name):
    """psum under the row-sharded driver (the Rabit-allreduce slot of the
    XGBoost hist design); identity on a single device."""
    return jax.lax.psum(v, axis_name) if axis_name is not None else v


def _shard_vary_opt(tree, axis_name):
    """shard_map varying-manual-axes shim for scan carries (see
    parallel/mesh.shard_vary); identity off-mesh."""
    if axis_name is None:
        return tree
    from ..parallel.mesh import shard_vary
    return shard_vary(tree, axis_name)


def _fold_split_scores(reg_lambda, min_child_weight, gamma):
    """_split_scores vmapped over the fold/lane axis.

    reg_lambda / min_child_weight / gamma (and learning_rate in the leaf
    pass) may be PER-LANE vectors [Fo] — the config-fused sweep batches
    grid points into the fold axis; eta and lambda are pure algebra
    scalars per lane. Scalars keep the scalar HLO — the single-config
    path's executables (and their persistent-cache entries) must stay
    byte-identical."""
    def _ax(v):
        return 0 if getattr(v, "ndim", 0) == 1 else None

    return jax.vmap(
        _split_scores,
        in_axes=(0,) * 9 + (_ax(reg_lambda), _ax(min_child_weight),
                            None, None, _ax(gamma), None, None))


def _leaf_payload(Gl, Hl, Cl, reg_lambda, alpha, max_delta_step,
                  learning_rate, leaf_mode="newton", leaf_offset=None):
    """Per-fold leaves from leaf sufficient statistics [Fo, L(, K)] —
    the one leaf rule of the fused growth form: newton steps for the
    boosters, grow_tree's weighted mean G / H for forest lanes, plus
    `leaf_offset` where the caller took it out of G (a centred label)."""
    if leaf_mode == "mean":
        leaf = Gl / (Hl + EPS)[..., None]
        if leaf_offset is not None:
            leaf = leaf + leaf_offset
    else:
        rl_col = reg_lambda[:, None] \
            if getattr(reg_lambda, "ndim", 0) == 1 else reg_lambda
        leaf = -_soft_l1(Gl, alpha) / (Hl + rl_col + EPS)[..., None]
        if max_delta_step > 0.0:  # [Fo, L, 1] — cap raw newton step
            leaf = jnp.clip(leaf, -max_delta_step, max_delta_step)
    leaf = jnp.where(Cl[..., None] >= 0.5, leaf, 0.0)
    lr_col = learning_rate[:, None, None] \
        if getattr(learning_rate, "ndim", 0) == 1 else learning_rate
    return lr_col * leaf


def _fold_leaves(last, *, n_leaves, reg_lambda, alpha, max_delta_step,
                 learning_rate, leaf_mode="newton", leaf_offset=None):
    """Leaf payloads [Fo, n_leaves, 1] read off the LAST level's
    cumulative histograms (`last` as produced by the level split) — same
    free-leaf trick as grow_tree's leaf pass, vmapped over folds."""
    GL, HL, CL, Gt, Ht, Ct, Gm, Hm, Cm, f_lvl, t_lvl, m_lvl = last
    n_half = n_leaves // 2

    def leaf_of(GLk, HLk, CLk, Gtk, Htk, Ctk, Gmk, Hmk, Cmk,
                fk, tk, mk):
        nid = jnp.arange(n_half)
        mr = mk.astype(jnp.float32)
        Gleft = GLk[nid, fk, tk, :] - mr[:, None] * Gmk[nid, fk, :]
        Hleft = HLk[nid, fk, tk] - mr * Hmk[nid, fk]
        Cleft = CLk[nid, fk, tk] - mr * Cmk[nid, fk]
        Gl = jnp.stack([Gleft, Gtk - Gleft], axis=1).reshape(
            n_leaves, Gleft.shape[-1])
        Hl = jnp.stack([Hleft, Htk - Hleft], axis=1).reshape(n_leaves)
        Cl = jnp.stack([Cleft, Ctk - Cleft], axis=1).reshape(n_leaves)
        return Gl, Hl, Cl

    Gl, Hl, Cl = jax.vmap(leaf_of)(GL, HL, CL, Gt, Ht, Ct, Gm, Hm, Cm,
                                   f_lvl, t_lvl, m_lvl)
    return _leaf_payload(Gl, Hl, Cl, reg_lambda, alpha, max_delta_step,
                         learning_rate, leaf_mode, leaf_offset)


def level_slots(depth: int) -> tuple:
    """Live nodes of each level of one depth-`depth` tree, root first.
    The fused fit splits level d at exactly this many slots; every level
    but the last then runs one fused route+histogram pass at that slot
    count (fused_level_slots), and the last one only routes."""
    return tuple(1 << d for d in range(depth))


def fused_level_slots(depth: int) -> tuple:
    """`n_nodes` of each pallas_hist.route_hist pass of one tree, in
    level order — the ints _grow_tree_folds' level loop passes, and what
    the `tree_fused` span sums into `slot_passes`."""
    return level_slots(depth)[:-1]


def dead_levels_end_tree(level_feature_frac: float = 1.0,
                         node_feature_frac: float = 1.0) -> bool:
    """Does a level at which no lane has a node left to split end the
    tree for the fused growth form? Only where a dead node's all-left
    child faces the candidates the node faced: no per-level and no
    per-node feature draw (both static). A child that draws its own
    subset may split where its parent could not, so there the level loop
    runs every pass, as it always did. THE predicate: _grow_tree_folds
    asks it before it puts a pass under a cond, the callers before they
    count the passes a fit ran (level_passes_run)."""
    return level_feature_frac >= 1.0 and node_feature_frac >= 1.0


def _held_by_dead_nodes(ok, passed, held):
    """Half (i) of the dead-level rule. A node with no allowed split sends
    every row LEFT, so its left child's histogram IS its own: take it from
    what is held (`held`, the parent level's) and not from the pass that
    summed the same rows again in another order (`passed`); the right
    child is then an exact zero by sibling subtraction. A lane's result no
    longer depends on whether the pass ran. ok [..., n]; passed / held
    [..., n, 3, F, B]."""
    return jnp.where(ok[..., None, None, None], passed, held)


@functools.partial(jax.jit, static_argnames=("depth", "n_bins"))
def level_passes_run(trees: Tree, *, depth: int, n_bins: int) -> jax.Array:
    """int32 count of the fused and routing passes the fits that returned
    `trees` RAN under the dead-level rule (planned: rounds x depth; the
    root's histogram pass is not a level pass). Read off the split tables
    [rounds, Fo, 2^depth - 1], on the device: a node is dead iff its table
    is (feat, thresh, miss) = (0, n_bins, 0) — no allowed split sends
    every row left, min_instances >= 1 and a gain that must exceed
    min_info_gain >= 0 refuse it — and a round ran the pass of every level
    before its first level with no live node in ANY lane. Only for a fit
    under dead_levels_end_tree; any other ran rounds x depth."""
    live = ~((trees.feat == 0) & (trees.thresh == n_bins)
             & (trees.miss == 0))                       # [R, Fo, 2^depth - 1]
    by_level = jnp.stack(
        [live[..., (1 << d) - 1:(2 << d) - 1].any(axis=(-2, -1))
         for d in range(depth)], axis=-1)                          # [R, depth]
    return jnp.cumprod(by_level.astype(jnp.int32), axis=-1).sum()


def _grow_tree_folds(Xb_t, G, H, *, depth, n_bins,
                     reg_lambda, min_child_weight, min_instances,
                     min_info_gain, gamma, learning_rate, feature_mask,
                     interpret=False, alpha=0.0, max_delta_step=0.0,
                     level_feature_frac=1.0, level_key=None,
                     feature_mask_count=None, axis_name=None,
                     normalize_gain=False, leaf_mode="newton",
                     node_feature_frac=1.0, node_keys=None,
                     payload_parts=1, payload_scale=None, leaf_offset=None,
                     classes=0, leaf_rows_of=None):
    """Grow one tree PER FOLD level-wise in shared fused passes.

    Xb_t [F, N] transposed bins (N pre-padded to the route block size by
    the caller); G/H [Fo, N] per-fold payloads (excluded and padded rows
    enter as zeros exactly as in grow_tree; the unit-count channel is
    derived in VMEM as (H > 0) — grow_tree's count_unit — instead of
    streaming its own HBM plane). Each level past the root arrives from
    ONE fused route+histogram pass (pallas_hist.route_hist): the level's
    split tables route every row in VMEM and the surviving left-child
    slot ids feed the next level's histogram in the same read of the
    binned matrix, so each level costs ONE Xb pass for every (fold x
    config) lane together — not a histogram pass plus a routing pass.
    The per-node split algebra (cumsums, _split_scores, argmax, leaves)
    is the grow_tree math vmapped over the fold axis. On CPU the
    dispatchers drop to gather/segment-sum fallbacks (same decisions).

    One growth form, unrolled over depth: level d emits its own program
    section and its fused pass runs at the level's OWN slot count
    (`n_nodes = 1 << d`, level_slots), so a level with one live node
    does not pay for the deepest level's 2^(depth-2). The cost is
    program size O(depth): one Mosaic route_hist program a level
    (depth 6: fit_gbt_folds compiles in ~124 s on the chip, PERF.md §6
    PR 28). `axis_name` names a shard_map mesh axis rows are sharded
    over: every level histogram psums across shards before the split
    algebra (DrJAX-style psum-merged MapReduce), routing stays local.

    A level at which NO lane has a node left to split ends the tree
    (dead_levels_end_tree: only where no node and no level draws its own
    features): the level's pass sits under a lax.cond on `any(ok)` and is
    not run — every row goes left, as the dead tables (0, B - 1, 0) send
    it, and the left children hold their parents' sums; the last level's
    routing and lookup likewise, the rows reading their nodes' left
    leaves. Every later level is then dead again and costs its split
    algebra alone. A dead node beside live ones takes its left child from
    the held histogram too (_held_by_dead_nodes), so a lane grows the
    same tree whether its pass ran or not. level_passes_run counts the
    passes run off the returned tables.

    Forest lanes (fit_forest_lanes) take the same loop with Spark's
    rules: `normalize_gain` compares the gain a weighted row with
    min_info_gain, `leaf_mode="mean"` makes leaves G / H, and
    `node_feature_frac` < 1 draws a fresh feature subset at EVERY node
    from `node_keys` [T, 2] — one key a tree, split level by level exactly
    as grow_tree splits its own, its subsets shared by the Fo // T lanes
    (tree-major) that are the tree's folds.

    `classes` = K carries K class channels a lane (a multiclass forest):
    G holds every row's class id, H its weight, and the kernels build the
    channels weight x (id == k) in VMEM and issue K + 1 rows a (lane,
    slot) — the K class sums and the count; the weight sums are the class
    sums added. The split algebra is the same code at a last axis of K
    (with `normalize_gain`, Spark's Gini gain a weighted row), the leaves
    [Fo, 2^depth, K]; and since a row's K leaf values are K lookups, the
    caller says what becomes of them: `leaf_rows_of(leaf, node)` gets the
    leaf table and the final routing state [Fo, N] and its result comes
    back in leaf_rows' place.

    Returns (Tree with leading [Fo] axes, leaf_rows [Fo, N], subsets)
    where leaf_rows are the learning-rate-scaled per-row leaf payloads —
    bitwise what predict_bins returns for each fold's tree, read off the
    final routing state instead of re-traversed — and subsets is the
    [T, 2^depth - 1, F] bool record of the per-node draws (None without
    them).
    """
    from . import pallas_hist

    F, N = Xb_t.shape
    Fo = G.shape[0]
    B = n_bins + 1
    split_scores_f = _fold_split_scores(reg_lambda, min_child_weight, gamma)
    ends_dead = dead_levels_end_tree(level_feature_frac, node_feature_frac)

    def interleave_f(left, right, n_nodes):
        # children along axis 1: [Fo, 2p, ...] from per-parent pairs
        return jnp.stack([left, right], axis=2).reshape(
            (Fo, n_nodes) + left.shape[2:])

    node = jnp.zeros((Fo, N), jnp.float32)
    # payload channel order per fold: the kernels expect fold-major
    # [Fo*C]; g/h are level-invariant, so build [Fo, 2, N] -> [2Fo, N]
    # once — the count channel is derived in VMEM (derive_count)
    pay = jnp.stack([G, H], axis=1).reshape(2 * Fo, N)
    # rows a (lane, slot) out of the kernels, and what is asked of them
    # (`classes` only where set: a one-channel call's keywords, which the
    # benchmark's spies record and replay, stay what they were)
    rows_a_slot = classes + 1 if classes else 3
    hist_kw = dict(interpret=interpret, allow_bf16=True, derive_count=True,
                   payload_parts=payload_parts)
    if classes:
        hist_kw["classes"] = classes
    feats, threshs, misses, subsets = [], [], [], []
    last = None
    prev = None
    hist = None

    def unscaled(h):
        # the g rows back in the label's units (a power of two: exact);
        # one scale for every lane (a forest's) or one a lane [Fo] (a
        # booster round's)
        if payload_scale is None:
            return h
        by = payload_scale if getattr(payload_scale, "ndim", 0) == 0 \
            else payload_scale[:, None, None]
        h = h.reshape(Fo, -1, 3, F * B)
        return h.at[:, :, 0].multiply(by).reshape(-1, F * B)

    for d, n_nodes in enumerate(level_slots(depth)):
        if d == 0:
            # root histogram: all rows slot 0, one plain batched pass
            hist = _allreduce(unscaled(pallas_hist.hist_folds(
                Xb_t, pay, node, n_slots=1, n_bins=B, **hist_kw)),
                axis_name)                                # [Fo*1*3, F*B]
            n_slots = 1
        else:
            # `hist` holds the LEFT-child histograms of THIS level,
            # produced by the fused route+hist pass at the end of the
            # previous iteration (sibling subtraction: right = parent -
            # left, same trick as grow_tree)
            n_slots = n_nodes // 2
        hist = hist.reshape(Fo, n_slots, rows_a_slot, F, B)
        if classes:
            hgl = jnp.moveaxis(hist[:, :, :classes], 2, -1)   # [Fo,S,F,B,K]
            hhl = hgl.sum(-1)            # the weight: the class sums added
            hcl = hist[:, :, classes]
        else:
            hgl = hist[:, :, 0][..., None]                    # [Fo,S,F,B,1]
            hhl = hist[:, :, 1]                               # [Fo,S,F,B]
            hcl = hist[:, :, 2]
        if d == 0:
            hg, hh, hc = hgl, hhl, hcl
        else:
            pg, ph, pc = prev
            hg = interleave_f(hgl, pg - hgl, n_nodes)
            hh = interleave_f(hhl, ph - hhl, n_nodes)
            hc = interleave_f(hcl, pc - hcl, n_nodes)
        prev = (hg, hh, hc)

        GL = jnp.cumsum(hg, axis=3)                       # [Fo,n,F,B,1]
        HL = jnp.cumsum(hh, axis=3)
        CL = jnp.cumsum(hc, axis=3)
        Gt, Ht, Ct = GL[:, :, 0, -1, :], HL[:, :, 0, -1], CL[:, :, 0, -1]
        Gm, Hm, Cm = hg[:, :, :, 0, :], hh[:, :, :, 0], hc[:, :, :, 0]

        gain = split_scores_f(GL, HL, CL, Gt, Ht, Ct, Gm, Hm, Cm,
                              reg_lambda, min_child_weight, min_instances,
                              min_info_gain, gamma, alpha, normalize_gain)
        if feature_mask is not None:
            gain = jnp.where(feature_mask[None, None, :, None, None],
                             gain, -jnp.inf)
        if node_feature_frac < 1.0 and node_keys is not None:
            # Spark featureSubsetStrategy: a subset a NODE, one stream a
            # tree (grow_tree's own key walk), the tree's fold lanes alike
            node_keys, subs = jnp.moveaxis(
                jax.vmap(jax.random.split)(node_keys), 1, 0)
            fm = jax.vmap(lambda k: _feature_mask(
                k, n_nodes, F, node_feature_frac))(subs)      # [T, n, F]
            subsets.append(fm)
            gain = jnp.where(
                jnp.repeat(fm, Fo // fm.shape[0], axis=0)[..., None, None],
                gain, -jnp.inf)
        if level_feature_frac < 1.0 and level_key is not None:
            # colsample_bylevel: one fresh subset per level, shared by
            # every fold (fold parity with the sequential loop, which
            # fits all folds with the same key), nested inside the
            # bytree subset exactly as grow_tree does
            level_key, sub = jax.random.split(level_key)
            fml = _level_feature_mask(sub, F, level_feature_frac,
                                      feature_mask, feature_mask_count)
            gain = jnp.where(fml[None, None, :, None, None],
                             gain, -jnp.inf)

        flat = gain.reshape(Fo, n_nodes, F * B * 2)
        best = jnp.argmax(flat, axis=2)                   # [Fo, n]
        best_gain = jnp.take_along_axis(flat, best[..., None],
                                        axis=2)[..., 0]
        ok = jnp.isfinite(best_gain)
        f_lvl = jnp.where(ok, (best // (B * 2)).astype(jnp.int32), 0)
        t_lvl = jnp.where(ok, ((best // 2) % B).astype(jnp.int32), B - 1)
        m_lvl = jnp.where(ok, (best % 2).astype(jnp.int32), 0)
        feats.append(f_lvl)
        threshs.append(t_lvl)
        misses.append(m_lvl)
        last = (GL, HL, CL, Gt, Ht, Ct, Gm, Hm, Cm, f_lvl, t_lvl, m_lvl)

        if d < depth - 1:
            # fused pass: route with this level's tables AND accumulate
            # the next level's left-child histograms in ONE Xb read
            def fused_pass(node):
                hist, node = pallas_hist.route_hist(
                    Xb_t, pay, node, f_lvl, t_lvl, m_lvl, n_nodes=n_nodes,
                    n_bins=B, **hist_kw)
                return _allreduce(unscaled(hist), axis_name), node

            if not ends_dead:
                hist, node = fused_pass(node)
            else:
                # a level with no live node in any lane: the pass is not
                # run. Every row goes left (what the tables (0, B - 1, 0)
                # do) and the left children hold their parents' sums
                held = jnp.concatenate(
                    [jnp.moveaxis(hg, -1, 2), hc[:, :, None]], axis=2) \
                    if classes else jnp.stack([hg[..., 0], hh, hc], axis=2)
                hist, node = jax.lax.cond(
                    jnp.any(ok), fused_pass,
                    lambda node: (held.reshape(-1, F * B), node * 2.0), node)
                hist = _held_by_dead_nodes(
                    ok, hist.reshape(held.shape), held).reshape(-1, F * B)
        else:
            # final level: no further histogram — plain routing pass to
            # land every row on its leaf
            def route_pass(node):
                return pallas_hist.route(
                    Xb_t, node, f_lvl, t_lvl, m_lvl, n_nodes=n_nodes,
                    interpret=interpret)

            if not ends_dead:
                node = route_pass(node)

    n_leaves = 1 << depth
    if depth == 0:
        if classes:
            Gl = _allreduce((H[:, :, None] * jax.nn.one_hot(
                G.astype(jnp.int32), classes)).sum(axis=1),
                axis_name)[:, None, :]
        else:
            Gl = _allreduce(G.sum(axis=1), axis_name)[:, None, None]
        Hl = _allreduce(H.sum(axis=1), axis_name)[:, None]
        Cl = _allreduce((H > 0).astype(jnp.float32).sum(axis=1),
                        axis_name)[:, None]
        leaf = _leaf_payload(Gl, Hl, Cl, reg_lambda, alpha,
                             max_delta_step, learning_rate, leaf_mode,
                             leaf_offset)
    else:
        leaf = _fold_leaves(last, n_leaves=n_leaves, reg_lambda=reg_lambda,
                            alpha=alpha, max_delta_step=max_delta_step,
                            learning_rate=learning_rate, leaf_mode=leaf_mode,
                            leaf_offset=leaf_offset)
    tbl = leaf[:, :, 0]
    if leaf_rows_of is not None:
        if ends_dead and depth > 0:   # routed, or every row left as it is
            node = jax.lax.cond(jnp.any(ok), route_pass,
                                lambda node: node * 2.0, node)
        leaf_rows = leaf_rows_of(leaf, node)
    elif ends_dead and depth > 0:
        # the last level: routed and looked up, or - no node left to split
        # in any lane - every row lands on its node's LEFT leaf as it
        # stands: the even leaves' table read at the node ids themselves
        thin = jnp.pad(tbl[:, ::2], ((0, 0), (0, n_leaves // 2)))
        leaf_rows = jax.lax.cond(
            jnp.any(ok),
            lambda node: pallas_hist.table_lookup(
                tbl, route_pass(node), interpret=interpret),
            lambda node: pallas_hist.table_lookup(
                thin, node, interpret=interpret), node)
    else:
        leaf_rows = pallas_hist.table_lookup(
            tbl, node, interpret=interpret)                   # [Fo, N]
    tree = Tree(jnp.concatenate(feats, axis=1),
                jnp.concatenate(threshs, axis=1), leaf,
                jnp.concatenate(misses, axis=1))
    return tree, leaf_rows, \
        (jnp.concatenate(subsets, axis=1) if subsets else None)


#: How a lane's payload g reaches the kernels' bfloat16 contraction, by the
#: word models/trees.payload_body gives an estimator: the bfloat16 parts of
#: g. A forest lane's g = weight x label. "indicator": a 0/1 label, g an
#: integer under 256 under unit sample weights, exact in ONE part and three
#: rows a (lane, slot). "centred_parts": a real-valued label less its
#: weighted mean and over a power of two that brings g into [-1, 1]
#: (forest_label_centre), g as THREE fixed-point parts, five rows a (lane,
#: slot) — every product exact, the parts' sums exact; the variance gain
#: does not see the shift, and the leaves get it back. A booster lane's g is
#: the loss's gradient x weight. "gradient": the logistic p - y, under 1 in
#: size, rounded ONCE to bfloat16 — what the boosters always issued.
#: "residual_parts": the squared loss's w (F - y), real-valued and as large
#: as the label's spread: a ROUND takes each lane's scale from its largest
#: size (_residual_scale) and hands g / scale on as three fixed-point
#: parts, five rows. No centre is taken out of a residual: the base score
#: is the label's weighted mean (base = wy / wsum), so round 1's g is the
#: centred label already and later rounds' lie about zero.
#: "class_indicators": a label of K classes under a multiclass sweep. A
#: lane hands over [class id, weight] and the kernels build the K channels
#: weight x (id == k) in VMEM: whole numbers under 256 under unit sample
#: weights, exact in ONE part, K + 1 rows a (lane, slot) — the K class sums
#: and the count, the weight sums being the class sums added. Its gain is
#: Spark's K-class Gini gain whole: minInfoGain as it stands.
PAYLOAD_PARTS = {"indicator": 1, "centred_parts": 3,
                 "gradient": 1, "residual_parts": 3,
                 "class_indicators": 1}


def payload_rows(payload: str, classes: int = 0) -> int:
    """Rows a (lane, slot) the fused passes issue under this payload word:
    g's parts, the weight and the derived count (3 | 5); under
    "class_indicators" the `classes` class sums and the count (K + 1: the
    word's rows are asked with the class count)."""
    from . import pallas_hist
    by_class = payload == "class_indicators"
    if by_class and classes < 2:
        raise ValueError(f"class_indicators: rows of {classes} classes")
    return pallas_hist.payload_rows(2, PAYLOAD_PARTS[payload], True,
                                    classes if by_class else 0)


def payload_min_info_gain(payload: str, min_info_gain: float) -> float:
    """Spark's minInfoGain on the scale of THIS payload's gain, the one
    place it is scaled: the variance gain of the one channel [w y] of a
    0/1 label ("indicator") is HALF the two-class Gini gain at every
    candidate, so its threshold is halved with it; every other payload's
    gain is Spark's own (the variance gain of a real label, the K-class
    Gini gain of K class channels) and meets the threshold whole."""
    return min_info_gain * (0.5 if payload == "indicator" else 1.0)


#: the name the forests' callers took before the boosters had a word
forest_payload_rows = payload_rows


def _residual_scale(g: jax.Array) -> jax.Array:
    """[Fo] the power of two just over each lane's largest |g| (g [Fo, N]
    float32): g / scale lies inside (-1, 1), where ops/parts.unit_cuts'
    parts are fixed-point and their sums exact. Read off the float32's own
    exponent field — no log2 / exp2 whose last bit a backend may round —
    so it IS a power of two and the division and the sums' way back are
    exact. One max-reduction a round over [Fo, N]; a lane of zeros reads
    2^-126 and stays zeros."""
    top = jnp.max(jnp.abs(g), axis=1)
    bits = jax.lax.bitcast_convert_type(top, jnp.int32)
    up = jnp.minimum((bits >> 23) + 1, 254) << 23
    return jax.lax.bitcast_convert_type(up, jnp.float32)


def _fit_gbt_folds_impl(Xb, y, W, key, *, n_rounds, depth, n_bins,
                        learning_rate=0.1, reg_lambda=1.0,
                        min_child_weight=0.0, min_instances=1.0,
                        min_info_gain=0.0, gamma=0.0, subsample=1.0,
                        feature_frac=1.0, loss="logistic",
                        interpret=False, alpha=0.0, max_delta_step=0.0,
                        colsample_bylevel=1.0, base_score=None,
                        axis_name=None, normalize_gain=False,
                        payload="gradient"):
    """Shared body of fit_gbt_folds (single device, axis_name=None) and
    fit_gbt_folds_sharded (inside shard_map: inputs hold this shard's
    LOCAL rows and every histogram/base-score reduction psums over
    `axis_name`; the sharded form hands over no `payload`: one part)."""
    grad_fn = _logistic_grad if loss == "logistic" else _squared_grad
    parts = PAYLOAD_PARTS[payload]
    if parts != 1 and axis_name is not None:
        raise ValueError("a payload in parts takes its scale from one "
                         "device's rows: the sharded route hands over none")
    Fo, N = W.shape
    n_orig = N
    if subsample < 1.0 and axis_name is not None:
        # per-shard uniform draws are index-local: every shard would draw
        # the SAME bits for its local rows — neither matching the
        # single-device mask nor independent. The sweep gate
        # (models/trees._sharded_route_ok) keeps such configs off this
        # route; this raise is the trace-time backstop, and tmoglint
        # SHD003 enforces it at LINT time: the raise is a recorded path
        # condition that makes the subsample draw below statically dead
        # on the sharded route — delete this guard and the linter flags
        # the draw before any sweep runs (tests/test_tmoglint_shd.py).
        raise ValueError("row subsample < 1.0 is not supported on the "
                         "sharded fused sweep route")
    wsum = _allreduce(W.sum(axis=1), axis_name) + EPS
    wy = _allreduce((W * y[None, :]).sum(axis=1), axis_name)
    if base_score is not None:  # pinned prior, fit_gbt semantics
        if loss == "logistic":
            # base_score is a python scalar at every call site (a jit
            # static arg of fit_gbt_folds / a closure constant of the
            # sharded driver), never traced
            # tmoglint: disable=TPU001  static python scalar
            p0 = min(max(float(base_score), 1e-6), 1 - 1e-6)
            base = jnp.full((Fo,), np.log(p0 / (1 - p0)), jnp.float32)
        else:
            # tmoglint: disable=TPU001  static python scalar
            base = jnp.full((Fo,), float(base_score), jnp.float32)
    elif loss == "logistic":
        p0 = jnp.clip(wy / wsum, 1e-6, 1 - 1e-6)
        base = jnp.log(p0 / (1 - p0))
    else:
        base = wy / wsum

    # pad rows once to the kernels' block size (inert: zero payloads)
    from . import pallas_hist
    blk = pallas_hist._ROUTE_BLK
    pad = (-N) % blk
    if pad:
        Xb = jnp.pad(Xb, ((0, pad), (0, 0)))
        y = jnp.pad(y, ((0, pad),))
        W = jnp.pad(W, ((0, 0), (0, pad)))
        N += pad
    valid = (jnp.arange(N) < n_orig).astype(jnp.float32)
    Xb_t = Xb.T

    def one(carry, k):
        margin, = carry
        ks, kc, kf = jax.random.split(k, 3)
        g, h = grad_fn(margin, y[None, :], W)             # [Fo, N] each
        # padded rows carry literal zeros (grow_tree pads H with 0; the
        # logistic clamp would otherwise leave them at EPS)
        h = h * valid[None, :]
        if subsample < 1.0:
            # draw over the UNPADDED row count so the mask matches
            # fit_gbt's uniform(ks, (n,)) unconditionally: under the
            # default jax_threefry_partitionable mode padded draws are
            # prefix-stable (bits are per-index), but with the flag off
            # bits depend on array size and a padded draw would break
            # the exact-parity contract above
            rw = (jax.random.uniform(ks, (n_orig,)) < subsample
                  ).astype(jnp.float32)
            rw = jnp.pad(rw, (0, N - n_orig))[None, :]
            g, h = g * rw, h * rw
        # count semantics follow grow_tree's count_unit = (H > 0) on the
        # POST-subsample hessian — derived in VMEM by the histogram
        # kernels (derive_count), no HBM plane: the logistic clamp keeps
        # excluded (W=0) real rows countable exactly as in the sequential
        # path, while subsampled-out and padded rows drop to 0
        fm = (_feature_mask(kc, 1, Xb_t.shape[0], feature_frac)[0]
              if feature_frac < 1.0 else None)
        # kf seeds the per-LEVEL colsample_bylevel draws (split exactly
        # like grow_tree splits its key, so the fused and sequential
        # routes draw identical level subsets); per-node resampling stays
        # unused — boosting samples features per tree/level, not per node
        scale = None
        if parts == 3:
            # THIS round's residual, every lane by its own largest size:
            # rounds shrink it, and a scale left from round 1 would push
            # the parts down the fixed-point grid bit by bit
            scale = _residual_scale(g)
            g = g * (1.0 / scale)[:, None]
        tree, leaf_rows, _ = _grow_tree_folds(
            Xb_t, g, h, depth=depth, n_bins=n_bins,
            reg_lambda=reg_lambda, min_child_weight=min_child_weight,
            min_instances=min_instances, min_info_gain=min_info_gain,
            gamma=gamma, learning_rate=learning_rate, feature_mask=fm,
            interpret=interpret, alpha=alpha,
            max_delta_step=max_delta_step,
            level_feature_frac=colsample_bylevel, level_key=kf,
            feature_mask_count=(
                # feature_frac: jit static arg / closure constant
                # tmoglint: disable=TPU001  static python scalar
                max(1, int(round(feature_frac * Xb_t.shape[0])))
                if feature_frac < 1.0 else None),
            axis_name=axis_name, normalize_gain=normalize_gain,
            payload_parts=parts, payload_scale=scale)
        return (margin + leaf_rows,), tree

    init = jnp.broadcast_to(base[:, None], (Fo, N)).astype(jnp.float32)
    init = _shard_vary_opt(init, axis_name)
    (margin,), trees = jax.lax.scan(one, (init,),
                                    jax.random.split(key, n_rounds))
    return trees, base, margin[:, :n_orig]


@functools.partial(
    jax.jit,
    static_argnames=("n_rounds", "depth", "n_bins", "loss", "subsample",
                     "feature_frac", "interpret", "alpha",
                     "max_delta_step", "colsample_bylevel", "base_score",
                     "normalize_gain", "payload"))
def fit_gbt_folds(Xb: jax.Array, y: jax.Array, W: jax.Array,
                  key: jax.Array, *, n_rounds: int, depth: int,
                  n_bins: int, learning_rate: float = 0.1,
                  reg_lambda: float = 1.0, min_child_weight: float = 0.0,
                  min_instances: float = 1.0, min_info_gain: float = 0.0,
                  gamma: float = 0.0, subsample: float = 1.0,
                  feature_frac: float = 1.0, loss: str = "logistic",
                  interpret: bool = False, alpha: float = 0.0,
                  max_delta_step: float = 0.0,
                  colsample_bylevel: float = 1.0,
                  base_score: Optional[float] = None,
                  normalize_gain: bool = False, payload: str = "gradient"):
    """Boosted trees for every CV fold in ONE device program.

    The mask-fold sweep (models/trees.mask_fit_scores) above the fold-vmap
    row limit used to loop folds through fit_gbt sequentially — each fold
    re-reading the binned matrix and re-building the (feature, bin)
    one-hots that dominate the histogram kernel, with a contraction M dim
    (slots x 3 payload channels) far under the 128-row MXU tile. Here the
    folds share every Xb pass (fold-fused pallas histograms + routing) and
    stack their payload rows into the same contraction. Each level's
    fused pass runs at that level's own slot count (_grow_tree_folds);
    one (shape, depth) compiles exactly one executable.

    Xb [N, F] binned (bin_matrix layout); y [N]; W [Fo, N] per-fold
    weights (0 = row excluded from that fold's fit). Per-fold quantities
    follow fit_gbt exactly — same base score, same gradient clamps, same
    per-round subsample/colsample draws (ONE draw shared by all folds,
    matching the sequential loop where every fold fits with the same
    key). Returns (trees [rounds, Fo, ...], base [Fo], margins [Fo, N]) —
    margins are the fitted scores for ALL rows (held-out rows are routed
    through each fold's trees), i.e. exactly what the sequential
    per-fold `base + predict_forest_bins(...)` loop produces.

    `normalize_gain` as in fit_gbt: Spark's minInfoGain, a weighted row.
    `payload` (PAYLOAD_PARTS; the CALLER vouches for it, by
    models/trees.payload_body) says how a round's g enters the kernels'
    bfloat16 contraction. "gradient": rounded once, at 2^-9 of each value —
    the logistic loss's, whose |g| < 1. "residual_parts": the squared
    loss's g = w (F - y) computed in float32, divided by the round's scale
    (_residual_scale: a power of two a lane, from that lane's largest |g|
    THIS round) and cut into three fixed-point bfloat16 parts, five rows a
    (lane, slot): every histogram sum of g the splits and the leaves are
    read from is the sum of exact products added exactly, to ~2^-25 of the
    scale a row. Who is exact: g whatever the sample weights (the parts
    hold all 24 bits of w (F - y)); h = w in its ONE part only while w x
    fold mask is 0 or 1 or another value of eight significant bits — under
    real-valued sample weights h is rounded at 2^-9 of each value, on
    every route (ROADMAP R7 (d) (4)).
    """
    return _fit_gbt_folds_impl(
        Xb, y, W, key, n_rounds=n_rounds, depth=depth, n_bins=n_bins,
        learning_rate=learning_rate, reg_lambda=reg_lambda,
        min_child_weight=min_child_weight, min_instances=min_instances,
        min_info_gain=min_info_gain, gamma=gamma, subsample=subsample,
        feature_frac=feature_frac, loss=loss, interpret=interpret,
        alpha=alpha, max_delta_step=max_delta_step,
        colsample_bylevel=colsample_bylevel, base_score=base_score,
        normalize_gain=normalize_gain, payload=payload)


# -- forest lanes -----------------------------------------------------------
# A forest's trees do not depend on one another, so (tree, fold) pairs are
# LANES of the passes the boosters share among folds: one read of the
# binned matrix and one (feature, bin) one-hot a level serve every lane of
# a group, and the histogram contraction's M grows to lanes x slots x 3.
# models/trees._ForestBase drives it a group at a time: forest_bootstrap
# draws the group's row weights on the device ([trees, N]: all of a
# forest's at once would be gigabytes), fit_forest_lanes grows the group
# and adds its votes, forest_vote_scores turns the votes into scores.

@functools.partial(jax.jit, static_argnames=("n_rows", "n_trees", "group",
                                             "bootstrap"))
def forest_bootstrap(key: jax.Array, start, subsample, *, n_rows: int,
                     n_trees: int, group: int, bootstrap: bool = True):
    """Row weights and node-subset keys of trees `start .. start + group`
    of the forest keyed `key`: (rw [group, n_rows] f32, node_keys
    [group, 2]). Tree t's key is fit_forest's — split(key, n_trees)[t],
    split again into the bootstrap draw and grow_tree's key — so the lane
    route and the sequential one grow the same forest from one seed. A
    slot past n_trees (the last group of a forest the group size does not
    divide) weighs nothing: it grows a dead tree whose every leaf is 0."""
    keys = jax.random.split(key, n_trees)
    keys = jnp.pad(keys, ((0, group), (0, 0)))
    ks = jax.lax.dynamic_slice(keys, (start, 0), (group, keys.shape[1]))
    kb, kf = jnp.moveaxis(jax.vmap(jax.random.split)(ks), 1, 0)
    rw = jax.vmap(lambda k: _bootstrap_weights(k, n_rows, subsample,
                                               bootstrap))(kb)
    live = (start + jnp.arange(group)) < n_trees
    return rw * live[:, None].astype(jnp.float32), kf


#: the largest bootstrap draw the payload's scale leaves room for (a
#: Poisson(1) draw passes it once in ~10^14; past it the parts round, as
#: ops/parts.unit_cuts says)
_PAYLOAD_DRAW_ROOM = 16.0


@jax.jit
def forest_label_centre(y: jax.Array, w: jax.Array) -> jax.Array:
    """[centre, scale] of a real-valued label's payload. The centre is its
    weighted mean over all rows, rounded to bfloat16's eight significant
    bits so that y - centre is exact wherever the two lie within a factor
    of two of each other; the scale the power of two at or over
    _PAYLOAD_DRAW_ROOM x the largest w x |y - centre|, which brings every
    lane's weight x (y - centre) into [-1, 1]. One pair for every fold and
    tree of a sweep: the variance gain is the same about any constant. (A
    booster's residual needs no centre and no leaf_offset: its base score
    is the label's weighted mean, so its payload lies about zero from
    round 1 — PAYLOAD_PARTS, "residual_parts".)"""
    c = (w * y).sum() / jnp.maximum(w.sum(), EPS)
    c = jax.lax.reduce_precision(c, exponent_bits=8, mantissa_bits=7)
    top = _PAYLOAD_DRAW_ROOM * jnp.max(w * jnp.abs(y - c))
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(top, 2.0 ** -100))))
    return jnp.stack([c, scale])


@functools.partial(jax.jit, static_argnames=("depth", "n_bins",
                                             "feature_frac", "interpret",
                                             "payload", "classes"))
def fit_forest_lanes(Xb: jax.Array, y: jax.Array, W: jax.Array,
                     rw: jax.Array, node_keys: jax.Array, votes: jax.Array,
                     *, depth: int, n_bins: int, feature_frac: float = 1.0,
                     min_instances=1.0, min_info_gain=0.0,
                     interpret: bool = False, payload: str = "indicator",
                     centre=None, classes: int = 0):
    """Grow one GROUP of a forest's trees for every CV fold in the fused
    passes and add their votes: lanes = (tree, fold), tree-major.

    Xb [N, F] binned; y [N] the one payload channel a unit of weight — the
    class-1 indicator of a binary label, or a regression target; W
    [folds, N] fold mask x sample weight; rw [T, N] the trees' bootstrap
    weights and node_keys [T, 2] their node-subset keys (forest_bootstrap);
    votes [folds, N] the running sum of leaf values. Lane (t, f) fits
    weights rw[t] * W[f], exactly fit_forest's tree t on fold f's rows:
    the tree's folds share its bootstrap vector and its per-node feature
    subsets (the sequential route hands every fold the same key), gains
    are Spark's (normalised by the node's weight, `min_instances`,
    `min_info_gain`), leaves weighted means.

    `payload` (PAYLOAD_PARTS; the CALLER vouches for it) says how
    g = weight x y enters the kernels' bfloat16 contraction. "indicator":
    y is 0 or 1, g rounded once — exact while the weights are integers
    under 256 (unit sample weights), else rounded at 2^-9 of each value
    like the weight row itself. "centred_parts": y real-valued and
    `centre` = forest_label_centre's [centre, scale]; g = weight x (y -
    centre) / scale goes as three fixed-point bfloat16 parts whose sum it
    is, so every histogram sum the splits and the leaves are read from is
    the sum of exact products added exactly (to ~2^-25 of the scale a row),
    and a leaf is centre + G / H. "class_indicators": y holds class ids
    0 .. `classes` - 1 and the lanes carry K = `classes` channels weight x
    (id == k), built in the kernels from [class id, weight]; the gain is
    Spark's K-class Gini gain a weighted row, a leaf the weighted class
    distribution of its rows, and votes [folds, K, N] — class-major: K as
    the minor axis would pad to 128 lanes on the chip — the sum over trees
    of each row's leaf distribution, a class at a time (one lookup a
    class of the same final routing state, one lane plane live).

    `min_info_gain` is compared with THIS payload's gain: the variance
    gain of one channel — for a regression target Spark's own (Variance
    impurity, a weighted row), unhalved. For a binary label that is half
    the two-class Gini gain grow_tree sums over a [w (1 - y), w y]
    payload, so a caller holding Spark's minInfoGain passes half of it;
    K class channels' gain is Spark's whole (payload_min_info_gain).

    Returns (votes + sum over the group's trees of the leaf value each
    row lands on, read off the final routing state — no tree is traversed
    a second time; Tree with leading [T * folds] axes; the per-node
    subsets [T, 2^depth - 1, F] or None)."""
    from . import pallas_hist
    folds, n_orig = W.shape
    pad = (-n_orig) % pallas_hist._ROUTE_BLK
    centred = payload == "centred_parts"
    by_class = payload == "class_indicators"
    if by_class != bool(classes):
        raise ValueError(f"payload {payload!r} with classes={classes}")
    H = (rw[:, None, :] * W[None, :, :]).reshape(-1, n_orig)  # [T*folds, N]
    if centred:
        centre, scale = jnp.asarray(centre, jnp.float32)
        G = H * ((y - centre) * (1.0 / scale))[None, :]
    elif by_class:   # the class id a row: the kernels make the channels
        G, scale = jnp.broadcast_to(y[None, :], H.shape), None
    else:
        G, scale = H * y[None, :], None
    if pad:  # inert: zero payloads, as in _fit_gbt_folds_impl
        Xb = jnp.pad(Xb, ((0, pad), (0, 0)))
        G = jnp.pad(G, ((0, 0), (0, pad)))
        H = jnp.pad(H, ((0, 0), (0, pad)))
    trees, leaf_rows, subsets = _grow_tree_folds(
        Xb.T, G, H, depth=depth, n_bins=n_bins, reg_lambda=0.0,
        min_child_weight=0.0, min_instances=min_instances,
        min_info_gain=min_info_gain, gamma=0.0, learning_rate=1.0,
        feature_mask=None, interpret=interpret, normalize_gain=True,
        leaf_mode="mean", node_feature_frac=feature_frac,
        node_keys=node_keys, payload_parts=PAYLOAD_PARTS[payload],
        payload_scale=scale, leaf_offset=centre if centred else None,
        **(dict(classes=classes, leaf_rows_of=functools.partial(
            _add_class_votes, votes, folds=folds, interpret=interpret))
           if by_class else {}))
    if by_class:
        return leaf_rows, trees, subsets
    group_votes = leaf_rows[:, :n_orig].reshape(-1, folds, n_orig).sum(0)
    return votes + group_votes, trees, subsets


def _add_class_votes(votes, leaf, node, *, folds, interpret):
    """votes [folds, K, n] + the sum over a lane group's trees (lanes
    tree-major) of each row's leaf distribution: leaf [lanes, leaves, K],
    node [lanes, N >= n] the final routing state. A class at a time: one
    lookup of the lanes' [leaves] column and one sum over its trees, the
    next class's lookup held behind it (optimization_barrier), so that ONE
    [lanes, N] plane of leaf rows is live and not K; the K sums join the
    votes in one pass."""
    from . import pallas_hist
    n = votes.shape[2]
    sums = []
    for k in range(votes.shape[1]):
        rows = pallas_hist.table_lookup(leaf[:, :, k], node,
                                        interpret=interpret)[:, :n]
        node, of_class = jax.lax.optimization_barrier(
            (node, rows.reshape(-1, folds, n).sum(0)))
        sums.append(of_class)
    return votes + jnp.stack(sums, axis=1)


class ClassMajorScores(NamedTuple):
    """Class scores [folds, K, n] of a multiclass sweep, class-major: the
    [folds, n, K] a validator's metric takes elsewhere would pad K to the
    128 lanes of the chip's tiles (18 x the bytes at K = 7). The wrapper
    is how the validator tells the layout (validators._class_major_metrics
    takes the argmax over axis 1)."""
    scores: jax.Array


@functools.partial(jax.jit, static_argnames=("n_trees",))
def forest_class_scores(votes: jax.Array, *, n_trees: int) -> jax.Array:
    """[folds, K, n] class scores from summed leaf distributions:
    _ForestBase._mask_score's rule — the mean over trees, clipped at 0,
    renormalised over the classes (a tree counts 0 for a row on a
    training-empty leaf)."""
    prob = jnp.clip(votes / n_trees, 0.0, None)
    return prob / jnp.maximum(prob.sum(axis=1, keepdims=True), 1e-12)


@functools.partial(jax.jit, static_argnames=("n_trees", "classification"))
def forest_vote_scores(votes: jax.Array, *, n_trees: int,
                       classification: bool) -> jax.Array:
    """[folds, N] scores from summed leaf values: the mean vote of a
    regression forest; for a binary one the logit of the mean class-1
    vote, clipped as _ForestBase._mask_score clips it."""
    mean = votes / n_trees
    if not classification:
        return mean
    p1 = jnp.clip(mean, 1e-7, 1.0 - 1e-7)
    return jnp.log(p1 / (1.0 - p1))


#: jitted shard_map program per (mesh, static config) — an explicit dict
#: (not lru_cache) so the kill switches can DROP programs for real.
_SHARDED_FIT_CACHE: dict = {}


def _sharded_gbt_fn(mesh, static_kw):
    """One jitted shard_map program per (mesh, static config) — cached
    (mirroring ops/glm_sweep's sharded-driver caching) so repeated
    sweeps at one shape reuse the compiled executable."""
    fn = _SHARDED_FIT_CACHE.get((mesh, static_kw))
    if fn is not None:
        return fn
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import BATCH_AXIS, build_shard_map

    kw = dict(static_kw)

    def core(Xb, y, W, key, learning_rate, reg_lambda, min_child_weight,
             gamma):
        return _fit_gbt_folds_impl(
            Xb, y, W, key, learning_rate=learning_rate,
            reg_lambda=reg_lambda, min_child_weight=min_child_weight,
            gamma=gamma, axis_name=BATCH_AXIS, **kw)

    sm = build_shard_map(
        core, mesh,
        in_specs=(P(BATCH_AXIS, None), P(BATCH_AXIS), P(None, BATCH_AXIS),
                  P(), P(None), P(None), P(None), P(None)),
        # trees/base replicate (they are grown from psum-merged
        # histograms, identical on every shard); margins stay row-sharded
        out_specs=(P(), P(), P(None, BATCH_AXIS)))
    fn = jax.jit(sm)
    _SHARDED_FIT_CACHE[(mesh, static_kw)] = fn
    return fn


def fit_gbt_folds_sharded(Xb: jax.Array, y: jax.Array, W: jax.Array,
                          key: jax.Array, *, mesh, n_rounds: int,
                          depth: int, n_bins: int,
                          learning_rate=0.1, reg_lambda=1.0,
                          min_child_weight=0.0, min_instances: float = 1.0,
                          min_info_gain: float = 0.0, gamma=0.0,
                          subsample: float = 1.0, feature_frac: float = 1.0,
                          loss: str = "logistic", interpret: bool = False,
                          alpha: float = 0.0, max_delta_step: float = 0.0,
                          colsample_bylevel: float = 1.0,
                          base_score: Optional[float] = None,
                          normalize_gain: bool = False):
    """fit_gbt_folds with rows sharded over the mesh batch axis.

    The DrJAX MapReduce shape over parallel/mesh.py: each device streams
    only its row shard of the binned matrix through the fused
    route+histogram passes, per-level histograms psum-merge across
    shards before the (replicated) split algebra, and routing stays
    local — so the (fold x config) lane axis of the sweep finally runs
    on a mesh instead of falling back to the sequential per-fold path.
    Requirements: the batch-axis device count must divide N (the
    validator pads rows up to a multiple of it via
    pad_rows_to_multiple) and subsample must stay 1.0 (per-shard
    draws are index-local — see _fit_gbt_folds_impl). The four per-lane
    algebra params always travel as [Fo] vectors here (one program
    shape for scalar and vector callers). Margins match the
    single-device fused fit up to f32 psum summation order.

    On a MULTI-PROCESS mesh Xb/y/W are THIS PROCESS's host-local rows
    (SPMD — every process calls with its own stripe); they land as the
    process's batch-axis block of one global array and the histogram
    psums become cross-host collectives. Histogram bin counts are
    integer sums of the same (row, weight) set as the single-process
    call, so trees match EXACTLY when gradients agree bit-for-bit and
    within f32 psum order otherwise. Margins come back as a HOST array
    holding only this process's rows (fetch_local), trimmed of layout
    padding.
    """
    from ..parallel.mesh import mesh_is_multiprocess

    Fo = W.shape[0]

    def lane(v):
        a = jnp.asarray(v, jnp.float32)
        return jnp.broadcast_to(a, (Fo,)) if a.ndim == 0 else a

    static_kw = (
        ("n_rounds", int(n_rounds)), ("depth", int(depth)),
        ("n_bins", int(n_bins)), ("min_instances", float(min_instances)),
        ("min_info_gain", float(min_info_gain)),
        ("subsample", float(subsample)),
        ("feature_frac", float(feature_frac)), ("loss", str(loss)),
        ("interpret", bool(interpret)), ("alpha", float(alpha)),
        ("max_delta_step", float(max_delta_step)),
        ("colsample_bylevel", float(colsample_bylevel)),
        ("base_score", None if base_score is None else float(base_score)),
        ("normalize_gain", bool(normalize_gain)))
    fn = _sharded_gbt_fn(mesh, static_kw)
    if mesh_is_multiprocess(mesh):
        from ..parallel import multihost as MH
        from ..parallel import podtrace

        Xl = np.asarray(Xb)
        n_local = Xl.shape[0]
        layout = MH.row_layout(n_local, mesh)
        with podtrace.ingest("tree_land", rows=int(n_local),
                             feat=int(Xl.shape[1])):
            # zero-weight padding is inert end to end: W=0 rows
            # contribute nothing to the base score, histograms or leaf
            # counts (the count unit is (H > 0) and H carries the
            # weight). Xb pads by repeating the last real row —
            # already-binned values, so any constant would do, but a
            # repeat keeps bin indices in range.
            Xb = MH.host_local_block(Xl, mesh, layout, pad_value=None)
            y = MH.host_local_block(np.asarray(y, np.float32), mesh,
                                    layout)
            W = MH.host_local_block(np.asarray(W, np.float32), mesh,
                                    layout, axis=1)
            key = MH.replicated_global(np.asarray(key), mesh)
            lanes = tuple(MH.replicated_global(np.asarray(lane(v)), mesh)
                          for v in (learning_rate, reg_lambda,
                                    min_child_weight, gamma))
        # collective window = sharded fit + local-margin fetch: the
        # histogram psums live inside the jitted program, so a victim
        # rank's barrier wall lands here (the skew table's attribution
        # contract — see parallel/podtrace.py)
        with podtrace.collective("tree_fit", rows=int(layout.n_padded),
                                 feat=int(Xl.shape[1]), folds=int(Fo),
                                 depth=int(depth), rounds=int(n_rounds)):
            trees, base, margins = fn(Xb, y, W, key, *lanes)
            margins = MH.fetch_local(margins, axis=1)[:, :n_local]
        return trees, base, margins
    return fn(Xb, y, W, key, lane(learning_rate), lane(reg_lambda),
              lane(min_child_weight), lane(gamma))


class _ShardedCacheClearer:
    """Adapter so the sharded-program dict sits on the pallas
    kill-switch consumer list (which calls .clear_cache())."""

    @staticmethod
    def clear_cache():
        _SHARDED_FIT_CACHE.clear()


@functools.partial(
    jax.jit,
    static_argnames=("n_rounds", "depth", "n_bins", "n_classes", "subsample",
                     "feature_frac", "alpha", "max_delta_step",
                     "colsample_bylevel"))
def fit_gbt_softmax(Xb: jax.Array, y: jax.Array, w: jax.Array,
                    key: jax.Array, *, n_rounds: int, depth: int,
                    n_bins: int, n_classes: int,
                    learning_rate: float = 0.1, reg_lambda: float = 1.0,
                    min_child_weight: float = 0.0, gamma: float = 0.0,
                    subsample: float = 1.0,
                    feature_frac: float = 1.0, alpha: float = 0.0,
                    max_delta_step: float = 0.0,
                    colsample_bylevel: float = 1.0) -> Tree:
    """Multiclass softmax boosting: per round, the class axis of the
    grad/hess tensors is vmapped into n_classes parallel tree growths
    (XGBoost multi:softprob shape). Returns trees with leading
    [n_rounds, n_classes] axes; margins = sum over rounds per class.
    """
    Y = jax.nn.one_hot(y.astype(jnp.int32), n_classes)

    def one(carry, k):
        margin, = carry                       # [N, C]
        ks, km, kf = jax.random.split(k, 3)
        p = jax.nn.softmax(margin, axis=1)
        g = w[:, None] * (p - Y)              # [N, C]
        h = jnp.maximum(w[:, None] * p * (1.0 - p), EPS)
        if subsample < 1.0:
            rw = (jax.random.uniform(ks, y.shape) < subsample
                  ).astype(jnp.float32)[:, None]
            g, h = g * rw, h * rw
        fm = (_feature_mask(km, 1, Xb.shape[1], feature_frac)[0]
              if feature_frac < 1.0 else None)  # colsample_bytree

        def per_class(gc, hc, kc):
            # allow_pallas=False: this grow sits under the class vmap and
            # pallas_call must not be batched
            return grow_tree(Xb, gc[:, None], hc, kc, depth=depth,
                             n_bins=n_bins, reg_lambda=reg_lambda,
                             min_child_weight=min_child_weight, gamma=gamma,
                             leaf_mode="newton", feature_mask=fm,
                             learning_rate=learning_rate,
                             normalize_gain=False, allow_pallas=False,
                             alpha=alpha, max_delta_step=max_delta_step,
                             level_feature_frac=colsample_bylevel,
                             feature_mask_count=(
                                 max(1, int(round(
                                     feature_frac * Xb.shape[1])))
                                 if feature_frac < 1.0 else None))
        trees = jax.vmap(per_class, in_axes=(1, 1, 0))(
            g, h, jax.random.split(kf, n_classes))
        step = jax.vmap(lambda t: predict_bins(t, Xb, depth)[:, 0])(trees)
        return (margin + step.T,), trees

    init = jnp.zeros((y.shape[0], n_classes), jnp.float32)
    (_,), trees = jax.lax.scan(one, (init,), jax.random.split(key, n_rounds))
    return trees


def _register_pallas_consumers():
    """Tree-fit executables bake the pallas choice in at trace time; the
    kill switch must be able to clear them (set_pallas_enabled)."""
    from . import pallas_hist
    for fn in (grow_tree, fit_forest, fit_gbt, fit_gbt_folds,
               fit_forest_lanes, fit_gbt_softmax, _ShardedCacheClearer()):
        pallas_hist.register_cache_consumer(fn)


_register_pallas_consumers()


# -- host-side (numpy) ensemble traversal for serving -----------------------

def np_predict_ensemble(feat: np.ndarray, thresh_val: np.ndarray,
                        leaf: np.ndarray, X: np.ndarray,
                        depth: int,
                        miss: Optional[np.ndarray] = None) -> np.ndarray:
    """Vectorized numpy traversal on RAW feature values.

    feat/thresh_val: [T, 2^depth - 1] (thresh in raw units; present values
    go right iff x >= thresh, +inf = all-left, -inf = all-present-right);
    miss: [T, 2^depth - 1] 0/1 learned default direction for NaN rows
    (None = all default-left, the pre-miss serialization); leaf:
    [T, 2^depth, K]; X: [N, F]. Returns per-tree payload sum [N, K] — this
    is the Spark-free "local scoring" path (reference
    local/.../OpWorkflowModelLocal.scala:93), no JAX required.

    Batches route through the native row-major traversal when the C++
    library is loaded (single-row calls stay in numpy: the ctypes
    call overhead exceeds one row's traversal).
    """
    N = X.shape[0]
    if N > 1:
        from . import trees_host as TH
        miss_arr = (np.zeros_like(np.asarray(feat, np.int32))
                    if miss is None else miss)
        out = TH.predict_raw_native(feat, thresh_val, leaf,
                                    np.asarray(X, np.float32), depth,
                                    miss_arr)
        if out is not None:
            return out
    T = feat.shape[0]
    rel = np.zeros((N, T), np.int64)
    t_idx = np.arange(T)[None, :]
    for d in range(depth):
        gi = (1 << d) - 1 + rel
        f = feat[t_idx, gi]                    # [N, T]
        tv = thresh_val[t_idx, gi]
        x = X[np.arange(N)[:, None], f]
        nan = np.isnan(x)
        right = ~nan & (x >= tv)               # NaN compares False
        if miss is not None:
            right |= nan & (miss[t_idx, gi] > 0)
        rel = 2 * rel + right
    return leaf[t_idx, rel].sum(axis=1)        # [N, K]
