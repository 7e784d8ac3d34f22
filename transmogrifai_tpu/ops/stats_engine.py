"""One-pass sharded statistics engine for the pre-model statistics layer.

The SanityChecker (automl/preparators.py), RawFeatureFilter
(filters/raw_feature_filter.py) and RecordInsightsCorr (insights/corr.py)
each used to make several separate device passes over the full feature
matrix — per-column moments, label correlations, the feature-feature
Pearson matrix, label moments, plus one device round-trip per categorical
indicator group and one un-jitted histogram program per numeric column.
All of those reductions are bandwidth-bound: the roofline is ONE read of X
(arxiv 2008.01040's learned TPU performance model puts fused reductions at
the HBM roof), and the DrJAX decomposition (arxiv 2403.07128) — sharded
map + psum-merged sufficient statistics — is exactly the shape this module
implements.

One blocked/jitted scan over row tiles accumulates EVERY sufficient
statistic in a single read of X:

- per-column count / mean / M2 / min / max / nnz via an exact
  Welford-style tile merge (two-pass moments WITHIN the in-registers
  tile, Chan's parallel merge ACROSS tiles — no catastrophic f32
  cancellation for large-mean columns, unlike raw E[x^2]-mean^2);
- label cross co-moments (the `X^T y` slot) and per-column-masked label
  moments with the same tile merge, giving pairwise-complete Pearson
  correlations with the label;
- the capped feature-feature Gram for the full Pearson matrix,
  shift-centered at the first tile's column means so the f32 matmul
  accumulators stay cancellation-safe;
- ALL categorical contingency tables as one matmul per tile against an
  on-device one-hot label (built per tile from the distinct-value vector;
  the [n, C] one-hot never exists in HBM), replacing the per-group host
  loop;
- numeric histograms for every column at once via the flattened-ids
  binning trick of ops/pallas_hist._hist_segment_jnp (column-offset
  segment ids, one segment-sum per tile);
- whole-label moments (count/mean/variance/min/max).

Three drivers mirror the PR 3 GLM sweep architecture:

- `fused_stats` — single jitted program for HBM-resident data;
- `fused_stats_sharded` — the SAME core under shard_map over the
  data-parallel mesh `batch` axis (parallel/mesh.build_shard_map), with
  an exact Chan merge ACROSS shards done as two tiny psum rounds, so
  stats run where sweep data already lives, no host gather (the
  psum-reaches-every-replicated-output contract is tmoglint-SHD001-
  checked — it cannot fail visibly on a 1-device-per-shard CI mesh);
- `stream_stats` — the double-buffered tileplane driver
  (parallel/tileplane.py) for datasets larger than HBM: a producer
  thread device_puts tile k+1 while the device Chan-merges tile k into
  a DEVICE-resident carry (fetched once at the end); accepts a
  `tileplane.RowSource` (Avro/CSV reader adapter) so X need never
  exist as one array, and a `mesh` for the shard_map tile lane.
  TMOG_TILEPLANE=0 restores the legacy synchronous loop with per-tile
  host f64 merge.

`run_stats` is the routed front door: it picks a driver, times the pass
with a block_until_ready fence, and reports a `stats_pass` kernel span +
StatsPass telemetry (utils/metrics) with analytic bytes so the "one pass"
claim is runtime-verifiable from any traced run.

The legacy multi-pass path (ops/stats called per statistic) is kept by
the consumers as a kill switch: TMOG_STATS_FUSED=0.
"""
from __future__ import annotations

import functools
import os
import time
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import stats as S
from ..parallel.mesh import BATCH_AXIS, build_shard_map, shard_vary
from ..utils.env import env_int, env_on

EPS = 1e-12

# Rows per scan tile: bounds the [c, d] f32 tile transient at ~32MB plus
# the per-tile one-hot/segment intermediates. Matches the glm_sweep tile
# philosophy — the scan carry ([d]-vectors + the optional [d, d] Gram
# accumulators) is microscopic next to the tile itself.
_TILE_BUDGET_BYTES = 32 << 20

# Widest matrix for which the full d x d Pearson Gram is accumulated.
# Past this, the three [d, d] f32 accumulators and the per-tile matmuls
# stop being "free riders" on the bandwidth-bound pass; the consumers
# (SanityChecker max_corr_matrix_columns, default 256) cap well below.
GRAM_MAX_D = 1024


def stats_row_block(d: int, n: int) -> int:
    c = _TILE_BUDGET_BYTES // max(4 * d, 1)
    c = max(min(c, 1 << 16), 1024)
    return max(min(c, n), 1)


def fused_enabled() -> bool:
    """THE kill switch for the one-pass engine (TMOG_STATS_FUSED=0
    restores the legacy multi-pass statistics in every consumer)."""
    return env_on("TMOG_STATS_FUSED")


def stream_threshold_bytes() -> int:
    """X size above which run_stats routes through the streamed driver
    (default 4GB — roughly the point where a second full-matrix resident
    would pressure a single device's HBM)."""
    return int(os.environ.get("TMOG_STATS_STREAM_MB", "4096")) << 20


def stream_tile_rows_default() -> int:
    """Rows per streamed statistics tile: TMOG_STATS_TILE_ROWS, default
    2^18 (64 MiB of float32 at 64 columns). One fixed tile shape, so the
    tile step compiles once a pass."""
    return env_int("TMOG_STATS_TILE_ROWS", 1 << 18)


def stats_pass_bytes(n: int, d: int, *, itemsize: int = 4,
                     y2d: bool = False, weighted: bool = False) -> int:
    """Analytic HBM bytes for ONE engine pass: a single read of X plus the
    label (a second [n, d] plane in rank/2-D-label mode) and the optional
    weight vector. Output vectors ([d]-shaped moments, the capped Gram)
    are noise at any n worth measuring. Analytic by construction — the
    whole pass is one jitted program, so per-invocation byte counters
    cannot exist inside it (same contract as pallas_hist traffic models).
    """
    b = n * d * itemsize
    b += n * d * 4 if y2d else n * 4
    if weighted:
        b += n * 4
    return int(b)


def legacy_pass_count(*, corr_matrix: bool, n_groups: int = 0,
                      spearman: bool = False) -> int:
    """How many device passes over X the pre-engine SanityChecker path
    made for the same statistics: col_stats + corr-with-label (2 passes
    through pearson/spearman internals) + the optional pearson matrix
    (col_stats + matmul = 2) + one contingency matmul per categorical
    group. Used by bench --stats-roofline and docs/performance.md so the
    before/after accounting has one source."""
    passes = 1 + (2 if spearman else 1)
    if corr_matrix:
        passes += 2
    return passes + n_groups


# -- results ----------------------------------------------------------------

class FusedStats(NamedTuple):
    """Host-side (numpy) results of one engine pass.

    Per-column arrays are [d]; `m2` is the raw centered second moment
    (population variance = m2 / count — RecordInsightsCorr needs the
    population convention, ColStats the unbiased one). `corr_matrix`,
    `contingency` ([d, C] vs the distinct label values, columns
    optionally clipped to 1 for multi-hot groups) and `hist`
    ([d, bins + 1]; last bin = missing mass) are None unless requested.
    """

    count: np.ndarray
    mean: np.ndarray
    variance: np.ndarray
    m2: np.ndarray
    min: np.ndarray
    max: np.ndarray
    num_non_zeros: np.ndarray
    fill_rate: np.ndarray
    corr_label: np.ndarray
    wsum: float
    label_count: float
    label_mean: float
    label_variance: float
    label_min: float
    label_max: float
    corr_matrix: Optional[np.ndarray] = None
    contingency: Optional[np.ndarray] = None
    hist: Optional[np.ndarray] = None


class _State(NamedTuple):
    """Mergeable sufficient-statistics state (device or host arrays).

    Moment fields are Chan-mergeable (count/mean/M2 + co-moments); the
    rest merge by elementwise min/max/sum. Optional members are None when
    the corresponding statistic was not requested (the pytree structure
    is fixed per trace by the driver's static flags)."""

    wsum: Any
    cnt: Any          # [d] valid weighted count
    mean: Any         # [d]
    m2: Any           # [d]
    cy: Any           # [d] co-moment of column with label (column-masked)
    ymean: Any        # [d] label mean over column-valid rows
    ym2: Any          # [d]
    minv: Any         # [d]
    maxv: Any         # [d]
    nnz: Any          # [d]
    ycnt: Any         # scalar: label moments over finite-label rows
    lmean: Any
    lm2: Any
    lmin: Any
    lmax: Any
    gzz: Any = None   # [d, d] shift-centered Gram accumulators
    gzv: Any = None
    gvv: Any = None
    cont: Any = None  # [d, C]
    hist: Any = None  # [d * (bins + 1)] flat


def _chan_merge(nA, mA, m2A, nB, mB, m2B):
    """Chan/Welford parallel merge of weighted (count, mean, M2)."""
    n = nA + nB
    safe = jnp.maximum(n, EPS)
    delta = mB - mA
    mean = mA + delta * (nB / safe)
    m2 = m2A + m2B + delta * delta * (nA * nB / safe)
    return n, mean, m2


def _tile_state(xb, yb, wb, shift, distinct, clip, lo, hi, *, bins: int,
                corr_matrix: bool, y2d: bool, big: float) -> _State:
    """Exact two-pass moments of ONE tile (the tile lives in registers /
    VMEM — the second 'pass' re-reads no HBM), shaped as a _State ready
    for the Chan merge."""
    finite = jnp.isfinite(xb)
    v01 = finite.astype(jnp.float32)
    v = v01 * wb[:, None]                                  # [c, d]
    xz = jnp.where(finite, xb, 0.0).astype(jnp.float32)
    cnt = v.sum(0)
    safe = jnp.maximum(cnt, EPS)
    mean = (xz * v).sum(0) / safe
    dx = xz - mean[None, :]
    m2 = (dx * dx * v).sum(0)

    yz2 = yb if y2d else yb[:, None]
    yz2 = jnp.where(jnp.isfinite(yz2), yz2, 0.0).astype(jnp.float32)
    ymean = (yz2 * v).sum(0) / safe
    dy = yz2 - ymean[None, :]
    ym2 = (dy * dy * v).sum(0)
    cy = (dx * dy * v).sum(0)

    minv = jnp.where(v > 0, xz, big).min(0)
    maxv = jnp.where(v > 0, xz, -big).max(0)
    nnz = ((xz != 0) & (v > 0)).astype(jnp.float32).sum(0)
    wsum = wb.sum()

    if y2d:
        ycnt = jnp.asarray(0.0, jnp.float32)
        lmean = jnp.asarray(0.0, jnp.float32)
        lm2 = jnp.asarray(0.0, jnp.float32)
        lmin = jnp.asarray(big, jnp.float32)
        lmax = jnp.asarray(-big, jnp.float32)
    else:
        lv = jnp.isfinite(yb).astype(jnp.float32) * wb
        yz = jnp.where(jnp.isfinite(yb), yb, 0.0).astype(jnp.float32)
        ycnt = lv.sum()
        lsafe = jnp.maximum(ycnt, EPS)
        lmean = (yz * lv).sum() / lsafe
        lm2 = (((yz - lmean) ** 2) * lv).sum()
        lmin = jnp.where(lv > 0, yz, big).min()
        lmax = jnp.where(lv > 0, yz, -big).max()

    gzz = gzv = gvv = None
    if corr_matrix:
        z = (xz - shift[None, :]) * v01                    # [c, d]
        zw = z * wb[:, None]
        vw = v01 * wb[:, None]
        gzz = jnp.matmul(zw.T, z, preferred_element_type=jnp.float32)
        gzv = jnp.matmul(zw.T, v01, preferred_element_type=jnp.float32)
        gvv = jnp.matmul(vw.T, v01, preferred_element_type=jnp.float32)

    cont = None
    if distinct is not None:
        yoh = (yb[:, None] == distinct[None, :]).astype(jnp.float32)
        xc = xz
        if clip is not None:
            xc = jnp.where(clip[None, :], jnp.minimum(xz, 1.0), xz)
        cont = jnp.matmul((xc * v).T, yoh,
                          preferred_element_type=jnp.float32)

    hist = None
    if bins > 0:
        d = xb.shape[1]
        # the shared binning rule (ops/stats.hist_bin_ids) with the
        # engine's finite-only validity mask — same clip semantics as the
        # standalone histogram_batched fallback by construction
        ids = S.hist_bin_ids(xb, lo, hi, bins, finite)
        wt = jnp.broadcast_to(wb[:, None], xb.shape)
        hist = jax.ops.segment_sum(wt.reshape(-1), ids.reshape(-1),
                                   num_segments=d * (bins + 1))

    return _State(wsum=wsum, cnt=cnt, mean=mean, m2=m2, cy=cy, ymean=ymean,
                  ym2=ym2, minv=minv, maxv=maxv, nnz=nnz, ycnt=ycnt,
                  lmean=lmean, lm2=lm2, lmin=lmin, lmax=lmax, gzz=gzz,
                  gzv=gzv, gvv=gvv, cont=cont, hist=hist)


def _merge_states(a: _State, b: _State) -> _State:
    """Chan-merge two states (jnp; works on traced or concrete arrays)."""
    cnt, mean, m2 = _chan_merge(a.cnt, a.mean, a.m2, b.cnt, b.mean, b.m2)
    safe = jnp.maximum(cnt, EPS)
    dxm = b.mean - a.mean
    dym = b.ymean - a.ymean
    cross = a.cnt * b.cnt / safe
    cy = a.cy + b.cy + dxm * dym * cross
    ymean = a.ymean + dym * (b.cnt / safe)
    ym2 = a.ym2 + b.ym2 + dym * dym * cross
    ycnt, lmean, lm2 = _chan_merge(a.ycnt, a.lmean, a.lm2,
                                   b.ycnt, b.lmean, b.lm2)
    return _State(
        wsum=a.wsum + b.wsum, cnt=cnt, mean=mean, m2=m2, cy=cy,
        ymean=ymean, ym2=ym2,
        minv=jnp.minimum(a.minv, b.minv), maxv=jnp.maximum(a.maxv, b.maxv),
        nnz=a.nnz + b.nnz, ycnt=ycnt, lmean=lmean, lm2=lm2,
        lmin=jnp.minimum(a.lmin, b.lmin), lmax=jnp.maximum(a.lmax, b.lmax),
        gzz=None if a.gzz is None else a.gzz + b.gzz,
        gzv=None if a.gzv is None else a.gzv + b.gzv,
        gvv=None if a.gvv is None else a.gvv + b.gvv,
        cont=None if a.cont is None else a.cont + b.cont,
        hist=None if a.hist is None else a.hist + b.hist)


def _zero_state(d: int, *, corr_matrix: bool, n_classes: int, bins: int,
                big: float) -> _State:
    f32 = jnp.float32
    return _State(
        wsum=jnp.asarray(0.0, f32), cnt=jnp.zeros(d, f32),
        mean=jnp.zeros(d, f32), m2=jnp.zeros(d, f32), cy=jnp.zeros(d, f32),
        ymean=jnp.zeros(d, f32), ym2=jnp.zeros(d, f32),
        minv=jnp.full(d, big, f32), maxv=jnp.full(d, -big, f32),
        nnz=jnp.zeros(d, f32), ycnt=jnp.asarray(0.0, f32),
        lmean=jnp.asarray(0.0, f32), lm2=jnp.asarray(0.0, f32),
        lmin=jnp.asarray(big, f32), lmax=jnp.asarray(-big, f32),
        gzz=jnp.zeros((d, d), f32) if corr_matrix else None,
        gzv=jnp.zeros((d, d), f32) if corr_matrix else None,
        gvv=jnp.zeros((d, d), f32) if corr_matrix else None,
        cont=jnp.zeros((d, n_classes), f32) if n_classes else None,
        hist=jnp.zeros(d * (bins + 1), f32) if bins else None)


def _first_tile_shift(X, w, c: int, allreduce) -> jax.Array:
    """Per-column masked mean of the first row tile — the common Gram
    shift. Under shard_map the psum makes it identical on every shard
    (accumulators centered at different shifts could not be psum-merged).
    The first tile is read twice (once here, once in the scan): 1/n_tiles
    of a pass, ignored by the traffic model."""
    xb = X[:c]
    finite = jnp.isfinite(xb)
    v = finite.astype(jnp.float32) * w[:c, None]
    xz = jnp.where(finite, xb, 0.0).astype(jnp.float32)
    s = allreduce((xz * v).sum(0))
    n = allreduce(v.sum(0))
    return jnp.where(n > 0, s / jnp.maximum(n, EPS), 0.0)


# -- finalize (host, f64) ----------------------------------------------------

def _finalize(st, shift, *, bins: int) -> FusedStats:
    """Moment state -> FusedStats. Host-side numpy: the state is [d]/[d,d]
    shaped — microscopic — and f64 here costs nothing while keeping the
    tiny final divisions exact. Mirrors ops/stats formulas exactly
    (unbiased variance clamp, EPS-guarded correlation denominators)."""
    # host finalize on fetched [d]-vectors; f64 never touches the device
    # program
    f8 = np.float64  # tmoglint: disable=TPU003  host-only precision
    cnt = np.asarray(st.cnt, f8)
    mean = np.asarray(st.mean, f8)
    m2 = np.asarray(st.m2, f8)
    cy = np.asarray(st.cy, f8)
    ym2 = np.asarray(st.ym2, f8)
    wsum = float(np.asarray(st.wsum))
    variance = np.maximum(m2 / np.maximum(cnt - 1.0, 1.0), 0.0)
    corr = cy / np.sqrt(np.maximum(m2 * ym2, EPS * EPS))
    fill = cnt / max(wsum, EPS)
    ycnt = float(np.asarray(st.ycnt))

    corr_matrix = None
    if st.gzz is not None:
        gzz = np.asarray(st.gzz, f8)
        gzv = np.asarray(st.gzv, f8)
        gvv = np.asarray(st.gvv, f8)
        a = mean - np.asarray(shift, f8)
        cov = gzz - gzv * a[None, :] - (gzv * a[None, :]).T \
            + np.outer(a, a) * gvv
        sd = np.sqrt(np.maximum(np.diag(cov), EPS))
        corr_matrix = cov / (sd[:, None] * sd[None, :])

    hist = None
    if st.hist is not None:
        hist = np.asarray(st.hist, f8).reshape(-1, bins + 1)

    return FusedStats(
        count=cnt, mean=mean, variance=variance, m2=m2,
        min=np.asarray(st.minv, f8), max=np.asarray(st.maxv, f8),
        num_non_zeros=np.asarray(st.nnz, f8), fill_rate=fill,
        corr_label=corr, wsum=wsum, label_count=ycnt,
        label_mean=float(np.asarray(st.lmean)),
        label_variance=float(max(np.asarray(st.lm2)
                                 / max(ycnt - 1.0, 1.0), 0.0)),
        label_min=float(np.asarray(st.lmin)),
        label_max=float(np.asarray(st.lmax)),
        corr_matrix=corr_matrix,
        contingency=(None if st.cont is None
                     else np.asarray(st.cont, f8)),
        hist=hist)


# -- drivers -----------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("bins", "corr_matrix"))
def _fused_stats_jit(X, y, w, distinct, clip, lo, hi, *, bins: int,
                     corr_matrix: bool):
    """Single-program driver: one scan, returns (state, shift)."""
    n, d = X.shape
    shift = jnp.zeros(d, jnp.float32)
    if corr_matrix:
        shift = _first_tile_shift(X, w, min(stats_row_block(d, n), n),
                                  lambda v: v)
    st = _scan_state_single(X, y, w, distinct, clip, lo, hi, bins=bins,
                            corr_matrix=corr_matrix, shift=shift)
    return st, shift


def _scan_state_single(X, y, w, distinct, clip, lo, hi, *, bins,
                       corr_matrix, shift, axis_name=None):
    """Single-scan body shared by the jitted single-program and sharded
    cores (shift already resolved by the caller)."""
    n, d = X.shape
    big = float(np.finfo(np.float32).max)
    y2d = y.ndim == 2
    c = stats_row_block(d, n)
    nb = -(-n // c)
    pad = nb * c - n
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
        y = jnp.pad(y, ((0, pad), (0, 0)) if y2d else (0, pad))
        w = jnp.pad(w, (0, pad))
    Xs = X.reshape(nb, c, d)
    ys = y.reshape((nb, c, d) if y2d else (nb, c))
    ws = w.reshape(nb, c)

    def body(acc, sl):
        xb, yb, wb = sl
        st = _tile_state(xb, yb, wb, shift, distinct, clip, lo, hi,
                         bins=bins, corr_matrix=corr_matrix, y2d=y2d,
                         big=big)
        return _merge_states(acc, st), None

    acc0 = shard_vary(
        _zero_state(d, corr_matrix=corr_matrix,
                    n_classes=0 if distinct is None else distinct.shape[0],
                    bins=bins, big=big),
        axis_name)
    st, _ = jax.lax.scan(body, acc0, (Xs, ys, ws))
    if axis_name is None:
        return st

    def psum(v):
        return jax.lax.psum(v, axis_name)

    cnt_g = psum(st.cnt)
    safe = jnp.maximum(cnt_g, EPS)
    mean_g = psum(st.cnt * st.mean) / safe
    ymean_g = psum(st.cnt * st.ymean) / safe
    m2_g = psum(st.m2 + st.cnt * (st.mean - mean_g) ** 2)
    ym2_g = psum(st.ym2 + st.cnt * (st.ymean - ymean_g) ** 2)
    cy_g = psum(st.cy + st.cnt * (st.mean - mean_g) * (st.ymean - ymean_g))
    ycnt_g = psum(st.ycnt)
    lsafe = jnp.maximum(ycnt_g, EPS)
    lmean_g = psum(st.ycnt * st.lmean) / lsafe
    lm2_g = psum(st.lm2 + st.ycnt * (st.lmean - lmean_g) ** 2)
    return _State(
        wsum=psum(st.wsum), cnt=cnt_g, mean=mean_g, m2=m2_g, cy=cy_g,
        ymean=ymean_g, ym2=ym2_g,
        minv=jax.lax.pmin(st.minv, axis_name),
        maxv=jax.lax.pmax(st.maxv, axis_name),
        nnz=psum(st.nnz), ycnt=ycnt_g, lmean=lmean_g, lm2=lm2_g,
        lmin=jax.lax.pmin(st.lmin, axis_name),
        lmax=jax.lax.pmax(st.lmax, axis_name),
        gzz=None if st.gzz is None else psum(st.gzz),
        gzv=None if st.gzv is None else psum(st.gzv),
        gvv=None if st.gvv is None else psum(st.gvv),
        cont=None if st.cont is None else psum(st.cont),
        hist=None if st.hist is None else psum(st.hist))


@functools.lru_cache(maxsize=None)
def _sharded_stats_fn(mesh, bins: int, corr_matrix: bool,
                      have_distinct: bool, have_clip: bool,
                      have_hist: bool, y2d: bool):
    """shard_map-wrapped core for one (mesh, feature-flag) combination.

    The optional-statistics flags select the exact positional signature so
    shard_map's in_specs always match the arg pytree (None args do not
    thread through shard_map specs)."""
    from jax.sharding import PartitionSpec as P

    def core(X, y, w, *extras):
        it = iter(extras)
        distinct = next(it) if have_distinct else None
        clip = next(it) if have_clip else None
        lo = next(it) if have_hist else None
        hi = next(it) if have_hist else None
        shift = jnp.zeros(X.shape[1], jnp.float32)
        if corr_matrix:
            shift = _first_tile_shift(
                X, w, min(stats_row_block(X.shape[1], X.shape[0]),
                          X.shape[0]),
                lambda v: jax.lax.psum(v, BATCH_AXIS))
        st = _scan_state_single(X, y, w, distinct, clip, lo, hi,
                                bins=bins, corr_matrix=corr_matrix,
                                shift=shift, axis_name=BATCH_AXIS)
        return st, shift

    n_extras = int(have_distinct) + int(have_clip) + 2 * int(have_hist)
    in_specs = (P(BATCH_AXIS, None),
                P(BATCH_AXIS, None) if y2d else P(BATCH_AXIS),
                P(BATCH_AXIS)) + (P(None),) * n_extras
    sm = build_shard_map(core, mesh, in_specs=in_specs, out_specs=P())
    return jax.jit(sm)


def _as_f32(x):
    a = jnp.asarray(x)
    if a.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        a = a.astype(jnp.float32)
    return a


def fused_stats(X, y, w=None, *, distinct=None, clip=None, lo=None,
                hi=None, bins: int = 0,
                corr_matrix: bool = False) -> Tuple[_State, jax.Array]:
    """One-pass sufficient statistics as a SINGLE jitted program.

    X [n, d] (NaN = missing); y [n] label or [n, d] per-column label
    (rank mode); w [n] row weights (None = 1). distinct [C] enables the
    batched contingency accumulation (clip [d] bool marks multi-hot
    columns counted at-most-once); (lo, hi, bins) enables fused
    histograms. Returns the raw (state, shift) pair; `run_stats` is the
    finalizing front door."""
    X = _as_f32(X)
    y = _as_f32(y)
    n, d = X.shape
    if corr_matrix and d > GRAM_MAX_D:
        raise ValueError(f"corr_matrix capped at {GRAM_MAX_D} columns "
                         f"(got {d}); the consumers cap far below")
    w = jnp.ones(n, jnp.float32) if w is None else _as_f32(w)
    distinct = None if distinct is None else _as_f32(distinct)
    clip = None if clip is None else jnp.asarray(clip, bool)
    lo = None if lo is None else _as_f32(lo)
    hi = None if hi is None else _as_f32(hi)
    if (lo is None) != (bins == 0):
        raise ValueError("histograms need both bins>0 and lo/hi ranges")
    return _fused_stats_jit(X, y, w, distinct, clip, lo, hi,
                            bins=int(bins), corr_matrix=bool(corr_matrix))


def fused_stats_sharded(mesh, X, y, w=None, *, distinct=None, clip=None,
                        lo=None, hi=None, bins: int = 0,
                        corr_matrix: bool = False):
    """The SAME one-pass core under shard_map over the mesh `batch` axis.

    X/y/w may be host arrays (device_put with row padding + zero-weight
    pad mask happens here) or pre-sharded jax arrays whose rows already
    divide the batch axis — the no-host-gather path when the matrix
    already lives on the mesh. Accumulator merges psum over ICI/DCN; the
    tiny finalize runs replicated.

    On a MULTI-PROCESS mesh X/y/w are THIS PROCESS's host-local row
    block (every process calls with its own rows — SPMD); the blocks
    land as the process's `batch`-axis stripe of one global array
    (multihost.host_local_block) and the psum merges become genuine
    cross-host collectives. The set of (row, weight) pairs equals the
    single-process call's, so the sufficient statistics match within
    float tolerance (docs/performance.md)."""
    from ..parallel import mesh as M

    if M.mesh_is_multiprocess(mesh):
        from ..parallel import multihost as MH

        Xl = np.asarray(X, np.float32)
        yl = np.asarray(y, np.float32)
        n, d = Xl.shape
        if corr_matrix and d > GRAM_MAX_D:
            raise ValueError(f"corr_matrix capped at {GRAM_MAX_D} columns")
        wl = np.ones(n, np.float32) if w is None else \
            np.asarray(w, np.float32)
        layout = MH.row_layout(n, mesh)       # collective (count gather)
        X = MH.host_local_block(Xl, mesh, layout)
        y = MH.host_local_block(yl, mesh, layout)
        w = MH.host_local_block(wl, mesh, layout)  # zero weight = inert pad
        extras = []
        if distinct is not None:
            extras.append(MH.replicated_global(
                np.asarray(distinct, np.float32), mesh))
        if clip is not None:
            extras.append(MH.replicated_global(np.asarray(clip, bool),
                                               mesh))
        if lo is not None:
            extras.append(MH.replicated_global(np.asarray(lo, np.float32),
                                               mesh))
            extras.append(MH.replicated_global(np.asarray(hi, np.float32),
                                               mesh))
        fn = _sharded_stats_fn(mesh, int(bins), bool(corr_matrix),
                               distinct is not None, clip is not None,
                               lo is not None, y.ndim == 2)
        return fn(X, y, w, *extras)

    X = _as_f32(X)
    y = _as_f32(y)
    n, d = X.shape
    if corr_matrix and d > GRAM_MAX_D:
        raise ValueError(f"corr_matrix capped at {GRAM_MAX_D} columns")
    w = jnp.ones(n, jnp.float32) if w is None else _as_f32(w)
    n_shards = mesh.shape[BATCH_AXIS]
    if n % n_shards:
        pad = n_shards - n % n_shards
        X = jnp.pad(X, ((0, pad), (0, 0)))
        y = jnp.pad(y, ((0, pad), (0, 0)) if y.ndim == 2 else (0, pad))
        w = jnp.pad(w, (0, pad))
    X = jax.device_put(X, M.batch_sharding(mesh, ndim=2))
    y = jax.device_put(y, M.batch_sharding(mesh, ndim=y.ndim))
    w = jax.device_put(w, M.batch_sharding(mesh, ndim=1))
    extras = []
    if distinct is not None:
        extras.append(jax.device_put(_as_f32(distinct), M.replicated(mesh)))
    if clip is not None:
        extras.append(jax.device_put(jnp.asarray(clip, bool),
                                     M.replicated(mesh)))
    if lo is not None:
        extras.append(jax.device_put(_as_f32(lo), M.replicated(mesh)))
        extras.append(jax.device_put(_as_f32(hi), M.replicated(mesh)))
    fn = _sharded_stats_fn(mesh, int(bins), bool(corr_matrix),
                           distinct is not None, clip is not None,
                           lo is not None, y.ndim == 2)
    return fn(X, y, w, *extras)


@functools.partial(jax.jit, static_argnames=("bins", "corr_matrix"))
def _stream_tile_jit(X, y, w, shift, distinct, clip, lo, hi, *, bins: int,
                     corr_matrix: bool):
    """One streamed tile's state (tiles arrive padded to a fixed row
    count with w=0, so every tile shares ONE executable)."""
    return _scan_state_single(X, y, w, distinct, clip, lo, hi, bins=bins,
                              corr_matrix=corr_matrix, shift=shift)


@jax.jit
def _tile_shift_jit(X, w):
    """Gram shift from the FIRST tile, on device: the per-column masked
    mean of the tile that is already resident for the pass's first step.
    Replaces the old host pre-pass over X[:c] (which read the first
    tile's rows twice — once on host, once when the loop re-sliced
    0:c)."""
    return _first_tile_shift(X, w, X.shape[0], lambda v: v)


@functools.partial(jax.jit, static_argnames=("bins", "corr_matrix"),
                   donate_argnums=(0,))
def _tileplane_step_jit(carry, X, y, w, distinct, clip, lo, hi, *,
                        bins: int, corr_matrix: bool):
    """Tileplane step: fold one fixed-shape tile into the DEVICE-resident
    carry (state, shift). The carry is DONATED — the output state aliases
    the input buffers, so a whole streamed pass updates one state
    in place and fetches it ONCE at the end (the legacy loop fetched and
    host-merged after every tile). Tile buffers are not donate-marked:
    they have no same-shaped output to alias (XLA would warn and copy);
    their last reference dies at dispatch, which frees them just as
    early."""
    st, shift = carry
    ts = _scan_state_single(X, y, w, distinct, clip, lo, hi, bins=bins,
                            corr_matrix=corr_matrix, shift=shift)
    return _merge_states(st, ts), shift


@functools.lru_cache(maxsize=None)
def _tileplane_sharded_step(mesh, bins: int, corr_matrix: bool,
                            have_distinct: bool, have_clip: bool,
                            have_hist: bool, y2d: bool):
    """The SAME tile-merge step under shard_map over the mesh batch axis:
    each shard scans its rows of the tile, a psum round Chan-merges
    across shards, and the replicated result merges into the replicated
    carry — the tileplane's optional mesh lane."""
    from jax.sharding import PartitionSpec as P

    def core(carry, X, y, w, *extras):
        it = iter(extras)
        distinct = next(it) if have_distinct else None
        clip = next(it) if have_clip else None
        lo = next(it) if have_hist else None
        hi = next(it) if have_hist else None
        st, shift = carry
        ts = _scan_state_single(X, y, w, distinct, clip, lo, hi,
                                bins=bins, corr_matrix=corr_matrix,
                                shift=shift, axis_name=BATCH_AXIS)
        return _merge_states(st, ts), shift

    n_extras = int(have_distinct) + int(have_clip) + 2 * int(have_hist)
    in_specs = (P(), P(BATCH_AXIS, None),
                P(BATCH_AXIS, None) if y2d else P(BATCH_AXIS),
                P(BATCH_AXIS)) + (P(),) * n_extras
    sm = build_shard_map(core, mesh, in_specs=in_specs, out_specs=P())
    # same donation rule as the single-device step: the replicated carry
    # aliases its output, so the [d, d] Gram accumulators update in place.
    # EXCEPT on a multi-process mesh: donating buffers into a program
    # whose psums run gloo cross-host collectives corrupts the CPU
    # client's heap on this jaxlib (observed: "corrupted double-linked
    # list" aborts on the second donated step) — the pod path keeps the
    # carry copy instead
    from ..parallel.mesh import mesh_is_multiprocess
    donate = () if mesh_is_multiprocess(mesh) else (0,)
    return jax.jit(sm, donate_argnums=donate)


@functools.lru_cache(maxsize=None)
def _tile_shift_sharded(mesh):
    from jax.sharding import PartitionSpec as P

    def core(X, w):
        return _first_tile_shift(X, w, X.shape[0],
                                 lambda v: jax.lax.psum(v, BATCH_AXIS))

    sm = build_shard_map(core, mesh, in_specs=(P(BATCH_AXIS, None),
                                               P(BATCH_AXIS)),
                         out_specs=P())
    return jax.jit(sm)


def _merge_states_host(a, b):
    """Host-side f64 Chan merge of two fetched states (streamed driver).
    Same arithmetic as _merge_states; numpy so a multi-hour stream never
    dispatches merge programs."""
    # host-side streamed-merge accumulators; device tiles stay f32
    f8 = np.float64  # tmoglint: disable=TPU003  host-only precision

    def arr(x):
        return np.asarray(x, f8)

    nA, nB = arr(a.cnt), arr(b.cnt)
    n = nA + nB
    safe = np.maximum(n, EPS)
    dxm = arr(b.mean) - arr(a.mean)
    dym = arr(b.ymean) - arr(a.ymean)
    cross = nA * nB / safe
    mean = arr(a.mean) + dxm * (nB / safe)
    m2 = arr(a.m2) + arr(b.m2) + dxm * dxm * cross
    cy = arr(a.cy) + arr(b.cy) + dxm * dym * cross
    ymean = arr(a.ymean) + dym * (nB / safe)
    ym2 = arr(a.ym2) + arr(b.ym2) + dym * dym * cross
    lA, lB = float(arr(a.ycnt)), float(arr(b.ycnt))
    ln = lA + lB
    lsafe = max(ln, EPS)
    ldm = float(arr(b.lmean)) - float(arr(a.lmean))
    lmean = float(arr(a.lmean)) + ldm * lB / lsafe
    lm2 = float(arr(a.lm2)) + float(arr(b.lm2)) + ldm * ldm * lA * lB / lsafe
    opt = {k: (None if getattr(a, k) is None
               else arr(getattr(a, k)) + arr(getattr(b, k)))
           for k in ("gzz", "gzv", "gvv", "cont", "hist")}
    return _State(
        wsum=float(arr(a.wsum)) + float(arr(b.wsum)), cnt=n, mean=mean,
        m2=m2, cy=cy, ymean=ymean, ym2=ym2,
        minv=np.minimum(arr(a.minv), arr(b.minv)),
        maxv=np.maximum(arr(a.maxv), arr(b.maxv)),
        nnz=arr(a.nnz) + arr(b.nnz), ycnt=ln, lmean=lmean, lm2=lm2,
        lmin=min(float(arr(a.lmin)), float(arr(b.lmin))),
        lmax=max(float(arr(a.lmax)), float(arr(b.lmax))), **opt)


def _fetch_state(st: _State) -> _State:
    return _State(*[None if x is None else np.asarray(x) for x in st])


# last streamed pass's pipeline stats (rows/tiles/peak-buffer): run_stats
# reads them for telemetry when the input was a RowSource whose row count
# is unknown before the pass
_last_stream_stats = None


def _stream_source(X, y, w, tile_rows: Optional[int]):
    """(source, tile_rows, d_probe) for the streamed driver. X may be a
    tileplane.RowSource yielding (x, y, w) chunks (y/w args must be None
    then) or a host array with companion y/w arrays."""
    from ..parallel import tileplane as TP

    if isinstance(X, TP.RowSource):
        if y is not None or w is not None:
            raise ValueError("pass y/w inside the RowSource chunks")
        x0 = X.peek()[0]
        d = int(x0.shape[1])
        c = int(tile_rows) if tile_rows else TP.tile_rows_for(4 * d,
                                                              X.n_rows)
        return X, c, d
    X = np.asarray(X)
    y = np.asarray(y)
    n, d = X.shape
    w_full = np.ones(n, np.float32) if w is None else \
        np.asarray(w, np.float32)
    c = int(tile_rows or min(stream_tile_rows_default(), max(n, 1)))
    return TP.ArraySource(X, y, w_full, chunk_rows=c), c, d


def stream_stats(X, y=None, w=None, *, tile_rows: Optional[int] = None,
                 distinct=None, clip=None, lo=None, hi=None, bins: int = 0,
                 corr_matrix: bool = False, mesh=None,
                 prefetch: Optional[int] = None):
    """Streamed row-tile driver for data larger than HBM.

    X may be a host array (with y/w arrays) or a `tileplane.RowSource`
    whose chunks yield (x, y, w) — e.g. the Avro/CSV reader adapter —
    so the matrix never materializes anywhere. Tiles flow through ONE
    fixed-shape jitted tile program via the double-buffered tileplane
    (parallel/tileplane.py): the producer thread device_puts tile k+1
    while the device merges tile k into the DEVICE-resident carry, which
    is fetched once at the end. With `mesh`, each tile is row-sharded
    over the batch axis and the tile step psum-merges across shards.
    The Gram shift comes from the first tile ON DEVICE (no second read
    of its rows). TMOG_TILEPLANE=0 restores the legacy synchronous loop
    with per-tile host f64 merge. Still exactly one read of every row of
    X per pass. `prefetch` overrides the tileplane ring depth for this
    pass (None = TMOG_TILE_PREFETCH, default 1; bit-identical at any
    depth). Returns (merged host state, shift)."""
    from ..parallel import mesh as M
    from ..parallel import tileplane as TP

    global _last_stream_stats
    source, c, d = _stream_source(X, y, w, tile_rows)
    if corr_matrix and d > GRAM_MAX_D:
        raise ValueError(f"corr_matrix capped at {GRAM_MAX_D} columns")
    distinct_j = None if distinct is None else _as_f32(distinct)
    clip_j = None if clip is None else jnp.asarray(clip, bool)
    lo_j = None if lo is None else _as_f32(lo)
    hi_j = None if hi is None else _as_f32(hi)
    bins = int(bins)
    corr_matrix = bool(corr_matrix)
    big = float(np.finfo(np.float32).max)

    if not TP.tileplane_enabled() and (mesh is None
                                       or not M.mesh_is_multiprocess(mesh)):
        # legacy synchronous loop (kill switch): per-tile dispatch ->
        # fetch -> host f64 Chan merge; same tile content as the
        # pipeline (shared assembly), zero copy/compute overlap.
        # A multi-process mesh NEVER takes this branch — its psum is a
        # pod collective every process must join, so it falls through to
        # the mesh tile path (which run_tileplane already runs
        # synchronously when the shardings span processes)
        merged = None
        shift = None
        for tile, _n_valid in TP.iter_fixed_tiles(source, c):
            xt, yt, wt = (jnp.asarray(a) for a in tile)
            if shift is None:
                shift = _tile_shift_jit(xt, wt) if corr_matrix \
                    else jnp.zeros(d, jnp.float32)
            st = _stream_tile_jit(xt, yt, wt, shift, distinct_j, clip_j,
                                  lo_j, hi_j, bins=bins,
                                  corr_matrix=corr_matrix)
            st = _fetch_state(st)
            merged = st if merged is None else \
                _merge_states_host(merged, st)
        _last_stream_stats = None
        return merged, np.asarray(shift, np.float32) if shift is not None \
            else np.zeros(d, np.float32)

    # tileplane path: device-resident carry, double-buffered H2D
    probe = source.peek()
    y2d = probe[1].ndim == 2
    shardings = None
    pc = 1
    if mesh is not None:
        n_shards = mesh.shape[M.BATCH_AXIS]
        pc = M.mesh_process_count(mesh)
        if pc > 1:
            # SPMD streaming: `source` is THIS PROCESS's stripe of the
            # row stream. The tile step's psum is a pod collective, so
            # every process must run the SAME tile count with the SAME
            # (uniform) tile shape: size tiles from the pod-uniform
            # padded per-process row count (row_layout is itself the
            # pod's one host collective), then pad the local stream so
            # uneven stripes still emit identical tile sequences.
            from ..parallel import multihost as MH

            if source.n_rows is None:
                raise ValueError("multi-host streaming needs a source "
                                 "with a known n_rows (the local stripe "
                                 "row count)")
            layout = MH.row_layout(int(source.n_rows), mesh)
            if not tile_rows:
                c = TP.tile_rows_for(4 * d, layout.per_process * pc)
            c = -(-c // n_shards) * n_shards
            c_local = c // pc
            n_tiles = -(-layout.per_process // c_local)
            source = TP.PaddedSource(source, n_tiles * c_local)
        else:
            c = -(-c // n_shards) * n_shards
        shardings = (M.batch_sharding(mesh, ndim=2),
                     M.batch_sharding(mesh, ndim=2 if y2d else 1),
                     M.batch_sharding(mesh, ndim=1))
        step_fn = _tileplane_sharded_step(
            mesh, bins, corr_matrix, distinct is not None,
            clip is not None, lo is not None, y2d)
        shift_fn = _tile_shift_sharded(mesh)
    else:
        step_fn = functools.partial(_tileplane_step_jit, bins=bins,
                                    corr_matrix=corr_matrix)
        shift_fn = _tile_shift_jit

    extras = tuple(a for a in (distinct_j, clip_j, lo_j, hi_j)
                   if a is not None)
    if mesh is not None:
        if pc > 1:
            from ..parallel import multihost as MH
            extras = tuple(MH.replicated_global(np.asarray(a), mesh)
                           for a in extras)
        else:
            extras = tuple(jax.device_put(a, M.replicated(mesh))
                           for a in extras)

    def step(carry, xt, yt, wt):
        if mesh is not None:
            return step_fn(carry, xt, yt, wt, *extras)
        return step_fn(carry, xt, yt, wt, distinct_j, clip_j, lo_j, hi_j)

    first_tile = None
    if corr_matrix:
        def first_tile(carry, xt, yt, wt):
            return carry[0], shift_fn(xt, wt)

    carry0 = (_zero_state(d, corr_matrix=corr_matrix,
                          n_classes=0 if distinct is None
                          else int(np.asarray(distinct).shape[0]),
                          bins=bins, big=big),
              jnp.zeros(d, jnp.float32))
    if pc > 1:
        # a multi-process jit cannot adopt single-device carry leaves:
        # land them replicated over the global mesh up front
        from ..parallel import multihost as MH
        carry0 = jax.tree_util.tree_map(
            lambda a: MH.replicated_global(np.asarray(a), mesh), carry0)
    # depth resolved HERE (TMOG_TILE_PREFETCH, default 1) so the pass
    # stats record the ring the pass actually ran with; depth never
    # changes tile boundaries, so results are bit-identical at any value
    depth = max(1, int(prefetch)) if prefetch else TP.tile_prefetch_depth()
    (st, shift), ps = TP.run_tileplane(
        source, step, carry0, tile_rows=c // pc, label="stats",
        first_tile=first_tile, shardings=shardings, prefetch=depth)
    _last_stream_stats = ps
    if pc > 1:
        # flight recorder: the ONE fetch of the pass is where a victim
        # rank absorbs its peers' lag (the tile psums are inside the
        # sharded step) — bracket it as the pass's collective window
        from ..parallel import podtrace
        with podtrace.collective("stats_fetch",
                                 rows=int(source.n_rows or 0),
                                 cols=int(d)):
            return _fetch_state(st), np.asarray(shift, np.float32)
    # the ONE fetch of the pass
    return _fetch_state(st), np.asarray(shift, np.float32)


# -- the routed, telemetry-emitting front door -------------------------------

_seen_shapes: set = set()


def run_stats(X, y=None, w=None, *, distinct=None, clip=None, lo=None,
              hi=None,
              bins: int = 0, corr_matrix: bool = False, mesh=None,
              driver: Optional[str] = None,
              tile_rows: Optional[int] = None,
              label: str = "stats") -> FusedStats:
    """One engine pass, finalized, timed and reported.

    Routing: `driver` in {"fused", "sharded", "streamed"} forces a route;
    otherwise `mesh` selects sharded, a host matrix larger than
    TMOG_STATS_STREAM_MB selects streamed, and everything else runs the
    single program. The pass is timed behind a block_until_ready fence
    and reported as a `stats_pass[<driver>]` kernel span (analytic bytes
    -> roofline attribution), a StatsPass telemetry record and a
    `stats_pass` event (utils/metrics.collector)."""
    from ..parallel.tileplane import RowSource
    from ..utils.metrics import collector

    src_mode = isinstance(X, RowSource)
    if src_mode:
        n, d = X.n_rows, None  # resolved after the pass
        y2d = False
        driver = "streamed"
    else:
        n, d = np.asarray(X).shape if isinstance(X, np.ndarray) else X.shape
        y2d = (np.asarray(y).ndim if isinstance(y, np.ndarray)
               else y.ndim) == 2
    if driver is None:
        if mesh is not None:
            driver = "sharded"
        elif isinstance(X, np.ndarray) and \
                X.nbytes > stream_threshold_bytes():
            driver = "streamed"
        else:
            driver = "fused"

    kw = dict(distinct=distinct, clip=clip, lo=lo, hi=hi, bins=bins,
              corr_matrix=corr_matrix)
    key = (driver, n, d, bins, corr_matrix, distinct is not None, y2d)
    cold = key not in _seen_shapes
    _seen_shapes.add(key)

    t0 = time.perf_counter()
    if driver == "sharded":
        if mesh is None:
            raise ValueError("driver='sharded' needs a mesh")
        st, shift = fused_stats_sharded(mesh, X, y, w, **kw)
        jax.block_until_ready(st)
    elif driver == "streamed":
        # mesh here selects the tileplane's shard_map lane (tiles
        # row-sharded over the batch axis, psum tile merge)
        st, shift = stream_stats(X, y, w, tile_rows=tile_rows, mesh=mesh,
                                 **kw)
        # host state: the pass already blocked on the final fetch
    else:
        st, shift = fused_stats(X, y, w, **kw)
        jax.block_until_ready(st)
    wall = time.perf_counter() - t0

    if driver == "streamed" and _last_stream_stats is not None:
        ps = _last_stream_stats
        n, tiles = ps.rows, ps.tiles
        d = int(np.asarray(st.cnt).shape[0])
    else:
        if d is None:
            d = int(np.asarray(st.cnt).shape[0])
        if n is None:
            n = int(round(float(np.asarray(st.wsum))))
        c = stats_row_block(d, n) if driver != "streamed" else \
            int(tile_rows or min(stream_tile_rows_default(), max(n, 1)))
        tiles = -(-n // c)
    bytes_hbm = stats_pass_bytes(n, d, y2d=y2d, weighted=w is not None)
    collector.stats_pass(driver=driver, rows=int(n), cols=int(d),
                         tiles=int(tiles), bytes_hbm=float(bytes_hbm),
                         wall_seconds=wall, cold=cold, label=label)
    return _finalize(st, shift, bins=int(bins))


# -- spearman rank pre-pass --------------------------------------------------

@jax.jit
def _rank_block_jit(Xc, y, w):
    """Tie-aware ranks of a COLUMN BLOCK plus the label re-ranked within
    each column's valid rows (pairwise-complete Spearman semantics,
    identical to ops/stats.spearman_with_label's inner vmap). Invalid
    entries rank NaN so the moment engine's finite mask drops them."""
    def per_col(col):
        wv = w * jnp.isfinite(col).astype(jnp.float32)
        cr = S._rank_with_nan(col, wv)
        yr = S._rank_with_nan(jnp.where(wv > 0, y, jnp.nan), wv)
        return cr, yr

    return jax.vmap(per_col, in_axes=1, out_axes=1)(Xc)


def rank_matrices(X, y, w=None, *, col_block: int = 128
                  ) -> Tuple[jax.Array, jax.Array]:
    """Blocked device rank pre-pass: (Rx [n, d], Ry [n, d]) ready for the
    moment engine's 2-D-label mode. Columns process in fixed-width blocks
    (ragged tail NaN-padded -> one executable), bounding the per-program
    sort workspace."""
    X = _as_f32(X)
    y = _as_f32(y)
    n, d = X.shape
    w = jnp.ones(n, jnp.float32) if w is None else _as_f32(w)
    cb = min(col_block, d)
    rx_parts, ry_parts = [], []
    for s in range(0, d, cb):
        xc = X[:, s:s + cb]
        if xc.shape[1] < cb:
            xc = jnp.pad(xc, ((0, 0), (0, cb - xc.shape[1])),
                         constant_values=jnp.nan)
        rx, ry = _rank_block_jit(xc, y, w)
        rx_parts.append(rx[:, :min(cb, d - s)])
        ry_parts.append(ry[:, :min(cb, d - s)])
    if len(rx_parts) == 1:
        return rx_parts[0], ry_parts[0]
    return jnp.concatenate(rx_parts, 1), jnp.concatenate(ry_parts, 1)
