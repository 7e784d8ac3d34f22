"""Lane-batched streaming GLM sweep: every (fold x grid) fit in ONE pass
over the feature matrix per Newton iteration — and, since the
convergence-aware restructure, only for the lanes that still need it.

The vmapped sweep (`automl/tuning/validators._sweep`) runs `fit_one` per
lane, so each of the L = folds x grid lanes re-streams the [n, d] matrix
from HBM every iteration and materializes its own weighted [n, d] product
for the Gram matmul — at the 10M-row BASELINE config that is ~5GB of HBM
traffic per lane-iteration and forces the validator to chunk the grid to a
handful of lanes per program. The whole sweep is HBM-bound at a few
percent MFU.

This kernel restructures the math so X streams ONCE per iteration for ALL
lanes (reference workload: the 8-thread pool of OpValidator.scala:270-332,
every thread refitting against the same cached DataFrame):

- one pass over the rows per Newton iteration, carrying per-lane
  accumulators (g [L, d], Hessians [L, d, d], their borders sum S xs
  [L, d], intercept sums). Which body
  runs the pass is read from what the program can observe
  (`glm_round_kernel`; no option): on a backend that has Mosaic, for a
  resident bfloat16 matrix of at most 120 columns or of 128, ONE Pallas
  program (`ops/pallas_glm.glm_moments`) that reads tiles of X in place,
  in the layout the chip keeps that width in (`glm_x_tile`), and
  keeps everything between the block and the sums in VMEM — margins,
  residual and curvature, every lane's weighted copy of the block, and one
  contraction of the block against all of them for the Grams and the
  gradient together, on one device and on every chip of a mesh alike;
  everywhere else (the CPU, a float32 matrix, 121 to 127 columns, the
  feature tiles past 128, the tileplane source steps) the XLA scan over
  row blocks described next (`_moments_blocks`), the same sums;
- lane etas in one MXU contraction `X_blk @ B.T` ([c, d] x [d, L]), B the
  float32 coefficients the iteration carries as their exact parts of the
  matrix's dtype (`parts.float32_parts`: [d, 3 L] against a
  bfloat16 block): a step taken at coefficients rounded to that dtype
  never settles under `tol`, and every lane ran to `max_iter`; the
  gradient's sum takes the residual x weight as parts of that dtype by
  the same routine, all of them here and the two leading ones in the
  fused pass (rounded to one, it floors delta at `tol` on tens of
  millions of rows);
- every lane's weighted Gram from ONE batched einsum 'cl,cd,ce->lde'
  with S [c, L] the per-lane curvature weights (narrow path, d <= 128).
  A compressed upper-triangle form (xf[:, iu0] * xf[:, iu1] then an
  [L, c] x [c, T] matmul) halves the arithmetic but its column GATHER
  dominated the pass on TPU — 7.8 TF/s vs the einsum's 25.8 TF/s on a
  v5 lite at the BASELINE shapes (tools/tpu_glm_hess_ab.py). No
  per-lane scaled copy of X exists anywhere in HBM;
- per-lane 64x64 Newton solves + proximal L1 + intercept steps are
  batched dense linalg on [L, d, d] — microscopic next to the pass. B and
  the intercept step TOGETHER (`_newton_prox_update`): one solve with two
  right-hand sides, u = H^-1 g and v = H^-1 c (c the Hessian's border,
  the curvature-weighted column means), then block Gauss-Seidel on the
  iteration's own quadratic model — dB = soft(B - u + v db0) - B, db0 =
  (g0 + c . dB) / h0, a few sweeps on [L, d] vectors. Its fixed points are
  those of the alternation it replaced (B by H^-1 g, the intercept by
  g0 / h0, each as if the other stood still), which on columns whose
  curvature-weighted means are far from zero (null indicators) converged
  linearly at ~0.62 a step: 30 passes over X where this takes 9.

Convergence awareness (docs/performance.md "Convergence-aware GLM
sweep"): one streamed kernel a loss, on top of the shared scan machinery.

1. `sweep_glm_squared_gram` — loss="squared" sufficient-statistics fast
   path. The squared-loss curvature is identically 1, so the lane Hessian
   collapses to the per-FOLD weighted Gram X^T diag(w * mask_f) X:
   iteration-invariant and only F matrices, not L. ONE streaming pass
   builds [F, d, d] Grams + X^T W_f y / X^T W_f 1 moments (psum'd under
   shard_map). Here the moments ARE the fit, so no operand of the pass is
   rounded (`_gram_moments`, `gram_pass_body`): a bfloat16 matrix's RAW
   rows go through the matrix unit once (their products are exact in
   float32) and the standardisation is applied to the [F, d, d] sums; a
   float32 matrix, a feature-tiled width and columns whose means dwarf
   their deviations are standardised in float32 a block and contracted at
   HIGHEST precision. The
   whole reg x alpha grid then solves off the cached
   moments — ridge lanes closed form (`ops/glm.ridge_gram_solve`),
   elastic-net lanes by proximal Newton on the cached Gram
   (`ops/glm.prox_newton_gram`, seeded from the ridge solution, on the
   centred label). Up to max_iter full-data passes become exactly one.
2. `sweep_glm_round` + the host driver `sweep_glm_streamed_rounds` — the
   IRLS losses (logistic, squared_hinge) run rounds of K iterations with
   a PER-LANE delta vector in the carry; after each round the host
   retires converged lanes (coefficients frozen — matching the per-lane
   solvers' own tol semantics, `ops/glm._newton_prox_fit`) and compacts
   survivors into the next round's program. The lane axis pads to a
   power-of-two bucket ladder (`bucket_lanes`) so recompiles are bounded
   and the jit cache is shared across rounds, chunks and sweeps; inert
   padded lanes carry zero fold weights. Round 0 optionally fits only
   each fold's strongest-regularization lane and seeds the rest of the
   fold from it (glmnet-style pathwise continuation).
3. `sweep_mlr_round` + `sweep_mlr_streamed_rounds` — the softmax loss: the
   same retirement loop (`_run_rounds`, written once for both drivers)
   around the multinomial round program, whose pass over X is ONE Pallas
   program on a backend that has Mosaic (`ops/pallas_softmax.py`) and an
   XLA loop over row blocks elsewhere (`round_kernel`).

`tol`/`max_iter` are traced scalars on every route (they only feed
while-loop conds), so tuning them never recompiles.

Fold masks enter as weights (mask * w), exactly like the vmapped path, so
fold semantics are identical; the elementwise residual/curvature rules per
loss mirror ops/glm's solvers (logistic IRLS, squared, squared-hinge).

Distribution: the `*_sharded` variants run the SAME cores inside a
shard_map over the mesh `batch` axis — each shard scans its local rows,
then every accumulator reduction psums over ICI/DCN (the Spark-shuffle /
Rabit-allreduce slot of SURVEY §2.9); the tiny replicated solves run on
every shard. Sharded standardization uses one-pass psum'd moments. The
replicated-out_spec claims of the sharded drivers are proved
statically by tmoglint SHD001 (a missing psum is invisible on the
1-device CI mesh — docs/static_analysis.md).

Standardization note: the per-lane solvers standardize with the lane's own
(fold-masked) weights; these kernels standardize ONCE with the global
weights so the standardized matrix can be shared by every lane. Fold
means/stds differ from global ones by O(1/sqrt(n)) — statistically inert
at the scales where these kernels are selected (the validator still routes
small problems through the per-lane path).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import glm as G
from . import pallas_glm, pallas_hist, pallas_softmax, pallas_wide
from . import parts as _parts

EPS = 1e-12

# Rows per scan block of the XLA bodies on the narrow path (the fused pass
# of the binary rounds, ops/pallas_glm.py, sizes its own tiles and makes no
# blocks): bounds the [c, d, d] pairwise intermediate XLA materializes when
# lowering the Gram einsum (f32, 512MB at d=64/c=32768) and the [c, L]
# residual/curvature blocks. _row_block() halves c as d grows so the
# transient never exceeds that budget (d=128 would otherwise double it).
_ROW_BLOCK = 32_768


def _row_block(d: int) -> int:
    c = _ROW_BLOCK
    while c > 4_096 and c * d * d * 4 > 512 * (1 << 20):
        c //= 2
    return c

# Widest matrix the single-pass (narrow) route handles. The narrow path
# is the full symmetric per-lane Gram einsum 'cl,cd,ce->lde' — 2x the
# arithmetic of the old compressed-triangle pair-product form but 3.3x
# the throughput on v5e (the triangle's column gather xf[:, iu0] was the
# wall; tools/tpu_glm_hess_ab.py). Past this width the [c, d, d] blocks
# outgrow the transient budget and the kernel switches to the
# feature-tiled accumulation (same math, tile-pair granularity).
TRI_MAX_D = 128

# Feature-tile edge for the wide path: each scan step materializes one
# [c, TILE^2] pair-product block per tile pair. 64 keeps MXU tiles square
# and the transient at c * 16K floats.
_FEATURE_TILE = 64

# Rows per scan block on the wide path — c * TILE^2 * 4B = 64MB at 4096.
_ROW_BLOCK_WIDE = 4_096

# Graph-size ceiling for the tiled path: the tile-pair loop is a Python
# unroll inside the scan body inside the Newton while_loop, so pairs
# multiply XLA graph size. 406 pairs = d_pad 1792 (28 tiles) — far past
# any transmogrified width seen in practice, well before compile blowup.
_MAX_TILE_PAIRS = 406

# Newton iterations per jitted round on the retirement route; the
# retirement granularity / wasted-iteration tradeoff (a lane converging
# mid-round keeps iterating until the round ends).
ROUND_ITERS_DEFAULT = 5

# Smallest lane bucket on the compaction ladder: buckets below this save
# almost no per-pass work but add compile entries.
_BUCKET_MIN = 8


def bucket_lanes(n_active: int) -> int:
    """Smallest power-of-two bucket >= n_active (floor _BUCKET_MIN): the
    round kernel's lane axis is padded to this, so a sweep compiles at
    most log2(L/floor)+1 distinct round programs per (n, d, F) shape,
    reused across rounds, grid chunks and repeated sweeps."""
    b = _BUCKET_MIN
    while b < n_active:
        b *= 2
    return b


def streamed_route_ok(d: int, lanes: int, budget_bytes: float) -> bool:
    """Can the streamed kernel take a (d features, lanes) sweep within
    `budget_bytes` of device memory? Owns the kernel's own padding and
    graph-size policy so route guards (validators._streamable) cannot
    drift from it: per-iteration footprint is the assembled [L, d, d]
    Hessian + LU workspace + tile accumulators (~4x) at the ROUND
    DRIVER'S first-round bucket (bucket_lanes pads the lane axis to the
    next power of two, up to ~2x the logical lane count), and the tiled
    path's Python-unrolled tile-pair loop is capped before XLA graph
    size explodes."""
    if d <= TRI_MAX_D:
        d_work = d
    else:
        nt = -(-d // _FEATURE_TILE)
        if nt * (nt + 1) // 2 > _MAX_TILE_PAIRS:
            return False
        d_work = nt * _FEATURE_TILE
    return bucket_lanes(lanes) * d_work * d_work * 4.0 * 4.0 <= budget_bytes


# the IRLS losses' residual and curvature: ONE elementwise rule, kept
# beside the fused kernel (a Mosaic body carries its source lines, and this
# file's move often) and read by every XLA body here
_residual_curvature = pallas_glm.residual_curvature


# -- shared scan geometry ----------------------------------------------------

def _tiling(d: int):
    """(tiled, d_work, bt, tile_pairs) — the narrow/wide Gram geometry for
    a d-feature matrix, shared by every streamed route so their padding
    and transient budgets cannot diverge."""
    if d <= TRI_MAX_D:
        return False, d, 0, []
    bt = _FEATURE_TILE
    nt = -(-d // bt)
    return True, nt * bt, bt, [(a, b) for a in range(nt)
                               for b in range(a, nt)]


def _gram_fns(tiled: bool, d_work: int, lanes: int, bt: int, tile_pairs,
              precision=None):
    """(hess_blocks, assemble, blocks0) for `lanes` weighted Grams of a
    d_work-wide block. `hess_blocks(xf [c, d_work] f32, S [c, lanes])`
    returns per-block accumulator contributions; `assemble` turns the
    summed accumulator into the full symmetric [lanes, d_work, d_work].
    `precision` is the contractions' (the IRLS rounds take the default:
    their Hessian only has to point downhill; the squared loss's Gram IS
    the fit, and takes HIGHEST).
    These are the XLA bodies' Grams (_moments_blocks, _gram_core, the
    tileplane steps); where the binary rounds run the fused pass
    (glm_round_kernel) the narrow accumulator comes from
    pallas_glm.glm_moments in this layout, and only `assemble` (the
    identity there) is used."""
    if tiled:
        def hess_blocks(xf, S):
            # Tile-pair contributions [npairs, lanes, bt*bt] — the wide-d
            # path: each pair materializes only a [c, bt^2] product (the
            # [c, d(d+1)/2] full triangle would outgrow HBM past ~128
            # features); off-diagonal tile pairs are computed once and
            # mirrored at assembly, keeping the triangle savings at tile
            # granularity.
            out = []
            for a, b in tile_pairs:
                xa = xf[:, a * bt:(a + 1) * bt]
                xb = xf[:, b * bt:(b + 1) * bt]
                P = (xa[:, :, None] * xb[:, None, :]).reshape(-1, bt * bt)
                out.append(jnp.matmul(S.T, P, precision=precision,
                                      preferred_element_type=jnp.float32))
            return jnp.stack(out)

        def assemble(hA):
            H = jnp.zeros((lanes, d_work, d_work), jnp.float32)
            for p, (a, b) in enumerate(tile_pairs):
                blk = hA[p].reshape(lanes, bt, bt)
                H = H.at[:, a * bt:(a + 1) * bt,
                         b * bt:(b + 1) * bt].set(blk)
                if a != b:
                    H = H.at[:, b * bt:(b + 1) * bt,
                             a * bt:(a + 1) * bt].set(
                                 blk.transpose(0, 2, 1))
            return H

        blocks0 = jnp.zeros((len(tile_pairs), lanes, bt * bt), jnp.float32)
        return hess_blocks, assemble, blocks0

    def hess_blocks(xf, S):
        # Per-lane weighted Gram [lanes, d, d] for one row block, as ONE
        # einsum XLA tiles directly. The previous compressed-triangle form
        # (xf[:, iu0] * xf[:, iu1] -> [c, T] then an [L, c] x [c, T]
        # matmul) halved the contraction FLOPs but its column GATHER
        # dominated the whole pass on TPU: measured on v5 lite at the
        # BASELINE shapes, the gather-built triangle ran 7.8 TF/s
        # end-to-end while this full symmetric einsum runs 25.8 TF/s —
        # 1.7x faster despite doing 2x the arithmetic
        # (tools/tpu_glm_hess_ab.py).
        return jnp.einsum('cl,cd,ce->lde', S, xf, xf, precision=precision,
                          preferred_element_type=jnp.float32)

    return (hess_blocks, lambda hA: hA,
            jnp.zeros((lanes, d_work, d_work), jnp.float32))


def _blocked(Xs, y, w, fold_masks, c: int):
    """Row-pad to the block multiple with w=0 (inert everywhere) and
    reshape into scan blocks."""
    n = Xs.shape[0]
    F = fold_masks.shape[0]
    nb = -(-n // c)
    pad = nb * c - n
    if pad:
        Xs = jnp.pad(Xs, ((0, pad), (0, 0)))
        y = jnp.pad(y, (0, pad))
        w = jnp.pad(w, (0, pad))
        fold_masks = jnp.pad(fold_masks, ((0, 0), (0, pad)))
    return (Xs.reshape(nb, c, Xs.shape[1]), y.reshape(nb, c),
            w.reshape(nb, c), fold_masks.reshape(F, nb, c).transpose(1, 0, 2))


# Sweeps of the coupled update's inner iteration after its first (a constant
# of the arithmetic, not a knob: on the null-tracked table's grid 2, 8 and 32
# stop every lane at the same iteration)
INTERCEPT_SWEEPS = 2


def _newton_prox_update(B, b0, gA, hA, g0A, h0A, cA, wsum_l, l1, l2, eye,
                        assemble, fit_intercept: bool):
    """THE damped-Newton + proximal-L1 + intercept update from streamed
    accumulators, shared by the resident round kernel and the tileplane
    source rounds, so a change to the update rule reaches both at once.
    (`ops/glm.prox_newton_gram`, the squared loss's moment-space solve, and
    `ops/glm._newton_prox_fit`, the per-lane solver, are functions of their
    own with the alternation this one had: the same fixed points.)

    With g, H the coefficients' gradient and Hessian (ridge included), g0,
    h0 the intercept's and c = cA / wsum the Hessian's border (the
    curvature-weighted column means), u, v = H^-1 g, H^-1 c and soft the
    threshold by l1 / diag(H):

        dB  = soft(B - u) - B;  db0 = (g0 + c . dB) / h0
        INTERCEPT_SWEEPS times:
            dB  = soft(B - u + v db0) - B    # B's step, the intercept
            db0 = (g0 + c . dB) / h0         #   moving by -db0; and back
        B, b0 <- B + dB, b0 - db0

    block Gauss-Seidel on the iteration's own quadratic model, on [lanes, d]
    vectors and with no further pass over X. The first line alone is the
    alternation this function had (each step as if the other stood still:
    block Jacobi, linear at ~0.62 a step where c is far from zero, as on
    null indicators). Where soft(B - u) = B and g0 = 0 every dB and db0
    here is zero, and a fixed point of this map has db0 = 0, hence g0 = 0
    and soft(B - u) = B: the two iterations have the same fixed points, so
    no documented answer moves, only the count of passes to it. (A bordered
    [d + 1, d + 1] solve followed by the threshold does NOT: the threshold
    moves B after the joint solve and the intercept never hears of it,
    which leaves g0 off zero.) cA's rounding moves no fixed point: there it
    multiplies a zero. Without an intercept, and in a lane whose B is
    thresholded to zero (dB = 0), the step is the older one to the letter.
    Returns (B_new, b0_new, delta_vec [L])."""
    g = gA / wsum_l[:, None] + l2[:, None] * B
    H = assemble(hA) / wsum_l[:, None, None]
    H = H + (l2[:, None, None] + 1e-6) * eye[None]
    hdiag = jnp.maximum(jnp.diagonal(H, axis1=1, axis2=2), EPS)
    thresh = l1[:, None] / hdiag

    def soft(z):
        return jnp.sign(z) * jnp.maximum(jnp.abs(z) - thresh, 0.0)

    if fit_intercept:
        c = cA / wsum_l[:, None]
        uv = jnp.linalg.solve(H, jnp.stack([g, c], axis=-1))
        Bu, v = B - uv[..., 0], uv[..., 1]
        g0 = g0A / wsum_l
        h0 = jnp.maximum(h0A / wsum_l, EPS)

        def intercept_step(B_new):
            return (g0 + (c * (B_new - B)).sum(axis=1)) / h0
        B_new = soft(Bu)
        db0 = intercept_step(B_new)
        for _ in range(INTERCEPT_SWEEPS):
            B_new = soft(Bu + v * db0[:, None])
            db0 = intercept_step(B_new)
        b0_new = b0 - db0
    else:
        B_new = soft(B - jnp.linalg.solve(H, g[..., None])[..., 0])
        b0_new = b0
    delta = jnp.abs(B_new - B).max(axis=1) + jnp.abs(b0_new - b0)
    return B_new, b0_new, delta


# shard_map construction + carry-vary shims live in parallel/mesh.py since
# the one-pass stats engine (ops/stats_engine.py) shares them; the private
# names stay importable for existing callers
from ..parallel.mesh import build_shard_map as _build_shard_map  # noqa: E402
from ..parallel.mesh import mesh_batch_count as _mesh_batch_count  # noqa: E402
from ..parallel.mesh import mesh_is_multiprocess as _mesh_is_mp  # noqa: E402
from ..parallel.mesh import shard_vary as _shard_vary  # noqa: E402


def _is_global_array(a) -> bool:
    """True for a jax.Array whose shards span other processes (already
    landed on a multi-process mesh) — such inputs pass through the
    sharded entry points untouched."""
    return isinstance(a, jax.Array) and not a.is_fully_addressable


def _land_rows_multihost(mesh, X, y, w, fold_masks):
    """Land THIS PROCESS's host-local sweep rows as global batch-sharded
    arrays (multihost.host_local_block; every process calls with its own
    stripe — SPMD). X/y/w pad along rows with zeros (zero weight = inert
    in every accumulator), fold masks pad along their row axis (axis 1)
    with ones (irrelevant under w=0) — the uneven-stripe generalization
    of the validator's pad_rows_to_multiple."""
    from ..parallel import multihost as MH
    from ..parallel import podtrace

    Xl = np.asarray(X)
    n = Xl.shape[0]
    layout = MH.row_layout(n, mesh)
    wl = np.ones(n, np.float32) if w is None else np.asarray(w, np.float32)
    with podtrace.ingest("glm_land", rows=int(n),
                         cols=int(Xl.shape[1]) if Xl.ndim > 1 else 1):
        return (MH.host_local_block(Xl, mesh, layout),
                MH.host_local_block(np.asarray(y, np.float32), mesh,
                                    layout),
                MH.host_local_block(wl, mesh, layout),
                MH.host_local_block(np.asarray(fold_masks, np.float32),
                                    mesh, layout, pad_value=1.0, axis=1))


def _psum_moments(X, w, allreduce):
    """Two-pass weighted column moments in f32 (psum-aware). One-pass
    E[x^2]-mean^2 cancels catastrophically in f32 for large-mean features
    (epoch-millisecond timestamps would lose ALL unit-scale variance),
    silently diverging from the two-pass path."""
    f32 = jnp.float32
    wsum = jnp.maximum(allreduce(w.sum().astype(f32)), EPS)
    xf = X.astype(f32)
    mean = allreduce((xf * w[:, None]).sum(0)) / wsum
    centered = xf - mean[None, :]
    var = allreduce((centered * centered * w[:, None]).sum(0)) / wsum
    std = jnp.sqrt(jnp.maximum(var, EPS))
    return mean, std


@jax.jit
def glm_standardize_stats(X: jax.Array, w: jax.Array
                          ) -> Tuple[jax.Array, jax.Array]:
    """Global-weight column (mean, std) for the round driver — computed
    once per sweep, applied on the fly inside every round's scan so no
    standardized [n, d] copy is ever materialized."""
    return _psum_moments(X, w, lambda v: v)


@functools.lru_cache(maxsize=None)
def _sharded_stats_fn(mesh):
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import BATCH_AXIS

    def glm_standardize_stats_sharded(X, w):
        return _psum_moments(
            X, w, lambda v: jax.lax.psum(v, BATCH_AXIS))

    sm = _build_shard_map(glm_standardize_stats_sharded, mesh,
                          in_specs=(P(BATCH_AXIS, None), P(BATCH_AXIS)),
                          out_specs=(P(None), P(None)))
    return jax.jit(sm)


# -- squared-loss sufficient-statistics fast path ----------------------------

_HIGHEST = jax.lax.Precision.HIGHEST

# the bodies of the Gram pass over X, as `gram_pass_body` names them to the
# `gram_pass` span's `moments_body` and the telemetry's `gram_moments_body`.
# Both are XLA loops over row blocks, and the span's `body` / the
# telemetry's `gram_body` say that of either: GRAM_PASS_BODY, as the
# benchmark's checks hold them
GRAM_PASS_BODY = "xla_blocks"
GRAM_PASS_RAW = "raw_bf16_moments"

# |mean| / std past which the pass does not contract raw rows. A column's
# raw second moment is 1 + r^2 times its variance, r = |mean| / std, and
# that is what standardising in moment space cancels: whatever the float32
# accumulation inside the matrix unit leaves on the raw sum (a few 2^-24 of
# it a block) comes out 1 + r^2 times as large on the standardised one. At
# r = 4 that is 17, four of float32's 24 bits: a moment good to 2^-20, the
# fit's own `tol`. A constant of the arithmetic, not a knob; the
# null-tracked table's largest r is 2.24, an epoch timestamp's millions.
RAW_MOMENTS_MAX_R = 4.0

# Rows a matrix-unit accumulation of the raw FIRST-order sums runs over: a
# float32 sum of same-signed terms loses with its length, and the fold's
# sum of a standardised column, sum w x - mean sum w, is what a
# cancellation of |mean| sum w leaves of the raw one (1 in 400 at 32 768
# rows, 1 in 25 000 at 20M). Each chunk of this many rows of a block has
# its own pair of accumulators; the Gram's sums cancel by 1 + r^2 at most
# and run over the whole block.
_RAW_SUM_ROWS = 512


def raw_moments_guard(mean, std):
    """Device bool: every column's mean within RAW_MOMENTS_MAX_R of its
    deviations of zero, so that the raw body's moment-space step loses
    four bits at most. The half of `gram_pass_body` that only the device
    can answer; a NaN moment answers no."""
    return (jnp.abs(mean) / std).max() <= RAW_MOMENTS_MAX_R


def gram_pass_body(dtype, d: int, guard=True) -> str:
    """The body of the Gram pass over a [*, d] matrix of `dtype`, from what
    the pass is handed and nothing else: GRAM_PASS_RAW for a bfloat16
    matrix on the narrow path (two bfloat16 values' product is exact in
    float32, so the matrix unit takes the rows as they are, once) whose
    column moments pass `raw_moments_guard` (`guard`: its verdict, where it
    is known; the program asks it on the device, under a `lax.cond`);
    GRAM_PASS_BODY, float32 blocks at HIGHEST, for everything else — a
    float32 matrix's raw products are not exact in one pass nor in six."""
    if d <= TRI_MAX_D and jnp.dtype(dtype) == jnp.bfloat16 and guard:
        return GRAM_PASS_RAW
    return GRAM_PASS_BODY


def _psum_over(axis_name: Optional[str]):
    """v -> v summed over the mesh axis; the identity on one device."""
    return (lambda v: jax.lax.psum(v, axis_name)) if axis_name \
        else (lambda v: v)


def _two_sum(a, b):
    """(s, e): s = fl(a + b) and a + b = s + e exactly (Knuth)."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _two_prod(a, b):
    """(p, e): p = fl(a b) and a b = p + e exactly (Dekker; the halves'
    products are exact, so a fused multiply-add changes nothing)."""
    def halves(v):
        t = 4097.0 * v
        hi = t - (t - v)
        return hi, v - hi
    p = a * b
    ah, al = halves(a)
    bh, bl = halves(b)
    return p, ((ah * bh - p) + ah * bl + al * bh) + al * bl


def _pair_add(acc, v):
    """The pair (hi, lo) of float32 arrays, read hi + lo, with the float32
    array v added: what float32 rounds off hi goes to lo."""
    hi, e = _two_sum(acc[0], v)
    return hi, acc[1] + e


def _pair_add_parts(acc, stacked, lanes: int, axis: int):
    """`_pair_add` of every slab of `lanes` that a contraction of
    `float32_parts`' stack leaves along `axis`, the smallest part's first:
    each slab is a sum of products of 16 significant bits, which float32
    holds nearly whole; the slabs' float32 sum (`slab_sum`) would round it
    to 24 before the pair could keep what is lost."""
    for k in range(stacked.shape[axis] - lanes, -1, -lanes):
        acc = _pair_add(acc, jax.lax.slice_in_dim(stacked, k, k + lanes,
                                                  axis=axis))
    return acc


def _pair_sum0(acc):
    """A pair of [k, ...] arrays summed over its leading axis (a loop, not
    k unrolled sums: the program's size is what a process pays to load
    it)."""
    def add(k, total):
        hi, e = _two_sum(total[0], acc[0][k])
        return hi, total[1] + (e + acc[1][k])
    return jax.lax.fori_loop(1, acc[0].shape[0], add, (acc[0][0], acc[1][0]))


def _pair_less_product(a, b, m):
    """float32 of (a_hi + a_lo) - (b_hi + b_lo) m for pairs a, b and a
    float32 m, the product and the difference carried as pairs: two sums
    of tens of millions of rows that agree in their leading digits leave
    their difference, not float32's rounding of either."""
    p, e = _two_prod(b[0], m)
    e = e + b[1] * m
    s, r = _two_sum(a[0], -p)
    return s + ((r - e) + a[1])


def _raw_sums(X, y, w, fold_masks, pivot, parts: int,
              axis_name: Optional[str]):
    """The raw body's loop over the local rows: (G [d, F d], s [F, d],
    t [F, d], W [F]) as float32 pairs and syA [F] — the sums over rows of
    w_f x x', w_f x, w_f (y - pivot) x, w_f and w_f (y - pivot) of the RAW
    rows. The block as it sits in HBM is one operand of every contraction
    (no cast, no subtract, no divide); the other is the fold-weighted
    block m_f w x [c, F d] in `parts` bfloat16 parts (1: the caller has
    seen that every m_f w is zero or a power of two, so the product is a
    bfloat16 value itself; 3: the float32 product's three) for the second
    moments, and the [c, 2 F] weights m_f w and m_f w (y - pivot) as their
    three parts for the first-order sums, a chunk of `_RAW_SUM_ROWS` rows a
    sum. Every product is exact in float32 and the matrix unit adds in
    float32; each part's sums of each block go into pairs
    (`_pair_add_parts`), since raw products do not average to zero and a
    running float32 sum of thousands of them drifts. With one part a block
    is `_ROW_BLOCK` rows whatever the width (nothing here is a [c, d, d]
    float32 transient); the three parts are cut from a float32 [c, F d]
    product, which at that length leaves the chip's fast memory: they keep
    `_row_block`'s rows."""
    f32, bf = jnp.float32, X.dtype
    n, d = X.shape
    F = fold_masks.shape[0]
    # whole chunks a block (the last block starts early: `_mlr_blocks`)
    k = min(_RAW_SUM_ROWS, n)
    c = min(_ROW_BLOCK if parts == 1 else _row_block(d), n)
    c -= c % k
    nb, take = _mlr_blocks(n, c, y, w, fold_masks)
    x_block = x_row_blocks(X, c)
    over_rows = (((0,), (0,)), ((), ()))

    def body(i, acc):
        y_blk, fresh, w_blk, m_blk = take(i)            # m_blk [F, c]
        gA, mA, wA, syA = acc
        x = x_block(i)                                  # [c, d], raw
        wlf = m_blk.T * (w_blk * fresh)[:, None]        # [c, F]
        wy = wlf * (y_blk - pivot)[:, None]             # [c, F]
        if parts == 1:
            xw = (wlf.astype(bf)[:, :, None] * x[:, None, :]
                  ).reshape(c, F * d)
        else:
            v = (wlf[:, :, None] * x.astype(f32)[:, None, :]
                 ).reshape(c, F * d)
            xw = _parts.stacked_parts(v.T, bf).T
        gA = _pair_add_parts(gA, jax.lax.dot_general(
            x, xw, over_rows, preferred_element_type=f32), F * d, 1)
        u = _parts.stacked_parts(
            jnp.concatenate([wlf, wy], axis=1).T, bf)   # [3 x 2 F, c]
        m = jnp.einsum('psk,skd->spd', u.reshape(-1, c // k, k),
                       x.reshape(c // k, k, d),
                       preferred_element_type=f32)      # [c / k, 3 x 2 F, d]
        return (gA, _pair_add_parts(mA, m, 2 * F, 1),
                _pair_add(wA, wlf.reshape(c // k, k, F).sum(1)),
                syA + wy.sum(0))

    def pair0(*shape):
        return jnp.zeros(shape, f32), jnp.zeros(shape, f32)
    acc0 = _shard_vary(
        (pair0(d, F * d), pair0(c // k, 2 * F, d), pair0(c // k, F),
         jnp.zeros(F, f32)), axis_name)
    gA, mA, wA, syA = jax.lax.fori_loop(0, nb, body, acc0)
    mA = _pair_sum0(mA)
    return (gA, tuple(v[:F] for v in mA), tuple(v[F:] for v in mA),
            _pair_sum0(wA), syA)


def _scale_only_weights(fold_masks, w):
    """Device bool: every fold weight m_f w is zero or a power of two, so
    that its product with a bfloat16 value is a bfloat16 value. The cut is
    `reduce_precision`'s: a cast and back can be fused away on the chip
    (ops/parts.py)."""
    wf = fold_masks * w[None, :]
    return (jax.lax.reduce_precision(wf, exponent_bits=8, mantissa_bits=0)
            == wf).all()


def _raw_moments(X, y, w, fold_masks, mean, std,
                 axis_name: Optional[str]):
    """`_gram_moments`' sums over the local rows of a bfloat16 matrix from
    RAW products (`_raw_sums`), the standardisation applied to the sums:
    G_jk - mean_k s_j - mean_j u_k over std_j std_k with s = sum w_f x and
    u = s - mean sum w_f, the large terms as pairs. The weighted rows take
    one bfloat16 part where the pass SEES that every fold weight m_f w is
    zero or a power of two (no sample weights under 0/1 masks: the product
    with a bfloat16 value is one), three for any other weights: one more
    read of w and the masks, under a `lax.cond`. On a v5e, 25M x 128 and 5
    folds, the pass takes 59.5 ms with one part and 172 with three, where
    the float32 blocks take 214 (PERF.md, PR 48)."""
    n, d = X.shape
    # about a constant near the label's mean (`_block_moments`)
    pivot = y[:min(_ROW_BLOCK, n)].mean()
    G, s, t, W, syA = jax.lax.cond(
        _scale_only_weights(fold_masks, w),
        *(functools.partial(_raw_sums, X, y, w, fold_masks, pivot, parts,
                            axis_name) for parts in (1, 3)))
    F = fold_masks.shape[0]
    G = tuple(v.reshape(d, F, d).transpose(1, 0, 2) for v in G)
    u = _pair_less_product(s, tuple(v[:, None] for v in W), mean[None, :])
    Gc = _pair_less_product(G, tuple(v[:, :, None] for v in s),
                            mean[None, None, :]) \
        - mean[None, :, None] * u[:, None, :]
    cA = ((t[0] - mean[None, :] * syA[:, None]) + t[1]) / std[None, :]
    sxA, wsum_f = u / std[None, :], W[0] + W[1]
    return (Gc / (std[:, None] * std[None, :])[None], cA + pivot * sxA, sxA,
            syA + pivot * wsum_f, wsum_f)


def _block_moments(X, y, w, fold_masks, mean, std,
                   axis_name: Optional[str], allreduce):
    """`_gram_moments`' sums, `allreduce`d, from blocks standardised in
    float32, every contraction at HIGHEST, float32 sums: the body of a
    float32 matrix, of the feature tiles, and of columns past
    `RAW_MOMENTS_MAX_R`. The default precision would round BOTH float32
    operands to bfloat16 on the chip's matrix unit, and a value that many
    rows share (an indicator's two standardised values, a fill) is then
    off by up to 2^-9 in all of them at once: an error of the moment
    itself, which no number of rows averages out."""
    f32 = jnp.float32
    n, d = X.shape
    F = fold_masks.shape[0]
    tiled, d_work, bt, tile_pairs = _tiling(d)
    c = min(_ROW_BLOCK_WIDE if tiled else _row_block(d_work), n)
    nb, take = _mlr_blocks(n, c, y, w, fold_masks)
    x_block = x_row_blocks(X, c)
    # the label's sums are taken about a constant near its mean and moved
    # back after the loop: a running float32 sum of thousands of blocks of
    # a label whose mean is 10 left the fold's label mean 6e-6 off on the
    # chip, six ulps of the intercept (PERF.md, PR 43)
    pivot = y[:c].mean()
    hess_blocks, assemble, h_acc0 = _gram_fns(
        tiled, d_work, F, bt, tile_pairs, precision=_HIGHEST)

    def moment(xf, wl):
        return jnp.matmul(xf.T, wl, precision=_HIGHEST,
                          preferred_element_type=f32).T

    def body(i, acc):
        y_blk, fresh, w_blk, m_blk = take(i)            # m_blk [F, c]
        hA, cA, sxA, syA = acc
        xf = (x_block(i).astype(f32) - mean[None, :]) / std[None, :]
        if d_work > d:
            xf = jnp.pad(xf, ((0, 0), (0, d_work - d)))
        wlf = m_blk.T * (w_blk * fresh)[:, None]        # [c, F]
        wy = wlf * (y_blk - pivot)[:, None]             # [c, F]
        return (hA + hess_blocks(xf, wlf), cA + moment(xf, wy),
                sxA + moment(xf, wlf), syA + wy.sum(0))

    acc0 = _shard_vary(
        (h_acc0, jnp.zeros((F, d_work), f32), jnp.zeros((F, d_work), f32),
         jnp.zeros(F, f32)), axis_name)
    hA, cA, sxA, syA = jax.lax.fori_loop(0, nb, body, acc0)
    wsum_f = (fold_masks * w[None, :]).sum(1)                     # [F]
    hA, cA, sxA, syA, wsum_f = allreduce(
        (hA, cA + pivot * sxA, sxA, syA + pivot * wsum_f, wsum_f))
    return assemble(hA), cA, sxA, syA, wsum_f


def _gram_moments(X, y, w, fold_masks, mean, std, *,
                  axis_name: Optional[str] = None):
    """The squared loss's ONE pass over X: per-FOLD sufficient statistics
    of the standardised rows xs = (x - mean) / std — (Gm [F, d, d] =
    sum w_f xs xs', cA [F, d] = sum w_f y xs, sxA [F, d] = sum w_f xs,
    syA [F] = sum w_f y, wsum_f [F]), psum'd over `axis_name`.

    Precision: here the moments ARE the fit, so no operand is rounded, by
    one of two bodies that `gram_pass_body` chooses from what the pass is
    handed:

    - a bfloat16 matrix of at most TRI_MAX_D columns, all within
      RAW_MOMENTS_MAX_R of their deviations of zero: `_raw_moments`. The
      RAW rows go through the matrix unit in ONE pass at its default
      precision and the standardisation is applied to the summed moments;
    - everything else: `_block_moments`, float32 operands at HIGHEST (six
      passes). Standardising in moment space cancels 1 + (mean / std)^2 of
      the raw sums, catastrophically for a column whose mean is large
      beside its deviation (`_psum_moments`): the `lax.cond` on
      `raw_moments_guard` keeps such a matrix here, at no fetch. On a mesh
      each shard standardises its own sums before the psum (the step is
      linear in them given the global `mean`; a float32 psum of raw sums
      would round them before the cancellation).

    X is read in place: a row block is a dynamic slice along the axis the
    chip keeps major (`x_row_blocks`; y, w and the masks by
    `_mlr_blocks`), no padded, reshaped or transposed copy — compiled for
    a v5e the program of a 25M x 128 (or x 64) bfloat16 matrix holds NO
    temporaries beside a block's, where the scan over `_blocked`'s reshape
    held a second copy of X and of the masks, 7.4 GB. Past TRI_MAX_D
    columns the block, not X, is padded to the feature tiles."""
    args = (X, y, w, fold_masks, mean, std, axis_name)
    allreduce = _psum_over(axis_name)
    if gram_pass_body(X.dtype, X.shape[1]) == GRAM_PASS_BODY:
        sums = _block_moments(*args, allreduce)
    else:
        sums = allreduce(jax.lax.cond(
            raw_moments_guard(mean, std), lambda: _raw_moments(*args),
            lambda: _block_moments(*args, lambda v: v)))
    return sums[:4] + (jnp.maximum(sums[4], EPS),)


def _gram_solve(Gm_f, cA, sxA, syA, wsum_f, mean, std, regs, alphas,
                max_iter, tol, *, fit_intercept: bool):
    """The whole reg x alpha grid off the per-fold moments, no data read:
    ridge lanes closed form, elastic-net lanes by proximal Newton seeded
    from the ridge solution (`ops/glm.{ridge_gram_solve,prox_newton_gram}`
    — the moment-space replay of the per-lane update rule), then back to
    RAW units (mean 0 / std 1 where the fit was not standardised; `mean`
    and `std` are the matrix's own width, the moments may be the feature
    tiles'). Returns (B [F, G, d], b0 [F, G], prox iterations executed,
    elastic-net lanes the iteration cap stopped over `tol`)."""
    F, d = Gm_f.shape[0], mean.shape[0]
    Gn = regs.shape[0]
    # expand per-fold moments to the fold-major lane axis l = f*Gn + g
    l1 = jnp.tile(regs * alphas, F)                     # [L]
    l2 = jnp.tile(regs * (1.0 - alphas), F)             # [L]
    Gm = jnp.repeat(Gm_f, Gn, axis=0)                   # [L, d, d]
    cm = jnp.repeat(cA, Gn, axis=0)
    sx = jnp.repeat(sxA, Gn, axis=0)
    sy = jnp.repeat(syA, Gn)
    sw = jnp.repeat(wsum_f, Gn)

    beta_r, b0_r = G.ridge_gram_solve(Gm, cm, sx, sy, sw, l2,
                                      fit_intercept=fit_intercept)
    beta_p, b0_p, iters, delta = G.prox_newton_gram(
        Gm, cm, sx, sy, sw, l1, l2, beta_r, b0_r, max_iter, tol,
        fit_intercept=fit_intercept)
    is_l1 = l1 > 0.0
    B = jnp.where(is_l1[:, None], beta_p, beta_r)[:, :d] / std[None, :]
    b0 = jnp.where(is_l1, b0_p, b0_r) - (B * mean[None, :]).sum(1)
    at_cap = (is_l1 & (delta > tol) & (iters >= max_iter)).sum()
    return (B.reshape(F, Gn, d), b0.reshape(F, Gn), iters,
            at_cap.astype(jnp.int32))


def _gram_core(X, y, w, fold_masks, regs, alphas, max_iter, tol, *,
               fit_intercept, standardize,
               axis_name: Optional[str] = None):
    """loss="squared" fast path: the column moments when standardize=True
    (one extra stats pass, two reads of X: `_psum_moments`), ONE streaming
    pass for the per-FOLD sufficient statistics (`_gram_moments`), then
    the whole grid solved off them (`_gram_solve`, whose returns these are,
    and after them `raw_moments_guard`'s verdict). On a mesh this is one
    program a sweep; on one device the three are programs of their own
    (`sweep_glm_squared_gram`), so that a trace tells them apart."""
    d = X.shape[1]
    if standardize:
        mean, std = _psum_moments(X, w, _psum_over(axis_name))
    else:
        mean, std = jnp.zeros(d, jnp.float32), jnp.ones(d, jnp.float32)
    return _gram_solve(
        *_gram_moments(X, y, w, fold_masks, mean, std, axis_name=axis_name),
        mean, std, regs, alphas, max_iter, tol, fit_intercept=fit_intercept
    ) + (raw_moments_guard(mean, std),)


@jax.jit
def sweep_gram_moments(X, y, w, fold_masks, mean, std):
    """`_gram_moments` on one device: the Gram route's pass over X."""
    return _gram_moments(X, y, w, fold_masks, mean, std)


# `raw_moments_guard` as a program of its own: the one-device route hands
# its verdict back beside the solves' counts (`sweep_glm_squared_gram`)
_sweep_gram_guard = jax.jit(raw_moments_guard)


@functools.partial(jax.jit, static_argnames=("fit_intercept",))
def sweep_gram_solve(Gm_f, cA, sxA, syA, wsum_f, mean, std, regs, alphas,
                     max_iter, tol, *, fit_intercept: bool = True):
    """`_gram_solve` on one device: the Gram route's moment-space solves."""
    return _gram_solve(Gm_f, cA, sxA, syA, wsum_f, mean, std, regs, alphas,
                       max_iter, tol, fit_intercept=fit_intercept)


def sweep_glm_squared_gram(X: jax.Array, y: jax.Array, w: jax.Array,
                           fold_masks: jax.Array, regs: jax.Array,
                           alphas: jax.Array, max_iter=50, tol=1e-6, *,
                           fit_intercept: bool = True,
                           standardize: bool = True
                           ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                      jax.Array, Any]:
    """Squared-loss (fold x grid) sweep from ONE streaming Gram pass, as
    three programs: `glm_standardize_stats` (the rounds' own; skipped when
    not standardising), `sweep_gram_moments`, `sweep_gram_solve`. Returns
    (B [F, G, d] f32 RAW units, b0 [F, G], prox-solve iters, elastic-net
    lanes stopped by `max_iter`, `raw_moments_guard`'s verdict for
    `gram_pass_body`), all on the device: the verdict is the word of a
    fourth program of a few hundred operations where the dtype and the
    width leave the body to the column moments, the host's False where
    they do not."""
    d = X.shape[1]
    if standardize:
        mean, std = glm_standardize_stats(X, w)
    else:
        mean, std = jnp.zeros(d, jnp.float32), jnp.ones(d, jnp.float32)
    raw = gram_pass_body(X.dtype, d) == GRAM_PASS_RAW \
        and _sweep_gram_guard(mean, std)
    return sweep_gram_solve(
        *sweep_gram_moments(X, y, w, fold_masks, mean, std), mean, std,
        regs, alphas, max_iter, tol, fit_intercept=bool(fit_intercept)
    ) + (raw,)


def gram_temp_bytes(X, y, w, fold_masks) -> int:
    """Bytes of temporaries the compiled one-device Gram pass holds beside
    its arguments (the compiler's `memory_analysis()`): a padded,
    transposed or float32 copy of X would show here. Lowered from the
    arrays' shapes and compiled (a load, where the persistent cache holds
    the program the sweep ran): ask once, after a warm-up
    (`glm_round_temp_bytes`)."""
    d = X.shape[1]
    col = jax.ShapeDtypeStruct((d,), jnp.float32)
    return int(sweep_gram_moments.lower(X, y, w, fold_masks, col, col)
               .compile().memory_analysis().temp_size_in_bytes)


@functools.lru_cache(maxsize=None)
def _sharded_gram_fn(mesh, fit_intercept, standardize):
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import BATCH_AXIS

    def core(X, y, w, fold_masks, regs, alphas, max_iter, tol):
        return _gram_core(X, y, w, fold_masks, regs, alphas, max_iter, tol,
                          fit_intercept=fit_intercept,
                          standardize=standardize, axis_name=BATCH_AXIS)

    sm = _build_shard_map(
        core, mesh,
        in_specs=(P(BATCH_AXIS, None), P(BATCH_AXIS), P(BATCH_AXIS),
                  P(None, BATCH_AXIS), P(None), P(None), P(), P()),
        out_specs=(P(None, None, None), P(None, None), P(), P(), P()))
    return jax.jit(sm)


def sweep_glm_squared_gram_sharded(mesh, X, y, w, fold_masks, regs, alphas,
                                   max_iter=50, tol=1e-6, *,
                                   fit_intercept: bool = True,
                                   standardize: bool = True
                                   ) -> Tuple[jax.Array, jax.Array,
                                              jax.Array, jax.Array,
                                              jax.Array]:
    """Row-sharded Gram fast path (`sweep_glm_squared_gram`'s returns):
    each shard accumulates its local rows' per-fold moments, one psum
    combines them, the grid solves replicated.
    Rows must be padded to the batch-axis multiple with zero weights (the
    validator's mesh device_put does this).

    On a MULTI-PROCESS mesh, host (or fully-addressable) X/y/w/fold_masks
    are treated as THIS PROCESS's rows and landed as the process's
    batch-axis block of one global array (_land_rows_multihost); the
    accumulator psums then cross hosts over DCN. Already-global inputs
    pass through untouched."""
    fn = _sharded_gram_fn(mesh, bool(fit_intercept), bool(standardize))
    if _mesh_is_mp(mesh):
        from ..parallel import multihost as MH
        from ..parallel import podtrace

        if not _is_global_array(X):
            X, y, w, fold_masks = _land_rows_multihost(mesh, X, y, w,
                                                       fold_masks)
        # flight recorder: the Gram psum is inside the jitted program, so
        # the collective window is the whole sharded call; the explicit
        # block (recording only) pins the barrier wall to this bracket
        # instead of the caller's eventual fetch
        with podtrace.collective(
                "glm_gram", rows=int(X.shape[0]), feat=int(X.shape[1]),
                lanes=int(np.asarray(regs).shape[0])) as _psp:
            out = fn(
                X, y, w, fold_masks,
                MH.replicated_global(np.asarray(regs, np.float32), mesh),
                MH.replicated_global(np.asarray(alphas, np.float32),
                                     mesh),
                MH.replicated_global(np.asarray(int(max_iter), np.int32),
                                     mesh),
                MH.replicated_global(np.asarray(float(tol), np.float32),
                                     mesh))
            if _psp is not None:
                jax.block_until_ready(out)
        return out
    return fn(
        X, y, w, fold_masks, regs, alphas,
        jnp.asarray(max_iter, jnp.int32), jnp.asarray(tol, jnp.float32))


# -- round kernel + host retirement driver (IRLS losses) ---------------------

def _moments_acc0(Lb: int, d_work: int):
    """Zeros of one pass's sums (gA, Hessian blocks, g0A, h0A, cA)."""
    tiled, _, bt, tile_pairs = _tiling(d_work)
    _, _, h_acc0 = _gram_fns(tiled, d_work, Lb, bt, tile_pairs)
    return (jnp.zeros((Lb, d_work), jnp.float32), h_acc0,
            jnp.zeros(Lb, jnp.float32), jnp.zeros(Lb, jnp.float32),
            jnp.zeros((Lb, d_work), jnp.float32))


def _moments_blocks(blocks, sel, B, b0, mean, std, *, loss,
                    axis_name: Optional[str] = None, acc0=None):
    """One Newton iteration's pass over X as an XLA scan over row blocks
    (`blocks` is `_blocked`'s), for a backend without Mosaic and for the
    shapes the fused pass leaves alone (`glm_round_kernel`): (gA [Lb, d],
    Hessian blocks, g0A [Lb], h0A [Lb], cA [Lb, d]), the sums over rows of
    R xs', S xs xs', R, S and S xs' (pallas_glm.glm_moments is the same
    sums in one program; cA, the Hessian's border, takes S rounded to the
    block's dtype as it does there: it shapes the step, not its fixed point).
    B [Lb, d] is the coefficients, float32 as the iteration carries them:
    the margins see them unrounded, as the fused pass's do and by the same
    split (`parts.float32_parts`: the three-part product against a
    bfloat16 block, which keeps the matrix unit's bfloat16 path; a float32
    block has one part and contracts as it did) — a step taken at rounded
    coefficients never settles under tol. The gradient's sum takes the
    float32 residual x weight R by the same routine, ALL its parts of the
    block's dtype in one contraction against the block, the slabs added:
    the float32 product to the last bit on every backend (a float32 block:
    R itself, as it was). Left as the float32 product `xf.T @ R`, the
    chip's default precision cut R to ONE bfloat16 part inside the matrix
    unit, and that rounding floored a lane's delta at tol (PERF.md, PR 44);
    the fused pass takes the two leading parts (2^-17 |R|: what its budget
    of vector work allows) and hands the second part's sum back beside the
    first, which this body has no reason to. mean / std are column-padded
    to the Gram geometry's width; `acc0` are sums to go on from (the
    tileplane steps' carry) in place of zeros."""
    rc = _residual_curvature(loss)
    d_work, Lb = mean.shape[0], sel.shape[1]
    tiled, _, bt, tile_pairs = _tiling(d_work)
    hess_blocks = _gram_fns(tiled, d_work, Lb, bt, tile_pairs)[0]
    dtype = blocks[0].dtype
    Bparts = _parts.stacked_parts(B, dtype).T

    def body(acc, sl):
        x_blk, y_blk, w_blk, m_blk = sl             # m_blk [F, c]
        gA, hA, g0A, h0A, cA = acc
        # standardize on the fly; the low-precision cast keeps the
        # eta contraction on the bf16 MXU path exactly like the
        # materialized-Xs route
        xs_low = ((x_blk.astype(jnp.float32) - mean[None, :])
                  / std[None, :]).astype(x_blk.dtype)
        eta = _parts.slab_sum(
            jnp.matmul(xs_low, Bparts, preferred_element_type=jnp.float32),
            Lb, axis=1) + b0[None, :]
        r0, s0 = rc(eta, y_blk[:, None])            # [c, Lb]
        wlf = m_blk.T * w_blk[:, None]              # [c, F]
        wl = jnp.matmul(wlf, sel,
                        preferred_element_type=jnp.float32)  # [c, Lb]
        R = r0 * wl
        S = s0 * wl
        xf = xs_low.astype(jnp.float32)
        gA = gA + _parts.slab_sum(jnp.matmul(
            xs_low.T, _parts.stacked_parts(R.T, dtype).T,
            preferred_element_type=jnp.float32), Lb, axis=1).T
        hA = hA + hess_blocks(xf, S)
        cA = cA + jnp.matmul(S.T.astype(dtype), xs_low,
                             preferred_element_type=jnp.float32)
        return (gA, hA, g0A + R.sum(0), h0A + S.sum(0), cA), None

    if acc0 is None:
        acc0 = _shard_vary(_moments_acc0(Lb, d_work), axis_name)
    return jax.lax.scan(body, acc0, blocks)[0]


def _round_core(X, y, w, fold_masks, sel, l1, l2, B0, b00, mean, std,
                iters_budget, tol, *, loss, fit_intercept,
                axis_name: Optional[str] = None):
    """K Newton iterations for one compacted lane bucket, with a PER-LANE
    delta vector in the carry so the host can retire converged lanes
    between rounds.

    sel [F, Lb] maps each bucket lane to its fold (one-hot columns);
    all-zero columns are the ladder's inert padding lanes — their weights
    vanish, so they sit at B=0/delta=0 and never gate the early exit.
    B0/b00 carry the lanes' standardized-space state between rounds (the
    host unstandardizes once at the end); mean/std are applied on the fly
    per block, so no standardized [n, d] copy is materialized per round.
    glm_round_kernel(d, dtype, Lb) names the body of the pass over X (one
    Mosaic program, which reads X in the layout glm_x_tile(d) names, or an
    XLA scan over row blocks); the iteration around it is one.
    B is float32 in the carry AND in the pass: the margins xs' B + b0 see
    the coefficients the step updates, not their cast to the matrix's dtype
    (either body splits B into exact parts of that dtype), so the step is
    taken where the gradient was read, delta falls through tol, and a lane
    retires there and not at max_iter. The residual x weight R of the
    gradient's sum goes in at float32 precision too, as parts of that dtype
    (the fused pass its two leading ones, `pallas_glm.residual_parts`, the
    second's sum returned as `gA_low` and added here; the XLA body all of
    them): cut to one, its rounding floored delta AT tol on 20M rows of a
    null-tracked table and held a few lanes to max_iter (PERF.md, PR 44).
    The other operands of the matrix unit (the block, S x block) stay in
    the matrix's dtype.
    The while cond early-exits as soon as EVERY bucket lane's delta clears
    tol, so a round never burns budget on an already-converged bucket.
    Returns (B [Lb, d] standardized space, b0 [Lb], delta [Lb], iters)."""
    n, d = X.shape
    Lb = sel.shape[1]
    tiled, d_work, bt, tile_pairs = _tiling(d)
    fused = glm_round_kernel(d, X.dtype, Lb) == "pallas_fused"
    x_tile = glm_x_tile(d)
    if d_work > d:
        dp = d_work - d
        X = jnp.pad(X, ((0, 0), (0, dp)))
        B0 = jnp.pad(B0, ((0, 0), (0, dp)))
        mean = jnp.pad(mean, (0, dp))
        std = jnp.pad(std, (0, dp), constant_values=1.0)

    def allreduce(v):
        return jax.lax.psum(v, axis_name) if axis_name else v

    wsum_f = jnp.maximum(
        allreduce((fold_masks * w[None, :]).sum(1)), EPS)         # [F]
    wsum_l = jnp.maximum((wsum_f[:, None] * sel).sum(0), EPS)     # [Lb]

    # once a round program, outside the iteration: the vectors the kernel
    # reads beside X.T, or the XLA body's padded row blocks
    if fused:
        y_rows, w_rows = (pallas_glm.dense_rows(v) for v in (y, w))
    else:
        c = min(_ROW_BLOCK_WIDE if tiled else _row_block(d_work), n)
        blocks = _blocked(X, y, w, fold_masks, c)
    eye = jnp.eye(d_work, dtype=jnp.float32)
    assemble = _gram_fns(tiled, d_work, Lb, bt, tile_pairs)[1]

    def accumulate(B, b0):
        if fused:
            gA, hA, g0A, h0A, gA_low, cA = pallas_glm.glm_moments(
                X if x_tile == "cols_minor" else X.T, y_rows, w_rows,
                fold_masks, sel, B, b0, mean, std, loss=loss,
                x_tile=x_tile)
            moments = gA + gA_low, hA, g0A, h0A, cA
        else:
            moments = _moments_blocks(blocks, sel, B, b0, mean, std,
                                      loss=loss, axis_name=axis_name)
        # ONE collective an iteration: the five accumulators merge over
        # the mesh together (round_psum_bytes)
        return allreduce(moments)

    def cond(state):
        i, _, _, delta = state
        return (i < iters_budget) & (delta.max() > tol)

    def body(state):
        i, B, b0, _ = state
        B_new, b0_new, delta_vec = _newton_prox_update(
            B, b0, *accumulate(B, b0), wsum_l, l1, l2, eye, assemble,
            fit_intercept)
        return i + 1, B_new, b0_new, delta_vec

    state = (jnp.asarray(0, jnp.int32), B0.astype(jnp.float32),
             b00.astype(jnp.float32),
             jnp.full((Lb,), jnp.inf, jnp.float32))
    i, B, b0, delta = jax.lax.while_loop(cond, body, state)
    return B[:, :d], b0, delta, i


@functools.partial(jax.jit, static_argnames=("loss", "fit_intercept"))
def sweep_glm_round(X: jax.Array, y: jax.Array, w: jax.Array,
                    fold_masks: jax.Array, sel: jax.Array, l1: jax.Array,
                    l2: jax.Array, B0: jax.Array, b00: jax.Array,
                    mean: jax.Array, std: jax.Array, iters_budget,
                    tol, *, loss: str, fit_intercept: bool = True
                    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One retirement round for a compacted lane bucket (see _round_core).
    Compiled per (n, d, F, bucket) shape; iters_budget/tol are traced."""
    return _round_core(X, y, w, fold_masks, sel, l1, l2, B0, b00, mean,
                       std, iters_budget, tol, loss=loss,
                       fit_intercept=fit_intercept, axis_name=None)


@functools.lru_cache(maxsize=None)
def _sharded_round_fn(mesh, loss, fit_intercept):
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import BATCH_AXIS

    def sweep_glm_round_sharded(X, y, w, fold_masks, sel, l1, l2, B0, b00,
                                mean, std, iters_budget, tol):
        return _round_core(X, y, w, fold_masks, sel, l1, l2, B0, b00,
                           mean, std, iters_budget, tol, loss=loss,
                           fit_intercept=fit_intercept,
                           axis_name=BATCH_AXIS)

    sm = _build_shard_map(
        sweep_glm_round_sharded, mesh,
        in_specs=(P(BATCH_AXIS, None), P(BATCH_AXIS), P(BATCH_AXIS),
                  P(None, BATCH_AXIS), P(None, None), P(None), P(None),
                  P(None, None), P(None), P(None), P(None), P(), P()),
        out_specs=(P(None, None), P(None), P(None), P()))
    return jax.jit(sm)


# both bake glm_round_kernel's answer in, so the Pallas switch clears them
# (the mesh form's programs hang off an lru_cache: the switch drops it)
pallas_hist.register_cache_consumer(sweep_glm_round)
_sharded_round_fn.clear_cache = _sharded_round_fn.cache_clear
pallas_hist.register_cache_consumer(_sharded_round_fn)


def glm_round_temp_bytes(X, y, w, fold_masks, bucket: int, *, loss: str,
                         fit_intercept: bool = True) -> int:
    """Bytes of temporaries the compiled one-device round program of this
    bucket holds beside its arguments (the compiler's `memory_analysis()`):
    the counter in which a padded or re-laid-out copy of X would show,
    whichever body runs the pass. Lowered from the arrays' shapes and
    compiled (a load, where the persistent cache holds the program the
    sweep ran): ask once, after a warm-up, not inside a timed job."""
    f32 = jnp.float32
    F, d = fold_masks.shape[0], X.shape[1]

    def shape(*dims, dtype=f32):
        return jax.ShapeDtypeStruct(dims, dtype)
    compiled = sweep_glm_round.lower(
        X, y, w, fold_masks, shape(F, bucket), shape(bucket), shape(bucket),
        shape(bucket, d), shape(bucket), shape(d), shape(d),
        shape(dtype=jnp.int32), shape(), loss=loss,
        fit_intercept=fit_intercept).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


def round_psum_bytes(bucket: int, d: int) -> int:
    """Bytes of the ONE collective an iteration of the sharded round
    program of this bucket issues over the mesh, from its static shape:
    the psum of the gradient, the Gram (or its tile pairs), its border and
    the two intercept sums together, float32. The round's other collective,
    the fold weight sums, is once a program and [F] floats."""
    tiled, d_work, bt, tile_pairs = _tiling(d)
    gram = len(tile_pairs) * bt * bt if tiled else d_work * d_work
    return 4 * bucket * (2 * d_work + gram + 2)


# -- tileplane source route (X streamed from disk, never resident) -----------

@functools.partial(jax.jit, donate_argnums=(0,))
def _source_prep_step(carry, xt, yt, wt, mt):
    """Streamed prep-pass step: global-weight column moments via an exact
    Chan tile merge (one-pass raw E[x^2] would cancel catastrophically in
    f32 for large-mean columns — same rationale as _psum_moments'
    two-pass form, restated per tile) plus the per-fold weight sums. The
    carry is donated: one device-resident accumulator for the pass."""
    cnt, mean, m2, wsum_f = carry
    xf = xt.astype(jnp.float32)
    c_t = wt.sum()
    safe = jnp.maximum(c_t, EPS)
    mean_t = (xf * wt[:, None]).sum(0) / safe
    m2_t = (((xf - mean_t[None, :]) ** 2) * wt[:, None]).sum(0)
    n = cnt + c_t
    nsafe = jnp.maximum(n, EPS)
    delta = mean_t - mean
    return (n, mean + delta * (c_t / nsafe),
            m2 + m2_t + delta * delta * (cnt * c_t / nsafe),
            wsum_f + (mt * wt[:, None]).sum(0))


@functools.partial(jax.jit, static_argnames=("loss",), donate_argnums=(0,))
def _source_round_step(carry, xt, yt, wt, mt, B, b0, sel, mean, std, *,
                       loss: str):
    """One fixed-shape tile's contribution to the round accumulators
    (g [Lb, d_work], Hessian blocks, intercept sums) — _moments_blocks,
    the resident rounds' XLA body, over one tile, standardizing on the fly.
    B/b0/sel/mean/std are per-PASS constants (mean/std column-padded to
    d_work by the driver); the donated carry is the pass's only
    accumulator. mt is [c, F] row-major (the natural source layout)."""
    d_work = mean.shape[0]
    if d_work > xt.shape[1]:
        xt = jnp.pad(xt, ((0, 0), (0, d_work - xt.shape[1])))
    c = min(_ROW_BLOCK_WIDE if _tiling(d_work)[0] else _row_block(d_work),
            xt.shape[0])
    return _moments_blocks(_blocked(xt, yt, wt, mt.T, c), sel, B, b0,
                           mean, std, loss=loss, acc0=carry)


@functools.partial(jax.jit, static_argnames=("fit_intercept",))
def _source_round_update(gA, hA, g0A, h0A, cA, B, b0, wsum_l, l1, l2, *,
                         fit_intercept: bool):
    """The Newton/prox/intercept update from one streamed pass's merged
    accumulators — the SAME _newton_prox_update as every other route, so
    the streamed-source sweep cannot drift from the resident kernels.
    Returns (B_new, b0_new, delta [Lb])."""
    d_work = B.shape[1]
    Lb = B.shape[0]
    tiled, _, bt, tile_pairs = _tiling(d_work)
    _, assemble, _ = _gram_fns(tiled, d_work, Lb, bt, tile_pairs)
    eye = jnp.eye(d_work, dtype=jnp.float32)
    return _newton_prox_update(B, b0, gA, hA, g0A, h0A, cA, wsum_l, l1, l2,
                               eye, assemble, fit_intercept)


def _new_round_state(L: int, d: int, n_classes: int = 0) -> Dict[str, Any]:
    """Resumable state of a round driver; the multinomial driver's
    coefficients carry a class axis ([L, d, K], [L, K])."""
    tail = (n_classes,) if n_classes else ()
    return {"B": np.zeros((L, d) + tail, np.float32),
            "b0": np.zeros((L,) + tail, np.float32),
            "delta": np.full(L, np.inf, np.float32),
            "iters": np.zeros(L, np.int32),
            "retired": np.zeros(L, bool), "warmed": False,
            "rounds": 0, "data_passes": 0, "lane_passes": 0,
            "padded_lane_passes": 0,
            "active_per_round": [], "iters_per_round": [],
            "bucket_sizes": []}


def _record_round(st, idx, Lb: int, Bb, b0b, db, it: int) -> None:
    """Land one finished round of bucket `Lb` (host arrays; rows past
    len(idx) are the ladder's padding) in the lanes `idx`, and bill it."""
    k = len(idx)
    st["B"][idx] = Bb[:k]
    st["b0"][idx] = b0b[:k]
    st["delta"][idx] = db[:k]
    st["iters"][idx] += it
    st["rounds"] += 1
    st["data_passes"] += it
    # useful work (active lanes) vs executed work (the padded bucket the
    # device actually ran) — the FLOP model bills the latter
    st["lane_passes"] += it * k
    st["padded_lane_passes"] += it * Lb
    st["active_per_round"].append(k)
    st["iters_per_round"].append(it)
    st["bucket_sizes"].append(Lb)


def _retire(st, idx, tol: float, max_iter: int) -> None:
    """THE retire rule: a lane stops at its own delta <= tol or at the
    iteration cap, its coefficients frozen."""
    st["retired"][idx] = (st["delta"][idx] <= tol) \
        | (st["iters"][idx] >= max_iter)


def _run_rounds(st, run_round: Callable, round_iters: int, max_iter: int,
                tol: float, on_round: Optional[Callable]) -> None:
    """The retirement loop of both host drivers: while a lane is active,
    `run_round(active, budget)` (the driver's own programs; it ends in
    _record_round), retire, checkpoint hook. The budget never carries a
    lane past max_iter."""
    while True:
        active = np.flatnonzero(~st["retired"])
        if active.size == 0:
            return
        run_round(active, max(1, min(
            round_iters, int((max_iter - st["iters"][active]).min()))))
        _retire(st, active, tol, max_iter)
        if on_round is not None:
            on_round(st)


def _rounds_info(st, tol: float, max_iter: int,
                 intercept_sweeps: int = 0) -> Dict[str, Any]:
    """The convergence telemetry every round driver reports.
    `intercept_sweeps`: the inner sweeps of the update that steps B and the
    intercept together (`_newton_prox_update`: INTERCEPT_SWEEPS where the
    binary rounds fit an intercept; 0 where none is fitted and in the
    drivers whose update is another)."""
    return {"glm_rounds": int(st["rounds"]),
            "data_passes": int(st["data_passes"]),
            "lane_passes": int(st["lane_passes"]),
            "padded_lane_passes": int(st["padded_lane_passes"]),
            "lanes_total": int(st["retired"].shape[0]),
            "lanes_retired": int((st["delta"] <= tol).sum()),
            "lanes_at_cap": int(((st["delta"] > tol)
                                 & (st["iters"] >= max_iter)).sum()),
            "active_per_round": [int(v) for v in st["active_per_round"]],
            "iters_per_round": [int(v) for v in st["iters_per_round"]],
            "bucket_sizes": [int(v) for v in st["bucket_sizes"]],
            "intercept_sweeps": int(intercept_sweeps)}


def sweep_glm_streamed_rounds(X, y, w, fold_masks, regs, alphas, *,
                              loss: str, max_iter: int = 50,
                              tol: float = 1e-6, fit_intercept: bool = True,
                              standardize: bool = True, mesh=None,
                              round_iters: int = ROUND_ITERS_DEFAULT,
                              warm_start: bool = True,
                              warm_seed: Optional[Tuple] = None,
                              state: Optional[Dict[str, Any]] = None,
                              on_round: Optional[Callable] = None
                              ) -> Tuple[np.ndarray, np.ndarray,
                                         Dict[str, Any]]:
    """Host-driven convergence-aware streamed sweep for the IRLS losses.

    Runs `sweep_glm_round` (`round_iters` Newton iterations per jitted
    round); after each round, lanes whose own delta cleared `tol` — or
    that exhausted `max_iter` — RETIRE with their coefficients frozen, and
    the survivors compact into the next round's power-of-two bucket
    (`bucket_lanes`).
    When `warm_start`, round 0 fits only each fold's
    strongest-regularization lane and seeds the rest of the fold from it
    (glmnet-style pathwise continuation), so low-reg lanes start near
    their optimum instead of at zero.

    `warm_seed` is the SAME continuation applied ACROSS TIME instead of
    across the regularization path (the retrain controller's refit):
    ``(beta_raw [d], b0_raw)`` — a previously-fitted model's RAW-unit
    coefficients seed EVERY lane (converted into this sweep's
    standardized space once mean/std are known) and replace the
    pathwise round 0, so a refit over shifted data starts near the
    serving model's optimum. Ignored when the dimension disagrees with
    this sweep's `d` (the vectorization changed — cold start is the
    only honest option) or when a resumed `state` already carries
    coefficients.

    X/y/w/fold_masks are device arrays (pre-sharded when `mesh` is given,
    exactly like sweep_glm_squared_gram_sharded's contract) — OR X is a
    `parallel.tileplane.RowSource` whose chunks yield
    (x [c, d], y [c], w [c], fold_masks [c, F]) with y/w/fold_masks
    passed as None: then every data pass (the standardization prep pass
    and each Newton iteration of each round) streams tiles from the
    source through the double-buffered tileplane — X is never resident,
    so the sweep runs at data sizes no HBM holds, re-reading disk once
    per iteration. `state`/`on_round`
    are the round-granular checkpoint hooks
    (automl/tuning/checkpoint.RoundCheckpoint): `on_round(state)` fires
    after every retirement boundary with the full resumable state dict,
    and passing that dict back as `state` resumes bit-identically.

    Returns (B [F, G, d] f32 RAW units, b0 [F, G], info) where info holds
    the convergence telemetry (glm_rounds, data_passes, lane_passes,
    lanes_retired, active_per_round, iters_per_round, bucket_sizes)."""
    from ..parallel import tileplane as TP

    regs = np.asarray(regs, np.float32)
    alphas = np.asarray(alphas, np.float32)
    src_mode = isinstance(X, TP.RowSource)
    if src_mode:
        if mesh is not None:
            raise ValueError("mesh and RowSource are exclusive: a source "
                             "sweep streams tiles to the default device")
        if any(a is not None for a in (y, w, fold_masks)):
            raise ValueError("with a RowSource, y/w/fold_masks ride the "
                             "source chunks — pass them as None")
        probe = X.peek()
        d = int(probe[0].shape[1])
        F = int(probe[3].shape[1])
        tile_rows = TP.tile_rows_for(4 * (d + F + 2), X.n_rows)
        # ring depth resolved ONCE for the whole sweep (prep pass +
        # every Newton round) — per-round re-resolution could let a
        # mid-sweep env/corpus change vary the ring between rounds,
        # and one sweep should run one configuration end to end
        prefetch = TP.tile_prefetch_depth()
    else:
        if _mesh_is_mp(mesh) and not _is_global_array(X):
            # multi-process resume/round driver: host inputs are THIS
            # PROCESS's rows (same landing contract as the sharded
            # sweeps); the host-driven retirement loop below is
            # deterministic on replicated round outputs, so every
            # process takes identical retire/compact decisions
            X, y, w, fold_masks = _land_rows_multihost(mesh, X, y, w,
                                                       fold_masks)
        F = int(fold_masks.shape[0])
        d = int(X.shape[1])
    Gn = int(regs.shape[0])
    L = F * Gn
    K = max(int(round_iters), 1)
    max_iter = int(max_iter)
    tol_f = float(tol)

    wsum_f_h = None
    if src_mode:
        # ONE streamed prep pass: exact Chan column moments + per-fold
        # weight sums (the resident path computes these per round from
        # the resident fold masks; here they are pass-invariant, so
        # hoisting them costs a single extra read of the stream)
        d_work = _tiling(d)[1]
        prep0 = (jnp.asarray(0.0, jnp.float32), jnp.zeros(d, jnp.float32),
                 jnp.zeros(d, jnp.float32), jnp.zeros(F, jnp.float32))
        (cnt, mu, m2, wsum_f_dev), _ = TP.run_tileplane(
            X, _source_prep_step, prep0, tile_rows=tile_rows,
            label="glm_prep", prefetch=prefetch)
        # host-side fold weight sums; device tiles stay f32
        wsum_f_h = np.maximum(np.asarray(
            wsum_f_dev, np.float64), EPS)  # tmoglint: disable=TPU003  host-only
        if standardize:
            var = jnp.maximum(m2 / jnp.maximum(cnt, EPS), EPS)
            mean = jnp.pad(mu, (0, d_work - d))
            std = jnp.pad(jnp.sqrt(var), (0, d_work - d),
                          constant_values=1.0)
        else:
            mean = jnp.zeros(d_work, jnp.float32)
            std = jnp.ones(d_work, jnp.float32)
    elif standardize:
        if mesh is None:
            mean, std = glm_standardize_stats(X, w)
        else:
            mean, std = _sharded_stats_fn(mesh)(X, w)
    elif _mesh_is_mp(mesh):
        from ..parallel import multihost as MH
        mean = MH.replicated_global(np.zeros(d, np.float32), mesh)
        std = MH.replicated_global(np.ones(d, np.float32), mesh)
    else:
        mean = jnp.zeros(d, jnp.float32)
        std = jnp.ones(d, jnp.float32)

    lane_fold = np.repeat(np.arange(F, dtype=np.int64), Gn)
    l1v = np.tile(regs * alphas, F).astype(np.float32)
    l2v = np.tile(regs * (1.0 - alphas), F).astype(np.float32)
    st = state if state is not None else _new_round_state(L, d)

    warm_seeded = False
    if (warm_seed is not None and not st["warmed"]
            and not st["retired"].any() and int(st["iters"].max()) == 0):
        seed_b = np.asarray(warm_seed[0], np.float32).reshape(-1)
        if seed_b.shape[0] == d:
            # across-time continuation: convert the RAW-unit seed into
            # THIS sweep's standardized space (st["B"] lives there; the
            # final unstandardize below inverts exactly this map)
            mean_h = np.asarray(mean, np.float32)[:d]
            std_h = np.asarray(std, np.float32)[:d]
            b_std = seed_b * std_h
            st["B"][:] = b_std[None, :]
            st["b0"][:] = (float(warm_seed[1])
                           + float((seed_b * mean_h).sum()))
            # the seed plays round 0's role: every lane starts near a
            # known-good solution, so the pathwise warm round is skipped
            st["warmed"] = True
            warm_seeded = True

    # what the mesh costs a round: 0 collectives on one device
    shards = _mesh_batch_count(mesh)
    psums = int(shards > 1)
    # which update the rounds take (`_newton_prox_update`)
    sweeps = INTERCEPT_SWEEPS if fit_intercept else 0

    # span hook: each retirement round is one child span of whatever the
    # validator opened (run -> sweep_fit -> sweep_round), carrying the
    # bucket/active shape — the trace view of the bucket-ladder story, and
    # the recompile tracker's attribution unit for round programs
    from ..utils.metrics import collector as _collector
    from ..parallel import podtrace as _podtrace

    def _run_source_round(sel, l1b, l2b, B0, b00, budget):
        """One retirement round for a compacted bucket, each Newton
        iteration = one double-buffered streamed pass over the source
        (accumulate) + one tiny jitted update, with the same
        per-iteration early exit as the resident while_loop's cond."""
        d_work = int(mean.shape[0])
        Lb = sel.shape[1]
        wsum_l = jnp.asarray(np.maximum(
            (wsum_f_h[:, None] * sel).sum(0), EPS).astype(np.float32))
        sel_j = jnp.asarray(sel)
        l1j = jnp.asarray(l1b)
        l2j = jnp.asarray(l2b)
        B = jnp.asarray(np.pad(B0, ((0, 0), (0, d_work - d))))
        b0j = jnp.asarray(b00)
        it = 0
        delta = np.full(Lb, np.inf, np.float32)
        for _ in range(int(budget)):
            def step(carry, xt, yt, wt, mt, B=B, b0j=b0j):
                return _source_round_step(carry, xt, yt, wt, mt, B, b0j,
                                          sel_j, mean, std, loss=loss)

            sums, _ps = TP.run_tileplane(
                X, step, _moments_acc0(Lb, d_work),
                tile_rows=tile_rows, label="glm_round",
                prefetch=prefetch)
            B, b0j, delta_dev = _source_round_update(
                *sums, B, b0j, wsum_l, l1j, l2j,
                fit_intercept=bool(fit_intercept))
            it += 1
            with _collector.trace_span("round_fetch", kind="host_step"):
                # [Lb]: the round's only fetch
                delta = np.asarray(delta_dev)
            if float(delta.max()) <= tol_f:
                break
        return np.asarray(B)[:, :d], np.asarray(b0j), delta, it

    def pass_body(Lb):
        # the tileplane steps are XLA programs of their own
        return "xla_blocks" if src_mode \
            else glm_round_kernel(d, X.dtype, Lb)

    bodies = set()

    def run_round(idx, budget):
        k = len(idx)
        Lb = bucket_lanes(k)
        mp_round = (not src_mode) and _mesh_is_mp(mesh)
        kernel = pass_body(Lb)
        bodies.add(kernel)
        with _collector.trace_span(
                f"glm_round[{Lb}]", kind="sweep_round", bucket=int(Lb),
                active=int(k), iters_budget=int(budget), kernel=kernel,
                body=kernel, x_tile=glm_x_tile(d), shards=shards,
                psums=psums, psum_bytes=psums * round_psum_bytes(Lb, d),
                intercept_sweeps=sweeps), \
                _podtrace.pod_round(st["rounds"], bucket=int(Lb),
                                    active=int(k)):
            args = None
            with _collector.trace_span("round_prep", kind="host_step"), \
                    _podtrace.compute("glm_prep", lanes=int(Lb)):
                sel = np.zeros((F, Lb), np.float32)
                sel[lane_fold[idx], np.arange(k)] = 1.0
                l1b = np.zeros(Lb, np.float32)
                l1b[:k] = l1v[idx]
                # inert pads get l2=1 so their (zero-data) Hessian stays
                # well-conditioned; their B stays exactly 0 from the
                # zero init
                l2b = np.ones(Lb, np.float32)
                l2b[:k] = l2v[idx]
                B0 = np.zeros((Lb, d), np.float32)
                B0[:k] = st["B"][idx]
                b00 = np.zeros(Lb, np.float32)
                b00[:k] = st["b0"][idx]
                if not src_mode:
                    if mp_round:
                        from ..parallel import multihost as MH

                        def land(a, dt):
                            return MH.replicated_global(
                                np.asarray(a, dt), mesh)
                    else:
                        def land(a, dt):
                            return jnp.asarray(a, dt)
                    args = (X, y, w, fold_masks, land(sel, np.float32),
                            land(l1b, np.float32), land(l2b, np.float32),
                            land(B0, np.float32), land(b00, np.float32),
                            mean, std, land(budget, np.int32),
                            land(tol_f, np.float32))

            def fetch(out):
                with _collector.trace_span("round_fetch",
                                           kind="host_step"):
                    # the host waits here for the round's program
                    return (np.asarray(out[0]), np.asarray(out[1]),
                            np.asarray(out[2]), int(out[3]))

            if src_mode:
                Bb, b0b, db, it = _run_source_round(sel, l1b, l2b, B0,
                                                    b00, budget)
            elif mesh is None:
                Bb, b0b, db, it = fetch(sweep_glm_round(
                    *args, loss=loss, fit_intercept=fit_intercept))
            else:
                # the psum lives INSIDE the jitted round program, so the
                # collective window on the multi-process path is program
                # call + result fetch: a victim rank's wall here is the
                # barrier wait the skew table attributes (single-process
                # meshes record the same window as plain compute)
                bracket = (_podtrace.collective if mp_round
                           else _podtrace.compute)
                with bracket("glm_round", rows=int(X.shape[0]),
                             feat=int(d), lanes=int(Lb),
                             iters=int(budget)):
                    Bb, b0b, db, it = fetch(_sharded_round_fn(
                        mesh, loss, bool(fit_intercept))(*args))
            with _podtrace.compute("glm_retire", active=int(k)):
                _record_round(st, idx, Lb, Bb, b0b, db, it)

    if (warm_start and not st["warmed"] and Gn > 1
            and not st["retired"].any() and int(st["iters"].max()) == 0):
        g_star = int(np.argmax(regs))
        warm_idx = np.arange(F, dtype=np.int64) * Gn + g_star
        run_round(warm_idx, min(K, max_iter))
        # pathwise continuation: every other lane of the fold starts at
        # its fold's strongest-regularization solution instead of zero
        for f in range(F):
            rows = np.arange(f * Gn, (f + 1) * Gn)
            others = rows[rows != warm_idx[f]]
            st["B"][others] = st["B"][warm_idx[f]]
            st["b0"][others] = st["b0"][warm_idx[f]]
        _retire(st, warm_idx, tol_f, max_iter)
        st["warmed"] = True
        if on_round is not None:
            on_round(st)

    _run_rounds(st, run_round, K, max_iter, tol_f, on_round)

    # host-side unstandardize in f32 (source-mode mean/std are
    # column-padded to d_work; the pads are inert — slice back to d)
    mean_h = np.asarray(mean, np.float32)[:d]
    std_h = np.asarray(std, np.float32)[:d]
    B = st["B"] / std_h[None, :]
    b0 = st["b0"] - (B * mean_h[None, :]).sum(1, dtype=np.float32)
    info = {"route": "streamed", "kernel": "rounds",
            "driver": "tileplane" if src_mode else "resident",
            # the body of the rounds' pass over X (both, where a bucket's
            # sums outgrew the kernel's VMEM and a smaller one's did not)
            "round_kernel": "+".join(sorted(
                bodies or {pass_body(bucket_lanes(L))})),
            **_rounds_info(st, tol_f, max_iter, sweeps),
            "warm_start": bool(st["warmed"]),
            "warm_seeded": warm_seeded,
            # what the mesh cost the rounds: one collective an iteration
            # and one a round program (the fold weight sums)
            "psums": psums * (st["data_passes"] + st["rounds"]),
            "psum_bytes": psums * sum(
                int(it) * round_psum_bytes(int(Lb), d) for it, Lb in
                zip(st["iters_per_round"], st["bucket_sizes"]))}
    return B.reshape(F, Gn, d), b0.reshape(F, Gn), info


def sweep_scores_fold(X: jax.Array, B_f: jax.Array, b0_f: jax.Array,
                      exact: bool = False) -> jax.Array:
    """[n, Gc] margins for one fold's grid chunk: one MXU contraction
    (bf16 X stays bf16; f32 accumulation). Past TRI_MAX_D columns the
    coefficients go in as two bf16 parts (`_wide_contract`): a margin is then a
    sum over thousands of products, and coefficients rounded to bf16 would
    move it by more than the fit resolves. `exact`: the contraction sees
    the float32 coefficients, as their exact parts of X's dtype
    (`parts.float32_parts`; a float32 matrix at HIGHEST) — for a
    metric that is not invariant to their rounding (regression: the
    held-out pass's `validators._heldout_regression` takes the same)."""
    if exact:
        parts = _parts.stacked_parts(B_f, X.dtype)
        return _parts.slab_sum(jnp.matmul(
            X, parts.T, preferred_element_type=jnp.float32,
            precision=_HIGHEST if X.dtype == jnp.float32 else None),
            B_f.shape[0], axis=1) + b0_f[None, :]
    if X.shape[1] > TRI_MAX_D:
        return _wide_contract(B_f, X.T).T + b0_f[None, :]
    return jnp.matmul(X, B_f.T.astype(X.dtype),
                      preferred_element_type=jnp.float32) + b0_f[None, :]


# -- streamed multinomial route (softmax loss, Boehning's bound) --------------
#
# The multiclass sibling of the IRLS rounds above, for ONE device and a
# resident matrix (a mesh or a RowSource keeps the vmapped route). The
# solver is ops/glm.fit_softmax's, lane for lane: Boehning's bound makes the
# curvature the CONSTANT A_l = 0.5 (1 - 1/K) Xs^T W_f Xs / sum(W_f) +
# (l2_l + 1e-6) I, so the weighted Gram is built once per FOLD and sweep
# (`mlr_gram_factor`), Cholesky-factored once per lane, and every iteration
# is one pass over X: tile-wise logits -> softmax -> residual -> gradient,
# nothing [n, K] resident. Departures from fit_softmax: standardization uses
# the global weights (module docstring), a lane stops at its own delta <= tol
# (fit_softmax always runs max_iter), and no pathwise warm round — an MM
# step is cheap next to a Newton step, and a cold start keeps a lane's
# iterates those of fit_softmax.

# bytes of one [lanes * K, c] f32 logits block of the XLA row-block loops:
# the metric pass's (validators._streamed_confusion, on every backend) and
# the rounds' own where there is no Mosaic (_mlr_gradient_blocks: the CPU,
# TMOG_NO_PALLAS, a 128-column matrix); on the chip the rounds run
# ops/pallas_softmax.mlr_gradient, which sizes its own tiles. A softmax
# holds a handful of such blocks, and on the v5e a loop is quickest while
# they all stay in VMEM (128 MiB): the rounds' loop ran 0.108 s an
# iteration at 16 MB (c = 8 192 for 16 lanes x 32 classes) and 0.109 at
# 32 MB at 25M x 64, 0.751 at 64 MB (PERF.md, PR 25). 16 MB keeps a factor
# 2 from the edge.
_MLR_BLOCK_BYTES = 16 << 20


def _mlr_row_block(lanes_k: int, n: int) -> int:
    c = _ROW_BLOCK
    while c > 1_024 and c * lanes_k * 4 > _MLR_BLOCK_BYTES:
        c //= 2
    return min(c, n)


def streamed_mlr_route_ok(d: int, lanes: int, n_classes: int,
                          budget_bytes: float) -> bool:
    """Can the multinomial rounds take a (d features, lanes, K classes)
    sweep within `budget_bytes`? The Gram step is the narrow einsum, so
    d <= TRI_MAX_D; the transients are ~8 blocks of [bucket * K, c] f32 at
    the smallest row block."""
    if d > TRI_MAX_D:
        return False
    return bucket_lanes(lanes) * n_classes * 1_024 * 4.0 * 8.0 \
        <= budget_bytes


def _mlr_blocks(n: int, c: int, XT, *rows):
    """(number of row blocks, take(i)) over the resident matrix WITHOUT a
    padded, reshaped or re-laid-out copy of it. XT is X.T [d, n]: on the
    chip a [n, d] matrix of few columns lives rows-minor, so its transpose
    is the layout it already has, and a block is one dynamic slice along
    the minor axis. take(i) -> (xT [d, c], fresh [c], row arrays' blocks):
    the last block starts early (clamped) and `fresh` zeroes the rows the
    block before already had; multiply it into the block's weights.
    `rows` are arrays whose LAST axis is the row axis."""
    nb = -(-n // c)

    def take(i):
        start = jnp.minimum(i * c, n - c)
        fresh = ((start + jnp.arange(c)) >= i * c).astype(jnp.float32)
        cut = [jax.lax.dynamic_slice_in_dim(a, start, c, axis=a.ndim - 1)
               for a in (XT,) + rows]
        return (cut[0], fresh) + tuple(cut[1:])
    return nb, take


def x_row_blocks(X, c: int):
    """block(i) -> [c, d]: the rows `_mlr_blocks(n, c, ...)`'s take(i)
    cuts (the same clamped start), read from the resident matrix in place:
    a slice along the axis the chip keeps major — rows of X where the
    width is whole 128-column groups (`glm_x_tile`), columns of X.T
    elsewhere. Compiled for a v5e, the other slice of a 25M x 128 matrix
    makes the program copy X into the other layout, 6.4 GB."""
    n, d = X.shape
    cols_minor = glm_x_tile(d) == "cols_minor"
    XT = None if cols_minor else X.T

    def block(i):
        start = jnp.minimum(i * c, n - c)
        if cols_minor:
            return jax.lax.dynamic_slice_in_dim(X, start, c, axis=0)
        return jax.lax.dynamic_slice_in_dim(XT, start, c, axis=1).T
    return block


def _mlr_coefficient_parts(B, dtype):
    """(Bt_hi, Bt_lo) [lanes * K, d] in `dtype`: the multinomial
    coefficients B [lanes, d, K], float32, as their two leading parts of the
    matrix's dtype (`parts.float32_parts`). A bfloat16 X keeps the matrix
    unit's bfloat16 path, and the second part gives back what rounding B to
    bfloat16 would lose (4e-3 relative, the same for every row, so it would
    shift the fixed point). Bt_lo is None for a float32 matrix, which has
    one part."""
    lanes, d, K = B.shape
    cut = _parts.float32_parts(B.transpose(0, 2, 1).reshape(lanes * K, d),
                               dtype, 2)
    return (*(p.astype(dtype) for p in cut), None)[:2]


def mlr_logits_t(xT, Bt_hi, Bt_lo):
    """[lanes * K, c] f32 logits of one row block xT [d, c], rows on the
    minor axis (the softmax then reduces over sublanes, the block stays
    dense). The two parts of the coefficients are ONE contraction over
    2 d ([hi | lo] against the block stacked on itself): two contractions
    write the [lanes * K, c] result twice, and on the v5e the write, not
    the MXU, is what a d = 64 contraction costs."""
    if Bt_lo is None:
        return jnp.matmul(Bt_hi, xT, preferred_element_type=jnp.float32)
    return jnp.matmul(jnp.concatenate([Bt_hi, Bt_lo], axis=1),
                      jnp.concatenate([xT, xT], axis=0),
                      preferred_element_type=jnp.float32)


def _standardized_t(xT, mean, inv_std, dtype):
    """The block standardized on the fly, back in the matrix's dtype (the
    contractions stay on the bf16 MXU path, like the binary rounds)."""
    return ((xT.astype(jnp.float32) - mean[:, None]) * inv_std[:, None]) \
        .astype(dtype)


@functools.partial(jax.jit, static_argnames=("n_classes",))
def mlr_gram_factor(X: jax.Array, w: jax.Array, fold_masks: jax.Array,
                    mean: jax.Array, std: jax.Array, lane_fold: jax.Array,
                    l2: jax.Array, *, n_classes: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """Once per sweep: ONE pass builds the F per-fold weighted Grams of the
    standardized matrix; every lane's bound matrix A_l is its fold's Gram
    plus its own ridge, factored here and never again. Returns (chol
    [L, d, d] lower, hdiag [L, d])."""
    n, d = X.shape
    F = fold_masks.shape[0]
    c = min(_ROW_BLOCK, n)
    nb, take = _mlr_blocks(n, c, X.T, w, fold_masks)
    inv_std = 1.0 / std

    def body(i, Gf):
        xT, fresh, w_blk, m_blk = take(i)
        xs = _standardized_t(xT, mean, inv_std, X.dtype)         # [d, c]
        wl = m_blk * (w_blk * fresh)[None, :]                    # [F, c]
        xw = (wl[:, None, :] * xs.astype(jnp.float32)[None]) \
            .astype(X.dtype).reshape(F * d, c)
        return Gf + jnp.matmul(xw, xs.T,
                               preferred_element_type=jnp.float32)

    Gf = jax.lax.fori_loop(0, nb, body, jnp.zeros((F * d, d), jnp.float32))
    wsum_f = jnp.maximum((fold_masks * w[None, :]).sum(1), EPS)
    coef = 0.5 * (1.0 - 1.0 / n_classes)
    A = coef * (Gf.reshape(F, d, d) / wsum_f[:, None, None])[lane_fold] \
        + (l2[:, None, None] + 1e-6) * jnp.eye(d, dtype=jnp.float32)[None]
    hdiag = jnp.maximum(jnp.diagonal(A, axis1=1, axis2=2), EPS)
    return jnp.linalg.cholesky(A), hdiag


def round_kernel(d: int, tile_rows: int = 128) -> str:
    """Which body a round's pass over a [n, d] matrix runs, for the round
    families whose fused body reads row tiles of X.T (the multinomial and
    the wide binary rounds and, through `glm_round_kernel`, the narrow
    binary rounds of a width that is no multiple of 128): "pallas_fused"
    (ops/pallas_softmax.mlr_gradient, ops/pallas_wide.wide_gradient,
    ops/pallas_glm.glm_moments: one Mosaic program a pass) where the
    backend has one, as the tree kernels choose theirs
    (pallas_hist.available()), else "xla_blocks" (_mlr_gradient_blocks,
    _wide_gradient_blocks, _moments_blocks). A matrix whose width fills its
    last 128-column group to within a sublane tile (a multiple of 128, or 1
    to 7 columns short of one) stays with the blocks HERE: rows-minor it
    would pad to the same size, so the chip keeps it columns-minor,
    X.T is not the layout it has and a program that read
    X.T would hold a transposed copy of it (compiled for a v5e: 6.4 GB at
    25M x 128, a whole copy at 121 to 127 columns; 64, 100, 120 and 4 104
    columns live rows-minor and are read in place). The narrow binary
    rounds have a tile form for a multiple of 128 (`glm_round_kernel`); the
    multinomial and the wide rounds, and 121 to 127 columns everywhere, do
    not yet. `tile_rows` is how many rows of X a grid step of the kernel
    can hold in VMEM at this width: under 128 there is no tile, and the
    blocks run (the narrow kernels tile any width they are routed;
    pallas_wide.tile_rows)."""
    if -(-d // 8) * 8 % 128 == 0 or tile_rows < 128 \
            or not pallas_hist.available():
        return "xla_blocks"
    return "pallas_fused"


def wide_round_kernel(d: int, dtype) -> str:
    """`round_kernel` for the wide rounds: the fused pass holds a [d, tile]
    tile of X.T in VMEM, and it is written for the two-part contraction of
    a bfloat16 matrix; a float32 matrix contracts at HIGHEST, which is
    another program (compiled for a v5e it spills 133 MB of registers at
    the tile bfloat16 runs), and stays with the blocks."""
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "xla_blocks"
    return round_kernel(d, pallas_wide.tile_rows(d))


def glm_x_tile(d: int) -> str:
    """Which tile form the binary rounds' fused pass reads a resident
    [n, d] matrix in (`pallas_glm.glm_moments`' `x_tile`), from the width
    alone: "cols_minor" ([rows, d] tiles of X itself, the columns on the
    lanes) where the width is whole 128-column groups, which the chip keeps
    columns-minor; "rows_minor" ([d, rows] tiles of X.T) everywhere else.
    (121 to 127 columns the chip keeps columns-minor too, and no tile form
    reads them in place: `round_kernel` leaves them to the blocks.)"""
    return "cols_minor" if d % 128 == 0 else "rows_minor"


def glm_round_kernel(d: int, dtype, lanes: int) -> str:
    """`round_kernel` for the binary IRLS rounds (_round_core): the fused
    pass (ops/pallas_glm.glm_moments) is written for the narrow Gram of a
    bfloat16 matrix, every lane's [d, d] block of it in one float32
    output block in VMEM, in two tile forms that the width chooses
    (`glm_x_tile`): up to 120 columns row tiles of X.T, at 128 columns
    [rows, 128] tiles of X turned over chunk by chunk in VMEM, each the
    layout the chip already keeps, so neither program holds a second copy
    of X. Past TRI_MAX_D columns the feature-tiled scan runs, a float32
    matrix contracts in another precision than the blocks give it (one
    bfloat16 pass), and a bucket whose sums outgrow the VMEM one kernel may
    claim has no tile (at 128 columns a 64-lane bucket holds 49 MiB and a
    128-lane one 89 MiB of a v5e's 96; 256 lanes do not fit): all three
    stay with the XLA blocks, as do 121 to 127 columns (columns-minor, and
    no whole 128-column group: `round_kernel`) and a backend without
    Mosaic."""
    if d > TRI_MAX_D or jnp.dtype(dtype) != jnp.bfloat16 \
            or pallas_glm.vmem_bytes(d, lanes) > pallas_hist._vmem_limit():
        return "xla_blocks"
    if d % 128 == 0:
        return "pallas_fused" if pallas_hist.available() else "xla_blocks"
    return round_kernel(d)


def _mlr_gradient_blocks(X, y, w, fold_masks, sel, Bt_hi, Bt_lo, b0, mean,
                         inv_std):
    """The round's pass over X as an XLA loop over row blocks, for a backend
    without Mosaic: (gA [Lb * K, d], g0A [Lb, K]), the sums over rows of
    R x xs' and of R (pallas_softmax.mlr_gradient is the same arithmetic in
    one program)."""
    n, d = X.shape
    Lb, K = b0.shape
    f32 = jnp.float32
    c = _mlr_row_block(Lb * K, n)
    nb, take = _mlr_blocks(n, c, X.T, y, w, fold_masks)
    classes = jnp.arange(K, dtype=f32)[:, None]                   # [K, 1]

    def body(i, acc):
        gA, g0A = acc
        xT, fresh, y_blk, w_blk, m_blk = take(i)    # m_blk [F, c]
        xs = _standardized_t(xT, mean, inv_std, X.dtype)
        # the barrier keeps the softmax three-dimensional: without it
        # XLA sinks the reshape below the elementwise ops, and the
        # per-(lane, row) and per-(class, row) operands can then only
        # be broadcast by writing four [Lb, K, c] blocks (a third of
        # the round's time on the v5e)
        z = jax.lax.optimization_barrier(
            mlr_logits_t(xs, Bt_hi, Bt_lo).reshape(Lb, K, c)) \
            + b0[:, :, None]
        e = jnp.exp(z - z.max(axis=1, keepdims=True))
        # one reciprocal a (lane, row), not one division a class
        P = e * (1.0 / e.sum(axis=1, keepdims=True))  # [Lb, K, c]
        Y = (y_blk[None, :] == classes).astype(f32)  # [K, c]
        # lane weights: exact for any w (sel is 0/1, the MXU's default
        # pass would round w to bf16)
        wl = jnp.matmul(sel.T, m_blk * (w_blk * fresh)[None, :],
                        precision=jax.lax.Precision.HIGHEST)  # [Lb, c]
        R = (P - Y[None]) * wl[:, None, :]
        gA = gA + jnp.einsum(
            "lkc,dc->lkd", R.astype(X.dtype), xs,
            preferred_element_type=f32).reshape(Lb * K, d)
        return gA, g0A + R.sum(axis=2)

    return jax.lax.fori_loop(
        0, nb, body,
        (jnp.zeros((Lb * K, d), f32), jnp.zeros((Lb, K), f32)))


def _mlr_round_core(X, y, w, fold_masks, sel, l1, l2, B0, b00, mean, std,
                    chol, hdiag, iters_budget, tol, *, fit_intercept):
    """Up to `iters_budget` bound-optimisation steps for one compacted lane
    bucket, the multinomial twin of _round_core: sel [F, Lb] maps bucket
    lanes to folds (all-zero columns are inert padding), B0 [Lb, d, K] /
    b00 [Lb, K] carry standardized-space state between rounds, chol/hdiag
    are the bucket's rows of mlr_gram_factor's result. round_kernel(d)
    names the body of the pass over X; the iteration around it is one. The
    while cond leaves as soon as every lane's delta clears tol. Returns
    (B, b0, delta [Lb], iters)."""
    n, d = X.shape
    Lb, _, K = B0.shape
    f32 = jnp.float32
    coef = 0.5 * (1.0 - 1.0 / K)
    wsum_f = jnp.maximum((fold_masks * w[None, :]).sum(1), EPS)   # [F]
    wsum_l = jnp.maximum((wsum_f[:, None] * sel).sum(0), EPS)     # [Lb]
    inv_std = 1.0 / std
    fused = round_kernel(d) == "pallas_fused"
    if fused:       # once a round program, outside the iteration
        y_rows, w_rows = (pallas_softmax.dense_rows(v) for v in (y, w))

    def accumulate(B, b0):
        Bt_hi, Bt_lo = _mlr_coefficient_parts(B, X.dtype)
        if fused:
            gA, g0A = pallas_softmax.mlr_gradient(
                X.T, y_rows, w_rows, fold_masks, sel, Bt_hi, Bt_lo, b0,
                mean, inv_std)
        else:
            gA, g0A = _mlr_gradient_blocks(
                X, y, w, fold_masks, sel, Bt_hi, Bt_lo, b0, mean, inv_std)
        return gA.reshape(Lb, K, d).transpose(0, 2, 1), g0A

    def cond(state):
        i, _, _, delta = state
        return (i < iters_budget) & (delta.max() > tol)

    def body(state):
        i, B, b0, _ = state
        gA, g0A = accumulate(B, b0)
        G = gA / wsum_l[:, None, None] + l2[:, None, None] * B
        B_new = B - jax.scipy.linalg.cho_solve((chol, True), G)
        B_new = (jnp.sign(B_new) * jnp.maximum(
            jnp.abs(B_new) - (l1[:, None] / hdiag)[:, :, None], 0.0))
        b0_new = b0 - (g0A / wsum_l[:, None]) / coef if fit_intercept \
            else b0
        delta = jnp.abs(B_new - B).max(axis=(1, 2)) \
            + jnp.abs(b0_new - b0).max(axis=1)
        return i + 1, B_new, b0_new, delta

    state = (jnp.asarray(0, jnp.int32), B0.astype(f32), b00.astype(f32),
             jnp.full((Lb,), jnp.inf, f32))
    i, B, b0, delta = jax.lax.while_loop(cond, body, state)
    return B, b0, delta, i


@functools.partial(jax.jit, static_argnames=("fit_intercept",))
def sweep_mlr_round(X: jax.Array, y: jax.Array, w: jax.Array,
                    fold_masks: jax.Array, sel: jax.Array, l1: jax.Array,
                    l2: jax.Array, B0: jax.Array, b00: jax.Array,
                    mean: jax.Array, std: jax.Array, chol: jax.Array,
                    hdiag: jax.Array, iters_budget, tol, *,
                    fit_intercept: bool = True):
    """One retirement round of the multinomial sweep (see _mlr_round_core).
    Compiled per (n, d, F, bucket, K) shape; iters_budget/tol are traced.
    The executable bakes round_kernel(d)'s answer in, so the Pallas
    switch clears this function's cache."""
    return _mlr_round_core(X, y, w, fold_masks, sel, l1, l2, B0, b00, mean,
                           std, chol, hdiag, iters_budget, tol,
                           fit_intercept=fit_intercept)


pallas_hist.register_cache_consumer(sweep_mlr_round)


def sweep_mlr_streamed_rounds(X, y, w, fold_masks, regs, alphas, *,
                              n_classes: int, max_iter: int = 50,
                              tol: float = 1e-6, fit_intercept: bool = True,
                              standardize: bool = True,
                              round_iters: int = ROUND_ITERS_DEFAULT,
                              state: Optional[Dict[str, Any]] = None,
                              on_round: Optional[Callable] = None
                              ) -> Tuple[np.ndarray, np.ndarray,
                                         Dict[str, Any]]:
    """Host-driven streamed sweep of multinomial logistic regression: the
    retirement loop of sweep_glm_streamed_rounds (_run_rounds: rounds of
    `round_iters` iterations, lanes retire at their own delta <= tol or at
    max_iter, survivors compact into `bucket_lanes` buckets, `state` /
    `on_round` checkpoint every boundary and resume bit-identically)
    around `sweep_mlr_round`,
    after ONE `mlr_gram_factor` pass. X/y/w/fold_masks are device arrays
    on one device; y holds class ids 0..n_classes-1 as floats.

    Returns (B [F, G, d, K] f32 RAW units, b0 [F, G, K], info)."""
    from ..utils.metrics import collector as _collector

    regs = np.asarray(regs, np.float32)
    alphas = np.asarray(alphas, np.float32)
    F, d, K = int(fold_masks.shape[0]), int(X.shape[1]), int(n_classes)
    Gn = int(regs.shape[0])
    L = F * Gn
    max_iter, tol_f = int(max_iter), float(tol)
    if standardize:
        mean, std = glm_standardize_stats(X, w)
    else:
        mean, std = jnp.zeros(d, jnp.float32), jnp.ones(d, jnp.float32)
    lane_fold = np.repeat(np.arange(F, dtype=np.int32), Gn)
    l1v = np.tile(regs * alphas, F).astype(np.float32)
    l2v = np.tile(regs * (1.0 - alphas), F).astype(np.float32)
    with _collector.trace_span("gram_factor", kind="host_step", folds=F,
                               lanes=L, classes=K):
        chol, hdiag = mlr_gram_factor(
            X, w, fold_masks, mean, std, jnp.asarray(lane_fold),
            jnp.asarray(l2v), n_classes=K)
    st = state if state is not None else _new_round_state(L, d, K)
    pass_body = round_kernel(d)

    def run_round(idx, budget):
        k = len(idx)
        Lb = bucket_lanes(k)
        with _collector.trace_span(
                f"mlr_round[{Lb}]", kind="sweep_round", bucket=int(Lb),
                active=int(k), iters_budget=int(budget), classes=K,
                kernel=pass_body):
            with _collector.trace_span("round_prep", kind="host_step"):
                # padding lanes: no fold (zero weights), lane idx[0]'s
                # factor; B = 0 is their fixed point
                lanes = np.full(Lb, idx[0], np.int64)
                lanes[:k] = idx
                sel = np.zeros((F, Lb), np.float32)
                sel[lane_fold[idx], np.arange(k)] = 1.0
                B0 = np.zeros((Lb, d, K), np.float32)
                B0[:k] = st["B"][idx]
                b00 = np.zeros((Lb, K), np.float32)
                b00[:k] = st["b0"][idx]
                pick = jnp.asarray(lanes)
                args = (X, y, w, fold_masks, jnp.asarray(sel),
                        jnp.asarray(l1v[lanes]), jnp.asarray(l2v[lanes]),
                        jnp.asarray(B0), jnp.asarray(b00), mean, std,
                        chol[pick], hdiag[pick],
                        jnp.asarray(budget, jnp.int32),
                        jnp.asarray(tol_f, jnp.float32))
            out = sweep_mlr_round(*args, fit_intercept=bool(fit_intercept))
            with _collector.trace_span("round_fetch", kind="host_step"):
                # the host waits here for the round's program
                Bb, b0b, db, it = (np.asarray(out[0]), np.asarray(out[1]),
                                   np.asarray(out[2]), int(out[3]))
        _record_round(st, idx, Lb, Bb, b0b, db, it)

    _run_rounds(st, run_round, int(round_iters), max_iter, tol_f, on_round)

    mean_h, std_h = np.asarray(mean, np.float32), np.asarray(std, np.float32)
    B = st["B"] / std_h[None, :, None]
    b0 = st["b0"] - (B * mean_h[None, :, None]).sum(1, dtype=np.float32)
    info = {"route": "streamed", "kernel": "mlr_rounds",
            "driver": "resident", "classes": K, "round_kernel": pass_body,
            **_rounds_info(st, tol_f, max_iter), "gram_passes": F}
    # every full read of X by the route's programs: the round iterations,
    # the Gram pass, the two passes of the moments
    info["data_passes"] += 1 + (2 if standardize else 0)
    return B.reshape(F, Gn, d, K), b0.reshape(F, Gn, K), info


def sweep_logits_fold_t(xT: jax.Array, B_f: jax.Array, b0_f: jax.Array
                        ) -> jax.Array:
    """[Gc, K, c] f32 logits of one row block xT [d, c] under one fold's
    grid chunk of multinomial coefficients B_f [Gc, d, K], b0_f [Gc, K]."""
    Gc, _, K = B_f.shape
    return mlr_logits_t(xT, *_mlr_coefficient_parts(B_f, xT.dtype)
                        ).reshape(Gc, K, -1) + b0_f[:, :, None]


# -- streamed wide route (binary logistic, d > TRI_MAX_D, one device) ---------
#
# Past TRI_MAX_D columns the IRLS rounds above cannot pay for themselves: a
# [d, d] Hessian a lane an iteration is n d^2 work and L d^2 memory. The wide
# rounds never form one. Boehning's bound p (1 - p) <= 1/4 and the fact that a
# fold's training rows are a subset of all rows give every lane of the sweep
# ONE constant curvature matrix,
#
#     H_l(B, b0) <= kappa_l [[Gs, 0], [0, W]],   kappa_l = 1 / (4 W_f),
#
# with Gs = sum_i w_i xs_i xs_i' the ALL-rows Gram of the standardised
# columns (built once a sweep by `wide_gram`), W = sum_i w_i and W_f the
# lane's fold's training weight. The off-diagonal block vanishes because the
# columns are centred on the all-rows mean. One outer iteration of a lane is
#
#   1. ONE pass over X for every lane at once: eta = X (B / std) + b0', the
#      residual r = m_f w (sigmoid(eta) - y), its moments X' r and sum r;
#      from them the exact gradient g of the lane's data term in the
#      standardised coordinates. X is read as it is: centre and scale are
#      applied to the coefficients going in and to the moments coming out.
#      Where the backend has Mosaic the pass is ONE program that holds a
#      tile of X in VMEM from the margins to the moments
#      (ops/pallas_wide.wide_gradient); elsewhere an XLA loop over row
#      blocks whose two contractions each read the block
#      (`wide_round_kernel` says which).
#   2. `_WIDE_INNER_STEPS` FISTA steps, from z = v = B and theta = 1, on the
#      bound's model  g'(z - B) + kappa_l/2 (z - B)' Gs (z - B) + l2/2 |z|^2
#      + l1 |z|_1  with step t_l = 1 / (kappa_l lam + l2), lam from
#      `wide_gram`'s power iteration: v-gradient, u = v - t grad, z+ = soft(u,
#      t l1), theta+ = (1 + sqrt(1 + 4 theta^2)) / 2, v+ = z+ + (theta - 1) /
#      theta+ (z+ - z). Each is one [lanes, d] x [d, d] product; no pass over
#      X, no factorisation. The prox is exact, so the fixed point is the
#      elastic-net optimum itself.
#   3. b0+ = b0 - 4 sum r / W (the bound's intercept step).
#
# from B = 0, b0 = 0 until the lane's delta = max |B+ - B| + |b0+ - b0| is
# <= tol or max_iter iterations are done. Upstream runs L-BFGS / OWL-QN; this
# is a departure (benchmark/reference_wide.py repeats it step for step).

#: FISTA steps on the bound's model per pass over X: each costs a [lanes, d]
#: x [d, d] float32 product (71 MB read at d = 4 224, ~0.1 ms on a v5e)
#: where the pass costs ~10 ms, so the model is solved nearly as far as it
#: is worth
_WIDE_INNER_STEPS = 16

#: power-iteration steps for the bound's largest eigenvalue, and the margin
#: laid over the Rayleigh quotient they end on (it approaches from below)
_WIDE_POWER_ITERS = 32
_WIDE_LAM_MARGIN = 1.05


def _wide_row_block(d: int, n: int) -> int:
    """Rows a block of the wide passes: at most 2^26 elements of X."""
    c = _ROW_BLOCK
    while c > 1_024 and c * d > (1 << 26):
        c //= 2
    return min(c, n)


def streamed_wide_route_ok(d: int, lanes: int, budget_bytes: float) -> bool:
    """Can the wide rounds take a (d features, lanes) sweep within
    `budget_bytes` beside the matrix? Three [d, d] float32 matrices a SWEEP
    (the raw Gram, the standardised one, a transient) and the per-block
    [rows, 2 x bucket] float32 margins and residuals; nothing grows with
    lanes x d x d."""
    if d <= TRI_MAX_D:
        return False
    return wide_footprint_bytes(d, lanes) <= budget_bytes


def wide_padded_cols(d: int) -> int:
    """Columns the chip's (8, 128)-tiled layout holds a [.., d] row in."""
    return -(-d // 128) * 128


def wide_footprint_bytes(d: int, lanes: int) -> float:
    """Planned device bytes of the wide rounds beside X (see
    streamed_wide_route_ok)."""
    Lb = bucket_lanes(lanes)
    dp = wide_padded_cols(d)
    return 3.0 * dp * dp * 4.0 + 6.0 * _wide_row_block(d, 1 << 30) \
        * 2 * Lb * 4.0 + 8.0 * Lb * dp * 4.0


def _wide_contract(A, xT, over_rows: bool = False):
    """float32 A against a block xT [d, c] of X.T (the matrix's dtype),
    accumulated in float32: A [k, d] -> the [k, c] margins A xT, or, with
    `over_rows`, A [k, c] -> the [k, d] moments A xT'. A bf16 matrix keeps
    the MXU's bf16 path: A goes in as its two leading bf16 parts stacked
    (`parts.stacked_parts`; one contraction of twice the height, which a
    128-wide MXU does for nothing while 2 k <= 128) and the halves are
    added. A float32 matrix has one part: A itself, at HIGHEST."""
    dims = (((1,), (1 if over_rows else 0,)), ((), ()))
    if _parts.n_parts(xT.dtype) == 1:
        return jax.lax.dot_general(A.astype(xT.dtype), xT, dims,
                                   precision=jax.lax.Precision.HIGHEST)
    k = A.shape[0]
    out = jax.lax.dot_general(_parts.stacked_parts(A, xT.dtype, 2), xT, dims,
                              preferred_element_type=jnp.float32)
    return out[:k] + out[k:]


@jax.jit
def wide_gram(X: jax.Array, w: jax.Array, mean: jax.Array,
              inv_std: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Once per sweep: (Gs [d, d] float32, lam). ONE pass accumulates the raw
    weighted Gram X' diag(w) X in float32 (a bf16 matrix of counts is exact
    in it; non-unit weights are rounded into the matrix's dtype, which
    loosens only the bound); centre and scale are then applied in moment
    space, Gs = D^-1 (G - W mean mean') D^-1. lam is _WIDE_LAM_MARGIN x the
    Rayleigh quotient after _WIDE_POWER_ITERS power-iteration steps from the
    constant vector: the bound's largest eigenvalue, which sets the inner
    step."""
    n, d = X.shape
    c = _wide_row_block(d, n)
    nb, take = _mlr_blocks(n, c, X.T, w)        # see _wide_round_core
    hp = jax.lax.Precision.HIGHEST

    def body(i, G):
        xT, fresh, wb = take(i)                                 # [d, c]
        xw = (xT.astype(jnp.float32) * (wb * fresh)[None, :]).astype(X.dtype)
        return G + jax.lax.dot_general(
            xw, xT, (((1,), (1,)), ((), ())), precision=hp,
            preferred_element_type=jnp.float32)

    G = jax.lax.fori_loop(0, nb, body, jnp.zeros((d, d), jnp.float32))
    Gs = (G - w.sum() * mean[:, None] * mean[None, :]) \
        * inv_std[:, None] * inv_std[None, :]
    Gs = 0.5 * (Gs + Gs.T)

    def power(_, v):
        u = jnp.matmul(Gs, v, precision=hp)
        return u / jnp.maximum(jnp.linalg.norm(u), EPS)

    v = jax.lax.fori_loop(0, _WIDE_POWER_ITERS, power,
                          jnp.full((d,), d ** -0.5, jnp.float32))
    lam = _WIDE_LAM_MARGIN * jnp.vdot(v, jnp.matmul(Gs, v, precision=hp))
    return Gs, lam


def _wide_gradient_blocks(X, y, w, fold_masks, sel, Braw, b0_raw):
    """The wide round's pass over X as an XLA loop over row blocks, for a
    backend without Mosaic: (gA [Lb, d], g0A [Lb]), the sums over rows of
    R x' and of R in raw units (pallas_wide.wide_gradient is the same
    arithmetic in one program that reads each block once: here the margins
    and the moments are a fusion each, and each reads it)."""
    n, d = X.shape
    Lb = sel.shape[1]
    f32 = jnp.float32
    c = _wide_row_block(d, n)
    # blocks are slices of X.T along its minor axis, rows on the lanes
    # (_mlr_blocks): a matrix whose width is no multiple of 128 lives
    # rows-minor on the chip (the layout that pads nothing), so X.T is the
    # layout it already has; a [c, d] block made XLA copy all of X into the
    # other layout first
    nb, take = _mlr_blocks(n, c, X.T, y, w, fold_masks)

    def body(i, acc):
        gA, g0A = acc
        xT, fresh, y_blk, w_blk, m_blk = take(i)    # m_blk [F, c]
        eta = _wide_contract(Braw, xT) + b0_raw[:, None]    # [Lb, c]
        # lane weights: exact for any w (sel is 0/1)
        wl = jnp.matmul(sel.T, m_blk * (w_blk * fresh)[None, :],
                        precision=jax.lax.Precision.HIGHEST)
        R = (jax.nn.sigmoid(eta) - y_blk[None, :]) * wl
        return (gA + _wide_contract(R, xT, over_rows=True),
                g0A + R.sum(1))

    return jax.lax.fori_loop(
        0, nb, body, (jnp.zeros((Lb, d), f32), jnp.zeros(Lb, f32)))


def _wide_round_core(X, y, w, fold_masks, sel, l1, l2, B0, b00, mean,
                     inv_std, Gs, lam, iters_budget, tol, *, fit_intercept):
    """Up to `iters_budget` outer iterations of the wide solver (the
    section comment above) for one compacted lane bucket: sel [F, Lb] maps
    bucket lanes to folds (all-zero columns are inert padding), B0 [Lb, d] /
    b00 [Lb] carry standardised-space state between rounds.
    wide_round_kernel(d, dtype) names the body of the pass over X; the
    iteration around it is one. The while cond leaves as soon as every
    lane's delta clears tol. Returns (B, b0, delta [Lb], iters)."""
    d = X.shape[1]
    Lb = sel.shape[1]
    f32 = jnp.float32
    hp = jax.lax.Precision.HIGHEST
    wsum = jnp.maximum(w.sum(), EPS)
    wsum_f = jnp.maximum((fold_masks * w[None, :]).sum(1), EPS)   # [F]
    wsum_l = jnp.maximum((wsum_f[:, None] * sel).sum(0), EPS)     # [Lb]
    kappa = 0.25 / wsum_l
    step = 1.0 / (kappa * lam + l2)
    fused = wide_round_kernel(d, X.dtype) == "pallas_fused"
    if fused:       # once a round program, outside the iteration
        rows = pallas_wide.side_rows(y, w, fold_masks)

    def accumulate(B, b0):
        Braw = B * inv_std[None, :]                     # [Lb, d] raw units
        b0_raw = b0 - (Braw * mean[None, :]).sum(1)
        if fused:
            gA, g0A = pallas_wide.wide_gradient(
                X.T, rows, sel, *(p.astype(X.dtype) for p in
                                  _parts.float32_parts(Braw, X.dtype, 2)),
                b0_raw)
        else:
            gA, g0A = _wide_gradient_blocks(X, y, w, fold_masks, sel, Braw,
                                            b0_raw)
        # r' X -> the standardised columns' moments: centre, then scale
        g = (gA - mean[None, :] * g0A[:, None]) * inv_std[None, :]
        return g / wsum_l[:, None], g0A

    def cond(state):
        i, _, _, delta = state
        return (i < iters_budget) & (delta.max() > tol)

    def body(state):
        i, B, b0, _ = state
        g, g0A = accumulate(B, b0)

        def inner(_, s):
            Z, V, th = s
            grad = g + kappa[:, None] * jnp.matmul(V - B, Gs, precision=hp) \
                + l2[:, None] * V
            U = V - step[:, None] * grad
            Zn = jnp.sign(U) * jnp.maximum(
                jnp.abs(U) - (step * l1)[:, None], 0.0)
            thn = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * th * th))
            return Zn, Zn + ((th - 1.0) / thn) * (Zn - Z), thn

        B_new, _, _ = jax.lax.fori_loop(
            0, _WIDE_INNER_STEPS, inner, (B, B, jnp.asarray(1.0, f32)))
        b0_new = b0 - 4.0 * g0A / wsum if fit_intercept else b0
        delta = jnp.abs(B_new - B).max(axis=1) + jnp.abs(b0_new - b0)
        return i + 1, B_new, b0_new, delta

    state = (jnp.asarray(0, jnp.int32), B0.astype(f32), b00.astype(f32),
             jnp.full((Lb,), jnp.inf, f32))
    i, B, b0, delta = jax.lax.while_loop(cond, body, state)
    return B, b0, delta, i


@functools.partial(jax.jit, static_argnames=("fit_intercept",))
def sweep_glm_wide_round(X: jax.Array, y: jax.Array, w: jax.Array,
                         fold_masks: jax.Array, sel: jax.Array,
                         l1: jax.Array, l2: jax.Array, B0: jax.Array,
                         b00: jax.Array, mean: jax.Array, inv_std: jax.Array,
                         Gs: jax.Array, lam: jax.Array, iters_budget, tol, *,
                         fit_intercept: bool = True):
    """One retirement round of the wide sweep (see _wide_round_core).
    Compiled per (n, d, F, bucket) shape; iters_budget/tol are traced. The
    executable bakes wide_round_kernel's answer in, so the Pallas switch
    clears this function's cache."""
    return _wide_round_core(X, y, w, fold_masks, sel, l1, l2, B0, b00, mean,
                            inv_std, Gs, lam, iters_budget, tol,
                            fit_intercept=fit_intercept)


pallas_hist.register_cache_consumer(sweep_glm_wide_round)


def sweep_glm_wide_streamed_rounds(X, y, w, fold_masks, regs, alphas, *,
                                   max_iter: int = 50, tol: float = 1e-6,
                                   fit_intercept: bool = True,
                                   standardize: bool = True,
                                   round_iters: int = ROUND_ITERS_DEFAULT,
                                   warm_seed: Optional[Tuple] = None,
                                   state: Optional[Dict[str, Any]] = None,
                                   on_round: Optional[Callable] = None
                                   ) -> Tuple[np.ndarray, np.ndarray,
                                              Dict[str, Any]]:
    """Host-driven streamed sweep of BINARY logistic regression over a
    matrix wider than TRI_MAX_D columns, resident on one device: the
    retirement loop of sweep_glm_streamed_rounds (_run_rounds: rounds of
    `round_iters` iterations, lanes retire at their own delta <= tol or at
    max_iter, survivors compact into `bucket_lanes` buckets, `state` /
    `on_round` checkpoint every boundary and resume bit-identically) around
    `sweep_glm_wide_round`, after the column moments and ONE `wide_gram`
    pass. Lanes start at zero — or, with `warm_seed` ((beta_raw [d],
    b0_raw), the retrain refit's across-time continuation), at that model.
    The columns are centred whenever an intercept is fitted (with
    standardize=False that is a reparametrisation: the optimum is the
    same) and scaled when `standardize`.

    Returns (B [F, G, d] f32 RAW units, b0 [F, G], info)."""
    from ..utils.metrics import collector as _collector

    regs = np.asarray(regs, np.float32)
    alphas = np.asarray(alphas, np.float32)
    F, d = int(fold_masks.shape[0]), int(X.shape[1])
    Gn = int(regs.shape[0])
    L = F * Gn
    max_iter, tol_f = int(max_iter), float(tol)
    stats_passes = 0
    mean, inv_std = jnp.zeros(d, jnp.float32), jnp.ones(d, jnp.float32)
    if standardize or fit_intercept:
        mean, std = glm_standardize_stats(X, w)
        stats_passes = 2
        if standardize:
            inv_std = 1.0 / std
    lane_fold = np.repeat(np.arange(F, dtype=np.int32), Gn)
    l1v = np.tile(regs * alphas, F).astype(np.float32)
    l2v = np.tile(regs * (1.0 - alphas), F).astype(np.float32)
    with _collector.trace_span("gram_factor", kind="host_step", folds=F,
                               lanes=L, cols=d, factorizations=0):
        Gs, lam = wide_gram(X, w, mean, inv_std)
    st = state if state is not None else _new_round_state(L, d)
    pass_body = wide_round_kernel(d, X.dtype)

    warm_seeded = False
    if (warm_seed is not None and not st["retired"].any()
            and int(st["iters"].max()) == 0):
        seed_b = np.asarray(warm_seed[0], np.float32).reshape(-1)
        if seed_b.shape[0] == d:
            # RAW-unit seed -> this sweep's standardised space (the final
            # unstandardize below inverts exactly this map)
            st["B"][:] = (seed_b / np.asarray(inv_std))[None, :]
            st["b0"][:] = float(warm_seed[1]) \
                + float((seed_b * np.asarray(mean)).sum())
            warm_seeded = True

    def run_round(idx, budget):
        k = len(idx)
        Lb = bucket_lanes(k)
        with _collector.trace_span(
                f"glm_wide_round[{Lb}]", kind="sweep_round", bucket=int(Lb),
                active=int(k), iters_budget=int(budget),
                kernel=pass_body):
            with _collector.trace_span("round_prep", kind="host_step"):
                sel = np.zeros((F, Lb), np.float32)
                sel[lane_fold[idx], np.arange(k)] = 1.0
                l1b = np.zeros(Lb, np.float32)
                l1b[:k] = l1v[idx]
                # inert pads: no fold (zero weights), l2 = 1; B = 0 is
                # their fixed point
                l2b = np.ones(Lb, np.float32)
                l2b[:k] = l2v[idx]
                B0 = np.zeros((Lb, d), np.float32)
                B0[:k] = st["B"][idx]
                b00 = np.zeros(Lb, np.float32)
                b00[:k] = st["b0"][idx]
                args = (X, y, w, fold_masks, jnp.asarray(sel),
                        jnp.asarray(l1b), jnp.asarray(l2b), jnp.asarray(B0),
                        jnp.asarray(b00), mean, inv_std, Gs, lam,
                        jnp.asarray(budget, jnp.int32),
                        jnp.asarray(tol_f, jnp.float32))
            out = sweep_glm_wide_round(*args,
                                       fit_intercept=bool(fit_intercept))
            with _collector.trace_span("round_fetch", kind="host_step"):
                # the host waits here for the round's program; ONE
                # transfer for its four results
                Bb, b0b, db, it = jax.device_get(out)
        _record_round(st, idx, Lb, Bb, b0b, db, int(it))

    _run_rounds(st, run_round, int(round_iters), max_iter, tol_f, on_round)

    mean_h, inv_std_h = jax.device_get((mean, inv_std))
    B = st["B"] * inv_std_h[None, :]
    b0 = st["b0"] - (B * mean_h[None, :]).sum(1, dtype=np.float32)
    info = {"route": "streamed", "kernel": "wide_rounds",
            "driver": "resident", "round_kernel": pass_body,
            **_rounds_info(st, tol_f, max_iter),
            "warm_seeded": warm_seeded, "cols": d,
            "padded_cols": wide_padded_cols(d),
            "gram_passes": 1, "factorizations": 0,
            "inner_steps": _WIDE_INNER_STEPS}
    # every whole read of X by the route's programs: the outer iterations
    # (data_passes), the Gram pass, the two passes of the moments
    info["x_passes"] = info["data_passes"] + 1 + stats_passes
    return B.reshape(F, Gn, d), b0.reshape(F, Gn), info
