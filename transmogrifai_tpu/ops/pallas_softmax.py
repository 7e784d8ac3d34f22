"""The multinomial round's pass over X as ONE Pallas program.

`mlr_gradient` is what `ops/glm_sweep._mlr_round_core` runs an iteration on
a backend that has Mosaic (the XLA block loop, `_mlr_gradient_blocks` there,
is the same arithmetic for every other backend): for one compacted lane
bucket it reads the resident matrix once, as row tiles of `X.T`, and returns
the bucket's gradient sums. Inside a tile nothing of shape [lanes x K, rows]
leaves VMEM: standardise -> logits (one contraction over the two bf16 parts
of the coefficients) -> max -> ONE float32 exponential -> sum -> reciprocal
-> residual -> gradient contraction, the sums kept in two float32 output
blocks that every grid step revisits. The grid is one sequential axis, so
the order of every sum is fixed and a job repeats bit for bit.

Precision is the XLA body's: float32 exponential, sum and exact reciprocal;
the residual is rounded to the matrix's dtype only as the gradient
contraction's operand, and the intercept's sum takes it unrounded.

Kept apart from ops/pallas_hist.py on purpose: a Mosaic body carries its
source locations, so an edit that moves that file's lines makes every tree
kernel miss the compile cache (PERF.md, PR 27).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import pallas_hist

# Columns of a tile (rows of X) worked on at a time: a lane's [K, _CHUNK]
# float32 slab of logits is 8 vector registers at K = 32, so its softmax
# runs in registers, and the two contractions of a chunk stream all
# lanes x K rows through the MXU against the chunk's columns.
_CHUNK = 256
# Chunks in one body of the tile's loop, each with its own residual buffer:
# a chunk's three steps depend on one another (contraction -> softmax ->
# contraction), so only ANOTHER chunk's contractions can run under this
# one's softmax, and the compiler interleaves what one loop body holds. On
# the v5e at 16 lanes x 32 classes, 25M x 64 (PERF.md, PR 32): one chunk of
# 512 a body 68.5 ms a pass, 2 / 4 / 6 / 8 chunks of 256 61.9 / 57.2 / 55.2
# / 57.3; the softmax alone 49.4, the contractions alone 53.0.
_UNROLL = 6
# Bodies a grid step: 12 288 rows a tile keep the per-step cost (~0.35 us
# and the pipeline's DMA descriptors) under a hundredth of the tile's work;
# 6 144 and 24 576 measured the same within 0.5 %.
_TILE_BODIES = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel(xT_ref, y_ref, w_ref, m_ref, bt_ref, b0_ref, selT_ref, mean_ref,
            istd_ref, gA_ref, g0_ref, r_scr, *, n, d, tile):
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    chunk, groups = _CHUNK, _CHUNK // 128
    i = pl.program_id(0)
    dp, dtype = xT_ref.shape[0], xT_ref.dtype
    lanes, folds = selT_ref.shape
    kp = bt_ref.shape[0] // lanes
    stacked = bt_ref.shape[1] == 2 * dp

    @pl.when(i == 0)
    def _():
        gA_ref[...] = jnp.zeros_like(gA_ref)
        g0_ref[...] = jnp.zeros_like(g0_ref)

    def lane_iota(rows):
        return jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)

    x_cols, f_cols = lane_iota(dp), lane_iota(folds)
    feat_ok = None if dp == d else \
        jax.lax.broadcasted_iota(jnp.int32, (dp, chunk), 0) < d
    classes = jax.lax.broadcasted_iota(jnp.int32, (kp, chunk), 0).astype(f32)
    mean, istd, bt, selT = (mean_ref[...], istd_ref[...], bt_ref[...],
                            selT_ref[...])

    def one_chunk(j, r_ref):
        off = pl.multiple_of(j * chunk, chunk)
        cols = pl.ds(off, chunk)
        # rows of X past n (the last tile's tail, whatever the buffer holds
        # there) lose x and the fold weights by a select, as y and w lost
        # theirs in `dense_rows`: a zero weight alone would leave NaN x 0
        left = n - (i * tile + off)
        x_ok = x_cols < left
        if feat_ok is not None:
            x_ok = x_ok & feat_ok
        xs = jnp.where(x_ok, (xT_ref[:, cols].astype(f32) - mean) * istd,
                       0.0).astype(dtype)                        # [dp, c]
        z = jnp.dot(bt, jnp.concatenate([xs, xs], axis=0) if stacked else xs,
                    preferred_element_type=f32)                  # [L kp, c]
        # y and w come dense, 128 rows of X a sublane (`dense_rows`)
        sub = pl.ds(pl.multiple_of(j * groups, groups), groups)
        y_row, w_row = (jnp.concatenate(
            [v[k:k + 1, :] for k in range(groups)], axis=1)
            for v in (y_ref[sub, :], w_ref[sub, :]))             # [1, c]
        Y = (y_row == classes).astype(f32)                       # [kp, c]
        mw = jnp.where(f_cols < left, m_ref[:, cols] * w_row, 0.0)
        # lane weights: sel is 0/1 with one fold a lane, so the sum is exact
        wl = selT[:, 0:1] * mw[0:1, :]
        for f in range(1, folds):
            wl = wl + selT[:, f:f + 1] * mw[f:f + 1, :]          # [L, c]
        for l in range(lanes):
            rows = slice(l * kp, (l + 1) * kp)
            zl = z[rows, :] + b0_ref[rows, :]
            e = jnp.exp(zl - zl.max(axis=0, keepdims=True))
            r = 1.0 / e.sum(axis=0, keepdims=True)
            R = (e * r - Y) * wl[l:l + 1, :]
            r_ref[rows, :] = R.astype(dtype)
            part = R[:, 0:128]
            for k in range(1, groups):
                part = part + R[:, k * 128:(k + 1) * 128]
            g0_ref[rows, :] += part
        gA_ref[...] += jax.lax.dot_general(
            r_ref[...], xs, (((1,), (1,)), ((), ())),
            preferred_element_type=f32)

    def body(j, carry):
        for u in range(_UNROLL):
            one_chunk(_UNROLL * j + u, r_scr.at[u])
        return carry

    jax.lax.fori_loop(0, tile // (chunk * _UNROLL), body, 0)


def _tile_rows(n: int) -> int:
    body = _CHUNK * _UNROLL
    return body * min(_TILE_BODIES, -(-n // body))


def dense_rows(v, n_rows=None):
    """A per-row vector (y, the weights) as `mlr_gradient` reads it:
    float32 [R, 128], 128 rows of X a sublane, its first `n_rows` entries
    (default: all) and zeros up to whole tiles. On the chip a [n] vector
    and a [1, n] block are tiled differently and XLA lays one out as the
    other by a loop; this form is one pad and a bitcast, made once a round
    program and not once a pass."""
    n = v.shape[0] if n_rows is None else int(n_rows)
    tile = _tile_rows(n)
    return jnp.pad(v[:n].astype(jnp.float32), (0, _round_up(n, tile) - n)) \
        .reshape(-1, 128)


@functools.partial(jax.jit, static_argnames=("n_rows", "interpret"))
def mlr_gradient(XT, y_rows, w_rows, fold_masks, sel, Bt_hi, Bt_lo, b0, mean,
                 inv_std, *, n_rows=None, interpret: bool = False):
    """(gA [lanes * K, d], g0A [lanes, K]) float32: the sums over the first
    `n_rows` rows (default: all) of R x xs' and of R, where xs is the
    standardised row in the matrix's dtype and R = (softmax(B xs + b0) -
    onehot(y)) x the lane's fold weight — one iteration's pass of
    `_mlr_round_core` for a lane bucket.

    XT [d, n] is X.T, the layout a resident matrix already has on the chip
    (no padded or re-laid-out copy is made of it: the last tile reads past n
    and masks); y_rows, w_rows are `dense_rows` of y and w; fold_masks
    [F, n]; sel [F, lanes] maps lanes to folds; Bt_hi, Bt_lo [lanes * K, d]
    are `glm_sweep._mlr_coefficient_parts`' two parts (Bt_lo None for a
    float32 matrix); b0 [lanes, K]; mean, inv_std [d]. Classes pad to whole
    sublane tiles with their logits at -inf and features to whole tiles
    with zero columns; both pads are cut from what is returned."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    d, n_buf = XT.shape
    n = n_buf if n_rows is None else int(n_rows)
    F, lanes = sel.shape
    K = Bt_hi.shape[0] // lanes
    # a vector register holds 8 rows of 32 bits, 16 of 16
    sub = 8 * 4 // jnp.dtype(XT.dtype).itemsize
    dp, kp = _round_up(d, sub), _round_up(K, sub)
    tile = _tile_rows(n)

    def padded(Bt):
        return jnp.pad(Bt.reshape(lanes, K, d),
                       ((0, 0), (0, kp - K), (0, dp - d))) \
            .reshape(lanes * kp, dp)
    bt = padded(Bt_hi) if Bt_lo is None else \
        jnp.concatenate([padded(Bt_hi), padded(Bt_lo)], axis=1)
    b0p = jnp.pad(b0.astype(f32), ((0, 0), (0, kp - K)),
                  constant_values=-jnp.inf).reshape(lanes * kp, 1)

    def column(v):
        return jnp.pad(v.astype(f32), (0, dp - d)).reshape(dp, 1)

    def by_rows(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    def whole(a):
        return by_rows(a.shape, lambda i: (0, 0))
    dense = by_rows((tile // 128, 128), lambda i: (i, 0))
    resident = (bt, b0p, sel.T.astype(f32), column(mean), column(inv_std))
    out_shape = (jax.ShapeDtypeStruct((lanes * kp, dp), f32),
                 jax.ShapeDtypeStruct((lanes * kp, 128), f32))
    gA, g0 = pl.pallas_call(
        functools.partial(_kernel, n=n, d=d, tile=tile),
        grid=(-(-n // tile),),
        in_specs=[by_rows((dp, tile), lambda i: (0, i)), dense, dense,
                  by_rows((F, tile), lambda i: (0, i))]
        + [whole(a) for a in resident],
        out_specs=tuple(whole(s) for s in out_shape),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((_UNROLL, lanes * kp, _CHUNK), XT.dtype)],
        compiler_params=pallas_hist._compiler_params(),
        name="mlr_gradient",
        interpret=interpret,
    )(XT, y_rows, w_rows, fold_masks.astype(f32), *resident)
    gA = gA.reshape(lanes, kp, dp)[:, :K, :d].reshape(lanes * K, d)
    return gA, g0.sum(axis=1).reshape(lanes, kp)[:, :K]
