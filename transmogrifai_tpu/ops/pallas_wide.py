"""The wide GLM round's pass over X as ONE Pallas program.

`wide_gradient` is what `ops/glm_sweep._wide_round_core` runs an outer
iteration on a backend that has Mosaic (the XLA block loop,
`_wide_gradient_blocks` there, is the same arithmetic for every other
backend): for one compacted lane bucket it reads the resident matrix ONCE,
as row tiles of `X.T`, and returns the bucket's residual moments. A tile
stays in VMEM from the margins to the moments: margins (the two parts of
the coefficients stacked, one contraction over the columns) -> lane weights
-> float32 sigmoid residual -> the intercept's sum from the unrounded
residual -> the residual's two parts stacked, one contraction over the
tile's rows, into float32 output blocks that every grid step revisits. The
XLA body makes each contraction a fusion of its own and each reads the
block from HBM again. The grid is one sequential axis, so the order of
every sum is fixed and a job repeats bit for bit.

The matrix's width is no multiple of 128 (`glm_sweep.round_kernel`), so its
columns are the tile's SUBLANES and split into a main part of whole
128-column groups and the LAST 128 sublanes of the block, which overlap the
main part: the coefficients are zero where they overlap and the block's
sublanes past the width are zeroed by a select, on that piece alone.

Kept apart from ops/pallas_hist.py and ops/pallas_softmax.py on purpose: a
Mosaic body carries its source locations, so an edit that moves a file's
lines makes every kernel of it miss the compile cache (PERF.md, PR 27).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import pallas_hist
from .pallas_softmax import _round_up

# Rows of X a grid step. The tile [d, _TILE] of X.T is double-buffered in
# VMEM (2 x 16.8 MB at 4 104 bf16 columns) and a step's two contractions pay
# one fill and one drain of the MXU between them, so a longer tile is the
# cheaper one. On the v5e at 64 lanes over 786 432 x 4 104 (PERF.md, PR 34;
# ms a pass): 128 rows 11.88, 256 10.27, 512 9.48, 1 024 9.10, 2 048 8.94,
# where the XLA blocks take 19.41, the read alone 8.67 and the contractions
# alone 8.89. Two or four independent sub-tiles a step, and the moments
# with X as the streamed operand, measured the same or worse.
_TILE = 2048
# Lanes the VMEM budget is taken at: the largest bucket (`bucket_lanes`),
# so that whether the kernel fits depends on the matrix alone and a sweep's
# rounds all run one body.
_BUDGET_LANES = 128


def vmem_bytes(d: int, tile: int) -> int:
    """What the kernel keeps in VMEM at `tile` rows a step for the largest
    bucket: the bfloat16 tile of X.T twice (the pipeline's two buffers), the
    stacked coefficients and the float32 moments twice each, one more
    moments block for the contraction's result, and the [lanes, tile]
    float32 margins, weights and residual."""
    stacked, dp = 2 * _BUDGET_LANES, _round_up(d, 128)
    return 2 * dp * tile * 2 + stacked * dp * (2 * 2 + 3 * 4) \
        + 8 * stacked * tile * 4


def tile_rows(d: int) -> int:
    """Rows of X a grid step at this width: `_TILE`, halved while the
    kernel's residents pass the VMEM one kernel may claim; 0 where not even
    128 rows fit (the round then stays with the XLA blocks)."""
    tile = _TILE
    while tile >= 128 and vmem_bytes(d, tile) > pallas_hist._vmem_limit():
        tile //= 2
    return tile if tile >= 128 else 0


def _kernel(x_ref, rows_ref, bt_ref, btail_ref, b0_ref, selT_ref,
            gA_ref, gtail_ref, g0_ref, *, n, d, tile):
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    i = pl.program_id(0)
    dp, dtype = x_ref.shape[0], x_ref.dtype
    lanes, folds = selT_ref.shape
    dmain = bt_ref.shape[1]
    over_rows = (((1,), (1,)), ((), ()))

    @pl.when(i == 0)
    def _():
        gA_ref[...] = jnp.zeros_like(gA_ref)
        gtail_ref[...] = jnp.zeros_like(gtail_ref)
        g0_ref[...] = jnp.zeros_like(g0_ref)

    # the block's last 128 sublanes reach past the matrix's width
    in_width = jax.lax.broadcasted_iota(jnp.int32, (128, tile), 0) \
        < d - (dp - 128)

    def step(ragged: bool):
        x = x_ref[0:dmain, :]
        xt = jnp.where(in_width, x_ref[dp - 128:dp, :], 0)
        rows = rows_ref[...]                    # [folds + 1, tile]
        if ragged:
            # the last tile reaches past n and holds whatever the buffer
            # held: x, the weights and y lose those rows by a select (a zero
            # weight alone would leave NaN x 0)
            ok = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) \
                < n - i * tile
            x, xt, rows = (jnp.where(ok, v, 0) for v in (x, xt, rows))
        eta = jnp.dot(bt_ref[...], x, preferred_element_type=f32) \
            + jnp.dot(btail_ref[...], xt, preferred_element_type=f32)
        eta = eta[:lanes, :] + eta[lanes:, :] + b0_ref[...]
        # lane weights: sel is 0/1 with one fold a lane, so the sum is exact
        selT = selT_ref[...]
        wl = selT[:, 0:1] * rows[0:1, :]
        for f in range(1, folds):
            wl = wl + selT[:, f:f + 1] * rows[f:f + 1, :]        # [L, tile]
        R = (jax.nn.sigmoid(eta) - rows[folds:folds + 1, :]) * wl
        g0_ref[...] += R.sum(axis=1, keepdims=True)
        hi = R.astype(dtype)
        R2 = jnp.concatenate([hi, (R - hi.astype(f32)).astype(dtype)], axis=0)
        gA_ref[...] += jax.lax.dot_general(
            R2, x, over_rows, preferred_element_type=f32)
        gtail_ref[...] += jax.lax.dot_general(
            R2, xt, over_rows, preferred_element_type=f32)

    if n % tile == 0:
        step(False)
    else:
        last = n // tile
        pl.when(i < last)(lambda: step(False))
        pl.when(i == last)(lambda: step(True))


def side_rows(y, w, fold_masks):
    """What `wide_gradient` reads beside X, float32 [F + 1, n]: every
    fold's row weights m_f * w, then y. Made once a round program and not
    once a pass: on the chip a [n] vector and a [1, n] block are tiled
    differently and XLA lays one out as the other by a loop (PERF.md,
    PR 32)."""
    f32 = jnp.float32
    return jnp.concatenate([fold_masks.astype(f32) * w.astype(f32)[None, :],
                            y.astype(f32)[None, :]], axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wide_gradient(XT, rows, sel, B_hi, B_lo, b0, *, interpret: bool = False):
    """(gA [lanes, d], g0A [lanes]) float32: the sums over the rows of X of
    R x' and of R, where R = (sigmoid(B x + b0) - y) x the lane's fold
    weight — one outer iteration's pass of `_wide_round_core` for a lane
    bucket, in raw units (centre and scale are the caller's).

    XT [d, n] is X.T, a bfloat16 matrix in the layout it already has on
    the chip at such a width (no padded or re-laid-out copy is made of it: the
    last tile reads past n and masks); rows is `side_rows`; sel [F, lanes]
    maps lanes to folds; B_hi, B_lo [lanes, d] are `parts.float32_parts`' two
    of the coefficients in the matrix's dtype; b0 [lanes]."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    d, n = XT.shape
    F, lanes = sel.shape
    # a vector register holds 16 rows of 16 bits
    dp = _round_up(d, 16)
    dmain, t0 = d // 128 * 128, dp - 128
    tile = min(tile_rows(d), _round_up(n, 128))
    bt = jnp.concatenate([B_hi, B_lo], axis=0)
    # the last 128 sublanes of the block hold columns t0 .. dp: those from
    # dmain on are theirs alone
    btail = jnp.pad(bt[:, dmain:], ((0, 0), (dmain - t0, dp - d)))

    def by_rows(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    def whole(a):
        return by_rows(a.shape, lambda i: (0, 0))
    resident = (bt[:, :dmain], btail, b0.astype(f32).reshape(lanes, 1),
                sel.T.astype(f32))
    out_shape = (jax.ShapeDtypeStruct((2 * lanes, dmain), f32),
                 jax.ShapeDtypeStruct((2 * lanes, 128), f32),
                 jax.ShapeDtypeStruct((lanes, 1), f32))
    gA, gtail, g0 = pl.pallas_call(
        functools.partial(_kernel, n=n, d=d, tile=tile),
        grid=(-(-n // tile),),
        in_specs=[by_rows((dp, tile), lambda i: (0, i)),
                  by_rows((F + 1, tile), lambda i: (0, i))]
        + [whole(a) for a in resident],
        out_specs=tuple(whole(s) for s in out_shape),
        out_shape=out_shape,
        compiler_params=pallas_hist._compiler_params(),
        name="wide_gradient",
        interpret=interpret,
    )(XT, rows, *resident)
    gA = jnp.concatenate([gA, gtail[:, dmain - t0:d - t0]], axis=1)
    return gA[:lanes] + gA[lanes:], g0[:, 0]
