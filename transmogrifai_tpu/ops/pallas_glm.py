"""The binary GLM round's pass over X as ONE Pallas program.

`glm_moments` is what `ops/glm_sweep._round_core` runs a Newton iteration on
a backend that has Mosaic (the XLA block scan, `_moments_blocks` there, is
the same sums for every other backend): for one compacted lane bucket it
reads the resident matrix once, as row tiles of `X.T`, and returns the
bucket's gradient, per-lane Gram and intercept sums. Inside a tile nothing
of shape [lanes, rows] or [lanes x d, rows] leaves VMEM: standardise ->
margins (one contraction) -> residual and curvature -> lane weights -> the
gradient's contraction -> every lane's weighted copy of the block, lane
above lane -> ONE contraction of the block against all of them for every
lane's Gram, summed into float32 output blocks that every grid step
revisits. The XLA body makes each of those a fusion of its own, each reads
and writes its [rows, lanes] blocks in HBM, and its Gram keeps the block,
64 columns wide, as the matrix unit's stationary operand, which fills half
of the array; here the weighted copies are the stationary operand and fill
it. The grid is one sequential axis, so the order of every sum is fixed and
a job repeats bit for bit.

Precision: the matrix unit's operands in the matrix's dtype, float32 sums;
the intercept's two sums take residual and curvature unrounded. The
standardised block and the curvature x weight x block are rounded to that
dtype once. The two operands the iteration's fixed point hangs on are NOT:
both go in as parts of the matrix's dtype (`parts.float32_parts`), one above
the other as ONE operand of the contraction that was there, the product's slabs
added in float32 (`slab_sum`). The coefficients: the margins xs' B + b0 see
the float32 B the iteration carries, as its exact split (three parts of
bfloat16), the slabs added chunk by chunk. A Newton step taken at rounded
coefficients has no fixed point — near the optimum it moves B by B's own
rounding error, 2^-9 |B| a step, and no lane's delta ever clears a tolerance
of 1e-6 (PERF.md, PR 38). The residual x weight R of the gradient sum_rows
R xs': its two leading parts (`residual_parts`: 16 significant bits of
bfloat16, a rounding of 2^-17 |R| where one part leaves 2^-9), each part's
slab summed over the rows on its own. The first slab is gA, the sum over R
rounded to the dtype, as the pass has always returned it; the second comes
out beside it as gA_low, and the round adds the two once a pass. R's
roundings do not average out over a column that a thousandth of the rows
share (a standardised null indicator reads 31 there): at 20M rows they
floored a lane's delta AT 1e-6, and a point on the soft threshold's kink
cycled on that floor up to `max_iter` (PERF.md, PR 44). A float32 matrix
has one part of each, the operand itself. The XLA body
(`glm_sweep._moments_blocks`) splits B the same way and R into ALL its
parts: it returns the one gradient, exact on every backend.

Under the residual's parts rides one more slab of the same contraction: the
curvature x weight S, rounded to the matrix's dtype once, whose product with
the block is cA = sum_rows S xs', the Hessian's border. It couples the
coefficients' step to the intercept's (`glm_sweep._newton_prox_update`), it
shapes the step and multiplies a zero at the fixed point, so its rounding
moves no answer: the standing the Gram's operands have. No further pass over
the block, one more [lanes, chunk] row group in a contraction that is 1/64
to 1/128 of the Gram's.

Kept apart from ops/pallas_hist.py, ops/pallas_softmax.py and
ops/pallas_wide.py on purpose: a Mosaic body carries its source locations,
so an edit that moves a file's lines makes every kernel of it miss the
compile cache (PERF.md, PR 27).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import pallas_hist
from . import parts as _parts
from .pallas_softmax import _round_up

# Columns of a tile (rows of X) worked on at a time, chunks in one body of
# the tile's loop, and bodies a grid step. The probes that set them (v5e,
# 25M x 64, ms a pass at 32 / 8 lanes; PERF.md, PR 36): chunks of 256 rows
# 59.5 / 29.7, 512 53.0 / 22.7, 1 024 47.9 / 18.3, 2 048 47.3 / 17.5 — every
# chunk pays the margins' contraction, the sums' read and write and the
# fill of the matrix unit once — two chunks a body 2 ms under one, 16 384
# rows a tile 0.6 ms under 8 192. The contraction alone takes 40.5 / —,
# the vector work alone 10.6.
_CHUNK = 1024
_UNROLL = 2
_TILE_BODIES = 8


def residual_curvature(loss: str):
    """Unweighted residual r and curvature s of an IRLS loss, elementwise:
    rc(eta, y) for margins `eta` and labels `y` of a shape that broadcasts
    against them (the XLA bodies hand [rows, lanes] and [rows, 1], the
    kernel [lanes, rows] and [1, rows])."""
    if loss == "logistic":
        def rc(eta, y):
            p = jax.nn.sigmoid(eta)
            return p - y, jnp.maximum(p * (1.0 - p), 1e-6)
    elif loss == "squared":
        def rc(eta, y):
            return eta - y, jnp.ones_like(eta)
    elif loss == "squared_hinge":
        def rc(eta, y):
            # loss 0.5*gap^2 (NOT gap^2): matches glm.fit_linear_svc's
            # residual/curvature so the streamed and per-lane routes see
            # the same effective L2 for a given reg_param
            ypm = 2.0 * y - 1.0
            gap = jnp.maximum(1.0 - ypm * eta, 0.0)
            return -gap * ypm, (gap > 0.0).astype(eta.dtype)
    else:
        raise ValueError(f"unknown streamed loss {loss!r}")
    return rc


def _pack(dtype) -> int:
    """Rows of `dtype` in a sublane tile: a cast fills whole ones."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def residual_parts(dtype) -> int:
    """Parts of `dtype` the gradient's contraction takes of the residual x
    weight: the two leading ones where `dtype` needs more than one (16
    significant bits of bfloat16), the operand itself where it does not."""
    return min(2, _parts.n_parts(dtype))


def _kernel(xT_ref, y_ref, w_ref, m_ref, bt_ref, b0_ref, selT_ref, mean_ref,
            std_ref, h_ref, g_ref, g0_ref, h0_ref, *, n, d, tile, loss):
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    chunk, groups = _CHUNK, _CHUNK // 128
    i = pl.program_id(0)
    dp, dtype = xT_ref.shape[0], xT_ref.dtype
    lanes, folds = selT_ref.shape
    pack = _pack(dtype)
    rc = residual_curvature(loss)
    over_rows = (((1,), (1,)), ((), ()))

    @pl.when(i == 0)
    def _():
        for ref in (h_ref, g_ref, g0_ref, h0_ref):
            ref[...] = jnp.zeros_like(ref)

    def lane_iota(rows):
        return jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)

    x_cols, f_cols = lane_iota(dp), lane_iota(folds)
    feat_ok = None if dp == d else \
        jax.lax.broadcasted_iota(jnp.int32, (dp, chunk), 0) < d
    bt, b0, selT = bt_ref[...], b0_ref[...], selT_ref[...]
    # across the chunk once a grid step, not once a chunk: 3.4 ms a pass
    mean, std = (jnp.broadcast_to(v[...], (dp, chunk))
                 for v in (mean_ref, std_ref))

    def lane_sums(ref, V):
        part = V[:, 0:128]
        for k in range(1, groups):
            part = part + V[:, k * 128:(k + 1) * 128]
        ref[...] += part

    def one_chunk(j):
        off = pl.multiple_of(j * chunk, chunk)
        cols = pl.ds(off, chunk)
        # rows of X past n (the last tile's tail, whatever the buffer holds
        # there) lose x and the fold weights by a select, as y and w lost
        # theirs in `dense_rows`: a zero weight alone would leave NaN x 0
        left = n - (i * tile + off)
        x_ok = x_cols < left
        if feat_ok is not None:
            x_ok = x_ok & feat_ok
        xs = jnp.where(x_ok, (xT_ref[:, cols].astype(f32) - mean) / std,
                       0.0).astype(dtype)                        # [dp, c]
        eta = _parts.slab_sum(
            jnp.dot(bt, xs, preferred_element_type=f32), lanes) + b0  # [L, c]
        # y and w come dense, 128 rows of X a sublane (`dense_rows`)
        sub = pl.ds(pl.multiple_of(j * groups, groups), groups)
        y_row, w_row = (jnp.concatenate(
            [v[k:k + 1, :] for k in range(groups)], axis=1)
            for v in (y_ref[sub, :], w_ref[sub, :]))             # [1, c]
        r0, s0 = rc(eta, y_row)
        mw = jnp.where(f_cols < left, m_ref[:, cols] * w_row, 0.0)
        # lane weights: sel is 0/1 with one fold a lane, so the sum is exact
        wl = selT[:, 0:1] * mw[0:1, :]
        for f in range(1, folds):
            wl = wl + selT[:, f:f + 1] * mw[f:f + 1, :]          # [L, c]
        R, S = r0 * wl, s0 * wl
        lane_sums(g0_ref, R)
        lane_sums(h0_ref, S)
        # The block streams through the matrix unit against what stays in
        # it: the residual's parts, one above the other (each part's slab
        # of the product sums on its own and the caller adds them: in here
        # that is a lane rotation and an add a chunk, 2 ms of a 64-lane
        # pass at 128 columns), then every lane's weighted copy of
        # the block, likewise. The copies fill the array's 128 columns; the
        # block, 64 wide, would fill half of them, and as the stationary
        # operand it measured 83.6 ms a pass where this form takes 57.5
        # (PERF.md, PR 36). They go to the contraction as a value: through
        # a VMEM scratch of the kernel's own the pass is 4 ms longer.
        Rw, Sw = R, S
        if lanes % pack:    # the cast fills whole sublane tiles
            Rw, Sw = (jnp.concatenate(
                [V, jnp.zeros((pack - lanes % pack, chunk), f32)], axis=0)
                for V in (R, S))
        # under the residual's parts the curvature x weight, one part: its
        # slab of the product is sum_rows S xs', the Hessian's border
        Rp = jnp.concatenate([V.astype(dtype) for V in _parts.float32_parts(
            Rw, dtype, residual_parts(dtype), in_kernel=True) + [Sw]], axis=0)
        g_ref[:, 0:Rp.shape[0]] += jax.lax.dot_general(
            xs, Rp, over_rows, preferred_element_type=f32)
        h_ref[...] += jax.lax.dot_general(
            xs, (S[:, None, :] * xs.astype(f32)[None, :, :]).astype(dtype)
            .reshape(lanes * dp, chunk), over_rows,
            preferred_element_type=f32)

    def body(j, carry):
        for u in range(_UNROLL):
            one_chunk(_UNROLL * j + u)
        return carry

    jax.lax.fori_loop(0, tile // (chunk * _UNROLL), body, 0)


def _tile_rows(n: int) -> int:
    body = _CHUNK * _UNROLL
    return body * min(_TILE_BODIES, -(-n // body))


def _padded(d: int, lanes: int, dtype) -> tuple:
    """(columns, lanes) as the kernel lays them out: columns in whole
    sublane tiles of the matrix's dtype (a vector register holds 8 rows of
    32 bits, 16 of 16), so that a lane's weighted block is whole tiles;
    lanes in whole float32 tiles, the padding lanes inert."""
    return _round_up(d, 8 * 4 // jnp.dtype(dtype).itemsize), \
        _round_up(lanes, 8)


def _gradient_slabs(lp: int, dtype) -> tuple:
    """(slabs, columns a slab) of the kernel's gradient block: a slab for
    each of the residual's parts and after them one for the curvature x
    weight, side by side, each as wide as a part has rows in the kernel —
    the lanes in whole sublane tiles of `dtype`, which the cast fills."""
    return residual_parts(dtype) + 1, _round_up(lp, _pack(dtype))


def vmem_bytes(d: int, lanes: int, dtype=jnp.bfloat16) -> int:
    """What the kernel keeps in VMEM for a bucket of `lanes`, in either
    tile form: the weighted blocks of every chunk of a body and beside them
    the residual's parts and the curvature's one, [lanes, chunk] each (three
    slabs of a bfloat16 matrix: 0.75 MiB at 64 lanes), the float32 sums and
    the tile of X (as X.T or as X: the same bytes), y, w and the fold masks
    twice each for the pipeline's buffers, and twice the coefficients' parts
    (three of a bfloat16 matrix: 0.1 MiB at 64 lanes of 128 columns). (The
    float32 products before the cast never exist whole: compiled for a v5e,
    256 lanes of 127 columns fit its 96 MiB.) At 128 columns a 64-lane bucket
    holds 32 MiB of weighted blocks, 8 MiB of sums and 9 MiB of tiles, 50
    MiB in all, and a 128-lane bucket 91 MiB (both compile for a v5e); 256
    lanes hold 172 MiB, and `glm_round_kernel` leaves that bucket to the
    blocks."""
    dp, lp = _padded(d, lanes, dtype)
    slabs, slab = _gradient_slabs(lp, dtype)
    item = jnp.dtype(dtype).itemsize
    tile = _CHUNK * _UNROLL * _TILE_BODIES
    return _UNROLL * (lp * dp + slabs * slab) * _CHUNK * item \
        + 2 * dp * (lp * dp + _round_up(slabs * slab, 128)) * 4 \
        + 2 * tile * (dp * item + 8 * 4 + 2 * 4) \
        + 2 * _parts.n_parts(dtype) * lp * dp * item


def dense_rows(v, n_rows=None):
    """A per-row vector (y, the weights) as `glm_moments` reads it: float32
    [R, 128], 128 rows of X a sublane, its first `n_rows` entries (default:
    all) and zeros up to whole tiles; made once a round program and not
    once a pass (`pallas_softmax.dense_rows`, at this kernel's tile)."""
    n = v.shape[0] if n_rows is None else int(n_rows)
    return jnp.pad(v[:n].astype(jnp.float32),
                   (0, _round_up(n, _tile_rows(n)) - n)).reshape(-1, 128)


@functools.partial(jax.jit, static_argnames=("loss", "n_rows", "interpret",
                                             "x_tile"))
def glm_moments(XT, y_rows, w_rows, fold_masks, sel, Bt, b0, mean, std, *,
                loss: str, n_rows=None, interpret: bool = False,
                x_tile: str = "rows_minor"):
    """(gA [lanes, d], hA [lanes, d, d], g0A [lanes], h0A [lanes], gA_low
    [lanes, d], cA [lanes, d]) float32: the sums over the first `n_rows`
    rows (default: all) of R xs', S xs xs', R and S, where xs is the
    standardised row in the matrix's dtype, R and S the loss's residual and
    curvature at xs' B + b0 times the lane's fold weight — one Newton
    iteration's pass of `_round_core` for a lane bucket. gA contracts R rounded to the matrix's
    dtype, as it always has; gA_low is the same sum over what that rounding
    left, R - rounded(R), cut to the dtype in its turn (zeros for a float32
    matrix, which rounds nothing): gA + gA_low is the gradient at the
    residual's float32 precision, and that is what the iteration steps on
    (`glm_sweep._round_core` adds the two before the mesh's all-reduce).
    cA, the last, is sum_rows S xs', the curvature-weighted column sums:
    the border of the Hessian, which couples the coefficients' step to the
    intercept's (`glm_sweep._newton_prox_update`). S rides the gradient's
    contraction as one more slab under the residual's parts, rounded to the
    matrix's dtype once: cA shapes the step and multiplies a zero at the
    fixed point, the standing the Gram's operands have. Every sum is zero
    in an inert lane.

    The matrix comes in the layout it already has on the chip, named by
    `x_tile` (`glm_sweep.glm_x_tile(d)`: what the width makes of it, no
    choice of the caller's), and no padded or re-laid-out copy is made of
    it in HBM: the last tile reads past n and masks.

    - "rows_minor": XT [d, n] is X.T, how the chip keeps a matrix whose
      width does not fill its last 128-column group (64, 100, 120
      columns); a grid step reads a [d, tile] tile of it.
    - "cols_minor": XT is X itself, [n, d] with d a multiple of 128, which
      the chip keeps as it is written; a grid step reads a [tile, d] tile,
      standardises a chunk of it with the columns on the lanes and turns
      the chunk over in VMEM (one float32 transpose of [chunk, d] a chunk),
      after which the two forms are one computation.

    y_rows, w_rows are `dense_rows` of y and w; fold_masks [F, n]; sel
    [F, lanes] maps lanes to folds; Bt [lanes, d] the coefficients, float32
    as the iteration carries them (or any dtype that holds them): the
    margins see them unrounded, through `float32_parts` made here once
    a pass — coefficients that are exact in the matrix's dtype leave every
    part but the first zero, and the sums are then, to the bit, those of
    the one-part contraction; b0 [lanes]; mean, std [d]. Columns pad to
    whole sublane tiles with zeros, cut from what is returned."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    cols_minor = x_tile == "cols_minor"
    n_buf, d = XT.shape if cols_minor else XT.shape[::-1]
    n = n_buf if n_rows is None else int(n_rows)
    F, lanes = sel.shape
    dp, lp = _padded(d, lanes, XT.dtype)
    slabs, slab = _gradient_slabs(lp, XT.dtype)
    if cols_minor and d % 128:
        raise ValueError(f"a cols_minor tile is whole 128-column groups, "
                         f"not {d} columns")
    tile = _tile_rows(n)

    def column(v, fill):
        v = jnp.pad(v.astype(f32), (0, dp - d), constant_values=fill)
        return v.reshape((1, dp) if cols_minor else (dp, 1))

    def by_rows(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    def whole(a):
        return by_rows(a.shape, lambda i: (0, 0))
    dense = by_rows((tile // 128, 128), lambda i: (i, 0))
    resident = (
        _parts.stacked_parts(
            jnp.pad(Bt, ((0, lp - lanes), (0, dp - d))), XT.dtype),
        jnp.pad(b0.astype(f32), (0, lp - lanes)).reshape(lp, 1),
        jnp.pad(sel.T.astype(f32), ((0, lp - lanes), (0, 0))),
        column(mean, 0.0), column(std, 1.0))
    out_shape = (jax.ShapeDtypeStruct((dp, lp * dp), f32),
                 jax.ShapeDtypeStruct((dp, _round_up(slabs * slab, 128)), f32),
                 jax.ShapeDtypeStruct((lp, 128), f32),
                 jax.ShapeDtypeStruct((lp, 128), f32))
    if cols_minor:
        kernel = functools.partial(_kernel_cols, n=n, tile=tile, loss=loss)
        x_spec = by_rows((tile, d), lambda i: (i, 0))
    else:
        kernel = functools.partial(_kernel, n=n, d=d, tile=tile, loss=loss)
        x_spec = by_rows((dp, tile), lambda i: (0, i))
    h, g, g0, h0 = pl.pallas_call(
        kernel,
        grid=(-(-n // tile),),
        in_specs=[x_spec, dense, dense,
                  by_rows((F, tile), lambda i: (0, i))]
        + [whole(a) for a in resident],
        out_specs=tuple(whole(s) for s in out_shape),
        out_shape=out_shape,
        compiler_params=pallas_hist._compiler_params(),
        name="glm_moments",
        interpret=interpret,
    )(XT, y_rows, w_rows, fold_masks.astype(f32), *resident)
    # h[j, lane * dp + i] = sum_c xs[j, c] (S[lane, c] xs[i, c])
    hA = h.reshape(dp, lp, dp).transpose(1, 2, 0)
    # g[j, part * slab + lane] = sum_c xs[j, c] R_part[lane, c], and in the
    # last slab sum_c xs[j, c] S[lane, c]
    gA = g[:d, :lanes].T
    border = (slabs - 1) * slab
    gA_low = _parts.slab_sum(g[:, slab:border], slab, axis=1)[:d, :lanes].T \
        if slabs > 2 else jnp.zeros_like(gA)
    return gA, hA[:lanes, :d, :d], g0.sum(axis=1)[:lanes], \
        h0.sum(axis=1)[:lanes], gA_low, g[:d, border:border + lanes].T


def _kernel_cols(x_ref, y_ref, w_ref, m_ref, bt_ref, b0_ref, selT_ref,
                 mean_ref, std_ref, h_ref, g_ref, g0_ref, h0_ref, *, n, tile,
                 loss):
    """`_kernel` for a [tile, d] tile of X, the columns on the lanes: a
    chunk is standardised as it lies, turned over ([chunk, d] -> [d, chunk],
    float32, in VMEM) and cast, and from there on every line is `_kernel`'s
    (kept apart from it, and after it in the file, so that the 64-column
    body keeps its source lines and with them its place in the compile
    cache). d is whole 128-column groups: no column is padded."""
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    chunk, groups = _CHUNK, _CHUNK // 128
    i = pl.program_id(0)
    d, dtype = x_ref.shape[1], x_ref.dtype
    lanes, folds = selT_ref.shape
    pack = _pack(dtype)
    rc = residual_curvature(loss)
    over_rows = (((1,), (1,)), ((), ()))

    @pl.when(i == 0)
    def _():
        for ref in (h_ref, g_ref, g0_ref, h0_ref):
            ref[...] = jnp.zeros_like(ref)

    x_rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, d), 0)
    f_cols = jax.lax.broadcasted_iota(jnp.int32, (folds, chunk), 1)
    bt, b0, selT = bt_ref[...], b0_ref[...], selT_ref[...]
    mean, std = (jnp.broadcast_to(v[...], (chunk, d))
                 for v in (mean_ref, std_ref))

    def lane_sums(ref, V):
        part = V[:, 0:128]
        for k in range(1, groups):
            part = part + V[:, k * 128:(k + 1) * 128]
        ref[...] += part

    def one_chunk(j):
        off = pl.multiple_of(j * chunk, chunk)
        left = n - (i * tile + off)
        xs = jnp.where(
            x_rows < left,
            (x_ref[pl.ds(off, chunk), :].astype(f32) - mean) / std,
            0.0).T.astype(dtype)                                  # [d, c]
        eta = _parts.slab_sum(
            jnp.dot(bt, xs, preferred_element_type=f32), lanes) + b0  # [L, c]
        sub = pl.ds(pl.multiple_of(j * groups, groups), groups)
        y_row, w_row = (jnp.concatenate(
            [v[k:k + 1, :] for k in range(groups)], axis=1)
            for v in (y_ref[sub, :], w_ref[sub, :]))              # [1, c]
        r0, s0 = rc(eta, y_row)
        mw = jnp.where(f_cols < left,
                       m_ref[:, pl.ds(off, chunk)] * w_row, 0.0)
        wl = selT[:, 0:1] * mw[0:1, :]
        for f in range(1, folds):
            wl = wl + selT[:, f:f + 1] * mw[f:f + 1, :]           # [L, c]
        R, S = r0 * wl, s0 * wl
        lane_sums(g0_ref, R)
        lane_sums(h0_ref, S)
        Rw, Sw = R, S
        if lanes % pack:
            Rw, Sw = (jnp.concatenate(
                [V, jnp.zeros((pack - lanes % pack, chunk), f32)], axis=0)
                for V in (R, S))
        Rp = jnp.concatenate([V.astype(dtype) for V in _parts.float32_parts(
            Rw, dtype, residual_parts(dtype), in_kernel=True) + [Sw]], axis=0)
        g_ref[:, 0:Rp.shape[0]] += jax.lax.dot_general(
            xs, Rp, over_rows, preferred_element_type=f32)
        h_ref[...] += jax.lax.dot_general(
            xs, (S[:, None, :] * xs.astype(f32)[None, :, :]).astype(dtype)
            .reshape(lanes * d, chunk), over_rows,
            preferred_element_type=f32)

    def body(j, carry):
        for u in range(_UNROLL):
            one_chunk(_UNROLL * j + u)
        return carry

    jax.lax.fori_loop(0, tile // (chunk * _UNROLL), body, 0)
