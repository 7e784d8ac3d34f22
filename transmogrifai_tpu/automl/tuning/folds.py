"""Fold assignment on the device: ONE jitted program from a seed to the
`[folds, rows]` float32 train-mask the sweep routes consume.

Reference: OpCrossValidation.scala:41 / OpTrainValidationSplit.scala:34
(MLUtils.kFold over a seeded shuffle; prepareStratification:203 for the
stratified variants). Every sweep route reads the mask on the device and
the matrix and labels already live there, so the mask is made there too:
`Validator.validate()` dispatches `assign_fold_masks` and does no work on
the host that grows with the number of rows.

The assignment is a function of (seed, rows, folds | validation share, and
the labels when stratified) alone. The random words come from the Threefry
2x32 hash applied directly (not through `jax.random.bits`, whose layout
follows the global `jax_threefry_partitionable` flag) and every other step
is integer arithmetic or a stable sort, so the CPU backend and the chip
draw the same folds and a checkpoint replays on either.

On a mesh (`assign_fold_masks_sharded`) the SAME assignment comes back
sharded on rows: the random words are a function of the row id alone, so
every chip forms the one global order for itself (the labels, when
stratified, all-gathered first), keeps the folds of its own rows and
builds only its `[F, rows / shards]` block of the mask. Bit for bit the
one-device masks, on any number of shards.
"""
from __future__ import annotations

import functools
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.extend.random import threefry_2x32

# Version of the fold-assignment algorithm, folded into every sweep
# checkpoint key (checkpoint.sweep_key): a record written under another
# assignment ran on other folds and must invalidate, not replay.
# 1 = numpy PCG64 shuffles on the host (before PR 24); 2 = this module.
FOLD_ASSIGNMENT_VERSION = 2


def fold_key(seed: int) -> np.ndarray:
    """uint32[2] Threefry key words of a validator seed — the key data of
    `jax.random.key(seed, impl="threefry2x32")` for seeds under 2**32,
    and defined for any Python int beyond it."""
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


# -- exact round(count * fraction) -------------------------------------------
_LIMB = 15
_LIMB_MASK = (1 << _LIMB) - 1


def _bit_field(digits, lo: int, width: int):
    """Bits [lo, lo + width) of the integer whose little-endian base-2**15
    digits are `digits`, as uint32 (width <= 31; positions are static)."""
    out = jnp.zeros_like(digits[0])
    for k, d in enumerate(digits):
        a, b = max(k * _LIMB, lo), min((k + 1) * _LIMB, lo + width)
        if a < b:
            piece = (d >> (a - k * _LIMB)) & jnp.uint32((1 << (b - a)) - 1)
            out = out | (piece << (a - lo))
    return out


def _round_count_share(count, fraction: float):
    """int32 `round(count * fraction)` for int32 counts >= 0 and a static
    float64 `fraction` in (0, 1), bit for bit what Python computes: the
    product rounded to float64 (nearest-even at 53 bits), then to the
    nearest integer, halves to even. Float32 cannot hold a 25M-row class
    count times a share to the integer, and the chip has no float64, so
    the product is formed exactly in 15-bit limbs of uint32."""
    mant, exp = math.frexp(fraction)
    M, S = int(mant * (1 << 53)), 53 - exp      # fraction == M / 2**S
    if S - 1 >= 6 * _LIMB:                      # count * fraction < 2**-6
        return jnp.zeros_like(count)
    c = count.astype(jnp.uint32)
    cs = [c & _LIMB_MASK, (c >> _LIMB) & _LIMB_MASK, c >> (2 * _LIMB)]
    ms = [(M >> (_LIMB * j)) & _LIMB_MASK for j in range(4)]
    digits, carry = [], jnp.zeros_like(c)       # P = count * M < 2**84
    for k in range(6):
        col = carry
        for i in range(3):
            if 0 <= k - i < 4 and ms[k - i]:
                col = col + cs[i] * jnp.uint32(ms[k - i])
        digits.append(col & _LIMB_MASK)
        carry = col >> _LIMB
    # float64 keeps 53 bits of P: it drops t low bits, to nearest-even
    bit_len = jnp.zeros_like(c)
    for k, d in enumerate(digits):
        bit_len = jnp.where(d > 0, k * _LIMB + 32 - lax.clz(d), bit_len)
    t = jnp.maximum(bit_len, 53) - 53
    slack = jnp.where(t > 0, jnp.uint32(1) << (jnp.maximum(t, 1) - 1), 0)
    # x = P / 2**S = whole + (half_bit * 2**(S-1) + g) / 2**S. The dropped
    # bits lie far below the half bit (t <= S - 22), so the rounded product
    # is EXACTLY whole + 1/2 when g is within `slack` of 0 (half bit set)
    # or of 2**(S-1) (half bit clear), and on the same side of it as x
    # otherwise.
    whole = _bit_field(digits, S, 31)
    half_bit = _bit_field(digits, S - 1, 1) == 1
    g_low = _bit_field(digits, 0, 31)
    g_high_zero = g_high_ones = jnp.ones_like(half_bit)
    for lo in range(31, S - 1, 31):
        width = min(31, S - 1 - lo)
        f = _bit_field(digits, lo, width)
        g_high_zero &= f == 0
        g_high_ones &= f == jnp.uint32((1 << width) - 1)
    tie = jnp.where(
        half_bit, g_high_zero & (g_low <= slack),
        g_high_ones & (g_low != 0)
        & ((jnp.uint32(1) << 31) - g_low <= slack))
    up = jnp.where(tie, whole & 1, half_bit.astype(jnp.uint32))
    return (whole + up).astype(jnp.int32)


# -- the program --------------------------------------------------------------
def _shuffled_rows(key, n: int, *leading):
    """(*leading, row ids 0..n-1) sorted by the `leading` keys, then by 64
    seeded random bits a row: ONE stable sort (two Threefry words; at 25M
    rows two rows share all 64 with probability 2e-5, and stability then
    decides). Row i's two words are the Threefry-2x32 block of the counter
    pair (i, n + i) under the key words `fold_key(seed)`. With no leading key the row ids come back in a uniformly
    random order; with the labels, grouped by class and in random order
    within each."""
    words = threefry_2x32((key[0], key[1]),
                          lax.iota(jnp.uint32, 2 * n).reshape(2, n))
    out = lax.sort((*leading, words[0], words[1], lax.iota(jnp.int32, n)),
                   num_keys=len(leading) + 2, is_stable=True)
    return (*out[:len(leading)], out[-1])


def _fold_of(key, y, n: int, n_folds: int, val_fraction: Optional[float],
             stratify: bool):
    """int32[n] fold that holds each row out (k-fold), or 0 = held out /
    1 = train (single split): assign_fold_masks' rule, before the mask."""
    split = val_fraction is not None
    if not stratify:
        # the sorted row ids ARE a uniformly random permutation: read as
        # "row i has rank ids[i]", no inverse needed
        rank, = _shuffled_rows(key, n)
        return (rank >= int(round(n * val_fraction))).astype(jnp.int32) \
            if split else rank % n_folds
    cls, rows = _shuffled_rows(key, n, y)
    pos = lax.iota(jnp.int32, n)
    first = jnp.concatenate([jnp.ones((1,), bool), cls[1:] != cls[:-1]])
    start = lax.cummax(jnp.where(first, pos, 0))
    rank = pos - start
    if split:
        last = jnp.concatenate([first[1:], jnp.ones((1,), bool)])
        end = lax.cummin(jnp.where(last, pos, n - 1), reverse=True)
        n_val = _round_count_share(end - start + 1, val_fraction)
        fold_sorted = (rank >= n_val).astype(jnp.int32)
    else:
        fold_sorted = rank % n_folds
    return lax.sort_key_val(rows, fold_sorted)[1]


def _train_masks(fold_of, n_folds: int, split: bool):
    folds = lax.iota(jnp.int32, 1 if split else n_folds)
    return (fold_of[None, :] != folds[:, None]).astype(jnp.float32)


@partial(jax.jit,
         static_argnames=("n", "n_folds", "val_fraction", "stratify"))
def assign_fold_masks(key, y, *, n: int, n_folds: int,
                      val_fraction: Optional[float] = None,
                      stratify: bool = False):
    """[F, n] float32 train-membership masks (1 = train, 0 = held out).

    `key` uint32[2] (`fold_key(seed)`, traced: a new seed never
    recompiles); `y` float32[n] labels, read only when `stratify`.
    k-fold (`val_fraction` None): F = `n_folds`; a row's fold is its rank
    in a random order modulo F, so every row is held out exactly once and
    fold sizes differ by at most one. Single split (`val_fraction` the
    held-out share): F = 1; the first `round(n * val_fraction)` ranks are
    held out. Stratified: the rank is taken within the row's class (rows
    sorted by class then random bits, rank = position less the class's
    segment start), so the balance holds per class; the per-row result
    returns to row order by a second sort on the row ids."""
    return _train_masks(
        _fold_of(key, y, n, n_folds, val_fraction, stratify), n_folds,
        val_fraction is not None)


@functools.lru_cache(maxsize=None)
def _sharded_fold_masks_fn(mesh, n: int, n_folds: int,
                           val_fraction: Optional[float], stratify: bool):
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import (
        BATCH_AXIS, build_shard_map, mesh_batch_count,
    )
    n_local = n // mesh_batch_count(mesh)

    def assign_fold_masks_sharded(key, *y_local):
        y = lax.all_gather(y_local[0], BATCH_AXIS, tiled=True) \
            if stratify else None
        own = lax.dynamic_slice_in_dim(
            _fold_of(key, y, n, n_folds, val_fraction, stratify),
            lax.axis_index(BATCH_AXIS) * n_local, n_local)
        return _train_masks(own, n_folds, val_fraction is not None)

    return jax.jit(build_shard_map(
        assign_fold_masks_sharded, mesh,
        in_specs=(P(),) + (P(BATCH_AXIS),) * int(stratify),
        out_specs=P(None, BATCH_AXIS)))


def assign_fold_masks_sharded(mesh, key, y, *, n: int, n_folds: int,
                              val_fraction: Optional[float] = None,
                              stratify: bool = False):
    """assign_fold_masks on a mesh: the same [F, n] masks bit for bit,
    sharded on rows over the batch axis (`sharded_along(mesh, 1, 2)`), no
    chip holding more than its [F, n / shards] block. Every chip sorts the
    whole order (the words depend on the row id alone; `y`, sharded on
    rows and read only when `stratify`, is all-gathered), so the program
    issues no collective unless stratified. `n` divides by the shards, as
    the rows of a sharded array do."""
    fn = _sharded_fold_masks_fn(mesh, n, n_folds, val_fraction,
                                bool(stratify))
    return fn(key, y) if stratify else fn(key)
