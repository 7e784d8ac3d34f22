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
is integer arithmetic or a sort whose keys leave no tie (the row id is the
last of them), so the CPU backend and the chip draw the same folds and a
checkpoint replays on either.

On a mesh (`assign_fold_masks_sharded`) the SAME assignment comes back
sharded on rows, and no chip sorts the whole table: the random words are a
function of the row id alone and uniform, so the one global order is
range-partitioned on the leading word without a sample. A chip makes the
words of its OWN rows, sorts them, sends every chip the run that falls in
that chip's fixed window of the leading word (one `all_to_all` of padded
runs), sorts what it receives and reads, at an offset that one `psum` of
the runs' lengths gives it, exactly the slice of the global order that
ranks its own rows. A seed whose runs outgrow the static padding is
answered inside the same program by the one sort of all the rows on every
chip, which is also how the stratified rule runs (it leads with the label,
whose classes no fixed range balances; the labels all-gathered first). Bit
for bit the one-device masks, on any number of shards, for every seed.
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
from jax.extend.random import threefry2x32_p

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
# The chip's sort takes 3 % (25M keys) to a tenth (32M) longer a key over a
# length that is no multiple of this (PERF.md, PRs 45 and 50): every sort
# here runs over one.
_SORT_TILE = 2048


def fold_sort_shape(n: int, stratify: bool) -> dict:
    """The shape of the one-device fold program's sort over `n` rows, as
    _shuffled_rows builds it and the `fold_assign` span says it:
    `sort_keys` (key operands — [label,] word 0, word 1, row id — and the
    sort carries nothing else), `sort_places` (`n` raised to a multiple of
    _SORT_TILE) and `pad_places` (the places past the rows)."""
    pad = -n % _SORT_TILE
    return dict(sort_keys=4 if stratify else 3, sort_places=n + pad,
                pad_places=pad)


def _row_words(key, n: int, start, count: int):
    """The two Threefry words of rows [start, start + count) of `n`: row
    i's are the Threefry-2x32 block of the counter pair (i, n + i) under
    the key words `fold_key(seed)` — what `threefry_2x32(key, [i, n + i])`
    returns, asked of the primitive itself: two arrays in, two out, where
    the wrapper concatenates the counters and reshapes the words, a pass
    over both that the chip runs as copies (PERF.md, PR 50)."""
    i = start.astype(jnp.uint32) + lax.iota(jnp.uint32, count)
    w0, w1 = threefry2x32_p.bind(key[0], key[1], i, jnp.uint32(n) + i)
    return w0, w1


def _shuffled_rows(key, n: int, labels=None):
    """([labels,] row ids) over `fold_sort_shape`'s places, sorted by the
    labels (when given), then by 64 seeded random bits a row (_row_words),
    then by the row id: ONE unstable sort whose every operand is a key. At
    25M rows two rows share all 64 bits with probability 2e-5, and the row
    id, the last key, then decides — the ids are distinct and ascending in
    input order, so that IS the order a stable sort on the words alone
    gives (version 2's tie rule), and stability costs the chip an index
    of its own: a fourth operand where the ids are no iota the compiler
    can see (a quarter of the sort: PERF.md, PR 45), 100 MB of temporaries
    at 25M rows where they are (PR 50). The places past the rows take
    all-ones words, the ids n, n + 1, ... and a label above every class,
    made in the fusion that makes the words (nothing is copied): larger
    than any row in the last key at the least, they sort behind every
    row, so the first `n` places are the rows'. With no labels the ids
    come back in a uniformly random order; with them, grouped by class and
    in random order within each."""
    shape = fold_sort_shape(n, labels is not None)
    places, pad = shape["sort_places"], shape["pad_places"]
    ids = lax.iota(jnp.int32, places)
    words = _row_words(key, n, jnp.uint32(0), places)
    if pad:
        words = [jnp.where(ids < n, w, jnp.uint32(0xFFFFFFFF)) for w in words]
    leading = () if labels is None else (
        jnp.pad(labels, (0, pad), constant_values=jnp.inf),)
    out = lax.sort((*leading, *words, ids), num_keys=shape["sort_keys"],
                   is_stable=False)
    return (*out[:len(leading)], out[-1])


def _fold_of_rank(rank, n: int, n_folds: int,
                  val_fraction: Optional[float]):
    """The unstratified rule, from a row's rank in the random order."""
    if val_fraction is not None:
        return (rank >= int(round(n * val_fraction))).astype(jnp.int32)
    return rank % n_folds


def _fold_of(key, y, n: int, n_folds: int, val_fraction: Optional[float],
             stratify: bool):
    """int32[n] fold that holds each row out (k-fold), or 0 = held out /
    1 = train (single split): assign_fold_masks' rule, before the mask."""
    split = val_fraction is not None
    if not stratify:
        # the sorted row ids ARE a uniformly random permutation: read as
        # "row i has rank ids[i]", no inverse needed
        rank, = _shuffled_rows(key, n)
        return _fold_of_rank(rank[:n], n, n_folds, val_fraction)
    # over all the sorted places: the padding is one more class behind the
    # rows', and its ids send its folds behind the rows' again
    cls, rows = _shuffled_rows(key, n, y)
    places = rows.shape[0]
    pos = lax.iota(jnp.int32, places)
    first = jnp.concatenate([jnp.ones((1,), bool), cls[1:] != cls[:-1]])
    start = lax.cummax(jnp.where(first, pos, 0))
    rank = pos - start
    if split:
        last = jnp.concatenate([first[1:], jnp.ones((1,), bool)])
        end = lax.cummin(jnp.where(last, pos, places - 1), reverse=True)
        n_val = _round_count_share(end - start + 1, val_fraction)
        fold_sorted = (rank >= n_val).astype(jnp.int32)
    else:
        fold_sorted = rank % n_folds
    # the keys are a permutation: no tie for stability to decide
    return lax.sort_key_val(rows, fold_sorted, is_stable=False)[1][:n]


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
    returns to row order by a second sort on the row ids. Rows whose 64
    random bits are equal keep their input order — the row id is the
    sort's last key (_shuffled_rows), which is what the stable sort of
    version 2 decided — so no sort here is stable and none carries an
    operand that is not a key; `fold_sort_shape` is the sort's shape."""
    return _train_masks(
        _fold_of(key, y, n, n_folds, val_fraction, stratify), n_folds,
        val_fraction is not None)


# -- the program on a mesh -----------------------------------------------------
# How far, in standard deviations of a binomial count, the static sizes of
# the partitioned order stand from what uniform words fill: a window of the
# leading word reaches that far past the positions its chip returns, and a
# run's padding that far past the run's expected length. Past it the
# program answers with the replicated sort, so this sets how often that
# happens (never, at 12), not what comes back.
_PARTITION_SIGMAS = 12


def _partition_plan(n: int, shards: int) -> tuple:
    """(margin, capacity) of the range-partitioned order over `n` rows on
    `shards` chips. Chip c returns the global positions [c, c + 1) *
    n / shards; its window is the leading words [ceil(c * 2**32 / shards)
    - margin, ceil((c + 1) * 2**32 / shards) + margin), `margin` the span
    of words that holds _PARTITION_SIGMAS deviations of the count of keys
    below a fixed word (at most sqrt(n) / 2). `capacity` is the places a
    run (one chip's keys in one window) is padded to: its expected length
    and as many deviations of it, and never more than the chip's rows; a
    multiple of _SORT_TILE, because the chip's sort of the `shards *
    capacity` places received takes a tenth longer over a length that is
    not one (32 271 872 keys 0.127 s, 2**25 keys 0.118: PERF.md, PR 45;
    the one-device sort's length is fold_sort_shape's, by the same rule)."""
    n_local = n // shards
    margin = math.ceil(_PARTITION_SIGMAS / 2 * math.sqrt(n) * 2 ** 32 / n)
    run = n_local * min(1.0, 1 / shards + 2 * margin / 2 ** 32)
    capacity = math.ceil(run + _PARTITION_SIGMAS * math.sqrt(run))
    return margin, min(n_local, capacity + -capacity % _SORT_TILE)


def _partitioned_ranks(w0, w1, n: int, axis_name: str,
                       capacity: Optional[int] = None):
    """Inside a shard_map over `axis_name`: (uint32[n / shards] row ids at
    the chip's own positions of the global order, bool[] overflow), from
    the chip's own rows' words `w0`, `w1`. The order is _shuffled_rows':
    by word 0, word 1, then row id, every operand a key and the sort
    unstable, which puts the padding (all ones, no row's id) after every
    key whatever its words.
    `overflow` is the same on every chip: a run longer than `capacity`
    (None: _partition_plan's; tests make a run overflow with a smaller
    one) or a window that misses a position its chip returns; the ids then
    mean nothing and the caller answers another way."""
    n_local = w0.shape[0]
    shards = n // n_local
    shard = lax.axis_index(axis_name)
    margin, planned = _partition_plan(n, shards)
    capacity = capacity or planned
    ids = shard.astype(jnp.uint32) * jnp.uint32(n_local) \
        + lax.iota(jnp.uint32, n_local)
    w0, w1, ids = lax.sort((w0, w1, ids), num_keys=3, is_stable=False)
    # window c of the sorted run is [lo[c], hi[c]): the keys whose leading
    # word lies in [edge c - margin, edge c + 1 + margin)
    edges = np.array([-(-c * 2 ** 32 // shards) for c in range(shards + 1)])

    def keys_below(words):
        found = jnp.searchsorted(
            w0, np.clip(words, 0, 2 ** 32 - 1).astype(np.uint32))
        return jnp.where(words >= 2 ** 32, n_local, found.astype(jnp.int32))

    lo, hi = keys_below(edges[:-1] - margin), keys_below(edges[1:] + margin)
    # ONE psum: where every window starts and ends in the global order,
    # and whether some run outgrew its places
    counts = lax.psum(jnp.concatenate(
        [lo, hi, jnp.any(hi - lo > capacity).astype(jnp.int32)[None]]),
        axis_name)
    first, last, long_runs = counts[:shards], counts[shards:-1], counts[-1]
    own = n_local * lax.iota(jnp.int32, shards)
    overflow = (long_runs > 0) | jnp.any(first > own) \
        | jnp.any(own + n_local > last)
    # a run's places: `capacity` of the sorted run from its start (from
    # further left where the run ends with the rows: a slice does not
    # leave the array), what lies outside the run padded over
    start = jnp.minimum(lo, n_local - capacity)
    at = start[:, None] + lax.iota(jnp.int32, capacity)[None, :]
    in_run = (at >= lo[:, None]) & (at < hi[:, None])
    pad = jnp.uint32(0xFFFFFFFF)
    runs = jnp.stack([
        jnp.where(in_run, jnp.stack([
            lax.dynamic_slice_in_dim(x, start[c], capacity)
            for c in range(shards)]), pad) for x in (w0, w1, ids)])
    got = lax.all_to_all(runs, axis_name, 1, 1).reshape(3, shards * capacity)
    order = lax.sort((got[0], got[1], got[2]), num_keys=3,
                     is_stable=False)[2]
    return lax.dynamic_slice_in_dim(
        order, n_local * shard - first[shard], n_local), overflow


def device_fold_route(n: int, stratify: bool) -> dict:
    """What assign_fold_masks runs over `n` rows, as the `fold_assign`
    span says it: `route` = `device` and fold_sort_shape's words."""
    return dict(route="device", **fold_sort_shape(n, stratify))


def sharded_fold_route(mesh, n: int, stratify: bool) -> dict:
    """What assign_fold_masks_sharded runs on `mesh` over `n` rows, as the
    `fold_assign` span says it: `route` (`partitioned`: each chip sorts
    its own rows and the runs it receives; `replicated`: every chip sorts
    all the rows — the stratified rule, and what answers a `partitioned`
    program whose overflow flag is set), `sort_keys` and `sort_places`
    (fold_sort_shape's words, for the longest sort a chip runs on that
    route; `pad_places` where the padding is static, the replicated
    sort's), `capacity` (places a run is padded to) and `exchange_bytes`
    (a chip's operand of the all_to_all)."""
    from ...parallel.mesh import mesh_batch_count
    if stratify:
        return dict(route="replicated", **fold_sort_shape(n, True),
                    capacity=0, exchange_bytes=0)
    shards = mesh_batch_count(mesh)
    _, capacity = _partition_plan(n, shards)
    return dict(route="partitioned", sort_keys=3,
                sort_places=shards * capacity, capacity=capacity,
                exchange_bytes=12 * shards * capacity)


@functools.lru_cache(maxsize=None)
def _sharded_fold_masks_fn(mesh, n: int, n_folds: int,
                           val_fraction: Optional[float], stratify: bool,
                           capacity: Optional[int] = None):
    """The jitted program of assign_fold_masks_sharded; `capacity` is
    _partitioned_ranks'."""
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import (
        BATCH_AXIS, build_shard_map, mesh_batch_count,
    )
    n_local = n // mesh_batch_count(mesh)

    def assign_fold_masks_sharded(key, *y_local):
        first_row = lax.axis_index(BATCH_AXIS) * n_local
        if stratify:
            y = lax.all_gather(y_local[0], BATCH_AXIS, tiled=True)
            own = lax.dynamic_slice_in_dim(
                _fold_of(key, y, n, n_folds, val_fraction, True),
                first_row, n_local)
            overflow = jnp.zeros((), bool)
        else:
            rank, overflow = _partitioned_ranks(
                *_row_words(key, n, first_row, n_local), n, BATCH_AXIS,
                capacity)
            rank = lax.cond(
                overflow,
                lambda: lax.dynamic_slice_in_dim(
                    _shuffled_rows(key, n)[0], first_row, n_local),
                lambda: rank.astype(jnp.int32))
            own = _fold_of_rank(rank, n, n_folds, val_fraction)
        return _train_masks(own, n_folds, val_fraction is not None), overflow

    return jax.jit(build_shard_map(
        assign_fold_masks_sharded, mesh,
        in_specs=(P(),) + (P(BATCH_AXIS),) * int(stratify),
        out_specs=(P(None, BATCH_AXIS), P())))


def assign_fold_masks_sharded(mesh, key, y, *, n: int, n_folds: int,
                              val_fraction: Optional[float] = None,
                              stratify: bool = False):
    """assign_fold_masks on a mesh: (the same [F, n] masks bit for bit,
    sharded on rows over the batch axis (`sharded_along(mesh, 1, 2)`), no
    chip holding more than its [F, n / shards] block; a device bool, the
    program's overflow flag). Unstratified, each chip sorts its own rows'
    keys and the runs of its window of the global order
    (_partitioned_ranks: one all_to_all, one psum of counts), and where
    the flag is set — a seed whose runs outgrow the static padding, which
    uniform words do not — every chip sorted all `n` keys in the same
    program instead. Stratified (`y` sharded on rows), the labels are
    all-gathered and every chip sorts the whole order; the flag is False.
    `n` divides by the shards, as the rows of a sharded array do."""
    fn = _sharded_fold_masks_fn(mesh, n, n_folds, val_fraction,
                                bool(stratify))
    return fn(key, y) if stratify else fn(key)
