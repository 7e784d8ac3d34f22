"""Pallas TPU kernel for histograms over a WIDE bin axis: the rank
metrics' 4 096 score bins (ops/metrics_ops.py).

ops/pallas_hist._kernel builds one (feature, bin) one-hot row a BIN: at 33
bins a feature that is the cheapest form, at 4 096 it is 4 096 compares and
selects on the VPU to add one weight to one cell, and a contraction whose
left operand has the ten slot-and-class rows of the metric pass. A bin
b = hi * 128 + lo factors, 1[b] = 1[hi] * 1[lo], and so does the histogram:

    out[f, (r, hi), lo] += sum_i  A[r, i] * 1[hi_fi = hi] * 1[lo_fi = lo]

with A the slot-and-class payload rows (fold, slot, channel), built ONCE a
row block because they do not depend on the feature. A feature then costs
n_bins / 128 + 128 compares an element, the products of A with the `hi`
one-hot, and ONE contraction over the block's rows of that left operand
against the [128, blk] `lo` one-hot, which fills the array's 128 columns.

Both one-hots are exact in bfloat16; the payload keeps its float32 value
as `parts` bfloat16 parts whose sum it is (ops/parts.float32_parts: three
hold its 24 bits), stacked in the left operand, every product exact,
accumulation float32. A payload of zeros and ones (unit weights under 0/1
masks and labels) IS its first part, and the caller that can vouch for it
says so.

Same contract as pallas_hist.hist_pallas, which dispatches here (hist_body)
and stays the one entry. Kept apart from ops/pallas_hist.py on purpose: a
Mosaic body carries its source locations, so an edit that moves that file's
lines makes every kernel of it miss the compile cache (PERF.md, PR 27).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import pallas_hist
from . import parts as _parts

# bins of the `lo` one-hot, the contraction's right operand: the array's
# 128 columns. Probed on the v5e at the sweep's two forms (25M rows x 6 grid
# points, 5 folds as slots; 100M flattened elements, 10 lanes as slots;
# PERF.md §6, PR 40), seconds a call at one part / three: 128 bins 0.065 /
# 0.193 and 0.091 / 0.279 — the bf16 peak's 0.062 / 0.187 and 0.083 / 0.250
# — 64 bins twice that (half the array idle), 256 bins 0.066–0.076 / 0.194
# and 0.097 / 0.278.
_LO_BINS = 128
_LO_SHIFT = _LO_BINS.bit_length() - 1

# narrowest bin axis the two-level body takes: under it the one-level
# one-hot is the smaller build
_MIN_BINS = 1024

# rows of the payload operand A that one contraction expands by the `hi`
# one-hot (8, 16, 32: the same to 1 %), and rows of the block a grid step
# holds (1 024 rows 3–12 % over 4 096, 2 048 rows 1–4 %)
_GROUP_ROWS = 16
_BLK = 4096


def hist_body(n_bins: int, use_bf16: bool) -> str:
    """Which body hist_pallas runs, from what it sees in its arguments:
    "two_level" (this module) for the float32 mode — the rank metrics' —
    over whole 128-bin groups, 1 024 bins and up; "one_level"
    (pallas_hist._kernel) for everything else: the tree histograms'
    bfloat16 mode at 33 bins, narrow metric calls."""
    if not use_bf16 and n_bins >= _MIN_BINS and n_bins % _LO_BINS == 0:
        return "two_level"
    return "one_level"


def payload_parts(unit_payload: bool) -> int:
    """bfloat16 parts the two-level body takes a float32 payload in: one
    where the caller vouches that every value is 0 or 1, else three."""
    return 1 if unit_payload else 3


def _kernel(xb_ref, pay_ref, slot_ref, out_ref, *, F, C, n_slots, n_folds,
            hi_rows, parts, derive_count):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    blk = xb_ref.shape[1]
    f32 = jnp.float32
    Co = C + (1 if derive_count else 0)

    # the slot-and-class payload rows A [parts * SC, blk], part-major: the
    # same for every feature
    slots = jax.lax.broadcasted_iota(jnp.int32, (n_slots, blk), 0) \
        .astype(f32)
    cuts = [[] for _ in range(parts)]
    for k in range(n_folds):
        slot_oh = (slots == slot_ref[k:k + 1, :]).astype(f32)  # [S, blk]
        pay = pallas_hist._fold_payload(pay_ref, k, C, f32, derive_count)
        # with one part the payload itself, which the caller vouches is
        # exact in bfloat16: the cut adds no operation to the body
        for p, cut in enumerate(_parts.float32_parts(
                pay, jnp.bfloat16, parts, in_kernel=True)):    # [Co, blk]
            cuts[p].append((slot_oh[:, None, :] * cut[None, :, :])
                           .reshape(n_slots * Co, blk))
    pieces = [piece for part in cuts for piece in part]
    a = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=0)
    R = a.shape[0]

    b = xb_ref[...].astype(jnp.int32)                          # [F, blk]
    hi, lo = b >> _LO_SHIFT, b & (_LO_BINS - 1)
    hi_ids = jax.lax.broadcasted_iota(jnp.int32, (hi_rows, blk), 0)
    lo_ids = jax.lax.broadcasted_iota(jnp.int32, (_LO_BINS, blk), 0)
    for f in range(F):
        lo_oh = (lo_ids == lo[f:f + 1, :]).astype(jnp.bfloat16)
        hi_oh = (hi_ids == hi[f:f + 1, :]).astype(f32)         # [hi, blk]
        for r0 in range(0, R, _GROUP_ROWS):
            r1 = min(r0 + _GROUP_ROWS, R)
            rows = jax.lax.slice_in_dim(a, r0, r1, axis=0)     # [g, blk]
            left = (rows[:, None, :] * hi_oh[None, :, :]) \
                .reshape((r1 - r0) * hi_rows, blk).astype(jnp.bfloat16)
            out_ref[f, r0 * hi_rows:r1 * hi_rows, :] += jax.lax.dot_general(
                left, lo_oh, (((1,), (1,)), ((), ())),
                preferred_element_type=f32)                    # [.., 128]


@functools.partial(jax.jit,
                   static_argnames=("n_slots", "n_bins", "interpret",
                                    "parts", "derive_count"))
def _hist_two_level_jit(Xb_t, pay_t, slot_t, *, n_slots, n_bins, interpret,
                        parts, derive_count=False):
    """pallas_hist.hist_pallas's contract (its arguments, its result
    [n_folds * n_slots * Co, F * n_bins] float32) by the two-level body.
    The kernel leaves [F, (part, fold, slot, channel, hi), lo]; the parts'
    sum and the turn to hist_pallas's layout are XLA's, on F * n_bins *
    rows floats."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    F, N = Xb_t.shape
    n_folds = slot_t.shape[0]
    if pay_t.shape[0] % n_folds:
        raise ValueError(f"pay_t channels {pay_t.shape[0]} not a multiple "
                         f"of slot_t folds {n_folds}")
    C = pay_t.shape[0] // n_folds
    SC = n_folds * n_slots * (C + (1 if derive_count else 0))
    n_hi = n_bins // _LO_BINS
    hi_rows = -(-n_hi // 8) * 8      # whole sublane tiles; the rest is cut
    pad = (-N) % _BLK
    if pad:
        Xb_t = jnp.pad(Xb_t, ((0, 0), (0, pad)))
        pay_t = jnp.pad(pay_t, ((0, 0), (0, pad)))
        slot_t = jnp.pad(slot_t, ((0, 0), (0, pad)),
                         constant_values=float(n_slots))  # dropped
        N += pad

    kernel = functools.partial(_kernel, F=F, C=C, n_slots=n_slots,
                               n_folds=n_folds, hi_rows=hi_rows,
                               parts=parts, derive_count=derive_count)
    out_block = (F, parts * SC * hi_rows, _LO_BINS)
    acc = pl.pallas_call(
        kernel,
        grid=(N // _BLK,),
        in_specs=[
            pl.BlockSpec((F, _BLK), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((n_folds * C, _BLK), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((n_folds, _BLK), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(out_block, lambda i: (0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(out_block, jnp.float32),
        compiler_params=pallas_hist._compiler_params(),
        interpret=interpret,
    )(Xb_t, pay_t, slot_t)
    acc = acc.reshape(F, parts, SC, hi_rows, _LO_BINS)
    hist = acc[:, -1]                # the smallest part's sums first
    for p in range(parts - 2, -1, -1):
        hist = hist + acc[:, p]
    return hist[:, :, :n_hi].reshape(F, SC, n_bins) \
        .transpose(1, 0, 2).reshape(SC, F * n_bins)
