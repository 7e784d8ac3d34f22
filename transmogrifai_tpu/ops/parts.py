"""A float32 as parts of a narrower dtype: the two cuts the package makes.

The matrix unit multiplies bfloat16 operands and adds in float32, and a
product of two bfloat16 values is exact in float32. So an operand that must
go in at float32 precision (the coefficients an iteration's fixed point
hangs on, a residual, a leaf table, a real-valued tree payload) goes in as
PARTS: float32 arrays, each exact in the operand's dtype, whose sum is the
value. The parts ride one contraction, one above the other or side by side,
and the product's slabs are added in float32 (`slab_sum`).

`float32_parts` is the floating cut: each part the nearest `dtype` value of
what the parts before it left, so three parts of bfloat16 hold a float32's
24 significant bits whatever its size. `unit_cuts` is the fixed-point cut of
a payload scaled into [-1, 1]: whole multiples of 2^-7 and 2^-15, so that
the SUMS of a part over millions of rows are exact in float32 too.

How a part is cut, said once (PERF.md §6, PR 29). Outside a Mosaic body the
cut is `lax.reduce_precision`, never a cast and back: XLA allows excess
precision inside a fusion, and on the v5e a fused float32 -> bfloat16 ->
float32 round trip of a program parameter came back UNROUNDED — the next
part was then zero and the sweep scored with coefficients rounded to
bfloat16. Inside a Mosaic body (`in_kernel`) the cast and back IS the cut:
Mosaic lowers no `reduce_precision` and fuses nothing away. Either way only
the parts before the last are cut and subtracted; the last is what is left,
and whoever casts it to `dtype` makes a one-way cast, which nothing elides.

Stdlib and jax only: this module imports nothing of the package, so every
kernel module and their callers can take it and no kernel module imports a
sibling for it. (Its lines are in the locations of the kernels that cut
inside their bodies, ops/pallas_glm.py and ops/pallas_rank_hist.py: an edit
that moves them moves those kernels' compile-cache keys.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def n_parts(dtype) -> int:
    """Parts of `dtype` that hold a float32's 24 significant bits."""
    return -(-24 // (jnp.finfo(dtype).nmant + 1))


def float32_parts(V, dtype, parts=None, *, in_kernel: bool = False) -> list:
    """The float32 operand V as its leading `parts` parts of `dtype`, the
    largest first: a list of float32 arrays of V's shape whose sum is V.
    The default, and the most, is as many as 24 significant bits take of
    `dtype`'s (`n_parts`: three of bfloat16, one of float32 — V itself, so
    a caller that asked for two and got one has a float32 matrix). Every
    part but the last is exact in `dtype`; the last is what the others left,
    which the caller's cast to `dtype` rounds: exactly, where all the parts
    were taken (their float32 sum is then V to the bit); of bfloat16, to
    2^-17 |V| at two parts and 2^-8 |V| at one. A V that is exact in `dtype`
    leaves every part after the first zero, and ONE part is V untouched: the
    loop adds no operation.
    A product of a part with a `dtype` block is exact in float32, so the
    contraction of the parts against a block, its slabs added (`slab_sum`),
    is the float32 contraction to that much. `in_kernel`: inside a Mosaic
    body (the module's docstring has why the cut differs)."""
    info, rest, out = jnp.finfo(dtype), V.astype(jnp.float32), []
    most = n_parts(dtype)
    for _ in range((most if parts is None else min(parts, most)) - 1):
        part = rest.astype(dtype).astype(jnp.float32) if in_kernel else \
            jax.lax.reduce_precision(rest, exponent_bits=info.nexp,
                                     mantissa_bits=info.nmant)
        out.append(part)
        rest = rest - part
    return out + [rest]


def stacked_parts(V, dtype, parts=None):
    """[parts x rows, cols] in `dtype`: `float32_parts` of V [rows, cols],
    each cast to `dtype`, one above the other as ONE operand of a
    contraction. (Each part cast by itself and the stack made in `dtype`:
    stacked in float32 and cast once, the Gram pass of
    `sweep-linreg-nulls128` read 1.6 % longer on the chip, PERF.md §6,
    PR 56.)"""
    return jnp.concatenate([p.astype(dtype) for p in
                            float32_parts(V, dtype, parts)], axis=0)


def slab_sum(stacked, lanes: int, axis: int = 0):
    """The float32 sum of the slabs of `lanes` that a contraction of
    `stacked_parts` leaves along `axis`, the smallest part's first."""
    slabs = [jax.lax.slice_in_dim(stacked, k, k + lanes, axis=axis)
             for k in range(0, stacked.shape[axis], lanes)]
    total = slabs[-1]
    for slab in slabs[-2::-1]:
        total = total + slab
    return total


def unit_cuts(x) -> list:
    """float32 x as three float32 arrays whose sum is x, the first two
    exact in bfloat16 while |x| <= 1: the nearest multiple of 2^-7 (a whole
    number of at most eight bits over 128), the nearest multiple of 2^-15 of
    what is left (the same over 2^15), and the rest, under 2^-16, which the
    caller's cast rounds at 2^-25. Fixed quanta and not `float32_parts`'
    floating cuts, because the histogram kernels add a part over 250 000
    rows a grid step and float32 would round a sum of floating parts at
    every one: sums of the first two parts over millions of rows are whole
    numbers under 2^24 in their own units, which float32 adds exactly. Past
    |x| = 1 the casts round the first two as well and the three still hold
    x's 24 bits.
    floor(. + 0.5), not round: Mosaic lowers it everywhere."""
    hi = jnp.floor(x * 128.0 + 0.5) * (1.0 / 128.0)
    hi = hi.astype(jnp.bfloat16).astype(jnp.float32)
    rest = x - hi
    mid = jnp.floor(rest * 32768.0 + 0.5) * (1.0 / 32768.0)
    mid = mid.astype(jnp.bfloat16).astype(jnp.float32)
    return [hi, mid, rest - mid]
