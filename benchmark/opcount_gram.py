"""Operations and bytes of the squared loss's Gram pass, from shapes: the
numerator of `lin_gram_roofline`. Kept with the benchmark so that no PR
that claims a gain can change it.

It counts what the mathematics needs, whatever implements it: the fit of
every fold and grid point is a function of the per-fold second moments of
[x, 1] and of their products with y, and a fold's training moments are all
rows' less its held-out rows' — so every row's (cols + 1)^2 outer product
is needed ONCE, whatever the number of folds, at one multiply and one add
an entry; and X, y, w and the fold masks are read once. A program that
issues a product a (row, training fold) — folds - 1 of them a row under
k-fold masks, or one a (row, fold) with a zero weight — does that many
times the counted operations and reads at most 1 / (folds - 1) of the
share; a pass at `highest` precision issues six bfloat16 matrix-unit
passes a float32 product and reads a sixth of its share of the bf16 peak
again. No later program that stops doing either can read over 100 %.
"""
from __future__ import annotations


def gram_pass(rows: int, cols: int, folds: int, itemsize: int) -> tuple:
    """(flops, bytes) of one Gram pass over a [rows, cols] matrix under
    `folds` fold masks: 2 x rows x (cols + 1)^2 operations; X once in its
    own dtype, y, w and the [folds, rows] masks once in float32."""
    return 2.0 * rows * (cols + 1) ** 2, \
        float(rows) * (cols * itemsize + 4 * (2 + folds))
