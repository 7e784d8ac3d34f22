"""Operations and bytes of the histogram passes of a forest sweep that runs
as (tree, fold) lanes of the fused kernels, from shapes: the numerator of
`rf_hist_kernel_roofline`. Kept with the benchmark so that no PR that claims
a gain can change it.

It counts the contraction the kernels ISSUE: a pass over the binned matrix
contracts q [lanes x slots x 3, rows] (every lane's slot one-hot times its
g, h and count rows) with the (feature, bin) one-hot [rows, F x B] — 2 x
lanes x slots x 3 x F x B x rows operations, over every row the pass reads
(rows padded to the routing block), at every slot of the level: a row
belongs to one slot, and the dense form multiplies it into all of them.
`opcount.tree_sweep`, the numerator of `sweep-gbt`'s `hist_kernel_roofline`,
counts two payload channels of the LIVE rows at ONE slot a level instead —
what a histogram needs, not what the MXU is handed — so the two shares are
not on one scale: this one says how near the bf16 peak the issued
contraction runs, and cannot pass 100 %; that one how much of the kernel's
time the needed sums would take at the peak.
"""
from __future__ import annotations

ROUTE_BLOCK = 4096      # rows are padded to the routing kernels' block
CHANNELS = 3            # g, h and the count the kernels derive from h


def slot_passes(depth: int) -> int:
    """Slots summed over the histogram passes of one depth-`depth` tree:
    the root at 1, then a fused route-and-histogram pass at 1, 2, ...,
    2^(depth - 2) left-child slots (the sibling is the parent less the
    left child; the last level only routes): 32 at depth 6."""
    return 1 + sum(1 << d for d in range(max(depth - 1, 0)))


def forest_group(rows: int, features: int, bins: int, lanes: int,
                 depth: int) -> tuple:
    """The histogram passes of ONE lane group: `depth` passes, each a read
    of the int8 binned matrix, of the lanes' two payload planes and node
    ids (and, fused, a write of the new ids), and a write of the level's
    [lanes x slots x 3, F x bins] float32 block. `bins` counts the
    missing-value bin. (flops, bytes)"""
    padded = -(-rows // ROUTE_BLOCK) * ROUTE_BLOCK
    flops = 2.0 * lanes * slot_passes(depth) * CHANNELS \
        * features * bins * padded
    byts = depth * (padded * features + lanes * padded * 4 * 3) \
        + max(depth - 1, 0) * lanes * padded * 4 \
        + lanes * slot_passes(depth) * CHANNELS * features * bins * 4
    return flops, float(byts)


def forest_sweep(rows: int, features: int, folds: int, lanes_per_group: int,
                 grids: list) -> tuple:
    """Every histogram pass of one sweep over forest grid points (dicts
    with num_trees, max_depth, max_bins; other families' points are
    skipped): a point's trees x folds go `lanes_per_group` lanes a group,
    the last group padded with dead trees that are contracted like live
    ones. (flops, bytes)"""
    flops = byts = 0.0
    trees_a_group = max(lanes_per_group // folds, 1)
    for g in grids:
        if "num_trees" not in g:
            continue
        groups = -(-g["num_trees"] // trees_a_group)
        f, b = forest_group(rows, features, g["max_bins"] + 1,
                            trees_a_group * folds, g["max_depth"])
        flops, byts = flops + groups * f, byts + groups * b
    return flops, byts
