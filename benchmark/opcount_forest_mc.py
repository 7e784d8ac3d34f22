"""Operations and bytes of the histogram passes of a MULTICLASS forest sweep
that runs as (tree, fold) lanes of the fused kernels under K class channels,
from shapes: the numerator of `rfm_hist_kernel_roofline`. Kept with the
benchmark so that no PR that claims a gain can change it.

`opcount_forest.forest_group`'s dense-slot count at K + 1 channels in place
of 3: a pass over the binned matrix contracts q [lanes x slots x (K + 1),
rows] — every lane's slot one-hot times its K class channels and its count
row; the weight sums are the class sums added and need no row — with the
(feature, bin) one-hot [rows, F x B]: 2 x lanes x slots x (K + 1) x F x B x
rows operations over every row the pass reads, at every slot of the level.
That is the LEAST a dense-slot contraction of K class sums and a count
issues, whatever rows the kernels hand the MXU: kernels that issue K + 1
rows a (lane, slot) (this PR's) can read up to 100 % of the peak, a form
that streamed a weight row beside them (K + 2) at most (K + 1) / (K + 2),
and no form reads over 100 %. Bytes: the same reads as the binary forest's
— the int8 binned matrix and a lane's two planes [class id, weight] a pass,
the node ids in and, fused, out — and the write of each level's [lanes x
slots x (K + 1), F x bins] float32 block.
"""
from __future__ import annotations

from benchmark.opcount_forest import ROUTE_BLOCK, slot_passes


def forest_group(rows: int, features: int, bins: int, lanes: int,
                 depth: int, classes: int) -> tuple:
    """The histogram passes of ONE lane group at K = `classes` class
    channels and the count a (lane, slot). `bins` counts the missing-value
    bin. (flops, bytes)"""
    channels = classes + 1
    padded = -(-rows // ROUTE_BLOCK) * ROUTE_BLOCK
    flops = 2.0 * lanes * slot_passes(depth) * channels \
        * features * bins * padded
    byts = depth * (padded * features + lanes * padded * 4 * 3) \
        + max(depth - 1, 0) * lanes * padded * 4 \
        + lanes * slot_passes(depth) * channels * features * bins * 4
    return flops, float(byts)


def forest_sweep(rows: int, features: int, folds: int, lanes_per_group: int,
                 classes: int, grids: list) -> tuple:
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
                            trees_a_group * folds, g["max_depth"], classes)
        flops, byts = flops + groups * f, byts + groups * b
    return flops, byts
