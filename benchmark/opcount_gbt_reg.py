"""Operations and bytes of the histogram passes of a REGRESSION booster
sweep (Spark's GBT family: a point's rounds under the key `max_iter`), from
shapes: the numerator of `gbr_hist_kernel_roofline`. Kept with the
benchmark so that no PR that claims a gain can change it.

`opcount.tree_sweep` reads a point's rounds under XGBoost's key
`num_round`; this is the same count under the other key and copies none of
its arithmetic: every pass is `opcount.tree_hist`'s — two payload channels
of the LIVE rows at ONE slot a level, what a histogram needs, whatever
carries the payload. Kernels that issue five rows a (lane, slot) for a
three-part residual do 5/3 of the contraction three rows would, for the
same counted work: the share reads lower, and cannot pass 100 %.
"""
from __future__ import annotations

from benchmark import opcount


def booster_sweep(rows: int, features: int, folds: int, grids: list) -> tuple:
    """Every histogram pass of one sweep over GBT grid points (dicts with
    max_iter, max_depth, max_bins; other families' points are skipped):
    opcount.tree_hist of each, fold lanes fused. (flops, bytes)"""
    flops = byts = 0.0
    for g in grids:
        if "max_iter" in g and "max_depth" in g:
            f, b = opcount.tree_hist(rows, features, folds, 1, g["max_iter"],
                                     g["max_depth"], g["max_bins"] + 1)
            flops, byts = flops + f, byts + b
    return flops, byts
