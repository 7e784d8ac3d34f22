"""Operations and bytes a kernel's work needs, computed from shapes: the
numerators of every roofline share. Kept with the benchmark so that no PR
that claims a gain can change them.

The histogram model is tools/tpu_roofline.py's; the GLM
model is bench.py's `glm_flops_estimate` (streamed route). Both count what
the algorithm as implemented must do — the one-hot contraction on the MXU,
one read of the binned matrix per level — not what a better algorithm
might get away with, and not recomputation.
"""
from __future__ import annotations


def hist_pass(rows: int, features: int, lanes: int, channels: int,
              slots: int, bins: int, payload_bytes: int) -> tuple:
    """One gradient-histogram pass over the binned matrix, all fold lanes
    fused. Reads Xb_t [F, N] int8, the payload [lanes*channels, N] and the
    slot ids [lanes, N] f32; writes hist [lanes*slots*channels, F*bins]
    f32. FLOPs: the dense one-hot contraction [lanes*channels, N] x
    [N, F*bins] on the MXU. Returns (flops, bytes)."""
    read = rows * features + lanes * channels * rows * payload_bytes \
        + lanes * rows * 4
    write = lanes * slots * channels * features * bins * 4
    flops = 2.0 * rows * (lanes * channels) * (features * bins)
    return flops, float(read + write)


def tree_hist(rows: int, features: int, folds: int, configs: int,
              rounds: int, depth: int, bins: int,
              payload_bytes: int = 2, channels: int = 2) -> tuple:
    """Every histogram pass of one fold-fused boosted-tree sweep: per
    config and round, `depth` passes (level l holds 2**l slots; from level
    1 on the routing of the level above is fused into the pass, which then
    also rewrites the node ids). bins counts the missing-value bin:
    max_bins + 1. (flops, bytes)."""
    flops = byts = 0.0
    for level in range(depth):
        f, b = hist_pass(rows, features, folds, channels, 1 << level,
                         bins, payload_bytes)
        if level:
            b += folds * rows * 4          # fused routing writes the ids
        flops, byts = flops + f, byts + b
    return flops * configs * rounds, byts * configs * rounds


def tree_sweep(rows: int, features: int, folds: int, grids: list) -> tuple:
    """Every histogram pass of one sweep over boosted-tree grid points
    (dicts with num_round, max_depth, max_bins; other families' points
    are skipped): tree_hist of each, fold lanes fused. (flops, bytes)."""
    flops = byts = 0.0
    for g in grids:
        if "max_depth" in g:
            f, b = tree_hist(rows, features, folds, 1, g["num_round"],
                             g["max_depth"], g["max_bins"] + 1)
            flops, byts = flops + f, byts + b
    return flops, byts


def glm_sweep(rows: int, cols: int, padded_lane_passes: int,
              data_passes: int, itemsize: int) -> tuple:
    """The streamed GLM sweep: per executed (padded) lane-pass, eta 2nd +
    gradient 2nd + the full symmetric per-lane Gram 2nd^2; every data pass
    reads X [rows, cols] once for all its lanes. (flops, bytes)."""
    flops = (4.0 * rows * cols + 2.0 * rows * cols * cols) \
        * padded_lane_passes
    return flops, float(data_passes) * rows * cols * itemsize


def least_seconds(flops: float, byts: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which roof sets it."""
    t_f = flops / peaks["bf16_flops"]
    t_b = byts / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
