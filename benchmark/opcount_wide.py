"""Operations and bytes of the wide binary GLM sweep, from shapes: the
numerators of `wglm_rounds_roofline` and `wglm_gram_roofline`. Kept with
the benchmark so that no PR that claims a gain can change them.

They count what the algorithm must do on the MXU and from HBM, not how the
program does it: a coefficient contraction split into two bf16 parts is
one contraction here, and the columns the chip's tiled layout pads a row
with are not read.
"""
from __future__ import annotations


def wide_rounds(rows: int, cols: int, padded_lane_passes: int,
                x_passes: int, gram_passes: int, inner_steps: int,
                itemsize: int) -> tuple:
    """The round programs and the column moments: per executed (padded)
    lane-pass the margins 2 x rows x cols, the residual's moments the same
    again, and `inner_steps` products of the lane's [cols] iterate with the
    [cols, cols] curvature matrix; every pass over X other than the Gram
    pass reads [rows, cols] once for all its lanes. (flops, bytes)"""
    flops = (4.0 * rows * cols + 2.0 * cols * cols * inner_steps) \
        * padded_lane_passes
    return flops, float(x_passes - gram_passes) * rows * cols * itemsize


def wide_gram(rows: int, cols: int, gram_passes: int, itemsize: int
              ) -> tuple:
    """The once-a-sweep Gram: the full symmetric weighted Gram 2 x rows x
    cols^2 a pass, one read of X and one write of the [cols, cols] float32
    result. (flops, bytes)"""
    return 2.0 * rows * cols * cols * gram_passes, \
        float(gram_passes) * (rows * cols * itemsize + cols * cols * 4)
