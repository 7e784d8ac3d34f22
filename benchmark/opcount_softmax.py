"""Operations and bytes of the streamed multinomial-LR sweep, from shapes:
the numerator of `mlr_rounds_roofline`. Kept with the benchmark so that no
PR that claims a gain can change it (readers/roofline.py reaches only
benchmark/opcount.py, so this work model and its reader are files of their
own).

It counts what the algorithm must do on the MXU and from HBM, not how the
program does it: a coefficient contraction split into two bf16 passes is
one contraction here. The softmax's exponentials, maxima and sums (rows x
classes x lanes a pass) run on the VPU/EUP and are in NEITHER roof, so a
round that they bound reads low against both.
"""
from __future__ import annotations


def mlr_sweep(rows: int, cols: int, classes: int, padded_lane_passes: int,
              gram_passes: int, data_passes: int, itemsize: int) -> tuple:
    """Per executed (padded) lane-pass: logits 2 x rows x cols x classes
    and the gradient the same again. Per Gram lane-pass (one per fold, once
    a sweep): the full symmetric weighted Gram, 2 x rows x cols^2. Every
    data pass reads X [rows, cols] once for all its lanes. (flops, bytes)"""
    flops = 4.0 * rows * cols * classes * padded_lane_passes \
        + 2.0 * rows * cols * cols * gram_passes
    return flops, float(data_passes) * rows * cols * itemsize
