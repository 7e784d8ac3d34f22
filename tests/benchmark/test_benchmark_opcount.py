"""benchmark/opcount.py against hand-worked shapes."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import opcount  # noqa: E402

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_hist_pass_by_hand():
    # 1000 rows, 4 features, 2 lanes x 3 channels, 8 slots, 16 bins, bf16
    flops, byts = opcount.hist_pass(1000, 4, 2, 3, 8, 16, 2)
    assert flops == 2 * 1000 * 6 * 64            # [6, N] x [N, 64]
    read = 1000 * 4 + 6 * 1000 * 2 + 2 * 1000 * 4
    write = 2 * 8 * 3 * 4 * 16 * 4
    assert byts == read + write == 24_000 + 12_288


def test_tree_hist_is_the_sum_of_its_passes():
    rows, F, folds, depth, bins = 1000, 4, 2, 3, 9
    flops = byts = 0.0
    for level in range(depth):
        f, b = opcount.hist_pass(rows, F, folds, 2, 1 << level, bins, 2)
        flops += f
        byts += b + (folds * rows * 4 if level else 0)
    got = opcount.tree_hist(rows, F, folds, configs=2, rounds=5,
                            depth=depth, bins=bins)
    assert got == (flops * 10, byts * 10)
    # at the flagship shape one job is bound by the one-hot contraction
    f, b = opcount.tree_hist(10_000_000, 64, 5, 2, 10, 6, 33)
    assert opcount.least_seconds(f, b, PEAKS)[1] == "flops"
    assert f == pytest.approx(2 * 1e7 * 10 * 64 * 33 * 6 * 20)


def test_tree_sweep_sums_the_tree_points_and_skips_the_others():
    one = opcount.tree_hist(1000, 4, 2, 1, 5, 3, 8 + 1)
    two = opcount.tree_hist(1000, 4, 2, 1, 7, 2, 16 + 1)
    grids = [{"num_round": 5, "max_depth": 3, "max_bins": 8, "eta": 0.1},
             {"reg_param": 0.1},
             {"num_round": 7, "max_depth": 2, "max_bins": 16}]
    assert opcount.tree_sweep(1000, 4, 2, grids) == \
        (one[0] + two[0], one[1] + two[1])
    assert opcount.tree_sweep(1000, 4, 2, [{"reg_param": 0.1}]) == (0.0, 0.0)


def test_glm_sweep_by_hand():
    flops, byts = opcount.glm_sweep(1000, 8, padded_lane_passes=30,
                                    data_passes=4, itemsize=2)
    assert flops == (4 * 1000 * 8 + 2 * 1000 * 64) * 30
    assert byts == 4 * 1000 * 8 * 2


def test_least_seconds_names_the_roof():
    assert opcount.least_seconds(197e12, 1.0, PEAKS) == (1.0, "flops")
    t, roof = opcount.least_seconds(1.0, 819e9 * 2, PEAKS)
    assert roof == "bytes" and t == pytest.approx(2.0)
