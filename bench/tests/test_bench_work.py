"""Byte models and pair counts against hand counts."""
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import peaks, work  # noqa: E402


def test_bytes_pair_window_ids_by_hand():
    # s=341, d=2: points 341*2*4=2728, ids 1364, count 4, box 16,
    # id row out 1364, count out 4
    assert work.bytes_pair_window_ids(1, 341, 2) == 2728 + 1364 + 4 + 16 + 1364 + 4
    assert work.bytes_pair_window_ids(10, 341, 2) == 10 * 5480


def test_window_pairs_counts_intersecting_boxes():
    lo = np.array([[0.0, 0.0], [0.5, 0.5], [0.9, 0.9]])
    hi = np.array([[0.1, 0.1], [0.6, 0.6], [1.0, 1.0]])
    los = np.array([[0.05, 0.05], [0.0, 0.0], [0.7, 0.7]])
    his = np.array([[0.55, 0.55], [1.0, 1.0], [0.8, 0.8]])
    # window 0 touches leaves 0 and 1, window 1 all three, window 2 none
    assert work.window_pairs(lo, hi, los, his, block=2) == 5
    assert work.window_pairs_each(lo, hi, los, his, block=2).tolist() == [
        2, 3, 0]


def test_peaks_are_keyed_by_device_kind():
    assert peaks.chip_peaks("TPU v5 lite")["hbm_bw"] == 819e9
    try:
        peaks.chip_peaks("cpu")
    except ValueError:
        pass
    else:
        raise AssertionError("an unknown device has no peaks")


def test_bytes_pair_dist2_by_hand():
    # s=341, d=2: points 341*2*4=2728, count 4, query 8, distance row 1364
    assert work.bytes_pair_dist2(1, 341, 2) == 2728 + 4 + 8 + 1364
    assert work.bytes_pair_dist2(10, 341, 2) == 10 * 4104
    # d=5, s=170: points 3400, count 4, query 20, row 680
    assert work.bytes_pair_dist2(3, 170, 5) == 3 * 4104


def test_knn_leaves_counts_boxes_within_the_kth_distance():
    lo = np.array([[0.0, 0.0], [0.5, 0.5], [0.875, 0.875]])
    hi = np.array([[0.125, 0.125], [0.625, 0.625], [1.0, 1.0]])
    qs = np.array([[0.25, 0.0625], [0.5625, 0.5625], [0.75, 0.75],
                   [0.25, 0.25]])
    kth = np.array([0.015625, 0.0, 0.03125, 0.015625])
    # query 0 reaches leaf 0 at exactly its k-th distance (0.125 in x);
    # query 1 sits inside leaf 1 with a k-th distance of 0; query 2 lies
    # 0.125 from leaves 1 and 2 in each dimension (0.03125, its k-th);
    # query 3 lies 0.125 from leaf 0 in each, past its k-th distance
    assert work.knn_leaves_each(lo, hi, qs, kth, block=3).tolist() == [
        1, 1, 2, 0]
