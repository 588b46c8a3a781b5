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
