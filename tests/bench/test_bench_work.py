"""The byte counters against a hand count at a tiny size."""

import numpy as np

from bench_tiny import REPO  # noqa: F401  (puts the checkout on sys.path)
from bench import work


def test_lookup_bytes_hand_count():
    raw = np.arange(10, dtype=np.float64)
    # three leaves over positions 0-3, 4-6, 7-9, and one empty leaf
    seg_lo = np.array([0, 4, 0, 7], np.int32)
    seg_hi = np.array([3, 6, 0, 9], np.int32)
    err_lo = np.array([-1, -2, 0, 0], np.float32)
    err_hi = np.array([1, 0, 0, 3], np.float32)
    q = np.array([0.0, 5.0, 9.0, 2.0])
    # windows: 3 (leaf 0), 3 (leaf 1), 4 (leaf 3), 3 (leaf 0)
    want = 4 * (8 + 16) + 4 * (3 + 3 + 4 + 3)
    assert work.lookup_bytes(q, raw, err_lo, err_hi, seg_lo, seg_hi) == want


def test_leaf_of_positions_skips_empty_leaves():
    seg_lo = np.array([0, 0, 5], np.int32)
    seg_hi = np.array([4, 0, 9], np.int32)
    assert list(work.leaf_of_positions(np.array([0, 4, 5, 9]), seg_lo,
                                       seg_hi)) == [0, 0, 2, 2]


def test_window_never_exceeds_the_key_count():
    raw = np.arange(4, dtype=np.float64)
    ones = np.ones(1, np.int32)
    got = work.lookup_bytes(np.array([1.0]), raw, -100 * ones, 100 * ones,
                            0 * ones, 3 * ones)
    assert got == 24 + 4 * 4


def test_scan_bytes_hand_count():
    # 2 bounds, 37 rows of key+value, 2 pages of 256 slots of 9 B
    assert work.scan_bytes(37, 2, 256) == 8 + 37 * 8 + 2 * 256 * 9
