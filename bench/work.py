"""Bytes each operation has to move, from shapes and from the index's
own leaf error bounds: one function per operation, not per strategy,
so they count the same work whatever implements it.  A roofline share
is the least time these bytes take at the chip's HBM bandwidth over
the device time measured for them."""

from __future__ import annotations

import numpy as np

QUERY_AND_RANK_B = 8      # f32 query in, i32 rank out
LEAF_RECORD_B = 16        # slope, intercept, two error bounds (f32)
KEY_B = 4                 # one f32 key of the searched window
ROW_B = 8                 # f32 key + i32 value
PAGE_SLOT_B = 9           # f32 key + i32 value + bool mask per page slot
BOUNDS_B = 8              # two f32 scan bounds


def leaf_of_positions(pos: np.ndarray, seg_lo: np.ndarray,
                      seg_hi: np.ndarray) -> np.ndarray:
    """The leaf whose segment of stored positions holds each position.
    Empty leaves carry the segment (0, 0) and are skipped."""
    held = np.flatnonzero(seg_hi > 0)
    if held.size == 0:
        return np.zeros(np.shape(pos), np.int64)
    order = held[np.argsort(seg_lo[held], kind="stable")]
    j = np.searchsorted(seg_lo[order], pos, side="right") - 1
    return order[np.clip(j, 0, order.size - 1)]


def lookup_bytes(q: np.ndarray, raw_keys: np.ndarray, err_lo: np.ndarray,
                 err_hi: np.ndarray, seg_lo: np.ndarray,
                 seg_hi: np.ndarray) -> int:
    """Bytes of a batch of point lookups: per query its key and rank,
    one leaf record, and every key of the window its leaf's error
    bounds admit (err_hi - err_lo + 1 positions, at most n)."""
    q = np.asarray(q, np.float64)
    pos = np.searchsorted(raw_keys, q)
    leaf = leaf_of_positions(pos, seg_lo, seg_hi)
    window = np.minimum(
        np.asarray(err_hi[leaf], np.int64) - np.asarray(err_lo[leaf], np.int64)
        + 1, raw_keys.size)
    return int(q.size * (QUERY_AND_RANK_B + LEAF_RECORD_B)
               + KEY_B * np.sum(window))


def scan_bytes(rows: int, pages: int, page_size: int) -> int:
    """Bytes of one fused scan: its two bounds, the rows it returns
    (key and value), and every slot of the pages it writes."""
    return BOUNDS_B + ROW_B * int(rows) + PAGE_SLOT_B * int(pages) * int(
        page_size)
