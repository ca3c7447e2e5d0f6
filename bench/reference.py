"""The plain reference: the same questions answered with NumPy over the
same live keys, and the comparison that decides ``correct``.

`Oracle` follows the one the bring-up check uses (live keys = base
minus deletes plus inserts, as sorted NumPy arrays); it imports nothing
of the program.  The service's two promises, as the configuration
files state them:

* ``get`` returns the exact f64 lower-bound rank and presence;
* ``scan`` returns the live rows whose float32 image (the affine frame
  over the first and last base key) lies in ``[f32(lo), f32(hi))``,
  with their values.

The controls answer the same questions one precision lower (float32
ranks for ``get``, a bfloat16 frame for ``scan``); they must fail the
comparison, which shows that it can fail.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

BFLOAT16 = ml_dtypes.bfloat16


class Oracle:
    """Live keys = base minus deletes plus inserts.  Deletes are base
    keys other than the two ends (so the normalization frame stays
    put); inserts are fresh keys strictly inside it."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        self.base, self.base_vals = keys, vals
        self.lo, self.hi = float(keys[0]), float(keys[-1])
        self._live = (keys, vals)
        self._images = {}

    def norm(self, x, dtype=np.float32) -> np.ndarray:
        """Image in the service's frame, rounded to ``dtype`` and held
        as float32 (every bfloat16 is one)."""
        x = np.asarray(x, np.float64)
        return ((x - self.lo) / (self.hi - self.lo)).astype(dtype).astype(
            np.float32)

    def apply(self, ins, ins_vals, dels) -> None:
        k, v = self._live
        keep = ~_has(np.sort(np.asarray(dels, np.float64)), k)
        k = np.concatenate([k[keep], ins])
        v = np.concatenate([v[keep], np.asarray(ins_vals, v.dtype)])
        order = np.argsort(k, kind="stable")
        self._live = (k[order], v[order])
        self._images = {}

    def image(self, dtype=np.float32) -> np.ndarray:
        key = np.dtype(dtype).name
        if key not in self._images:
            self._images[key] = self.norm(self._live[0], dtype)
        return self._images[key]

    def rank(self, q) -> np.ndarray:
        return np.searchsorted(self._live[0], q)

    def member(self, q) -> np.ndarray:
        return _has(self._live[0], q)

    def frame_rows(self, lo: float, hi: float, dtype=np.float32):
        """Live rows whose ``dtype`` image lies in [img(lo), img(hi)):
        their float32 images and values.  Images are monotone in the
        key, so the rows are one slice of the live order."""
        img = self.image(dtype)
        a, b = np.searchsorted(img, self.norm([lo, hi], dtype))
        if b <= a:
            return np.empty(0, np.float32), self._live[1][:0]
        return self.image()[a:b], self._live[1][a:b]

    def get_control(self, q):
        """``get`` answered in float32: rank and presence among the
        live keys' float32 images."""
        qn = self.norm(q)
        return np.searchsorted(self.image(), qn), _has(self.image(), qn)

    def scan_control(self, lo: float, hi: float):
        """``scan`` answered in a bfloat16 frame."""
        return self.frame_rows(lo, hi, BFLOAT16)


def _has(sorted_arr: np.ndarray, q) -> np.ndarray:
    if not sorted_arr.size:
        return np.zeros(np.shape(q), bool)
    i = np.searchsorted(sorted_arr, q)
    return (i < sorted_arr.size) & (
        sorted_arr[np.minimum(i, sorted_arr.size - 1)] == q)


def gets_wrong(oracle: Oracle, q, rank, found) -> int:
    """How many ``get`` answers say the wrong thing."""
    return int(np.sum((np.asarray(rank) != oracle.rank(q))
                      | (np.asarray(found) != oracle.member(q))))


def scan_wrong(oracle: Oracle, lo: float, hi: float, keys32, vals) -> bool:
    """Whether a scan's rows differ from the reference's as a set of
    (float32 image, value) pairs: rows tied in float32 may come back in
    either order."""
    want_k, want_v = oracle.frame_rows(lo, hi)
    keys32, vals = np.asarray(keys32, np.float32), np.asarray(vals)
    if want_k.size != keys32.size:
        return True
    a = np.lexsort((want_v, want_k))
    b = np.lexsort((vals, keys32))
    return not (np.array_equal(want_k[a], keys32[b])
                and np.array_equal(want_v[a], vals[b]))
