"""The plain reference: the same questions answered with NumPy over the
same live keys, and the comparison that decides ``correct``.

`Oracle` holds the stored keys as sorted NumPy arrays and imports
nothing of the program.  The service's two promises, as the
configuration files state them:

* ``get`` returns the exact f64 lower-bound rank and presence;
* ``scan`` returns the live rows whose float32 image (the affine frame
  over the first and last base key) lies in ``[f32(lo), f32(hi))``,
  with their values.

Under writes (a `WriteLog` of the window's inserts) each read is judged
between two key sets: ``must``, the keys stored when the window opened
and the inserts acknowledged before the read was sent, and ``may``,
those and every insert sent before its answer came.  A ``get`` finds a
key of ``must``, misses one outside ``may`` and ranks it between its
lower bounds in the two; a ``scan`` returns every row of ``must`` in its
range and only rows of ``may``, each row's image taken in the frame of
a snapshot that was current between send and answer.  The frame moves
when a compaction commits; the service's swaps say when, and the frame
each says it installed counts only where it is the frame over the first
and last key of a key set the service may hold then (`Oracle`).  With
no writes both sets are the stored keys and the comparison is the exact
one.

The controls answer the same questions one precision lower (float32
ranks for ``get``, a bfloat16 frame for ``scan``); they must fail the
comparison that decides ``correct`` (`gets_wrong_between`,
`scans_wrong_between`), which shows that it can fail.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np

BFLOAT16 = ml_dtypes.bfloat16

# (installed no earlier than, installed no later than, frame lo, frame hi)
Frame = Tuple[float, float, float, float]


@dataclasses.dataclass
class WriteLog:
    """The window's inserts that entered the service, in send order
    (times in seconds after the window opened): ``sent`` just before the
    request was handed over, ``done`` once its answer was in hand (nan
    if it never came), ``acked`` where that answer was no error."""

    keys: np.ndarray
    vals: np.ndarray
    sent: np.ndarray
    done: np.ndarray
    acked: np.ndarray

    @property
    def size(self) -> int:
        return int(self.keys.size)


class Oracle:
    """Keys stored when the window opened (``keys``, sorted, with their
    ``vals``), the window's inserts (``log``) and the frames the service
    says it served in (``frames``; by default none but the one over the
    first and last built key).  ``built`` is the first and last key the
    service was built over; by default those of ``keys``."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray,
                 log: Optional[WriteLog] = None,
                 frames: Optional[Sequence[Frame]] = None,
                 built: Optional[Tuple[float, float]] = None):
        self.base, self.base_vals = keys, vals
        self.lo, self.hi = float(keys[0]), float(keys[-1])
        self.built = ((self.lo, self.hi) if built is None
                      else (float(built[0]), float(built[1])))
        self._images = {}
        self.log = log if log is not None and log.size else None
        self.frames = np.array(
            [self._admit(f)
             for f in frames or [(-np.inf, -np.inf, *self.built)]],
            np.float64)

    def _admit(self, frame: Frame) -> Frame:
        """A frame the service says it installed no later than ``post``,
        kept where it is the frame over the first and last key of a set
        it may hold then: the built keys (no mix deletes one), perhaps
        with keys stored at the open or sent before ``post``.  Else the
        built keys' frame, in which a service that serves scans in a
        frame of its own making fails."""
        pre, post, lo, hi = frame
        b_lo, b_hi = self.built
        log = self.log

        def sent(k: float) -> bool:
            return bool(_has(self.base, k)) or (log is not None and bool(
                np.any((log.keys == k) & (log.sent < post))))
        if ((lo == b_lo or (lo < b_lo and sent(lo)))
                and (hi == b_hi or (hi > b_hi and sent(hi)))):
            return pre, post, lo, hi
        return pre, post, b_lo, b_hi

    def norm(self, x, dtype=np.float32) -> np.ndarray:
        """Image in the frame over the first and last stored key,
        rounded to ``dtype`` and held as float32 (every bfloat16 is
        one)."""
        return _image(x, self.lo, self.hi, dtype).astype(np.float32)

    def image(self, dtype=np.float32) -> np.ndarray:
        """`norm` of every stored key, kept."""
        key = np.dtype(dtype).name
        if key not in self._images:
            self._images[key] = self.norm(self.base, dtype)
        return self._images[key]

    def frame_rows(self, lo: float, hi: float, dtype=np.float32):
        """Stored rows whose ``dtype`` image lies in [img(lo), img(hi)):
        their float32 images and values.  Images are monotone in the
        key, so the rows are one slice of the stored order."""
        img = self.image(dtype)
        a, b = np.searchsorted(img, self.norm([lo, hi], dtype))
        if b <= a:
            return np.empty(0, np.float32), self.base_vals[:0]
        return self.image()[a:b], self.base_vals[a:b]

    def get_control(self, q):
        """``get`` answered in float32: rank and presence among the
        stored keys' float32 images."""
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


def _image(x, frame_lo: float, frame_hi: float,
           dtype=np.float32) -> np.ndarray:
    """``dtype`` image of ``x`` in the frame [frame_lo, frame_hi]."""
    return ((np.asarray(x, np.float64) - frame_lo)
            / (frame_hi - frame_lo)).astype(dtype)


def _prefix_below(keys: np.ndarray, order: np.ndarray, cnt: np.ndarray,
                  q: np.ndarray) -> np.ndarray:
    """``#{keys[order[:cnt[i]]] < q[i]}`` for each i: the queries grouped
    by their prefix, the prefix grown one key at a time."""
    out = np.zeros(q.size, np.int64)
    if not order.size or not q.size:
        return out
    by = np.argsort(cnt, kind="stable")
    starts = np.searchsorted(cnt[by], np.arange(order.size + 2))
    grown = np.empty(0, keys.dtype)
    for c in range(order.size + 1):
        sel = by[starts[c]:starts[c + 1]]
        if sel.size:
            out[sel] = np.searchsorted(grown, q[sel])
        if c < order.size:
            k = keys[order[c]]
            grown = np.insert(grown, np.searchsorted(grown, k), k)
    return out


def _in_prefix(keys: np.ndarray, order: np.ndarray, cnt: np.ndarray,
               q: np.ndarray) -> np.ndarray:
    """Whether ``q[i]`` is one of ``keys[order[:cnt[i]]]`` (keys are
    unique)."""
    if not order.size:
        return np.zeros(q.shape, bool)
    place = np.full(keys.size, np.iinfo(np.int64).max)
    place[order] = np.arange(order.size)
    by_key = np.argsort(keys, kind="stable")
    j = np.minimum(np.searchsorted(keys[by_key], q), keys.size - 1)
    hit = keys[by_key[j]] == q
    return hit & (place[by_key[j]] < cnt)


def _must_may(log: WriteLog, sent: np.ndarray, done: np.ndarray):
    """Per read: the insert orders and how many of each belong to
    ``must`` (acknowledged before the read was sent, by answer time)
    and to ``may`` (sent before its answer came, by send time)."""
    acked = np.flatnonzero(log.acked)
    order_a = acked[np.argsort(log.done[acked], kind="stable")]
    cnt_a = np.searchsorted(log.done[order_a], sent)
    order_b = np.arange(log.size)
    cnt_b = np.searchsorted(log.sent, done)
    return order_a, cnt_a, order_b, cnt_b


def _bracket(oracle: Oracle, q, order_a=None, cnt_a=None, order_b=None,
             cnt_b=None):
    """Lower-bound ranks in ``must`` and ``may``, and membership."""
    q = np.asarray(q)
    lb = np.searchsorted(oracle.base, q)
    in_base = _has(oracle.base, q)
    log = oracle.log
    if log is None:
        return lb, lb, in_base, in_base
    return (lb + _prefix_below(log.keys, order_a, cnt_a, q),
            lb + _prefix_below(log.keys, order_b, cnt_b, q),
            in_base | _in_prefix(log.keys, order_a, cnt_a, q),
            in_base | _in_prefix(log.keys, order_b, cnt_b, q))


def gets_wrong_between(oracle: Oracle, q, rank, found, sent,
                       done) -> int:
    """How many ``get`` answers say what neither ``must`` nor ``may``
    allows: a rank outside their lower bounds, a key of ``must`` missed,
    a key outside ``may`` found.  With no log: the exact rank and
    presence among the stored keys."""
    log = oracle.log
    lo, hi, must, may = _bracket(
        oracle, q, *(() if log is None else _must_may(log, sent, done)))
    rank, found = np.asarray(rank), np.asarray(found)
    return int(np.sum((rank < lo) | (rank > hi) | (must & ~found)
                      | (~may & found)))


def lost_after(oracle: Oracle, keys, rank, found) -> int:
    """How many acknowledged inserts a ``get`` after the window misses
    or ranks outside the final ``must`` / ``may`` (every acknowledged
    insert / every insert sent)."""
    log = oracle.log
    acked = np.flatnonzero(log.acked)
    n = np.size(keys)
    lo, hi, _, _ = _bracket(oracle, keys, acked, np.full(n, acked.size),
                            np.arange(log.size), np.full(n, log.size))
    return int(np.sum(~np.asarray(found) | (rank < lo) | (rank > hi)))


def _base_slices(base: np.ndarray, lo_img: np.ndarray, hi_img: np.ndarray,
                 frame_lo: float, frame_hi: float):
    """For each scan, the slice ``[a, b)`` of ``base`` whose images lie
    in ``[lo_img, hi_img)``: two lower bounds over the (monotone) images,
    by bisection, so no image of the whole key set is held."""
    def lower_bound(target):
        a = np.zeros(target.size, np.int64)
        b = np.full(target.size, base.size, np.int64)
        while np.any(a < b):
            open_ = a < b
            mid = (a + b) // 2
            below = np.zeros(target.size, bool)
            below[open_] = (_image(base[mid[open_]], frame_lo, frame_hi)
                            < target[open_])
            a = np.where(open_ & below, mid + 1, a)
            b = np.where(open_ & ~below, mid, b)
        return a
    return lower_bound(lo_img), lower_bound(hi_img)


def _scan_ok(oracle, a, b, t_lo, t_hi, frame, may_j, must_j, keys32,
             vals) -> bool:
    """One scan's rows against one frame's ``must`` and ``may`` rows."""
    log = oracle.log
    want_v = oracle.base_vals[a:b]
    want_k = _image(oracle.base[a:b], *frame)
    may_v, may_k = want_v, want_k
    if log is not None:
        img = _image(log.keys, *frame)
        inr = (img >= t_lo) & (img < t_hi)
        must_v = np.concatenate([want_v, log.vals[inr & must_j]])
        may_v = np.concatenate([want_v, log.vals[inr & may_j]])
        may_k = np.concatenate([want_k, img[inr & may_j]])
    else:
        must_v = want_v
    vals = np.asarray(vals)
    if np.unique(vals).size != vals.size:
        return False
    if not (np.isin(must_v, vals).all() and np.isin(vals, may_v).all()):
        return False
    by = np.argsort(may_v, kind="stable")
    at = by[np.searchsorted(may_v[by], vals)]
    return bool(np.array_equal(may_k[at], np.asarray(keys32, np.float32)))


def scans_wrong_between(oracle: Oracle, lo, hi, sent, done,
                        answers: List[tuple]) -> int:
    """How many scans return rows that no frame current between their
    send and answer allows: a row of ``must`` in range left out, a row
    outside ``may`` or outside the range, a row twice, or a row whose
    float32 image is not its key's.  With no log and one frame: the
    scan's rows equal the reference's as (image, value) pairs."""
    lo, hi = np.asarray(lo), np.asarray(hi)
    sent, done = np.asarray(sent), np.asarray(done)
    frames = oracle.frames
    cand = np.zeros((lo.size, len(frames)), bool)
    for k, (pre, _, _, _) in enumerate(frames):
        nxt_post = frames[k + 1][1] if k + 1 < len(frames) else np.inf
        cand[:, k] = (pre < done) & (nxt_post > sent)
    slices = {}
    for k in np.flatnonzero(cand.any(axis=0)):
        frame = (frames[k][2], frames[k][3])
        t_lo, t_hi = _image(lo, *frame), _image(hi, *frame)
        slices[k] = (frame, t_lo, t_hi,
                     *_base_slices(oracle.base, t_lo, t_hi, *frame))
    log = oracle.log
    wrong = 0
    for i in range(lo.size):
        may_j = must_j = None
        if log is not None:
            may_j = log.sent < done[i]
            must_j = log.acked & (log.done < sent[i])
        ok = False
        for k in np.flatnonzero(cand[i]):
            frame, t_lo, t_hi, a, b = slices[k]
            if _scan_ok(oracle, a[i], b[i], t_lo[i], t_hi[i], frame, may_j,
                        must_j, *answers[i]):
                ok = True
                break
        wrong += not ok
    return wrong
