"""From a profiler trace to device and span figures.

`read_xplane` turns the ``.xplane.pb`` that `jax.profiler` writes into
plain event lists; `reduce_trace` turns those into the device busy time
(the union of the intervals in which a device program ran, averaged
over the chips), the idle share, the operations that took most time and
the longest idle gaps, each named by the host span that was open across
it, and a table of the host spans.

Host spans are the harness's annotations (``bench.*``) and the
program's own (`repro.obs.trace`): every host event whose name is a
dotted lower-case path such as ``service.get`` or ``scan.pack_slab``,
so a span the program adds later is read without an edit here.  A
``bench.window`` span marks the part of the session the harness
measured: busy time, idle time and spans are clipped to it.  Without
one the whole session counts.

A TPU plane has a line of programs (``XLA Modules``) and a line of the
operations inside them (``XLA Ops``).  Busy time is read from the
programs: a program that waits on its own asynchronous copies is busy
for the device, though no operation of the ops line runs meanwhile.
Tests feed `reduce_trace` small event lists, so every PR computes these
numbers in the same way.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

import numpy as np

Event = Tuple[str, float, float]   # (name, start_ns, duration_ns)
# host events carry the thread (line) they ran on as a fourth field

PROGRAMS_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_NAME = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+")
WINDOW = "bench.window"
WAIT = "frontend.wait"      # the dispatcher waiting for requests


def short_name(hlo: str) -> str:
    """``%while.5`` from ``%while.5 = (s32[], ...) while(...)``."""
    return hlo.split(" = ", 1)[0]


def read_xplane(log_dir: str) -> Dict[str, object]:
    """``{"devices": {plane: [Event]}, "ops": {plane: [Event]},
    "host": [(name, start_ns, duration_ns, thread)]}`` from the one trace
    under ``log_dir``: programs and operations per TPU plane, and the
    host spans (`SPAN_NAME`) of every thread."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices: Dict[str, List[Event]] = {}
    ops: Dict[str, List[Event]] = {}
    host: List[tuple] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                into = {PROGRAMS_LINE: devices, OPS_LINE: ops}.get(line.name)
                if into is not None:
                    into[plane.name] = [
                        (short_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                thread = f"{plane.name}#{k}"
                host += [(e.name, float(e.start_ns), float(e.duration_ns),
                          thread) for e in line.events
                         if SPAN_NAME.fullmatch(e.name)]
    return {"devices": devices, "ops": ops, "host": host}


def union(intervals: np.ndarray) -> np.ndarray:
    """Merge (start, end) rows into disjoint sorted intervals."""
    if not len(intervals):
        return np.empty((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(np.append(new[1:], True))
    return np.stack([starts, ends[last]], axis=1)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Rows of ``intervals`` cut to [lo, hi]; empty rows dropped."""
    iv = np.clip(np.asarray(intervals, float).reshape(-1, 2), lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def complement(merged: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The parts of [lo, hi] that disjoint sorted ``merged`` leaves out."""
    edges = np.concatenate([[lo], merged.ravel(), [hi]])
    return clip(edges.reshape(-1, 2), lo, hi)


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two disjoint sorted interval
    lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i, 1], b[j, 1]) - max(a[i, 0], b[j, 0]))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def _host(events) -> List[tuple]:
    """Host events as (name, start, end, thread)."""
    return [(e[0], e[1], e[1] + e[2], e[3] if len(e) > 3 else "")
            for e in events]


def span_table(host: List[tuple], lo: float,
               hi: float) -> Dict[str, Dict[str, float]]:
    """Per span name and per ``<parent>/<name>`` (the innermost span of
    the same thread around it): the count of spans that overlap [lo, hi],
    and their total and self seconds inside it."""
    table: Dict[str, Dict[str, float]] = {}
    by_thread: Dict[str, list] = {}
    for e in host:
        if e[0] != WINDOW:
            by_thread.setdefault(e[3], []).append(e)
    for evs in by_thread.values():
        evs.sort(key=lambda e: (e[1], -e[2]))
        inside = [max(0.0, min(e[2], hi) - max(e[1], lo)) for e in evs]
        own = list(inside)
        parent = [None] * len(evs)
        stack: List[int] = []
        for i, e in enumerate(evs):
            while stack and evs[stack[-1]][2] < e[2]:
                stack.pop()
            if stack:
                parent[i] = stack[-1]
                own[stack[-1]] -= inside[i]
            stack.append(i)
        for i, e in enumerate(evs):
            if inside[i] <= 0:
                continue
            keys = [e[0]]
            if parent[i] is not None:
                keys.append(f"{evs[parent[i]][0]}/{e[0]}")
            for key in keys:
                row = table.setdefault(key, {"count": 0, "total_s": 0.0,
                                             "self_s": 0.0})
                row["count"] += 1
                row["total_s"] += inside[i] * 1e-9
                row["self_s"] += own[i] * 1e-9
    return table


def reduce_trace(events: Dict[str, object],
                 top: int = 10) -> Dict[str, object]:
    """Busy seconds per chip (averaged), the top operations by total
    time, the longest idle gaps by what the host was doing, the device's
    idle seconds under a span other than `WAIT` (``idle_host_s``) and
    under no span (``idle_unattributed_s``), and the span table
    (``spans``).  Only the span table where no device program ran."""
    host = _host(events.get("host", []))
    marks = [e for e in host if e[0] == WINDOW]
    spans = [e for e in host if e[0] != WINDOW]
    devices = {k: v for k, v in events["devices"].items() if v}
    if marks:
        w0, w1 = marks[0][1], marks[0][2]
    else:
        ends = [(s, s + d) for evs in devices.values() for _, s, d in evs]
        ends += [(e[1], e[2]) for e in spans]
        w0 = min((a for a, _ in ends), default=0.0)
        w1 = max((b for _, b in ends), default=0.0)
    out: Dict[str, object] = {"spans": span_table(spans, w0, w1)}
    if not devices:
        return out
    busy, per_op, gaps = [], {}, []
    idle_host, idle_none = [], []
    under = union(np.array([(e[1], e[2]) for e in spans]).reshape(-1, 2))
    under_work = union(np.array([(e[1], e[2]) for e in spans
                                 if e[0] != WAIT]).reshape(-1, 2))
    for evs in devices.values():
        merged = union(np.array([(s, s + d) for _, s, d in evs]))
        if marks:
            merged = clip(merged, w0, w1)
            idle = complement(merged, w0, w1)
        else:
            idle = np.stack([merged[:-1, 1], merged[1:, 0]], axis=1)
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])) * 1e-9)
        gaps += [tuple(g) for g in idle]
        idle_host.append(overlap(idle, under_work) * 1e-9)
        idle_none.append((float(np.sum(idle[:, 1] - idle[:, 0]))
                          - overlap(idle, under)) * 1e-9)
    for evs in events.get("ops", {}).values():
        for name, s, d in evs:
            if not marks or w0 <= s < w1:
                per_op[name] = per_op.get(name, 0.0) + d * 1e-9
    gaps.sort(key=lambda g: g[0] - g[1])
    h_start = np.array([e[1] for e in spans])
    h_end = np.array([e[2] for e in spans])
    labelled = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        open_ = np.flatnonzero((h_start <= mid) & (h_end >= mid))
        # the innermost span open across the gap's middle
        label = (spans[open_[np.argmax(h_start[open_])]][0] if open_.size
                 else "no span")
        labelled.append([label, float(b - a) * 1e-9])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])
    out.update(
        busy_s=float(np.mean(busy)),
        chips=len(devices),
        device_ops=[[k, v] for k, v in ops[:top]],
        idle_gaps=labelled,
        idle_host_s=float(np.mean(idle_host)),
        idle_unattributed_s=float(np.mean(idle_none)),
    )
    return out
