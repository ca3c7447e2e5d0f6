"""From a profiler trace to device figures.

`read_xplane` turns the ``.xplane.pb`` that `jax.profiler` writes into
plain event lists; `reduce_trace` turns those into the device busy time
(the union of the intervals in which a device program ran, averaged
over the chips), the idle share, the operations that took most time and
the longest idle gaps, each named by the host annotation (``bench.*``)
that was open across it.

A TPU plane has a line of programs (``XLA Modules``) and a line of the
operations inside them (``XLA Ops``).  Busy time is read from the
programs: a program that waits on its own asynchronous copies is busy
for the device, though no operation of the ops line runs meanwhile.
Tests feed `reduce_trace` a small recorded event list, so every PR
computes these numbers in the same way.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

import numpy as np

Event = Tuple[str, float, float]   # (name, start_ns, duration_ns)

PROGRAMS_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."


def short_name(hlo: str) -> str:
    """``%while.5`` from ``%while.5 = (s32[], ...) while(...)``."""
    return hlo.split(" = ", 1)[0]


def read_xplane(log_dir: str) -> Dict[str, object]:
    """``{"devices": {plane: [Event]}, "ops": {plane: [Event]},
    "host": [Event]}`` from the one trace under ``log_dir``: programs and
    operations per TPU plane, and the harness's host annotations."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices: Dict[str, List[Event]] = {}
    ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                into = {PROGRAMS_LINE: devices, OPS_LINE: ops}.get(line.name)
                if into is not None:
                    into[plane.name] = [
                        (short_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, float(e.start_ns), float(e.duration_ns))
                         for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
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


def reduce_trace(events: Dict[str, object],
                 top: int = 10) -> Dict[str, object]:
    """Busy seconds per chip (averaged), the top operations by total
    time and the longest idle gaps by what the host was doing.  Empty
    when no device program ran."""
    devices = {k: v for k, v in events["devices"].items() if v}
    if not devices:
        return {}
    busy, per_op, gaps = [], {}, []
    for evs in devices.values():
        merged = union(np.array([(s, s + d) for _, s, d in evs]))
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])) * 1e-9)
        gaps += list(zip(merged[:-1, 1], merged[1:, 0]))
    for evs in events.get("ops", {}).values():
        for name, _, d in evs:
            per_op[name] = per_op.get(name, 0.0) + d * 1e-9
    gaps.sort(key=lambda g: g[0] - g[1])
    host = events["host"]
    h_start = np.array([e[1] for e in host])
    h_end = np.array([e[1] + e[2] for e in host])
    labelled = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        open_ = np.flatnonzero((h_start <= mid) & (h_end >= mid))
        # the innermost annotation open across the gap's middle
        label = (host[open_[np.argmax(h_start[open_])]][0] if open_.size
                 else "no service call")
        labelled.append([label, float(b - a) * 1e-9])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": float(np.mean(busy)),
        "chips": len(devices),
        "device_ops": [[k, v] for k, v in ops[:top]],
        "idle_gaps": labelled,
    }
