"""Arithmetic the metric readers in ``bench/metrics/`` share."""

from __future__ import annotations

import math
from typing import Optional

import numpy as np


def tail_ms(lat: Optional[np.ndarray], pct: float) -> Optional[float]:
    """The ``pct`` percentile by nearest rank, in ms: a time some
    request really took."""
    if lat is None or not lat.size:
        return None
    s = np.sort(lat)
    return float(s[max(0, math.ceil(pct / 100 * s.size) - 1)] * 1e3)


def device_seconds_per_call(rec: dict, op: str) -> Optional[float]:
    """Device busy seconds in the trace per ``op`` call; None without a
    trace, without such calls, or where other calls shared the trace
    (the busy time could not be split between them)."""
    tr = rec.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    calls = tr["calls"]
    if not calls.get(op) or any(n for k, n in calls.items() if k != op):
        return None
    return tr["busy_s"] / calls[op]


def roofline_pct(rec: dict, op: str) -> Optional[float]:
    """100 x (counted bytes / HBM bandwidth) / device busy seconds."""
    per_call = device_seconds_per_call(rec, op)
    moved = rec["trace"]["bytes"].get(op) if per_call else None
    if not moved:
        return None
    least = moved / float(rec["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / rec["trace"]["busy_s"]


def span_ms(rec: dict, path: str, own: bool = False) -> Optional[float]:
    """Mean ms per span ``path`` (a span name or ``<parent>/<name>``) in
    the traced window, its self time with ``own``; None where the trace
    holds no such span."""
    tr = rec.get("trace")
    row = (tr or {}).get("spans", {}).get(path)
    if not row or not row["count"]:
        return None
    return 1e3 * row["self_s" if own else "total_s"] / row["count"]


def counter_ratio(rec: dict, num: str, den: str,
                  scale: float = 1.0) -> Optional[float]:
    """``scale`` x the window's change of counter ``num`` over that of
    ``den``; None where ``den`` did not move."""
    c = rec.get("counters", {})
    if not c.get(den):
        return None
    return scale * c.get(num, 0) / c[den]


def idle_pct(rec: dict, key: str) -> Optional[float]:
    """100 x the trace's idle seconds ``key`` over the traced window."""
    tr = rec.get("trace")
    if not tr or not tr.get("busy_s") or not tr.get("window_s"):
        return None
    return 100.0 * tr[key] / tr["window_s"]
