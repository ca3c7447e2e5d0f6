"""The open loop: requests are sent when they are due, whatever the
service is doing, and each is timed from when it was due until its
answer is in the client's hands.

Three threads share the process (the chip belongs to one process):
this module's generator (the caller's thread), the frontend's own
dispatcher, and a collector that waits for answers in send order and
brings scan rows to the host.  What each request kind sends and keeps
comes from its module (``ops``, `bench.traffic`), looked up once per
kind before the window.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from bench.traffic import Plan

TENANT = "client"
GRACE_S = 60.0   # how long past the close an answer is still waited for


class TimedService:
    """Sits between `IndexFrontend` and the service: times each ``get``,
    ``scan_batch`` and ``insert`` call on the host clock and wraps it in
    a profiler annotation (``bench.service.<op>``).  It adds no sync: a
    call's time is what the service spends before it returns."""

    OPS = ("get", "scan_batch", "insert")

    def __init__(self, service):
        self._service = service
        self.calls = dict.fromkeys(self.OPS, 0)
        self.seconds = dict.fromkeys(self.OPS, 0.0)
        self.log: Optional[list] = None  # (op, args, out) while tracing

    def _timed(self, op: str, fn: Callable, *args):
        with jax.profiler.TraceAnnotation(f"bench.service.{op}"):
            t = time.perf_counter()
            out = fn(*args)
            dt = time.perf_counter() - t
        self.calls[op] += 1
        self.seconds[op] += dt
        log = self.log
        if log is not None:
            log.append((op, args, out))
        return out

    def get(self, keys):
        return self._timed("get", self._service.get, keys)

    def scan_batch(self, lo, hi, page_size=256):
        return self._timed("scan_batch", self._service.scan_batch, lo, hi,
                           page_size)

    def insert(self, keys, vals=None):
        return self._timed("insert", self._service.insert, keys, vals)

    def totals(self) -> dict:
        return {op: (self.calls[op], self.seconds[op]) for op in self.OPS}

    def __getattr__(self, name):
        return getattr(self._service, name)


class CompileCounter:
    """Counts the executables JAX builds or loads (backend compiles,
    persistent-cache hits included) through `jax.monitoring`."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclasses.dataclass
class Window:
    """What one window did, per request in plan order (times in seconds
    after the window opened; nan where it never happened)."""

    plan: Plan
    seconds: float
    t0: float                    # host clock when the window opened
    sent: np.ndarray             # just before it was handed over
    done: np.ndarray             # its answer in hand
    refused: np.ndarray          # the frontend would not take it
    error: np.ndarray            # answered with an error
    answers: List[object]        # as its kind's module keeps them
    # of the errors, the frontend's `DeadlineExceeded` (its queue
    # deadline passed): a failure, and no wrong answer
    late: np.ndarray

    def answered_ok(self) -> np.ndarray:
        return ~self.refused & ~self.error & ~np.isnan(self.done)

    def latency(self) -> np.ndarray:
        """Due to answer in hand; a refused, failed or missing answer
        counts as later than any answer that came (the whole grace)."""
        lat = self.done - self.plan.due
        lat[~self.answered_ok()] = self.seconds + GRACE_S
        return lat


def send(fe, plan: Plan, ops: Dict[str, object], idx,
         page_size: int) -> list:
    """Submit requests ``idx`` of ``plan`` at once (warm-up)."""
    out = []
    for i in idx:
        kind = plan.kinds[plan.kind[i]]
        out.append(fe.submit(TENANT, kind,
                             *ops[kind].args(plan, i, page_size)))
    return out


def drive(fe, plan: Plan, seconds: float, page_size: int,
          ops: Dict[str, object],
          marks: Tuple[Tuple[float, Callable[[], None]], ...] = ()) -> Window:
    """Run one window of ``plan`` against the started frontend ``fe``.
    ``marks`` are (seconds after the open, action) pairs run on a helper
    thread, for starting and stopping a trace."""
    from repro.serve.frontend import DeadlineExceeded

    n = plan.size
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    refused = np.zeros(n, bool)
    error = np.zeros(n, bool)
    late = np.zeros(n, bool)
    answers: List[object] = [None] * n
    inbox: "queue.SimpleQueue" = queue.SimpleQueue()
    names = plan.kinds
    args_of = [ops[k].args if k in ops else None for k in names]
    answer_of = {k: ops[k].answer for k in names if k in ops}
    t0 = time.perf_counter()
    close = t0 + seconds

    def collect():
        while True:
            item = inbox.get()
            if item is None:
                return
            i, req = item
            if not req.event.wait(max(0.0, close + GRACE_S
                                      - time.perf_counter())):
                continue
            if req.error is not None:
                error[i] = True
                late[i] = isinstance(req.error, DeadlineExceeded)
                done[i] = time.perf_counter() - t0
                continue
            answers[i] = answer_of[req.kind](req.result)
            done[i] = time.perf_counter() - t0

    def run_marks():
        for at, action in marks:
            time.sleep(max(0.0, t0 + at - time.perf_counter()))
            action()

    collector = threading.Thread(target=collect, name="bench-collector")
    collector.start()
    marker = threading.Thread(target=run_marks, name="bench-marks")
    marker.start()
    due = plan.due
    i = 0
    while i < n:
        now = time.perf_counter() - t0
        if due[i] > now:
            time.sleep(min(due[i] - now, 0.002))
            continue
        while i < n and due[i] <= now:
            k = plan.kind[i]
            args = args_of[k](plan, i, page_size)
            sent[i] = time.perf_counter() - t0
            try:
                req = fe.submit(TENANT, names[k], *args, timeout=0.0)
            except RuntimeError:  # Backpressure or the ladder's refusals
                refused[i] = True
            else:
                inbox.put((i, req))
            i += 1
    inbox.put(None)
    collector.join()
    marker.join()
    return Window(plan=plan, seconds=seconds, t0=t0, sent=sent, done=done,
                  refused=refused, error=error, answers=answers, late=late)
