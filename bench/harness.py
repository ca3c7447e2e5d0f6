"""One run of one cell: build the deployment from the seed, warm up
every shape the window uses, drive `IndexFrontend` in an open loop for
the window, check every answer against the reference, and reduce what
was recorded to the cell's metrics.

`run_cell` does all of it except the look for a chip, which the entry
point (`bench/run.py`) makes first; tests call it directly on the CPU
at a small size.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
from typing import Dict

import jax
import numpy as np

from bench import catalog, datagen, loop, reference, trace_reduce, work
from bench.metrics_util import tail_ms
from bench.traffic import KINDS, make_plan

WARM_SCANS = 64          # scans sent through the frontend while warming
TRACE_AT = 0.3           # the trace opens this far into the window
TRACE_S = 4.0            # and lasts this long at most (or 40% of it)


def log(msg: str) -> None:
    print(msg, flush=True)


def enable_cache() -> str:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    or a fixed directory in the checkout), keeping every program."""
    from repro.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def build(cell: catalog.Cell, seed: int, split: Dict[str, float]):
    """Keys from the seed, then the service and its frontend."""
    from repro.index_service import IndexService, ServiceConfig
    from repro.serve.frontend import FrontendConfig, IndexFrontend

    cfg = cell.config
    t = time.perf_counter()
    keys = datagen.GENERATORS[cfg["generator"]](
        int(cfg["keys"]), seed, int(cfg["shape_seed"]))
    vals = np.arange(keys.size, dtype=np.int64)
    split["generate"] = time.perf_counter() - t
    t = time.perf_counter()
    svc = IndexService(keys, ServiceConfig(**cfg["service"]), vals=vals)
    split["build"] = time.perf_counter() - t
    proxy = loop.TimedService(svc)
    fe = IndexFrontend(proxy, FrontendConfig(**cfg["frontend"]))
    return keys, vals, svc, proxy, fe


def warm_up(fe, plan, page_size: int, split: Dict[str, float]) -> None:
    """Every round size of the coalesced kinds (each may pad to another
    shape), then a burst of scans; the first call, which uploads the
    index and compiles, is timed apart."""
    def round_of(idx):
        reqs = loop.send(fe, plan, idx, page_size)
        fe.pump()
        for r in reqs:
            loop.answer(r.kind, r.wait(0))

    gets = np.flatnonzero(plan.kind == KINDS.index("get"))
    scans = np.flatnonzero(plan.kind == KINDS.index("scan"))
    t = time.perf_counter()
    round_of((gets if gets.size else scans)[:1])
    split["first_call"] = time.perf_counter() - t
    t = time.perf_counter()
    if gets.size:
        for k in range(1, fe.config.max_round + 1):
            round_of(np.resize(gets, k))
    for chunk in np.array_split(scans[:WARM_SCANS],
                                max(1, min(WARM_SCANS, scans.size) // 4)):
        if chunk.size:
            round_of(chunk)
    split["warm_up"] = time.perf_counter() - t


def check(oracle: reference.Oracle, win: loop.Window) -> Dict[str, dict]:
    """Every answer of the window against the reference: each number
    with its limit (an exact comparison: 0)."""
    plan = win.plan
    answered = ~np.isnan(win.done) & ~win.error
    out = {}
    for k, kind in enumerate(KINDS):
        mine = plan.kind == k
        if not mine.any():
            continue
        ok = np.flatnonzero(mine & answered)
        if kind == "get":
            rank = np.array([win.answers[i][0] for i in ok], np.int64)
            found = np.array([win.answers[i][1] for i in ok], bool)
            wrong = reference.gets_wrong(oracle, plan.lo[ok], rank, found)
        else:
            wrong = sum(reference.scan_wrong(oracle, plan.lo[i], plan.hi[i],
                                             *win.answers[i]) for i in ok)
        # an answer that came back as an error says the wrong thing
        wrong += int(np.sum(mine & win.error & ~win.refused))
        out[f"{kind}_wrong"] = {"value": int(wrong), "limit": 0}
    missing = np.isnan(win.done) & ~win.refused
    out["unanswered"] = {"value": int(missing.sum()), "limit": 0}
    return out


def traced_work(proxy_log, snap) -> Dict[str, int]:
    """Bytes the calls made inside the trace had to move: each lane of
    a (padded) lookup batch, each fused scan."""
    out = {"get": 0, "scan_batch": 0}
    queries = [args[0] for op, args, _ in proxy_log if op == "get"]
    if queries:
        idx = snap.index
        out["get"] = work.lookup_bytes(
            np.concatenate(queries), snap.keys.raw, idx.err_lo, idx.err_hi,
            idx.seg_lo, idx.seg_hi)
    for op, _, result in proxy_log:
        if op == "scan_batch":
            keys, _, live = jax.device_get(result)
            out["scan_batch"] += work.scan_bytes(
                int(np.sum(live)), keys.shape[0], keys.shape[1])
    return out


class Tracer:
    """Opens and closes a profiler trace inside the window, on the
    marks thread, and notes the proxy's counts at both ends."""

    def __init__(self, proxy: loop.TimedService):
        self.proxy = proxy
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.t = [0.0, 0.0]
        self.totals = [None, None]

    def start(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.proxy.log = []
        self.totals[0] = self.proxy.totals()
        self.t[0] = time.perf_counter()

    def stop(self) -> None:
        self.t[1] = time.perf_counter()
        self.totals[1] = self.proxy.totals()
        self.log, self.proxy.log = self.proxy.log, None
        jax.profiler.stop_trace()

    def reduce(self, snap) -> dict:
        try:
            red = trace_reduce.reduce_trace(trace_reduce.read_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        calls = {op: self.totals[1][op][0] - self.totals[0][op][0]
                 for op in loop.TimedService.OPS}
        red.update(window_s=self.t[1] - self.t[0], calls=calls,
                   bytes=traced_work(self.log, snap))
        return red


def lateness_line(win: loop.Window) -> str:
    """How late the generator sent requests after they were due."""
    sent = ~np.isnan(win.sent)
    late = win.sent[sent] - win.plan.due[sent]
    if not late.size:
        return "generator lateness: none sent"
    p50, p99 = np.quantile(late, [0.5, 0.99])
    return (f"generator lateness: p50 {p50 * 1e3:.3f} ms, p99 "
            f"{p99 * 1e3:.3f} ms, max {late.max() * 1e3:.3f} ms")


@dataclasses.dataclass
class Deployment:
    """A cell's service, built and warmed, ready for windows."""

    keys: np.ndarray
    vals: np.ndarray
    svc: object
    proxy: loop.TimedService
    fe: object
    page_size: int
    compiles: loop.CompileCounter
    split: Dict[str, float]
    cache_dir: str


def deploy(cell: catalog.Cell, seed: int) -> Deployment:
    """Everything before the first due request: keys, service,
    frontend, and the warm-up of every shape the cell's traffic uses."""
    split: Dict[str, float] = {}
    cache_dir = enable_cache()
    compiles = loop.CompileCounter()
    keys, vals, svc, proxy, fe = build(cell, seed, split)
    page_size = int(cell.mix.get("page_size", fe.config.scan_page_size))
    warm = make_plan(cell.mix, keys, seed, 1.0,
                     rate=4 * fe.config.max_round + WARM_SCANS, stream=1)
    warm_up(fe, warm, page_size, split)
    split["programs"] = compiles.count
    split["from_cache"] = compiles.cache_hits
    split["compile_s"] = compiles.seconds
    return Deployment(keys, vals, svc, proxy, fe, page_size, compiles,
                      split, cache_dir)


def window(dep: Deployment, plan, seconds: float, marks=()) -> loop.Window:
    """One open-loop window through the frontend's own dispatcher.  The
    garbage left by set-up is collected first; the cyclic collector
    stays on inside the window, as in a server."""
    gc.collect()
    dep.fe.start()
    try:
        return loop.drive(dep.fe, plan, seconds, dep.page_size, marks=marks)
    finally:
        dep.fe.stop()


def run_cell(cell: catalog.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, root=catalog.ROOT) -> dict:
    """One run; returns the result object (its ``checks`` key last)."""
    dep = deploy(cell, seed)
    keys, vals, svc, proxy, fe = dep.keys, dep.vals, dep.svc, dep.proxy, dep.fe
    compiles, split = dep.compiles, dep.split
    plan = make_plan(cell.mix, keys, seed, seconds)
    tracer = Tracer(proxy) if trace else None
    marks = ()
    if tracer is not None:
        at = TRACE_AT * seconds
        marks = ((at, tracer.start),
                 (at + min(TRACE_S, 0.4 * seconds), tracer.stop))
    fe0 = fe.serving_summary()
    svc0 = proxy.totals()
    n0 = compiles.count
    win = window(dep, plan, seconds, marks)
    setup_s = win.t0 - t_start
    fe1 = fe.serving_summary()
    svc1 = proxy.totals()
    in_window = compiles.count - n0
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    held = int(svc.num_keys)
    snap = svc._mgr.current()

    log(f"cell {cell.name}: {keys.size} keys, seed {seed}, {plan.size} "
        f"requests in {seconds} s, compile cache {dep.cache_dir}")
    log("setup: " + ", ".join(f"{k} {v:.3f}" for k, v in split.items())
        + f", setup_s {setup_s:.3f} s")
    log(lateness_line(win) + f"; refused {int(win.refused.sum())}, "
        f"errors {int(win.error.sum())}, unanswered "
        f"{int(np.sum(np.isnan(win.done) & ~win.refused))}")

    trace_red = tracer.reduce(snap) if tracer is not None else None
    oracle = reference.Oracle(keys, vals)
    t = time.perf_counter()
    checks = check(oracle, win)
    log(f"check: {time.perf_counter() - t:.3f} s")

    lat = win.latency()
    for k, kind in enumerate(KINDS):
        mine = lat[plan.kind == k]
        if mine.size:
            log(f"{kind} latency: " + ", ".join(
                f"p{q} {tail_ms(mine, q):.3f} ms" for q in (50, 95, 99))
                + f" over {mine.size} requests")
    rec = {
        "seconds": seconds,
        "setup_s": setup_s,
        "latency_s": {kind: lat[plan.kind == k] for k, kind in enumerate(KINDS)
                      if np.any(plan.kind == k)},
        "ops_ok_in_window": int(np.sum(win.answered_ok()
                                       & (win.done <= seconds))),
        "frontend": {"enqueued": fe1["requests"] - fe0["requests"],
                     "rounds": fe1["rounds"] - fe0["rounds"]},
        "service": {op: {"calls": svc1[op][0] - svc0[op][0],
                         "seconds": svc1[op][1] - svc0[op][1]}
                    for op in loop.TimedService.OPS},
        "compiles_in_window": in_window,
        "memory": {"peak_bytes": peak, "keys": held},
        "trace": trace_red,
    }
    device = jax.devices()[0]
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": jax.device_count(), "memory_peak_bytes": peak}
    result = {}
    if trace:
        rec["peaks"] = catalog.load_peaks(device.device_kind, root)
        metrics = catalog.read_metrics(cell.per_layer, rec, root)
        if trace_red.get("busy_s"):
            dev.update(busy_s=trace_red["busy_s"],
                       window_s=trace_red["window_s"])
            result["breakdown"] = {"device_ops": trace_red["device_ops"],
                                   "idle_gaps": trace_red["idle_gaps"]}
    else:
        metrics = catalog.read_metrics(cell.end_to_end, rec, root)
    failed = int(np.sum(~win.answered_ok()))
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return {"correct": correct, "attempted": plan.size, "failed": failed,
            "metrics": metrics, "device": dev, **result, "checks": checks}

