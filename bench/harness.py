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
import math
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Tuple

import jax
import numpy as np

from bench import catalog, loop, reference, trace_reduce, traffic, work
from bench.metrics_util import tail_ms
from bench.traffic import KeySpace, make_plan

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


class SwapLog:
    """When the service swapped snapshots (a compaction's commit), on
    the host clock just before and just after the swap, and the frame
    it says each installed; first the one it serves in now.  The
    reference admits a frame only where the keys allow it
    (`reference.Oracle`)."""

    def __init__(self, svc):
        mgr = svc._mgr
        keys = mgr.current().keys
        self.frames: List[tuple] = [(-np.inf, -np.inf, keys.lo, keys.hi)]
        swap = mgr.swap

        def recorded(new):
            t = time.perf_counter()
            swap(new)
            self.frames.append((t, time.perf_counter(), new.keys.lo,
                                new.keys.hi))
        mgr.swap = recorded

    def since(self, t0: float) -> List[tuple]:
        """The frames with times in seconds after ``t0``."""
        return [(a - t0, b - t0, lo, hi) for a, b, lo, hi in self.frames]


def build(cell: catalog.Cell, seed: int, pool: int, split: Dict[str, float]):
    """Keys from the seed, with ``pool`` of them held back for the mix's
    inserts, then the service over the rest and its frontend.  Each key's
    value is its row id in the full key set."""
    from repro.index_service import IndexService, ServiceConfig
    from repro.serve.frontend import FrontendConfig, IndexFrontend

    cfg = cell.config
    t = time.perf_counter()
    final = catalog.load_generator(cfg["generator"], cell.root)(
        int(cfg["keys"]), seed, int(cfg["shape_seed"]))
    held = traffic.hold_back(cell.mix, final, pool, seed)
    space = KeySpace(final, held, int(final.size - held.size))
    if held.size:
        vals = space.base()
        keys = final[vals]
    else:
        keys, vals = final, np.arange(final.size, dtype=np.int64)
    split["generate"] = time.perf_counter() - t
    t = time.perf_counter()
    svc = IndexService(keys, ServiceConfig(**cfg["service"]), vals=vals)
    split["build"] = time.perf_counter() - t
    proxy = loop.TimedService(svc)
    fe = IndexFrontend(proxy, FrontendConfig(**cfg["frontend"]))
    return space, svc, proxy, fe


def warm_up(fe, plan, ops, page_size: int, split: Dict[str, float]) -> None:
    """What each kind's module sends (`bench.traffic`): the kinds that
    store keys first, so that reads warm up against the state the window
    opens on.  The first read, which uploads the index and compiles, is
    timed apart."""
    def round_of(idx):
        reqs = loop.send(fe, plan, ops, idx, page_size)
        fe.pump()
        for r in reqs:
            ops[r.kind].answer(r.wait(0))

    present = [(k, np.flatnonzero(plan.kind == c))
               for c, k in enumerate(plan.kinds) if np.any(plan.kind == c)]
    reads = [idx for k, idx in present if not ops[k].ADDS_KEYS]
    t = time.perf_counter()
    if reads:
        round_of(reads[0][:1])
    split["first_call"] = time.perf_counter() - t
    t = time.perf_counter()
    for k, idx in sorted(present, key=lambda p: not ops[p[0]].ADDS_KEYS):
        for chunk in ops[k].warm_rounds(idx, fe.config.max_round):
            if chunk.size:
                round_of(chunk)
    split["warm_up"] = time.perf_counter() - t


def warm_rate(mix: dict, ops, max_round: int) -> int:
    """The warm-up plan's rate over its one second: enough requests of
    each kind of the mix for what its module's warm-up sends."""
    shares = {k: float(v) for k, v in mix["ops"].items() if float(v) > 0}
    total = sum(shares.values())
    return max(math.ceil(ops[k].warm_count(mix, max_round) * total / v)
               for k, v in shares.items())


def write_log(win: loop.Window, ops) -> reference.WriteLog:
    """The window's requests that store keys and entered the service."""
    plan = win.plan
    adds = [c for c, k in enumerate(plan.kinds)
            if k in ops and ops[k].ADDS_KEYS]
    idx = np.flatnonzero(np.isin(plan.kind, adds) & ~win.refused)
    return reference.WriteLog(plan.lo[idx], plan.val[idx], win.sent[idx],
                              win.done[idx], win.answered_ok()[idx])


def check(oracle: reference.Oracle, win: loop.Window, ops,
          service) -> Dict[str, dict]:
    """Every answer of the window against the reference, by each kind's
    module: each number with its limit (an exact comparison: 0)."""
    plan = win.plan
    out = {}
    for c, kind in enumerate(plan.kinds):
        idx = np.flatnonzero(plan.kind == c)
        if idx.size:
            found = ops[kind].check(oracle, win, idx, service)
            out.update({name: {"value": int(v), "limit": 0}
                        for name, v in found.items()})
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
    marks thread: the program's own spans are on while it is open, and
    a ``bench.window`` annotation marks the part that the reduction
    reads.  Notes the proxy's counts at both ends."""

    def __init__(self, proxy: loop.TimedService):
        self.proxy = proxy
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.t = [0.0, 0.0]
        self.totals = [None, None]
        self._mark = None

    def start(self) -> None:
        from repro.obs import trace as obs_trace

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        obs_trace.TRACER.enable()
        self._mark = jax.profiler.TraceAnnotation("bench.window")
        self._mark.__enter__()
        self.proxy.log = []
        self.totals[0] = self.proxy.totals()
        self.t[0] = time.perf_counter()

    def stop(self) -> None:
        from repro.obs import trace as obs_trace

        self.t[1] = time.perf_counter()
        self.totals[1] = self.proxy.totals()
        self.log, self.proxy.log = self.proxy.log, None
        self._mark.__exit__(None, None, None)
        obs_trace.TRACER.disable()
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

    space: KeySpace          # the keys, and how many are stored now
    svc: object
    proxy: loop.TimedService
    fe: object
    ops: Dict[str, object]   # the mix's request kinds
    page_size: int
    compiles: loop.CompileCounter
    swaps: SwapLog
    built: Tuple[float, float]   # the first and last key built over
    split: Dict[str, float]
    cache_dir: str
    mix: dict


def deploy(cell: catalog.Cell, seed: int,
           windows: Tuple[Tuple[float, float], ...] = ()) -> Deployment:
    """Everything before the first due request: keys, service,
    frontend, and the warm-up of every shape the cell's traffic uses.
    ``windows`` are the (seconds, rate or None) of the windows to come:
    the keys their inserts store are held back from the build."""
    split: Dict[str, float] = {}
    cache_dir = enable_cache()
    compiles = loop.CompileCounter()
    from repro.serve.frontend import FrontendConfig

    ops = traffic.load_ops(cell.mix, cell.root)
    rate = warm_rate(cell.mix, ops,
                     FrontendConfig(**cell.config["frontend"]).max_round)

    def adds(seconds, rate):
        counts = traffic.kind_counts(cell.mix, seconds, rate)
        return sum(c for k, c in counts.items() if ops[k].ADDS_KEYS)
    pool = adds(1.0, rate) + sum(adds(s, r) for s, r in windows)
    space, svc, proxy, fe = build(cell, seed, pool, split)
    ends = space.final[space.index_of(np.array([0, space.base_size - 1]))]
    swaps = SwapLog(svc)
    page_size = int(cell.mix.get("page_size", fe.config.scan_page_size))
    warm = make_plan(cell.mix, space, seed, 1.0, rate=rate, stream=1,
                     ops=ops)
    warm_up(fe, warm, ops, page_size, split)
    split["programs"] = compiles.count
    split["from_cache"] = compiles.cache_hits
    split["compile_s"] = compiles.seconds
    return Deployment(space.after(warm, ops), svc, proxy, fe, ops,
                      page_size, compiles, swaps, (ends[0], ends[1]), split,
                      cache_dir, cell.mix)


def next_plan(dep: Deployment, seed: int, seconds: float,
              rate: float = None):
    """The next window's plan, and the key space it opens on."""
    space = dep.space
    plan = make_plan(dep.mix, space, seed, seconds, rate=rate, ops=dep.ops)
    dep.space = space.after(plan, dep.ops)
    return plan, space


def window(dep: Deployment, plan, seconds: float, marks=()) -> loop.Window:
    """One open-loop window through the frontend's own dispatcher.  The
    garbage left by set-up is collected first; the cyclic collector
    stays on inside the window, as in a server."""
    gc.collect()
    dep.fe.start()
    try:
        return loop.drive(dep.fe, plan, seconds, dep.page_size, dep.ops,
                          marks=marks)
    finally:
        dep.fe.stop()


def counter_values(*registries) -> Dict[str, float]:
    """Every counter of the registries, by name."""
    out = {}
    for reg in registries:
        out.update(reg.snapshot()["counters"])
    return out


def run_cell(cell: catalog.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, root=catalog.ROOT) -> dict:
    """One run; returns the result object (its ``checks`` key last)."""
    dep = deploy(cell, seed, ((seconds, None),))
    svc, proxy, fe, ops = dep.svc, dep.proxy, dep.fe, dep.ops
    compiles, split = dep.compiles, dep.split
    plan, space = next_plan(dep, seed, seconds)
    tracer = Tracer(proxy) if trace else None
    marks = ()
    if tracer is not None:
        at = TRACE_AT * seconds
        marks = ((at, tracer.start),
                 (at + min(TRACE_S, 0.4 * seconds), tracer.stop))
    fe0 = fe.serving_summary()
    svc0 = proxy.totals()
    c0 = counter_values(svc.metrics, fe.metrics)
    n0 = compiles.count
    win = window(dep, plan, seconds, marks)
    setup_s = win.t0 - t_start
    fe1 = fe.serving_summary()
    svc1 = proxy.totals()
    c1 = counter_values(svc.metrics, fe.metrics)
    in_window = compiles.count - n0
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    held = int(svc.num_keys)
    snap = svc._mgr.current()

    log(f"cell {cell.name}: {space.stored} keys at the open, seed {seed}, "
        f"{plan.size} "
        f"requests in {seconds} s, compile cache {dep.cache_dir}")
    log("setup: " + ", ".join(f"{k} {v:.3f}" for k, v in split.items())
        + f", setup_s {setup_s:.3f} s")
    log(lateness_line(win) + f"; refused {int(win.refused.sum())}, "
        f"errors {int(win.error.sum())}, unanswered "
        f"{int(np.sum(np.isnan(win.done) & ~win.refused))}")

    trace_red = tracer.reduce(snap) if tracer is not None else None
    t = time.perf_counter()
    stored = space.base()
    keys = (space.final if stored.size == space.final.size
            else space.final[stored])
    oracle = reference.Oracle(keys, stored, write_log(win, ops),
                              dep.swaps.since(win.t0), dep.built)
    checks = check(oracle, win, ops, svc)
    log(f"check: {time.perf_counter() - t:.3f} s")

    lat = win.latency()
    by_kind = {kind: lat[plan.kind == c] for c, kind in enumerate(plan.kinds)
               if np.any(plan.kind == c)}
    for kind, mine in by_kind.items():
        if mine.size:
            log(f"{kind} latency: " + ", ".join(
                f"p{q} {tail_ms(mine, q):.3f} ms" for q in (50, 95, 99))
                + f" over {mine.size} requests")
    rec = {
        "seconds": seconds,
        "setup_s": setup_s,
        "latency_s": by_kind,
        "ops_ok_in_window": int(np.sum(win.answered_ok()
                                       & (win.done <= seconds))),
        "frontend": {"enqueued": fe1["requests"] - fe0["requests"],
                     "rounds": fe1["rounds"] - fe0["rounds"]},
        "service": {op: {"calls": svc1[op][0] - svc0[op][0],
                         "seconds": svc1[op][1] - svc0[op][1]}
                    for op in loop.TimedService.OPS},
        "counters": {k: v - c0.get(k, 0) for k, v in c1.items()},
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

