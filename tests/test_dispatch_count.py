"""Dispatch-discipline regression tests.

The perf contract of the device read path is structural, not just a
benchmark number: every hot read must be ONE device dispatch.  The
counting wrapper in `kernels.ops` (`count_dispatches`) increments at
each non-jitted op boundary — one increment per jitted program entry —
so a read path that silently regresses into per-shard or per-page
dispatch loops fails here long before a latency dashboard notices.

Pinned: `IndexService.scan_batch`, `ShardedIndexService.scan_batch`,
`ShardedIndexService.lookup_batch` / `get` / `contains` — exactly one
dispatch per call, kernel strategies and XLA fallbacks alike, cache
cold or warm.

The dispatch counter sees op boundaries only: an eager jnp op run
after the jitted program inside the same op (a dtype cast of its
output, say) is a second device program it cannot see.  Programs are
pinned by what JAX compiles instead: a cold call at a fresh signature
builds one executable per program it launches, so
`test_cold_scan_batch_compiles_one_program` holds `scan_batch` to
exactly one.
"""

import threading

import numpy as np
import pytest

from repro.index_service import (
    IndexService,
    ServiceConfig,
    ShardedIndexService,
)
from repro.kernels import ops


def _lattice(n=4_000):
    return np.arange(2, n + 2, dtype=np.float64) * 1024.0


def _dispatches(fn) -> int:
    fn()  # warmup: compile + fill device-plane caches
    with ops.count_dispatches() as n:
        fn()
        return n()


@pytest.mark.parametrize("strategy", ["binary", "pallas_fused"])
def test_scan_batch_single_dispatch(strategy):
    base = _lattice()
    svc = IndexService(
        base, ServiceConfig(delta_capacity=512, strategy=strategy),
        vals=np.arange(base.size, dtype=np.int64),
    )
    svc.insert(np.arange(3, 300, 7, dtype=np.float64) * 1024.0 + 512.0)
    svc.delete(base[::11])
    lo, hi = float(base[10]), float(base[-10])
    assert _dispatches(lambda: svc.scan_batch(lo, hi, 128)) == 1
    # a write invalidates the scan plane; the rebuild still costs ONE
    # dispatch (re-pack is host work, not a device program)
    svc.insert(np.array([5.0 * 1024.0 + 512.0]))
    with ops.count_dispatches() as n:
        svc.scan_batch(lo, hi, 128)
        assert n() == 1


@pytest.mark.parametrize("strategy", ["binary", "pallas_fused"])
def test_cold_scan_batch_compiles_one_program(strategy):
    """A cold `scan_batch` compiles exactly one executable — the fused
    scan with its argument casts and the live mask's bool cast inside
    — on the XLA twin and the kernel path alike: no eager op runs
    before or after the program."""
    import jax

    from repro.obs import metrics as obs_metrics

    me = threading.get_ident()
    seen = []

    def listener(event, duration, **_):
        if event == obs_metrics.COMPILE_EVENT and threading.get_ident() == me:
            seen.append(event)

    def retraces():
        return sum(r["retraces"] for r in ops.dispatch_summary()["rows"]
                   if r["op"] == "rmi_scan_range"
                   and r["strategy"] == strategy)

    base = _lattice()
    svc = IndexService(
        base, ServiceConfig(delta_capacity=512, strategy=strategy),
        vals=np.arange(base.size, dtype=np.int64),
    )
    svc.insert(np.arange(3, 300, 7, dtype=np.float64) * 1024.0 + 512.0)
    lo, hi = float(base[10]), float(base[-10])
    svc.scan_batch(lo, hi, 128)  # builds and uploads the scan plane
    before = retraces()
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        # a page size no other test (nor the other strategy) uses
        out = svc.scan_batch(lo, hi, {"binary": 112, "pallas_fused": 120}[
            strategy])
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert len(seen) == 1
    assert retraces() - before == 1
    assert out[2].dtype == bool


@pytest.mark.parametrize("strategy", ["binary", "pallas_fused"])
def test_sharded_read_paths_single_dispatch(strategy):
    base = _lattice(6_000)
    svc = ShardedIndexService(base, ServiceConfig(
        num_shards=3, delta_capacity=512, strategy=strategy,
        bloom_fpr=0.02,
    ))
    svc.insert(np.arange(3, 900, 13, dtype=np.float64) * 1024.0 + 512.0)
    sample = np.concatenate([
        base[::17], np.arange(7, 400, 31, dtype=np.float64) * 1024.0 + 256.0,
    ])
    lo, hi = float(base[20]), float(base[-20])
    assert _dispatches(lambda: svc.lookup_batch(sample)) == 1
    assert _dispatches(lambda: svc.scan_batch(lo, hi, 128)) == 1
    assert _dispatches(lambda: svc.get(sample)) == 1
    assert _dispatches(lambda: svc.contains(sample)) == 1


def test_sharded_plan_reuse_across_reads():
    """Interleaved read kinds share one device plan: no per-call
    re-pack forcing extra dispatches, and a single-shard write only
    re-packs that shard (the plan key diff) — still one dispatch."""
    base = _lattice(6_000)
    svc = ShardedIndexService(base, ServiceConfig(
        num_shards=3, delta_capacity=512,
    ))
    sample = base[::13]
    svc.lookup_batch(sample)  # warm
    with ops.count_dispatches() as n:
        svc.get(sample)
        svc.contains(sample)
        svc.lookup_batch(sample)
        assert n() == 3  # one each, nothing hidden
    # write to exactly one shard, then read: the incremental plan
    # rebuild is host-side; reads stay one dispatch each
    svc.insert(np.array([3.0 * 1024.0 + 128.0]))
    with ops.count_dispatches() as n:
        svc.get(sample)
        assert n() == 1


def test_count_dispatches_is_thread_local():
    """A background thread churning its own service must not leak
    dispatches into another thread's counting window — the old
    module-global counter did exactly that, poisoning every windowed
    assertion above whenever background compaction fired."""
    import threading

    base = _lattice()
    mine = IndexService(base, ServiceConfig(delta_capacity=512))
    other = IndexService(base + 512.0, ServiceConfig(delta_capacity=512))
    mine.scan_batch(float(base[10]), float(base[-10]), 128)  # warm
    stop = threading.Event()
    started = threading.Event()

    def churn():
        q = base + 512.0
        while not stop.is_set():
            other.lookup_batch(q[:256])
            started.set()

    t = threading.Thread(target=churn)
    t.start()
    try:
        assert started.wait(timeout=30)
        with ops.count_dispatches() as n:
            mine.scan_batch(float(base[10]), float(base[-10]), 128)
            assert n() == 1  # the noisy neighbour is invisible
    finally:
        stop.set()
        t.join()
    # ...but the process-level ledger saw both threads
    per_thread = ops.thread_dispatch_counts()
    assert len(per_thread) >= 2
    assert sum(per_thread.values()) == ops.DISPATCH_COUNT


def test_dispatch_attribution_rows_and_retraces():
    """The attribution ledger tags every op boundary with
    (op, kernel-vs-fallback, strategy), accumulates wall time, and
    counts the executables JAX compiles inside it as retraces: a fresh
    shape compiles, a repeat does not."""
    base = _lattice()
    svc = IndexService(
        base, ServiceConfig(delta_capacity=512, strategy="binary"),
        vals=np.arange(base.size, dtype=np.int64),
    )
    lo, hi = float(base[10]), float(base[-10])
    page = 96  # unusual page size: a fresh jit signature regardless of
    # which tests ran before this one in the process

    def row():
        for r in ops.dispatch_summary()["rows"]:
            if r["op"] == "rmi_scan_range" and r["strategy"] == "binary":
                return r
        return None

    before = row() or {"count": 0, "wall_s": 0.0, "retraces": 0}
    svc.scan_batch(lo, hi, page)
    after = row()
    assert after is not None
    assert after["path"] == "fallback"  # binary = XLA, not the kernel
    assert after["count"] == before["count"] + 1
    assert after["wall_s"] > before["wall_s"]
    assert after["retraces"] > before["retraces"]  # fresh signature

    svc.scan_batch(lo, hi, page)  # identical call: cached program
    again = row()
    assert again["count"] == after["count"] + 1
    assert again["retraces"] == after["retraces"]  # no new trace

    svc.scan_batch(lo, hi, page // 2)  # new page size: new signature
    assert row()["retraces"] > again["retraces"]


def test_reset_dispatch_stats_clears_ledger_not_signatures():
    base = _lattice()
    svc = IndexService(base, ServiceConfig(delta_capacity=512))
    svc.scan_batch(float(base[10]), float(base[-10]), 160)
    assert ops.dispatch_summary()["total"] >= 1
    ops.reset_dispatch_stats()
    s = ops.dispatch_summary()
    assert s["total"] == 0 and s["rows"] == []
    # jax's compile cache survives the reset, so a replayed call
    # compiles nothing and is no retrace
    svc.scan_batch(float(base[10]), float(base[-10]), 160)
    r = ops.dispatch_summary()["rows"][0]
    assert r["count"] == 1 and r["retraces"] == 0


def test_retraces_count_backend_compiles():
    """Each executable JAX builds inside a dispatch span is one retrace
    of that op: the ledger's count equals the backend-compile events
    JAX reported on the thread during the call."""
    import jax

    from repro.obs import metrics as obs_metrics

    seen = []

    def listener(event, duration, **_):
        if event == obs_metrics.COMPILE_EVENT:
            seen.append(event)

    base = _lattice()
    svc = IndexService(base, ServiceConfig(delta_capacity=512))
    lo, hi = float(base[10]), float(base[-10])
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        ops.reset_dispatch_stats()
        svc.scan_batch(lo, hi, 80)  # a page size no other test uses
        fresh = sum(r["retraces"] for r in ops.dispatch_summary()["rows"])
        assert fresh == len(seen) >= 1
        svc.scan_batch(lo, hi, 80)
        again = sum(r["retraces"] for r in ops.dispatch_summary()["rows"])
        assert again == fresh
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def test_compile_windows_nest_and_stay_on_their_thread():
    """A compile is charged to the innermost window open on the thread
    that compiles; another thread's compiles are not."""
    import jax
    import jax.numpy as jnp

    from repro.obs import metrics as obs_metrics

    seen = []  # the compiling thread of each backend compile

    def listener(event, duration, **_):
        if event == obs_metrics.COMPILE_EVENT:
            seen.append(threading.get_ident())

    def fresh_program(k):
        return jax.jit(lambda x: x * k + 1.0)(jnp.ones(3))

    me = threading.get_ident()
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        with obs_metrics.count_compiles() as outer:
            fresh_program(1)
            mark = len(seen)
            with obs_metrics.count_compiles() as inner:
                fresh_program(2)
            in_inner = len(seen) - mark
            t = threading.Thread(target=fresh_program, args=(3,))
            t.start()
            t.join()
        with obs_metrics.count_compiles() as cached:
            np.asarray(jax.jit(jnp.sin)(jnp.ones(3)))
            np.asarray(jax.jit(jnp.sin)(jnp.ones(3)))
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert inner[0] == in_inner >= 1
    assert outer[0] + inner[0] + cached[0] == seen.count(me)
    assert any(tid != me for tid in seen)  # the thread's own compile
    assert cached[0] <= 1  # the second call hits the in-memory cache
