"""Multi-tenant frontend tests: coalescing keeps the one-dispatch
discipline, admission control backpressures, writes shed under
degradation while reads keep serving, and read-your-writes holds
across delta freezes and compaction stalls.

`pump()` runs a round on the calling thread, so `count_dispatches`
windows (thread-local) wrap the frontend's device work directly — the
threaded dispatcher exercises the same `_round` code path.
"""

import threading

import numpy as np
import pytest

from repro.index_service import IndexService, ServiceConfig
from repro.kernels import ops
from repro.obs import lockstat
from repro.serve import Backpressure, FrontendConfig, IndexFrontend, WriteShed


def _lattice(n=2_000):
    return np.arange(2, n + 2, dtype=np.float64) * 1024.0


def _frontend(base=None, delta_capacity=512, **svc_kw):
    base = _lattice() if base is None else base
    svc = IndexService(
        base, ServiceConfig(delta_capacity=delta_capacity, **svc_kw)
    )
    return IndexFrontend(svc, FrontendConfig(max_queue=256))


def _pump_dispatches(fe, enqueue) -> int:
    enqueue()
    fe.pump()  # warmup round: compile + fill device-plane caches
    enqueue()
    with ops.count_dispatches() as n:
        fe.pump()
        return n()


# ---- coalescing keeps the one-dispatch discipline --------------------------

def test_coalesced_gets_one_dispatch():
    fe = _frontend()
    base = _lattice()

    def enqueue():
        for c in range(12):  # 12 tenants' point reads, one round
            fe.submit(f"t{c}", "get", base[c * 7: c * 7 + 4])

    # 12 clients x 4 keys -> ONE batched svc.get -> ONE dispatch
    assert _pump_dispatches(fe, enqueue) == 1


def test_mixed_round_dispatches_per_kind_not_per_request():
    fe = _frontend()
    base = _lattice()
    fresh = [7.25]  # insert target far from the lattice

    def enqueue():
        fresh[0] += 1.0
        for c in range(8):
            fe.submit(f"g{c}", "get", base[c: c + 3])
        for c in range(6):
            fe.submit(f"c{c}", "contains", base[c * 5: c * 5 + 2])
        fe.submit("w", "insert", np.array([fresh[0]]),
                  np.zeros(1, np.int64))

    # 8 gets coalesce to one dispatch, 6 contains to another; the
    # staged insert is host work — NOT 15 dispatches
    assert _pump_dispatches(fe, enqueue) == 2


# ---- admission control -----------------------------------------------------

def test_backpressure_when_queue_full():
    fe = _frontend()
    fe.config = FrontendConfig(max_queue=2, submit_timeout_s=0.05)
    fe.submit("a", "get", np.array([2048.0]))
    fe.submit("a", "get", np.array([2048.0]))
    with pytest.raises(Backpressure):
        fe.submit("a", "get", np.array([2048.0]))
    assert fe.metrics.counter("frontend.rejected").value == 1
    # a pump drains room; admission recovers
    fe.pump()
    fe.submit("a", "get", np.array([2048.0]))
    fe.pump()


def test_write_shed_keeps_reads_serving():
    class _DegradedService:
        def insert(self, keys, vals=None):
            raise OverflowError("delta full; compaction stalled")

        def get(self, keys):
            q = np.atleast_1d(keys)
            return np.zeros(q.shape, np.int64), np.ones(q.shape, bool)

    fe = IndexFrontend(_DegradedService(), FrontendConfig())
    w = fe.submit("a", "insert", np.array([1.0]), np.zeros(1, np.int64))
    r = fe.submit("b", "get", np.array([1.0]))
    fe.pump()
    with pytest.raises(WriteShed):
        w.wait(1)
    _, live = r.wait(1)  # the read in the SAME round still served
    assert live.all()
    assert fe.metrics.counter("frontend.shed_writes").value == 1
    summary = fe.serving_summary()
    assert summary["tenants"]["a"]["shed_writes"] == 1
    assert summary["tenants"]["b"]["errors"] == 0


# ---- read-your-writes across the maintenance machinery ---------------------

def test_threaded_clients_read_their_writes():
    # lock-order sanitizer armed for the run: the frontend condition +
    # service lock acquisitions across 8 client threads, the dispatcher
    # and delta freezes must form an acyclic order graph
    lockstat.enable()
    lockstat.reset()
    fe = _frontend(delta_capacity=64)  # small: force freezes mid-run
    errors = []

    def client(tenant, lo):
        keys = lo + np.arange(24, dtype=np.float64) * 0.5
        try:
            for chunk in np.split(keys, 4):
                fe.insert(tenant, chunk, np.arange(chunk.size))
                _, live = fe.get(tenant, chunk)  # acked -> visible
                if not live.all():
                    errors.append((tenant, "get missed acked insert"))
                if not fe.contains(tenant, chunk).all():
                    errors.append((tenant, "contains missed acked insert"))
        except BaseException as e:  # noqa: BLE001 — collected for assert
            errors.append((tenant, repr(e)))

    with fe:
        threads = [
            threading.Thread(target=client, args=(f"t{i}", 7.0 + i * 100))
            for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    try:
        lockstat.assert_acyclic()
    finally:
        lockstat.disable()
        lockstat.reset()
    assert not errors
    # the churn actually crossed at least one freeze/swap boundary
    assert fe.service.metrics.counter("delta.freezes").value >= 1
    summary = fe.serving_summary()
    assert len(summary["tenants"]) == 8
    for name, row in summary["tenants"].items():
        assert row["requests"] == 12, name  # 4 chunks x 3 ops
        assert row["errors"] == 0
        assert set(row["ops"]) == {"insert", "get", "contains"}


def test_read_your_writes_across_compaction_stall():
    # 17 live keys, capacity-16 delta: deleting 16 fills the delta and
    # the compaction attempt merges to 1 < min_keys — a stall.  The
    # frontend must keep serving exact reads from the pinned view and
    # keep accepting the writes that cure the stall.
    base = np.arange(2, 19, dtype=np.float64) * 1024.0  # 17 keys
    fe = _frontend(base=base, delta_capacity=16)
    svc = fe.service

    r0 = fe.submit("a", "delete", base[:16])
    fe.pump()
    r0.wait(1)
    r_del = fe.submit("a", "delete", base[16:])
    r_live = fe.submit("b", "contains", base)
    fe.pump()
    r_del.wait(1)
    assert svc.stats["compact_stalls"] >= 1
    # reads during the stall are exact: every key is dead
    assert not r_live.wait(1).any()

    # fresh inserts land in the stall-stretched delta and cure it
    fresh = np.arange(40, 72, dtype=np.float64) * 1024.0 + 512.0
    r_ins = fe.submit("a", "insert", fresh, np.arange(fresh.size))
    fe.pump()
    assert r_ins.wait(1) == fresh.size
    r_chk = fe.submit("a", "contains", fresh)
    fe.pump()
    assert r_chk.wait(1).all()


def test_ryw_across_forced_freeze_single_thread():
    fe = _frontend(delta_capacity=32)
    svc = fe.service
    start = float(_lattice()[-1]) + 1000.0
    for round_i in range(6):  # 6 x 16 staged writes across a 32 delta
        keys = start + round_i * 100 + np.arange(16, dtype=np.float64)
        fe.submit("a", "insert", keys, np.arange(16))
        r = fe.submit("a", "get", keys)
        fe.pump()
        _, live = r.wait(1)
        assert live.all(), f"round {round_i} lost acked writes"
    assert svc.metrics.counter("delta.freezes").value >= 1


# ---- round counters ---------------------------------------------------------

def test_round_counters_are_exact(monkeypatch):
    """Queue wait, lanes sent and padded lanes are added once a round,
    exactly: the round starts at a fixed clock, each request was
    enqueued a known time before it."""
    from types import SimpleNamespace

    from repro.serve import frontend as frontend_mod

    fe = _frontend()
    base = _lattice()
    monkeypatch.setattr(frontend_mod, "time",
                        SimpleNamespace(perf_counter=lambda: 1000.0))
    waits = [0.25, 0.5, 1.0, 2.0]
    reqs = [fe.submit("a", "get", base[:3]), fe.submit("b", "get", base[5:10]),
            fe.submit("a", "contains", base[20:24]),
            fe.submit("c", "scan", float(base[3]), float(base[9]), 64)]
    for r, w in zip(reqs, waits):
        r.enqueued_at = 1000.0 - w
    fe.pump()
    assert [r.round for r in reqs] == [1, 1, 1, 1]
    ctr = {k: fe.metrics.counter(f"frontend.{k}").value
           for k in ("queue_wait_s", "read_lanes", "padded_lanes")}
    # 8 gets pad to one 64-lane batch, 4 contains to another; the scan
    # sends no keyed lanes
    assert ctr == {"queue_wait_s": sum(waits), "read_lanes": 128,
                   "padded_lanes": 128 - 12}
    fe.submit("a", "get", base[:2]).enqueued_at = 1000.0 - 4.0
    fe.pump()
    assert fe.metrics.counter("frontend.queue_wait_s").value == sum(waits) + 4
    assert fe.metrics.counter("frontend.read_lanes").value == 192
    assert fe.metrics.counter("frontend.padded_lanes").value == 192 - 14
