"""Fault plane + self-healing tests.

Tier-1: (a) the fault registry is complete — every point declared in
`repro.faults.FAULT_POINTS` is fired by the canonical trigger map
below, so a weave site cannot silently detach; (b) schedules are
deterministic and scoped; (c) each healing path does what its contract
says: checksummed checkpoints quarantine corruption and fall back to
the newest intact step, the compactor supervisor restarts a crashed
worker (and escalates after the cap), kernel dispatch fails over
stickily to the bit-identical XLA fallback and recovers on re-probe,
a crashed router re-fit aborts cleanly, and the frontend walks its
degradation ladder HEALTHY -> DEGRADED_WRITES -> STALE_READS ->
UNAVAILABLE with deadlines enforced at dispatch time.
"""

import os
import random
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro import faults
from repro.core import RMIConfig, build_rmi, make_keyset
from repro.distributed.fault_tolerance import (
    CheckpointCorrupt,
    CheckpointManager,
    IndexCheckpointer,
    newest_intact_step,
    restore_checkpoint,
    save_checkpoint,
)
from repro.index_service import IndexService, ServiceConfig, ShardedIndexService
from repro.kernels import ops as kernels_ops
from repro.serve import (
    DEGRADED_WRITES,
    HEALTHY,
    STALE_READS,
    UNAVAILABLE,
    Backpressure,
    DeadlineExceeded,
    FrontendConfig,
    IndexFrontend,
    WriteShed,
    retry_with_backoff,
)


def _keys(n=2048, seed=0):
    rng = np.random.default_rng(seed)
    return np.unique(rng.integers(0, 1 << 40, n).astype(np.float64))


def _fresh(base, n=512, seed=1):
    rng = np.random.default_rng(seed)
    return np.setdiff1d(
        rng.integers(0, 1 << 40, 4 * n).astype(np.float64), base
    )[:n]


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(0, 1, (4, 8)), jnp.float32),
        "nested": {"b": jnp.asarray(rng.integers(0, 5, (3,)), jnp.int32)},
    }


# ---- schedules -----------------------------------------------------------

def test_schedule_int_shorthand_and_counts():
    s = faults.FaultSchedule({"compactor.crash": 2})
    hits = [s.should("compactor.crash") for _ in range(5)]
    assert hits == [True, True, False, False, False]
    assert s.fired["compactor.crash"] == 2
    assert s.probes["compactor.crash"] == 5


def test_schedule_after_skips_probes():
    s = faults.FaultSchedule(
        {"compactor.crash": {"after": 2, "times": 2}}
    )
    hits = [s.should("compactor.crash") for _ in range(6)]
    assert hits == [False, False, True, True, False, False]


def test_schedule_prob_is_seed_deterministic():
    plan = {"kernel.dispatch": {"times": None, "prob": 0.5}}
    a = faults.FaultSchedule(plan, seed=42)
    b = faults.FaultSchedule(plan, seed=42)
    fa = [a.should("kernel.dispatch") for _ in range(200)]
    fb = [b.should("kernel.dispatch") for _ in range(200)]
    assert fa == fb
    assert any(fa) and not all(fa)
    c = faults.FaultSchedule(plan, seed=43)
    fc = [c.should("kernel.dispatch") for _ in range(200)]
    assert fc != fa


def test_unregistered_point_rejected_at_schedule_and_probe():
    with pytest.raises(KeyError):
        faults.FaultSchedule({"no.such.point": 1})
    with faults.inject(faults.FaultSchedule({})):
        with pytest.raises(KeyError):
            faults.should("no.such.point")


def test_disabled_plane_is_inert_and_scopes_nest():
    assert faults.active() is None
    assert faults.should("compactor.crash") is False
    faults.maybe("compactor.crash")  # no-op without a schedule
    outer = faults.FaultSchedule({"compactor.crash": 1})
    inner = faults.FaultSchedule({"router.refit": 1})
    with faults.inject(outer):
        assert faults.active() is outer
        with faults.inject(inner):
            assert faults.active() is inner
        assert faults.active() is outer
    assert faults.active() is None


def test_register_rejects_conflicting_redefinition():
    faults.register("compactor.crash", faults.FAULT_POINTS["compactor.crash"])
    with pytest.raises(ValueError):
        faults.register("compactor.crash", "something else entirely")


def test_injections_are_counted_in_obs_metrics():
    from repro.obs.metrics import default_registry

    ctr = default_registry().counter("faults.compactor.crash.injected")
    before = ctr.value
    with faults.inject(faults.FaultSchedule({"compactor.crash": 1})):
        assert faults.should("compactor.crash") is True
    assert ctr.value == before + 1


# ---- fault-point completeness (satellite: every point has a trigger) ----

def _trigger_ckpt_torn(tmp):
    save_checkpoint(str(tmp), 1, _tree())  # torn fires post-publish


def _trigger_ckpt_crash(tmp):
    with pytest.raises(faults.InjectedFault):
        save_checkpoint(str(tmp), 1, _tree())


def _trigger_compactor_crash(tmp):
    svc = IndexService(_keys(512), ServiceConfig(
        delta_capacity=64, compact_backoff_s=0.001,
        compact_backoff_cap_s=0.002,
    ))
    svc.insert(_fresh(_keys(512), 80))  # crosses the compaction trigger


def _trigger_kernel_dispatch(tmp):
    kernels_ops.reset_failover()
    kernels_ops.run_with_failover(
        "trigger_op", "pallas", lambda: "k", lambda: "f"
    )
    kernels_ops.reset_failover()


def _trigger_router_refit(tmp):
    keys = _keys(512)
    svc = ShardedIndexService(keys, ServiceConfig(
        delta_capacity=256, num_shards=2))
    with pytest.raises(faults.InjectedFault):
        svc.rebalance()


def _trigger_frontend_delay(tmp):
    f = IndexFrontend(_StubService(), FrontendConfig(request_deadline_s=5.0))
    f.submit("t", "get", np.array([1.0]))
    f.pump()


TRIGGERS = {
    "ckpt.write.torn": _trigger_ckpt_torn,
    "ckpt.write.crash": _trigger_ckpt_crash,
    "compactor.crash": _trigger_compactor_crash,
    "kernel.dispatch": _trigger_kernel_dispatch,
    "router.refit": _trigger_router_refit,
    "frontend.queue.delay": _trigger_frontend_delay,
}


def test_every_registered_fault_point_fires(tmp_path):
    # the registry is the contract: every declared point must have a
    # canonical trigger here, and firing it must actually probe the
    # woven site (a renamed weave cannot silently detach)
    assert set(TRIGGERS) >= set(faults.FAULT_POINTS), (
        "fault points missing a trigger: "
        f"{set(faults.FAULT_POINTS) - set(TRIGGERS)}"
    )
    for name, trigger in TRIGGERS.items():
        sub = tmp_path / name.replace(".", "_")
        sub.mkdir()
        with faults.inject(faults.FaultSchedule({name: 1})) as sched:
            trigger(sub)
        assert sched.fired[name] == 1, f"{name} never fired"


# ---- checkpoint integrity ------------------------------------------------

def test_torn_checkpoint_quarantined_and_restore_falls_back(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 5, t)
    with faults.inject(faults.FaultSchedule({"ckpt.write.torn": 1})):
        save_checkpoint(str(tmp_path), 9, _tree(seed=9))
    restored, step = restore_checkpoint(str(tmp_path), t)
    assert step == 5
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.asarray(t["w"]))
    assert os.path.isdir(tmp_path / "step_0000000009.quarantine")
    assert not os.path.isdir(tmp_path / "step_0000000009")


def test_crash_before_publish_leaves_no_step(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 5, t)
    with faults.inject(faults.FaultSchedule({"ckpt.write.crash": 1})):
        with pytest.raises(faults.InjectedFault):
            save_checkpoint(str(tmp_path), 9, t)
    assert not os.path.isdir(tmp_path / "step_0000000009")
    _, step = restore_checkpoint(str(tmp_path), t)
    assert step == 5


def test_manual_corruption_detected_by_checksum(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 5, t)
    save_checkpoint(str(tmp_path), 9, t)
    # bit rot: truncate one leaf of the newest step
    d = tmp_path / "step_0000000009"
    leaves = [p for p in sorted(os.listdir(d)) if p != "manifest.json"]
    victim = d / leaves[0]
    victim.write_bytes(victim.read_bytes()[: max(1, victim.stat().st_size // 2)])
    _, step = restore_checkpoint(str(tmp_path), t)
    assert step == 5
    assert os.path.isdir(tmp_path / "step_0000000009.quarantine")


def test_explicit_corrupt_step_raises_not_falls_back(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 5, t)
    save_checkpoint(str(tmp_path), 9, t)
    d = tmp_path / "step_0000000009"
    leaves = [p for p in sorted(os.listdir(d)) if p != "manifest.json"]
    (d / leaves[0]).write_bytes(b"rot")
    with pytest.raises(CheckpointCorrupt):
        newest_intact_step(str(tmp_path), step=9)


def test_restore_or_init_falls_back_to_init_on_corruption(tmp_path):
    mgr = CheckpointManager(str(tmp_path), every=10)
    t = _tree()
    mgr.save(10, t)
    d = tmp_path / "step_0000000010"
    leaves = [p for p in sorted(os.listdir(d)) if p != "manifest.json"]
    (d / leaves[0]).write_bytes(b"rot")
    init_calls = []

    def init_fn():
        init_calls.append(1)
        return t

    got, step = mgr.restore_or_init(t, init_fn)
    assert step == 0 and init_calls  # quarantined -> nothing intact -> init


def test_index_checkpointer_restores_newest_intact(tmp_path):
    keys = _keys(1024)
    cfg = ServiceConfig(delta_capacity=256, num_shards=2)
    svc = ShardedIndexService(keys, cfg)
    fresh = _fresh(keys, 200)
    svc.insert(fresh[:100])
    probe = np.concatenate([keys[:128], fresh])
    want = svc.contains(probe)
    ckpt = IndexCheckpointer(str(tmp_path), keep_last=4)
    ckpt.save(1, svc)
    svc.insert(fresh[100:])
    with faults.inject(faults.FaultSchedule({"ckpt.write.torn": 1})) as s:
        ckpt.save(2, svc)
    assert s.fired["ckpt.write.torn"] == 1
    del svc
    back, step = ckpt.restore(cfg)
    assert step == 1  # step 2 quarantined, fell back
    np.testing.assert_array_equal(back.contains(probe), want)


# ---- supervised compactor ------------------------------------------------

def test_compactor_crash_restarts_and_heals():
    keys = _keys(2048)
    svc = IndexService(keys, ServiceConfig(
        delta_capacity=128, background=True,
        compact_backoff_s=0.005, compact_backoff_cap_s=0.02,
    ))
    fresh = _fresh(keys, 400)
    probe = np.concatenate([keys[:200], fresh])
    with faults.inject(faults.FaultSchedule({"compactor.crash": 2})) as s:
        svc.insert(fresh[:200])
        deadline = time.time() + 30.0
        while s.fired["compactor.crash"] < 2 or svc.stats["compactions"] < 1:
            assert time.time() < deadline, "supervisor never healed"
            # reads keep serving through the crashes
            got = svc.contains(probe)
            want = np.isin(probe, keys) | np.isin(probe, fresh[:200])
            np.testing.assert_array_equal(got, want)
            time.sleep(0.005)
    assert int(svc.metrics.counter("compact.worker_crashes").value) == 2
    assert int(svc.metrics.counter("compact.worker_restarts").value) == 2
    assert not svc.compactor_escalated
    svc.insert(fresh[200:])
    svc.flush()
    want = np.isin(probe, keys) | np.isin(probe, fresh)
    np.testing.assert_array_equal(svc.contains(probe), want)


def test_compactor_escalates_after_consecutive_failures():
    keys = _keys(1024)
    svc = IndexService(keys, ServiceConfig(
        delta_capacity=128, compact_max_failures=3,
        compact_backoff_s=0.001, compact_backoff_cap_s=0.002,
    ))
    fresh = _fresh(keys, 200)
    with faults.inject(
        faults.FaultSchedule({"compactor.crash": {"times": None}})
    ) as s:
        try:
            svc.insert(fresh[:150])  # crosses the trigger, crashes inline
        except RuntimeError:
            pass  # the parked worker error may surface here
        assert s.fired["compactor.crash"] == 3  # capped, not infinite
    assert svc.compactor_escalated
    assert int(svc.metrics.counter("compact.escalations").value) == 1
    # reads still serve from the frozen stack while escalated
    got = svc.contains(fresh[:150])
    assert got.all()
    # healing: the next successful merge clears the escalation
    with pytest.raises(RuntimeError):
        svc.flush()  # surfaces the parked error first
    svc.flush()
    assert not svc.compactor_escalated
    assert svc.contains(fresh[:150]).all()


def test_sharded_service_surfaces_escalation():
    keys = _keys(1024)
    svc = ShardedIndexService(keys, ServiceConfig(
        delta_capacity=128, num_shards=2, compact_max_failures=2,
        compact_backoff_s=0.001, compact_backoff_cap_s=0.002,
    ))
    assert not svc.compactor_escalated
    fresh = _fresh(keys, 300)
    with faults.inject(
        faults.FaultSchedule({"compactor.crash": {"times": None}})
    ):
        try:
            svc.insert(fresh)
        except RuntimeError:
            pass
    assert svc.compactor_escalated  # any shard escalated => service-level


# ---- kernel failover -----------------------------------------------------

def test_failover_retries_once_then_sticks_then_recovers():
    kernels_ops.reset_failover()
    calls = {"kernel": 0, "fallback": 0}

    def broken():
        calls["kernel"] += 1
        raise RuntimeError("kernel boom")

    def fallback():
        calls["fallback"] += 1
        return "fb"

    assert kernels_ops.run_with_failover("t_op", "pallas", broken,
                                         fallback) == "fb"
    assert calls["kernel"] == 2  # retried once before failing over
    st = kernels_ops.failover_summary()["t_op:pallas"]
    assert st["disabled"]
    # sticky: the kernel is not attempted again off the re-probe cadence
    assert kernels_ops.run_with_failover("t_op", "pallas", broken,
                                         fallback) == "fb"
    assert calls["kernel"] == 2

    def healed():
        calls["kernel"] += 1
        return "kk"

    # the re-probe window re-attempts the kernel and re-enables on success
    outs = set()
    for _ in range(kernels_ops.FAILOVER_REPROBE_EVERY + 2):
        outs.add(kernels_ops.run_with_failover("t_op", "pallas", healed,
                                               fallback))
    assert "kk" in outs
    assert not kernels_ops.failover_summary()["t_op:pallas"]["disabled"]
    kernels_ops.reset_failover()


def test_kernel_build_error_raises_but_injected_fault_fails_over():
    """A kernel that cannot be lowered (here: Mosaic lowering requested
    on a backend without it) raises from the op instead of rerouting —
    a retry cannot heal it, and the fallback would hide that the kernel
    never ran.  The injected run-time fault still fails over to the
    bit-identical fallback and recovers on the re-probe cadence."""
    kernels_ops.reset_failover()
    ks = make_keyset(_keys(2048))
    idx = build_rmi(ks, RMIConfig(num_leaves=32, stage0_hidden=(),
                                  stage0_train_steps=0))
    q = jnp.asarray(ks.norm[::7])
    dk = jnp.full((64,), jnp.inf, jnp.float32)
    dp = jnp.zeros((65,), jnp.int32)

    def op(**kw):
        return kernels_ops.rmi_merged_lookup_op(idx, ks.norm, q, dk, dp,
                                                **kw)

    want = [np.asarray(a) for a in op(use_kernel=False)]
    with pytest.raises(ValueError, match="interpret"):
        op(interpret=False)  # Mosaic lowering on the CPU backend
    st = kernels_ops.failover_summary()["rmi_merged_lookup:pallas_fused"]
    assert not st["disabled"] and st["fallback_calls"] == 0

    with faults.inject(faults.FaultSchedule({"kernel.dispatch": 2})) as s:
        got = op()
    assert s.fired["kernel.dispatch"] == 2
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)
    assert kernels_ops.failover_summary()[
        "rmi_merged_lookup:pallas_fused"]["disabled"]
    for _ in range(kernels_ops.FAILOVER_REPROBE_EVERY):
        op()
    assert not kernels_ops.failover_summary()[
        "rmi_merged_lookup:pallas_fused"]["disabled"]
    kernels_ops.reset_failover()


def test_injected_kernel_fault_reroutes_bit_exact():
    kernels_ops.reset_failover()
    keys = _keys(2048)
    svc = IndexService(keys, ServiceConfig(
        delta_capacity=256, strategy="pallas_fused"))
    oracle = IndexService(keys, ServiceConfig(
        delta_capacity=256, strategy="binary"))
    fresh = _fresh(keys, 100)
    svc.insert(fresh)
    oracle.insert(fresh)
    probe = np.concatenate([keys[:200], fresh, _fresh(keys, 50, seed=3)])
    want_f, want_r = oracle.get(probe)
    svc.get(probe)  # warm the kernel path
    from repro.obs.metrics import default_registry

    before = default_registry().counter("kernel_failover").value
    with faults.inject(faults.FaultSchedule({"kernel.dispatch": 2})) as s:
        got_f, got_r = svc.get(probe)  # retry also injected -> failover
    assert s.fired["kernel.dispatch"] == 2
    assert default_registry().counter("kernel_failover").value == before + 1
    np.testing.assert_array_equal(got_f, want_f)
    np.testing.assert_array_equal(got_r, want_r)
    # sticky fallback keeps serving bit-exact after the schedule ends
    got_f2, got_r2 = svc.get(probe)
    np.testing.assert_array_equal(got_f2, want_f)
    np.testing.assert_array_equal(got_r2, want_r)
    kernels_ops.reset_failover()


# ---- router re-fit clean abort ------------------------------------------

def test_router_refit_crash_aborts_cleanly():
    keys = _keys(2048)
    svc = ShardedIndexService(keys, ServiceConfig(
        delta_capacity=256, num_shards=4))
    fresh = _fresh(keys, 300)
    svc.insert(fresh)
    probe = np.concatenate([keys[:300], fresh])
    want = svc.contains(probe)
    with faults.inject(faults.FaultSchedule({"router.refit": 1})):
        with pytest.raises(faults.InjectedFault):
            svc.rebalance()
    # old router and shards intact: answers unchanged
    np.testing.assert_array_equal(svc.contains(probe), want)
    svc.rebalance()  # the retry heals
    np.testing.assert_array_equal(svc.contains(probe), want)


# ---- frontend: degradation ladder + deadlines ---------------------------

class _StubService:
    """Deterministic op surface for ladder tests."""

    def __init__(self):
        self.fail_reads = False
        self.fail_writes = None  # exception TYPE to raise, or None
        self.compactor_escalated = False

    def _maybe_fail_read(self):
        if self.fail_reads:
            raise RuntimeError("service down")

    def get(self, q):
        self._maybe_fail_read()
        return np.zeros(q.size, bool), np.zeros(q.size, np.int64)

    def contains(self, q):
        self._maybe_fail_read()
        return np.zeros(q.size, bool)

    def range_lookup(self, lo, hi):
        self._maybe_fail_read()
        return np.array([], np.float64)

    def scan_batch(self, lo, hi, page):
        self._maybe_fail_read()
        return []

    def insert(self, keys, vals):
        if self.fail_writes is not None:
            raise self.fail_writes("write pressure")
        return keys.size

    def delete(self, keys):
        if self.fail_writes is not None:
            raise self.fail_writes("write pressure")
        return keys.size


def test_ladder_degraded_writes_then_recovers():
    svc = _StubService()
    f = IndexFrontend(svc, FrontendConfig())
    assert f.health() == HEALTHY
    svc.fail_writes = OverflowError
    req = f.submit("t", "insert", np.array([1.0]), np.array([0]))
    f.pump()
    with pytest.raises(WriteShed):
        req.wait(1.0)
    assert f.health() == DEGRADED_WRITES
    assert f.serving_summary()["health"] == DEGRADED_WRITES
    # a clean write run climbs back up
    svc.fail_writes = None
    req = f.submit("t", "insert", np.array([2.0]), np.array([0]))
    f.pump()
    assert req.wait(1.0) == 1
    assert f.health() == HEALTHY


def test_ladder_stale_reads_fails_writes_fast_at_admission():
    svc = _StubService()
    svc.compactor_escalated = True
    f = IndexFrontend(svc, FrontendConfig())
    assert f.health() == STALE_READS
    with pytest.raises(WriteShed):
        f.submit("t", "insert", np.array([1.0]), np.array([0]))
    # reads still admitted and served
    req = f.submit("t", "contains", np.array([1.0]))
    f.pump()
    assert req.wait(1.0) is not None
    svc.compactor_escalated = False
    assert f.health() == HEALTHY


def test_ladder_unavailable_rejects_all_then_probe_recovers():
    svc = _StubService()
    f = IndexFrontend(svc, FrontendConfig(unavailable_after=3))
    svc.fail_reads = True
    for _ in range(3):
        req = f.submit("t", "get", np.array([1.0]))
        f.pump()
        with pytest.raises(RuntimeError):
            req.wait(1.0)
    assert f.health() == UNAVAILABLE
    with pytest.raises(Backpressure):
        f.submit("t", "get", np.array([1.0]))
    with pytest.raises(Backpressure):
        f.submit("t", "insert", np.array([1.0]), np.array([0]))
    assert int(f.metrics.counter("frontend.probe_failures").value) == 0
    f.pump()  # empty queue + UNAVAILABLE -> probe (still down)
    assert int(f.metrics.counter("frontend.probe_failures").value) == 1
    assert f.health() == UNAVAILABLE
    svc.fail_reads = False
    f.pump()  # probe succeeds -> ladder climbs back up
    assert f.health() == HEALTHY
    req = f.submit("t", "get", np.array([1.0]))
    f.pump()
    assert req.wait(1.0) is not None


def test_injected_queue_delay_fails_deadline_not_serves_late():
    svc = _StubService()
    f = IndexFrontend(svc, FrontendConfig(request_deadline_s=5.0))
    req = f.submit("t", "get", np.array([1.0]))
    with faults.inject(
        faults.FaultSchedule({"frontend.queue.delay": 1})
    ) as s:
        served = f.pump()
    assert s.fired["frontend.queue.delay"] == 1
    assert served == 1
    with pytest.raises(DeadlineExceeded):
        req.wait(1.0)
    assert int(f.metrics.counter("frontend.deadline_exceeded").value) == 1
    assert f.serving_summary()["deadline_exceeded"] == 1
    # no delay scheduled: the same request shape is served normally
    req = f.submit("t", "get", np.array([1.0]))
    f.pump()
    assert req.wait(1.0) is not None


def test_deadline_disabled_when_none():
    svc = _StubService()
    f = IndexFrontend(svc, FrontendConfig(request_deadline_s=None))
    req = f.submit("t", "get", np.array([1.0]))
    req.enqueued_at -= 3600.0  # an hour old
    f.pump()
    assert req.wait(1.0) is not None  # served, never expired


def test_default_timeout_comes_from_config():
    svc = _StubService()
    f = IndexFrontend(svc, FrontendConfig(default_timeout_s=0.05))
    # no dispatcher running: the synchronous client times out fast
    t0 = time.perf_counter()
    with pytest.raises(TimeoutError):
        f.get("t", [1.0])
    assert time.perf_counter() - t0 < 5.0  # not the old hard-coded 60s
    # explicit timeout still wins over the config default
    with pytest.raises(TimeoutError):
        f.contains("t", [1.0], timeout=0.01)


def test_retry_with_backoff_retries_then_succeeds():
    calls = {"n": 0}
    delays = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise Backpressure("full")
        return "ok"

    out = retry_with_backoff(
        flaky, attempts=5, base_s=0.01, cap_s=0.5,
        rng=random.Random(0), sleep=delays.append,
    )
    assert out == "ok"
    assert calls["n"] == 3
    assert len(delays) == 2
    assert delays[1] > delays[0]  # exponential growth
    assert all(d <= 0.5 * 1.5 for d in delays)  # capped (plus jitter)


def test_retry_with_backoff_exhausts_and_raises_last():
    delays = []

    def always():
        raise Backpressure("full")

    with pytest.raises(Backpressure):
        retry_with_backoff(always, attempts=3, base_s=0.001,
                           rng=random.Random(1), sleep=delays.append)
    assert len(delays) == 2  # no sleep after the last attempt

    with pytest.raises(DeadlineExceeded):
        # non-retryable errors propagate immediately
        retry_with_backoff(
            lambda: (_ for _ in ()).throw(DeadlineExceeded("late")),
            attempts=3, sleep=delays.append,
        )
    assert len(delays) == 2  # no extra sleeps


def test_frontend_dispatcher_thread_probes_while_unavailable():
    svc = _StubService()
    svc.fail_reads = True
    f = IndexFrontend(svc, FrontendConfig(unavailable_after=1))
    with f:
        with pytest.raises(RuntimeError):
            f.get("t", [1.0], timeout=5.0)
        deadline = time.time() + 5.0
        while f.health() != UNAVAILABLE and time.time() < deadline:
            time.sleep(0.01)
        assert f.health() == UNAVAILABLE
        svc.fail_reads = False
        deadline = time.time() + 5.0
        while f.health() != HEALTHY and time.time() < deadline:
            time.sleep(0.01)
        assert f.health() == HEALTHY  # background probe recovered
        assert f.contains("t", [1.0]) is not None
