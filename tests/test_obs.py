"""Observability-plane tests: metrics registry, stats parity, trace.

Four contracts pinned here:

  1. Registry primitives are exact — counters under thread contention,
     histograms' percentile estimates bounded by what was observed,
     legacy ``stats`` dict semantics (ints stay ints) preserved by the
     StatsView facade.
  2. Instrumentation is COMPLETE: every public op in
     ``INSTRUMENTED_OPS`` records a latency histogram on BOTH service
     front ends, and histogram sample counts equal op call counts — an
     op added without wiring its histogram fails here (tier-1).
  3. Counters are monotone across structural events (rebalance retires
     shards and the router; compaction stalls and recovers) — the
     aggregate numbers in ``stats_summary`` never go backwards.
  4. Program spans land on the profiler's host plane, one line per
     thread, with the nesting the plane promises (frontend round ->
     service op -> its steps -> dispatch), and only while the tracer is
     enabled.
"""

import glob
import json
import os
import re
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.index_service import (
    IndexService,
    ServiceConfig,
    ShardedIndexService,
)
from repro.index_service.service import INSTRUMENTED_OPS
from repro.obs import (
    MetricsRegistry,
    StatsView,
    Tracer,
)
from repro.obs import trace as obs_trace
from repro.serve import IndexFrontend
from repro.obs.export import op_latency_rows, prometheus_text
from repro.obs.metrics import DEFAULT_LATENCY_EDGES


def _lattice(n=2_000):
    return np.arange(2, n + 2, dtype=np.float64) * 1024.0


def _drive_all_ops(svc, base, rounds=3):
    """One call (per round) of every instrumented public op."""
    for r in range(rounds):
        svc.get(float(base[5 + r]))
        svc.contains(float(base[6 + r]))
        svc.range_lookup(float(base[3]), float(base[60]))
        svc.insert(np.array([float(base[7 + r]) + 512.0 + r]))
        svc.delete(np.array([float(base[200 + r])]))
        for _ in svc.scan(float(base[3]), float(base[90]), 64):
            pass
        np.asarray(svc.lookup_batch(base[:16]))
        np.asarray(svc.scan_batch(float(base[3]), float(base[90]), 64))


# ---- registry primitives --------------------------------------------------

def test_counter_threaded_exact():
    reg = MetricsRegistry("t")
    ctr = reg.counter("hits")
    n_threads, per = 8, 5_000

    def bump():
        for _ in range(per):
            ctr.add(1)

    ts = [threading.Thread(target=bump) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert ctr.value == n_threads * per
    assert isinstance(ctr.value, int)  # int-in, int-out (legacy stats)


def test_histogram_percentiles_bounded_by_observations():
    reg = MetricsRegistry("t")
    h = reg.histogram("lat")
    obs = [1e-6, 5e-6, 1e-5, 1e-4, 1e-3, 2e-3, 0.5]
    for v in obs:
        h.observe(v)
    assert h.count == len(obs)
    for q in (50, 90, 99):
        est = h.percentile(q)
        assert min(obs) <= est <= max(obs)
    ps = h.percentiles()
    assert set(ps) == {"p50", "p90", "p99"}
    assert ps["p50"] <= ps["p90"] <= ps["p99"]
    # single observation: every percentile clamps to the exact value
    h1 = reg.histogram("one")
    h1.observe(3.3e-4)
    assert h1.percentile(50) == pytest.approx(3.3e-4)
    assert h1.percentile(99) == pytest.approx(3.3e-4)


def test_histogram_edges_cover_ns_to_hours():
    assert DEFAULT_LATENCY_EDGES[0] <= 1e-7
    assert DEFAULT_LATENCY_EDGES[-1] >= 1e4
    d = np.diff(np.log10(DEFAULT_LATENCY_EDGES))
    assert np.allclose(d, 0.2)  # 5 buckets per decade


def test_stats_view_is_a_legacy_dict():
    reg = MetricsRegistry("t")
    s = StatsView(reg, "svc", ("gets", "get_s"))
    assert s["gets"] == 0
    s["gets"] += 3
    s["get_s"] += 0.25
    assert s["gets"] == 3 and isinstance(s["gets"], int)
    assert s["get_s"] == pytest.approx(0.25)
    assert dict(s)["gets"] == 3
    assert set(s) >= {"gets", "get_s"}
    # the same numbers are visible as registry counters
    assert reg.counter("svc.gets").value == 3


def test_registry_type_collision_raises():
    reg = MetricsRegistry("t")
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


# ---- completeness + parity (tier-1 contract) ------------------------------

@pytest.mark.parametrize("make", [
    pytest.param(
        lambda base: IndexService(
            base, ServiceConfig(delta_capacity=256),
            vals=np.arange(base.size, dtype=np.int64),
        ), id="index_service"),
    pytest.param(
        lambda base: ShardedIndexService(
            base, ServiceConfig(delta_capacity=256, num_shards=4),
            vals=np.arange(base.size, dtype=np.int64),
        ), id="sharded_k4"),
])
def test_every_public_op_has_a_latency_histogram(make):
    base = _lattice()
    svc = make(base)
    _drive_all_ops(svc, base, rounds=1)
    for op in INSTRUMENTED_OPS:
        h = svc.metrics.get(f"op.{op}.latency_s")
        assert h is not None, f"op.{op}.latency_s never registered"
        assert h.count >= 1, f"op.{op}.latency_s recorded no samples"
        # and the op shows up in the benchmark-artifact rows
    rows = op_latency_rows(svc.metrics)
    assert set(INSTRUMENTED_OPS) <= set(rows)
    for op in INSTRUMENTED_OPS:
        assert rows[op]["count"] >= 1
        assert rows[op]["p50_us"] <= rows[op]["p99_us"]


def test_histogram_counts_equal_op_counts():
    base = _lattice()
    svc = IndexService(base, ServiceConfig(delta_capacity=256))
    rounds = 4
    _drive_all_ops(svc, base, rounds=rounds)
    for op in ("get", "contains", "range", "insert", "delete",
               "lookup_batch", "scan_batch", "scan"):
        h = svc.metrics.get(f"op.{op}.latency_s")
        assert h.count == rounds, f"op.{op}: {h.count} != {rounds}"
    # per-element stats counters scale with batch size, not call count
    assert svc.stats["lookup_batch"] == rounds * 16


def test_unsharded_vs_sharded_k1_stats_parity():
    base = _lattice()
    flat = IndexService(
        base, ServiceConfig(delta_capacity=256),
        vals=np.arange(base.size, dtype=np.int64),
    )
    k1 = ShardedIndexService(
        base, ServiceConfig(delta_capacity=256, num_shards=1),
        vals=np.arange(base.size, dtype=np.int64),
    )
    _drive_all_ops(flat, base)
    _drive_all_ops(k1, base)
    for key in ("get", "get_hits", "contains", "contains_hits", "range",
                "insert", "delete", "scan", "scan_pages", "scan_rows",
                "lookup_batch", "scan_batch"):
        assert flat.stats[key] == k1.stats[key], key
    for op in INSTRUMENTED_OPS:
        a = flat.metrics.get(f"op.{op}.latency_s").count
        b = k1.metrics.get(f"op.{op}.latency_s").count
        assert a == b, f"op.{op}: {a} != {b}"


def test_shards_do_not_share_registries():
    base = _lattice(4_000)
    svc = ShardedIndexService(
        base, ServiceConfig(delta_capacity=256, num_shards=4))
    svc.get(float(base[7]))
    # the front-end op lands ONCE in the service registry, not once
    # per shard registry
    assert svc.metrics.get("op.get.latency_s").count == 1
    inner = sum(
        s.metrics.get("op.get.latency_s").count
        for s in svc._shards
        if s.metrics.get("op.get.latency_s") is not None
    )
    assert inner == 0  # sharded gets ride lookup_batch, not shard.get


# ---- monotonicity across structural events --------------------------------

def test_counters_monotone_across_rebalance():
    base = _lattice(4_000)
    svc = ShardedIndexService(
        base, ServiceConfig(delta_capacity=256, num_shards=4))
    rng = np.random.default_rng(3)
    _drive_all_ops(svc, base)
    before = svc.stats_summary()
    svc.rebalance()
    svc.insert(rng.integers(1, 1 << 40, 64).astype(np.float64))
    _drive_all_ops(svc, base)
    after = svc.stats_summary()
    for key in ("insert_applied", "delete_applied", "compactions",
                "rebalances"):
        assert after[key] >= before[key], key
    for op in ("get", "contains", "range", "scan"):
        assert after[op]["count"] > before[op]["count"], op
    r0, r1 = before["router"], after["router"]
    assert r1["routed"] > r0["routed"]
    assert r1["refits"] >= r0["refits"] + 1
    assert r1["model_hit_rate"] is not None
    assert 0.0 <= r1["model_hit_rate"] <= 1.0
    assert r1["live_count_skew"] >= 1.0


def test_router_health_survives_router_retirement():
    base = _lattice(4_000)
    svc = ShardedIndexService(
        base, ServiceConfig(delta_capacity=256, num_shards=4))
    svc.lookup_batch(base[:256])
    routed_before = svc.stats_summary()["router"]["routed"]
    assert routed_before >= 256
    svc.rebalance()  # retires the router (fresh stats dict)
    assert svc.router.stats["routed"] == 0
    # ...but the service-lifetime aggregate kept the history
    assert svc.stats_summary()["router"]["routed"] >= routed_before


def test_compaction_counters_on_stall_and_recovery():
    base = np.arange(2, 34, dtype=np.float64) * 1024.0
    svc = IndexService(base, ServiceConfig(delta_capacity=2048))
    svc.delete(base)  # drains everything: compaction must stall
    svc.flush()  # stalls, does not raise
    assert svc.stats["compact_stalls"] >= 1
    assert svc.metrics.counter("delta.freezes").value >= 1
    stalls = svc.stats["compact_stalls"]
    svc.insert(np.arange(1, 65, dtype=np.float64) * 512.0 + 128.0)
    svc.flush()  # headroom restored: compacts cleanly
    assert svc.stats["compactions"] >= 1
    assert svc.metrics.counter("snapshot.swaps").value >= 1
    assert svc.stats["compact_stalls"] >= stalls  # never reset


# ---- plane cache hit/miss -------------------------------------------------

def test_plane_cache_hit_miss_counters():
    base = _lattice()
    svc = IndexService(base, ServiceConfig(delta_capacity=256))
    svc.lookup_batch(base[:8])   # cold: miss
    svc.lookup_batch(base[:8])   # warm: hit
    hits = svc.metrics.counter("plane.lookup.hit").value
    misses = svc.metrics.counter("plane.lookup.miss").value
    assert misses >= 1 and hits >= 1
    svc.insert(np.array([float(base[3]) + 512.0]))
    svc.lookup_batch(base[:8])   # invalidated: miss again
    assert svc.metrics.counter("plane.lookup.miss").value > misses


# ---- tracing --------------------------------------------------------------

def _spans(log_dir, prefixes=("frontend.", "service.", "dispatch.")):
    """[(name, start_ns, end_ns, line, stats)] of the program's spans in
    the one profiler trace under ``log_dir``."""
    import jax

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, i,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith(prefixes)]
    return out


def _traced(tmp_path, body, enable=True, **spans_kw):
    """Run ``body`` under a profiler trace (the program's spans on while
    ``enable``) and return the spans that reached it."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    if enable:
        obs_trace.TRACER.enable()
    try:
        body()
    finally:
        obs_trace.TRACER.disable()
        jax.profiler.stop_trace()
    return _spans(str(tmp_path), **spans_kw)


def _inside(inner, outer):
    return (inner[3] == outer[3] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


def _pumped_gets(fe, base, n=3):
    for c in range(n):
        fe.submit(f"t{c}", "get", base[c * 5: c * 5 + 2])
    fe.pump()


def test_disabled_tracer_records_nothing(monkeypatch):
    # disabled, a span is the shared null object and no annotation is
    # ever built, not even for an instant
    def no_annotation(*a, **k):
        raise AssertionError("annotation opened while disabled")

    monkeypatch.setattr(obs_trace, "TraceAnnotation", no_annotation)
    tr = Tracer()
    assert not tr.enabled
    with tr.span("x", cat="t", n=1) as sp:
        assert sp is tr.span("y")
    tr.instant("z")
    assert obs_trace.span("service.get") is tr.span("x")
    obs_trace.instant("frontend.health")


def test_program_spans_reach_the_profiler_trace(tmp_path):
    base = _lattice()
    fe = IndexFrontend(IndexService(base, ServiceConfig(delta_capacity=256)))
    _pumped_gets(fe, base)  # compile outside the trace
    spans = _traced(tmp_path, lambda: _pumped_gets(fe, base))
    (rnd,) = [s for s in spans if s[0] == "frontend.round"]
    assert rnd[4]["round"] == 2 and rnd[4]["requests"] == 3
    (get,) = [s for s in spans if s[0] == "service.get"]
    assert _inside(get, rnd)
    steps = {s[0]: s for s in spans
             if s[0] in ("service.prepare", "service.dispatch",
                         "service.readback", "service.refine")}
    assert len(steps) == 4
    assert all(_inside(s, get) for s in steps.values())
    # the steps follow each other in the order the work is done
    order = sorted(steps.values(), key=lambda s: s[1])
    assert [s[0] for s in order] == ["service.prepare", "service.dispatch",
                                     "service.readback", "service.refine"]
    (disp,) = [s for s in spans if s[0].startswith("dispatch.")]
    assert _inside(disp, steps["service.dispatch"])


def test_disabled_tracer_spans_stay_out_of_the_trace(tmp_path):
    base = _lattice()
    fe = IndexFrontend(IndexService(base, ServiceConfig(delta_capacity=256)))
    _pumped_gets(fe, base)
    assert _traced(tmp_path, lambda: _pumped_gets(fe, base),
                   enable=False) == []


def test_scan_batch_spans_split_prepare_and_dispatch(tmp_path):
    base = _lattice()
    svc = IndexService(base, ServiceConfig(delta_capacity=256),
                       vals=np.arange(base.size))
    lo, hi = float(base[3]), float(base[90])
    svc.scan_batch(lo, hi, 64)
    spans = _traced(tmp_path, lambda: svc.scan_batch(lo, hi, 64))
    (scan,) = [s for s in spans if s[0] == "service.scan_batch"]
    (prep,) = [s for s in spans if s[0] == "service.prepare"]
    (disp,) = [s for s in spans if s[0] == "service.dispatch"]
    (op,) = [s for s in spans if s[0] == "dispatch.rmi_scan_range"]
    assert _inside(prep, scan) and _inside(disp, scan)
    assert prep[2] <= disp[1] and _inside(op, disp)


def test_insert_then_scan_updates_the_plane_in_a_span(tmp_path):
    base = _lattice()
    svc = IndexService(base, ServiceConfig(delta_capacity=256),
                       vals=np.arange(base.size))
    lo, hi = float(base[3]), float(base[90])
    svc.insert(np.array([float(base[5]) + 512.0]), np.array([-1]))
    svc.scan_batch(lo, hi, 64)   # full build and compile outside the trace

    def insert_then_scan():
        svc.insert(np.array([float(base[7]) + 512.0]), np.array([-2]))
        svc.scan_batch(lo, hi, 64)
    spans = _traced(tmp_path, insert_then_scan,
                    prefixes=("plane.", "scan.", "service."))
    (update,) = [s for s in spans if s[0] == "plane.scan.update"]
    (prep,) = [s for s in spans if s[0] == "service.prepare"]
    assert _inside(update, prep) and update[4]["staged"] == 2
    # the snapshot was kept: no rebuild in the trace
    assert not [s for s in spans if s[0] == "scan.pack_slab"]
    assert svc.metrics.counter("plane.scan.updates").value == 1
    assert svc.metrics.counter("plane.scan.rebuilds").value == 1


def test_idle_dispatcher_waits_in_a_span_and_instants_are_points(tmp_path):
    fe = IndexFrontend(IndexService(_lattice(),
                                    ServiceConfig(delta_capacity=256)))

    def idle():
        fe.start()
        time.sleep(0.3)
        obs_trace.instant("frontend.health", state="HEALTHY")
        fe.stop()

    spans = _traced(tmp_path, idle)
    waits = [s for s in spans if s[0] == "frontend.wait"]
    assert waits and all(s[2] > s[1] for s in waits)
    (health,) = [s for s in spans if s[0] == "frontend.health"]
    # an instant opens and closes at once: a point beside the waits
    assert health[2] - health[1] < 1e6 and health[4]["state"] == "HEALTHY"
    # the dispatcher's waits share one line, not the caller's
    assert {s[3] for s in waits} != {health[3]}


def _lookup_program(svc, base):
    from repro.index_service import snapshot

    snap, _, _, dk, dp = svc._capture()
    idx = snap.index
    return snapshot._xla_merged.lower(
        jnp.asarray(snap.keys.normalize(base[:64])), dk, dp,
        idx.as_pytree(), snap._device_base()[0], strategy="binary",
        n=idx.n, num_leaves=idx.num_leaves, max_window=idx.max_window)


def _scan_program(svc, base):
    from repro.kernels import ops

    snap, slab, _ = svc._scan_plane_cached()
    bounds = jnp.asarray(snap.keys.normalize(base[[3, 90]]), jnp.float32)
    return ops._scan_range_jit.lower(
        bounds, *snap._device_base(), *slab,
        page_size=64, max_pages=2, use_kernel=False, interpret=None)


@pytest.mark.parametrize("program,scopes", [
    (_lookup_program, ("base_search", "delta_search", "prefix_gather")),
    (_scan_program, ("endpoint_search", "delta_search", "row_gather")),
])
def test_device_programs_name_their_steps(program, scopes):
    """Each step of the served device programs carries a named scope in
    its operations' metadata, the name a device trace shows."""
    base = _lattice()
    svc = IndexService(base, ServiceConfig(delta_capacity=256),
                       vals=np.arange(base.size))
    hlo = program(svc, base).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in scopes:
        assert any(f"/{scope}/" in n for n in names), scope


# ---- exporters ------------------------------------------------------------

def test_prometheus_text_exposition():
    reg = MetricsRegistry("exp")
    reg.counter("svc.gets").add(7)
    reg.gauge("fill").set(0.5)
    h = reg.histogram("op.get.latency_s")
    for v in (1e-5, 2e-4, 3e-3):
        h.observe(v)
    text = prometheus_text(reg)
    assert "# TYPE svc_gets counter" in text
    assert "svc_gets 7" in text
    assert "# TYPE op_get_latency_s histogram" in text
    assert 'op_get_latency_s_bucket{le="+Inf"} 3' in text
    assert "op_get_latency_s_count 3" in text
    # cumulative bucket counts never decrease
    counts = [
        int(line.rsplit(" ", 1)[1])
        for line in text.splitlines()
        if line.startswith("op_get_latency_s_bucket")
    ]
    assert counts == sorted(counts)


def test_registry_snapshot_roundtrips_to_json():
    base = _lattice()
    svc = IndexService(base, ServiceConfig(delta_capacity=256))
    _drive_all_ops(svc, base, rounds=1)
    snap = json.loads(json.dumps(svc.metrics.snapshot()))
    assert snap["counters"]["svc.get"] == 1
    assert snap["histograms"]["op.get.latency_s"]["count"] == 1
