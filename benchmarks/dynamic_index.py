"""Dynamic-index workload: the writable index service under writes.

Four questions, all ns/lookup CSV rows:

  1. What does the delta buffer cost readers?  Sweep the staged-write
     fill 0-100% of capacity and time the jitted merged lookup (RMI
     over the base + one fused branchless search over the delta)
     against the static RMI baseline on the same key set.  The paper's
     static numbers are the floor; the service must stay within ~2x of
     it at 10% fill to be a serious §3.3 answer.
  2. Does FUSING the delta search into the RMI kernel pay?  At each
     fill fraction, the two-dispatch merged lookup (`binary`: XLA RMI
     search + separate delta op) races `pallas_fused` (one pallas_call
     covering both) and `xla_fused` (the one-XLA-program fallback).
     On CPU the kernel runs in interpret mode, so its absolute numbers
     are NOT meaningful there — the row records the dispatch-count
     comparison for TPU runs, where fusion removes an HBM round-trip.
  3. What does a mixed 90/10 read/write stream cost end to end
     (staging + merged lookups + any compactions amortized in)?
  4. Does compaction restore the static rate (post-compaction row)?
  5. What does sharding cost readers?  K-shard sweep (per-shard deltas
     behind the learned router, one stacked merged-lookup dispatch) vs
     the K=1 baseline — `sharded_sweep`, also runnable alone via
     LIX_SHARDED_ONLY=1 (the CI benchmark-smoke job does).
  6. What do range *scans* cost (pages/s) as the delta fills, and does
     the paged iterator beat naive re-merge-then-slice?  `scan_sweep`
     drains a fixed row range through `IndexService.scan` at several
     delta fill fractions and races materializing the whole merged
     array per query — also runnable alone via LIX_SCAN_ONLY=1 (the
     CI benchmark-smoke job does).
  7. What does the multi-tenant serving tier sustain?  `serve_sweep`
     drives C concurrent client threads of mixed gets/contains/scans/
     inserts through the coalescing `IndexFrontend`, records QPS and
     end-to-end p50/p99 per client count against a p99 SLO
     (LIX_SERVE_SLO_MS), spot-checks read-your-writes after every
     acknowledged insert, and pins the coalesced-read dispatch count —
     also runnable alone via LIX_SERVE_ONLY=1 (the CI benchmark-smoke
     job does).
  8. What does a crash cost, and how bad is the worst write stall?
     `chaos_sweep` checkpoints a churned K-shard service, drops every
     in-memory structure, restores from disk and times recovery to the
     first bit-exact read; then it measures worst-case single-insert
     latency under the leveled compactor (max_delta_levels 1 vs 4) so
     the bounded-write-stall claim is a recorded number, not a test
     assertion only — also runnable alone via LIX_CHAOS_ONLY=1 (the CI
     benchmark-smoke job does).
"""

from __future__ import annotations

import json
import os

import numpy as np
import jax.numpy as jnp

from benchmarks.common import BENCH_LOOKUPS, BENCH_N, emit, ns_per_item
from repro.compile_cache import enable_compile_cache
from repro.core import RMIConfig, build_rmi, compile_lookup, make_keyset
from repro.data import gen_weblogs
from repro.index_service import (
    IndexService,
    ServiceConfig,
    ShardedIndexService,
)
from repro.kernels import ops as kernels_ops
from repro.kernels.rmi_lookup import default_interpret
from repro.obs import TRACER
from repro.obs.export import op_latency_rows

DELTA_CAPACITY = 4096
# interpret-mode pallas is orders of magnitude slower than compiled
# XLA; keep the fused-vs-two-dispatch comparison batch bounded on CPU
FUSED_BATCH = 4096

# machine-readable mirror of the CSV rows: per-sweep median latency,
# dispatch counts, and speedups, merged into BENCH_dynamic_index.json
# at exit (standalone LIX_*_ONLY runs merge into the same file, so the
# CI bench-smoke steps accumulate one artifact)
JSON_PATH = os.environ.get("LIX_BENCH_JSON", "BENCH_dynamic_index.json")
# profiler traces (program spans beside the device lanes), one run
# directory per process, each with a perfetto_trace.json.gz
TRACE_DIR = os.environ.get("LIX_TRACE_DIR", "BENCH_dynamic_index_trace")
_JSON_ROWS: list = []
# observability sections, merged into the artifact beside the rows:
# per-service op-latency percentiles keyed by sweep label, the process
# dispatch/attribution ledger keyed by entrypoint, and the serving-tier
# QPS/SLO summaries keyed by client count
_OBS_LATENCY: dict = {}
_SERVING: dict = {}
_CHAOS: dict = {}
_FAULTS: dict = {}
_RUN_LABEL = "main"


def record_latency(label: str, registry) -> None:
    rows = op_latency_rows(registry)
    if rows:
        _OBS_LATENCY[label] = rows


def record(name: str, us_per_item: float, derived: str = "", **extra):
    """CSV row + JSON row in one call."""
    emit(name, us_per_item, derived)
    _JSON_ROWS.append({
        "name": name,
        "median_us_per_item": round(float(us_per_item), 4),
        "derived": derived,
        **extra,
    })


def write_json() -> None:
    data = {
        "bench": "dynamic_index",
        "n": BENCH_N,
        "lookups": BENCH_LOOKUPS,
        "interpret": default_interpret(),
        "rows": [],
        "observability": {"op_latency": {}, "dispatch": {}},
    }
    if os.path.exists(JSON_PATH):
        try:
            with open(JSON_PATH) as f:
                old = json.load(f)
            fresh = {r["name"] for r in _JSON_ROWS}
            data["rows"] = [
                r for r in old.get("rows", []) if r["name"] not in fresh
            ]
            old_obs = old.get("observability", {})
            data["observability"]["op_latency"] = {
                k: v for k, v in old_obs.get("op_latency", {}).items()
                if k not in _OBS_LATENCY
            }
            data["observability"]["dispatch"] = {
                k: v for k, v in old_obs.get("dispatch", {}).items()
                if k != _RUN_LABEL
            }
            data["observability"]["serving"] = {
                k: v for k, v in old_obs.get("serving", {}).items()
                if k not in _SERVING
            }
            data["observability"]["chaos"] = {
                k: v for k, v in old_obs.get("chaos", {}).items()
                if k not in _CHAOS
            }
            data["observability"]["faults"] = {
                k: v for k, v in old_obs.get("faults", {}).items()
                if k not in _FAULTS
            }
        except (OSError, ValueError, KeyError):
            pass
    data["rows"] += _JSON_ROWS
    data["observability"]["op_latency"].update(_OBS_LATENCY)
    if _SERVING:
        data["observability"].setdefault("serving", {}).update(_SERVING)
    if _CHAOS:
        data["observability"].setdefault("chaos", {}).update(_CHAOS)
    if _FAULTS:
        data["observability"].setdefault("faults", {}).update(_FAULTS)
    data["observability"]["dispatch"][_RUN_LABEL] = (
        kernels_ops.dispatch_summary()
    )
    data["observability"]["trace_dir"] = TRACE_DIR
    with open(JSON_PATH, "w") as f:
        json.dump(data, f, indent=2)
    print(f"wrote {JSON_PATH} ({len(data['rows'])} rows)", flush=True)


def dispatches(fn) -> int:
    """Device-op entries one call of ``fn`` costs (post-warmup)."""
    import jax

    jax.block_until_ready(fn())
    with kernels_ops.count_dispatches() as n:
        jax.block_until_ready(fn())
        return n()


def sharded_sweep(raw=None, ks=None) -> None:
    """Question 5: what does sharding the write path cost readers?
    K-shard service (per-shard delta + compaction, learned router) vs
    the K=1 baseline on the same key set and op stream: one-dispatch
    stacked merged lookup (ns/op) and a mixed 90/10 stream.  On CPU the
    shard axis is host-simulated unless XLA exposes multiple devices
    (CI forces 8 via --xla_force_host_platform_device_count)."""
    import jax

    rng = np.random.default_rng(1)
    if raw is None:  # standalone (LIX_SHARDED_ONLY) path
        raw = gen_weblogs(BENCH_N)
        ks = make_keyset(raw)
    b = min(BENCH_LOOKUPS, ks.n)
    sample = raw[rng.choice(ks.n, b)]
    fresh = np.setdiff1d(
        rng.integers(0, 1 << 52, DELTA_CAPACITY).astype(np.float64), ks.raw
    )
    for k in (1, 4, 8):
        svc = ShardedIndexService(ks.raw, ServiceConfig(
            delta_capacity=DELTA_CAPACITY, num_shards=k))
        svc.insert(fresh)  # staged writes spread over the K deltas
        t = ns_per_item(
            lambda q: jax.block_until_ready(svc.lookup_batch(q)),
            sample, batch=b,
        )
        d = dispatches(lambda: svc.lookup_batch(sample))
        summary = svc.stats_summary()
        record(
            f"dynamic_index/sharded_k{k}",
            t / 1e3,
            f"devices={len(jax.devices())};"
            f"router_hit={svc.router.model_hit_rate:.3f};"
            f"compactions={summary['compactions']};dispatches={d}",
            dispatches=d,
        )
        # one-dispatch stacked scan over all touched shards
        lo, hi = float(ks.raw[ks.n // 8]), float(ks.raw[(7 * ks.n) // 8])
        page = 512
        t_s = ns_per_item(
            lambda: jax.block_until_ready(svc.scan_batch(lo, hi, page)),
            batch=1,
        )
        d_s = dispatches(lambda: svc.scan_batch(lo, hi, page))
        record(
            f"dynamic_index/sharded_scan_k{k}",
            t_s / 1e3,
            f"page={page};dispatches={d_s};interpret={default_interpret()}",
            dispatches=d_s,
        )
        record_latency(f"sharded_k{k}", svc.metrics)


def scan_sweep(raw=None, ks=None) -> None:
    """Question 6: paged merged scans vs naive re-merge-then-slice.

    At each delta fill fraction (staged inserts + tombstones), drain a
    fixed key range through the paged scan iterator and through the
    naive baseline that materializes the whole merged live array per
    query (tombstone filter + concatenate + argsort) and slices it —
    what a reader without the scan subsystem would do.  Also times the
    one-dispatch device scan (`scan_batch`; interpret-mode numbers off
    TPU are not meaningful, same caveat as the lookup kernels)."""
    import time

    import jax

    rng = np.random.default_rng(2)
    if raw is None:  # standalone (LIX_SCAN_ONLY) path
        raw = gen_weblogs(BENCH_N)
        ks = make_keyset(raw)
    n = ks.n
    page = 512
    span = max(2 * page, min(n // 4, 50_000))
    lo, hi = float(ks.raw[n // 8]), float(ks.raw[n // 8 + span])
    svc = IndexService(
        ks.raw, ServiceConfig(delta_capacity=DELTA_CAPACITY),
        vals=np.arange(n, dtype=np.int64),
    )
    fresh = iter(np.setdiff1d(
        rng.integers(0, 1 << 52, 3 * DELTA_CAPACITY).astype(np.float64),
        ks.raw,
    ))

    def t_best(fn, repeats=3):
        best = np.inf
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    def drain():
        rows = 0
        for pg in svc.scan(lo, hi, page):
            rows += pg.count
        return rows

    def naive():
        snap, frozen, active = svc._state()
        keys, vals = snap.keys.raw, snap.vals
        for level in (frozen, active):
            if level is None or len(level) == 0:
                continue
            keep = np.ones(keys.size, bool)
            if level.del_keys.size:
                i = np.clip(np.searchsorted(level.del_keys, keys), 0,
                            level.del_keys.size - 1)
                keep = level.del_keys[i] != keys
            keys = np.concatenate([keys[keep], level.ins_keys])
            vals = np.concatenate([vals[keep], level.ins_vals])
            order = np.argsort(keys, kind="stable")
            keys, vals = keys[order], vals[order]
        r0, r1 = np.searchsorted(keys, [lo, hi])
        return keys[r0:r1], vals[r0:r1]

    filled = 0
    for pct in (0, 10, 50, 100):
        target = int(DELTA_CAPACITY * pct / 100)
        if target > filled:
            add = target - filled
            # 3/4 staged inserts, 1/4 tombstones: scans must both
            # weave and elide
            svc.insert(np.array([next(fresh) for _ in range(add - add // 4)]))
            live = svc._mgr.current().keys.raw
            svc.delete(rng.choice(live, add // 4, replace=False))
            filled = target
        rows = drain()
        pages = -(-rows // page)
        t_scan = t_best(drain)
        t_naive = t_best(lambda: naive())
        record(
            f"dynamic_index/scan_fill_{pct}pct",
            t_scan / pages * 1e6,
            f"rows={rows};pages_per_s={pages / t_scan:.0f};"
            f"rows_per_s={rows / t_scan:.0f};"
            f"naive_remerge_ms={t_naive * 1e3:.3f};"
            f"scan_vs_naive={t_naive / t_scan:.1f}x",
            speedup_vs_naive=round(t_naive / t_scan, 2),
        )
    # one-dispatch fused device scan at the final fill vs the PR 4
    # path (host rank round-trip + per-call re-pack + rank-addressed
    # page op) on the same service state.  Kernel caveat: off TPU the
    # pallas path interprets; the XLA fallback is the honest CPU
    # number, so use the configured strategy's default.
    pages_n = max(1, -(-span // page))
    t_dev = t_best(lambda: jax.block_until_ready(
        svc.scan_batch(lo, hi, page)
    ))
    t_pr4 = t_best(lambda: jax.block_until_ready(
        _scan_batch_pr4(svc, lo, hi, page)
    ))
    d_new = dispatches(lambda: svc.scan_batch(lo, hi, page))
    d_pr4 = dispatches(lambda: _scan_batch_pr4(svc, lo, hi, page))
    record(
        "dynamic_index/scan_device_batch",
        t_dev / pages_n * 1e6,
        f"pages={pages_n};interpret={default_interpret()};"
        f"pr4_us_per_page={t_pr4 / pages_n * 1e6:.3f};"
        f"fused_vs_pr4={t_pr4 / t_dev:.1f}x;"
        f"dispatches={d_new};pr4_dispatches={d_pr4}",
        dispatches=d_new,
        pr4_dispatches=d_pr4,
        speedup_vs_pr4=round(t_pr4 / t_dev, 2),
    )
    record_latency("scan_sweep", svc.metrics)


def _scan_batch_pr4(svc: IndexService, lo, hi, page_size):
    """The PR 4 scan_batch read path, preserved as the benchmark
    baseline: pin + collapse the delta PER CALL, rank the endpoints on
    the host, re-pack/upload the delta arrays, then dispatch the
    rank-addressed page op over host-computed starts."""
    from repro.index_service.scan import device_scan_plan, pin_view

    with svc._lock:
        snap = svc._mgr.current()
        view = pin_view(snap, svc._frozen, svc._active)
    r0, r1 = (int(r) for r in view.rank(np.array([lo, hi])))
    if hi < lo:
        r1 = r0
    ins, ivals, dpos = device_scan_plan(view, snap.keys.normalize)
    starts = np.arange(r0, max(r1, r0 + 1), page_size, np.int32)
    fn = snap.scan_page_fn(svc.config.strategy, page_size)
    return fn(
        jnp.asarray(starts), jnp.asarray(ins), jnp.asarray(ivals),
        jnp.asarray(dpos), np.int32(r1),
    )


def serve_sweep(raw=None, ks=None) -> None:
    """Question 7: sustained mixed multi-client throughput through the
    coalescing serving tier (`repro.serve.IndexFrontend`).  C client
    threads each drive a ~80/10/5/5 get/contains/scan/insert stream
    (inserts from disjoint per-client fresh-key pools, read-your-writes
    spot-checked after every acknowledged insert); the frontend
    coalesces each round into the one-dispatch batched service ops.
    Records QPS + end-to-end p50/p99 per client count and a p99 SLO
    verdict (LIX_SERVE_SLO_MS, generous by default — the gate is
    against pathological serialization, not CPU absolute numbers),
    plus a pump-mode dispatch window proving N coalesced point reads
    still cost ONE device dispatch."""
    import threading
    import time

    from repro.serve import FrontendConfig, IndexFrontend

    rng = np.random.default_rng(7)
    if raw is None:  # standalone (LIX_SERVE_ONLY) path
        raw = gen_weblogs(BENCH_N)
        ks = make_keyset(raw)
    n = ks.n
    slo_ms = float(os.environ.get("LIX_SERVE_SLO_MS", "2000"))
    iters = int(os.environ.get("LIX_SERVE_ITERS", "30"))
    # small delta: the sweep's insert volume crosses at least one
    # freeze/snapshot-swap boundary at CI sizes
    svc = IndexService(ks.raw, ServiceConfig(delta_capacity=64))

    # dispatch discipline through the frontend: 8 clients' coalesced
    # point reads in a pump-mode window == ONE device program entry
    fe0 = IndexFrontend(svc, FrontendConfig())
    sample8 = [raw[rng.integers(0, n, 8)] for _ in range(8)]
    for keys in sample8:
        fe0.submit("warm", "get", keys)
    fe0.pump()  # warmup: compile + fill the device plane
    for c, keys in enumerate(sample8):
        fe0.submit(f"t{c}", "get", keys)
    with kernels_ops.count_dispatches() as nd:
        fe0.pump()
        coalesced_dispatches = nd()

    for clients in (2, 8, 16):
        fe = IndexFrontend(svc, FrontendConfig(slo_p99_ms=slo_ms))
        pools = np.setdiff1d(
            rng.integers(0, 1 << 52, 2 * clients * iters * 4)
            .astype(np.float64), ks.raw,
        )[: clients * iters * 4].reshape(clients, -1)
        ryw_failures: list = []

        def client(idx, fe=fe, pools=pools, ryw_failures=ryw_failures):
            crng = np.random.default_rng(1000 + idx)
            tenant = f"c{idx}"
            pool, pi = pools[idx], 0
            for _ in range(iters):
                u = crng.random()
                if u < 0.80:
                    fe.get(tenant, raw[crng.integers(0, n, 8)])
                elif u < 0.90:
                    fe.contains(tenant, raw[crng.integers(0, n, 8)])
                elif u < 0.95:
                    i = int(crng.integers(0, n - 256))
                    fe.scan(tenant, float(ks.raw[i]),
                            float(ks.raw[i + 200]), page_size=128)
                else:
                    fresh = pool[pi: pi + 4]
                    pi += 4
                    fe.insert(tenant, fresh, np.arange(fresh.size))
                    if not fe.contains(tenant, fresh).all():
                        ryw_failures.append(tenant)

        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(clients)
        ]
        t0 = time.perf_counter()
        with fe:
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        wall = time.perf_counter() - t0
        if ryw_failures:
            raise RuntimeError(
                f"read-your-writes violated for {sorted(set(ryw_failures))}"
            )
        summary = fe.serving_summary(slo_ms)
        requests = summary["requests"]
        qps = requests / wall
        label = f"serve_c{clients}"
        record(
            f"dynamic_index/{label}",
            wall / max(1, requests) * 1e6,
            f"clients={clients};qps={qps:.0f};"
            f"p99_ms={summary['worst_read_p99_ms']};"
            f"slo={'pass' if summary['slo_pass'] else 'FAIL'};"
            f"freezes={int(svc.metrics.counter('delta.freezes').value)}",
            clients=clients,
            qps=round(qps, 1),
        )
        _SERVING[label] = {
            "clients": clients,
            "requests": requests,
            "qps": round(qps, 1),
            "wall_s": round(wall, 4),
            "coalesced_get_dispatches": coalesced_dispatches,
            **summary,
        }
        record_latency(label, fe.metrics)
    record_latency("serve_service", svc.metrics)


def chaos_sweep(raw=None, ks=None) -> None:
    """Question 8: availability numbers.

    Recovery: churn a K-shard service (staged inserts + tombstones so
    the checkpoint must cover delta WAL slices, not just snapshots),
    `IndexCheckpointer.save`, drop ALL in-memory state, restore, and
    time to the first read — which must be bit-exact against the
    pre-crash answers or the row is refused.

    Write stall: identical insert bursts through max_delta_levels=1
    (historical freeze-then-merge every fill) and =4 (merge deferred
    until four levels); the worst single-burst latency is the stall the
    leveled compactor bounds, and the compaction counts prove the merge
    schedule."""
    import shutil
    import tempfile
    import time

    from repro.distributed.fault_tolerance import IndexCheckpointer

    rng = np.random.default_rng(3)
    if raw is None:  # standalone (LIX_CHAOS_ONLY) path
        raw = gen_weblogs(BENCH_N)
        ks = make_keyset(raw)

    # ---- crash recovery: checkpoint -> kill -> restore -> first read -----
    fresh = np.setdiff1d(
        rng.integers(0, 1 << 52, 3 * DELTA_CAPACITY).astype(np.float64),
        ks.raw,
    )
    for k in (1, 4, 8):
        cfg = ServiceConfig(delta_capacity=DELTA_CAPACITY, num_shards=k)
        svc = ShardedIndexService(ks.raw, cfg)
        svc.insert(fresh[: 2 * DELTA_CAPACITY])  # crosses a compaction
        svc.delete(rng.choice(ks.raw, DELTA_CAPACITY // 2, replace=False))
        svc.insert(fresh[2 * DELTA_CAPACITY :])  # leaves staged deltas
        probe = np.concatenate([
            raw[rng.integers(0, ks.n, 384)], fresh[rng.integers(0, fresh.size, 128)],
        ])
        want = svc.contains(probe)
        root = tempfile.mkdtemp(prefix="lix_chaos_")
        try:
            ckpt = IndexCheckpointer(root, keep_last=1)
            t0 = time.perf_counter()
            ckpt.save(1, svc)
            t_save = time.perf_counter() - t0
            del svc  # SIGKILL simulation
            t0 = time.perf_counter()
            back, _ = ckpt.restore(cfg)
            got = back.contains(probe)  # recovery ends at the first read
            t_rec = time.perf_counter() - t0
            bit_exact = bool(np.array_equal(got, want))
            if not bit_exact:
                raise RuntimeError(
                    f"chaos k={k}: restored service diverged from "
                    "pre-crash answers"
                )
            label = f"chaos_recovery_k{k}"
            record(
                f"dynamic_index/{label}",
                t_rec * 1e6,
                f"shards={back.num_shards};save_ms={t_save * 1e3:.1f};"
                f"recovery_ms={t_rec * 1e3:.1f};bit_exact={bit_exact}",
                recovery_ms=round(t_rec * 1e3, 2),
            )
            _CHAOS[label] = {
                "shards": int(back.num_shards),
                "save_ms": round(t_save * 1e3, 2),
                "recovery_ms": round(t_rec * 1e3, 2),
                "bit_exact": bit_exact,
            }
            record_latency(label, back.metrics)
        finally:
            shutil.rmtree(root, ignore_errors=True)

    # ---- bounded write stall: leveled vs single-level compaction ---------
    cap = 512
    burst = int(cap * 0.8)
    pool = np.setdiff1d(
        rng.integers(0, 1 << 52, 40 * burst).astype(np.float64), ks.raw
    )
    for levels in (1, 4):
        svc = IndexService(ks.raw, ServiceConfig(
            delta_capacity=cap, max_delta_levels=levels))
        lat = []
        for r in range(16):
            chunk = pool[r * burst : (r + 1) * burst]
            t0 = time.perf_counter()
            svc.insert(chunk)
            lat.append(time.perf_counter() - t0)
        worst, med = float(np.max(lat)), float(np.median(lat))
        label = f"chaos_stall_L{levels}"
        record(
            f"dynamic_index/{label}",
            worst * 1e6,
            f"median_us={med * 1e6:.1f};stall_ratio={worst / max(med, 1e-9):.1f}x;"
            f"compactions={svc.stats['compactions']};"
            f"freezes={int(svc.metrics.counter('delta.freezes').value)};"
            f"write_stalls={svc.stats['write_stalls']}",
            max_delta_levels=levels,
        )
        _CHAOS[label] = {
            "max_delta_levels": levels,
            "worst_insert_ms": round(worst * 1e3, 3),
            "median_insert_ms": round(med * 1e3, 3),
            "compactions": int(svc.stats["compactions"]),
            "write_stalls": int(svc.stats["write_stalls"]),
            "write_stall_s": round(float(svc.stats["write_stall_s"]), 4),
        }
        record_latency(label, svc.metrics)


def fault_sweep(raw=None, ks=None) -> None:
    """Question 9: the chaos matrix — read availability and recovery
    time per fault class, under the deterministic fault plane
    (`repro.faults`).  Every row is refused unless recovery is
    bit-exact, and the compactor-crash row additionally demands read
    availability >= 99% while the supervisor is restarting the worker
    (`check_obs_artifact.py` enforces both).  Also runnable alone via
    LIX_FAULTS_ONLY=1 (the CI bench-smoke job does).

    Classes:
      ckpt_torn        — the NEWEST checkpoint is torn after publish;
                         restore must quarantine it and fall back to
                         the previous intact step, bit-exact.
      compactor_crash  — the merge worker crashes twice mid-churn; the
                         supervisor restarts it with backoff while
                         reads keep serving, and the healed service
                         matches the oracle.
      kernel_failover  — the Pallas dispatch raises twice; the op is
                         retried then stickily rerouted to its
                         bit-identical XLA fallback.
      router_refit     — a shard-router re-fit crashes mid-rebalance;
                         the abort is clean (old router, old shards)
                         and reads never diverge.
    """
    import shutil
    import tempfile
    import time

    from repro import faults
    from repro.distributed.fault_tolerance import IndexCheckpointer
    from repro.obs.metrics import default_registry

    rng = np.random.default_rng(7)
    if raw is None:  # standalone (LIX_FAULTS_ONLY) path
        raw = gen_weblogs(BENCH_N)
        ks = make_keyset(raw)
    fresh = np.setdiff1d(
        rng.integers(0, 1 << 52, 4 * DELTA_CAPACITY).astype(np.float64),
        ks.raw,
    )
    probe = np.concatenate([
        raw[rng.integers(0, ks.n, 384)],
        fresh[rng.integers(0, fresh.size, 128)],
    ])

    # ---- ckpt_torn: newest checkpoint torn -> fall back one step ---------
    cfg = ServiceConfig(delta_capacity=DELTA_CAPACITY, num_shards=4)
    svc = ShardedIndexService(ks.raw, cfg)
    svc.insert(fresh[:DELTA_CAPACITY])
    want = svc.contains(probe)
    root = tempfile.mkdtemp(prefix="lix_fault_")
    try:
        ckpt = IndexCheckpointer(root, keep_last=4)
        ckpt.save(1, svc)
        svc.insert(fresh[DELTA_CAPACITY: 2 * DELTA_CAPACITY])
        with faults.inject(faults.FaultSchedule({"ckpt.write.torn": 1})) as sched:
            ckpt.save(2, svc)  # published, then torn
        assert sched.fired["ckpt.write.torn"] == 1
        del svc  # SIGKILL simulation
        t0 = time.perf_counter()
        back, step = ckpt.restore(cfg)
        got = back.contains(probe)
        t_rec = time.perf_counter() - t0
        bit_exact = bool(step == 1 and np.array_equal(got, want))
        if not bit_exact:
            raise RuntimeError(
                f"fault ckpt_torn: restore landed on step {step} or diverged"
            )
        _FAULTS["ckpt_torn"] = {
            "recovery_ms": round(t_rec * 1e3, 2),
            "restored_step": int(step),
            "bit_exact": bit_exact,
            "read_availability": 1.0,
            "restore_fallbacks": int(
                default_registry().counter("ckpt.restore_fallbacks").value
            ),
            "quarantined": int(
                default_registry().counter("ckpt.quarantined").value
            ),
        }
        record(
            "dynamic_index/fault_ckpt_torn", t_rec * 1e6,
            f"recovery_ms={t_rec * 1e3:.1f};restored_step={step};"
            f"bit_exact={bit_exact}",
        )
    finally:
        shutil.rmtree(root, ignore_errors=True)

    # ---- compactor_crash: worker dies twice, reads keep serving ----------
    cap = 1024
    svc = IndexService(ks.raw, ServiceConfig(
        delta_capacity=cap, background=True,
        compact_backoff_s=0.01, compact_backoff_cap_s=0.05,
    ))
    pool = fresh[2 * DELTA_CAPACITY:]
    inserted = np.array([], np.float64)
    reads = failures = 0
    with faults.inject(faults.FaultSchedule({"compactor.crash": 2})) as sched:
        t0 = time.perf_counter()
        step_sz = int(cap * 0.4)
        for r in range(6):
            chunk = pool[r * step_sz: (r + 1) * step_sz]
            svc.insert(chunk)
            inserted = np.concatenate([inserted, chunk])
            want_now = np.isin(probe, ks.raw) | np.isin(probe, inserted)
            try:
                got = svc.contains(probe)
            except RuntimeError:
                failures += 1
            else:
                if not np.array_equal(got, want_now):
                    raise RuntimeError("read diverged during compactor churn")
            reads += 1
        # heal: the supervisor's third attempt merges for real
        deadline = time.perf_counter() + 30.0
        while (sched.fired["compactor.crash"] < 2
               or svc.stats["compactions"] < 1):
            if time.perf_counter() > deadline:
                raise RuntimeError("fault compactor_crash: never healed")
            try:
                svc.contains(probe)
            except RuntimeError:
                failures += 1
            reads += 1
            time.sleep(0.005)
        t_heal = time.perf_counter() - t0
    want_now = np.isin(probe, ks.raw) | np.isin(probe, inserted)
    bit_exact = bool(np.array_equal(svc.contains(probe), want_now))
    availability = 1.0 - failures / max(1, reads)
    restarts = int(svc.metrics.counter("compact.worker_restarts").value)
    if not bit_exact or restarts < 1:
        raise RuntimeError(
            f"fault compactor_crash: bit_exact={bit_exact} restarts={restarts}"
        )
    _FAULTS["compactor_crash"] = {
        "recovery_ms": round(t_heal * 1e3, 2),
        "bit_exact": bit_exact,
        "read_availability": round(availability, 4),
        "reads": reads,
        "worker_crashes": int(
            svc.metrics.counter("compact.worker_crashes").value),
        "worker_restarts": restarts,
        "escalated": bool(svc.compactor_escalated),
    }
    record(
        "dynamic_index/fault_compactor_crash", t_heal * 1e6,
        f"availability={availability:.4f};restarts={restarts};"
        f"bit_exact={bit_exact}",
    )
    record_latency("fault_compactor_crash", svc.metrics)

    # ---- kernel_failover: pallas raises -> sticky XLA fallback -----------
    kernels_ops.reset_failover()
    svc = IndexService(ks.raw, ServiceConfig(
        delta_capacity=DELTA_CAPACITY, strategy="pallas_fused"))
    oracle = IndexService(ks.raw, ServiceConfig(
        delta_capacity=DELTA_CAPACITY, strategy="binary"))
    keys = fresh[:256]
    svc.insert(keys)
    oracle.insert(keys)
    want_f, want_r = oracle.get(probe)
    svc.get(probe)  # warm the kernel path before injecting
    failovers0 = int(default_registry().counter("kernel_failover").value)
    with faults.inject(faults.FaultSchedule({"kernel.dispatch": 2})) as sched:
        t0 = time.perf_counter()
        got_f, got_r = svc.get(probe)  # retried once, then rerouted
        t_rec = time.perf_counter() - t0
    got_f2, got_r2 = svc.get(probe)  # sticky fallback path
    bit_exact = bool(
        np.array_equal(got_f, want_f) and np.array_equal(got_r, want_r)
        and np.array_equal(got_f2, want_f) and np.array_equal(got_r2, want_r)
    )
    failovers = int(
        default_registry().counter("kernel_failover").value) - failovers0
    if not bit_exact or failovers < 1 or sched.fired["kernel.dispatch"] != 2:
        raise RuntimeError(
            f"fault kernel_failover: bit_exact={bit_exact} "
            f"failovers={failovers} fired={sched.fired}"
        )
    _FAULTS["kernel_failover"] = {
        "recovery_ms": round(t_rec * 1e3, 2),
        "bit_exact": bit_exact,
        "read_availability": 1.0,
        "failovers": failovers,
        "failover_state": kernels_ops.failover_summary(),
    }
    record(
        "dynamic_index/fault_kernel_failover", t_rec * 1e6,
        f"failovers={failovers};bit_exact={bit_exact}",
    )
    kernels_ops.reset_failover()

    # ---- router_refit: re-fit crash aborts cleanly -----------------------
    svc = ShardedIndexService(
        ks.raw, ServiceConfig(delta_capacity=DELTA_CAPACITY, num_shards=4))
    svc.insert(fresh[:DELTA_CAPACITY])
    want = svc.contains(probe)
    aborted = False
    with faults.inject(faults.FaultSchedule({"router.refit": 1})):
        t0 = time.perf_counter()
        try:
            svc.rebalance()
        except faults.InjectedFault:
            aborted = True
        t_rec = time.perf_counter() - t0
    bit_exact = bool(np.array_equal(svc.contains(probe), want))
    svc.rebalance()  # the retry heals: fresh router installs cleanly
    bit_exact = bit_exact and bool(np.array_equal(svc.contains(probe), want))
    if not (aborted and bit_exact):
        raise RuntimeError(
            f"fault router_refit: aborted={aborted} bit_exact={bit_exact}"
        )
    _FAULTS["router_refit"] = {
        "recovery_ms": round(t_rec * 1e3, 2),
        "bit_exact": bit_exact,
        "read_availability": 1.0,
        "aborted_cleanly": aborted,
    }
    record(
        "dynamic_index/fault_router_refit", t_rec * 1e6,
        f"aborted_cleanly={aborted};bit_exact={bit_exact}",
    )


def main() -> None:
    enable_compile_cache()
    rng = np.random.default_rng(0)
    raw = gen_weblogs(BENCH_N)
    ks = make_keyset(raw)
    n = ks.n
    b = min(BENCH_LOOKUPS, n)

    cfg = RMIConfig(num_leaves=max(16, n // 64), stage0_hidden=(),
                    stage0_train_steps=0)
    sample = rng.choice(n, b)
    qn = jnp.asarray(ks.norm[sample])

    # ---- static floor: the read-only RMI of §3 ---------------------------
    static_lookup = compile_lookup(build_rmi(ks, cfg), ks)
    t_static = ns_per_item(static_lookup, qn, batch=b)
    record("dynamic_index/static_rmi", t_static / 1e3, f"n={n}")

    # ---- merged path vs delta fill ---------------------------------------
    svc = IndexService(ks.raw, ServiceConfig(
        delta_capacity=DELTA_CAPACITY, rmi=cfg))
    fresh = iter(np.setdiff1d(
        rng.integers(0, 1 << 52, 3 * DELTA_CAPACITY).astype(np.float64),
        ks.raw,
    ))
    filled = 0
    for pct in (0, 10, 25, 50, 100):
        target = int(DELTA_CAPACITY * pct / 100)
        if target > filled:
            svc.insert(np.array([next(fresh) for _ in range(target - filled)]))
            filled = target
        snap, _, _, dk, dp = svc._capture()
        fn = snap.merged_lookup_fn(svc.config.strategy)
        t = ns_per_item(fn, qn, dk, dp, batch=b)
        record(
            f"dynamic_index/fill_{pct}pct",
            t / 1e3,
            f"delta={target};vs_static={t / t_static:.2f}x",
        )

        # ---- fused kernel vs two-dispatch at this fill fraction ----------
        if pct > 0:
            bf = min(b, FUSED_BATCH)
            qf = qn[:bf]
            t2 = ns_per_item(snap.merged_lookup_fn("binary"), qf, dk, dp,
                             batch=bf)
            tx = ns_per_item(snap.merged_lookup_fn("xla_fused"), qf, dk, dp,
                             batch=bf)
            tf = ns_per_item(snap.merged_lookup_fn("pallas_fused"), qf, dk,
                             dp, batch=bf)
            record(
                f"dynamic_index/fused_fill_{pct}pct",
                tf / 1e3,
                f"two_dispatch_us={t2 / 1e3:.4f};xla_fused_us={tx / 1e3:.4f};"
                f"fused_vs_2dispatch={tf / t2:.2f}x;"
                f"interpret={default_interpret()}",
            )

    # ---- mixed 90/10 read/write stream -----------------------------------
    svc = IndexService(ks.raw, ServiceConfig(
        delta_capacity=DELTA_CAPACITY, rmi=cfg))
    writes_per_round = max(1, b // 10)
    new_keys = np.setdiff1d(
        rng.integers(0, 1 << 52, 20 * writes_per_round).astype(np.float64),
        ks.raw,
    )
    import time
    ops = 0
    t0 = time.perf_counter()
    for r in range(10):
        w = new_keys[r * writes_per_round:(r + 1) * writes_per_round]
        svc.insert(w)
        svc.lookup_batch(raw[rng.choice(n, b - writes_per_round)]
                         ).block_until_ready()
        ops += b
    t_mixed = (time.perf_counter() - t0) / ops * 1e9
    record(
        "dynamic_index/mixed_90_10",
        t_mixed / 1e3,
        f"compactions={svc.stats['compactions']};vs_static={t_mixed / t_static:.2f}x",
    )
    record_latency("mixed_90_10", svc.metrics)

    # ---- after compaction the merged path is the static path -------------
    svc.flush()
    snap, _, _, dk, dp = svc._capture()
    fn = snap.merged_lookup_fn(svc.config.strategy)
    qn2 = jnp.asarray(snap.keys.normalize(raw[sample]))
    t_post = ns_per_item(fn, qn2, dk, dp, batch=b)
    record(
        "dynamic_index/post_compaction",
        t_post / 1e3,
        f"version={svc.version};leaves_refit={svc.stats['leaves_refit']};"
        f"vs_static={t_post / t_static:.2f}x",
    )

    sharded_sweep(raw, ks)
    scan_sweep(raw, ks)
    serve_sweep(raw, ks)
    chaos_sweep(raw, ks)
    fault_sweep(raw, ks)


if __name__ == "__main__":
    import jax

    TRACER.enable()  # program spans land in the profiler trace
    with jax.profiler.trace(TRACE_DIR, create_perfetto_trace=True):
        if os.environ.get("LIX_SHARDED_ONLY", "0") == "1":
            _RUN_LABEL = "sharded_sweep"
            sharded_sweep()
        elif os.environ.get("LIX_SCAN_ONLY", "0") == "1":
            _RUN_LABEL = "scan_sweep"
            scan_sweep()
        elif os.environ.get("LIX_SERVE_ONLY", "0") == "1":
            _RUN_LABEL = "serve_sweep"
            serve_sweep()
        elif os.environ.get("LIX_CHAOS_ONLY", "0") == "1":
            _RUN_LABEL = "chaos_sweep"
            chaos_sweep()
        elif os.environ.get("LIX_FAULTS_ONLY", "0") == "1":
            _RUN_LABEL = "fault_sweep"
            fault_sweep()
        else:
            main()
    TRACER.disable()
    write_json()
