"""CI gate: the dynamic-index benchmark artifact must carry the
observability sections PR 6 added — per-op latency percentiles and the
dispatch-cost attribution ledger (with retrace counts) — plus the
serving-tier section (per-tenant percentiles, QPS per client count,
the one-dispatch coalescing proof, and the latency-SLO verdict, which
gates), the chaos section (bit-exact crash recovery per shard count
and the leveled-vs-single-level write-stall rows, where a leveled run
merging as often as single-level fails the gate), the faults section
(the chaos matrix: every fault class must heal bit-exact with read
availability >= 99%, the compactor-crash schedule must show a
supervisor restart without escalation, and the kernel class a sticky
failover), and the profiler traces must hold the program's
``service.*`` and ``dispatch.*`` spans.

Run after the bench-smoke steps:

    PYTHONPATH=src python benchmarks/check_obs_artifact.py

Exits non-zero with a message naming the first missing piece, so a
refactor that silently drops instrumentation fails the smoke job
instead of shipping a hollow artifact.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys

JSON_PATH = os.environ.get("LIX_BENCH_JSON", "BENCH_dynamic_index.json")


def fail(msg: str) -> None:
    print(f"check_obs_artifact: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def main() -> None:
    if not os.path.exists(JSON_PATH):
        fail(f"{JSON_PATH} not found (run benchmarks/dynamic_index.py first)")
    with open(JSON_PATH) as f:
        data = json.load(f)

    obs = data.get("observability")
    if not isinstance(obs, dict):
        fail("no 'observability' section in artifact")

    # ---- per-op latency percentiles --------------------------------------
    lat = obs.get("op_latency") or {}
    if not lat:
        fail("observability.op_latency is empty")
    n_ops = 0
    for label, rows in lat.items():
        if not rows:
            fail(f"op_latency[{label!r}] has no ops")
        for op, row in rows.items():
            for field in ("count", "p50_us", "p90_us", "p99_us", "mean_us"):
                if field not in row:
                    fail(f"op_latency[{label!r}][{op!r}] missing {field!r}")
            if row["count"] < 1:
                fail(f"op_latency[{label!r}][{op!r}] recorded zero samples")
            if row["p99_us"] < row["p50_us"]:
                fail(f"op_latency[{label!r}][{op!r}] p99 < p50")
            n_ops += 1

    # ---- dispatch attribution with retraces ------------------------------
    disp = obs.get("dispatch") or {}
    if not disp:
        fail("observability.dispatch is empty")
    n_rows = 0
    for label, summary in disp.items():
        rows = summary.get("rows") or []
        if not rows:
            fail(f"dispatch[{label!r}] has no attribution rows")
        if summary.get("total", 0) < 1:
            fail(f"dispatch[{label!r}] counted zero dispatches")
        for row in rows:
            for field in ("op", "path", "count", "wall_s", "retraces"):
                if field not in row:
                    fail(f"dispatch[{label!r}] row missing {field!r}: {row}")
        n_rows += len(rows)

    # ---- serving tier: per-tenant percentiles + SLO verdict --------------
    serving = obs.get("serving") or {}
    if not serving:
        fail("observability.serving is empty (run the serve sweep: "
             "LIX_SERVE_ONLY=1 python -m benchmarks.dynamic_index)")
    n_tenants = 0
    for label, sweep in serving.items():
        for field in ("clients", "qps", "slo_p99_ms", "slo_pass",
                      "worst_read_p99_ms", "requests",
                      "coalesced_get_dispatches"):
            if field not in sweep:
                fail(f"serving[{label!r}] missing {field!r}")
        if not sweep["slo_pass"]:
            fail(f"serving[{label!r}] read p99 "
                 f"{sweep['worst_read_p99_ms']}ms blew the "
                 f"{sweep['slo_p99_ms']}ms SLO")
        if sweep["coalesced_get_dispatches"] != 1:
            fail(f"serving[{label!r}]: coalesced point reads cost "
                 f"{sweep['coalesced_get_dispatches']} dispatches, not 1 "
                 "— the one-dispatch discipline broke in the frontend")
        if sweep["qps"] <= 0 or sweep["requests"] < sweep["clients"]:
            fail(f"serving[{label!r}] served no meaningful traffic")
        tenants = sweep.get("tenants") or {}
        if len(tenants) < sweep["clients"]:
            fail(f"serving[{label!r}] has {len(tenants)} tenant rows "
                 f"for {sweep['clients']} clients")
        for tname, trow in tenants.items():
            ops = trow.get("ops") or {}
            if trow.get("requests", 0) > 0 and not ops:
                fail(f"serving[{label!r}] tenant {tname!r} served "
                     "requests but has no per-op latency rows")
            for op, row in ops.items():
                for field in ("count", "p50_us", "p99_us"):
                    if field not in row:
                        fail(f"serving[{label!r}] tenant {tname!r} "
                             f"op {op!r} missing {field!r}")
            n_tenants += 1

    # ---- chaos: recovery was bit-exact, the merge schedule is leveled ----
    chaos = obs.get("chaos") or {}
    if not chaos:
        fail("observability.chaos is empty (run the chaos sweep: "
             "LIX_CHAOS_ONLY=1 python -m benchmarks.dynamic_index)")
    rec = {k: v for k, v in chaos.items() if k.startswith("chaos_recovery")}
    if not rec:
        fail("observability.chaos has no recovery rows")
    for label, row in rec.items():
        for field in ("shards", "save_ms", "recovery_ms", "bit_exact"):
            if field not in row:
                fail(f"chaos[{label!r}] missing {field!r}")
        if not row["bit_exact"]:
            fail(f"chaos[{label!r}]: restored service was NOT bit-exact "
                 "against pre-crash answers")
        if row["recovery_ms"] <= 0:
            fail(f"chaos[{label!r}] recorded no recovery time")
    l1 = chaos.get("chaos_stall_L1")
    l4 = chaos.get("chaos_stall_L4")
    if not (l1 and l4):
        fail("observability.chaos missing stall rows (L1/L4)")
    for label, row in (("chaos_stall_L1", l1), ("chaos_stall_L4", l4)):
        for field in ("worst_insert_ms", "median_insert_ms", "compactions",
                      "write_stalls", "write_stall_s"):
            if field not in row:
                fail(f"chaos[{label!r}] missing {field!r}")
    if l4["compactions"] >= l1["compactions"]:
        fail(f"chaos: leveled compactor merged {l4['compactions']}x vs "
             f"{l1['compactions']}x single-level — the deferred merge "
             "schedule (the bounded-write-stall mechanism) is broken")

    # ---- faults: post-fault recovery exact, reads stayed available -------
    fault_rows = obs.get("faults") or {}
    if not fault_rows:
        fail("observability.faults is empty (run the fault sweep: "
             "LIX_FAULTS_ONLY=1 python -m benchmarks.dynamic_index)")
    required_classes = ("ckpt_torn", "compactor_crash", "kernel_failover")
    for cls in required_classes:
        if cls not in fault_rows:
            fail(f"observability.faults missing the {cls!r} class")
    for label, row in fault_rows.items():
        for field in ("recovery_ms", "bit_exact", "read_availability"):
            if field not in row:
                fail(f"faults[{label!r}] missing {field!r}")
        if not row["bit_exact"]:
            fail(f"faults[{label!r}]: post-fault recovery was NOT "
                 "bit-exact — healing changed answers")
        if row["read_availability"] < 0.99:
            fail(f"faults[{label!r}]: read availability "
                 f"{row['read_availability']:.4f} < 0.99 — reads did not "
                 "keep serving through the fault")
    cc = fault_rows["compactor_crash"]
    if cc.get("worker_restarts", 0) < 1:
        fail("faults['compactor_crash']: supervisor never restarted the "
             "crashed worker")
    if cc.get("escalated", False):
        fail("faults['compactor_crash']: supervisor escalated on a "
             "recoverable crash schedule")
    if fault_rows["kernel_failover"].get("failovers", 0) < 1:
        fail("faults['kernel_failover']: no sticky kernel->XLA failover "
             "was recorded")

    # ---- profiler traces ------------------------------------------------
    trace_dir = obs.get("trace_dir") or ""
    paths = glob.glob(os.path.join(trace_dir, "**", "perfetto_trace.json.gz"),
                      recursive=True) if trace_dir else []
    if not paths:
        fail(f"no perfetto_trace.json.gz under trace dir {trace_dir!r}")
    names = set()
    for path in paths:
        with gzip.open(path, "rt") as f:
            events = json.load(f).get("traceEvents") or []
        names.update(str(ev.get("name", "")) for ev in events)
    for prefix in ("service.", "dispatch."):
        if not any(n.startswith(prefix) for n in names):
            fail(f"no {prefix}* span in the traces under {trace_dir!r}")
    n_names = len(names)

    print(
        f"check_obs_artifact: OK — {n_ops} latency rows over "
        f"{len(lat)} sweeps, {n_rows} dispatch rows over "
        f"{len(disp)} runs, {n_tenants} tenant rows over "
        f"{len(serving)} serve sweeps (SLO pass), {len(rec)} bit-exact "
        f"recoveries + leveled stall rows, {len(fault_rows)} fault classes "
        f"healed (availability >= 99%), {n_names} span names in "
        f"{len(paths)} traces"
    )


if __name__ == "__main__":
    main()
