"""Looks for pauses of the whole process, with no request in flight.

    python3 bench/pause_probe.py --backend none|cpu|tpu --seconds 20

``none`` imports no JAX; ``cpu`` and ``tpu`` start that JAX backend,
put one array on its device and leave it idle.  A loop sleeps 1 ms at
a time and notes each gap over ``--gap-ms``, with the CPU time that
each of the process's threads (by name, from ``/proc/self/task``) used
since the last look before the gap.  A gap in which no thread used CPU
time is a pause from outside the process; one in which a thread did is
a pause inside it.  Prints one JSON line per gap, then a summary line.
"""

import argparse
import json
import os
import sys
import time


def thread_cpu() -> dict:
    """``{tid: (name, utime + stime in clock ticks)}``."""
    out = {}
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/stat") as f:
                stat = f.read()
        except OSError:  # the thread ended
            continue
        rest = stat[stat.rindex(")") + 2:].split()
        out[tid] = (stat[stat.index("(") + 1:stat.rindex(")")],
                    int(rest[11]) + int(rest[12]))
    return out


def start_backend(backend: str) -> str:
    if backend == "none":
        return "none"
    if backend == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np

    device = jax.devices()[0]
    if device.platform != backend:
        raise SystemExit(f"asked for {backend}, JAX gave {device.platform}")
    jax.device_put(np.ones(1 << 20, np.float32)).block_until_ready()
    return device.device_kind


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--backend", choices=("none", "cpu", "tpu"),
                    required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--gap-ms", type=float, default=20.0)
    args = ap.parse_args(argv)
    kind = start_backend(args.backend)
    tick_ms = 1e3 / os.sysconf("SC_CLK_TCK")
    gap_s = args.gap_ms / 1e3
    before = thread_cpu()
    gaps = []
    t0 = last = time.perf_counter()
    looks = 0
    while last - t0 < args.seconds:
        time.sleep(0.001)
        now = time.perf_counter()
        if now - last > gap_s:
            after = thread_cpu()
            used = {}
            for tid, (name, ticks) in after.items():
                d = (ticks - before.get(tid, (name, 0))[1]) * tick_ms
                if d:
                    used[name] = used.get(name, 0.0) + d
            gaps.append(now - last)
            print(json.dumps({"at_s": round(last - t0, 3),
                              "gap_ms": (now - last) * 1e3,
                              "threads": len(after),
                              "cpu_ms_by_thread": used}), flush=True)
            before = after
        elif looks % 10 == 0:  # a look every ~10 ms keeps `before` fresh
            before = thread_cpu()
        looks += 1
        last = now
    print(json.dumps({"backend": args.backend, "device_kind": kind,
                      "seconds": args.seconds, "gap_ms_over": args.gap_ms,
                      "gaps": len(gaps),
                      "gap_ms_max": max(gaps, default=0.0) * 1e3,
                      "gap_ms_total": sum(gaps) * 1e3}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
