"""Finds a cell's knee: the highest offered rate that is sustained, as
is every lower rate swept.  A rate is sustained where nothing is
refused or fails, the p95 over the window stays within the frontend's
own ``slo_p99_ms``, and the backlog does not grow: the p95 of the
requests due in the window's last fifth stays within it too.  The p99
is printed but not judged: the host stops the process for about 110 ms
a few times in a window, about 1% of its time, so a p99 over the SLO
says how many pauses came, at any rate (PERF.md).  One set-up, then
one window per rate, all in one process on the chip; give the windows
the benchmark's own length (``run_seconds``), so that they meet the
host's pauses as often as a run does.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 30 \\
        --rates 4000,8000,16000

Prints one JSON line per rate and a last line with the knee and 0.8 x
the knee, the rate a cell's mix file then fixes.  Used once per cell;
the benchmark's own runs never sweep.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import (  # noqa: E402
    ROOT, chips_or_exit, keep_runtime_logs_home)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import catalog

    cell = catalog.load_cell(args.workload, ROOT)
    keep_runtime_logs_home()
    import jax
    import numpy as np

    chips_or_exit(jax, cell.chips)
    from bench import harness
    from bench.metrics_util import tail_ms

    dep = harness.deploy(cell, args.seed,
                         tuple((args.seconds, r) for r in rates))
    slo = dep.fe.config.slo_p99_ms
    harness.log(f"setup {time.perf_counter() - T_START:.1f} s; slo p99 "
                f"{slo} ms")
    knee, held = None, True
    for rate in rates:
        plan, _ = harness.next_plan(dep, args.seed, args.seconds, rate=rate)
        win = harness.window(dep, plan, args.seconds)
        lat = win.latency()
        ok = win.answered_ok()
        # a growing backlog shows as the last fifth waiting longer
        tail = plan.due >= 0.8 * args.seconds
        row = {
            "rate": rate, "requests": plan.size,
            "p50_ms": tail_ms(lat, 50), "p95_ms": tail_ms(lat, 95),
            "p99_ms": tail_ms(lat, 99),
            "p95_last_fifth_ms": tail_ms(lat[tail], 95),
            "answered_in_window": float(np.mean(ok & (win.done
                                                      <= args.seconds))),
            "failed": int(np.sum(~ok)),
            "lateness": harness.lateness_line(win),
        }
        row["sustained"] = bool(row["failed"] == 0 and row["p95_ms"] <= slo
                                and row["p95_last_fifth_ms"] <= slo)
        held = held and row["sustained"]
        if held:  # this rate and every lower one were sustained
            knee = rate
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "seconds": args.seconds, "rates": rates, "knee": knee,
                      "rate_ops_s": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
