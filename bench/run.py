"""Runs one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's deployment from the seed, warms up, drives the
learned-index service through `IndexFrontend` in an open loop for the
window, checks every answer against the plain reference, and prints as
its last line one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, last, ``checks`` (each number
compared, with its limit).  Exits non-zero, printing no result, where
JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def keep_runtime_logs_home() -> None:
    """The TPU runtime logs to a fixed directory under /tmp unless told
    otherwise; a run writes nothing outside its checkout and its own
    HOME and TMPDIR."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def chips_or_exit(jax, want: int) -> None:
    """Refuse to run anywhere but on ``want`` TPU chips or more."""
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"bench: needs {want} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(2)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import catalog

    cell = catalog.load_cell(args.workload, ROOT)
    keep_runtime_logs_home()
    import jax

    chips_or_exit(jax, cell.chips)
    from bench import harness

    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              T_START, ROOT)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
