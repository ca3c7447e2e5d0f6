"""The control of a cell's comparison: the reference put in the program's
place, one precision below what the configuration states (float32 ranks
for ``get``, a bfloat16 frame for ``scan``), on the cell's own key set
and request stream.  It has to come out as not correct.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

Prints one JSON line per seed with each number the benchmark compares
(its limit is 0).  The benchmark's own runs never run it.
"""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT)]
    import numpy as np

    from bench import catalog, datagen, reference
    from bench.traffic import KINDS, make_plan

    cell = catalog.load_cell(args.workload, ROOT)
    cfg = cell.config
    for seed in (int(s) for s in args.seeds.split(",")):
        keys = datagen.GENERATORS[cfg["generator"]](
            int(cfg["keys"]), seed, int(cfg["shape_seed"]))
        oracle = reference.Oracle(keys, np.arange(keys.size, dtype=np.int64))
        plan = make_plan(cell.mix, keys, seed, args.seconds)
        row = {"workload": cell.name, "seed": seed, "keys": int(keys.size),
               "requests": plan.size}
        gets = plan.kind == KINDS.index("get")
        if gets.any():
            q = plan.lo[gets]
            row["get_wrong"] = reference.gets_wrong(
                oracle, q, *oracle.get_control(q))
        scans = np.flatnonzero(plan.kind == KINDS.index("scan"))
        if scans.size:
            row["scan_wrong"] = int(sum(
                reference.scan_wrong(oracle, plan.lo[i], plan.hi[i],
                                     *oracle.scan_control(plan.lo[i],
                                                          plan.hi[i]))
                for i in scans))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
