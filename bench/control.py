"""The control of a cell's comparison: the reference put in the program's
place, one precision below what the configuration states (float32 ranks
for ``get``, a bfloat16 frame for ``scan``), on the cell's own key set
and request stream.  It has to come out as not correct.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

Prints one JSON line per seed with each number the benchmark compares
(its limit is 0), from each request kind's ``control(oracle, plan,
idx)`` (`bench/ops/`; a kind without one has no control here) over the
keys stored when the window opens.  The benchmark's own runs never run
it.
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

    from bench import catalog, reference, traffic

    cell = catalog.load_cell(args.workload, ROOT)
    cfg = cell.config
    ops = traffic.load_ops(cell.mix, ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        final = catalog.load_generator(cfg["generator"], ROOT)(
            int(cfg["keys"]), seed, int(cfg["shape_seed"]))
        adds = sum(c for k, c in traffic.kind_counts(
            cell.mix, args.seconds).items() if ops[k].ADDS_KEYS)
        held = traffic.hold_back(cell.mix, final, adds, seed)
        space = traffic.KeySpace(final, held, int(final.size - held.size))
        stored = space.base()
        oracle = reference.Oracle(final[stored], stored)
        plan = traffic.make_plan(cell.mix, space, seed, args.seconds,
                                 ops=ops)
        row = {"workload": cell.name, "seed": seed,
               "keys": int(stored.size), "requests": plan.size}
        for c, kind in enumerate(plan.kinds):
            idx = np.flatnonzero(plan.kind == c)
            if idx.size and hasattr(ops[kind], "control"):
                row.update(ops[kind].control(oracle, plan, idx))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
