"""A copy of the benchmark's data at a size the CPU holds in a second:
the real `BENCHMARK.json`, metric readers, request kinds, key generators
and peaks (with the CPU added as a device), configurations cut to a few
thousand keys and a short frontend round, mixes at a low rate.  Runs
skip the look for a chip and the persistent compile cache."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

KEYS = 20_000
MAX_ROUND = 16


def tiny_root(tmp: pathlib.Path, rate: float = 200.0) -> pathlib.Path:
    root = tmp / "checkout"
    (root / "bench").mkdir(parents=True)
    shutil.copy(REPO / "BENCHMARK.json", root)
    for d in ("metrics", "configs", "traffic", "ops", "generators"):
        shutil.copytree(REPO / "bench" / d, root / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))
    for f in (root / "bench" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        cfg["keys"] = KEYS
        cfg["frontend"] = {**cfg["frontend"], "max_round": MAX_ROUND}
        f.write_text(json.dumps(cfg))
    for f in (root / "bench" / "traffic").glob("*.json"):
        mix = json.loads(f.read_text())
        mix["rate_ops_s"] = rate
        f.write_text(json.dumps(mix))
    return root


def run(root: pathlib.Path, cell: str, monkeypatch, *, seed: int = 7,
        seconds: float = 1.0, trace: bool = False) -> dict:
    from bench import catalog, harness

    monkeypatch.setattr(harness, "enable_cache", lambda: "off")
    return harness.run_cell(catalog.load_cell(cell, root), seed, seconds,
                            trace, time.perf_counter(), root)
