"""Finds every piece of a cell by its name in `BENCHMARK.json`:

* ``bench/configs/<config>.json``     one deployment;
* ``bench/generators/<name>.py``      the key generator a configuration
  names (``"generator"``), ``generate(n, seed, shape_seed)``;
* ``bench/traffic/<mix>.json``        one traffic mix;
* ``bench/ops/<kind>.py``             one request kind a mix's ``ops``
  names (see `bench.traffic` for what a kind module gives);
* ``bench/metrics/<metric>.py``       one metric's reader, ``read(rec)``;
* ``bench/peaks.json``                the chips' peaks, by ``device_kind``.

A later cell, mix, configuration, request kind, key generator or metric
is added as files and entries; nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]   # the metrics a --trace 0 run reports
    per_layer: List[dict]    # the metrics a --trace 1 run reports
    root: pathlib.Path = ROOT  # the checkout its files came from


def _load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise LookupError(f"no such file: {path}")
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str, reported: set) -> bool:
    """A metric with ``workloads`` applies to those cells; one without
    to every cell (end to end) or every cell that reports the metric it
    moves (per layer)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics;
    LookupError for a name `BENCHMARK.json` does not hold."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise LookupError(f"unknown workload {name!r}; "
                          f"known: {sorted(cells)}")
    w = cells[name]
    config = _load_json(root / "bench" / "configs" / f"{w['config']}.json")
    mix = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=e2e, per_layer=per_layer, root=root)


def _load_module(folder: str, name: str, root: pathlib.Path):
    """The module ``bench/<folder>/<name>.py`` of the checkout ``root``;
    LookupError where there is no such file."""
    path = root / "bench" / folder / f"{name}.py"
    if not path.is_file():
        raise LookupError(f"no {folder} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(metric: str, root: pathlib.Path = ROOT) -> Callable:
    """``read(rec) -> float | None`` from ``bench/metrics/<metric>.py``."""
    return _load_module("metrics", metric, root).read


def load_generator(name: str, root: pathlib.Path = ROOT) -> Callable:
    """``generate(n, seed, shape_seed) -> sorted unique keys`` from
    ``bench/generators/<name>.py``; the keys keep the dtype it returns."""
    return _load_module("generators", name, root).generate


def load_kinds(names, root: pathlib.Path = ROOT) -> Dict[str, object]:
    """``{kind: module}`` from ``bench/ops/<kind>.py`` for each name."""
    return {k: _load_module("ops", k, root) for k in names}


def load_peaks(device_kind: str,
               root: pathlib.Path = ROOT) -> Dict[str, float]:
    """The peaks of ``device_kind``; LookupError for a chip the table
    does not hold."""
    table = _load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise LookupError(f"no peaks for device kind {device_kind!r} in "
                          "bench/peaks.json")
    return table["devices"][device_kind]


def read_metrics(metrics: List[dict], rec: dict,
                 root: pathlib.Path = ROOT) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` for each metric whose reader finds
    something to read."""
    out = {}
    for m in metrics:
        value: Optional[float] = load_reader(m["name"], root)(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
