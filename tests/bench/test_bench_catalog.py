"""Discovery by name: a cell, configuration, mix and metric added as
files and entries are found and run; an unknown name fails."""

import json

import pytest

from bench_tiny import run, tiny_root


def test_unknown_names_fail(tmp_path):
    from bench import catalog

    root = tiny_root(tmp_path)
    with pytest.raises(LookupError):
        catalog.load_cell("no_such.cell", root)
    with pytest.raises(LookupError):
        catalog.load_reader("no_such_metric", root)
    with pytest.raises(LookupError):
        catalog.load_peaks("TPU v0", root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "x.y", "config": "missing",
                               "traffic": "ycsb_c_zipf", "chips": 1,
                               "why": "a cell whose configuration is absent"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(LookupError):
        catalog.load_cell("x.y", root)


def test_cells_take_their_metrics_by_list():
    from bench import catalog

    cell = catalog.load_cell("weblogs200m.scan_latest")
    assert [m["name"] for m in cell.end_to_end] == [
        "scan_p95_ms", "ops_per_s", "device_bytes_per_key", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "scan_p99_ms", "service.scan_ms", "snapshot.compiles.scan",
        "scan.device_us",
        "scan_roofline", "device.idle_pct", "frontend.queue_wait_ms",
        "frontend.round_self_ms", "service.scan.prepare_ms",
        "service.scan.dispatch_ms", "device.idle_host_pct",
        "device.idle_unattributed_pct"}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(catalog.load_reader(m["name"]))


def test_new_cell_from_added_files_alone(tmp_path, monkeypatch):
    root = tiny_root(tmp_path)
    cfg = json.loads((root / "bench/configs/maps200m.json").read_text())
    cfg.update(name="maps_tiny", shape_seed=3)
    (root / "bench/configs/maps_tiny.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/zipf_mixed.json").write_text(json.dumps({
        "ops": {"get": 0.5, "scan": 0.5},
        "keys": {"dist": "scrambled_zipfian", "theta": 0.99},
        "scan_rows": [1, 8], "arrival": {"process": "poisson"},
        "rate_ops_s": 150}))
    (root / "bench/metrics/get_p50_ms.py").write_text(
        "import numpy as np\n\n\ndef read(rec):\n"
        "    return float(np.median(rec['latency_s']['get']) * 1e3)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "maps_tiny.mixed",
                               "config": "maps_tiny",
                               "traffic": "zipf_mixed", "chips": 1,
                               "why": "gets and scans in one mix"})
    bench["end_to_end"].append({"name": "get_p50_ms", "unit": "ms",
                                "better": "lower", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["maps_tiny.mixed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    res = run(root, "maps_tiny.mixed", monkeypatch)
    assert res["correct"] and res["attempted"] == 150
    assert set(res["metrics"]) == {"get_p50_ms", "ops_per_s", "setup_s"}
    assert set(res["checks"]) == {"get_wrong", "scan_wrong", "unanswered"}
