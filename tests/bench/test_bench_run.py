"""The whole run on the CPU at a tiny size: the look for a chip
refuses, a sound run is correct, and each fault of the timed path that
the cells can have turns ``correct`` false."""

import pytest

from bench_tiny import run, tiny_root

GET_CELL = "maps200m.get_zipf"
SCAN_CELL = "weblogs200m.scan_latest"


def test_run_refuses_without_a_tpu(capsys, monkeypatch):
    from bench import run as entry

    monkeypatch.setenv("TPU_LOG_DIR", "disabled")  # restored afterwards
    with pytest.raises(SystemExit) as exc:
        entry.main(["--workload", GET_CELL, "--seed", "1", "--seconds", "1"])
    assert exc.value.code != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("cell,metrics", [
    (GET_CELL, {"read_p95_ms", "ops_per_s", "setup_s"}),
    (SCAN_CELL, {"scan_p95_ms", "ops_per_s", "setup_s"}),
])
def test_sound_run_is_correct(tmp_path, monkeypatch, cell, metrics):
    res = run(tiny_root(tmp_path), cell, monkeypatch, seed=2**31 + 5)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] == 200
    # no memory reading on the CPU, so no bytes per key
    assert set(res["metrics"]) == metrics
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["checks"].values())


def test_trace_run_reports_layer_metrics(tmp_path, monkeypatch):
    res = run(tiny_root(tmp_path), GET_CELL, monkeypatch, trace=True)
    assert res["correct"]
    # the CPU has no TPU plane: the device metrics find nothing to read;
    # the program's spans and counters are read as on the chip
    steps = {f"service.get.{s}_ms"
             for s in ("prepare", "dispatch", "readback", "refine")}
    assert set(res["metrics"]) == {"read_p99_ms",
                                   "frontend.requests_per_round",
                                   "service.get_ms", "snapshot.compiles.get",
                                   "frontend.queue_wait_ms",
                                   "frontend.round_self_ms",
                                   "frontend.pad_share"} | steps
    assert res["metrics"]["snapshot.compiles.get"]["value"] == 0
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert all(m[k] > 0 for k in steps | {"frontend.queue_wait_ms",
                                          "frontend.round_self_ms"})
    assert 0 <= m["frontend.pad_share"] < 100


def _alter_get(monkeypatch):
    from repro.index_service import IndexService

    orig = IndexService.get

    def get(self, keys):
        rank, found = orig(self, keys)
        rank = rank.copy()
        rank[0] += 1
        return rank, found
    monkeypatch.setattr(IndexService, "get", get)


def _half_batch(monkeypatch):
    from repro.serve.frontend import IndexFrontend

    orig = IndexFrontend._apply_keyed

    def apply(self, batch, op, split):
        kept = batch[:(len(batch) + 1) // 2]
        orig(self, kept, op, split)
        for r in batch[len(kept):]:
            r.result = kept[-1].result
    monkeypatch.setattr(IndexFrontend, "_apply_keyed", apply)


def _alter_scan(monkeypatch):
    from repro.index_service import IndexService

    orig = IndexService.scan_batch

    def scan_batch(self, lo, hi, page_size=256):
        keys, vals, live = orig(self, lo, hi, page_size)
        return keys, vals.at[0, 0].add(1), live
    monkeypatch.setattr(IndexService, "scan_batch", scan_batch)


@pytest.mark.parametrize("cell,fault,rate", [
    (GET_CELL, _alter_get, 200.0),
    (GET_CELL, _half_batch, 3000.0),
    (SCAN_CELL, _alter_scan, 200.0),
])
def test_fault_in_timed_path_is_not_correct(tmp_path, monkeypatch, cell,
                                            fault, rate):
    fault(monkeypatch)
    res = run(tiny_root(tmp_path, rate=rate), cell, monkeypatch)
    assert not res["correct"]
    kind = "get" if cell == GET_CELL else "scan"
    assert res["checks"][f"{kind}_wrong"]["value"] > 0
