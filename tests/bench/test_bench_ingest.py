"""The two scan cells over writes and a second key set on the CPU at a
tiny size: a sound run of each is correct.  In the time-ordered ingest
cell, inserts are acknowledged inside the window and reach the scans
through incremental updates of the scan plane, with no full build and
no compile; a plane that keeps serving the staged inserts it packed
before a write is caught."""

import numpy as np
import pytest

from bench import harness
from bench_tiny import run, tiny_root

SCAN_SHORT_CELL = "maps200m.scan_short"
INGEST_CELL = "weblogs200m.ingest"


@pytest.mark.parametrize("cell", [SCAN_SHORT_CELL, INGEST_CELL])
def test_new_cell_sound_run_is_correct(tmp_path, monkeypatch, cell):
    res = run(tiny_root(tmp_path), cell, monkeypatch, seed=2**31 + 5)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] == 200
    # no memory reading on the CPU, so no bytes per key
    assert set(res["metrics"]) == {"scan_p95_ms", "ops_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["checks"].values())


def _spy_write_log(monkeypatch):
    logs = []
    orig = harness.write_log

    def write_log(win, ops):
        logs.append(orig(win, ops))
        return logs[-1]
    monkeypatch.setattr(harness, "write_log", write_log)
    return logs


def test_ingest_window_updates_the_plane_without_rebuilds(tmp_path,
                                                          monkeypatch):
    logs = _spy_write_log(monkeypatch)
    res = run(tiny_root(tmp_path), INGEST_CELL, monkeypatch,
              seed=2**31 + 23, trace=True)
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert set(res["checks"]) == {"scan_wrong", "insert_wrong",
                                  "insert_lost", "unanswered"}
    (log,) = logs
    assert log.size == 10 and int(np.sum(log.acked)) == 10
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["plane.scan.rebuilds"] == 0
    assert m["snapshot.compiles.scan"] == 0
    assert m["plane.scan.update_ms"] > 0
    # three arrays of the staged-insert pad, and no page index
    assert 0 < m["plane.scan.upload_kb_per_update"] < 200


def _stale_staged_inserts(monkeypatch):
    from repro.index_service import plane

    orig, first = plane.staged_scan_arrays, []

    def staged(view, base_norm, normalize, **kw):
        # the arrays of the first update, served after every later write
        if not first:
            first.append(orig(view, base_norm, normalize, **kw))
        return first[0]
    monkeypatch.setattr(plane, "staged_scan_arrays", staged)


def test_stale_staged_inserts_are_wrong(tmp_path, monkeypatch):
    _stale_staged_inserts(monkeypatch)
    # 30 inserts in the window: most late scans reach back over some
    res = run(tiny_root(tmp_path, rate=600.0), INGEST_CELL, monkeypatch,
              seed=2**31 + 29)
    assert not res["correct"]
    assert res["checks"]["scan_wrong"]["value"] > 0
