"""The reduction from a profiler trace to device figures, on a small
trace recorded on a TPU v5e: 15 ms of a traced
`weblogs200m.scan_latest` run, its programs, their operations and the
harness's host annotations."""

import json
import pathlib

import numpy as np
import pytest

from bench_tiny import REPO  # noqa: F401  (puts the checkout on sys.path)
from bench import catalog, trace_reduce

RECORDED = pathlib.Path(__file__).with_name("data") / "tpu_trace.json"


def _by_hand_busy(events):
    """Union length by a plain sweep, in seconds."""
    total, end = 0.0, -np.inf
    for s, e in sorted((s, s + d) for _, s, d in events):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total * 1e-9


@pytest.fixture(scope="module")
def recorded():
    return json.loads(RECORDED.read_text())


def test_busy_is_the_union_of_device_ops(recorded):
    red = trace_reduce.reduce_trace(recorded)
    (evs,) = recorded["devices"].values()
    assert red["chips"] == 1
    assert red["busy_s"] == pytest.approx(_by_hand_busy(evs), rel=1e-12)
    assert red["busy_s"] <= sum(d for _, _, d in evs) * 1e-9 + 1e-15
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    gaps = [g for _, g in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)


def test_idle_share_and_time_per_call(recorded):
    red = trace_reduce.reduce_trace(recorded)
    red.update(window_s=recorded["window_s"], calls=recorded["calls"],
               bytes={"get": 0, "scan_batch": 10**6})
    rec = {"trace": red,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    idle = catalog.load_reader("device.idle_pct")(rec)
    assert idle == pytest.approx(
        100 * (1 - red["busy_s"] / recorded["window_s"]))
    assert 0 < idle < 100
    per_call = catalog.load_reader("scan.device_us")(rec)
    assert per_call == pytest.approx(
        1e6 * red["busy_s"] / recorded["calls"]["scan_batch"])
    roof = catalog.load_reader("scan_roofline")(rec)
    assert roof == pytest.approx(100 * (1e6 / 819e9) / red["busy_s"])
    # no gets in this trace: nothing to read for them
    assert catalog.load_reader("lookup.device_us")(rec) is None
    assert catalog.load_reader("lookup_roofline")(rec) is None


def test_gaps_are_named_by_the_open_host_annotation():
    events = {
        "devices": {"/device:TPU:0": [["jit_f", 0, 10], ["jit_f", 5, 3],
                                      ["jit_f", 20, 10], ["jit_g", 60, 5]]},
        "ops": {"/device:TPU:0": [["a", 0, 10], ["b", 5, 3], ["a", 20, 10],
                                  ["c", 60, 5]]},
        "host": [["bench.service.get", 11, 8],
                 ["bench.service.get", 31, 40],
                 ["bench.service.scan_batch", 40, 10]],
    }
    red = trace_reduce.reduce_trace(events)
    assert red["busy_s"] == pytest.approx(25e-9)
    assert red["idle_gaps"] == [
        ["bench.service.scan_batch", pytest.approx(30e-9)],
        ["bench.service.get", pytest.approx(10e-9)]]
    assert red["device_ops"][0] == ["a", pytest.approx(20e-9)]


def test_no_device_ops_reads_nothing():
    # no device program: the (empty) span table and no device figure
    assert trace_reduce.reduce_trace({"devices": {}, "host": []}) == {
        "spans": {}}
    assert catalog.load_reader("device.idle_pct")({"trace": {}}) is None
