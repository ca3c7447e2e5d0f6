"""Writable deployments as files: plans of the existing mixes as they
always were, insert mixes judged between ``must`` and ``may``, faults of
the write path caught, a request kind and a key generator added as files
alone, and the span table of a trace."""

import hashlib
import json

import numpy as np
import pytest

from bench_tiny import REPO, run, tiny_root
from bench import catalog, reference, trace_reduce, traffic

# sha256 (first 32 hex digits) of make_plan's (due, kind, lo, hi) and of
# the generators' keys (20,000 drawn, shape seed 0), as the harness made
# them before request kinds and key generators were files; its plans
# coded every kind by its place in PARENT_KINDS
PARENT_KINDS = ("get", "scan")
GOLDEN = {
    "keys/maps/1": "3ea6bee9b6b9cb652877bf32decd3a69",
    "plan/ycsb_c_zipf/1/0": "1d0ab57bfa262a21343698bb779e4921",
    "plan/ycsb_c_zipf/1/1": "d54816532fed9eee379c61a4cbce2116",
    "keys/maps/2147483653": "3bc9c4f2f7af9fbeed120c706c870b1e",
    "plan/ycsb_c_zipf/2147483653/0": "4ab11eef01eb52de6feab6713114b838",
    "plan/ycsb_c_zipf/2147483653/1": "82666f2ddf24e964fc8926b235725116",
    "keys/maps/8589934599": "1821f1f513d6db6f3165509a1abc38fc",
    "plan/ycsb_c_zipf/8589934599/0": "5e9fe4dddde7e04be368f2d70e17e225",
    "plan/ycsb_c_zipf/8589934599/1": "b19f3538c6aea19377b12a886d2d712f",
    "keys/weblogs/1": "49ea235966160b1dbd1b445fe35649d4",
    "plan/ycsb_e_latest_ro/1/0": "0b8ab7f00b32403996c5367dfafa94b3",
    "plan/ycsb_e_latest_ro/1/1": "ecfc4b60a5b6c68ea015edc59365756d",
    "keys/weblogs/2147483653": "60504124e058f6fb668ff505e0d97775",
    "plan/ycsb_e_latest_ro/2147483653/0": "5ddd2bbf7c962e84c18f9c002aff4734",
    "plan/ycsb_e_latest_ro/2147483653/1": "72f50d1b47ec401d5cc95daf538b768a",
    "keys/weblogs/8589934599": "1c707a3b31bd5658f6c46efaf3c9b739",
    "plan/ycsb_e_latest_ro/8589934599/0": "171898171c586b0549c5eb25abd6f5e3",
    "plan/ycsb_e_latest_ro/8589934599/1": "580b6dfa4aa7ee35f0342ed677ae5ac7",
}
MIXES = {"ycsb_c_zipf": "maps", "ycsb_e_latest_ro": "weblogs"}
SEEDS = (1, 2**31 + 5, 2**33 + 7)


def _digest(*arrs) -> str:
    m = hashlib.sha256()
    for a in arrs:
        a = np.ascontiguousarray(a)
        m.update(a.dtype.str.encode())
        m.update(a.tobytes())
    return m.hexdigest()[:32]


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("stream", (0, 1))
def test_existing_mixes_plan_as_before(mix, seed, stream):
    gen = MIXES[mix]
    keys = catalog.load_generator(gen)(20_000, seed, 0)
    assert _digest(keys) == GOLDEN[f"keys/{gen}/{seed}"]
    spec = json.loads((REPO / "bench/traffic" / f"{mix}.json").read_text())
    # the window's stream (2 s) and the warm-up's (1 s, at the rate the
    # harness gave it then: 4 x 256 + 64)
    seconds, rate = (2.0, None) if stream == 0 else (1.0, 4 * 256 + 64)
    p = traffic.make_plan(spec, traffic.KeySpace.of(keys), seed, seconds,
                          rate=rate, stream=stream)
    codes = np.array([PARENT_KINDS.index(k) for k in p.kinds], np.int8)
    assert _digest(p.due, codes[p.kind], p.lo, p.hi) == GOLDEN[
        f"plan/{mix}/{seed}/{stream}"]
    assert np.all(p.val == -1)


INGEST_SCANS = {"ops": {"scan": 0.9, "insert": 0.1},
                "keys": {"dist": "latest", "theta": 0.99},
                "scan_rows": [1, 100], "page_size": 256,
                "inserts": {"order": "newest"},
                "arrival": {"process": "poisson"}, "rate_ops_s": 200}
INGEST_GETS = {"ops": {"get": 0.9, "insert": 0.1},
               "keys": {"dist": "latest", "theta": 0.99},
               "inserts": {"order": "uniform"},
               "arrival": {"process": "poisson"}, "rate_ops_s": 200}


def _add_cell(root, name, config, mix, service=None, generator=None):
    """A cell of the tiny checkout from a configuration and a mix, as
    files and entries alone."""
    cfg = json.loads((root / "bench/configs/weblogs200m.json").read_text())
    cfg.update(name=config)
    if service is not None:
        cfg["service"] = service
    if generator is not None:
        cfg["generator"] = generator
    (root / f"bench/configs/{config}.json").write_text(json.dumps(cfg))
    (root / f"bench/traffic/{name}.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": f"{config}.{name}", "config": config,
                               "traffic": name, "chips": 1,
                               "why": "a tiny cell of the tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return f"{config}.{name}"


def _ingest(tmp_path, which):
    root = tiny_root(tmp_path)
    if which == "uniform scans":
        # keys not yet sent lie inside the scans' ranges
        return root, _add_cell(
            root, "ingest_uniform", "weblogs_tiny",
            dict(INGEST_SCANS, inserts={"order": "uniform"}))
    if which == "scans":
        # a small delta: compactions commit in the window, and each
        # moves the frame (the newest keys lie past the last base key)
        return root, _add_cell(root, "ingest_scans", "weblogs_tiny",
                               INGEST_SCANS, {"delta_capacity": 16})
    return root, _add_cell(root, "ingest_gets", "weblogs_tiny", INGEST_GETS)


def test_key_space_counts_in_insertion_order():
    final = np.arange(10.0)
    space = traffic.KeySpace(final, np.array([7, 2, 5]), 7)
    assert list(space.index_of(np.arange(10))) == [0, 1, 3, 4, 6, 8, 9,
                                                   7, 2, 5]
    assert list(space.base()) == [0, 1, 3, 4, 6, 8, 9]
    later = traffic.KeySpace(final, np.array([7, 2, 5]), 8)
    assert list(later.base()) == [0, 1, 3, 4, 6, 7, 8, 9]
    with pytest.raises(ValueError):
        space.index_of(np.array([10]))


def test_latest_follows_the_ingest():
    final = np.arange(100_000, dtype=np.float64)
    pool = traffic.hold_back(INGEST_SCANS, final, 2000, 1)
    assert list(pool[:2]) == [98_000, 98_001]
    space = traffic.KeySpace(final, pool, final.size - pool.size)
    plan = traffic.make_plan(INGEST_SCANS, space, 3, 10.0)
    ins = plan.kind == plan.kinds.index("insert")
    assert plan.kinds == ("scan", "insert") and ins.sum() == 200
    # inserts come in pool order, each with its row id as its value
    assert np.array_equal(plan.lo[ins], final[98_000:98_200])
    assert np.array_equal(plan.val[ins], np.arange(98_000, 98_200))
    # scans count back from the newest key inserted before them
    scans = ~ins
    stored = 98_000 + np.cumsum(ins) - ins
    assert np.all(plan.hi[scans] <= stored[scans] - 1)
    back = stored[scans] - plan.lo[scans]
    assert np.median(back) < 1000 and np.mean(back < 200) > 0.4
    uniform = traffic.hold_back(INGEST_GETS, final, 2000, 1)
    assert np.unique(uniform).size == 2000
    assert not np.all(np.diff(uniform) > 0)


@pytest.mark.parametrize("which", ("scans", "gets", "uniform scans"))
def test_insert_mix_is_correct(tmp_path, monkeypatch, which):
    root, cell = _ingest(tmp_path, which)
    res = run(root, cell, monkeypatch, seed=2**31 + 11)
    assert res["correct"] and res["failed"] == 0, res["checks"]
    read = "get_wrong" if which == "gets" else "scan_wrong"
    assert set(res["checks"]) == {read, "insert_wrong", "insert_lost",
                                  "unanswered"}
    assert set(res["metrics"]) == {"ops_per_s", "setup_s"}


def _ack_without_staging(monkeypatch):
    from repro.index_service import IndexService

    orig, calls = IndexService.insert, [0]

    def insert(self, keys, vals=None):
        calls[0] += 1
        if calls[0] % 10 == 0:
            return int(np.size(keys))
        return orig(self, keys, vals)
    monkeypatch.setattr(IndexService, "insert", insert)


def _reads_ignore_the_delta(monkeypatch):
    import jax.numpy as jnp
    from repro.index_service import IndexService
    from repro.index_service.delta import DeltaBuffer, combine_for_device
    from repro.index_service.scan import pin_view

    def capture(self):
        snap, empty = self._mgr.current(), DeltaBuffer(1)
        dk, dp = combine_for_device(None, empty, snap.keys.normalize)
        return snap, None, empty, jnp.asarray(dk), jnp.asarray(dp)

    def scan_plane(self):
        snap = self._mgr.current()
        view = pin_view(snap, None, DeltaBuffer(1))
        slab, n = self._plane.build_scan_slab(
            (snap,), view, snap.keys.norm, snap.keys.normalize)
        return snap, slab, n
    monkeypatch.setattr(IndexService, "_capture", capture)
    monkeypatch.setattr(IndexService, "_scan_plane_cached", scan_plane)


def _rows_before_their_insert(monkeypatch):
    from bench import harness

    orig = harness.build

    def build(cell, seed, pool, split):
        space, svc, proxy, fe = orig(cell, seed, pool, split)
        # the service holds every pooled key before it is sent
        svc.insert(space.final[space.pool], space.pool)
        return space, svc, proxy, fe
    monkeypatch.setattr(harness, "build", build)


@pytest.mark.parametrize("which,fault,caught", [
    ("gets", _ack_without_staging, "insert_lost"),
    ("scans", _reads_ignore_the_delta, "scan_wrong"),
    ("gets", _reads_ignore_the_delta, "get_wrong"),
    ("uniform scans", _rows_before_their_insert, "scan_wrong"),
])
def test_write_faults_are_not_correct(tmp_path, monkeypatch, which, fault,
                                      caught):
    root, cell = _ingest(tmp_path, which)
    fault(monkeypatch)
    res = run(root, cell, monkeypatch, seed=2**31 + 13)
    assert not res["correct"]
    assert res["checks"][caught]["value"] > 0


CONTAINS = '''"""contains: presence of a stored key, or of one just past it."""

import numpy as np

from bench import reference, traffic

ADDS_KEYS = False


def draw(mix, count, rng):
    return rng.random(count), rng.random(count) < 0.5


def place(mix, drawn, space, stored):
    u, miss = drawn
    keys = space.final[space.index_of(traffic.items_of(mix["keys"], u,
                                                       stored))]
    return np.where(miss, np.nextafter(keys, np.inf), keys), None, None


def args(plan, i, page_size):
    return (plan.lo[i:i + 1],)


def answer(result):
    return bool(result[0])


def warm_count(mix, max_round):
    return max_round


def warm_rounds(idx, max_round):
    return [np.resize(idx, k) for k in range(1, max_round + 1)]


def check(oracle, win, idx, service):
    ok = idx[win.answered_ok()[idx]]
    found = np.array([win.answers[i] for i in ok], bool)
    want = reference._has(oracle.base, win.plan.lo[ok])
    return {"contains_wrong": int(np.sum(found != want))}
'''

UNIFORM = '''"""uniform: keys spread evenly over [0, 1e6)."""

import numpy as np


def generate(n, seed, shape_seed):
    return np.unique(np.random.default_rng(seed).uniform(0, 1e6, n))
'''


def test_new_kind_and_generator_from_files_alone(tmp_path, monkeypatch):
    root = tiny_root(tmp_path)
    (root / "bench/ops/contains.py").write_text(CONTAINS)
    (root / "bench/generators/uniform.py").write_text(UNIFORM)
    cell = _add_cell(root, "contains_zipf", "uniform_tiny", {
        "ops": {"contains": 0.5, "get": 0.5},
        "keys": {"dist": "scrambled_zipfian", "theta": 0.99},
        "arrival": {"process": "poisson"}, "rate_ops_s": 150},
        generator="uniform")
    res = run(root, cell, monkeypatch)
    assert res["correct"] and res["attempted"] == 150
    assert set(res["checks"]) == {"get_wrong", "contains_wrong",
                                  "unanswered"}


def test_span_table_and_idle_split_in_the_window():
    # window 100-200 ns; the device runs 90-110 and 150-160: idle
    # 110-150 and 160-200 inside the window
    t = "/host:CPU#1"
    events = {
        "devices": {"/device:TPU:0": [["jit_f", 90, 20], ["jit_f", 150, 10]]},
        "ops": {"/device:TPU:0": [["a", 90, 20], ["a", 150, 10]]},
        "host": [
            ["bench.window", 100, 100, "/host:CPU#0"],
            ["frontend.round", 80, 60, t],        # 80-140, clipped 100-140
            ["service.get", 95, 40, t],           # 95-135 inside the round
            ["service.prepare", 95, 10, t],       # 95-105
            ["service.readback", 110, 25, t],     # 110-135
            ["frontend.wait", 140, 45, t],        # 140-185
            ["frontend.round", 190, 20, t],       # 190-210, clipped 190-200
        ],
    }
    red = trace_reduce.reduce_trace(events)
    spans = red["spans"]
    assert spans["frontend.round"]["count"] == 2
    assert spans["frontend.round"]["total_s"] == pytest.approx(50e-9)
    # self: 100-140 less service.get's 100-135, plus 190-200
    assert spans["frontend.round"]["self_s"] == pytest.approx(15e-9)
    # service.get's own time: 105-110, between its two steps
    assert spans["service.get"]["self_s"] == pytest.approx(5e-9)
    assert spans["frontend.round/service.get"]["count"] == 1
    assert spans["service.get/service.prepare"]["total_s"] == pytest.approx(
        5e-9)
    assert spans["service.get/service.readback"]["total_s"] == \
        pytest.approx(25e-9)
    assert "bench.window" not in spans
    assert red["busy_s"] == pytest.approx(20e-9)
    # idle 110-150 (under the round to 140, then the wait) and 160-200
    # (the wait to 185, nothing to 190, the round to 200)
    assert red["idle_host_s"] == pytest.approx((30 + 10) * 1e-9)
    assert red["idle_unattributed_s"] == pytest.approx(5e-9)
    # each gap named by the innermost span open at its middle
    assert red["idle_gaps"] == [["service.readback", pytest.approx(40e-9)],
                                ["frontend.wait", pytest.approx(40e-9)]]
    assert red["device_ops"] == [["a", pytest.approx(10e-9)]]

    from bench.metrics_util import counter_ratio, idle_pct, span_ms
    rec = {"trace": dict(red, window_s=100e-9),
           "counters": {"frontend.queue_wait_s": 0.5,
                        "frontend.enqueued": 100, "frontend.read_lanes": 0}}
    assert span_ms(rec, "frontend.round", own=True) == pytest.approx(7.5e-6)
    assert span_ms(rec, "service.scan_batch/service.prepare") is None
    assert counter_ratio(rec, "frontend.queue_wait_s", "frontend.enqueued",
                         1e3) == pytest.approx(5.0)
    assert counter_ratio(rec, "frontend.padded_lanes",
                         "frontend.read_lanes") is None
    assert idle_pct(rec, "idle_unattributed_s") == pytest.approx(5.0)


def _frame_of_its_own(monkeypatch):
    from repro.index_service import IndexService

    orig = IndexService.__init__

    def init(self, keys, config=None, vals=None, **kw):
        # one more key far below the first: the service's frame starts
        # there, and no scan of the mix reaches it
        low = keys[0] - (keys[-1] - keys[0])
        orig(self, np.concatenate([[low], keys]), config,
             vals=np.concatenate([[-2], vals]), **kw)
    monkeypatch.setattr(IndexService, "__init__", init)


def test_scans_in_a_frame_the_service_moved_are_wrong(tmp_path,
                                                       monkeypatch):
    root = tiny_root(tmp_path)
    _frame_of_its_own(monkeypatch)
    res = run(root, "weblogs200m.scan_latest", monkeypatch,
              seed=2**31 + 17)
    assert not res["correct"]
    assert res["checks"]["scan_wrong"]["value"] > 0


def test_frames_are_admitted_by_the_keys():
    base = np.array([10.0, 20.0, 30.0, 40.0])
    log = reference.WriteLog(
        keys=np.array([45.0, 5.0, 50.0]), vals=np.array([4, 5, 6]),
        sent=np.array([1.0, 2.0, 3.0]), done=np.array([1.5, 2.5, 3.5]),
        acked=np.ones(3, bool))
    # built over 20-40; 10 was stored before the window opened
    claims = [(-np.inf, -np.inf, 10.0, 40.0),   # 10 stored: kept
              (0.5, 0.7, 10.0, 45.0),           # 45 not yet sent: built
              (2.2, 2.4, 5.0, 45.0),            # both sent: kept
              (3.2, 3.4, 10.0, 50.0),           # 10 stored, 50 sent: kept
              (4.0, 4.2, 20.0, 50.0),           # kept
              (5.0, 5.2, 15.0, 50.0)]           # 15 never sent: built
    oracle = reference.Oracle(base, np.arange(4), log, claims,
                              built=(20.0, 40.0))
    assert oracle.frames[:, 2:].tolist() == [
        [10.0, 40.0], [20.0, 40.0], [5.0, 45.0], [10.0, 50.0],
        [20.0, 50.0], [20.0, 40.0]]
    assert reference.Oracle(base, np.arange(4)).frames.tolist() == [
        [-np.inf, -np.inf, 10.0, 40.0]]


def test_must_and_may_bracket_reads_in_flight():
    base = np.array([10.0, 20.0, 30.0])
    log = reference.WriteLog(
        keys=np.array([15.0, 25.0]), vals=np.array([7, 8]),
        sent=np.array([1.0, 2.0]), done=np.array([1.5, np.nan]),
        acked=np.array([True, False]))
    oracle = reference.Oracle(base, np.array([0, 1, 2]), log)
    # sent at 3 (15 acknowledged), answered at 4 (25 sent, unanswered)
    q = np.array([15.0, 25.0, 25.0, 26.0, 26.0, 26.0])
    # 25 lies below three keys of either set: the 2 is wrong
    rank = np.array([1, 3, 2, 3, 4, 2])
    found = np.array([True, True, False, False, False, False])
    sent, done = np.full(6, 3.0), np.full(6, 4.0)
    assert reference.gets_wrong_between(oracle, q[:5], rank[:5],
                                        found[:5], sent[:5], done[:5]) == 1
    # 15 missed although acknowledged before the read was sent
    assert reference.gets_wrong_between(oracle, q[:1], rank[:1], ~found[:1],
                                        sent[:1], done[:1]) == 1
    # a read answered before 15 was sent may not see it
    assert reference.gets_wrong_between(oracle, q[:1], rank[:1], found[:1],
                                        np.array([0.1]),
                                        np.array([0.5])) == 1


def test_late_answers_fail_and_are_not_wrong(tmp_path, monkeypatch):
    # a service slower than the offered rate: requests age past the
    # frontend's queue deadline and fail as late, which is no wrong answer
    import time

    from repro.index_service import IndexService

    root = tiny_root(tmp_path)
    cfg = json.loads((root / "bench/configs/weblogs200m.json").read_text())
    cfg["frontend"]["request_deadline_s"] = 0.05
    (root / "bench/configs/weblogs200m.json").write_text(json.dumps(cfg))
    orig = IndexService.scan_batch

    def slow(self, lo, hi, page_size=256):
        time.sleep(0.02)
        return orig(self, lo, hi, page_size)
    monkeypatch.setattr(IndexService, "scan_batch", slow)
    res = run(root, "weblogs200m.scan_latest", monkeypatch)
    assert res["failed"] > 0
    assert res["correct"], res["checks"]


def test_other_timeouts_are_wrong(tmp_path, monkeypatch):
    # only the frontend's own deadline makes a failed read late; any
    # other error, a service's TimeoutError too, says the wrong thing
    from repro.index_service import IndexService

    root = tiny_root(tmp_path)
    orig, calls = IndexService.scan_batch, [0]

    def timing_out(self, lo, hi, page_size=256):
        calls[0] += 1   # the warm-up's 65 calls pass
        if calls[0] > 100 and calls[0] % 5 == 0:
            raise TimeoutError("a service's own timeout")
        return orig(self, lo, hi, page_size)
    monkeypatch.setattr(IndexService, "scan_batch", timing_out)
    res = run(root, "weblogs200m.scan_latest", monkeypatch)
    assert res["failed"] > 0 and not res["correct"]
    assert res["checks"]["scan_wrong"]["value"] == res["failed"]
