"""The copied reference against the service at a tiny size, and its
controls: one precision lower, the same comparison fails."""

import numpy as np

from bench_tiny import REPO  # noqa: F401  (puts the checkout on sys.path)
from bench import catalog, reference, traffic


def test_oracle_agrees_with_index_service_under_writes():
    from repro.index_service import IndexService

    keys = catalog.load_generator("maps")(20_000, seed=4, shape_seed=0)
    vals = np.arange(keys.size, dtype=np.int64)
    svc = IndexService(keys, vals=vals)
    rng = np.random.default_rng(4)
    dels = rng.choice(keys[1:-1], 300, replace=False)
    ins = np.setdiff1d(rng.uniform(keys[0], keys[-1], 300), keys)
    ins_vals = np.arange(ins.size) + keys.size
    svc.insert(ins, ins_vals)
    svc.delete(dels)
    # the reference over the live keys: base minus deletes plus inserts
    keep = ~np.isin(keys, dels)
    live = np.concatenate([keys[keep], ins])
    order = np.argsort(live)
    oracle = reference.Oracle(
        live[order], np.concatenate([vals[keep], ins_vals])[order])

    q = np.concatenate([keys[::37], ins, dels,
                        rng.uniform(keys[0], keys[-1], 500)])
    rank, found = svc.get(q)
    t = np.zeros(q.size)
    assert reference.gets_wrong_between(oracle, q, rank, found, t, t) == 0
    assert reference.gets_wrong_between(oracle, q, rank + (q == q[0]),
                                        found, t, t) > 0

    one = np.zeros(1)
    for _ in range(20):
        i = int(rng.integers(0, keys.size - 400))
        lo, hi = float(keys[i]), float(keys[i + int(rng.integers(1, 400))])
        k, v, live = (np.asarray(a) for a in svc.scan_batch(lo, hi, 256))
        assert reference.scans_wrong_between(
            oracle, [lo], [hi], one, one, [(k[live], v[live])]) == 0
        assert reference.scans_wrong_between(
            oracle, [lo], [hi], one, one, [(k[live][1:], v[live][1:])]) == 1


def test_controls_fail_where_float32_collides():
    # 2M weblog timestamps are dense enough at their peaks that float32
    # images tie, as the full 200M are everywhere; each control goes
    # through the comparison that decides ``correct``
    keys = catalog.load_generator("weblogs")(2_000_000, seed=1,
                                               shape_seed=0)
    space = traffic.KeySpace.of(keys)
    oracle = reference.Oracle(keys, np.arange(keys.size))
    ops = catalog.load_kinds(["get", "scan"])
    gets = traffic.make_plan(
        {"ops": {"get": 1.0},
         "keys": {"dist": "scrambled_zipfian", "theta": 0.99},
         "arrival": {"process": "poisson"}, "rate_ops_s": 2000},
        space, 1, 1.0)
    t = np.zeros(gets.size)
    assert reference.gets_wrong_between(
        oracle, gets.lo, np.searchsorted(keys, gets.lo),
        np.isin(gets.lo, keys), t, t) == 0
    assert ops["get"].control(oracle, gets,
                              np.arange(gets.size))["get_wrong"] > 0

    scans = traffic.make_plan(
        {"ops": {"scan": 1.0}, "keys": {"dist": "latest", "theta": 0.99},
         "scan_rows": [1, 100], "arrival": {"process": "poisson"},
         "rate_ops_s": 200}, space, 1, 1.0)
    t = np.zeros(scans.size)
    right = reference.scans_wrong_between(
        oracle, scans.lo, scans.hi, t, t,
        [oracle.frame_rows(lo, hi) for lo, hi in zip(scans.lo, scans.hi)])
    assert right == 0
    assert ops["scan"].control(oracle, scans,
                               np.arange(scans.size))["scan_wrong"] > 0
