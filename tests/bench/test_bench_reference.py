"""The copied reference against the service at a tiny size, and its
controls: one precision lower, the same comparison fails."""

import numpy as np

from bench_tiny import REPO  # noqa: F401  (puts the checkout on sys.path)
from bench import datagen, reference, traffic


def test_oracle_agrees_with_index_service_under_writes():
    from repro.index_service import IndexService

    keys = datagen.maps(20_000, seed=4, shape_seed=0)
    vals = np.arange(keys.size, dtype=np.int64)
    svc = IndexService(keys, vals=vals)
    oracle = reference.Oracle(keys, vals)
    rng = np.random.default_rng(4)
    dels = rng.choice(keys[1:-1], 300, replace=False)
    ins = np.setdiff1d(rng.uniform(keys[0], keys[-1], 300), keys)
    ins_vals = np.arange(ins.size) + keys.size
    svc.insert(ins, ins_vals)
    svc.delete(dels)
    oracle.apply(ins, ins_vals, dels)

    q = np.concatenate([keys[::37], ins, dels,
                        rng.uniform(keys[0], keys[-1], 500)])
    rank, found = svc.get(q)
    assert reference.gets_wrong(oracle, q, rank, found) == 0
    assert reference.gets_wrong(oracle, q, rank + (q == q[0]), found) > 0

    for _ in range(20):
        i = int(rng.integers(0, keys.size - 400))
        lo, hi = float(keys[i]), float(keys[i + int(rng.integers(1, 400))])
        k, v, live = (np.asarray(a) for a in svc.scan_batch(lo, hi, 256))
        assert not reference.scan_wrong(oracle, lo, hi, k[live], v[live])
        assert reference.scan_wrong(oracle, lo, hi, k[live][1:],
                                    v[live][1:])


def test_controls_fail_where_float32_collides():
    # 2M weblog timestamps are dense enough at their peaks that float32
    # images tie, as the full 200M are everywhere
    keys = datagen.weblogs(2_000_000, seed=1, shape_seed=0)
    oracle = reference.Oracle(keys, np.arange(keys.size))
    gets = traffic.make_plan(
        {"ops": {"get": 1.0},
         "keys": {"dist": "scrambled_zipfian", "theta": 0.99},
         "arrival": {"process": "poisson"}, "rate_ops_s": 2000},
        keys, 1, 1.0)
    rank, found = oracle.get_control(gets.lo)
    assert reference.gets_wrong(oracle, gets.lo, oracle.rank(gets.lo),
                                oracle.member(gets.lo)) == 0
    assert reference.gets_wrong(oracle, gets.lo, rank, found) > 0

    scans = traffic.make_plan(
        {"ops": {"scan": 1.0}, "keys": {"dist": "latest", "theta": 0.99},
         "scan_rows": [1, 100], "arrival": {"process": "poisson"},
         "rate_ops_s": 200}, keys, 1, 1.0)
    wrong = [reference.scan_wrong(oracle, lo, hi,
                                  *oracle.scan_control(lo, hi))
             for lo, hi in zip(scans.lo, scans.hi)]
    right = [reference.scan_wrong(oracle, lo, hi,
                                  *oracle.frame_rows(lo, hi))
             for lo, hi in zip(scans.lo, scans.hi)]
    assert sum(wrong) > 0 and sum(right) == 0
