"""The one traffic generator: fixed work per seed, YCSB's
distributions, arrivals inside the window."""

import numpy as np
import pytest

from bench_tiny import REPO  # noqa: F401  (puts the checkout on sys.path)
from bench import traffic

GET_MIX = {"ops": {"get": 1.0},
           "keys": {"dist": "scrambled_zipfian", "theta": 0.99},
           "arrival": {"process": "poisson"}, "rate_ops_s": 1000}
SCAN_MIX = {"ops": {"scan": 1.0}, "keys": {"dist": "latest", "theta": 0.99},
            "scan_rows": [1, 100], "arrival": {"process": "poisson"},
            "rate_ops_s": 500}
KEYS = np.sort(np.random.default_rng(0).random(50_000)) * 1e6
SPACE = traffic.KeySpace.of(KEYS)


def test_zeta_matches_ycsb_constant():
    # YCSB's ScrambledZipfianGenerator hard-codes zeta(1e10, 0.99)
    assert traffic.zeta(traffic.YCSB_ITEM_COUNT, 0.99) == pytest.approx(
        26.46902820178302, rel=1e-11)


def test_same_seed_same_plan_and_every_seed_the_same_work():
    a = traffic.make_plan(GET_MIX, SPACE, 2**31 + 9, 4.0)
    b = traffic.make_plan(GET_MIX, SPACE, 2**31 + 9, 4.0)
    c = traffic.make_plan(GET_MIX, SPACE, 11, 4.0)
    assert np.array_equal(a.lo, b.lo) and np.array_equal(a.due, b.due)
    assert a.size == c.size == 4000
    assert not np.array_equal(a.lo, c.lo)
    assert np.all(np.diff(a.due) >= 0) and 0 <= a.due[0] and a.due[-1] < 4.0


def test_zipfian_skew_and_scramble():
    plan = traffic.make_plan(GET_MIX, SPACE, 3, 20.0)
    _, counts = np.unique(plan.lo, return_counts=True)
    # a few hot keys take a large share; the hot keys are spread out
    top = np.sort(counts)[::-1]
    assert top[:10].sum() > 0.1 * plan.size
    hot = plan.lo[np.isin(plan.lo, np.unique(plan.lo)[counts >= top[4]])]
    assert np.ptp(hot) > 0.1 * np.ptp(KEYS)
    assert np.all(np.isin(plan.lo, KEYS))


def test_latest_scans_hit_the_newest_keys():
    plan = traffic.make_plan(SCAN_MIX, SPACE, 5, 4.0)
    lo_i = np.searchsorted(KEYS, plan.lo)
    hi_i = np.searchsorted(KEYS, plan.hi)
    rows = hi_i - lo_i
    assert rows.min() >= 1 and rows.max() <= 100
    assert np.median(lo_i) > 0.99 * KEYS.size
    assert hi_i.max() < KEYS.size


def test_unknown_parameters_fail():
    with pytest.raises(ValueError):
        traffic.make_plan(dict(GET_MIX, ops={"put": 1.0}), SPACE, 1, 1.0)
    with pytest.raises(ValueError):
        traffic.make_plan(dict(GET_MIX, keys={"dist": "uniform"}), SPACE, 1,
                          1.0)
    with pytest.raises(ValueError):
        traffic.make_plan(dict(GET_MIX, arrival={"process": "closed"}), SPACE,
                          1, 1.0)
