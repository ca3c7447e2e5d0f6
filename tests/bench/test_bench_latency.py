"""The latency statistic: due to answer in hand, failures over any
limit, and a stall injected into a synthetic window moves the tail."""

import numpy as np
import pytest

from bench_tiny import REPO  # noqa: F401  (puts the checkout on sys.path)
from bench import catalog, loop
from bench.metrics_util import tail_ms


def _window(done_after, seconds=10.0, n=5000, fail=()):
    due = np.linspace(0, seconds, n, endpoint=False)
    plan = loop.Plan(due=due, kind=np.zeros(n, np.int8), lo=due.copy(),
                     hi=np.full(n, np.nan), val=np.full(n, -1, np.int64),
                     kinds=("get",))
    error = np.zeros(n, bool)
    error[list(fail)] = True
    return loop.Window(plan=plan, seconds=seconds, t0=0.0, sent=due.copy(),
                       done=due + done_after, refused=np.zeros(n, bool),
                       error=error, answers=[None] * n,
                       late=np.zeros(n, bool))


def test_latency_runs_from_due_to_answer():
    win = _window(np.full(5000, 0.002))
    assert np.allclose(win.latency(), 0.002)
    assert abs(tail_ms(win.latency(), 95) - 2.0) < 1e-9


def test_stall_moves_the_tail():
    base = np.full(5000, 0.002)
    due = np.linspace(0, 10.0, 5000, endpoint=False)

    def stalled(length):
        # the server stalls from t=4 s: everything due in the stall
        # waits for its end, whatever the generator sent meanwhile
        hit = (due >= 4.0) & (due < 4.0 + length)
        return _window(np.where(hit, 4.0 + length - due + 0.002, base))

    calm = _window(base).latency()
    assert abs(tail_ms(calm, 95) - 2.0) < 1e-9
    # a stall over 3% of the window moves the p99 and not the p95;
    # one over 8% moves both
    short, long_ = stalled(0.3).latency(), stalled(0.8).latency()
    assert tail_ms(short, 99) > 100 and abs(tail_ms(short, 95) - 2.0) < 1e-9
    assert tail_ms(long_, 95) > 100


@pytest.mark.parametrize("metric,kind", [("read_p99_ms", "get"),
                                         ("scan_p99_ms", "scan")])
def test_p99_readers_see_a_stall_the_p95_misses(metric, kind):
    due = np.linspace(0, 10.0, 5000, endpoint=False)
    hit = (due >= 4.0) & (due < 4.3)
    lat = np.where(hit, 4.3 - due + 0.002, 0.002)
    read = catalog.load_reader(metric)
    assert read({"latency_s": {kind: lat}}) > 100
    assert read({"latency_s": {}}) is None
    assert abs(tail_ms(lat, 95) - 2.0) < 1e-9


def test_failed_requests_count_over_any_limit():
    win = _window(np.full(5000, 0.002), fail=range(300))
    assert tail_ms(win.latency(), 95) >= (win.seconds + loop.GRACE_S) * 1e3
    assert win.answered_ok().sum() == 5000 - 300


def test_tail_is_a_time_some_request_took():
    lat = np.arange(1, 201) * 1e-3
    assert tail_ms(lat, 95) == 190.0
    assert tail_ms(lat, 99) == 198.0
    assert tail_ms(np.empty(0), 95) is None
