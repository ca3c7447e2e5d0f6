"""``get``: one stored key, drawn by the mix's key distribution; the
answer is the exact lower-bound rank and presence, judged between what
must and what may be stored when it was asked (`bench.reference`)."""

import numpy as np

from bench import reference, traffic

ADDS_KEYS = False


def draw(mix, count, rng):
    return rng.random(count)


def place(mix, u, space, stored):
    items = traffic.items_of(mix["keys"], u, stored)
    return space.final[space.index_of(items)], None, None


def args(plan, i, page_size):
    return (plan.lo[i:i + 1],)


def answer(result):
    rank, found = result
    return int(rank[0]), bool(found[0])


def warm_count(mix, max_round):
    return max_round


def warm_rounds(idx, max_round):
    """Every round size up to the frontend's largest: each pads to its
    own bucket."""
    return [np.resize(idx, k) for k in range(1, max_round + 1)]


def check(oracle, win, idx, service):
    ok = idx[win.answered_ok()[idx]]
    rank = np.array([win.answers[i][0] for i in ok], np.int64)
    found = np.array([win.answers[i][1] for i in ok], bool)
    wrong = reference.gets_wrong_between(
        oracle, win.plan.lo[ok], rank, found, win.sent[ok], win.done[ok])
    # an answer that came back as an error says the wrong thing, unless
    # it says that it came too late
    failed = win.error[idx] & ~win.refused[idx] & ~win.late[idx]
    wrong += int(np.sum(failed))
    return {"get_wrong": int(wrong)}


def control(oracle, plan, idx):
    """The reference answering in float32 (`bench/control.py`)."""
    q, t = plan.lo[idx], np.zeros(idx.size)
    return {"get_wrong": reference.gets_wrong_between(
        oracle, q, *oracle.get_control(q), t, t)}
