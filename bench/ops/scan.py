"""``scan``: the rows of ``[key[i], key[i + length])`` over the stored
keys, ``length`` uniform in the mix's ``scan_rows`` and ``key[i]`` by
its key distribution; the answer (rows on the host) is judged between
what must and what may be stored when it was asked
(`bench.reference`)."""

import jax
import numpy as np

from bench import reference, traffic

ADDS_KEYS = False
WARM_SCANS = 64   # scans the warm-up sends, four to a round


def draw(mix, count, rng):
    r0, r1 = mix["scan_rows"]
    length = rng.integers(int(r0), int(r1) + 1, count)
    return length, rng.random(count)


def place(mix, drawn, space, stored):
    length, u = drawn
    r1 = int(mix["scan_rows"][1])
    start = space.index_of(traffic.items_of(mix["keys"], u,
                                            stored - r1 - 1))
    # rows of the final key set, some perhaps not inserted yet
    start = np.minimum(start, space.final.size - 1 - r1)
    return space.final[start], space.final[start + length], None


def args(plan, i, page_size):
    return (float(plan.lo[i]), float(plan.hi[i]), page_size)


def answer(result):
    keys, vals, live = jax.device_get(result)
    return keys[live], vals[live]


def warm_count(mix, max_round):
    return WARM_SCANS


def warm_rounds(idx, max_round):
    return np.array_split(idx[:WARM_SCANS],
                          max(1, min(WARM_SCANS, idx.size) // 4))


def check(oracle, win, idx, service):
    ok = idx[win.answered_ok()[idx]]
    plan = win.plan
    wrong = reference.scans_wrong_between(
        oracle, plan.lo[ok], plan.hi[ok], win.sent[ok], win.done[ok],
        [win.answers[i] for i in ok])
    failed = win.error[idx] & ~win.refused[idx] & ~win.late[idx]
    wrong += int(np.sum(failed))
    return {"scan_wrong": int(wrong)}


def control(oracle, plan, idx):
    """The reference answering in a bfloat16 frame (`bench/control.py`)."""
    lo, hi, t = plan.lo[idx], plan.hi[idx], np.zeros(idx.size)
    return {"scan_wrong": reference.scans_wrong_between(
        oracle, lo, hi, t, t,
        [oracle.scan_control(a, b) for a, b in zip(lo, hi)])}
