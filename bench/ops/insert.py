"""``insert``: one key of the pool the build held back (`traffic.KeySpace`),
in the mix's insert order, with its row id in the final key set as its
value.  The answer is the number of keys staged: an acknowledgement that
is not one is ``insert_wrong``.  Once the window has closed and every
answer is in, one batched ``get`` of every acknowledged key has to find
each at its rank in the final key set: one that it misses or misranks is
``insert_lost``.  An insert answered with an error is no fault of
itself: the reference counts it only among the keys that may be
stored."""

import numpy as np

from bench import reference

ADDS_KEYS = True


def draw(mix, count, rng):
    return None


def place(mix, drawn, space, stored):
    at = space.index_of(stored)
    return space.final[at], None, at


def args(plan, i, page_size):
    return (plan.lo[i:i + 1], plan.val[i:i + 1])


def answer(result):
    return int(result)


def warm_count(mix, max_round):
    """``inserts.warm`` of the mix, or one: an insert stages its key on
    the host, and the reads after it see the delta at the size the
    warm-up leaves."""
    return int(mix["inserts"].get("warm", 1))


def warm_rounds(idx, max_round):
    """Every warm-up insert once, in order, one to a round."""
    return [idx[k:k + 1] for k in range(idx.size)]


def check(oracle, win, idx, service):
    acked = idx[win.answered_ok()[idx]]
    wrong = sum(win.answers[i] != 1 for i in acked)
    keys = win.plan.lo[acked]
    lost = 0
    if keys.size:
        rank, found = service.get(keys)
        lost = reference.lost_after(oracle, keys, np.asarray(rank),
                                    np.asarray(found))
    return {"insert_wrong": int(wrong), "insert_lost": int(lost)}
