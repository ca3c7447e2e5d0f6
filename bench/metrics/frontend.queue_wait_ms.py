"""Mean time a request waited in the frontend's queue before its round
started: the window's change in ``frontend.queue_wait_s`` over that in
``frontend.enqueued``, in ms."""

from bench.metrics_util import counter_ratio


def read(rec):
    return counter_ratio(rec, "frontend.queue_wait_s", "frontend.enqueued",
                         1e3)
