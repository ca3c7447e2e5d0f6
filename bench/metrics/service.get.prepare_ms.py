"""Mean ms of the ``service.prepare`` step inside ``service.get`` in the
traced window (capture the state, normalize the queries, upload them)."""

from bench.metrics_util import span_ms


def read(rec):
    return span_ms(rec, "service.get/service.prepare")
