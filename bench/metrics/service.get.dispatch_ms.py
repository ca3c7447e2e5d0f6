"""Mean ms of the ``service.dispatch`` step inside ``service.get`` in the
traced window (call into the jitted lookup)."""

from bench.metrics_util import span_ms


def read(rec):
    return span_ms(rec, "service.get/service.dispatch")
