"""Mean ms of the ``service.refine`` step inside ``service.get`` in the
traced window (the f64 refinement and the delta's counts)."""

from bench.metrics_util import span_ms


def read(rec):
    return span_ms(rec, "service.get/service.refine")
