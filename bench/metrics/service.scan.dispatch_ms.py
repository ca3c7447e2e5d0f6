"""Mean ms of the ``service.dispatch`` step inside ``service.scan_batch`` in
the traced window (the one call of the fused scan program)."""

from bench.metrics_util import span_ms


def read(rec):
    return span_ms(rec, "service.scan_batch/service.dispatch")
