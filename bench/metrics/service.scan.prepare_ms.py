"""Mean ms of the ``service.prepare`` step inside ``service.scan_batch`` in
the traced window (the scan plane, the page bound, the program for it)."""

from bench.metrics_util import span_ms


def read(rec):
    return span_ms(rec, "service.scan_batch/service.prepare")
