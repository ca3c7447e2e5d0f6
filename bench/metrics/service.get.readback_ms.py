"""Mean ms of the ``service.readback`` step inside ``service.get`` in the
traced window (wait for the device and copy its ranks back)."""

from bench.metrics_util import span_ms


def read(rec):
    return span_ms(rec, "service.get/service.readback")
