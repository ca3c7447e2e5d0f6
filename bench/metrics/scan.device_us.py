"""Device busy time in the traced window per fused scans (``scan_batch``) call.
Nothing to read where the trace also holds the other kind of call."""

from bench.metrics_util import device_seconds_per_call


def read(rec):
    s = device_seconds_per_call(rec, "scan_batch")
    return None if s is None else 1e6 * s
