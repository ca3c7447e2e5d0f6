"""99th percentile of point-get latency over every get due in the
window, due to answer in hand (a failed get counts as over any limit)."""

from bench.metrics_util import tail_ms


def read(rec):
    return tail_ms(rec["latency_s"].get("get"), 99)
