"""95th percentile of scan latency over every scan due in the window,
due to rows on the host (a failed scan counts as over any limit)."""

from bench.metrics_util import tail_ms


def read(rec):
    return tail_ms(rec["latency_s"].get("scan"), 95)
