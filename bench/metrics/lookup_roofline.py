"""Share of the roofline of point lookups (``get``): the least time the
bytes `bench.work` counts for the traced calls take at the chip's HBM
bandwidth, over the device busy time of those calls."""

from bench.metrics_util import roofline_pct


def read(rec):
    return roofline_pct(rec, "get")
