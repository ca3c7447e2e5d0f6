"""Share of the lanes of coalesced point reads that are padding: the
window's change in ``frontend.padded_lanes`` over that in
``frontend.read_lanes``, in %."""

from bench.metrics_util import counter_ratio


def read(rec):
    return counter_ratio(rec, "frontend.padded_lanes", "frontend.read_lanes",
                         100.0)
