"""Share of the traced window in which the device was idle and no host
span was open at all (not even ``frontend.wait``): what no span names
yet, such as the collector's read-back of scan rows or a host pause."""

from bench.metrics_util import idle_pct


def read(rec):
    return idle_pct(rec, "idle_unattributed_s")
