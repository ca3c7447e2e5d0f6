"""Share of the traced window in which the device was idle while a host
span other than ``frontend.wait`` was open: idle time the host's own
work holds the device back by."""

from bench.metrics_util import idle_pct


def read(rec):
    return idle_pct(rec, "idle_host_s")
