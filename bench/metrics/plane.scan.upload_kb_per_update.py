"""KiB the scan plane uploaded in the window per incremental update: the
window's change of ``plane.scan.upload_bytes`` over that of
``plane.scan.updates``.  Nothing to read where no update was counted."""

from bench.metrics_util import counter_ratio


def read(rec):
    return counter_ratio(rec, "plane.scan.upload_bytes",
                         "plane.scan.updates", 1 / 1024)
