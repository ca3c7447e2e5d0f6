"""Mean ms of one ``plane.scan.update`` span in the traced window: the
scan plane's re-pack and upload of the staged-insert arrays after a
write that left the tombstones alone.  Nothing to read where the
program has no such span or no write reached a scan."""

from bench.metrics_util import span_ms


def read(rec):
    return span_ms(rec, "plane.scan.update")
