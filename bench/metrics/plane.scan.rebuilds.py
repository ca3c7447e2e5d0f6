"""Full builds of the scan plane in the window (the O(n) live-prefix
page index built and uploaded anew): the window's change of the
service's ``plane.scan.rebuilds`` counter.  Nothing to read where the
program keeps no such counter."""


def read(rec):
    return rec.get("counters", {}).get("plane.scan.rebuilds")
