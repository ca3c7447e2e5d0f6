"""Mean host time of one ``IndexService.scan_batch`` call in the window
(it returns before the device finishes), from the timing proxy."""


def read(rec):
    s = rec["service"]["scan_batch"]
    return 1e3 * s["seconds"] / s["calls"] if s["calls"] else None
