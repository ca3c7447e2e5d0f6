"""Mean host time of one coalesced ``IndexService.get`` call in the
window (device sync and f64 refinement included), from the timing
proxy between frontend and service."""


def read(rec):
    s = rec["service"]["get"]
    return 1e3 * s["seconds"] / s["calls"] if s["calls"] else None
