"""The chip's peak bytes in use after the window over the keys the index
holds: the paper's size claim, on the device."""


def read(rec):
    mem = rec["memory"]
    if not mem["peak_bytes"] or not mem["keys"]:
        return None
    return mem["peak_bytes"] / mem["keys"]
