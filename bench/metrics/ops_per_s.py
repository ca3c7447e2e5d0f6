"""Operations answered without error inside the window, per second of
the window."""


def read(rec):
    return rec["ops_ok_in_window"] / rec["seconds"]
