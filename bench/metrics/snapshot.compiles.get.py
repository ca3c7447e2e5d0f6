"""Executables built or loaded from the compile cache inside a window
that served gets (the target is 0: warm-up covers every shape)."""


def read(rec):
    if not rec["service"]["get"]["calls"]:
        return None
    return rec["compiles_in_window"]
