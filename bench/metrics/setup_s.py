"""Process start to the first due request: generate, build, upload,
warm up (compiles or cache loads included)."""


def read(rec):
    return rec["setup_s"]
