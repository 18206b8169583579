"""Set-up time: process start to the window's first dispatch (engine
build, compile or cache load, window fill, warm read)."""
UNIT = "s"


def read(rec):
    return rec["setup_s"]
