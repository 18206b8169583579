"""Share of the window's tenant-ticks that evicted (the engine's device
counters): 100 when every window was full, as the cell intends."""
UNIT = "%"


def read(rec):
    c = rec["counters"]
    return 100.0 * c["evictions"] / c["ticks"] if c.get("ticks") else None
