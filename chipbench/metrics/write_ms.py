"""Device self time of the tick program under its named scope
``write``, per tick: the ``D`` row and column insert and the X, y,
list and arrival-id writes. Read from the scoped summary
(``scopes.summarize``), averaged over the cell's chips."""
UNIT = "ms"
SCOPE = "write"


def read(rec):
    t = rec.get("trace", {}).get("scopes", {}).get("tick", {}).get(SCOPE)
    if t is None or not rec["ticks_in_window"]:
        return None
    return 1e3 * t / rec["ticks_in_window"]
