"""Device self time of the tick program under its named scope
``learn``, per tick: the ``stream_update`` kernel, the pricing, and
the new point's own list and arrival-id merge. Read from the scoped summary
(``scopes.summarize``), averaged over the cell's chips."""
UNIT = "ms"
SCOPE = "learn"


def read(rec):
    t = rec.get("trace", {}).get("scopes", {}).get("tick", {}).get(SCOPE)
    if t is None or not rec["ticks_in_window"]:
        return None
    return 1e3 * t / rec["ticks_in_window"]
