"""Device self time of the tick program under its named scope
``evict``, per tick: the gated head advance and the backfill repair
of the k-best lists (``drop_backfill``). Read from the scoped summary
(``scopes.summarize``), averaged over the cell's chips."""
UNIT = "ms"
SCOPE = "evict"


def read(rec):
    t = rec.get("trace", {}).get("scopes", {}).get("tick", {}).get(SCOPE)
    if t is None or not rec["ticks_in_window"]:
        return None
    return 1e3 * t / rec["ticks_in_window"]
