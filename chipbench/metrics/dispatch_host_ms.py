"""Mean host time of one ``observe_many`` dispatch: the engine's span
``repro.observe_many`` (occupancy checks, the chunk's arguments, the
launch and the stats fold), over the chunks wholly inside the window.
Read from the scoped summary (``scopes.summarize``)."""
UNIT = "ms"


def read(rec):
    row = rec.get("trace", {}).get("program", {}).get("observe_many")
    if not row or not row["count"]:
        return None
    return 1e3 * row["total_s"] / row["count"]
