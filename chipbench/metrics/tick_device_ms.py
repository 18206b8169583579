"""Device time of the tick program (``observe_many``'s chunk) per tick,
from the trace, averaged over the cell's chips."""
UNIT = "ms"


def read(rec):
    tr = rec.get("trace")
    t = tr and tr["program_s"].get("tick")
    if not t or not rec["ticks_in_window"]:
        return None
    return 1e3 * t / rec["ticks_in_window"]
