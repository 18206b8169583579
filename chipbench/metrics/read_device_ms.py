"""Device time of the read program (``predict`` or ``intervals``) per
read dispatch, from the trace, averaged over the cell's chips."""
UNIT = "ms"


def read(rec):
    tr = rec.get("trace")
    t = tr and tr["program_s"].get("read")
    if not t or not rec["reads"]["batches"]:
        return None
    return 1e3 * t / rec["reads"]["batches"]
