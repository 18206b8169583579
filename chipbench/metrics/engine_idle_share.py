"""Share of the traced window in which the device idles while the host
is inside an engine span (``repro.*``: preparing, launching or folding a
dispatch), each idle gap given to the innermost span that overlaps it
most. Read from the scoped summary (``scopes.summarize``); nothing to
read in a trace without engine spans."""
UNIT = "%"


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr.get("program") or tr["window_s"] <= 0:
        return None
    idle = tr["idle_program"]
    inside = sum(v for k, v in idle.items() if k != "outside")
    return 100.0 * inside / tr["window_s"]
