"""Share of the traced window in which no operation ran on the device
(averaged over the cell's chips), in a cell whose ticks run back to
back."""
UNIT = "%"


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
