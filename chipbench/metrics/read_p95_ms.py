"""95th percentile, over every read that arrived in the window, of the
time from its arrival to its answer reaching the host."""
import numpy as np

UNIT = "ms"


def read(rec):
    r = rec["reads"]
    lat = np.asarray(r["done"]) - np.asarray(r["arrival"])
    if lat.size == 0:
        return None
    # a read never answered counts as missing every limit
    lat = np.where(np.isnan(lat), np.inf, lat)
    return float(np.percentile(lat, 95)) * 1e3
