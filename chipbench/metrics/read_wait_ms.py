"""Mean time a read waits in the serving loop before its dispatch (the
in-flight tick chunk, or the read batch ahead of it)."""
import numpy as np

UNIT = "ms"


def read(rec):
    r = rec["reads"]
    wait = np.asarray(r["dispatch"]) - np.asarray(r["arrival"])
    wait = wait[~np.isnan(wait)]
    return float(np.mean(wait)) * 1e3 if wait.size else None
