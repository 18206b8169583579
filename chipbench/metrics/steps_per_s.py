"""Tenant-ticks whose p-values reached the host, per second of the
window (from its start to the end of its last chunk)."""
UNIT = "steps/s"


def read(rec):
    if not rec["chunks"] or rec["tick_window_s"] <= 0:
        return None
    return rec["tenants"] * rec["ticks_in_window"] / rec["tick_window_s"]
