"""The tick program's share of the HBM-bandwidth roof: the bytes a tick
must move on one chip (the measure's ``tick_bytes`` per tenant, from
shapes) over the chip's peak bandwidth, divided by the tick's device
time. A tick moves far more bytes than it has operations, so bandwidth
bounds it."""
UNIT = "%"


def read(rec):
    tr = rec.get("trace")
    t = tr and tr["program_s"].get("tick")
    if not t or not rec["ticks_in_window"]:
        return None
    per_chip = rec["tenants"] // rec["chips"]
    least_s = (rec["measure"].tick_bytes(rec["cfg"]) * per_chip
               / rec["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (t / rec["ticks_in_window"])
