"""The ``stream_update`` kernel's share of the HBM-bandwidth roof: the
bytes one call must move (the measure's ``kernel_bytes`` per tenant,
from shapes; one call per tick serves every tenant on the chip) over
the chip's peak bandwidth, divided by the kernel's device time per
tick. The kernel's ops in the trace are named after the jitted wrapper:
``%stream_update.<n>`` under the classification engine,
``%vmap_jit_stream_update__.<n>`` under the regression engine."""
UNIT = "%"
OP_NAME = "stream_update"


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec["ticks_in_window"]:
        return None
    t = sum(v for k, v in tr["ops"].items() if OP_NAME in k.split()[0])
    if not t:
        return None
    per_chip = rec["tenants"] // rec["chips"]
    least_s = (rec["measure"].kernel_bytes(rec["cfg"]) * per_chip
               / rec["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (t / rec["ticks_in_window"])
