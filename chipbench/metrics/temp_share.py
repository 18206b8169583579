"""The chunk program's temporaries as a share of the tenant state a chip
holds (the engine's counters ``chunk_temp_bytes`` and ``state_bytes``).
HBM the tick spends on temporaries holds no tenant, so this share caps
the tenants a chip serves; a device's peak memory does not show it."""
UNIT = "%"


def read(rec):
    c = rec["counters"]
    if not c.get("state_bytes") or "chunk_temp_bytes" not in c:
        return None
    return 100.0 * c["chunk_temp_bytes"] / c["state_bytes"]
