"""Tiny cells for the CPU tests: the measures' real code at toy sizes."""
import json

from chipbench import harness

CONFIGS = {
    "knn_class": dict(measure="knn_class", tenants=4, window=32, capacity=32,
                      dim=3, k=3, labels=2, chips=1, dtype="float32"),
    "knn_reg": dict(measure="knn_reg", tenants=4, window=32, capacity=32,
                    dim=3, k=3, chips=1, dtype="float32"),
}


def cell(measure: str, mix: str = "ticks", **cfg_over) -> harness.Cell:
    """``measure``'s tiny configuration under the named mix, shrunk to
    8-tick chunks, checking every tenant and every sampled read."""
    cfg = dict(CONFIGS[measure], **cfg_over)
    m = json.loads((harness.BENCH / "mixes" / f"{mix}.json").read_text())
    m.update(chunk=8, reads_per_s=40.0,
             check=dict(tenants=cfg["tenants"], ticks=6, reads=200))
    if m["ticks_per_s"] is not None:
        m["ticks_per_s"] = 200.0
    return harness.Cell(f"tiny-{measure}-{mix}", cfg, m, 1)
