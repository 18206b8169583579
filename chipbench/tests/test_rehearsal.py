"""The harness's whole run at a tiny size on the CPU: set-up, window,
check and every metric reader, for each measure and mix."""
import json

import numpy as np
import pytest

from chipbench import harness
from chipbench.tests import tiny

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("measure,mix", [("knn_class", "ticks"),
                                         ("knn_reg", "ticks"),
                                         ("knn_reg", "reads")])
def test_tiny_run_is_correct(measure, mix):
    cell = tiny.cell(measure, mix)
    rec = harness.run(cell, 2 ** 33 + 17, 1.0)
    assert rec["correct"], rec["checks"]
    assert rec["compiles_in_window"] == 0
    assert rec["ticks_in_window"] > 0 and rec["reads"]["batches"] > 0
    assert rec["checked"]["ticks"] > 0 and rec["checked"]["reads"] > 0
    gap = rec["longest_gap"]
    parts = gap["before"] + gap["traffic"] + gap["dispatch"] + gap["sync"]
    assert gap["s"] == pytest.approx(parts) and gap["sync"] >= 0
    c = rec["counters"]
    assert c["evictions"] == c["ticks"] == rec["ticks_in_window"] * 4
    rec["peaks"] = {"hbm_bytes_per_s": 819e9}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    got = harness.read_metrics(rec, e2e - ({"steps_per_s"} if mix == "reads"
                                           else set()))
    assert got["setup_s"]["value"] > 0
    assert got["read_p95_ms"]["value"] > 0
    if mix == "ticks":
        assert got["steps_per_s"]["value"] > 0


def test_same_seed_same_traffic():
    """The seed fixes the reads' arrivals and every tick's data; how
    many ticks fit in the window is the clock's, so the final state is
    not compared."""
    import jax.numpy as jnp

    cell = tiny.cell("knn_class")
    a = harness.run(cell, 5, 0.5)
    b = harness.run(cell, 5, 0.5)
    assert np.array_equal(a["reads"]["arrival"] - a["t_start"],
                          b["reads"]["arrival"] - b["t_start"])
    traffic = cell.fns()["traffic"]
    chunk = lambda seed: traffic(harness.seed_key(seed), jnp.int32(8))  # noqa
    for x, y, z in zip(chunk(5), chunk(5), chunk(6)):
        assert np.array_equal(x, y) and not np.array_equal(x, z)
