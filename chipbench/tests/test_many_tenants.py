"""The many-tenant cell (``knn-class-many-w256`` under ``ticks-many``,
four chips) rehearsed at a tiny size on the CPU: its configuration and
mix files, shrunk, through ``harness.run`` on four host devices, with
the ``temp_share`` reader. A child process, so that this one keeps its
single device."""
import json
import subprocess
import sys
import textwrap

from chipbench import harness

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json, sys
    sys.path[:0] = [{src!r}, {root!r}]
    import jax
    from chipbench import harness

    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.Cell.from_benchmark(bench, "class-many-4chip-ticks")
    assert cell.chips == 4 and cell.cfg["tenants"] == 65536
    # window 64: each label's k + 1 = 16 points are in every window
    cfg = dict(cell.cfg, tenants=16, window=64, capacity=64)
    mix = dict(cell.mix, reads_per_s=40.0,
               check=dict(tenants=8, ticks=6, reads=40))
    tiny = harness.Cell("tiny-many", cfg, mix, cell.chips, cell.bench_dir)
    rec = harness.run(tiny, 2 ** 40 + 3, 1.0)
    assert rec["correct"], rec["checks"]
    assert rec["compiles_in_window"] == 0
    assert rec["ticks_in_window"] > 0 and rec["reads"]["batches"] > 0
    c = rec["counters"]
    assert c["evictions"] == c["ticks"] == 16 * rec["ticks_in_window"]
    state = jax.eval_shape(tiny.engine().init_state)
    leaf = sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(state))
    assert c["state_bytes"] == leaf // 4
    names = harness.metric_names(bench, "class-many-4chip-ticks", True)
    assert "temp_share" in names
    got = harness.read_metrics(rec, ["temp_share", "evicting_share"])
    assert got["temp_share"]["value"] == \\
        100.0 * c["chunk_temp_bytes"] / c["state_bytes"]
    assert got["evicting_share"]["value"] == 100.0
    print("MANY_CELL_OK", json.dumps(got))
""")


def test_many_tenant_cell_rehearses_on_four_devices():
    script = SCRIPT.format(src=str(harness.ROOT / "src"),
                           root=str(harness.ROOT))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600)
    assert "MANY_CELL_OK" in r.stdout, r.stdout + r.stderr[-4000:]


def test_temp_share_reads_nothing_without_the_counters():
    """A program without the two byte counters (the parent of this
    metric) leaves ``temp_share`` out rather than failing."""
    assert harness.read_metrics({"counters": {"ticks": 4}},
                                ["temp_share"]) == {}
    got = harness.read_metrics(
        {"counters": {"state_bytes": 400, "chunk_temp_bytes": 100}},
        ["temp_share"])
    assert got == {"temp_share": {"value": 25.0, "unit": "%"}}


def test_many_tenant_config_is_the_deployment():
    """The configuration as it is run: nothing reduced, 16,384 tenants a
    chip, and its stated state size from its shapes."""
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    conf = {c["name"]: c for c in bench["configs"]}["knn-class-many-w256"]
    cfg = json.loads((harness.ROOT / conf["file"]).read_text())
    assert conf["reduced"] == cfg["reduced"] == []
    assert cfg["tenants"] // cfg["chips"] == 16384
    W, dim, k = cfg["window"], cfg["dim"], cfg["k"]
    assert cfg["state_bytes_per_tenant"] == 4 * (
        W * W + W * dim + W * k + 2 * W + 3)
