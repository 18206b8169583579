"""A configuration, a mix, a measure and a metric dropped into a copy of
the benchmark as new files run and report with no edit to any file
that was there."""
import json
import shutil

from chipbench import harness
from chipbench.tests import tiny


def test_new_files_are_found_by_name(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    new = tmp_path / "chipbench"
    cfg = dict(tiny.CONFIGS["knn_class"], measure="knn_class_copy")
    (new / "configs" / "tiny-new.json").write_text(json.dumps(cfg))
    mix = json.loads((new / "mixes" / "ticks.json").read_text())
    mix.update(chunk=8, reads_per_s=20.0,
               check=dict(tenants=2, ticks=2, reads=4))
    (new / "mixes" / "tiny-new.json").write_text(json.dumps(mix))
    shutil.copy(new / "measures" / "knn_class.py",
                new / "measures" / "knn_class_copy.py")
    (new / "metrics" / "chunks_served.py").write_text(
        'UNIT = "chunks"\n\n\ndef read(rec):\n    return len(rec["chunks"])\n')
    bench["configs"].append({"name": "tiny-new", "source": "a test",
                             "file": "chipbench/configs/tiny-new.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "tiny-new-cell", "config": "tiny-new",
                               "traffic": "tiny-new", "chips": 1,
                               "why": "a test"})
    bench["per_layer"].append({"name": "chunks_served", "unit": "chunks",
                               "better": "higher", "source": "host_clock",
                               "layer": "serving loop",
                               "moves": "read_p95_ms",
                               "workloads": ["tiny-new-cell"]})

    cell = harness.Cell.from_benchmark(bench, "tiny-new-cell", tmp_path)
    assert str(new) in cell.measure.__file__
    rec = harness.run(cell, 9, 0.5)
    assert rec["correct"]
    names = harness.metric_names(bench, "tiny-new-cell", per_layer=True)
    assert "chunks_served" in names
    got = harness.read_metrics(rec, ["chunks_served"], cell.bench_dir)
    assert got["chunks_served"]["value"] == len(rec["chunks"]) > 0


def test_split_metric_shares_one_reader():
    """``idle_share.ticks`` and ``idle_share.reads`` are one quantity,
    named apart by the end-to-end metric each moves: both read
    ``metrics/idle_share.py``."""
    rec = {"trace": {"busy_s": 3.0, "window_s": 4.0}}
    got = harness.read_metrics(rec, ["idle_share.ticks", "idle_share.reads"])
    assert got == {n: {"value": 25.0, "unit": "%"}
                   for n in ("idle_share.ticks", "idle_share.reads")}
