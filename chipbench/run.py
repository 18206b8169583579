"""Chip benchmark of the multi-tenant exact CP serving engines.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Runs the cell ``<cell>`` of ``BENCHMARK.json`` once, in this process,
on the chips it asks for: set-up (engine, compile, window fill, warm
read), ``--seconds`` of measured window, then the check against the
float64 reference. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the window with the JAX profiler and reports its
per-layer metrics. The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` when traced, ``checks``); each compared number and its
limit also close standard error.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 1: nothing falls back to the CPU. JAX's persistent
compilation cache lives in ``.jax_cache/`` at the root of the checkout,
so only a cell's first run there compiles.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def _fail(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _device(chips: int):
    """The chips to run on, or exit: no TPU, no run."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        _fail(f"no TPU found (JAX runs on {devs[0].platform}); the "
              "benchmark does not fall back")
    if len(devs) < chips:
        _fail(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs


def prepare(workload: str):
    """Load the cell, refuse to run without its chips, and point JAX's
    persistent compilation cache at the checkout. Returns the parsed
    BENCHMARK.json, the cell, the devices and their peaks."""
    if not (ROOT / "src" / "repro").is_dir():
        _fail("the system under test (src/repro) is not in this checkout")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # the cache directory is fixed inside the checkout, whatever the
    # environment says, so two checkouts never share compiled programs
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)

    from chipbench import harness

    cell = harness.Cell.from_benchmark(bench, workload, ROOT)
    devs = _device(cell.chips)
    kind = devs[0].device_kind
    peaks = json.loads((harness.BENCH / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        _fail(f"no peaks for device kind {kind!r} in peaks.json")

    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return bench, cell, devs, peaks[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, devs, peaks = prepare(args.workload)
    from chipbench import harness

    rec = harness.run(cell, args.seed, args.seconds, trace=bool(args.trace),
                      t_proc0=T_PROC0)
    rec["peaks"] = peaks
    metrics = harness.read_metrics(
        rec, harness.metric_names(bench, cell.name, bool(args.trace)),
        cell.bench_dir)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips,
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    out = {"correct": rec["correct"], "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if args.trace:
        tr = rec["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["info"] = {"setup_parts": rec["setup_parts"],
                   "ticks_in_window": rec["ticks_in_window"],
                   "reads": int(len(rec["reads"]["arrival"])),
                   "read_batches": rec["reads"]["batches"],
                   "counters": rec["counters"],
                   "compiles_in_window": rec["compiles_in_window"],
                   "longest_gap": rec["longest_gap"],
                   "gc_pause_max_s": rec["gc_pause_max_s"],
                   "checked": rec["checked"]}
    out["checks"] = rec["checks"]
    print(f"[chipbench] correct: {rec['correct']}", file=sys.stderr)
    for name, c in rec["checks"].items():
        print(f"[chipbench] check {name}: {c['value']!r} (limit "
              f"{c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
