"""One traced run of a cell, read down to the program's own named scopes
and host spans.

    python3 chipbench/scoped.py --workload <cell> --seed <n> \\
        --seconds <s> [--set window=128 ...] [--record PATH]

Runs the cell as ``run.py --trace 1`` does (same set-up, window and
check, in ``harness.run``) and reads the same profile a second way:
``scopes.summarize`` gives device time per named scope of the tick and
read programs (their instructions' ``op_name`` from the engine's
``lower_tick`` and ``lower_read``, compiled after the window), the
engines' ``repro.*`` spans, and the device idle time inside them. The
last line of standard output is one JSON object: ``correct``,
``metrics`` (the cell's end-to-end and per-layer metrics, and those of
``SCOPED_METRICS`` read from the scoped summary; the end-to-end ones
with the profiler on, so they say what tracing costs), ``device``,
``breakdown``, ``scoped`` (with ``unnamed``: the device ops of each
program whose instruction the compiled text does not name, which is 0
when the text is the program that ran) and ``checks``.

``--set key=value`` replaces a number of the cell's configuration or mix
(a cut-down cell: ``--set tenants=16 --set window=128 --set
capacity=128``). ``--record PATH`` writes the window's first
``--record-ms`` milliseconds of events, with the ``op_name`` of each
instruction in them, as JSON: the form ``tests/test_scopes.py`` reads.
Like ``run.py``, it runs only on a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import run  # noqa: E402

# the per-layer metrics read from the scoped summary, by the end-to-end
# metric they move
SCOPED_METRICS = {
    "steps_per_s": ("backfill_ms", "learn_ms", "write_ms",
                    "dispatch_host_ms", "engine_idle_share.ticks"),
    "read_p95_ms.reads": ("engine_idle_share.reads",),
}


def _keep_scoped_events(kept: dict) -> None:
    """Make ``trace.load`` (which ``harness.run`` calls on its profile
    before deleting it) also keep the engine spans in ``kept``."""
    from chipbench import scopes, trace

    def load(trace_dir):
        from jax.profiler import ProfileData

        files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        ev = scopes.events(ProfileData.from_file(str(files[-1])))
        kept.update(ev)
        return {k: v for k, v in ev.items() if k != "engine"}

    trace.load = load


def _names(cell) -> dict:
    """``op_names`` of the tick and read programs as the cell runs them
    (the compile finds them in the persistent cache)."""
    from chipbench import scopes

    eng, mix = cell.engine(), cell.mix
    lowered = {"tick": eng.lower_tick(mix["chunk"]),
               "read": eng.lower_read(mix["queries"] * mix["read_slots"])}
    return {k: scopes.op_names(v.compile().as_text())
            for k, v in lowered.items()}


def _unnamed(ev, cell, names) -> dict:
    """Per program, the window's ops and how many of them name an
    instruction that ``names`` does not hold."""
    from chipbench import scopes, trace

    lo, hi = [(s, e) for n, s, e in ev["spans"] if n == "window"][-1]
    dev = ev["devices"].get(0, {"ops": [], "modules": []})
    meas = cell.measure
    prefixes = {"tick": meas.TICK_PROGRAM, "read": meas.READ_PROGRAM}
    out = {}
    for key, prefix in prefixes.items():
        mods = [(s, e) for n, s, e in dev["modules"]
                if n.startswith(prefix) and e > lo and s < hi]
        ops = [n for n, s, e in trace._clip(dev["ops"], lo, hi)
               if any(a <= s < b for a, b in mods)]
        out[key] = [len(ops), sum(scopes.instruction(n) not in names[key]
                                  for n in ops)]
    return out


def _cut(ev: dict, names: dict, ms: float) -> dict:
    """The events of the window's first ``ms`` milliseconds (ops by their
    label), the window span shortened to match, and the ``op_name`` of
    their instructions."""
    from chipbench import scopes, trace

    lo = [s for n, s, _ in ev["spans"] if n == "window"][-1]
    hi = lo + ms * 1e6

    def keep(evs):
        return [list(x) for x in evs if x[2] > lo and x[1] < hi]
    spans = [list(x) for x in ev["spans"] if x[0] != "window"]
    devices = {str(i): {"modules": keep(d["modules"]),
                        "ops": [[trace.op_label(n), s, e]
                                for n, s, e in keep(d["ops"])]}
               for i, d in ev["devices"].items()}
    used = {scopes.instruction(op[0]) for d in devices.values()
            for op in d["ops"]}
    return {"devices": devices,
            "spans": keep(spans) + [["window", lo, hi]],
            "engine": keep(ev["engine"]),
            "names": {k: {i: p for i, p in v.items() if i in used}
                      for k, v in names.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE")
    ap.add_argument("--record", default="")
    ap.add_argument("--record-ms", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench, cell, devs, peaks = run.prepare(args.workload)
    from chipbench import harness, scopes

    cfg, mix = dict(cell.cfg), dict(cell.mix)
    for item in args.set:
        key, _, value = item.partition("=")
        (cfg if key in cfg else mix)[key] = json.loads(value)
    cell = harness.Cell(cell.name, cfg, mix, cell.chips, cell.bench_dir)
    kept: dict = {}
    _keep_scoped_events(kept)
    rec = harness.run(cell, args.seed, args.seconds, trace=True,
                      t_proc0=run.T_PROC0)
    rec["peaks"] = peaks
    meas = cell.measure
    names = _names(cell)
    scoped = scopes.summarize(kept, meas.TICK_PROGRAM, meas.READ_PROGRAM,
                              cell.chips, names)
    rec["trace"].update(scoped)
    scoped["unnamed"] = _unnamed(kept, cell, names)
    end_to_end = harness.metric_names(bench, cell.name, False)
    names = end_to_end + harness.metric_names(bench, cell.name, True)
    for moved in end_to_end:
        names += SCOPED_METRICS.get(moved, ())
    tr = rec["trace"]
    out = {"correct": rec["correct"],
           "metrics": harness.read_metrics(rec, names, cell.bench_dir),
           "device": {"kind": devs[0].device_kind,
                      "memory_peak_bytes": rec["memory_peak_bytes"],
                      "busy_s": tr["busy_s"], "window_s": tr["window_s"]},
           "breakdown": {"program_s": tr["program_s"],
                         "device_ops": tr["device_ops"],
                         "idle_gaps": tr["idle_gaps"]},
           "scoped": scoped,
           "info": {"ticks_in_window": rec["ticks_in_window"],
                    "read_batches": rec["reads"]["batches"],
                    "cfg": {k: v for k, v in cfg.items()
                            if isinstance(v, (int, float))}},
           "checks": rec["checks"]}
    if args.record:
        Path(args.record).write_text(
            json.dumps(_cut(kept, names, args.record_ms)))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
