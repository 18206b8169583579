"""Readings behind the benchmark's limits and rates, on the chip.

    python3 chipbench/calibrate.py readings --workload <cell> \\
        --seeds 201-212 --control-seeds 301-303 --seconds 4
    python3 chipbench/calibrate.py sweep --workload <cell> --seed 7 \\
        --rates 100,400,1600 --seconds 5

``readings`` runs the cell on each seed, then the control on each
control seed, all in one process so that set-up compiles once: the
program as configured, and the program with its ``bfloat16`` path
switched on (the engines' ``dtype``), the precision below the float32
that the configurations state. One JSON line per run gives every
compared number; the last line gives, per number, the largest that the
program read (the lower reading) and the smallest that the control
read (the upper reading), which PERF.md turns into the limits.

``sweep`` serves the cell at each read rate in turn and prints the read
tail and how many reads were still queued when the window closed. A
rate is sustained where the reads arriving in the window's second half
wait, at the 95th percentile, no more than ``SUSTAINED`` times as long
as those of its first half: a queue that grows makes them wait longer.
The last line gives the highest rate sustained, with every rate below
it sustained too, and four fifths of it rounded down to ten: the rate a
mix offers. Neither command is part of a benchmark run.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import run as run_m  # noqa: E402

SUSTAINED = 1.25


def _seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def _line(**kw) -> None:
    print(json.dumps(kw), flush=True)


def readings(cell, seeds, control_seeds, seconds) -> None:
    from chipbench import harness

    runs = {"program": (cell, seeds)}
    if control_seeds:
        cfg = dict(cell.cfg, dtype="bfloat16")
        runs["control"] = (harness.Cell(cell.name, cfg, cell.mix, cell.chips,
                                        harness.BENCH), control_seeds)
    summary = {}
    for side, (c, ss) in runs.items():
        for seed in ss:
            t0 = time.perf_counter()
            rec = harness.run(c, seed, seconds)
            nums = {k: v["value"] for k, v in rec["checks"].items()}
            _line(side=side, seed=seed, correct=rec["correct"], numbers=nums,
                  checked=rec["checked"], setup_parts=rec["setup_parts"],
                  seconds=time.perf_counter() - t0)
            for k, v in nums.items():
                agg = summary.setdefault(k, {})
                if side == "program":
                    agg["lower"] = max(agg.get("lower", 0.0), v)
                else:
                    agg["upper"] = min(agg.get("upper", float("inf")), v)
    _line(summary=summary)


def sweep(cell, seed, rates, seconds) -> None:
    import numpy as np

    from chipbench import harness

    knee, failed = None, False
    for rate in sorted(rates):
        cell.mix["reads_per_s"] = rate
        rec = harness.run(cell, seed, seconds)
        r = rec["reads"]
        lat = np.asarray(r["done"]) - np.asarray(r["arrival"])
        late = np.asarray(r["dispatch"]) - (rec["t_start"] + seconds)
        # a queue that grows makes the later half of the window wait longer
        half = np.asarray(r["arrival"]) - rec["t_start"] >= seconds / 2
        first = float(np.percentile(lat[~half], 95)) * 1e3
        second = float(np.percentile(lat[half], 95)) * 1e3
        ok = second <= SUSTAINED * first
        failed = failed or not ok
        if not failed:
            knee = rate
        _line(rate=rate, reads=int(lat.size), batches=r["batches"],
              p50_ms=float(np.percentile(lat, 50)) * 1e3,
              p95_ms=float(np.percentile(lat, 95)) * 1e3,
              p95_first_half_ms=first, p95_second_half_ms=second,
              sustained=ok, max_ms=float(np.max(lat)) * 1e3,
              queued_at_close=int(np.sum(late > 0)),
              ticks=rec["ticks_in_window"], correct=rec["correct"])
    _line(highest_sustained=knee,
          four_fifths=None if knee is None else int(0.8 * knee) // 10 * 10)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="201-212")
    ap.add_argument("--control-seeds", default="301-303")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rates", default="100,400,1600")
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    _, cell, _, _ = run_m.prepare(args.workload)
    if args.mode == "readings":
        readings(cell, _seeds(args.seeds), _seeds(args.control_seeds)
                 if args.control_seeds else [], args.seconds)
    else:
        sweep(cell, args.seed, [float(r) for r in args.rates.split(",")],
              args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
