"""Benchmark aggregator: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig2,...]

Prints ``bench,config,us_per_call,derived`` CSV rows. CPU container note:
absolute times are CPU-XLA; the asymptotic slopes across the n-grid are
the quantities that reproduce the paper's figures (see EXPERIMENTS.md).
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import HEADER, row


def _fleet_rows(quick: bool) -> list[str]:
    """Run fleet_bench in a child process and render its rows as CSV."""
    import json
    import os
    import subprocess
    import tempfile

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "fleet_bench.py")
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "fleet.json")
        cmd = [sys.executable, script, "--out", out]
        if quick:
            cmd.append("--quick")
        subprocess.run(cmd, check=True)
        with open(out) as f:
            results = json.load(f)["results"]
    rows = []
    for r in results:
        if r["bench_kind"] == "fleet_scaling":
            rows.append(row(
                "fleet/scaling",
                f"S={r['tenants']},shards={r['shards']}",
                r["tenants"] / r["session_steps_per_s"],
                f"steps={r['session_steps_per_s']:.0f}/s "
                f"tick_p99={r['tick_p99_s'] * 1e3:.2f}ms "
                f"speedup={r.get('shard_speedup_vs_1shard', 1):.2f}x "
                f"cores={r['host_cores']}"))
        elif r["bench_kind"] == "fleet_lifecycle":
            rows.append(row(
                "fleet/lifecycle", f"S={r['tenants']}",
                r["observe_round_p50_s"],
                f"admit={r['admit_s_per_tenant'] * 1e6:.0f}us "
                f"migrations={r['migrations']} "
                f"round_max={r['observe_round_max_s'] * 1e3:.0f}ms"))
    return rows


def _faults_rows(quick: bool) -> list[str]:
    """Run chaos_bench in a child process and render its rows as CSV."""
    import json
    import os
    import subprocess
    import tempfile

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "chaos_bench.py")
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "chaos.json")
        cmd = [sys.executable, script, "--out", out]
        if quick:
            cmd.append("--quick")
        subprocess.run(cmd, check=True)
        with open(out) as f:
            results = json.load(f)["results"]
    rows = []
    for r in results:
        if r["bench_kind"] == "chaos_guard_overhead":
            rows.append(row(
                "chaos/guard_overhead",
                f"S={r['sessions']},cap={r['capacity']}",
                r["observe_many_s_guarded"] / r["chunk"],
                f"overhead={100 * r['guard_overhead_frac']:+.1f}% "
                f"plain={r['observe_many_s_plain'] * 1e3:.2f}ms "
                f"bit_identical={r['bit_identical_clean']}"))
        elif r["bench_kind"] == "chaos_fault_saver":
            rows.append(row(
                "chaos/fault_saver", f"S={r['sessions']}",
                r["save_wall_s"],
                f"retries={r['snapshot_retries']:.0f} "
                f"committed={r['committed']}"))
        elif r["bench_kind"] == "chaos_fault_restore":
            rows.append(row(
                "chaos/fault_restore", f"S={r['sessions']}",
                r["restore_wall_s"],
                f"fallbacks={r['restore_fallbacks']:.0f} "
                f"step={r['recovered_step']} "
                f"bit_exact={r['recovered_bit_exact']}"))
    return rows


def _audit_rows(quick: bool) -> list[str]:
    """Run the static invariant audit in a child process, render rows.

    Subprocessed for the same reason as the fleet bench: the sharded
    targets need XLA_FLAGS virtual devices before jax's first import.
    A failing audit raises, so perf runs cannot record bench rows
    against a tree that violates the compiled-artifact invariants."""
    import json
    import os
    import subprocess
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "audit.json")
        cmd = [sys.executable, "-m", "repro.analysis.audit", "--out", out]
        if quick:
            cmd.append("--quick")
        r = subprocess.run(cmd, capture_output=True, text=True)
        print(r.stdout, end="", file=sys.stderr)  # keep the CSV clean
        if r.returncode and not os.path.exists(out):
            raise RuntimeError(f"audit crashed: {r.stderr[-500:]}")
        with open(out) as f:
            rep = json.load(f)
    s = rep["summary"]
    if s["fail"]:
        bad = [r for r in rep["checks"] if r["status"] == "fail"]
        raise RuntimeError(
            f"{s['fail']} audit check(s) failed, first: "
            f"{bad[0]['check']} @ {bad[0]['target']}")
    rows = [row("audit/summary",
                f"targets={len(rep['targets'])},shards<="
                f"{rep['matrix']['max_shards']}",
                rep["elapsed_s"],
                f"pass={s['pass']} fail={s['fail']} "
                f"waived={s['waived']} skipped={s['skipped']} "
                f"trip_fallbacks={s['trip_fallbacks']}")]
    for r in rep["checks"]:
        if r["status"] == "fail":
            rows.append(row(f"audit/{r['check']}", r["target"], 0.0,
                            "FAIL " + (r["violations"][0].get("line", "")
                                       if r["violations"] else "")))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller n-grids (CI mode)")
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)

    from benchmarks import (bootstrap_bench, fig2_predict_time,
                            fig3_train_time, fig4_regression, online_bench,
                            regression_bench, replay_bench, roofline,
                            serve_bench, table2_highdim, table3_parallel)

    def _sliding_rows(fn, tag, caps):
        return [
            row(f"{tag}/sliding", f"S={r['sessions']},cap={r['capacity']}",
                r["sessions"] / r["session_steps_per_s_sliding"],
                f"ring={r['session_steps_per_s_sliding']:.0f}/s "
                f"compact={r['session_steps_per_s_sliding_compact']:.0f}/s "
                f"ring_vs_compact={r['ring_speedup_vs_compact']:.2f}x "
                f"evictfree={r['session_steps_per_s_evictfree']:.0f}/s "
                f"mem_roof={100 * r['mem_roof_fraction']:.0f}% "
                f"compile={r['compile_s_ring']:.2f}s")
            for r in fn(caps)]

    suites = {
        # The child-process suites run first, while this process has not
        # yet touched a device: an accelerator belongs to one process at
        # a time, and the first in-process suite below takes it.
        # Sharded-fleet scaling curve (virtual host devices on the CPU
        # need XLA_FLAGS before jax is first imported).
        "fleet": lambda: _fleet_rows(args.quick),
        # chaos harness: guarded-tick overhead (5% CI budget) + keyed
        # I/O fault smoke (saver retries, restore fallback)
        "faults": lambda: _faults_rows(args.quick),
        # static invariant audit alongside the perf rows (raises — and
        # so records ERROR — on any violation)
        "audit": lambda: _audit_rows(args.quick),
        "fig2": lambda: fig2_predict_time.run(
            n_grid=(64, 256) if args.quick else fig2_predict_time.N_GRID),
        "fig3": lambda: fig3_train_time.run(
            n_grid=(64, 256) if args.quick else fig3_train_time.N_GRID),
        "fig4": lambda: fig4_regression.run(
            n_grid=(64, 256) if args.quick else fig4_regression.N_GRID),
        "table2": lambda: table2_highdim.run(
            n_train=256 if args.quick else table2_highdim.N_TRAIN,
            m_test=8 if args.quick else table2_highdim.M_TEST),
        "table3": lambda: table3_parallel.run(
            n=256 if args.quick else table3_parallel.N),
        "bootstrap": lambda: [
            row(f"bootstrap/{k}", f"n={r['n']},B={r['B']}", r[k],
                f"B'={r['b_prime']} "
                f"speedup={r['speedup_optimized_vs_standard']:.1f}x")
            for r in bootstrap_bench.run(
                n_grid=(24,) if args.quick else (48,), m=1, B=5, depth=3)
            for k in ("t_fit_s", "t_optimized_per_point_s",
                      "t_standard_per_point_s", "t_tick_s")],
        "online": lambda: online_bench.run(
            t_grid=(64,) if args.quick else (64, 256, 1024)),
        # window-full sliding eviction: the ring-layout O(cap)-evict
        # columns (ISSUE 5) — keeps the BENCH trajectory comparable
        "serve_sliding": lambda: _sliding_rows(
            serve_bench.run_sliding, "serve",
            (256,) if args.quick else (256, 1024)),
        "reg_sliding": lambda: _sliding_rows(
            regression_bench.run_sliding, "regression",
            (256,) if args.quick else (256, 1024)),
        # telemetry-instrumentation cost on the chunked hot path (the
        # 5% budget CI gates on BENCH_serve.json)
        "serve_overhead": lambda: [
            row("serve/overhead",
                f"S={r['sessions']},cap={r['capacity']}",
                r["observe_many_s_instrumented"] / r["chunk"],
                f"overhead={100 * r['instrumentation_overhead_frac']:+.1f}"
                f"% plain={r['observe_many_s_plain'] * 1e3:.2f}ms")
            for r in serve_bench.run_overhead()],
        # trace replay under load (loadgen workloads) + the cost-model
        # chunk auto-tune vs the hand-tuned constant
        "replay": lambda: [
            row(f"replay/{r['workload']}",
                f"S={r['tenants']},cap={r['capacity']},x{r['speedup']:g}",
                r["observe_p99_s"],
                f"p50={r['observe_p50_s'] * 1e3:.2f}ms "
                f"sojourn_p99={r['observe_sojourn_p99_s'] * 1e3:.2f}ms "
                f"slo_viol={r['slo_violation_frac']:.2f} "
                f"q_max={r['queue_depth_max']:.0f}")
            for r in replay_bench.run_workloads(
                ops=96 if args.quick else 256)
        ] + [
            row("replay/autotune",
                f"chunk={r['chunk_suggested']}vs{r['chunk_hand']}",
                r["tenants"] / r["steps_per_s_auto"],
                f"auto={r['steps_per_s_auto']:.0f}/s "
                f"hand={r['steps_per_s_hand']:.0f}/s "
                f"ratio={r['autotune_ratio']:.2f}x")
            for r in replay_bench.run_autotune(
                ops=192 if args.quick else 384)],
        "roofline": lambda: roofline.run(mesh_filter=None),
    }
    only = set(args.only.split(",")) if args.only else set(suites)

    print(HEADER)
    failed = 0
    for name, fn in suites.items():
        if name not in only:
            continue
        t0 = time.time()
        try:
            for r in fn():
                print(r)
        except Exception as e:  # noqa: BLE001
            failed += 1
            print(f"{name},ERROR,0,{type(e).__name__}: {e}")
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
