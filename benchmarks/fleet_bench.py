"""Sessions-scaling curve for the sharded serving fleet.

Two row families, merged into BENCH_serve.json under the ``fleet``
bench_kind prefix (``benchmarks.common.merge_bench_rows`` — the
serve/replay rows are preserved):

* ``fleet_scaling`` — the tenant axis swept 1 -> 10k+ at 1 shard and
  at ``--devices`` shards: chunked session-steps/s, single-tick
  latency p50/p99 (each tick individually synced), per-shard mean
  occupancy from the device tick counters, and the measured
  ``shard_speedup_vs_1shard``. Every row records ``host_cores``: on a
  single-core container the 8 virtual XLA host devices time-slice one
  core, so the honest speedup there is ~1x — the row exists to show
  sharding costs nothing, and the CI gate scales its expectation with
  the core count rather than asserting a parallel win the hardware
  cannot deliver.
* ``fleet_lifecycle`` — tenant admit / serve / bucket-migrate / retire
  wall costs through ``repro.serving.Fleet`` (capacity-bucketed engine
  pools), with the migration count that the bucketed pools confine to
  one tenant's lane instead of a pool-wide retrace.

MUST run as its own process (``python benchmarks/fleet_bench.py`` or
the ``fleet`` suite of ``benchmarks.run``, which subprocesses it):
virtual host devices only exist if XLA_FLAGS is set before jax is
first imported, so all jax-touching imports here are deferred.

    PYTHONPATH=src python benchmarks/fleet_bench.py [--quick] \\
        [--devices 8] [--out BENCH_serve.json]
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _ensure_devices(n: int) -> None:
    """Ask for ``n`` virtual devices on the CPU backend (the flag has no
    effect on an accelerator, which brings its own devices). Must
    precede any jax import."""
    if "jax" in sys.modules:
        raise SystemExit(
            "fleet_bench must set XLA_FLAGS before jax is imported; "
            "run it as its own process (benchmarks.run subprocesses it)")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


def _host_cores() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # non-Linux
        return os.cpu_count() or 1


def run_scaling(tenants_grid, shard_grid, *, capacity=128, dim=16, k=7,
                chunk=16, chunks=2, lat_ticks=24, seed=0):
    """One row per (tenants, shards) point of the scaling curve."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving import ServingEngine

    cores = _host_cores()
    rows, base = [], {}
    for n_sessions in tenants_grid:
        for shards in shard_grid:
            if n_sessions % shards:
                continue
            eng = ServingEngine(
                n_sessions=n_sessions, capacity=capacity, dim=dim, k=k,
                n_labels=2, window=capacity // 2, shards=shards,
                instrument=True)
            key = jax.random.PRNGKey(seed)
            kx, ky, kt = jax.random.split(key, 3)
            T = chunk * (chunks + 1) + lat_ticks
            xs = jax.random.normal(kx, (T, n_sessions, dim), jnp.float32)
            ys = jax.random.bernoulli(ky, 0.5, (T, n_sessions)).astype(
                jnp.int32)
            ts = jax.random.uniform(kt, (T, n_sessions), jnp.float32)

            state = eng.init_state()
            # warmup chunk: trace + compile + execute, timed separately
            t0 = time.perf_counter()
            state, p = eng.observe_many(state, xs[:chunk], ys[:chunk],
                                        ts[:chunk])
            jax.block_until_ready(p)
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            for c in range(1, chunks + 1):
                lo = c * chunk
                state, p = eng.observe_many(
                    state, xs[lo:lo + chunk], ys[lo:lo + chunk],
                    ts[lo:lo + chunk])
            jax.block_until_ready(p)
            wall = time.perf_counter() - t0
            steps_per_s = n_sessions * chunk * chunks / wall

            # single-tick latency distribution: every dispatch synced
            lats = []
            off = chunk * (chunks + 1)
            state1, p = eng.observe(state, xs[off], ys[off], ts[off])
            jax.block_until_ready(p)  # single-tick compile
            state = state1
            for t in range(off + 1, off + lat_ticks):
                t0 = time.perf_counter()
                state, p = eng.observe(state, xs[t], ys[t], ts[t])
                jax.block_until_ready(p)
                lats.append(time.perf_counter() - t0)
            lats = np.asarray(lats)

            drained = eng.telemetry.ticks.drain()
            per_shard = eng.telemetry.ticks.shard_vals or [drained]
            occ = [sh["occupancy_sum"] / max(sh["ticks"], 1)
                   for sh in per_shard]

            row = {
                "bench_kind": "fleet_scaling",
                "tenants": n_sessions,
                "shards": shards,
                "devices": jax.device_count(),
                "host_cores": cores,
                "capacity": capacity,
                "window": capacity // 2,
                "dim": dim,
                "k": k,
                "chunk": chunk,
                "compile_s": compile_s,
                "session_steps_per_s": steps_per_s,
                "tick_p50_s": float(np.percentile(lats, 50)),
                "tick_p99_s": float(np.percentile(lats, 99)),
                "per_shard_occupancy": [round(o, 2) for o in occ],
            }
            if shards == 1:
                base[n_sessions] = steps_per_s
            if n_sessions in base:
                row["shard_speedup_vs_1shard"] = (
                    steps_per_s / base[n_sessions])
            rows.append(row)
            print(f"[fleet_bench] S={n_sessions:6d} shards={shards} "
                  f"{steps_per_s:10.0f} steps/s  tick p50 "
                  f"{row['tick_p50_s'] * 1e3:6.2f}ms p99 "
                  f"{row['tick_p99_s'] * 1e3:6.2f}ms  "
                  f"speedup={row.get('shard_speedup_vs_1shard', 1):.2f}x")
            del state, eng
    return rows


def run_lifecycle(*, tenants=24, steps=72, dim=8, k=5, cap_min=8,
                  cap_max=64, pool_sessions=8, seed=0):
    """Admit / serve / migrate / retire costs through the fleet."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving import Fleet
    from repro.telemetry import MetricsRegistry

    metrics = MetricsRegistry()
    fleet = Fleet(dim=dim, k=k, cap_min=cap_min, cap_max=cap_max,
                  pool_sessions=pool_sessions, metrics=metrics)
    t0 = time.perf_counter()
    for tid in range(tenants):
        fleet.admit(tid)
    admit_s = (time.perf_counter() - t0) / tenants

    key = jax.random.PRNGKey(seed)
    round_walls = []
    for step in range(steps):
        key, kx, ky, kt = jax.random.split(key, 4)
        X = jax.random.normal(kx, (tenants, dim), jnp.float32)
        y = jax.random.bernoulli(ky, 0.5, (tenants,)).astype(jnp.int32)
        tau = jax.random.uniform(kt, (tenants,), dtype=jnp.float32)
        items = {tid: (X[tid], y[tid], tau[tid]) for tid in range(tenants)}
        t0 = time.perf_counter()
        out = fleet.observe(items)
        jax.block_until_ready(list(out.values()))
        round_walls.append(time.perf_counter() - t0)
    migrations = int(
        metrics.counter("fleet_migrations_total",
                        mode="classification").value)

    t0 = time.perf_counter()
    for tid in range(tenants):
        fleet.retire(tid)
    retire_s = (time.perf_counter() - t0) / tenants

    walls = np.asarray(round_walls)
    row = {
        "bench_kind": "fleet_lifecycle",
        "tenants": tenants,
        "steps": steps,
        "buckets": list(fleet.buckets),
        "pool_sessions": pool_sessions,
        "host_cores": _host_cores(),
        "admit_s_per_tenant": admit_s,
        "retire_s_per_tenant": retire_s,
        "migrations": migrations,
        # steady rounds vs rounds that absorbed a migration/compile:
        # the median is the serve cost, the max bounds one repad
        "observe_round_p50_s": float(np.percentile(walls, 50)),
        "observe_round_max_s": float(walls.max()),
    }
    print(f"[fleet_bench] lifecycle {tenants} tenants: admit "
          f"{admit_s * 1e6:.0f}us retire {retire_s * 1e6:.0f}us  "
          f"{migrations} migrations  round p50 "
          f"{row['observe_round_p50_s'] * 1e3:.2f}ms max "
          f"{row['observe_round_max_s'] * 1e3:.2f}ms")
    return [row]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual host devices to force (= max shards)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: 1k-tenant ceiling, short sweeps")
    ap.add_argument("--tenants", type=int, default=0,
                    help="single tenant count instead of the sweep")
    args = ap.parse_args(argv)

    _ensure_devices(args.devices)
    if args.tenants:
        grid = (args.tenants,)
    elif args.quick:
        grid = (8, 64, 1024)
    else:
        # 1 -> 10k+ tenants; non-multiples of --devices only get the
        # 1-shard point. 1024 is also CI's quick smoke point, so the
        # committed curve carries a row its gate can compare against.
        grid = (1, 8, 64, 512, 1024, 2048, 10240)
    import jax

    shards = sorted({1, min(args.devices, jax.device_count())})
    rows = run_scaling(grid, tuple(shards),
                       chunks=1 if args.quick else 2,
                       lat_ticks=12 if args.quick else 24)
    rows += run_lifecycle(steps=36 if args.quick else 72)

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from common import merge_bench_rows
    merge_bench_rows(args.out, rows, owned_prefixes=("fleet",))
    print(f"[fleet_bench] merged {len(rows)} fleet rows -> {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
