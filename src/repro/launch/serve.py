"""Serving launcher: batched decode + conformal guarantees per request.

    python -m repro.launch.serve --arch qwen2-1.5b --reduced \\
        --requests 16 --gen-tokens 8 --calib 512

Multi-tenant online CP mode (``--sessions N``) serves N concurrent
per-tenant conformal sessions through ``repro.serving.ServingEngine``:
one vmapped jitted step per tick advances every tenant's sliding-window
CP state (the paper's incremental&decremental O(n) updates), drifted
tenants are flagged by their exchangeability martingales, and tenant
state is snapshotted/restored through the crash-safe checkpoint store::

    python -m repro.launch.serve --sessions 32 --steps 200 --window 64

Adding ``--regression`` switches those sessions to streaming full-CP
*regression* (paper Section 8.1 served online, ``repro.regression``):
each tick prices the observed label (martingale drift detection), and
the read path returns exact prediction intervals for every tenant in
one dispatch::

    python -m repro.launch.serve --sessions 32 --regression --steps 200 \\
        --window 128 --capacity 128 --dim 2 --drift 3.0

(k-NN regression needs a dense neighbourhood to price drift: prefer low
--dim / window >= 100 for the drift demo.)

``--measure NAME`` instead serves the sessions through the measure
*registry* (``repro.serving.registry.ConformalPredictor``) — one exact-
shape predictor per tenant, sliding-window via the paper's incremental
``observe`` / decremental ``evict``. This is how the measures without a
fixed-shape vmapped engine (notably ``bootstrap``, Algorithm 3) are
served end-to-end::

    python -m repro.launch.serve --sessions 4 --measure bootstrap \\
        --steps 48 --window 24 --boot-b 5 --tree-depth 3

(Registry mode flags drift on the running-max log martingale; expect few
or no flags for bootstrap — its ensemble retrains on the live window
every tick and re-conforms within a few ticks of a change. The
sustained-drift detection demo is the vmapped engine mode above.)

Every serving mode reports through one telemetry pipeline
(``repro.telemetry``): per-op latency histograms, device-side tick
counters and online validity monitors (rolling coverage vs 1-eps,
p-value-uniformity KS, drift martingales) all render via the metrics
text export. ``--metrics-out`` dumps the same snapshot as JSON and
``--trace-out`` records one JSONL trace record per engine op::

    python -m repro.launch.serve --sessions 8 --steps 64 \\
        --metrics-out metrics.json --trace-out trace.jsonl

A profile of the run (``jax.profiler.trace``) holds the engines' own
host spans (``repro.<op>``, see ``core.engine_utils.DispatchSpans``) and
the named scopes of their programs, with or without these flags.

``--replay TRACE`` turns the launcher into a load-test driver
(``repro.telemetry.replay``): TRACE is either a recorded JSONL trace
file or a ``loadgen:<workload>`` spec (steady / bursty / diurnal /
zipf) synthesized on the fly. The trace's ops are dispatched against a
fresh engine (classification, or regression with ``--regression``),
preserving inter-arrival timing compressed by ``--speedup`` (default
``inf`` = as-fast-as-possible), and the report adds p50/p99 per-op
latency, steps/s, queue depth and the ``--slo-ms`` violation fraction.
``--auto-tune`` fits the per-(op, capacity-bucket) cost model
(``repro.telemetry.costmodel``) and replaces the hand-tuned
observe_many chunk with ``suggest_chunk()``::

    python -m repro.launch.serve --replay loadgen:bursty --steps 256 \\
        --sessions 8 --speedup inf --slo-ms 50 --auto-tune

Pipeline per batch of requests:
    1. prefill the prompt, build per-layer KV/recurrent caches,
    2. greedy decode ``gen_tokens`` steps with the serve_step,
    3. conformal OOD p-value per request (simplified k-NN CP over sequence
       embeddings, the paper's optimized O(n)-per-query path) — the serving
       feature the paper's speedups make affordable at this layer.

Prefill fills the KV caches by running serve_step over prompt positions
(teacher-forced); production prefill is the fused prefill_step (dry-run
cell), cache handoff being the same structure.
"""
from __future__ import annotations

import argparse
import time


def _class_drift_traffic(args, S, T, dim):
    """Per-tenant synthetic classification traffic; odd tenants drift at
    T/2 (the online change-detection workload of paper App. C.5).
    Shared by the engine and registry serving modes."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(args.seed)
    kx, ky, kt = jax.random.split(key, 3)
    X = jax.random.normal(kx, (S, T, dim), jnp.float32)
    centers = jnp.arange(S, dtype=jnp.float32)[:, None, None] * 0.1
    y = jax.random.bernoulli(ky, 0.5, (S, T)).astype(jnp.int32)
    X = X + centers + y[..., None].astype(jnp.float32)
    drifted = jnp.arange(S) % 2 == 1
    X = jnp.where((drifted[:, None] & (jnp.arange(T)[None, :] >= T // 2))
                  [..., None], X + args.drift, X)
    taus = jax.random.uniform(kt, (S, T), dtype=jnp.float32)
    return X, y, taus, drifted


def _chaos_traffic(args, X, y, taus, *, mode):
    """``--faults SEED``: corrupt the (S, T) synthetic traffic with a
    keyed ``robustness.faults.FaultPlan`` (NaN/Inf features,
    out-of-range labels/taus). Returns numpy copies — the engine casts
    on dispatch — or the inputs untouched when chaos is off."""
    if args.faults < 0:
        return X, y, taus
    import numpy as np

    from repro.robustness import VALUE_FAULTS, FaultPlan, corrupt_traffic

    X, y, taus = np.array(X), np.array(y), np.array(taus)
    S, T = y.shape
    plan = FaultPlan.random(args.faults, steps=T, tenants=S,
                            rate=args.fault_rate, kinds=VALUE_FAULTS)
    hits = corrupt_traffic(plan, X, y, taus, mode=mode, n_labels=2,
                           time_axis=1)
    print(f"[serve] chaos: {len(plan)} traffic fault(s) over {T} steps "
          f"(seed {args.faults}, rate {args.fault_rate}, "
          f"{len({h[1] for h in hits})} tenant(s) hit)")
    return X, y, taus


def _maybe_guard(args, eng, state, metrics, tracer):
    """``--guard``: wrap the engine in a ``TickGuard`` (admission +
    poison-lane quarantine). With ``--snapshot-dir`` an initial
    committed snapshot seeds the quarantine-restore source."""
    if not args.guard:
        return eng, None
    from repro.robustness import TickGuard

    store = None
    if args.snapshot_dir:
        from repro.serving import SessionStore
        store = SessionStore(args.snapshot_dir, metrics=metrics,
                             tracer=tracer)
        store.save(0, state, meta=eng.meta(), blocking=True)
    guard = TickGuard(eng, store=store, metrics=metrics)
    src = "snapshot" if store is not None else "none (tripped lanes stay frozen)"
    print(f"[serve] guard: admission + quarantine on (restore source: {src})")
    return guard, guard


def _drain_guard(guard, state):
    if guard is None:
        return state
    state = guard.finalize(state)  # flush the deferred poison sweep
    rep = guard.drain()
    print(f"[serve] guard: rejected {sum(rep['rejected'].values())} "
          f"input(s) {dict(rep['rejected'])}, "
          f"{rep['quarantines']} quarantine(s), "
          f"{rep['restores']} restore(s), "
          f"{len(rep['quarantined_lanes'])} lane(s) still frozen")
    return state


def _snapshot_injector(args, metrics):
    """``--faults`` + ``--snapshot-dir``: an I/O fault injector for the
    snapshot roundtrip — one transient write failure on the final save,
    so every chaos run exercises the async saver's retry loop (the
    randomized keyed plans live in the test/bench suites)."""
    if args.faults < 0:
        return None
    from repro.robustness import Fault, FaultInjector, FaultPlan
    plan = FaultPlan(args.faults, (
        Fault("store.write", args.steps, "write_fail", times=1),))
    return FaultInjector(plan, metrics=metrics)


def _check_shards(shards: int, sessions: int) -> None:
    """CLI-friendly validation of --shards against --sessions and the
    visible device count (engine ctors raise ValueError for the same)."""
    if shards < 1:
        raise SystemExit("--shards must be >= 1")
    if shards == 1:
        return
    if sessions % shards:
        raise SystemExit(
            f"--sessions {sessions} is not divisible by --shards "
            f"{shards}; pad the session count")
    import jax

    if shards > jax.device_count():
        raise SystemExit(
            f"--shards {shards} exceeds the {jax.device_count()} visible "
            "device(s); on CPU, set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=N before launching")


def _telemetry(args):
    """One metrics registry + optional JSONL tracer per serving run."""
    from repro.telemetry import MetricsRegistry, Tracer

    metrics = MetricsRegistry()
    tracer = Tracer(args.trace_out) if args.trace_out else None
    return metrics, tracer


def _validity_metrics(pvals, drifted, args, *, engine, metrics,
                      use_max=False):
    """Feed the recorded per-tenant p-value stream ((S, T), NaN on
    warmup/inactive ticks) through the online validity monitors
    (``repro.telemetry.validity``) and publish the results as metrics:
    rolling empirical coverage vs 1-eps, the p-value-uniformity KS
    distance, and the exchangeability drift martingales (per-tenant
    ``drift_log_m`` gauges for the first 8 tenants, aggregate gauges for
    all). ``use_max`` flags drift on the running max of log M (valid by
    Ville's inequality) — the right read-out for measures that
    re-conform quickly after a change. Returns the per-tenant flags."""
    import numpy as np

    from repro.telemetry.validity import (CoverageMonitor, DriftMonitor,
                                          UniformityMonitor)

    p = np.asarray(pvals, float)
    S, T = p.shape
    cov = CoverageMonitor(args.eps, S, window=T)
    uni = UniformityMonitor(S, window=T)
    drift = DriftMonitor(S, threshold=args.log_threshold)
    for t in range(T):
        col = p[:, t]
        cov.update(col)
        uni.update(col)
        drift.update(col)
    cov.export(metrics, engine=engine)
    uni.export(metrics, engine=engine)
    drift.export(metrics, engine=engine, use_max=use_max)
    stat = drift.max_log_m if use_max else drift.log_m()
    for s in range(min(S, 8)):
        metrics.gauge("drift_log_m", engine=engine,
                      tenant=s, injected=bool(drifted[s])).set(
            float(stat[s]))
    metrics.gauge("drift_tenants_injected", engine=engine).set(
        int(np.asarray(drifted).sum()))
    return drift.flagged(use_max=use_max)


def _emit_report(args, metrics, tracer, *, mode) -> None:
    """THE report path — every serving mode renders through the metrics
    text export (single formatting code path) and the same two output
    files (``--metrics-out`` JSON dump, ``--trace-out`` JSONL trace)."""
    print(f"[serve] telemetry ({mode}):")
    for line in metrics.to_text().splitlines():
        print("  " + line)
    if args.metrics_out:
        metrics.dump(args.metrics_out)
        print(f"[serve] metrics -> {args.metrics_out}")
    if tracer is not None:
        tracer.close()
        print(f"[serve] trace -> {tracer.path}")


def _device_line() -> str:
    """Platform, device kind and count of the devices JAX serves on."""
    import jax

    d = jax.devices()[0]
    return f"{d.platform} {d.device_kind} x{len(jax.devices())}"


def _check_steps(args) -> None:
    """``--steps`` must be whole ``--chunk``s, more than one: the first
    chunk is the compile warmup, and a shorter last chunk would compile
    a new tick count inside the timed window."""
    T, c = args.steps, args.chunk
    if T <= c or T % c:
        raise SystemExit(
            f"--steps ({T}) must be a multiple of --chunk ({c}) and exceed "
            "it: the first chunk is the compile warmup, and a shorter last "
            "chunk would compile inside the clock")


def _drive(args, drv, state, X, y, taus):
    """Serve the (S, T) traffic through ``drv.observe_many`` in
    ``--chunk``-tick dispatches. The first chunk is the compile warmup
    and stays outside the clock; every chunk syncs on its p-values.
    Returns the final state, the (S, T) p-values, the warmup seconds,
    the timed seconds and the timed session-steps/s."""
    import jax.numpy as jnp
    import numpy as np

    S, T = y.shape
    c = args.chunk
    Xt, yt, tt = (jnp.swapaxes(jnp.asarray(a), 0, 1) for a in (X, y, taus))

    def chunk(state, lo):
        hi = lo + c
        state, p = drv.observe_many(state, Xt[lo:hi], yt[lo:hi], tt[lo:hi])
        return state, np.asarray(p).T

    pvals = np.zeros((S, T), np.float32)
    t0 = time.perf_counter()
    state, pvals[:, :c] = chunk(state, 0)
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    for lo in range(c, T, c):
        state, pvals[:, lo:lo + c] = chunk(state, lo)
    dt = time.perf_counter() - t0
    rate = S * (T - c) / dt
    return state, pvals, warm, dt, rate


def _run_sessions(args, metrics, tracer):
    """Build the classification engine and serve ``--steps`` ticks of
    seeded drift traffic. Returns everything a report or a check of the
    served results needs."""
    from types import SimpleNamespace

    from repro.serving import ServingEngine

    S, T, dim = args.sessions, args.steps, args.dim
    _check_steps(args)
    _check_shards(args.shards, S)
    eng = ServingEngine(
        n_sessions=S, capacity=args.capacity, dim=dim, k=args.k,
        n_labels=2, window=args.window, instrument=True, metrics=metrics,
        tracer=tracer, shards=args.shards)
    state = eng.init_state()
    metrics.gauge("serve_shards", mode="classification").set(args.shards)
    print(f"[serve] engine: {S} sessions x cap {args.capacity} "
          f"(window={args.window}, k={args.k}, shards={args.shards}, "
          f"chunk={args.chunk}) on {_device_line()}")

    X, y, taus, drifted = _class_drift_traffic(args, S, T, dim)
    X, y, taus = _chaos_traffic(args, X, y, taus, mode="classification")
    drv, guard = _maybe_guard(args, eng, state, metrics, tracer)
    state, pvals, warm, dt, rate = _drive(args, drv, state, X, y, taus)
    metrics.gauge("serve_wall_s", mode="classification").set(dt)
    metrics.gauge("serve_session_steps_per_s", mode="classification").set(
        rate)
    eng.telemetry.drain()
    state = _drain_guard(guard, state)
    return SimpleNamespace(eng=eng, state=state, pvals=pvals, X=X, y=y,
                           taus=taus, drifted=drifted, warmup_s=warm,
                           steps_per_s=rate)


def _serve_sessions(args) -> int:
    """Multi-tenant online CP serving on the micro-batching engine."""
    metrics, tracer = _telemetry(args)
    run = _run_sessions(args, metrics, tracer)
    _validity_metrics(run.pvals[:, 1:], run.drifted, args,
                      engine="classification", metrics=metrics)
    rc = 0
    if args.snapshot_dir:
        rc = _snapshot_roundtrip(args, run.state, run.eng, metrics, tracer)
    _emit_report(args, metrics, tracer, mode="classification")
    return rc


def _snapshot_roundtrip(args, state, eng, metrics, tracer) -> int:
    """Save + restore the final state, asserting bit-exactness. With
    ``--shards > 1`` the save goes through the async double-buffered
    sharded saver (host I/O of shard i overlaps the device pull of
    shard i+1 and any still-running compute)."""
    import jax
    import numpy as np

    from repro.serving import AsyncShardedSaver, SessionStore

    injector = _snapshot_injector(args, metrics)
    store = SessionStore(args.snapshot_dir, metrics=metrics, tracer=tracer,
                         injector=injector)
    if args.shards > 1 or injector is not None:
        # chaos mode routes even single-shard saves through the async
        # saver: its keyed-backoff retry loop is what absorbs injected
        # transient write failures
        saver = AsyncShardedSaver(store, max(args.shards, 1),
                                  metrics=metrics, seed=args.seed)
        saver.save(args.steps, state, meta=eng.meta())
        saver.close()
    else:
        store.save(args.steps, state, meta=eng.meta(), blocking=True)
    eng2, state2, step = store.restore_engine()
    leaves2 = jax.tree_util.tree_leaves(state2)
    same = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(state), leaves2))
    # a sharded snapshot must come back on as many devices as it left
    placed = eng2.shards == eng.shards and all(
        len(a.sharding.device_set) == eng.shards for a in leaves2)
    print(f"[serve] snapshot@step {step} -> restore "
          f"{'bit-exact' if same else 'MISMATCH'} on {eng2.shards} "
          f"shard(s){'' if placed else f' (saved with {eng.shards})'}")
    return 0 if same and placed else 1


def _serve_registry(args) -> int:
    """Multi-tenant sliding-window serving through the measure registry.

    Python-loops over tenants (the registry predictors are the exact-
    shape API; the vmapped engines in ``repro.serving`` / ``repro.
    regression`` cover knn/regression) — this is the serving path for
    measures without a fixed-shape engine, e.g. ``bootstrap``.

    Drift is flagged on the *running maximum* of the log martingale: a
    registry measure that retrains on the live window every tick (the
    bootstrap ensemble especially) re-conforms within a few ticks of a
    change, so the evidence is a brief spike, not a sustained climb —
    and with a strongly adaptive measure even the spike can stay under
    the threshold. That fast re-conformance is expected behavior, not a
    detection bug; the sustained-drift showcase is the vmapped k-NN
    engine mode above.
    """
    import numpy as np

    from repro.serving import registry
    from repro.telemetry import EngineTelemetry

    spec = registry.get(args.measure)
    if spec.intervals is not None:
        raise SystemExit(
            f"--measure {args.measure} is a regression measure; use "
            "--regression for the engine-served regression path")
    S, T, dim, w = args.sessions, args.steps, args.dim, args.window
    warm = min(w, max(8, T // 4))
    if T <= warm + 2:
        raise SystemExit(f"--steps must exceed the warmup ({warm + 2})")

    metrics, tracer = _telemetry(args)
    tele = EngineTelemetry(engine="registry", metrics=metrics,
                           tracer=tracer)
    X, y, _, drifted = _class_drift_traffic(args, S, T, dim)
    X, y = np.asarray(X), np.asarray(y)

    hp_all = {"k": args.k, "n_labels": 2, "B": args.boot_b,
              "depth": args.tree_depth}
    hp = {k: v for k, v in hp_all.items() if k in spec.defaults}
    t0 = time.time()
    pvals = np.full((S, T), np.nan, np.float32)
    for s in range(S):
        cp = registry.ConformalPredictor(
            args.measure,
            **({**hp, "seed": args.seed + s} if "seed" in spec.defaults
               else hp))
        with tele.timed("fit", signature=args.measure, tenants=1):
            cp.fit(X[s, :warm], y[s, :warm])
        for t in range(warm, T):
            with tele.timed("pvalues", signature=args.measure, tenants=1):
                pvals[s, t] = np.asarray(
                    cp.pvalues(X[s, t][None]))[0, y[s, t]]
            with tele.timed("observe", signature=args.measure, tenants=1):
                cp.observe(X[s, t], int(y[s, t]))
            if cp.n > w:
                with tele.timed("evict", signature=args.measure,
                                tenants=1):
                    cp.evict(0)
    dt = time.time() - t0
    metrics.gauge("serve_wall_s", mode="registry",
                  measure=args.measure).set(dt)
    metrics.gauge("serve_session_steps_per_s", mode="registry",
                  measure=args.measure).set(S * (T - warm) / dt)
    _validity_metrics(pvals[:, warm:], drifted, args, engine="registry",
                      metrics=metrics, use_max=True)
    _emit_report(args, metrics, tracer, mode=f"registry:{args.measure}")
    return 0


def _run_regression(args, metrics, tracer):
    """Build the regression engine, serve ``--steps`` ticks of seeded
    drift traffic, then read exact prediction intervals for a fresh
    query batch, every tenant in one dispatch."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.regression import RegressionServingEngine

    S, T, dim = args.sessions, args.steps, args.dim
    _check_steps(args)
    _check_shards(args.shards, S)
    eng = RegressionServingEngine(
        n_sessions=S, capacity=args.capacity, dim=dim, k=args.k,
        window=args.window, instrument=True, metrics=metrics,
        tracer=tracer, shards=args.shards)
    state = eng.init_state()
    metrics.gauge("serve_shards", mode="regression").set(args.shards)
    print(f"[serve] regression engine: {S} sessions x cap {args.capacity} "
          f"(window={args.window}, k={args.k}, shards={args.shards}, "
          f"chunk={args.chunk}) on {_device_line()}")

    # per-tenant linear traffic y = <w_s, x> + noise; odd tenants change
    # their regression function at T/2 (streaming drift detection)
    key = jax.random.PRNGKey(args.seed)
    kw, kx, kn, kt = jax.random.split(key, 4)
    W = jax.random.normal(kw, (S, dim), jnp.float32)
    X = jax.random.normal(kx, (S, T, dim), jnp.float32)
    noise = 0.1 * jax.random.normal(kn, (S, T), jnp.float32)
    y = jnp.einsum("sd,std->st", W, X) + noise
    drifted = jnp.arange(S) % 2 == 1
    late = jnp.arange(T)[None, :] >= T // 2
    y = jnp.where(drifted[:, None] & late, y + args.drift, y)
    taus = jax.random.uniform(kt, (S, T), dtype=jnp.float32)
    X, y, taus = _chaos_traffic(args, X, y, taus, mode="regression")
    drv, guard = _maybe_guard(args, eng, state, metrics, tracer)
    state, pvals, warm, dt, rate = _drive(args, drv, state, X, y, taus)
    metrics.gauge("serve_wall_s", mode="regression").set(dt)
    metrics.gauge("serve_session_steps_per_s", mode="regression").set(rate)
    eng.telemetry.drain()
    state = _drain_guard(guard, state)

    Xq = jax.random.normal(jax.random.PRNGKey(args.seed + 1),
                           (4, dim), jnp.float32)
    iv = np.asarray(eng.intervals(state, Xq, epsilon=args.eps))
    return SimpleNamespace(eng=eng, state=state, pvals=pvals, X=X, y=y,
                           taus=taus, drifted=drifted, warmup_s=warm,
                           steps_per_s=rate, Xq=Xq, intervals=iv)


def _serve_regression(args) -> int:
    """Multi-tenant streaming regression CP on the regression engine."""
    import numpy as np

    metrics, tracer = _telemetry(args)
    run = _run_regression(args, metrics, tracer)
    warm = 2 * args.k  # k-NN warmup: earliest p-values are degenerate
    _validity_metrics(run.pvals[:, warm:], run.drifted, args,
                      engine="regression", metrics=metrics)
    iv = run.intervals
    metrics.gauge("intervals_finite_frac", engine="regression").set(
        float(np.isfinite(iv).mean()))
    metrics.gauge("intervals_median_width", engine="regression").set(
        float(np.nanmedian(iv[:, :, 1] - iv[:, :, 0])))
    rc = 0
    if args.snapshot_dir:
        rc = _snapshot_roundtrip(args, run.state, run.eng, metrics, tracer)
    _emit_report(args, metrics, tracer, mode="regression")
    return rc


def _serve_replay(args) -> int:
    """Trace replay / load-test mode (``--replay``): drive one engine
    from a recorded trace or a ``loadgen:<workload>`` spec, report
    p50/p99-under-load, and (``--auto-tune``) swap the hand-tuned
    observe_many chunk for the cost model's ``suggest_chunk``."""
    from repro.telemetry import (CostModel, calibrate_engine, iter_trace,
                                 loadgen, replay)
    from repro.telemetry.tracer import capacity_bucket

    kind = "regression" if args.regression else "classification"
    slo_s = args.slo_ms / 1000.0 if args.slo_ms > 0 else None
    speedup = float(args.speedup)  # accepts "inf"

    if args.replay.startswith("loadgen:"):
        plan = None
        if args.faults >= 0:
            from repro.robustness import VALUE_FAULTS, FaultPlan
            plan = FaultPlan.random(
                args.faults, steps=args.steps, tenants=args.sessions or 8,
                rate=args.fault_rate,
                kinds=VALUE_FAULTS + ("duplicate_arrival", "delay"),
                param=0.001)
            print(f"[serve] chaos: stamping {len(plan)} fault(s) onto "
                  f"the generated trace (seed {args.faults})")
        workload = args.replay.split(":", 1)[1]
        records = loadgen.generate(
            workload, ops=args.steps, tenants=args.sessions or 8,
            capacity=args.capacity, engine=kind, rate=args.rate,
            seed=args.seed, slo_s=slo_s, faults=plan)
        src = args.replay
    else:
        records = list(iter_trace(args.replay))
        src = args.replay
    tenants = max(int(r.get("tenants", 1)) for r in records)
    cap = max((int(r.get("capacity", 0)) for r in records),
              default=0) or args.capacity

    # cost model: load one > fit from the trace's steady timing > probe
    # the engine (loadgen traces record arrivals, not costs)
    model = None
    chunk = None
    if args.cost_model:
        model = CostModel.load(args.cost_model)
        print(f"[serve] cost model <- {args.cost_model}")
    elif args.auto_tune or args.cost_model_out:
        model = CostModel.fit(records, source=src)
        if not model.entries:
            print("[serve] trace carries no steady timing; "
                  "calibrating the engine")
            model = CostModel.fit(
                calibrate_engine(kind, tenants=tenants, capacity=cap,
                                 dim=args.dim, k=args.k, seed=args.seed),
                source="calibrate")
    if args.auto_tune and model is not None and model.entries:
        chunk = model.suggest_chunk(cap_bucket=capacity_bucket(cap),
                                    engine=kind)
        print(f"[serve] auto-tune: observe_many chunk <- {chunk}")
    if args.cost_model_out and model is not None:
        model.save(args.cost_model_out)
        print(f"[serve] cost model -> {args.cost_model_out}")

    if args.shards > 1 and args.shards > tenants:
        raise SystemExit(f"--shards {args.shards} exceeds the trace's "
                         f"{tenants} tenants")
    metrics, tracer = _telemetry(args)
    metrics.gauge("serve_shards", mode="replay").set(args.shards)
    res = replay(records, engine=kind, dim=args.dim, k=args.k,
                 window=min(args.window, cap),  # trace may be smaller
                 speedup=speedup, seed=args.seed,
                 slo_s=slo_s, chunk=chunk, eps=args.eps, metrics=metrics,
                 tracer=tracer, shards=args.shards,
                 shed_depth=args.shed_depth if args.shed_depth > 0 else None,
                 guard=args.guard)
    rep = res.report
    print(f"[serve] replay {src} -> {kind} engine "
          f"({rep['tenants']} tenants x cap {rep['capacity']}, "
          f"{rep['shards']} shard(s)): "
          f"{rep['ops_replayed']} ops ({rep['ops_skipped']} skipped), "
          f"{rep['ticks']} ticks in {rep['wall_s']:.3f}s "
          f"({rep['steps_per_s']:.0f} session steps/s)")
    if rep["shards"] > 1:
        for sh in rep["per_shard"]:
            print(f"  shard {sh['shard']}: {sh['tenants']} tenants, "
                  f"{sh['session_steps']} steps, occupancy mean "
                  f"{sh['occupancy_mean']:.1f} max {sh['occupancy_max']}")
    for op, d in rep["per_op"].items():
        print(f"  {op:12s} p50={d['p50_s'] * 1e3:8.3f}ms "
              f"p99={d['p99_s'] * 1e3:8.3f}ms "
              f"sojourn_p99={d['sojourn_p99_s'] * 1e3:8.3f}ms "
              f"n={d['count']:.0f}")
    if slo_s is not None:
        print(f"  SLO {args.slo_ms:g}ms: violation fraction "
              f"{rep['slo_violation_frac']:.4f}")
    print(f"  queue depth max {rep['queue_depth_max']:.0f}")
    if rep.get("duplicates_dropped"):
        print(f"  chaos: {rep['duplicates_dropped']} duplicate "
              f"arrival(s) dropped")
    if rep.get("shed_depth") is not None:
        print(f"  shed(depth {rep['shed_depth']}): "
              f"{rep['shed_ops']} read(s) shed, "
              f"{rep['deferred_observes']} observe(s) deferred")
    if "guard" in rep:
        g = rep["guard"]
        print(f"  guard: rejected {sum(g['rejected'].values())} input(s) "
              f"{dict(g['rejected'])}, {g['quarantines']} quarantine(s), "
              f"{g['restores']} restore(s)")
    _emit_report(args, metrics, tracer, mode=f"replay:{kind}")
    return 0


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-tokens", type=int, default=8)
    ap.add_argument("--calib", type=int, default=256)
    ap.add_argument("--eps", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    # multi-tenant online CP mode (repro.serving)
    ap.add_argument("--sessions", type=int, default=0,
                    help="serve N concurrent CP sessions (0 = LM mode)")
    ap.add_argument("--steps", type=int, default=128)
    ap.add_argument("--dim", type=int, default=8)
    ap.add_argument("--k", type=int, default=7)
    ap.add_argument("--capacity", type=int, default=128)
    ap.add_argument("--window", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=1,
                    help="engine modes: ticks per observe_many dispatch "
                         "(--steps must be a multiple)")
    ap.add_argument("--drift", type=float, default=2.0)
    ap.add_argument("--log-threshold", type=float, default=2.0)
    ap.add_argument("--snapshot-dir", default="")
    ap.add_argument("--shards", type=int, default=1,
                    help="shard the session axis across N devices "
                         "(engine modes: one shard_map'd dispatch per "
                         "tick, bit-identical to --shards 1; replay "
                         "mode: N per-shard engines with merged "
                         "metrics). On CPU, force virtual devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N")
    ap.add_argument("--regression", action="store_true",
                    help="with --sessions: serve streaming regression CP "
                         "(prediction intervals) instead of classification")
    ap.add_argument("--measure", default="",
                    help="with --sessions: serve through the measure "
                         "registry (e.g. bootstrap) instead of the "
                         "vmapped engine")
    ap.add_argument("--boot-b", type=int, default=5,
                    help="bootstrap ensemble size B (--measure bootstrap)")
    ap.add_argument("--tree-depth", type=int, default=3,
                    help="bootstrap tree depth (--measure bootstrap)")
    # trace replay / load testing (repro.telemetry.replay)
    ap.add_argument("--replay", default="",
                    help="replay a JSONL trace file, or synthesize one "
                         "with loadgen:<workload> (steady|bursty|diurnal|"
                         "zipf, --steps ops, --sessions tenants)")
    ap.add_argument("--speedup", default="inf",
                    help="compress the trace's inter-arrival times by "
                         "this factor; 'inf' (default) replays "
                         "back-to-back (deterministic, CI mode)")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="latency SLO in ms; report the fraction of "
                         "replayed ops whose sojourn exceeds it "
                         "(0 = no SLO)")
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="loadgen mean arrival rate, ops/s of the trace "
                         "clock (rescaled by --speedup)")
    ap.add_argument("--auto-tune", action="store_true",
                    help="with --replay: fit the per-(op, capacity-"
                         "bucket) cost model and use its suggest_chunk "
                         "instead of the hand-tuned observe_many chunk")
    ap.add_argument("--cost-model", default="",
                    help="load a fitted cost model JSON instead of "
                         "fitting/calibrating one")
    ap.add_argument("--cost-model-out", default="",
                    help="save the fitted cost model JSON here")
    # telemetry (repro.telemetry) — serving modes only
    ap.add_argument("--metrics-out", default="",
                    help="write the end-of-run metrics snapshot to this "
                         "JSON file (the same snapshot the report prints)")
    ap.add_argument("--trace-out", default="",
                    help="record one JSONL trace record per engine op to "
                         "this file (schema: repro.telemetry.tracer)")
    # chaos / fault tolerance (repro.robustness)
    ap.add_argument("--faults", type=int, default=-1, metavar="SEED",
                    help="inject a keyed random fault plan (repro."
                         "robustness.FaultPlan.random) with this seed: "
                         "engine modes corrupt the synthetic traffic and "
                         "(with --snapshot-dir) the snapshot I/O path; "
                         "loadgen replay stamps value/duplicate/delay "
                         "faults onto the trace. -1 (default) disables")
    ap.add_argument("--fault-rate", type=float, default=0.02,
                    help="per-(step, site) fault probability for --faults")
    ap.add_argument("--guard", action="store_true",
                    help="wrap the engine in the robustness TickGuard: "
                         "in-graph admission of observe inputs + poison-"
                         "lane quarantine (restore from --snapshot-dir "
                         "when set). Engine-serving and replay modes")
    ap.add_argument("--shed-depth", type=int, default=0,
                    help="with --replay: shed reads once the replay "
                         "backlog exceeds this depth and defer observes "
                         "past twice it (0 = no shedding)")
    # static invariant audit (repro.analysis.audit)
    ap.add_argument("--audit", action="store_true",
                    help="run the compiled-artifact invariant audit over "
                         "the engine matrix and exit (no serving); "
                         "nonzero exit on any violation")
    ap.add_argument("--audit-out", default="audit_report.json",
                    help="with --audit: JSON report path")
    return ap


def main(argv=None) -> int:
    args = _parser().parse_args(argv)

    if args.audit:
        from repro.analysis import audit as audit_m
        return audit_m.main(
            ["--out", args.audit_out, "--no-reexec",
             "--max-shards", str(max(args.shards, 1))])
    if args.chunk < 1:
        raise SystemExit("--chunk must be >= 1")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.replay:
        if args.measure:
            raise SystemExit("--replay and --measure are exclusive")
        return _serve_replay(args)
    if args.sessions > 0:
        if args.measure:
            if args.regression:
                raise SystemExit("--measure and --regression are exclusive")
            if args.guard or args.faults >= 0:
                raise SystemExit("--guard/--faults cover the engine and "
                                 "replay modes, not --measure")
            return _serve_registry(args)
        if args.regression:
            return _serve_regression(args)
        return _serve_sessions(args)
    if args.regression:
        raise SystemExit("--regression requires --sessions N")
    if args.measure:
        raise SystemExit("--measure requires --sessions N")

    import jax
    import jax.numpy as jnp

    import repro.configs as cfgs
    from repro.core.lm_conformal import (ConformalOodDetector,
                                         sequence_embedding)
    from repro.data.lm_pipeline import TokenStream
    from repro.models import lm

    cfg = cfgs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    B, P, G = args.requests, args.prompt_len, args.gen_tokens
    params = lm.init_lm(jax.random.PRNGKey(args.seed), cfg)

    # ---- calibration traffic -> conformal OOD head ------------------------
    stream = TokenStream(cfg, args.calib, P, seed=args.seed)
    calib_batch = {k: jnp.asarray(v) for k, v in stream.batch_at(0).items()}
    emb_fn = jax.jit(lambda p, b: sequence_embedding(p, cfg, b, lm))
    calib_emb = emb_fn(params, calib_batch)
    ood = ConformalOodDetector(k=7).fit(calib_emb)
    print(f"[serve] conformal OOD head fit on {args.calib} sequences")

    # ---- requests: half in-distribution, half corrupted --------------------
    req_stream = TokenStream(cfg, B, P, seed=args.seed + 1)
    req = {k: jnp.asarray(v) for k, v in req_stream.batch_at(0).items()}
    tokens = req["tokens"]
    key = jax.random.PRNGKey(args.seed + 2)
    noise = jax.random.randint(key, tokens[B // 2:].shape, 0,
                               cfg.vocab_size, dtype=tokens.dtype)
    tokens = tokens.at[B // 2:].set(noise)  # OOD half: uniform tokens
    req["tokens"] = tokens

    # ---- decode loop -------------------------------------------------------
    max_len = P + G
    cache = lm.init_cache(cfg, B, max_len)
    if cfg.is_encoder_decoder:
        cache["cross"] = lm.prefill_cross_cache(params, cfg, req["frames"])
    step = jax.jit(
        lambda p, t, c, i: lm.decode_step(p, cfg, t, c, i),
        donate_argnums=(2,))

    t0 = time.time()
    logits = None
    for i in range(P):  # prefill via teacher-forced decode steps
        logits, cache = step(params, tokens[:, i:i + 1], cache, i)
    generated = []
    cur = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    for g in range(G):
        generated.append(cur)
        logits, cache = step(params, cur, cache, P + g)
        cur = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    gen = jnp.concatenate(generated, axis=1)
    dt = time.time() - t0

    # ---- conformal OOD p-values per request -------------------------------
    req_emb = emb_fn(params, req)
    pvals = ood.pvalues(req_emb)
    print(f"[serve] {B} requests x {G} tokens in {dt:.2f}s "
          f"({B * G / dt:.1f} tok/s)")
    for i in range(B):
        flag = "OOD!" if pvals[i] <= args.eps else "ok  "
        print(f"  req {i:2d} [{flag}] p={float(pvals[i]):.3f} "
              f"gen={[int(t) for t in gen[i][:6]]}")
    in_p = pvals[:B // 2]
    out_p = pvals[B // 2:]
    print(f"[serve] mean p in-dist={float(jnp.mean(in_p)):.3f} "
          f"corrupted={float(jnp.mean(out_p)):.3f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
