"""One run of one benchmark cell: set-up, the measured window, the check.

A cell is a configuration (``configs/<name>.json``: the deployment's
sizes and its measure) under a traffic mix (``mixes/<name>.json``). The
measure file (``measures/<measure>.py``) knows how to build the engine,
generate the traffic, issue a read, and recompute every answer in
float64. Nothing here is specific to one cell.

The window drives the engines' public API exactly as a server does:

* ticks: ``observe_many`` in chunks of ``mix["chunk"]`` ticks, every
  tenant active on every tick; each chunk syncs on its p-values.
  ``"ticks_per_s": null`` runs chunks back to back (saturated);
  a number offers them open-loop at that rate;
* reads: ``mix["reads_per_s"]`` reads arrive open-loop, each for one
  tenant with ``mix["queries"]`` query points. Between tick chunks the
  loop serves pending reads in one fixed-shape read dispatch over every
  tenant (``predict`` / ``intervals``); a read's latency runs from its
  arrival to the moment its answer reaches the host.

All traffic comes from ``--seed``: tick data is a pure function of
(seed, tick, tenant), so any tenant's stream can be regenerated for
the check, and the reads are a fixed number of arrivals spread
uniformly over the window (a Poisson process given its count).
"""
from __future__ import annotations

import collections
import gc
import json
import shutil
import tempfile
import time
from pathlib import Path
from types import ModuleType

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path, name: str) -> ModuleType:
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


class Cell:
    """A configuration under a traffic mix, with its measure module."""

    def __init__(self, name: str, cfg: dict, mix: dict, chips: int,
                 bench_dir: Path = BENCH):
        self.name, self.cfg, self.mix, self.chips = name, cfg, mix, chips
        self.bench_dir = bench_dir
        self.measure = load_module(
            bench_dir / "measures" / f"{cfg['measure']}.py",
            f"chipbench_measure_{cfg['measure']}")
        if cfg["window"] % mix["chunk"]:
            raise ValueError(f"{name}: the window {cfg['window']} is not a "
                             f"whole number of {mix['chunk']}-tick chunks")
        self._eng = None
        self._fns = None

    @classmethod
    def from_benchmark(cls, bench: dict, workload: str, root: Path = ROOT):
        """The cell ``workload`` of a parsed BENCHMARK.json at ``root``."""
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        w = cells[workload]
        conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
        bench_dir = root / bench["paths"][0]
        return cls(workload, _json(root / conf["file"]),
                   _json(bench_dir / "mixes" / f"{w['traffic']}.json"),
                   int(w["chips"]), bench_dir)

    # -- compiled pieces (built once per process, reused across runs) ---------

    @property
    def dtype(self):
        import jax.numpy as jnp

        return jnp.dtype(self.cfg.get("dtype", "float32"))

    def engine(self):
        if self._eng is None:
            self._eng = self.measure.engine(self.cfg, dtype=self.dtype,
                                            shards=self.chips)
        return self._eng

    def fns(self):
        """Jitted traffic, query and answer helpers."""
        if self._fns is not None:
            return self._fns
        import jax
        import jax.numpy as jnp

        cfg, mix, meas = self.cfg, self.mix, self.measure
        S, T, dim = cfg["tenants"], mix["chunk"], cfg["dim"]
        q = mix["queries"]
        m = q * mix["read_slots"]
        dtype = self.dtype

        point = lambda key, t, s: meas.point(key, t, s, cfg, mix)  # noqa
        grid = jax.vmap(jax.vmap(point, (None, None, 0)), (None, 0, None))

        def traffic(key, t0):
            ticks = t0 + jnp.arange(T, dtype=jnp.int32)
            return grid(key, ticks, jnp.arange(S, dtype=jnp.int32))

        def traffic_cast(key, t0):
            xs, ys, taus = traffic(key, t0)
            return xs.astype(dtype), ys, taus.astype(dtype)

        def queries(qs, ten, slot):
            rows = slot[:, None] * q + jnp.arange(q)[None, :]
            return jnp.zeros((S, m, dim), dtype).at[ten[:, None], rows].set(
                qs.astype(dtype), mode="drop")

        def answers(out, ten, slot):
            rows = slot[:, None] * q + jnp.arange(q)[None, :]
            return out[jnp.minimum(ten, S - 1)[:, None], rows].astype(
                jnp.float32)

        def take(tree, idx):
            return jax.tree_util.tree_map(lambda a: a[idx], tree)

        def take_tenants(a, idx):
            return a[:, idx]

        self._fns = dict(
            traffic=jax.jit(traffic),
            traffic_cast=jax.jit(traffic_cast),
            queries=jax.jit(queries),
            answers=jax.jit(answers),
            take=jax.jit(take),
            take_tenants=jax.jit(take_tenants),
        )
        return self._fns


def metric_names(bench: dict, cell: str, per_layer: bool) -> list[str]:
    """The cell's end-to-end metrics, or its per-layer ones."""
    group = bench["per_layer" if per_layer else "end_to_end"]
    return [m["name"] for m in group if cell in m.get("workloads", [cell])]


def read_metrics(rec: dict, names, bench_dir: Path = BENCH) -> dict:
    """Each metric's reader applied to a run record; a reader that finds
    nothing to read leaves its metric out. The reader of ``<q>.<part>``
    is ``metrics/<q>.<part>.py``, or else ``metrics/<q>.py``: one
    quantity, split by the end-to-end metric it moves, keeps one reader."""
    out = {}
    for name in names:
        path = bench_dir / "metrics" / f"{name}.py"
        if not path.exists():
            path = bench_dir / "metrics" / f"{name.split('.')[0]}.py"
        reader = load_module(path,
                             "chipbench_metric_" + name.replace(".", "_"))
        value = reader.read(rec)
        if value is not None:
            out[name] = {"value": float(value), "unit": reader.UNIT}
    return out


def seed_key(seed: int):
    """A threefry key from any whole number (64 bits of it)."""
    import jax

    words = np.random.SeedSequence(seed % 2 ** 64).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def _span(name: str):
    """A host span of the loop, written into the profiler's trace (as
    ``chipbench.<name>``, which ``trace.py`` reads back)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(f"chipbench.{name}")


class _Reads:
    """The open-loop read stream: arrivals, FIFO queue, answers."""

    def __init__(self, cell: Cell, seed: int, seconds: float):
        cfg, mix = cell.cfg, cell.mix
        rng = np.random.default_rng(np.random.SeedSequence([seed % 2 ** 64,
                                                            2]))
        n = int(round(mix["reads_per_s"] * seconds))
        self.arrival = np.sort(rng.uniform(0.0, seconds, n))
        self.tenant = rng.integers(0, cfg["tenants"], n)
        self.q = mix["queries"]
        self.X = np.stack([cell.measure.queries(rng, cfg, int(s), self.q)
                           for s in self.tenant]) if n else np.zeros(
            (0, self.q, cfg["dim"]), np.float32)
        self.dispatch = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.ticks = np.zeros(n, np.int64)
        self.answer = [None] * n
        self.batches = 0
        self._next = 0
        self._queue: collections.deque = collections.deque()
        self.P, self.R = mix["reads_per_dispatch"], mix["read_slots"]

    def arrive(self, t: float) -> None:
        while self._next < len(self.arrival) and self.arrival[self._next] <= t:
            self._queue.append(self._next)
            self._next += 1

    def pending(self) -> bool:
        return bool(self._queue)

    def first_pending(self) -> float:
        return self.arrival[self._queue[0]]

    def next_arrival(self) -> float:
        return (self.arrival[self._next] if self._next < len(self.arrival)
                else np.inf)

    def batch(self) -> list[tuple[int, int]]:
        """Up to P queued reads in arrival order, at most R per tenant:
        (read, slot) pairs. The rest wait for the next dispatch."""
        used: collections.Counter = collections.Counter()
        take, keep = [], collections.deque()
        while self._queue:
            i = self._queue.popleft()
            s = int(self.tenant[i])
            if len(take) < self.P and used[s] < self.R:
                take.append((i, used[s]))
                used[s] += 1
            else:
                keep.append(i)
        self._queue = keep
        return take


def _serve_reads(cell, eng, state, reads, fns, clock, ticks_done):
    import jax.numpy as jnp

    S = cell.cfg["tenants"]
    with _span("read_batch"):
        take = reads.batch()
        P, q, dim = reads.P, reads.q, cell.cfg["dim"]
        qs = np.zeros((P, q, dim), np.float32)
        ten = np.full(P, S, np.int32)  # tenant S: a padding row, dropped
        slot = np.zeros(P, np.int32)
        for j, (i, sl) in enumerate(take):
            qs[j], ten[j], slot[j] = reads.X[i], reads.tenant[i], sl
        t = clock()
        Xq = fns["queries"](jnp.asarray(qs), jnp.asarray(ten),
                            jnp.asarray(slot))
        out = cell.measure.read(eng, state, Xq, cell.mix)
        ans = np.asarray(fns["answers"](out, jnp.asarray(ten),
                                        jnp.asarray(slot)))
        done = clock()
    for j, (i, _) in enumerate(take):
        reads.dispatch[i], reads.done[i] = t, done
        reads.ticks[i] = ticks_done
        reads.answer[i] = ans[j]
    reads.batches += 1


def run(cell: Cell, seed: int, seconds: float, *, trace: bool = False,
        t_proc0: float | None = None) -> dict:
    """One run: set-up, the window, then the check. Returns the run
    record the metric readers take their numbers from."""
    import jax
    import jax.numpy as jnp

    clock = time.perf_counter
    t_proc0 = clock() if t_proc0 is None else t_proc0
    cfg, mix, meas = cell.cfg, cell.mix, cell.measure
    S, T, W = cfg["tenants"], mix["chunk"], cfg["window"]
    t_run0 = clock()
    eng = cell.engine()
    state = eng.init_state()
    fns = cell.fns()
    jax.block_until_ready(state)
    t_init = clock()
    key = seed_key(seed)
    traffic = fns["traffic"] if cell.dtype == jnp.float32 else fns[
        "traffic_cast"]

    # set-up: fill every window by serving ticks in the cell's chunk
    # shape (no engine call loads a window at once), then warm the read
    for c in range(W // T):
        xs, ys, taus = traffic(key, jnp.int32(c * T))
        state, _ = eng.observe_many(state, xs, ys, taus)
    jax.block_until_ready(state)
    t_fill = clock()
    reads = _Reads(cell, seed, seconds)
    warm = _Reads(cell, seed, 1.0)
    warm.arrive(np.inf)
    _serve_reads(cell, eng, state, warm, fns, clock, W)
    jax.block_until_ready(state)
    eng.telemetry.drain()  # the fill's counters stay out of the window
    gc.collect()  # set-up's garbage is not the window's to collect

    rng = np.random.default_rng(np.random.SeedSequence([seed % 2 ** 64, 3]))
    check = mix["check"]
    tick_tenants = np.sort(rng.choice(S, min(check["tenants"], S),
                                      replace=False))
    served_p = []  # (tick, p (len(tick_tenants),))
    chunks = []  # (traffic, dispatch, enqueued, p-values on host)

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(trace_dir)
    compiles = _CompileCounter()
    gc_pauses = _GcPauses()
    ticks_done = W
    rate = mix["ticks_per_s"]
    period = None if rate is None else T / rate
    t_start = clock()
    setup_s = t_start - t_proc0
    end = t_start + seconds
    next_due = t_start
    with compiles, gc_pauses, _span("window"):
        while True:
            now = clock()
            reads.arrive(now - t_start)
            tick_due = now < end and (period is None or next_due <= now)
            # open-loop ticks and reads are served first come, first served
            if tick_due and (period is None or not reads.pending()
                             or next_due - t_start <= reads.first_pending()):
                t_traffic = clock()
                with _span("traffic"):
                    xs, ys, taus = traffic(key, jnp.int32(ticks_done))
                t_disp = clock()
                with _span("chunk_dispatch"):
                    state, p = eng.observe_many(state, xs, ys, taus)
                t_queued = clock()
                with _span("pvalue_sync"):
                    p = np.asarray(p)
                chunks.append((t_traffic, t_disp, t_queued, clock()))
                served_p.extend((ticks_done + j, p[j, tick_tenants])
                                for j in range(T))
                ticks_done += T
                if period is not None:
                    next_due += period
                    continue
                reads.arrive(clock() - t_start)
            if reads.pending():
                _serve_reads(cell, eng, state, reads, fns, clock,
                             ticks_done)
                continue
            if now >= end:
                break  # every read arrives inside the window
            wake = min(reads.next_arrival() + t_start, end,
                       np.inf if period is None else next_due)
            with _span("wait"):
                time.sleep(max(0.0, wake - clock()))
    t_stop = clock()
    if trace:
        jax.profiler.stop_trace()

    jax.block_until_ready(state)
    peak = _memory_peak(cell.chips)
    counters = eng.telemetry.drain()
    rec = dict(
        cell=cell.name, seed=seed, seconds=seconds, setup_s=setup_s,
        setup_parts=dict(start=t_run0 - t_proc0, init=t_init - t_run0,
                         fill=t_fill - t_init, warm=t_start - t_fill),
        t_start=t_start, t_stop=t_stop, chunks=chunks, tenants=S,
        chunk=T, ticks_in_window=ticks_done - W,
        tick_window_s=(chunks[-1][-1] - t_start) if chunks else 0.0,
        reads=dict(arrival=reads.arrival + t_start, dispatch=reads.dispatch,
                   done=reads.done, batches=reads.batches),
        counters=counters, memory_peak_bytes=peak,
        compiles_in_window=compiles.count,
        longest_gap=_longest_gap(chunks, t_start),
        gc_pause_max_s=gc_pauses.longest,
        cfg=cfg, mix=mix, measure=meas, chips=cell.chips)
    if trace:
        from chipbench import trace as trace_m

        rec["trace"] = trace_m.summarize(
            trace_m.load(trace_dir), meas.TICK_PROGRAM, meas.READ_PROGRAM,
            cell.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the check, after the window: pull what it needs, free the state
    served = _served(cell, state, fns, key, rng, tick_tenants, served_p,
                     reads, ticks_done)
    del state
    numbers = meas.check(cfg, mix, served)
    rec["checks"] = {k: {"value": float(numbers[k]),
                         "limit": float(meas.LIMITS[k])} for k in meas.LIMITS}
    rec["correct"] = all(v["value"] <= v["limit"]
                         for v in rec["checks"].values())
    rec["checked"] = dict(tenants=len(served["tenants"]),
                          ticks=sum(len(t["ticks"]) for t in
                                    served["tenants"]),
                          reads=sum(len(t["reads"]) for t in
                                    served["tenants"]))
    lost = int(np.sum(np.isnan(reads.done)))
    rec["attempted"] = S * (ticks_done - W) + len(reads.arrival)
    rec["failed"] = lost
    return rec


def _served(cell, state, fns, key, rng, tick_tenants, served_p, reads,
            t_end):
    """What the check compares, per checked tenant: its stream, its
    sampled ticks' served p-values, its sampled reads' answers and its
    final state."""
    import jax.numpy as jnp

    cfg, mix, meas = cell.cfg, cell.mix, cell.measure
    W, T = cfg["window"], mix["chunk"]
    check = mix["check"]
    per = {int(s): dict(ticks=[], reads=[], need=t_end - W)
           for s in tick_tenants}
    picks = rng.choice(len(served_p), min(check["ticks"], len(served_p)),
                       replace=False) if served_p else []
    for i in np.sort(np.asarray(picks, int)):
        t, p = served_p[i]
        for j, s in enumerate(tick_tenants):
            per[int(s)]["ticks"].append((t, float(p[j])))
            per[int(s)]["need"] = min(per[int(s)]["need"], t - W + 1)
    done = np.flatnonzero(~np.isnan(reads.done))
    for i in np.sort(rng.choice(done, min(check["reads"], len(done)),
                                replace=False)) if len(done) else []:
        s = int(reads.tenant[i])
        ten = per.setdefault(s, dict(ticks=[], reads=[], need=t_end - W))
        ten["reads"].append((reads.X[i], int(reads.ticks[i]),
                             np.asarray(reads.answer[i], np.float64)))
        ten["need"] = min(ten["need"], int(reads.ticks[i]) - W)
    tenants = np.array(sorted(per), np.int32)
    leaves = fns["take"](meas.state_leaves(state), jnp.asarray(tenants))
    leaves = {k: np.asarray(v) for k, v in leaves.items()}

    # regenerate the checked tenants' streams with the run's own traffic
    # program (same shapes, same bits), in float32
    t0 = min(ten["need"] for ten in per.values()) // T * T
    parts = {"X": [], "y": [], "tau": []}
    idx = jnp.asarray(tenants)
    for c0 in range(t0, t_end, T):
        xs, ys, taus = fns["traffic"](key, jnp.int32(c0))
        for name, a in zip(("X", "y", "tau"), (xs, ys, taus)):
            parts[name].append(np.asarray(fns["take_tenants"](a, idx)))
    streams = {k: np.concatenate(v, 0) for k, v in parts.items()}
    out = []
    for j, s in enumerate(tenants):
        ten = per[int(s)]
        out.append(dict(
            tenant=int(s), t0=t0, t_end=t_end,
            X=streams["X"][:, j], y=streams["y"][:, j],
            tau=streams["tau"][:, j], ticks=ten["ticks"],
            reads=ten["reads"],
            state={k: v[j] for k, v in leaves.items()}))
    return dict(tenants=out)


def _longest_gap(chunks, t_start) -> dict:
    """The longest time between two chunks' p-values reaching the host,
    split by what the loop was doing: serving reads or waiting
    (``before``), making the traffic, enqueueing the chunk, waiting for
    its p-values."""
    if not chunks:
        return {}
    ends = [t_start] + [c[-1] for c in chunks]
    i = int(np.argmax(np.diff(ends)))
    a, b, c, d = chunks[i]
    return dict(s=d - ends[i], before=a - ends[i], traffic=b - a,
                dispatch=c - b, sync=d - c)


def _memory_peak(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class _GcPauses:
    """The longest pause of Python's garbage collector while active."""

    def __init__(self):
        self.longest = 0.0
        self._t0 = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.longest = max(self.longest, time.perf_counter() - self._t0)

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


class _CompileCounter:
    """Counts XLA compilations while active (none belong in the window)."""

    def __init__(self):
        self.count = 0
        self._on = False

    def _listen(self, event, duration, **kw):
        if self._on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def __enter__(self):
        import jax

        if not getattr(_CompileCounter, "_registered", False):
            jax.monitoring.register_event_duration_secs_listener(
                _CompileCounter._dispatch)
            _CompileCounter._registered = True
        _CompileCounter._active = self
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False
        _CompileCounter._active = None

    @staticmethod
    def _dispatch(event, duration, **kw):
        active = getattr(_CompileCounter, "_active", None)
        if active is not None:
            active._listen(event, duration, **kw)
