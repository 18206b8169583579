"""The micro-batching multi-tenant CP engine, over a measure binding.

An ``Engine`` stacks many tenants' session states into one pytree
(leading axis = tenant slot) and advances them all with one fixed-shape
jitted, donated ``lax.scan`` chunk of ticks: one device dispatch per
chunk whatever the tenant count, no retracing as windows fill, slide,
or tenants come and go.

A family's ``Measure`` is all the engine knows of it: its session state,
its sliding steps and a few host-side accessors, read also by the
fleet, the snapshot store and the guard. A family's engine is a
subclass that fixes ``measure`` and adds its read programs
(``serving.engine.ServingEngine``, ``regression.engine.
RegressionServingEngine``); ``serving.families`` names them all.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import inspect
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


@dataclasses.dataclass(frozen=True)
class Measure:
    """One measure family, as the engine and its callers see it.

    name:      ``"classification"`` or ``"regression"``: the telemetry
               ``engine=`` label, the snapshot meta's and the fleet's
               ``mode``.
    init:      ``init(capacity, dim, k, dtype=, wrap=)``: one tenant's
               empty state.
    step, step_compact: the fused sliding tick (evict-if-full, observe,
               active mask) of the ring and the compact layout.
    repad:     ``repad(state, capacity)``: the state in linear order at a
               larger capacity (``Engine.grow``, the fleet's migration,
               the guard's lane restore).
    n_of, X_of: a state's occupancy and its feature rows.
    y_dtype:   the label dtype; None: the engine's float dtype.
    label_ok:  ``label_ok(ys, n_labels, xp)``: which labels may be
               observed (``xp``: numpy on the host, jax.numpy in a
               program).
    poison:    a stacked state's (S,) lanes whose checked float leaves
               hold a non-finite value (``robustness.guard``).
    """

    name: str
    init: Callable
    step: Callable
    step_compact: Callable
    repad: Callable
    n_of: Callable
    X_of: Callable
    y_dtype: Any
    label_ok: Callable
    poison: Callable

    @functools.cached_property
    def treedef(self):
        """The state's tree structure, stacked or not: a snapshot's
        leaves are in its order."""
        return jax.tree_util.tree_structure(
            jax.eval_shape(lambda: self.init(1, 1, 1)))


def scan_chunk(vstep, stats_fn=None):
    """Wrap a vmapped per-tick step into a T-tick ``lax.scan`` chunk.

    One jitted dispatch advances T ticks; the leading-axis chunk length
    is the only retrace axis (the scan is rolled). Donating the carry at
    the jit boundary makes every per-tick (cap, cap) row/column insert
    an in-place update.

    ``stats_fn`` (optional — the telemetry hook, built by
    ``telemetry.device.make_chunk_stats_fn``) is evaluated ONCE per
    chunk, on the pre-chunk state and the full (T, S) active mask,
    *outside* the scan body — the tick statistics are a closed form of
    the integer bookkeeping leaves (occupancy / ring head / modulus)
    and the active mask, and even a few extra ops inside the compiled
    per-tick loop measure as a several-% regression. The chunk then
    returns ``(state, (pvals, stats))`` with ``stats`` one int32
    vector. The stats never read the float state, so the step's
    p-values and state stay bit-identical to the uninstrumented chunk
    (tested) and the donated in-place (cap, cap) updates are
    unaffected.
    """
    def chunk(state, xs, ys, taus, windows, actives):
        if stats_fn is not None:
            with jax.named_scope("stats"):
                st = stats_fn(state, windows, actives)

        def body(s, inp):
            x, y, tau, act = inp
            return vstep(s, x, y, tau, windows, act)

        out, ps = jax.lax.scan(body, state, (xs, ys, taus, actives))
        return (out, (ps, st)) if stats_fn is not None else (out, ps)

    return chunk


class DispatchSpans:
    """Host spans of an engine's dispatches, on the profiler's clock.

    ``with spans("observe_many") as span:`` opens ``repro.observe_many``
    and yields ``span(phase)``, which opens ``repro.<phase>`` inside it
    (``prepare``, ``launch``, ``fold``). Every span of one dispatch
    carries that dispatch's sequence number as its ``seq`` argument, so
    a chunk's spans share one identifier in a trace. The spans are
    ``jax.profiler.TraceAnnotation``s: written only while a profile is
    being captured, a no-op check otherwise, with or without telemetry.
    """

    def __init__(self):
        self.seq = 0

    @contextlib.contextmanager
    def __call__(self, op: str):
        self.seq += 1
        seq = self.seq
        with TraceAnnotation(f"repro.{op}", seq=seq):
            yield lambda phase: TraceAnnotation(f"repro.{phase}", seq=seq)


class ChunkPrograms:
    """An engine's chunk executables. Called with a chunk's arguments,
    it returns the jitted chunk ``step`` compiled ahead of time for
    their shapes and dtypes, on their first appearance, and reused
    after. Every chunk dispatch calls one of these, so what runs is an
    object that can be asked for its own memory
    (``memory_analysis()``)."""

    def __init__(self, step):
        self.step = step
        self._exes: dict = {}

    def __len__(self) -> int:
        return len(self._exes)

    def __call__(self, args):
        key = tuple((a.shape, a.dtype)
                    for a in jax.tree_util.tree_leaves(args))
        exe = self._exes.get(key)
        if exe is None:
            exe = self._exes[key] = self.step.lower(*args).compile()
        return exe


def restorable_shards(shards: int, n_sessions: int) -> int:
    """Shard count a snapshot saved with ``shards`` restores onto here.

    A snapshot from a sharded fleet restores wherever it lands (results
    are bit-identical either way), but falling back to one device when
    the saved shard count cannot be honoured is announced, never silent.
    """
    if shards <= 1:
        return 1
    if shards <= jax.device_count() and n_sessions % shards == 0:
        return shards
    warnings.warn(
        f"snapshot was saved with {shards} shards; restoring onto one "
        f"device ({jax.device_count()} visible, {n_sessions} sessions)",
        RuntimeWarning, stacklevel=3)
    return 1


class Engine:
    """Fixed-slot, fixed-shape multi-tenant CP engine over ``measure``.

    Parameters
    ----------
    n_sessions: number of tenant slots (the micro-batch width).
    capacity:   per-session padded training capacity.
    dim:        feature dimension.
    k:          k-NN neighbourhood size.
    window:     sliding-window length (<= capacity); None => grow mode
                (capacity doubles when full instead of evicting).
    donate:     donate the input state to the jitted observe step (the
                O(cap) in-place path). The state passed to ``observe`` /
                ``observe_many`` is deleted by the call; reuse raises.
                ``False`` restores copy semantics (input stays valid).
    layout:     "ring" (default) — circular row indexing; a sliding tick
                evicts by advancing the per-session head pointer, so the
                (cap, cap) distance matrices are never shifted/copied.
                "compact" — the historic positional layout whose
                eviction compacts every leaf (O(cap^2) memory traffic
                per tick); kept as the benchmark baseline and the
                exactness oracle, bit-identical to "ring".
    instrument: attach telemetry (``repro.telemetry``): per-op latency
                histograms + trace records, and in-graph per-tick device
                counters (evictions / ring wraps / occupancy) folded
                into a lazy accumulator — drain with
                ``engine.telemetry.drain()``. Bit-identical to the
                uninstrumented engine (the stats only read the integer
                bookkeeping leaves; property-tested).
    metrics:    ``MetricsRegistry`` to publish into (default: the
                process-wide registry). Only read when ``instrument``.
    tracer:     optional ``telemetry.Tracer`` — one JSONL record per
                engine dispatch. Only read when ``instrument``.
    sync_timing: with ``instrument``: block until the device finishes
                inside each timed op, so the latency histograms and
                trace records (``dispatch_s``) are device-true instead
                of enqueue time. Used by the replay harness; leave off
                on the serving hot path (it serializes dispatches).
    shards:     shard the tenant axis across this many devices
                (``core.distributed`` 1-D "tenants" mesh). A tick stays
                ONE dispatch — shard_map'd, zero collectives in the
                body — and every state leaf carries a tenant-sharded
                NamedSharding; results are bit-identical to the
                single-device vmap (property-tested). Requires
                ``n_sessions % shards == 0`` (pad uneven tenant counts
                with inactive lanes: ``distributed.pad_tenant_count``)
                and ``shards <= jax.device_count()``.
    A subclass fixes ``measure`` and ``read_op``, returns its jitted
    read programs by op name from ``_read_programs()`` (and what
    ``read_op``'s takes after the queries from ``_read_args``), and may
    add keywords of its read side in ``options`` (name -> default), each
    stored as an attribute and in ``meta()``.
    """

    measure: Measure
    #: keywords the subclass's reads add, with their defaults
    options: dict[str, Any] = {}
    #: the op ``read`` dispatches under
    read_op: str

    def __init__(self, *, n_sessions: int, capacity: int, dim: int, k: int,
                 window: int | None = None, dtype=jnp.float32,
                 donate: bool = True, layout: str = "ring",
                 instrument: bool = False, metrics=None, tracer=None,
                 sync_timing: bool = False, shards: int = 1, **options):
        unknown = sorted(set(options) - set(self.options))
        if unknown:
            raise TypeError(f"{type(self).__name__} got unexpected "
                            f"keyword arguments {unknown}")
        if window is not None and window > capacity:
            raise ValueError(f"window {window} exceeds capacity {capacity}")
        if window is not None and window < 1:
            raise ValueError("window must be >= 1")
        if capacity < k:
            raise ValueError(f"capacity {capacity} < k {k}")
        if layout not in ("ring", "compact"):
            raise ValueError(f"unknown layout {layout!r}")
        if shards > 1 and n_sessions % shards != 0:
            raise ValueError(
                f"n_sessions {n_sessions} not divisible by shards "
                f"{shards}; pad with inactive lanes "
                "(core.distributed.pad_tenant_count)")
        for name, default in self.options.items():
            setattr(self, name, options.get(name, default))
        self.n_sessions = n_sessions
        self.capacity = capacity
        self.dim = dim
        self.k = k
        self.window = window
        self.dtype = dtype
        self.donate = donate
        self.layout = layout
        self.shards = shards
        self._mesh = None
        if shards > 1:
            from repro.core import distributed as dist
            self._mesh = dist.tenant_mesh(shards)
        m = self.measure
        # the fused sliding step: evict-if-full + observe + active mask
        # in one pass; grow mode (window=None) statically drops the
        # eviction machinery. A sliding window statically bounds
        # occupancy, so the tick runs on the [:window] block of every
        # leaf with ring modulus == window (cost scales with the window,
        # not the padded capacity) — observe_many verifies the
        # occupancy + ring-modulus invariants once per externally
        # supplied state.
        wmax = None if window is None else max(min(window, capacity), k)
        step = functools.partial(
            m.step if layout == "ring" else m.step_compact, k=k,
            evictable=window is not None, wmax=wmax)
        self._wmax = wmax
        self._w_checked = False
        self.telemetry = None
        if instrument:
            from repro.telemetry import EngineTelemetry
            self.telemetry = EngineTelemetry(
                engine=m.name, metrics=metrics, tracer=tracer,
                sync=sync_timing, n_of=m.n_of)
        vstep = jax.vmap(step, in_axes=(0, 0, 0, 0, 0, 0))
        chunk = scan_chunk(
            vstep, self.telemetry.stats_fn if instrument else None)
        if self._mesh is not None:
            from repro.core import distributed as dist
            chunk = dist.shard_tenant_chunk(chunk, self._mesh,
                                            with_stats=instrument)
        self._step_many = jax.jit(
            chunk, donate_argnums=(0,) if donate else ())
        self._chunks = ChunkPrograms(self._step_many)
        self._reads = self._read_programs()
        # host-side upper bound on max_s n_s, for grow-mode occupancy
        # checks without a per-tick device sync
        self._n_bound: int | None = None
        self._spans = DispatchSpans()

    def _sharded(self, fn, in_tenant):
        """``fn`` shard_map'd over the tenant mesh (as is without one);
        ``in_tenant`` marks the arguments with a tenant axis."""
        if self._mesh is None:
            return fn
        from repro.core import distributed as dist
        return dist.shard_tenant_fn(fn, self._mesh, in_tenant)

    # -- state --------------------------------------------------------------

    @property
    def y_dtype(self):
        """The dtype the chunk takes its labels in."""
        return self.measure.y_dtype or self.dtype

    def init_state(self):
        """Stacked session state with a leading (n_sessions,) axis.

        Sliding engines confine every session's ring to the
        ``[:window]`` leaf block (``wrap == wmax``); grow mode uses the
        full capacity as the modulus (the ring never wraps there).
        With ``shards > 1`` every leaf is made with a tenant-sharded
        NamedSharding across the mesh, each device filling only its own
        tenants' slice."""
        def build():
            one = self.measure.init(self.capacity, self.dim, self.k,
                                    dtype=self.dtype, wrap=self._wmax)
            return jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (self.n_sessions,) + a.shape),
                one)

        if self._mesh is None:
            return build()
        from repro.core import distributed as dist
        return dist.init_tenant_sharded(build, self._mesh)

    def _shard_state(self, state):
        """Lay the stacked state out tenant-sharded across the mesh."""
        if self._mesh is None:
            return state
        from repro.core import distributed as dist
        return dist.put_tenant_sharded(state, self._mesh)

    def adopt(self, state):
        """Take a stacked state this engine did not produce (a restored
        snapshot, a rewritten lane) into service: a linear ring that
        fits the window is re-pinned to the window block, the leaves
        are laid out on the tenant mesh, and the occupancy checks
        re-arm for the next ``observe``."""
        self.reset_occupancy()
        return self._shard_state(self._fit_ring_modulus(state))

    def _fit_ring_modulus(self, state):
        """A pre-ring (legacy) snapshot restores with a full-capacity
        modulus; a sliding engine runs its ring inside the ``[:window]``
        block. The two agree whenever the state is unwrapped (head == 0)
        and fits the window — then the modulus can simply be re-pinned.
        Anything else is a real mismatch and is left for
        ``_check_window_occupancy`` to reject with its diagnostic."""
        if self._wmax is None:
            return state
        wrap = jnp.asarray(state.wrap)
        if (int(jnp.max(wrap)) == int(jnp.min(wrap)) == self._wmax
                or int(jnp.max(state.head)) != 0
                or int(jnp.max(self.measure.n_of(state))) > self._wmax):
            return state
        return dataclasses.replace(state,
                                   wrap=jnp.full_like(wrap, self._wmax))

    def taus(self, key) -> jnp.ndarray:
        """One tie-breaking uniform per session slot for this tick."""
        return jax.random.uniform(key, (self.n_sessions,), dtype=self.dtype)

    def _windows(self, state) -> jnp.ndarray:
        cap = state.capacity
        w = cap + 1 if self.window is None else self.window  # +1: never evict
        return jnp.full((self.n_sessions,), w, dtype=jnp.int32)

    # -- serving ------------------------------------------------------------

    def observe(self, state, x, y, tau, active=None):
        """One micro-batched tick: learn (x[s], y[s]) in every active slot.

        x: (S, dim); y: (S,); tau: (S,) tie-break uniforms; active: (S,)
        bool (default all). Returns (state, pvalues (S,)) — the smoothed
        online p-value of each observed label, NaN on inactive slots. In
        grow mode, auto-doubles capacity first if any session is full
        (host-side sync + retrace, O(log n) times total). The T=1 case
        of ``observe_many`` (bit-identical, tested); with ``donate=True``
        (default) the input ``state`` is consumed.
        """
        state, p = self._dispatch_chunk(
            state, x[None], y[None], tau[None],
            None if active is None else active[None], op="observe")
        return state, p[0]

    def observe_many(self, state, xs, ys, taus, active=None):
        """A chunk of T micro-batched ticks in ONE jitted dispatch.

        xs: (T, S, dim); ys: (T, S); taus: (T, S); active: (T, S) bool
        (default all). Returns (state, pvalues (T, S)) — tick t's row is
        bit-identical to calling ``observe`` T times (the chunk is a
        ``lax.scan`` over the same per-tick step; property-tested). In
        grow mode the whole chunk's worst-case occupancy is provisioned
        up front (capacity doubles until ``n + T <= cap``), so the scan
        never needs a mid-chunk host sync. With ``donate=True`` the
        input ``state`` is consumed.
        """
        return self._dispatch_chunk(state, xs, ys, taus, active,
                                    op="observe_many")

    def _dispatch_chunk(self, state, xs, ys, taus, active, *, op: str):
        """The observe/observe_many dispatch, under the host spans
        ``repro.<op>`` > ``prepare`` (room, invariants, the chunk's
        arguments), ``place`` (with ``shards > 1`` only: the arguments
        laid out on the tenant mesh), ``launch`` (the chunk program)
        and, when instrumented, ``fold`` (the tick stats into their
        accumulator).

        The chunk runs as the engine's ``ChunkPrograms`` executable for
        the arguments' shapes, so an instrumented engine's telemetry
        reads the byte counts of the very program it dispatches."""
        with self._spans(op) as span:
            with span("prepare"):
                if active is None:
                    active = jnp.ones(xs.shape[:2], dtype=bool)
                state = self._ensure_room(state, xs.shape[0])
                self._check_window_occupancy(state)
                args = (state, xs, ys.astype(self.y_dtype),
                        taus.astype(self.dtype), self._windows(state),
                        active)
            if self._mesh is not None:
                from repro.core import distributed as dist
                with span("place"):
                    args = dist.place_chunk_args(args, self._mesh)
            if self.telemetry is None:
                with span("launch"):
                    return self._chunks(args)(*args)
            T, S = xs.shape[:2]
            with self.telemetry.timed(op, signature=(xs.shape, self.capacity),
                                      ticks=T, tenants=S,
                                      capacity=self.capacity) as tm:
                with span("launch"):
                    run = self._chunks(args)
                    self.telemetry.note_program(run, args[0],
                                                shards=self.shards)
                    state, (p, stats) = run(*args)
                tm.sync(p)
            with span("fold"):
                self.telemetry.ticks.fold(stats)
            return state, p

    def _dispatch_read(self, op: str, *args):
        """One read dispatch ``self._reads[op](state, X_test, ...)``
        under the host spans ``repro.<op>`` > ``launch``, timed by the
        telemetry when instrumented."""
        fn = self._reads[op]
        with self._spans(op) as span:
            if self.telemetry is None:
                with span("launch"):
                    return fn(*args)
            with self.telemetry.timed(op,
                                      signature=(args[1].shape, self.capacity),
                                      tenants=self.n_sessions,
                                      capacity=self.capacity) as tm:
                with span("launch"):
                    out = fn(*args)
                return tm.sync(out)

    def _per_tenant(self, X_test):
        """(m, dim) queries broadcast to every tenant; (S, m, dim) as is."""
        if X_test.ndim == 2:
            return jnp.broadcast_to(X_test, (self.n_sessions,) + X_test.shape)
        return X_test

    def _read_args(self, epsilon: float) -> tuple:
        """What ``read_op``'s program takes after the queries."""
        return ()

    def read(self, state, X_test, epsilon: float = 0.1):
        """The family's read, ``read_op``, of every tenant's queries
        (X_test: (S, m, dim), or (m, dim) broadcast to every tenant), at
        level ``epsilon`` where the read takes one (p-values of every
        label answer every level)."""
        return self._dispatch_read(self.read_op, state,
                                   self._per_tenant(X_test),
                                   *self._read_args(epsilon))

    def lower_read(self, queries: int = 1, epsilon: float = 0.1):
        """Lower (but do NOT execute) ``read`` over ``queries`` query
        points a tenant: the read's compiled form, as ``lower_tick``
        gives the tick's (a profile names the compiled instructions;
        their ``op_name`` metadata holds the named scopes)."""
        X = jnp.zeros((self.n_sessions, queries, self.dim), self.dtype)
        return self._reads[self.read_op].lower(self.init_state(), X,
                                               *self._read_args(epsilon))

    def _ensure_room(self, state, ticks: int):
        """Grow-mode host-side capacity check for the next ``ticks`` ticks.

        n grows by at most 1 per tick, so a host counter upper-bounds
        occupancy; the true max is synced only at startup and when the
        bound would cross capacity (after external state swaps, call
        ``reset_occupancy`` or ``adopt`` to re-sync). Returns the
        (possibly grown) state.
        """
        if self.window is not None:
            return state
        cap = state.capacity
        if self._n_bound is None or self._n_bound + ticks > cap:
            self._n_bound = int(jnp.max(self.measure.n_of(state)))
            while self._n_bound + ticks > cap:
                state = self.grow(state)
                cap = state.capacity
        self._n_bound += ticks
        return state

    def _check_window_occupancy(self, state) -> None:
        """One-time ring/occupancy invariant check.

        The fused sliding step runs on the ``[:wmax]`` block of every leaf
        with ring modulus ``wmax``, which is only valid while (a) no
        session's occupancy exceeds the window and (b) every session's
        stored ring modulus (``wrap``) equals the engine's ``wmax`` — a
        state evolved under a different modulus places live slots where
        this engine would not look. Engine-produced states keep both
        invariants by construction; this guards externally supplied
        states with a single device sync per engine lifetime
        (``reset_occupancy`` re-arms it).

        Grow-mode engines (no window) need the modulus check too: their
        insert slot is ``(head + n) % wrap``, so a sliding-engine state
        (wrap == its window block) handed to a grow engine would wrap at
        the smaller modulus and silently overwrite live points once n
        crosses it. Their required modulus is the full capacity.
        """
        if self._w_checked:
            return
        if self.window is not None:
            nmax = int(jnp.max(self.measure.n_of(state)))
            if nmax > self._wmax:
                raise ValueError(
                    f"state occupancy {nmax} exceeds the sliding window "
                    f"{self.window}: this engine keeps live rows inside "
                    "the [:window] block; evict down to the window (or "
                    "use a larger-window engine) before serving")
        lo, hi = int(jnp.min(state.wrap)), int(jnp.max(state.wrap))
        if self.window is None and not lo == hi == state.capacity:
            raise ValueError(
                f"state ring modulus {lo}..{hi} does not match this "
                f"grow-mode engine's capacity {state.capacity}: the "
                "state was evolved under a sliding window's confined "
                "ring. Normalize it first (session to_linear / "
                "grow), or serve it with a sliding engine whose "
                "window matches")
        if self.window is not None and not lo == hi == self._wmax:
            raise ValueError(
                f"state ring modulus {lo}..{hi} does not match this "
                f"engine's window block {self._wmax}: the state was "
                "evolved under a different ring layout. Normalize it "
                "first (session to_linear + init with wrap=window), or "
                "serve it with an engine whose window matches")
        self._w_checked = True

    def lower_tick(self, ticks: int = 4):
        """Lower (but do NOT execute) a ``ticks``-long observe_many chunk.

        Returns the ``jax.stages.Lowered`` for the engine's compiled
        step on a zeros example batch — the artifact the static auditor
        (``repro.analysis.audit``) inspects for donation aliasing,
        collective-freedom and dense-materialization budgets. Tracing
        only: engine state and jit caches are untouched beyond the
        cache entry the first real tick would create anyway.
        """
        state = self.init_state()
        S, T = self.n_sessions, ticks
        xs = jnp.zeros((T, S, self.dim), self.dtype)
        ys = jnp.zeros((T, S), self.y_dtype)
        taus = jnp.zeros((T, S), self.dtype)
        active = jnp.ones((T, S), dtype=bool)
        return self._step_many.lower(state, xs, ys, taus,
                                     self._windows(state), active)

    def program_counts(self) -> dict[str, int]:
        """Shapes compiled so far: chunk executables (``step``) and read
        programs (``read``)."""
        return {"step": len(self._chunks),
                "read": sum(f._cache_size() for f in self._reads.values())}

    def reset_occupancy(self) -> None:
        """Forget the host-side occupancy bound (grow mode) and the
        window-invariant check; the next ``observe`` re-syncs/re-checks
        from device. Call after substituting a state that this engine
        did not produce (``adopt`` does)."""
        self._n_bound = None
        self._w_checked = False

    def grow(self, state, factor: int = 2):
        """Double every session's capacity (host-side, preserves state).

        ``self.capacity`` follows the grown state so ``meta()`` and
        ``init_state()`` stay consistent with the states this engine
        produces. The measure's ``repad`` normalizes each ring to linear
        order with a full-capacity modulus; a sliding engine pins the
        modulus back to its window block (the normalized state fits it:
        head == 0, n <= window)."""
        grow_all = jax.vmap(functools.partial(
            self.measure.repad, capacity=state.capacity * factor))
        if self.telemetry is not None:
            with self.telemetry.timed("grow", tenants=self.n_sessions,
                                      capacity=self.capacity * factor,
                                      signature=self.capacity):
                out = grow_all(state)
        else:
            out = grow_all(state)
        self.capacity = out.capacity
        if self._wmax is not None:
            out = dataclasses.replace(
                out, wrap=jnp.full_like(out.wrap, self._wmax))
        return self._shard_state(out)

    # -- snapshot -----------------------------------------------------------

    def meta(self) -> dict[str, Any]:
        """JSON-serializable engine config, stored alongside snapshots."""
        return {
            "mode": self.measure.name,
            "n_sessions": self.n_sessions,
            "capacity": self.capacity,
            "dim": self.dim,
            "k": self.k,
            **{name: getattr(self, name) for name in self.options},
            "window": self.window,
            "dtype": jnp.dtype(self.dtype).name,
            "shards": self.shards,
        }

    @classmethod
    def from_meta(cls, meta: dict[str, Any]) -> "Engine":
        """The engine ``meta()`` describes; keys only another family's
        engine reads (``n_labels``) are ignored."""
        meta = dict(meta)
        mode = meta.pop("mode", cls.measure.name)
        if mode != cls.measure.name:
            raise ValueError(f"not a {cls.measure.name}-engine meta: "
                             f"mode={mode!r}")
        meta["dtype"] = jnp.dtype(meta.get("dtype", "float32"))
        meta["shards"] = restorable_shards(
            int(meta.pop("shards", 1)), meta["n_sessions"])
        return cls.build(**meta)

    @classmethod
    def build(cls, **kw) -> "Engine":
        """``cls(**kw)`` without the keywords only another family's
        engine takes, so one call builds any family's engine."""
        return cls(**{key: v for key, v in kw.items()
                      if key in _KEYWORDS or key in cls.options})


_KEYWORDS = frozenset(inspect.signature(Engine.__init__).parameters) - {
    "self", "options"}


__all__ = ["Measure", "Engine", "scan_chunk", "DispatchSpans",
           "ChunkPrograms", "restorable_shards"]
