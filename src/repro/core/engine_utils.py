"""Host-side bookkeeping shared by the two serving engines.

`serving.engine.ServingEngine` (classification) and
`regression.engine.RegressionServingEngine` differ only in their state
pytree and per-tick step; the stateful host-side logic around the jitted
dispatch — grow-mode capacity provisioning, the sliding-window occupancy
invariant, the scan-chunk wrapper, and the tick and read dispatches
with their host spans — is identical and easy to let drift apart. It
lives here once, parameterized on an ``n_of`` accessor that reads the
per-session occupancy array from the engine's state.
(This module is import-neutral: both engine modules can use it without
touching the ``repro.serving`` package __init__, which would be
circular.)
"""
from __future__ import annotations

import contextlib
import warnings

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


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


def dispatch_chunk(eng, state, xs, ys, taus, active, *, op: str, n_of,
                   y_dtype):
    """The engines' shared observe/observe_many dispatch, under the host
    spans ``repro.<op>`` > ``prepare`` (room, invariants, the chunk's
    arguments), ``place`` (with ``shards > 1`` only: the arguments laid
    out on the tenant mesh), ``launch`` (the chunk program) and, when
    instrumented, ``fold`` (the tick stats into their accumulator).

    The chunk runs as the engine's ``ChunkPrograms`` executable for the
    arguments' shapes, so an instrumented engine's telemetry reads the
    byte counts of the very program it dispatches."""
    with eng._spans(op) as span:
        with span("prepare"):
            if active is None:
                active = jnp.ones(xs.shape[:2], dtype=bool)
            state = ensure_room(eng, state, xs.shape[0], n_of)
            check_window_occupancy(eng, state, n_of, lambda s: s.wrap)
            args = (state, xs, ys.astype(y_dtype), taus.astype(eng.dtype),
                    eng._windows(state), active)
        if eng._mesh is not None:
            from repro.core import distributed as dist
            with span("place"):
                args = dist.place_chunk_args(args, eng._mesh)
        if eng.telemetry is None:
            with span("launch"):
                return eng._chunks(args)(*args)
        T, S = xs.shape[:2]
        with eng.telemetry.timed(op, signature=(xs.shape, eng.capacity),
                                 ticks=T, tenants=S,
                                 capacity=eng.capacity) as tm:
            with span("launch"):
                run = eng._chunks(args)
                eng.telemetry.note_program(run, args[0], shards=eng.shards)
                state, (p, stats) = run(*args)
            tm.sync(p)
        with span("fold"):
            eng.telemetry.ticks.fold(stats)
        return state, p


def dispatch_read(eng, op: str, fn, *args):
    """One read dispatch ``fn(state, X_test, ...)`` under the host spans
    ``repro.<op>`` > ``launch``, timed by the telemetry when
    instrumented."""
    with eng._spans(op) as span:
        if eng.telemetry is None:
            with span("launch"):
                return fn(*args)
        with eng.telemetry.timed(op, signature=(args[1].shape, eng.capacity),
                                 tenants=eng.n_sessions,
                                 capacity=eng.capacity) as tm:
            with span("launch"):
                out = fn(*args)
            return tm.sync(out)


def ensure_room(eng, state, ticks: int, n_of):
    """Grow-mode host-side capacity check for the next ``ticks`` ticks.

    n grows by at most 1 per tick, so a host counter upper-bounds
    occupancy; the true max is synced only at startup and when the bound
    would cross capacity (after external state swaps, call the engine's
    ``reset_occupancy`` to re-sync). Mutates ``eng._n_bound``; returns
    the (possibly grown) state.
    """
    if eng.window is not None:
        return state
    cap = state.capacity
    if eng._n_bound is None or eng._n_bound + ticks > cap:
        eng._n_bound = int(jnp.max(n_of(state)))
        while eng._n_bound + ticks > cap:
            state = eng.grow(state)
            cap = state.capacity
    eng._n_bound += ticks
    return state


def check_window_occupancy(eng, state, n_of, wrap_of=None) -> None:
    """One-time ring/occupancy invariant check for sliding engines.

    The fused sliding step runs on the ``[:wmax]`` block of every leaf
    with ring modulus ``wmax``, which is only valid while (a) no
    session's occupancy exceeds the window and (b) every session's
    stored ring modulus (``wrap``) equals the engine's ``wmax`` — a
    state evolved under a different modulus places live slots where this
    engine would not look. Engine-produced states keep both invariants
    by construction; this guards externally supplied states with a
    single device sync per engine lifetime (``reset_occupancy`` re-arms
    it).

    Grow-mode engines (no window) need the modulus check too: their
    insert slot is ``(head + n) % wrap``, so a sliding-engine state
    (wrap == its window block) handed to a grow engine would wrap at
    the smaller modulus and silently overwrite live points once n
    crosses it. Their required modulus is the full capacity.
    """
    if eng._w_checked:
        return
    if eng.window is None:
        if wrap_of is not None:
            w = wrap_of(state)
            lo, hi = int(jnp.min(w)), int(jnp.max(w))
            if lo != state.capacity or hi != state.capacity:
                raise ValueError(
                    f"state ring modulus {lo}..{hi} does not match this "
                    f"grow-mode engine's capacity {state.capacity}: the "
                    "state was evolved under a sliding window's confined "
                    "ring. Normalize it first (session to_linear / "
                    "grow), or serve it with a sliding engine whose "
                    "window matches")
        eng._w_checked = True
        return
    nmax = int(jnp.max(n_of(state)))
    if nmax > eng._wmax:
        raise ValueError(
            f"state occupancy {nmax} exceeds the sliding window "
            f"{eng.window}: this engine keeps live rows inside the "
            "[:window] block; evict down to the window (or use a "
            "larger-window engine) before serving")
    if wrap_of is not None:
        w = wrap_of(state)
        lo, hi = int(jnp.min(w)), int(jnp.max(w))
        if lo != eng._wmax or hi != eng._wmax:
            raise ValueError(
                f"state ring modulus {lo}..{hi} does not match this "
                f"engine's window block {eng._wmax}: the state was "
                "evolved under a different ring layout. Normalize it "
                "first (session to_linear + init with wrap=window), or "
                "serve it with an engine whose window matches")
    eng._w_checked = True


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


__all__ = ["scan_chunk", "DispatchSpans", "dispatch_chunk", "dispatch_read",
           "ensure_room", "check_window_occupancy", "restorable_shards"]
