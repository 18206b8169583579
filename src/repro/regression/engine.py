"""Micro-batching multi-tenant streaming regression-CP engine.

The regression counterpart of ``serving.engine.ServingEngine``: many
per-tenant ``RegStreamState``s stacked into one pytree (leading axis =
session slot), advanced by a single fixed-shape jitted ``vmap`` step per
tick, and served by a single vmapped dispatch that returns prediction
intervals for every tenant at once.

Usage::

    from repro.regression import RegressionServingEngine

    eng = RegressionServingEngine(n_sessions=64, capacity=256, dim=16,
                                  k=7, window=128)
    state = eng.init_state()
    for t in range(T):
        state, pvals = eng.observe(state, x_t, y_t, tau_t)  # (64,) smoothed
    # or: T ticks in ONE dispatch (xs: (T, 64, 16), ys/taus: (T, 64))
    state, pvals = eng.observe_many(state, xs, ys, taus)    # (T, 64)
    iv = eng.intervals(state, x_query, epsilon=0.1)  # (64, m, 2)

Per-session state is bit-identical to feeding that session's stream
through ``regression.stream`` alone, which in turn is bit-identical to
``regression.fit`` refit-from-scratch on the live window (tested); the
interval read path routes through the fused Pallas kernel on TPU. The
per-tick ``observe`` p-values (each tenant's observed label against its
current window) feed the same exchangeability martingales as the
classification engine — streaming drift detection for regression tenants.

As in ``serving.engine``, the observe path is O(cap) per tick: the
jitted step donates its input state (the (S, cap, cap) distance
matrices update in place — the input ``state`` is consumed; pass
``donate=False`` for copy semantics), and ``observe_many`` amortizes
dispatch overhead by scanning a whole chunk of ticks under one jit
(``observe`` is its T=1 case; both bit-neutral, property-tested).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import engine_utils
from repro.regression import session as sess_m
from repro.regression.stream import RegStreamState


class RegressionServingEngine:
    """Fixed-slot, fixed-shape multi-tenant regression-CP engine.

    Parameters
    ----------
    n_sessions: number of tenant slots (the micro-batch width).
    capacity:   per-session padded training capacity.
    dim:        feature dimension.
    k:          k-NN neighbourhood size (paper Section 8.1 measure).
    window:     sliding-window length (<= capacity); None => grow mode
                (capacity doubles when full instead of evicting).
    donate:     donate the input state to the jitted observe step (the
                O(cap) in-place path). The state passed to ``observe`` /
                ``observe_many`` is deleted by the call; reuse raises.
                ``False`` restores copy semantics (input stays valid).
    layout:     "ring" (default) — circular row indexing; a sliding tick
                evicts by advancing the per-session head pointer, so the
                (cap, cap) distance matrices are never shifted/copied.
                "compact" — the historic positional layout (O(cap^2)
                eviction traffic); kept as the benchmark baseline and
                the exactness oracle, bit-identical to "ring".
    instrument: attach telemetry (``repro.telemetry``): per-op latency
                histograms + trace records, and in-graph per-tick device
                counters (evictions / ring wraps / occupancy) folded
                into a lazy accumulator — drain with
                ``engine.telemetry.drain()``. Bit-identical to the
                uninstrumented engine (tested); ``metrics`` / ``tracer``
                / ``sync_timing`` as in ``serving.engine.ServingEngine``.
    shards:     partition the session axis across this many devices
                (``core.distributed.tenant_mesh``): state leaves get a
                tenant-sharded ``NamedSharding`` and every dispatch runs
                shard_map'd, one program per device with zero
                cross-device collectives — bit-identical to the
                single-device vmap (tested). ``n_sessions`` must divide
                evenly; pad with inactive lanes otherwise.
    """

    def __init__(self, *, n_sessions: int, capacity: int, dim: int, k: int,
                 window: int | None = None, dtype=jnp.float32,
                 donate: bool = True, layout: str = "ring",
                 instrument: bool = False, metrics=None, tracer=None,
                 sync_timing: bool = False, shards: int = 1):
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
                f"n_sessions {n_sessions} not divisible by shards {shards};"
                " pad with inactive lanes"
                " (core.distributed.pad_tenant_count)")
        self.shards = shards
        self._mesh = None
        if shards > 1:
            from repro.core import distributed as dist
            self._mesh = dist.tenant_mesh(shards)
        self.n_sessions = n_sessions
        self.capacity = capacity
        self.dim = dim
        self.k = k
        self.window = window
        self.dtype = dtype
        self.donate = donate
        self.layout = layout
        # the fused sliding step: evict-if-full + observe + active mask
        # in one pass; grow mode (window=None) statically drops the
        # eviction machinery. A sliding window statically bounds
        # occupancy, so the tick runs on the [:window] block of every
        # leaf with ring modulus == window (cost scales with the window,
        # not the padded capacity) — observe_many verifies the
        # occupancy + ring-modulus invariants once per externally
        # supplied state.
        wmax = None if window is None else max(min(window, capacity), k)
        step_fn = (sess_m._sliding_step if layout == "ring"
                   else sess_m._sliding_step_compact)
        step = functools.partial(step_fn, k=k,
                                 evictable=window is not None, wmax=wmax)
        self._wmax = wmax
        self._w_checked = False
        self.telemetry = None
        if instrument:
            from repro.telemetry import EngineTelemetry
            self.telemetry = EngineTelemetry(
                engine="regression", metrics=metrics, tracer=tracer,
                sync=sync_timing,
                n_of=lambda s: s.n, head_of=lambda s: s.head,
                wrap_of=lambda s: s.wrap)
        vstep = jax.vmap(step, in_axes=(0, 0, 0, 0, 0, 0))
        chunk = engine_utils.scan_chunk(
            vstep, self.telemetry.stats_fn if instrument else None)
        # lax.map, not vmap: the scanned body keeps the exact per-session
        # graph, so served reads stay bit-identical to the single-session
        # path (vmap re-batches the distance GEMMs and count reductions,
        # which round differently at large capacities)
        pvals = lambda st, xt, tq: jax.lax.map(
            lambda args: sess_m.pvalues(args[0], args[1], tq, k=k),
            (st, xt))
        ivals = lambda st, xt, eps: jax.lax.map(
            lambda args: sess_m.intervals(args[0], args[1], k=k,
                                          epsilon=eps), (st, xt))
        if self._mesh is not None:
            from repro.core import distributed as dist
            chunk = dist.shard_tenant_chunk(chunk, self._mesh,
                                            with_stats=instrument)
            pvals = dist.shard_tenant_fn(pvals, self._mesh,
                                         (True, True, False))
            ivals = dist.shard_tenant_fn(ivals, self._mesh,
                                         (True, True, False))
        self._step_many = jax.jit(
            chunk, donate_argnums=(0,) if donate else ())
        self._chunks = engine_utils.ChunkPrograms(self._step_many)
        self._pvalues = jax.jit(pvals)
        self._intervals = jax.jit(ivals)
        self._n_bound: int | None = None
        self._spans = engine_utils.DispatchSpans()

    # -- state --------------------------------------------------------------

    def init_state(self) -> RegStreamState:
        """Stacked RegStreamState with a leading (n_sessions,) axis.

        Sliding engines confine every session's ring to the
        ``[:window]`` leaf block (``wrap == wmax``); grow mode uses the
        full capacity as the modulus (the ring never wraps there).
        With ``shards > 1`` every leaf is made with a tenant-sharded
        NamedSharding across the mesh, each device filling only its own
        tenants' slice."""
        def build():
            one = sess_m.init(self.capacity, self.dim, self.k,
                              dtype=self.dtype, wrap=self._wmax)
            return jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (self.n_sessions,) + a.shape),
                one)

        if self._mesh is None:
            return build()
        from repro.core import distributed as dist
        return dist.init_tenant_sharded(build, self._mesh)

    def _shard_state(self, state: RegStreamState) -> RegStreamState:
        """Lay the stacked state out tenant-sharded across the mesh."""
        if self._mesh is None:
            return state
        from repro.core import distributed as dist
        return dist.put_tenant_sharded(state, self._mesh)

    def taus(self, key) -> jnp.ndarray:
        """One tie-breaking uniform per session slot for this tick."""
        return jax.random.uniform(key, (self.n_sessions,), dtype=self.dtype)

    def _windows(self, state: RegStreamState) -> jnp.ndarray:
        cap = state.capacity
        w = cap + 1 if self.window is None else self.window  # +1: never evict
        return jnp.full((self.n_sessions,), w, dtype=jnp.int32)

    # -- serving ------------------------------------------------------------

    def observe(self, state: RegStreamState, x, y, tau, active=None):
        """One micro-batched tick: learn (x[s], y[s]) in every active slot.

        x: (S, dim); y: (S,); tau: (S,) tie-break uniforms; active: (S,)
        bool (default all). Returns (state, pvalues (S,)) — the smoothed
        online p-value of each observed label, NaN on inactive slots. In
        grow mode, auto-doubles capacity first if any session is full
        (host-side sync + retrace, O(log n) times total). The T=1 case
        of ``observe_many`` (bit-identical, tested); with ``donate=True``
        (default) the input ``state`` is consumed.
        """
        state, p = self._dispatch(
            state, x[None], y[None], tau[None],
            None if active is None else active[None], op="observe")
        return state, p[0]

    def observe_many(self, state: RegStreamState, xs, ys, taus,
                     active=None):
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
        return self._dispatch(state, xs, ys, taus, active,
                              op="observe_many")

    def _dispatch(self, state: RegStreamState, xs, ys, taus, active, *,
                  op: str):
        """The shared observe/observe_many dispatch (telemetry-aware,
        under the engine's host spans)."""
        return engine_utils.dispatch_chunk(
            self, state, xs, ys, taus, active, op=op,
            n_of=lambda s: s.n, y_dtype=self.dtype)

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
        ys = jnp.zeros((T, S), self.dtype)
        taus = jnp.zeros((T, S), self.dtype)
        active = jnp.ones((T, S), dtype=bool)
        return self._step_many.lower(state, xs, ys, taus,
                                     self._windows(state), active)

    def lower_read(self, queries: int = 1, epsilon: float = 0.1):
        """Lower (but do NOT execute) the read program, ``intervals`` over
        ``queries`` query points a tenant: the read's compiled form, as
        ``lower_tick`` gives the tick's (a profile names the compiled
        instructions; their ``op_name`` metadata holds the named scopes)."""
        X = jnp.zeros((self.n_sessions, queries, self.dim), self.dtype)
        return self._intervals.lower(self.init_state(), X,
                                     jnp.asarray(epsilon, self.dtype))

    def reset_occupancy(self) -> None:
        """Forget the host-side occupancy bound (grow mode) and the
        window-invariant check; the next ``observe`` re-syncs/re-checks
        from device."""
        self._n_bound = None
        self._w_checked = False

    def grow(self, state: RegStreamState, factor: int = 2) -> RegStreamState:
        """Double every session's capacity (host-side, preserves state).

        Session-level grow normalizes each ring to linear order with a
        full-capacity modulus; a sliding engine pins the modulus back to
        its window block (the normalized state fits it: head == 0,
        n <= window)."""
        grow_all = jax.vmap(functools.partial(sess_m.grow, factor=factor))
        if self.telemetry is not None:
            with self.telemetry.timed("grow", tenants=self.n_sessions,
                                      capacity=self.capacity * factor,
                                      signature=self.capacity):
                out = grow_all(state)
        else:
            out = grow_all(state)
        self.capacity = out.capacity
        if self._wmax is not None:
            out = RegStreamState(out.X, out.y, out.D, out.nbr_d, out.nbr_y,
                                 out.n, out.head, out.aid,
                                 jnp.full_like(out.wrap, self._wmax),
                                 out.nbr_a)
        return self._shard_state(out)

    def intervals(self, state: RegStreamState, X_test,
                  epsilon: float) -> jnp.ndarray:
        """Prediction intervals per session: (S, m, 2), one dispatch.

        X_test: (S, m, dim) per-session query batch, or (m, dim) broadcast
        to every session; ``epsilon`` is traced (no recompile per level).
        Inside the single jitted call the fused kernel (Pallas on TPU)
        computes distances + score updates + critical points; the hull
        sweep finishes per test point.
        """
        if X_test.ndim == 2:
            X_test = jnp.broadcast_to(
                X_test, (self.n_sessions,) + X_test.shape)
        eps = jnp.asarray(epsilon, self.dtype)
        return engine_utils.dispatch_read(self, "intervals", self._intervals,
                                          state, X_test, eps)

    def pvalues(self, state: RegStreamState, X_test,
                t_query) -> jnp.ndarray:
        """P-values at query labels per session: (S, m, nq), one dispatch."""
        if X_test.ndim == 2:
            X_test = jnp.broadcast_to(
                X_test, (self.n_sessions,) + X_test.shape)
        return engine_utils.dispatch_read(self, "pvalues", self._pvalues,
                                          state, X_test, t_query)

    # -- snapshot -----------------------------------------------------------

    def meta(self) -> dict[str, Any]:
        """JSON-serializable engine config, stored alongside snapshots."""
        return {
            "mode": "regression",
            "n_sessions": self.n_sessions,
            "capacity": self.capacity,
            "dim": self.dim,
            "k": self.k,
            "window": self.window,
            "dtype": jnp.dtype(self.dtype).name,
            "shards": self.shards,
        }

    @classmethod
    def from_meta(cls, meta: dict[str, Any]) -> "RegressionServingEngine":
        meta = dict(meta)
        mode = meta.pop("mode", "regression")
        if mode != "regression":
            raise ValueError(f"not a regression-engine meta: mode={mode!r}")
        meta.pop("n_labels", None)  # tolerate classification-era keys
        meta["dtype"] = jnp.dtype(meta.get("dtype", "float32"))
        meta["shards"] = engine_utils.restorable_shards(
            int(meta.pop("shards", 1)), meta["n_sessions"])
        return cls(**meta)


__all__ = ["RegressionServingEngine"]
