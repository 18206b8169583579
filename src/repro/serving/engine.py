"""Micro-batching multi-tenant online CP engine.

Batches many per-tenant ``serving.session.Session``s into one stacked
pytree (leading axis = session slot) and advances them all with a single
fixed-shape jitted ``vmap`` step — the serving form of the paper's O(n)
online update: one device dispatch per tick regardless of tenant count,
no retracing as windows fill, slide, or tenants come and go.

Usage::

    from repro.serving.engine import ServingEngine

    eng = ServingEngine(n_sessions=64, capacity=256, dim=16, k=7,
                        n_labels=2, window=128)
    state = eng.init_state()
    for t in range(T):                      # one micro-batch per tick
        x_t, y_t = traffic_at(t)            # (64, 16), (64,)
        tau_t = eng.taus(jax.random.PRNGKey(t))
        state, pvals = eng.observe(state, x_t, y_t, tau_t)  # (64,) smoothed
    # or: T ticks in ONE dispatch (xs: (T, 64, 16), ys/taus: (T, 64))
    state, pvals = eng.observe_many(state, xs, ys, taus)    # (T, 64)
    sets = eng.predict(state, x_query)      # (64, m, n_labels) full-CP query

Per-session p-values are bit-identical to running that session's stream
through ``core.online.run_stream`` alone (tested); sliding-window
eviction is the exact decremental update of ``serving.session``. The
read-only ``predict`` routes score-update + counting through the fused
Pallas kernel (``kernels/cp_update.py``) on TPU.

Tenants with no traffic on a tick are masked via ``active`` (state
bitwise unchanged, NaN p-value) — the micro-batch shape never changes.
When no ``window`` is set the engine auto-grows: once any session hits
capacity, every array doubles (host-side, O(log n) retraces total).

Two memory-system optimizations keep the hot tick O(cap) instead of
O(cap^2) (both bit-neutral, property-tested): the jitted step *donates*
its input state (``donate_argnums``), so the (S, cap, cap) distance
matrices update in place instead of being copied per tick — the input
``state`` is consumed by ``observe``/``observe_many`` and must not be
reused (pass ``donate=False`` to keep copy semantics) — and
``observe_many`` runs a whole chunk of ticks under one ``lax.scan``
dispatch, amortizing the per-dispatch overhead that otherwise dominates
at high tenant counts (``observe`` is its T=1 case).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import engine_utils
from repro.serving import session as sess_m
from repro.serving.session import Session


class ServingEngine:
    """Fixed-slot, fixed-shape multi-tenant CP serving engine.

    Parameters
    ----------
    n_sessions: number of tenant slots (the micro-batch width).
    capacity:   per-session padded training capacity.
    dim:        feature dimension.
    k:          k-NN neighbourhood size (paper's simplified k-NN measure).
    n_labels:   label alphabet for ``predict``.
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
                bookkeeping leaves; property-tested) and inside the
                <= 5 % overhead budget CI enforces on ``observe_many``.
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
    """

    def __init__(self, *, n_sessions: int, capacity: int, dim: int, k: int,
                 n_labels: int = 2, window: int | None = None,
                 dtype=jnp.float32, donate: bool = True,
                 layout: str = "ring", instrument: bool = False,
                 metrics=None, tracer=None, sync_timing: bool = False,
                 shards: int = 1):
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
        self.n_sessions = n_sessions
        self.capacity = capacity
        self.dim = dim
        self.k = k
        self.n_labels = n_labels
        self.window = window
        self.dtype = dtype
        self.donate = donate
        self.layout = layout
        self.shards = shards
        self._mesh = None
        if shards > 1:
            from repro.core import distributed as dist
            self._mesh = dist.tenant_mesh(shards)
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
                engine="classification", metrics=metrics, tracer=tracer,
                sync=sync_timing,
                n_of=lambda s: s.knn.n, head_of=lambda s: s.head,
                wrap_of=lambda s: s.wrap)
        vstep = jax.vmap(step, in_axes=(0, 0, 0, 0, 0, 0))
        chunk = engine_utils.scan_chunk(
            vstep, self.telemetry.stats_fn if instrument else None)
        pred = jax.vmap(functools.partial(
            sess_m.predict_pvalues, k=k, n_labels=n_labels))
        if self._mesh is not None:
            from repro.core import distributed as dist
            chunk = dist.shard_tenant_chunk(chunk, self._mesh,
                                            with_stats=instrument)
            pred = dist.shard_tenant_fn(pred, self._mesh, (True, True))
        self._step_many = jax.jit(
            chunk, donate_argnums=(0,) if donate else ())
        self._chunks = engine_utils.ChunkPrograms(self._step_many)
        self._predict = jax.jit(pred)
        # host-side upper bound on max_s n_s, for grow-mode occupancy
        # checks without a per-tick device sync
        self._n_bound: int | None = None
        self._spans = engine_utils.DispatchSpans()

    # -- state --------------------------------------------------------------

    def init_state(self) -> Session:
        """Stacked Session pytree with a leading (n_sessions,) axis.

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

    def _shard_state(self, state: Session) -> Session:
        if self._mesh is None:
            return state
        from repro.core import distributed as dist
        return dist.put_tenant_sharded(state, self._mesh)

    def taus(self, key) -> jnp.ndarray:
        """One tie-breaking uniform per session slot for this tick."""
        return jax.random.uniform(key, (self.n_sessions,), dtype=self.dtype)

    def _windows(self, state: Session) -> jnp.ndarray:
        cap = state.capacity
        w = cap + 1 if self.window is None else self.window  # +1: never evict
        return jnp.full((self.n_sessions,), w, dtype=jnp.int32)

    # -- serving ------------------------------------------------------------

    def observe(self, state: Session, x, y, tau, active=None):
        """One micro-batched tick: learn (x[s], y[s]) in every active slot.

        x: (S, dim); y: (S,); tau: (S,) tie-break uniforms; active: (S,)
        bool (default all). Returns (state, pvalues (S,)) — NaN p-value on
        inactive slots. In grow mode, auto-doubles capacity first if any
        session is full (host-side sync + retrace, O(log n) times total).
        The T=1 case of ``observe_many`` (bit-identical, tested); with
        ``donate=True`` (default) the input ``state`` is consumed.
        """
        state, p = self._dispatch(
            state, x[None], y[None], tau[None],
            None if active is None else active[None], op="observe")
        return state, p[0]

    def observe_many(self, state: Session, xs, ys, taus, active=None):
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

    def _dispatch(self, state: Session, xs, ys, taus, active, *, op: str):
        """The shared observe/observe_many dispatch (telemetry-aware,
        under the engine's host spans)."""
        return engine_utils.dispatch_chunk(
            self, state, xs, ys, taus, active, op=op,
            n_of=lambda s: s.knn.n, y_dtype=jnp.int32)

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
        ys = jnp.zeros((T, S), jnp.int32)
        taus = jnp.zeros((T, S), self.dtype)
        active = jnp.ones((T, S), dtype=bool)
        return self._step_many.lower(state, xs, ys, taus,
                                     self._windows(state), active)

    def lower_read(self, queries: int = 1):
        """Lower (but do NOT execute) the read program, ``predict`` over
        ``queries`` query points a tenant: the read's compiled form, as
        ``lower_tick`` gives the tick's (a profile names the compiled
        instructions; their ``op_name`` metadata holds the named scopes)."""
        X = jnp.zeros((self.n_sessions, queries, self.dim), self.dtype)
        return self._predict.lower(self.init_state(), X)

    def reset_occupancy(self) -> None:
        """Forget the host-side occupancy bound (grow mode) and the
        window-invariant check; the next ``observe`` re-syncs/re-checks
        from device. Call after substituting a state that this engine
        did not produce."""
        self._n_bound = None
        self._w_checked = False

    def grow(self, state: Session, factor: int = 2) -> Session:
        """Double every session's capacity (host-side, preserves state).

        ``self.capacity`` follows the grown state so ``meta()`` and
        ``init_state()`` stay consistent with the states this engine
        produces. Session-level grow normalizes each ring to linear
        order with a full-capacity modulus; a sliding engine pins the
        modulus back to its window block (the normalized state fits it:
        head == 0, n <= window)."""
        grow_one = functools.partial(sess_m.grow, factor=factor)
        if self.telemetry is not None:
            with self.telemetry.timed("grow", tenants=self.n_sessions,
                                      capacity=self.capacity * factor,
                                      signature=self.capacity):
                out = jax.vmap(grow_one)(state)
        else:
            out = jax.vmap(grow_one)(state)
        self.capacity = out.capacity
        if self._wmax is not None:
            out = Session(out.knn, out.D, out.head, out.aid,
                          jnp.full_like(out.wrap, self._wmax))
        return self._shard_state(out)

    def predict(self, state: Session, X_test) -> jnp.ndarray:
        """Read-only full-CP p-values per session: (S, m, n_labels).

        X_test: (S, m, dim) per-session query batch, or (m, dim) broadcast
        to every session. One vmapped jitted dispatch for all sessions;
        inside it the fused kernel (Pallas on TPU) does the score update
        + count in a single pass.
        """
        if X_test.ndim == 2:
            X_test = jnp.broadcast_to(
                X_test, (self.n_sessions,) + X_test.shape)
        return engine_utils.dispatch_read(self, "predict", self._predict,
                                          state, X_test)

    # -- snapshot -----------------------------------------------------------

    def meta(self) -> dict[str, Any]:
        """JSON-serializable engine config, stored alongside snapshots."""
        return {
            "n_sessions": self.n_sessions,
            "capacity": self.capacity,
            "dim": self.dim,
            "k": self.k,
            "n_labels": self.n_labels,
            "window": self.window,
            "dtype": jnp.dtype(self.dtype).name,
            "shards": self.shards,
        }

    @classmethod
    def from_meta(cls, meta: dict[str, Any]) -> "ServingEngine":
        meta = dict(meta)
        meta["dtype"] = jnp.dtype(meta.get("dtype", "float32"))
        meta["shards"] = engine_utils.restorable_shards(
            int(meta.pop("shards", 1)), meta["n_sessions"])
        return cls(**meta)


__all__ = ["ServingEngine"]
