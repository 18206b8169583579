"""Tenant lifecycle over capacity-bucketed engine pools.

The engines serve a *fixed* slot grid: ``n_sessions`` lanes, one
capacity. A real fleet has tenants arriving, leaving, and growing at
different rates — and in grow mode one slow-growing tenant filling its
lane forces ``ensure_room`` to double the capacity of EVERY lane in
the engine (a pool-wide retrace plus an O(S·cap²) copy). The fleet
fixes that with the classic serving move: group tenants into pools by
*capacity bucket* and migrate a tenant to the next bucket's pool as it
grows, so growth costs one O(cap²) lane copy instead of a pool-wide
retrace.

Bucket boundaries come from the fitted cost model when one is
available (``CostModel.suggest_buckets`` — geometric in modeled
per-tick *cost*, replacing the static power-of-two
``telemetry.tracer.capacity_bucket`` scheme); without a model the
power-of-two ladder is the fallback. Each pool is one ordinary
``ServingEngine`` / ``RegressionServingEngine`` (grow mode, donated,
optionally tenant-sharded across devices via ``shards``), so every
exactness property those engines carry transfers: a fleet-served
tenant's p-value stream is bit-identical to a dedicated single-lane
engine fed the same stream (tested), because p-values are
capacity-padding-invariant and migration is exactly the engines'
proven ``grow`` transformation generalized to an arbitrary target
capacity (normalize the ring to linear order, then pad every leaf
with its inert fill).

    fleet = Fleet(dim=8, k=5, n_labels=2)
    fleet.admit("alice"); fleet.admit("bob")
    ps = fleet.observe({"alice": (x_a, y_a, tau_a),
                        "bob": (x_b, y_b, tau_b)})
    sets = fleet.predict("alice", X_query)       # (m, n_labels)
    fleet.retire("bob")                          # lane returns to the pool

Tenants past the last bucket boundary stay in the last pool and let
its engine auto-grow (the pre-fleet behavior, now confined to the
tenants that actually need it).
"""
from __future__ import annotations

import bisect
from typing import Any

import jax
import jax.numpy as jnp

from repro.serving.families import ENGINES, MEASURES


def pow2_buckets(cap_min: int, cap_max: int) -> list[int]:
    """The static power-of-two bucket ladder (the no-cost-model
    fallback, and what ``suggest_buckets`` reproduces under linear
    cost scaling)."""
    bounds = [int(cap_min)]
    while bounds[-1] < cap_max:
        bounds.append(min(bounds[-1] * 2, int(cap_max)))
    return bounds


class _Pool:
    """One engine + its state + lane bookkeeping at one capacity."""

    def __init__(self, fleet: "Fleet", capacity: int, index: int):
        self.capacity = capacity
        self.index = index
        self.engine = fleet._make_engine(capacity)
        self.state = self.engine.init_state()
        S = self.engine.n_sessions
        self.free: list[int] = list(range(S - 1, -1, -1))
        self.lane_tenant: dict[int, Any] = {}

    def set_lane(self, lane: int, lane_state) -> None:
        """Scatter one session tree into the stacked state (host-side
        rare path: O(S·cap²) copy, like the engines' own ``grow``)."""
        self.state = self.engine.adopt(jax.tree_util.tree_map(
            lambda L, v: L.at[lane].set(v.astype(L.dtype)), self.state,
            lane_state))

    def get_lane(self, lane: int):
        return jax.tree_util.tree_map(lambda L: L[lane], self.state)


class Fleet:
    """Admit / observe / retire tenants across bucketed engine pools.

    Parameters
    ----------
    dim, k, n_labels, dtype: per-tenant CP geometry (``n_labels`` only
                read in classification mode).
    mode:       "classification" (``ServingEngine``) or "regression"
                (``RegressionServingEngine``). All pools run grow mode
                (window=None) — bucketing exists to absorb growth.
    cost_model: optional fitted ``telemetry.costmodel.CostModel``;
                bucket boundaries come from its ``suggest_buckets``
                (cost-geometric). ``None`` => power-of-two ladder.
    cap_min, cap_max: the bucket range; ``cap_min`` is every new
                tenant's starting capacity (must be >= k).
    cost_ratio: per-bucket top-vs-bottom modeled-cost ratio for
                ``suggest_buckets``.
    pool_sessions: lanes per pool engine (rounded up to a multiple of
                ``shards``); a full pool just spills into a sibling.
    shards:     tenant-shard every pool engine across this many devices.
    metrics:    optional ``MetricsRegistry`` for fleet counters/gauges.
    guard:      admission-check observe inputs host-side (features
                finite, label in range, tau in [0, 1]); a rejected
                tenant's tick is never dispatched — its state stays
                bitwise unchanged and it gets a NaN p-value back
                (``fleet_rejected_observes_total``). The in-graph
                equivalent for raw engines is
                ``robustness.guard.TickGuard``.
    """

    def __init__(self, *, dim: int, k: int, n_labels: int = 2,
                 mode: str = "classification", cost_model=None,
                 cap_min: int = 32, cap_max: int = 4096,
                 cost_ratio: float = 2.0, pool_sessions: int = 64,
                 dtype=jnp.float32, shards: int = 1, metrics=None,
                 guard: bool = False):
        if mode not in MEASURES:
            raise ValueError(f"unknown fleet mode {mode!r}")
        if cap_min < k:
            raise ValueError(f"cap_min {cap_min} < k {k}")
        self.dim = dim
        self.k = k
        self.n_labels = n_labels
        self.mode = mode
        self._measure = MEASURES[mode]
        self.dtype = dtype
        self.shards = shards
        self.pool_sessions = -(-pool_sessions // shards) * shards
        self.metrics = metrics
        self.guard = guard
        if cost_model is not None:
            self.buckets = cost_model.suggest_buckets(
                cap_min=cap_min, cap_max=cap_max, cost_ratio=cost_ratio,
                engine=mode)
        else:
            self.buckets = pow2_buckets(cap_min, cap_max)
        self._pools: dict[int, list[_Pool]] = {}
        self._where: dict[Any, tuple[int, int, int]] = {}  # cap, pool, lane
        self._occ: dict[Any, int] = {}
        self._init_lane_cache: dict[int, Any] = {}

    # -- engine/pool plumbing -----------------------------------------------

    def _make_engine(self, capacity: int):
        return ENGINES[self.mode].build(
            n_sessions=self.pool_sessions, capacity=capacity, dim=self.dim,
            k=self.k, n_labels=self.n_labels, window=None, dtype=self.dtype,
            shards=self.shards)

    def _init_lane(self, capacity: int):
        lane = self._init_lane_cache.get(capacity)
        if lane is None:
            lane = self._measure.init(capacity, self.dim, self.k,
                                      dtype=self.dtype)
            self._init_lane_cache[capacity] = lane
        return lane

    def _alloc(self, capacity: int) -> tuple[_Pool, int]:
        pools = self._pools.setdefault(capacity, [])
        for pool in pools:
            if pool.free:
                return pool, pool.free.pop()
        pool = _Pool(self, capacity, len(pools))
        pools.append(pool)
        if self.metrics is not None:
            self.metrics.gauge("fleet_pools", mode=self.mode).set(
                sum(len(ps) for ps in self._pools.values()))
        return pool, pool.free.pop()

    def _counter(self, name: str):
        if self.metrics is not None:
            self.metrics.counter(name, mode=self.mode).inc()

    def _set_tenants_gauge(self) -> None:
        if self.metrics is not None:
            self.metrics.gauge("fleet_tenants", mode=self.mode).set(
                len(self._where))

    # -- lifecycle ----------------------------------------------------------

    def admit(self, tid) -> None:
        """Give ``tid`` a fresh lane in the smallest-capacity pool."""
        if tid in self._where:
            raise KeyError(f"tenant {tid!r} already admitted")
        cap = self.buckets[0]
        pool, lane = self._alloc(cap)
        # free lanes are always init-fresh (retire/migrate reset them
        # eagerly), so admission is O(1) host bookkeeping
        pool.lane_tenant[lane] = tid
        self._where[tid] = (cap, pool.index, lane)
        self._occ[tid] = 0
        self._counter("fleet_admissions_total")
        self._set_tenants_gauge()

    def retire(self, tid) -> None:
        """Return ``tid``'s lane to its pool (state cleared)."""
        cap, pi, lane = self._where.pop(tid)
        pool = self._pools[cap][pi]
        del pool.lane_tenant[lane]
        del self._occ[tid]
        # eager reset: a stale full lane would otherwise count toward
        # the pool's grow-mode occupancy bound and retrace the pool
        # (engine.capacity, not the bucket key — the last pool may have
        # auto-grown past its boundary)
        pool.set_lane(lane, self._init_lane(pool.engine.capacity))
        pool.free.append(lane)
        self._counter("fleet_retirements_total")
        self._set_tenants_gauge()

    def _migrate(self, tid, needed: int) -> None:
        """Move ``tid`` to the smallest bucket holding ``needed`` points
        — one lane repad (the engines' ``grow``, arbitrary target cap)
        instead of a pool-wide retrace."""
        src_cap, spi, slane = self._where[tid]
        i = bisect.bisect_left(self.buckets, needed)
        new_cap = self.buckets[min(i, len(self.buckets) - 1)]
        if new_cap <= src_cap:
            return
        src_pool = self._pools[src_cap][spi]
        lane_state = self._measure.repad(src_pool.get_lane(slane), new_cap)
        del src_pool.lane_tenant[slane]
        src_pool.set_lane(slane, self._init_lane(src_pool.engine.capacity))
        src_pool.free.append(slane)
        pool, lane = self._alloc(new_cap)
        pool.set_lane(lane, lane_state)
        pool.lane_tenant[lane] = tid
        self._where[tid] = (new_cap, pool.index, lane)
        self._counter("fleet_migrations_total")

    # -- serving ------------------------------------------------------------

    def observe(self, items: dict[Any, tuple]) -> dict[Any, jnp.ndarray]:
        """One fleet tick: ``items`` maps tid -> (x, y, tau).

        Tenants about to outgrow their pool migrate first (so
        ``ensure_room`` never doubles a whole pool on their account —
        only past the last bucket does the old auto-grow fire), then
        each pool with traffic runs ONE engine tick with the other
        lanes masked inactive. Returns tid -> p-value (0-d jax array,
        still async; ``float()`` to sync). With ``guard=True`` a
        malformed item is rejected before dispatch (NaN p, state
        untouched, occupancy unchanged).
        """
        import numpy as np

        if self.guard:
            m = self._measure
            ydt = m.y_dtype or self.dtype
            live = {}
            out_rej: dict[Any, jnp.ndarray] = {}
            for tid, (x, y, tau) in items.items():
                ok = bool(np.all(np.isfinite(
                    np.asarray(x, dtype=np.float64))))
                yf = float(np.asarray(y).astype(np.float64))
                ok = ok and bool(np.isfinite(yf)) and bool(m.label_ok(
                    np.asarray(yf).astype(ydt), self.n_labels, np))
                tau_f = float(tau)
                ok = ok and bool(np.isfinite(tau_f)) and 0.0 <= tau_f <= 1.0
                if ok:
                    live[tid] = (x, y, tau)
                else:
                    self._counter("fleet_rejected_observes_total")
                    out_rej[tid] = jnp.asarray(float("nan"),
                                               dtype=self.dtype)
            out_rej.update(self._observe_live(live))
            return out_rej
        return self._observe_live(items)

    def _observe_live(self, items: dict[Any, tuple]) -> dict[Any, jnp.ndarray]:
        last = self.buckets[-1]
        for tid in items:
            cap, _, _ = self._where[tid]
            if self._occ[tid] + 1 > cap and cap < last:
                self._migrate(tid, self._occ[tid] + 1)
        groups: dict[tuple[int, int], dict[int, tuple]] = {}
        for tid, (x, y, tau) in items.items():
            cap, pi, lane = self._where[tid]
            groups.setdefault((cap, pi), {})[lane] = (tid, x, y, tau)
        import numpy as np

        out: dict[Any, jnp.ndarray] = {}
        for (cap, pi), lanes in sorted(groups.items()):
            pool = self._pools[cap][pi]
            S = pool.engine.n_sessions
            xs = np.zeros((S, self.dim), dtype=self.dtype)
            ys = np.zeros((S,), dtype=pool.engine.y_dtype)
            taus = np.zeros((S,), dtype=self.dtype)
            act = np.zeros((S,), dtype=bool)
            for lane, (tid, x, y, tau) in lanes.items():
                xs[lane] = np.asarray(x)
                ys[lane] = y
                taus[lane] = tau
                act[lane] = True
            pool.state, p = pool.engine.observe(
                pool.state, jnp.asarray(xs), jnp.asarray(ys),
                jnp.asarray(taus), active=jnp.asarray(act))
            for lane, (tid, _, _, _) in lanes.items():
                out[tid] = p[lane]
                self._occ[tid] += 1
        return out

    def _lane_of(self, tid) -> tuple[_Pool, int]:
        cap, pi, lane = self._where[tid]
        return self._pools[cap][pi], lane

    def predict(self, tid, X_test) -> jnp.ndarray:
        """Classification full-CP p-values (m, n_labels) for one tenant."""
        pool, lane = self._lane_of(tid)
        return pool.engine.predict(pool.state, X_test)[lane]

    def intervals(self, tid, X_test, epsilon: float) -> jnp.ndarray:
        """Regression prediction intervals (m, 2) for one tenant."""
        pool, lane = self._lane_of(tid)
        return pool.engine.intervals(pool.state, X_test, epsilon)[lane]

    def pvalues(self, tid, X_test, t_query) -> jnp.ndarray:
        """Regression p-values (m, nq) for one tenant."""
        pool, lane = self._lane_of(tid)
        return pool.engine.pvalues(pool.state, X_test, t_query)[lane]

    # -- introspection ------------------------------------------------------

    def occupancy(self, tid) -> int:
        """Host-tracked live-point count (exact in grow mode)."""
        return self._occ[tid]

    def stats(self) -> dict[str, Any]:
        """Host-side fleet snapshot; publishes pool occupancy gauges."""
        pools = []
        for cap in sorted(self._pools):
            for pool in self._pools[cap]:
                used = len(pool.lane_tenant)
                occ = [self._occ[t] for t in pool.lane_tenant.values()]
                pools.append({
                    "capacity": cap,
                    "pool": pool.index,
                    "lanes": pool.engine.n_sessions,
                    "lanes_used": used,
                    "occupancy_max": max(occ, default=0),
                    "occupancy_mean": (sum(occ) / used) if used else 0.0,
                })
                if self.metrics is not None:
                    self.metrics.gauge(
                        "fleet_pool_lanes_used", mode=self.mode,
                        capacity=cap, pool=pool.index).set(used)
        return {"tenants": len(self._where), "buckets": self.buckets,
                "pools": pools}


__all__ = ["Fleet", "pow2_buckets"]
