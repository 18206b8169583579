"""Device-side tick counters carried alongside engine state.

The serving engines advance every tenant inside one donated jitted
``lax.scan`` — host code never sees which lanes evicted, wrapped their
ring, or how full they are, and syncing the state out to look would
destroy the O(cap) in-place path. The key observation is that every
tick statistic is a *closed form* of the pre-chunk integer bookkeeping
leaves (``n``/``head``/``wrap``) and the chunk's (T, S) active mask:
occupancy evolves as ``min(n0 + cumsum(active), window)``, an eviction
fires exactly on active ticks that start window-full, and the ring
head advances once per eviction — so ring wraps per session are
``(head0 + evictions) // wrap``. The whole (len(STAT_KEYS),) int32
stat vector is therefore computed *outside the scan body* in one
fused O(T·S) integer pass per chunk (zero added work inside the
per-tick loop, where even a few extra ops measure as a several-%
regression), and the engine folds each chunk's vector into a tiny
device-resident accumulator (one async jitted add per chunk — no host
sync on the hot path). ``TickStats.drain()`` converts the accumulator
to host ints and publishes metrics; only exporters pay the sync.

Bit-exactness: the stats are pure reads of integer leaves that never
feed the float arithmetic, so the instrumented step's p-values and
state are bit-identical to the uninstrumented step's
(property-tested in tests/test_telemetry.py). Donation is unaffected:
the reads happen before the donated buffers are overwritten, and the
(cap, cap) float leaves are never touched.

Per-tick stats (each reduced over the session axis):

    ticks          active lanes this tick
    evictions      active lanes at a full window (the decremental path
                   runs, with its one fused backfill reduction; 0 by
                   construction in grow mode)
    ring_wraps     evictions whose head pointer rolls over to slot 0
    occupancy_max  max post-tick live count over sessions
    occupancy_sum  sum of post-tick live counts (mean = sum / sessions)
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

# stats whose accumulation over ticks is a max, not a sum
_MAX_KEYS = ("occupancy_max",)
STAT_KEYS = ("ticks", "evictions", "ring_wraps", "occupancy_sum",
             "occupancy_max")
_MAX_MASK_IDX = tuple(STAT_KEYS.index(k) for k in _MAX_KEYS)


def make_chunk_stats_fn(n_of: Callable, head_of: Callable,
                        wrap_of: Callable):
    """Build the in-graph chunk-level stats function for one engine.

    ``n_of``/``head_of``/``wrap_of`` read the per-session occupancy,
    ring head, and ring modulus arrays from the *stacked* engine state
    (e.g. ``lambda s: s.knn.n`` / ``lambda s: s.n``). The returned
    ``stats_fn(state, windows, actives)`` evaluates on the pre-chunk
    state and the chunk's (T, S) active mask and returns a
    (len(STAT_KEYS),) int32 vector in ``STAT_KEYS`` order — the exact
    per-tick counts, computed in closed form (module doc) rather than
    inside the scan body.
    """

    def stats_fn(state, windows, actives) -> jnp.ndarray:
        i32 = jnp.int32
        n0 = n_of(state)
        head0 = head_of(state)
        wrap = wrap_of(state)
        w = windows
        act = actives.astype(i32)                       # (T, S)
        c = jnp.cumsum(act, axis=0)                     # arrivals <= t
        n_after = jnp.minimum(n0[None, :] + c, w[None, :])
        n_pre = jnp.minimum(n0[None, :] + c - act, w[None, :])
        # active tick at a full window => the decremental evict runs
        # (grow mode passes w = cap + 1, so n_pre < w always: zero)
        ev = (actives & (n_pre >= w[None, :])).astype(i32)
        ev_total = jnp.sum(ev, axis=0)                  # (S,)
        # one head step per eviction, mod wrap: full turns completed
        wraps = (head0 + ev_total) // wrap - head0 // wrap
        return jnp.stack([
            jnp.sum(act),        # ticks
            jnp.sum(ev),         # evictions
            jnp.sum(wraps),      # ring_wraps
            jnp.sum(n_after),    # occupancy_sum
            jnp.max(n_after),    # occupancy_max
        ])

    return stats_fn


def combine(acc: jnp.ndarray, stat: jnp.ndarray) -> jnp.ndarray:
    """Accumulate one stat vector into another (sum, max where marked)."""
    is_max = jnp.zeros((len(STAT_KEYS),), bool)
    is_max = is_max.at[jnp.asarray(_MAX_MASK_IDX)].set(True)
    return jnp.where(is_max, jnp.maximum(acc, stat), acc + stat)


_fold_into = jax.jit(combine)


class TickStats:
    """Host-side accumulator for the engines' per-chunk stat vectors.

    ``fold(vec)`` merges one chunk's accumulated (len(STAT_KEYS),)
    vector into the running device accumulator — ONE async jitted
    dispatch, no host sync (a dozen eager ops here would be measurable
    host overhead on the per-tick path). ``drain()`` syncs the
    accumulator to host ints, publishes them to ``metrics`` under
    ``engine_<stat>`` (counters for the monotone ones, gauges for the
    occupancy watermarks), and resets it.
    """

    def __init__(self, metrics=None, *, engine: str = "classification"):
        self.metrics = metrics
        self.engine = engine
        self._acc: Any | None = None
        self.totals: dict[str, int] = {k: 0 for k in STAT_KEYS}
        # last drain's per-shard rows (sharded engines only): one
        # {stat: int} dict per shard, in mesh order
        self.shard_vals: list[dict[str, int]] = []

    def fold(self, vec: jnp.ndarray) -> None:
        if self._acc is None:
            self._acc = vec
        else:
            self._acc = _fold_into(self._acc, vec)

    def reset(self) -> None:
        """Discard the pending accumulator and totals without
        publishing (e.g. to exclude warmup dispatches from a run)."""
        self._acc = None
        self.totals = {k: 0 for k in STAT_KEYS}
        self.shard_vals = []

    def drain(self) -> dict[str, int]:
        """Sync + publish + reset; returns this drain's host values."""
        if self._acc is None:
            return {k: 0 for k in STAT_KEYS}
        import numpy as np

        host = np.asarray(self._acc)
        if host.ndim == 2:
            # sharded chunk: one row per shard (mesh order). Merge rows
            # the same way ticks merge — sum, max for the watermarks —
            # and keep the per-shard rows for occupancy reporting.
            self.shard_vals = [
                {k: int(row[i]) for i, k in enumerate(STAT_KEYS)}
                for row in host]
            merged = host.sum(axis=0)
            for i in _MAX_MASK_IDX:
                merged[i] = host[:, i].max()
            host = merged
        vals = {k: int(host[i]) for i, k in enumerate(STAT_KEYS)}
        self._acc = None
        for k, v in vals.items():
            if k in _MAX_KEYS:
                self.totals[k] = max(self.totals[k], v)
            else:
                self.totals[k] += v
        if self.metrics is not None:
            for k, v in vals.items():
                if k in _MAX_KEYS:
                    # high-water mark over the whole run
                    self.metrics.gauge(
                        f"engine_{k}", engine=self.engine).set(
                        self.totals[k])
                else:
                    # mean occupancy = occupancy_sum_total / ticks_total
                    self.metrics.counter(
                        f"engine_{k}_total", engine=self.engine).inc(v)
        return vals


__all__ = ["STAT_KEYS", "combine", "make_chunk_stats_fn", "TickStats"]
