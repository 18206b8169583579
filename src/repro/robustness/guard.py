"""Guarded engine tick: input admission + poison-lane quarantine.

A single NaN feature admitted into one tenant's lane contaminates that
lane's maintained (cap, cap) distance matrix and every subsequent
p-value — CP validity is only as good as the stream it conditions on
(Ndiaye's stability analysis formalizes the sensitivity). ``TickGuard``
wraps a serving engine with two defenses, both *outside* the engine's
scan body so the hot per-tick loop is untouched (PR 6's closed-form
tick-counter pattern):

admission (in-graph, per chunk)
    A jitted elementwise check on the observe inputs — features finite,
    label in range (``[0, n_labels)`` classification / finite
    regression), tau in ``[0, 1]`` — folds rejections into the chunk's
    ``active`` mask. A rejected observe simply never happens for that
    lane-tick: state stays bitwise unchanged (the engines' ``active``
    contract) and the returned p-value is NaN. Rejection counts
    accumulate device-side (one async add per chunk) and publish as
    ``guard_rejected_inputs_total{kind}`` on ``drain()``.

poison detection + quarantine (closed form, per sweep)
    In-memory corruption that admission cannot see (bit flips, a buggy
    kernel, a poisoned snapshot) shows up as non-finite values in the
    per-lane float state leaves. The detector is a closed-form
    ``any(~isfinite)`` reduction over the cheap leaves (features +
    neighbour scores — NOT the (S, cap, cap) distance matrix, whose
    poison can only arrive through those same inputs), dispatched
    asynchronously after the chunk and *fetched one sweep later*: the
    (S,) bool synced at sweep point n is the detector output of sweep
    point n-1, whose device work has already drained behind the
    intervening chunk — the hot loop never stalls on the check.
    Non-finite poison is sticky in those leaves, so the one-sweep
    detection lag loses nothing; call ``finalize(state)`` at end of
    stream to flush the last pending check. A tripped lane is FROZEN
    (masked out of every subsequent tick: ``quarantined_lanes`` gauge,
    ``guard_quarantines_total``), then restored from the last committed
    snapshot via the fleet's one-lane repad migration when a
    ``SessionStore`` is attached (``guard_restores_total``); with no
    snapshot available it stays frozen rather than serving garbage.

When the stream is clean the guard is bit-neutral: the effective mask
equals the caller's ``active`` mask, the engine sees identical inputs,
and the dispatch signature never changes — zero new retraces
(property-tested; the chunked-path overhead is CI-gated ≤ 5 %).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

#: rejection-kind order in the device-side accumulator
REJECT_KINDS = ("nonfinite_feature", "label_out_of_range",
                "tau_out_of_range")

class TickGuard:
    """Wrap ``engine`` (classification or regression serving engine)
    with admission + quarantine. Drop-in for the observe path::

        guard = TickGuard(engine, store=session_store, metrics=reg)
        state, p = guard.observe_many(state, xs, ys, taus)

    Reads (``predict`` / ``intervals`` / ``pvalues`` / ``meta`` / ...)
    pass through to the engine untouched.

    Parameters
    ----------
    engine:      a ``ServingEngine`` / ``RegressionServingEngine``.
    store:       optional ``serving.snapshot.SessionStore`` holding
                 committed snapshots of THIS engine's state — the
                 quarantine-restore source. ``None`` => tripped lanes
                 stay frozen.
    metrics:     optional ``MetricsRegistry``.
    check_every: run the poison sweep every N guarded chunks (default
                 2: the deferred (S,) fetch costs one host/device
                 round-trip, and poison is sticky in the checked
                 leaves, so sweeping every other chunk halves the cost
                 at a bounded detection lag; 1 = every chunk).
    """

    def __init__(self, engine, *, store=None, metrics=None,
                 check_every: int = 2):
        self.engine = engine
        self.store = store
        self.metrics = metrics
        self.check_every = max(int(check_every), 1)
        S = engine.n_sessions
        label_ok = engine.measure.label_ok
        n_labels = getattr(engine, "n_labels", None)

        def admit(xs, ys, taus, active, qmask, racc):
            ok_x = jnp.all(jnp.isfinite(xs), axis=-1)
            ok_y = label_ok(ys, n_labels)
            ok_tau = jnp.isfinite(taus) & (taus >= 0.0) & (taus <= 1.0)
            live = active & ~qmask[None, :]
            eff = live & ok_x & ok_y & ok_tau
            counts = jnp.stack([
                jnp.sum(live & ~ok_x),
                jnp.sum(live & ok_x & ~ok_y),
                jnp.sum(live & ok_x & ok_y & ~ok_tau),
            ]).astype(jnp.int32)
            return eff, racc + counts

        self._admit = jax.jit(admit)
        self._poison = jax.jit(engine.measure.poison)
        self._qmask = jnp.zeros((S,), dtype=bool)
        self.quarantined: set = set()
        self._racc = jnp.zeros((len(REJECT_KINDS),), dtype=jnp.int32)
        self._chunks = 0
        self._ones = None  # cached all-ones active mask, keyed by shape
        self._pending = None  # deferred (S,) poison flags, device-side
        self._quarantines = 0
        self._restores = 0
        self._cache_step = None
        self._cache_state = None

    # -- observe path -------------------------------------------------------

    def observe(self, state, x, y, tau, active=None):
        """Guarded T=1 tick; same contract as ``engine.observe``."""
        state, p = self.observe_many(state, x[None], y[None], tau[None],
                                     None if active is None
                                     else active[None])
        return state, p[0]

    def observe_many(self, state, xs, ys, taus, active=None):
        """Guarded chunk: admission-filtered ``engine.observe_many``
        followed by the poison sweep (every ``check_every`` chunks)."""
        eng = self.engine
        xs = jnp.asarray(xs)
        ys = jnp.asarray(ys)
        taus = jnp.asarray(taus)
        ydt = eng.y_dtype
        if active is None:  # cached all-ones mask: no per-chunk alloc
            if self._ones is None or self._ones.shape != ys.shape:
                self._ones = jnp.ones(ys.shape, dtype=bool)
            active = self._ones
        eff, self._racc = self._admit(
            xs, ys if ys.dtype == ydt else ys.astype(ydt),
            taus, jnp.asarray(active), self._qmask, self._racc)
        state, p = eng.observe_many(state, xs, ys, taus, active=eff)
        self._chunks += 1
        if self._chunks % self.check_every == 0:
            state = self._sweep(state)  # consumes the PREVIOUS flags
            self._pending = self._poison(state)  # async; fetched next
        return state, p

    def finalize(self, state):
        """Flush the deferred poison check at end of stream (the last
        chunk's flags are still pending). Returns the possibly lane-
        restored state; call before ``drain()``."""
        state = self._sweep(state)
        self._pending = self._poison(state)
        return self._sweep(state)

    # -- quarantine ---------------------------------------------------------

    def _sweep(self, state):
        """Consume the pending poison flags; freeze newly tripped lanes,
        then try a restore. The flags were computed on an earlier
        version of ``state`` — non-finite poison in the checked leaves
        is sticky, so a lane flagged then is still poisoned now."""
        if self._pending is None:
            return state
        bad = np.asarray(self._pending)
        self._pending = None
        hit = [int(i) for i in np.nonzero(bad)[0]
               if int(i) not in self.quarantined]
        if not hit:
            return state
        for lane in hit:
            self.quarantined.add(lane)
            self._quarantines += 1
            if self.metrics is not None:
                self.metrics.counter("guard_quarantines_total").inc()
        self._sync_qmask()
        for lane in hit:
            state = self._restore_lane(state, lane)
        return state

    def _sync_qmask(self):
        q = np.zeros((self.engine.n_sessions,), dtype=bool)
        for lane in self.quarantined:
            q[lane] = True
        self._qmask = jnp.asarray(q)
        if self.metrics is not None:
            self.metrics.gauge("quarantined_lanes").set(
                len(self.quarantined))

    def _snapshot_state(self):
        """Last committed snapshot state (cached per committed step)."""
        if self.store is None:
            return None
        step = self.store.latest_step()
        if step is None:
            return None
        if step != self._cache_step:
            snap, got, _meta = self.store.restore()  # walk-back enabled
            self._cache_step = step
            self._cache_state = snap
        return self._cache_state

    def _restore_lane(self, state, lane: int):
        """One-lane restore from the snapshot: the fleet's repad
        migration scattered into the live stacked state. On any
        incompatibility (no snapshot, different lane grid, shrinking
        capacity, sliding-window mismatch) the lane just stays frozen."""
        snap = self._snapshot_state()
        if snap is None:
            return state
        eng = self.engine
        S_snap = int(jax.tree_util.tree_leaves(snap)[0].shape[0])
        if S_snap != eng.n_sessions:
            return state
        lane_state = jax.tree_util.tree_map(lambda L: L[lane], snap)
        snap_cap = int(lane_state.D.shape[-1])
        cur_cap = int(state.D.shape[-1])
        if snap_cap != cur_cap:
            if eng.window is not None or snap_cap > cur_cap:
                return state
            lane_state = eng.measure.repad(lane_state, cur_cap)
        if any(np.issubdtype(np.asarray(l).dtype, np.floating)
               and not np.all(np.isfinite(np.asarray(l)))
               for l in jax.tree_util.tree_leaves(lane_state)):
            return state  # the snapshot itself is poisoned: stay frozen
        state = eng.adopt(jax.tree_util.tree_map(
            lambda L, v: L.at[lane].set(v.astype(L.dtype)), state,
            lane_state))
        self.quarantined.discard(lane)
        self._restores += 1
        if self.metrics is not None:
            self.metrics.counter("guard_restores_total").inc()
        self._sync_qmask()
        return state

    # -- reporting ----------------------------------------------------------

    def drain(self) -> dict:
        """Sync + publish the guard counters; reset the accumulators.

        Returns ``{rejected: {kind: n}, quarantines, restores,
        quarantined_lanes}``."""
        rej = [int(v) for v in np.asarray(self._racc)]
        self._racc = jnp.zeros_like(self._racc)
        if self.metrics is not None:
            for kind, n in zip(REJECT_KINDS, rej):
                if n:
                    self.metrics.counter("guard_rejected_inputs_total",
                                         kind=kind).inc(n)
        out = {
            "rejected": dict(zip(REJECT_KINDS, rej)),
            "quarantines": self._quarantines,
            "restores": self._restores,
            "quarantined_lanes": sorted(self.quarantined),
        }
        self._quarantines = 0
        self._restores = 0
        return out

    def __getattr__(self, name):
        return getattr(self.engine, name)


__all__ = ["TickGuard", "REJECT_KINDS"]
