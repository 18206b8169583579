"""Tenant-state snapshot/restore for the serving engine.

Wires ``serving.engine`` state through ``checkpoint.store.CheckpointStore``
so tenant CP state survives process restarts: atomic commit (a crash
mid-write can never corrupt the latest snapshot), per-shard checksums,
async double-buffered writes. The engine config travels in the
manifest's ``extra`` field, so ``restore_engine`` can rebuild the whole
serving stack from a bare directory::

    store = SessionStore("/var/lib/cp-serving")
    store.save(step, state, meta=engine.meta())     # during serving
    ...
    engine, state, step = SessionStore(root).restore_engine()  # on restart

Restore is self-describing: the target pytree is reconstructed from the
manifest's leaf shapes (capacity growth between snapshots is fine — the
restored engine adopts the snapshot's capacity, not the configured one).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.checkpoint.store import CheckpointStore
from repro.serving.families import ENGINES, MEASURES


def _state_of(leaves):
    """The serving state whose flattened leaves ``leaves`` are: 8 for
    a classification ``Session`` (X, y, best, n, D, head, aid, wrap), 10
    for a regression ``RegStreamState`` (X, y, D, nbr_d, nbr_y, n, head,
    aid, wrap, nbr_a)."""
    for m in MEASURES.values():
        if m.treedef.num_leaves == len(leaves):
            return m.treedef.unflatten(leaves)
    raise ValueError(
        f"snapshot has {len(leaves)} leaves; expected 8 (classification "
        "Session), 10 (regression RegStreamState), or the legacy 5/6 "
        "linear forms — not a serving snapshot?")


def _like_from_manifest(manifest: dict):
    """Zero-filled restore target (possibly batched) matching the leaves.

    Pre-ring snapshots carried 5 / 6 leaves (no ring bookkeeping); they
    restore into a plain leaf list that ``_from_legacy`` upgrades to a
    linear-layout ring state.
    """
    specs = manifest["leaves"]
    zeros = [jnp.zeros(tuple(s["shape"]), dtype=s["dtype"]) for s in specs]
    if len(specs) in (5, 6):
        return zeros  # legacy linear snapshot: synthesized below
    return _state_of(zeros)


def _from_legacy(leaves):
    """Upgrade a pre-ring linear snapshot to the ring layout.

    The legacy layout was arrival-ordered rows [0, n): exactly a ring at
    head == 0 with a full-capacity modulus and positional arrival ids.
    The regression neighbour-arrival-id lists (which the legacy format
    never stored) are reconstructed exactly from the saved distance
    matrix: per row, a ties-toward-lowest-index top-k — fit's tie rule,
    which positional storage realized by construction.
    """
    if len(leaves) == 5:
        X, y, best, n, D = leaves
    else:
        X, y, D, nbr_d, nbr_y, n = leaves
    cap = D.shape[-1]
    head = jnp.zeros_like(n)
    pos = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32), y.shape)
    live = pos < jnp.asarray(n)[..., None]
    aid = jnp.where(live, pos, 0).astype(jnp.int32)
    wrap = jnp.full_like(n, cap)
    if len(leaves) == 5:
        return _state_of([X, y, best, n, D, head, aid, wrap])

    k = nbr_d.shape[-1]

    def rebuild_nbr_a(Di):
        neg, idxm = jax.lax.top_k(-Di, k)
        return jnp.where(-neg >= 1e30, 0, idxm).astype(jnp.int32)

    fn = rebuild_nbr_a
    for _ in range(D.ndim - 2):
        fn = jax.vmap(fn)
    nbr_a = fn(D)
    return _state_of([X, y, D, nbr_d, nbr_y, n, head, aid, wrap, nbr_a])


class SessionStore:
    """Crash-safe snapshot store for (batched) serving sessions.

    ``metrics`` / ``tracer`` (optional, ``repro.telemetry``) time every
    save and restore: histograms ``snapshot_save_s`` /
    ``snapshot_restore_s`` and one trace record per call. A
    non-blocking ``save`` measures the host-copy + enqueue time (the
    cost the serving loop actually pays); ``blocking=True`` measures
    through the committed write.
    """

    def __init__(self, root: str, keep: int = 3, *, metrics=None,
                 tracer=None, injector=None):
        self.root = root
        self._store = CheckpointStore(root, keep=keep, injector=injector)
        self._metrics = metrics
        self._tracer = tracer

    def _timed(self, op: str, fn, *, tenants=None):
        import time as _time

        t0 = _time.perf_counter()
        out = fn()
        wall = _time.perf_counter() - t0
        if self._metrics is not None:
            self._metrics.histogram(f"{op}_s").observe(wall)
        if self._tracer is not None:
            self._tracer.record(op, wall, tenants=tenants)
        return out

    def save(self, step: int, state, *, meta: dict | None = None,
             blocking: bool = False) -> None:
        """Snapshot ``state``; ``meta`` (e.g. ``engine.meta()``) rides in
        the manifest. Async by default — call ``wait()`` before exit."""
        self._timed(
            "snapshot_save",
            lambda: self._store.save(step, state, blocking=blocking,
                                     extra=meta or {}))

    def wait(self) -> None:
        self._store.wait()

    def latest_step(self) -> int | None:
        return self._store.latest_step()

    def discard(self, step: int) -> None:
        """Drop a step so ``latest_step`` never points at it (see
        ``CheckpointStore.discard``)."""
        self._store.discard(step)

    def restore(self, step: int | None = None
                ) -> tuple[Any, int, dict[str, Any]]:
        """Load (state, step, meta) — target shapes come from the manifest.

        Without an explicit ``step``, a corrupted latest snapshot falls
        back to the previous committed one (``restore_fallback_total``
        counts each skipped step); an explicit ``step`` still raises on
        corruption.
        """
        def _on_fallback(s, exc):
            if self._metrics is not None:
                self._metrics.counter("restore_fallback_total").inc()

        def _restore():
            state, s = self._store.restore(
                _like_from_manifest, step, on_fallback=_on_fallback)
            if isinstance(state, list):  # legacy 5/6-leaf linear snapshot
                state = _from_legacy(state)
            manifest = self._store.read_manifest(s)
            return state, s, manifest.get("extra", {})

        return self._timed("snapshot_restore", _restore)

    def restore_engine(self, step: int | None = None):
        """Rebuild the engine *and* its state from the latest snapshot.

        Returns ``(engine, state, step)``: the engine of the saved
        state's family (``serving.families``), which must agree with the
        saved meta's ``mode`` (classification where a snapshot predates
        the key). Geometry (n_sessions, capacity, dim) is taken from the
        saved arrays; k / n_labels / window / dtype from the saved meta.
        """
        state, step, meta = self.restore(step)
        if "k" not in meta:
            raise ValueError(
                f"snapshot step {step} carries no engine meta (saved "
                "without meta=engine.meta()?) — use restore() and "
                "construct the ServingEngine yourself")
        treedef = jax.tree_util.tree_structure(state)
        m = next(m for m in MEASURES.values() if m.treedef == treedef)
        if meta.get("mode", "classification") != m.name:
            raise ValueError(
                f"snapshot step {step}: state/meta mode mismatch "
                f"({type(state).__name__} vs meta mode "
                f"{meta.get('mode')!r})")
        meta = {
            **meta,
            "mode": m.name,
            "n_sessions": int(state.D.shape[0]),
            "capacity": int(state.D.shape[-1]),
            "dim": int(m.X_of(state).shape[-1]),
        }
        engine = ENGINES[m.name].from_meta(meta)
        return engine, engine.adopt(state), step


class AsyncShardedSaver:
    """Double-buffered sharded snapshot pipeline over a ``SessionStore``.

    Overlaps host I/O with device compute. ``save(step, state)`` slices
    the stacked state into per-shard tenant blocks *on device* — the
    slices are fresh buffers, so the serving loop is free to donate and
    overwrite ``state`` on the very next tick — then hands them to a
    background worker that pulls each shard to host in sequence
    (``device_get`` of shard *i* overlaps the tick that is already
    computing, and with one block per device the per-shard pulls drain
    different devices back-to-back), reassembles the full host state,
    and commits it through the store's atomic write path. A bounded
    queue (default depth 2: one snapshot being written + one buffered)
    gives double buffering with backpressure instead of unbounded
    device-memory growth when snapshots outpace disk.

    Transient write errors (``OSError``, incl. the chaos harness's
    ``TransientWriteError``) are retried up to ``retries`` times on a
    keyed deterministic exponential-backoff schedule
    (``faults.backoff_schedule(seed, step, ...)`` — same (seed, step),
    same waits; ``snapshot_retries_total`` counts them). Anything else
    (incl. ``PermanentWriteError``) surfaces immediately. When retries
    are exhausted the failed step is DISCARDED from the store before
    the error is parked (``snapshot_failed_steps_total``), so
    ``latest_step()`` can never point at a half-written snapshot.

    Worker errors surface on the *next* ``save``/``wait`` call — the
    serving loop finds out, just not mid-tick. Always ``wait()`` (or
    ``close()``) before reading the store back.
    """

    def __init__(self, store: SessionStore, shards: int, *, depth: int = 2,
                 metrics=None, retries: int = 3, retry_base_s: float = 0.05,
                 seed: int = 0):
        import queue as _queue
        import threading as _threading

        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.store = store
        self.shards = shards
        self.retries = int(retries)
        self.retry_base_s = float(retry_base_s)
        self._seed = int(seed)
        self._metrics = metrics
        self._q: Any = _queue.Queue(maxsize=depth)
        self._err: BaseException | None = None
        self._worker = _threading.Thread(
            target=self._run, name="sharded-snapshot-saver", daemon=True)
        self._worker.start()

    def _check_err(self) -> None:
        if self._err is not None:
            err, self._err = self._err, None
            raise RuntimeError("async snapshot save failed") from err

    def save(self, step: int, state, *, meta: dict | None = None) -> None:
        """Enqueue a snapshot of ``state`` (blocks only when the queue
        is full — backpressure at ``depth`` in-flight snapshots)."""
        self._check_err()
        S = jax.tree_util.tree_leaves(state)[0].shape[0]
        cuts = [S * i // self.shards for i in range(self.shards + 1)]
        # device-side slicing: new buffers per shard, donation-safe. A
        # slice that spans the whole leaf is the leaf itself, which the
        # next donating tick deletes under the worker: copy that one
        def cut(leaf, a, b):
            part = leaf[a:b]
            return jnp.copy(part) if part is leaf else part

        slices = [
            jax.tree_util.tree_map(
                lambda l: cut(l, cuts[i], cuts[i + 1]), state)
            for i in range(self.shards)]
        self._q.put((step, slices, meta))

    def _commit_with_retry(self, step: int, full, meta) -> None:
        import time as _time

        from repro.robustness.faults import (PermanentWriteError,
                                             backoff_schedule)

        delays = backoff_schedule(self._seed, step, self.retries,
                                  self.retry_base_s)
        attempt = 0
        while True:
            try:
                self.store.save(step, full, meta=meta, blocking=True)
                return
            except PermanentWriteError:
                raise
            except OSError:
                if attempt >= self.retries:
                    raise
                if self._metrics is not None:
                    self._metrics.counter("snapshot_retries_total").inc()
                _time.sleep(delays[attempt])
                attempt += 1

    def _run(self) -> None:
        import time as _time

        import numpy as np

        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            step, slices, meta = item
            try:
                t0 = _time.perf_counter()
                host = [jax.device_get(s) for s in slices]  # shard-by-shard
                full = jax.tree_util.tree_map(
                    lambda *ls: np.concatenate(ls, axis=0), *host)
                self._commit_with_retry(step, full, meta)
                if self._metrics is not None:
                    self._metrics.histogram(
                        "snapshot_async_save_s", shards=self.shards
                    ).observe(_time.perf_counter() - t0)
            except BaseException as e:  # surfaced on next save()/wait()
                # failed for good: drop the step so latest_step() can
                # never point at a half-written snapshot (discard never
                # raises — the original error is what surfaces)
                self.store.discard(step)
                if self._metrics is not None:
                    self._metrics.counter(
                        "snapshot_failed_steps_total").inc()
                self._err = e
            finally:
                self._q.task_done()

    def wait(self) -> None:
        """Block until every enqueued snapshot is committed."""
        self._q.join()
        self.store.wait()
        self._check_err()

    def close(self) -> None:
        """Drain, stop the worker, and surface any pending error."""
        self._q.put(None)
        self._q.join()
        self._worker.join()
        self.store.wait()
        self._check_err()


__all__ = ["SessionStore", "AsyncShardedSaver"]
