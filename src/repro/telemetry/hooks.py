"""Per-engine instrumentation bundle used by the serving engines.

``EngineTelemetry`` owns everything an instrumented engine needs:

* op timing — each engine-level dispatch (observe / observe_many /
  predict / intervals / pvalues / grow) lands in a latency histogram
  (steady-state calls separate from the compile-including first call
  at each shape signature) and, when a ``Tracer`` is attached, as one
  JSONL trace record with the compile-vs-steady flag.
* device tick stats — the in-graph per-tick counters from
  ``telemetry.device`` folded into a lazy device accumulator
  (``.ticks``); ``drain()`` publishes them.
* the chunk program's memory — before each chunk dispatch the engine
  hands ``note_program`` the executable it calls, and ``drain()``
  reports two byte counts beside the tick stats: ``state_bytes``, one
  chip's share of the engine state (leaf bytes over the shard count),
  and ``chunk_temp_bytes``, that executable's temporaries
  (``memory_analysis()``). A device's peak memory does not show the
  temporaries apart from the state they sit beside.

The timing wrapper never forces a device sync by default: ``wall_s`` is
host wall time around the (async) dispatch. Loops that synchronize per
call (fetching p-values each tick) therefore get device-true
histograms; a fire-and-forget caller measures enqueue time, which the
trace schema documents. This is what keeps the instrumented hot path
inside the <= 5 % overhead budget that CI enforces.

``sync=True`` opts into device-true timing: the engines hand each
dispatch's output to the yielded handle's ``sync()``, which blocks
until the device finishes *inside* the timed region and stamps the
trace record's ``dispatch_s``. The replay harness uses this — replayed
p50/p99 must measure the device, not the enqueue — while the serving
hot path keeps the default fire-and-forget wrapper.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable

from repro.telemetry.device import TickStats, make_chunk_stats_fn
from repro.telemetry.metrics import MetricsRegistry, get_registry
from repro.telemetry.tracer import Tracer


class _TimedHandle:
    """Yielded by ``EngineTelemetry.timed``; carries late record fields.

    ``sync(value)`` is the engines' synchronization hook: a no-op
    pass-through under the default fire-and-forget timing, a
    ``block_until_ready`` (stamping ``dispatch_s``) when the telemetry
    was built with ``sync=True``.
    """

    __slots__ = ("_sync", "_t0", "late")

    def __init__(self, sync_enabled: bool, t0: float):
        self._sync = sync_enabled
        self._t0 = t0
        self.late: dict[str, Any] = {}

    def sync(self, value):
        if self._sync:
            import jax
            jax.block_until_ready(value)
            self.late["dispatch_s"] = time.perf_counter() - self._t0
        return value


class EngineTelemetry:
    """Instrumentation state attached to one serving engine."""

    def __init__(self, *, engine: str, n_of: Callable | None = None,
                 head_of: Callable | None = None,
                 wrap_of: Callable | None = None,
                 metrics: MetricsRegistry | None = None,
                 tracer: Tracer | None = None, sync: bool = False):
        self.engine = engine
        self.metrics = metrics if metrics is not None else get_registry()
        self.tracer = tracer
        self.sync = sync
        # device tick stats need the state accessors; host-only callers
        # (e.g. the registry serving loop) skip them and get timing only
        if n_of is not None:
            self.stats_fn = make_chunk_stats_fn(n_of, head_of, wrap_of)
            self.ticks = TickStats(self.metrics, engine=engine)
        else:
            self.stats_fn = None
            self.ticks = None
        self._seen: set = set()
        # the last chunk executable noted, and its byte counts
        self._noted = None
        self.memory: dict[str, int] = {}

    def first_call(self, op: str, signature: Any) -> bool:
        key = (op, signature)
        if key in self._seen:
            return False
        self._seen.add(key)
        return True

    def record_op(self, op: str, wall_s: float, *, compile_flag: bool,
                  ticks: int | None = None, tenants: int | None = None,
                  capacity: int | None = None,
                  dispatch_s: float | None = None) -> None:
        m = self.metrics
        m.counter("engine_ops_total", op=op, engine=self.engine).inc()
        suffix = "compile_s" if compile_flag else "wall_s"
        m.histogram(f"engine_{op}_{suffix}", engine=self.engine).observe(
            wall_s)
        if self.tracer is not None:
            self.tracer.record(op, wall_s, compile=compile_flag,
                               ticks=ticks, tenants=tenants,
                               capacity=capacity, engine=self.engine,
                               dispatch_s=dispatch_s)

    @contextlib.contextmanager
    def timed(self, op: str, *, signature: Any = None,
              ticks: int | None = None, tenants: int | None = None,
              capacity: int | None = None):
        """Time one engine dispatch (no forced sync unless the engine
        routes its output through the yielded handle's ``sync()`` and
        this telemetry was built with ``sync=True``; see module doc)."""
        compile_flag = self.first_call(op, signature)
        t0 = time.perf_counter()
        handle = _TimedHandle(self.sync, t0)
        yield handle
        wall = time.perf_counter() - t0
        self.record_op(op, wall, compile_flag=compile_flag, ticks=ticks,
                       tenants=tenants, capacity=capacity,
                       dispatch_s=handle.late.get("dispatch_s"))

    def note_program(self, exe, state, *, shards: int = 1) -> None:
        """Note the chunk executable about to run on ``state``: its
        byte counts become the ones ``drain()`` reports (read once per
        executable)."""
        if exe is self._noted:
            return
        import jax

        mem = {"state_bytes": sum(a.size * a.dtype.itemsize
                                  for a in jax.tree_util.tree_leaves(state))
               // shards}
        analysis = exe.memory_analysis()
        if analysis is not None:
            mem["chunk_temp_bytes"] = int(analysis.temp_size_in_bytes)
        self._noted, self.memory = exe, mem

    def drain(self) -> dict[str, int]:
        """Publish accumulated device tick stats (one host sync), with
        the byte counts of the last chunk program dispatched."""
        if self.ticks is None:
            return {}
        return {**self.ticks.drain(), **self.memory}


__all__ = ["EngineTelemetry", "_TimedHandle"]
