"""Multi-tenant online conformal-prediction serving.

The paper's incremental&decremental updates make exact full-CP cheap
enough to serve online; this package turns the repo's single-shot CP
primitives into a serving system:

* ``session``  — per-tenant capacity-padded CP state with exact
  decremental eviction (sliding windows) and capacity-doubling growth;
* ``engine``   — ``ServingEngine``, ``core.engine.Engine`` over the
  session: one jitted step advances every tenant, Pallas-fused reads;
* ``registry`` — declarative measure registry (k-NN / KDE / LS-SVM and
  user plug-ins) behind one fit/observe/evict/pvalues surface;
* ``snapshot`` — crash-safe tenant-state snapshot/restore, plus the
  async double-buffered sharded saver;
* ``fleet``    — tenant lifecycle (admit/retire/migrate) over
  capacity-bucketed engine pools;
* ``families`` — each measure family's ``Measure`` and engine, by name.
"""
from repro.serving.engine import ServingEngine
from repro.serving.fleet import Fleet
from repro.serving.registry import ConformalPredictor, MeasureSpec
from repro.serving.snapshot import AsyncShardedSaver, SessionStore

__all__ = ["ServingEngine", "ConformalPredictor", "MeasureSpec",
           "SessionStore", "AsyncShardedSaver", "Fleet"]
