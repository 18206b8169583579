"""Streaming full-CP regression (paper Section 8.1, served online).

The batch path in ``repro.core.regression`` fits once and predicts; this
package turns it into a streaming system with the paper's incremental &
decremental updates:

* ``stream``  — capacity-padded ``RegStreamState``: exact ``observe`` /
  ``evict`` that keep the per-point neighbour statistics (``a_prime``,
  ``kth_dist``, ``kth_label``) bit-identical to ``regression.fit`` on the
  live window, by maintaining the live pairwise-distance matrix;
* ``session`` — per-tenant sliding-window session (evict-if-full,
  capacity-doubling growth) + the padded read paths: prediction
  ``intervals`` and p-values, routed through the fused
  ``kernels/interval_sweep`` Pallas kernel on TPU;
* ``engine``  — ``RegressionServingEngine``, ``core.engine.Engine``
  over the session: one jitted step advances every tenant, one dispatch
  serves every tenant's prediction intervals.
"""
from repro.regression.engine import RegressionServingEngine
from repro.regression.stream import RegStreamState

__all__ = ["RegressionServingEngine", "RegStreamState"]
