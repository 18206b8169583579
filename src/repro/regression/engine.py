"""The regression engine: ``core.engine.Engine`` over k-NN regression CP.

Batches many per-tenant ``RegStreamState``s and advances them with one
fixed-shape jitted step per chunk of ticks (``Engine`` holds the tick,
its parameters and its dispatch). This module adds the regression
reads: ``intervals``, each tenant's prediction intervals at a level, and
``pvalues``, its p-values at query labels, each one dispatch for every
tenant.

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
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.engine import Engine
from repro.regression import session as sess_m


class RegressionServingEngine(Engine):
    """Multi-tenant k-NN regression CP engine (paper Section 8.1
    measure); ``Engine``'s parameters."""

    measure = sess_m.MEASURE
    read_op = "intervals"

    def _read_programs(self):
        k = self.k
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
        return {
            "pvalues": jax.jit(self._sharded(pvals, (True, True, False))),
            "intervals": jax.jit(self._sharded(ivals, (True, True, False))),
        }

    def _read_args(self, epsilon):
        return (jnp.asarray(epsilon, self.dtype),)

    def intervals(self, state, X_test, epsilon: float) -> jnp.ndarray:
        """Prediction intervals per session: (S, m, 2), one dispatch.

        X_test: (S, m, dim) per-session query batch, or (m, dim) broadcast
        to every session; ``epsilon`` is traced (no recompile per level).
        Inside the single jitted call the fused kernel (Pallas on TPU)
        computes distances + score updates + critical points; the hull
        sweep finishes per test point.
        """
        return self.read(state, X_test, epsilon)

    def pvalues(self, state, X_test, t_query) -> jnp.ndarray:
        """P-values at query labels per session: (S, m, nq), one dispatch."""
        return self._dispatch_read("pvalues", state,
                                   self._per_tenant(X_test), t_query)


__all__ = ["RegressionServingEngine"]
