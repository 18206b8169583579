"""The classification engine: ``core.engine.Engine`` over simplified k-NN.

Batches many per-tenant ``serving.session.Session``s and advances them
with one fixed-shape jitted step per chunk of ticks (``Engine`` holds
the tick, its parameters and its dispatch). This module adds the
classification read: ``predict``, full-CP p-values of every label for
each tenant's queries, in one vmapped dispatch whose score-update and
count run in the fused Pallas kernel (``kernels/cp_update.py``) on TPU.

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
eviction is the exact decremental update of ``serving.session``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.engine import Engine
from repro.serving import session as sess_m


class ServingEngine(Engine):
    """Multi-tenant simplified-k-NN classification engine.

    Takes ``Engine``'s parameters and ``n_labels``, the label alphabet
    of ``predict`` (default 2).
    """

    measure = sess_m.MEASURE
    options = {"n_labels": 2}
    read_op = "predict"

    def _read_programs(self):
        pred = jax.vmap(functools.partial(
            sess_m.predict_pvalues, k=self.k, n_labels=self.n_labels))
        return {"predict": jax.jit(self._sharded(pred, (True, True)))}

    def predict(self, state, X_test) -> jnp.ndarray:
        """Read-only full-CP p-values per session: (S, m, n_labels).

        X_test: (S, m, dim) per-session query batch, or (m, dim) broadcast
        to every session. One vmapped jitted dispatch for all sessions;
        inside it the fused kernel (Pallas on TPU) does the score update
        + count in a single pass.
        """
        return self.read(state, X_test)


__all__ = ["ServingEngine"]
