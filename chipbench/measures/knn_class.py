"""Simplified k-NN classification CP, served by ``ServingEngine``.

A tenant's score of a point is the sum of its k smallest distances to
the other points of its label in the window (paper Section 9, the
simplified k-NN measure). A tick prices the incoming labelled point
with a smoothed p-value against the window, evicting the oldest point
first once the window is full; a read (``predict``) returns one
p-value per label for each query point.

This file holds what belongs to the measure: its traffic, how to build
the engine and issue a read, the bytes a tick must move, and the plain
float64 reference that decides ``correct``. The reference imports
nothing of the system under test.
"""
from __future__ import annotations

import numpy as np

from chipbench import checks

#: program names in the profiler trace (``jax.jit`` names the module
#: after the function it wraps)
TICK_PROGRAM = "jit_chunk"
READ_PROGRAM = "jit_predict_pvalues"

#: limits of the compared numbers; PERF.md gives the readings behind each
LIMITS = {
    "tick_p_out": 5e-3,   # served tick p-value outside its reference range
    "dist_err": 1e-4,     # final distance block, relative
    "knn_err": 1e-4,      # final k-best same-label lists, relative
    "read_p_out": 5e-3,   # served predict p-value outside its range
}

# Per-tenant centre offsets, bounded so that every tenant's points stay
# within a few units of the origin at any tenant count.
CENTRE_STEP, CENTRE_GROUPS = 0.1, 32


def point(key, t, s, cfg, mix):
    """One tenant's labelled point at tick ``t``: (x (dim,), y, tau).
    Odd tenants drift by ``mix["drift"]`` in every other period of
    ``mix["drift_period"]`` ticks."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(jax.random.fold_in(key, t), s)
    kx, ky, kt = jax.random.split(k, 3)
    y = jax.random.bernoulli(ky, 0.5).astype(jnp.int32)
    x = jax.random.normal(kx, (cfg["dim"],), jnp.float32)
    shifted = (s % 2 == 1) & ((t // mix["drift_period"]) % 2 == 1)
    x = (x + CENTRE_STEP * (s % CENTRE_GROUPS).astype(jnp.float32)
         + y.astype(jnp.float32)
         + jnp.where(shifted, jnp.float32(mix["drift"]), jnp.float32(0)))
    tau = jax.random.uniform(kt, (), jnp.float32)
    return x, y, tau


def queries(rng, cfg, tenant: int, q: int) -> np.ndarray:
    """A read's ``q`` query points for ``tenant`` (label unknown)."""
    lab = rng.integers(0, cfg["labels"], size=(q, 1))
    x = rng.standard_normal((q, cfg["dim"]))
    return (x + CENTRE_STEP * (tenant % CENTRE_GROUPS) + lab).astype(
        np.float32)


def engine(cfg, *, dtype, shards):
    from repro.serving import ServingEngine
    from repro.telemetry.metrics import MetricsRegistry

    return ServingEngine(
        n_sessions=cfg["tenants"], capacity=cfg["window"], dim=cfg["dim"],
        k=cfg["k"], n_labels=cfg["labels"], window=cfg["window"],
        dtype=dtype, instrument=True, metrics=MetricsRegistry(),
        shards=shards)


def read(eng, state, Xq, mix):
    """One read dispatch over every tenant: (S, m, labels) p-values."""
    return eng.predict(state, Xq)


def state_leaves(state) -> dict:
    """The leaves the reference checks, by name (stacked over tenants)."""
    return {"X": state.knn.X, "y": state.knn.y, "best": state.knn.best,
            "n": state.knn.n, "D": state.D, "head": state.head,
            "wrap": state.wrap}


# -- bytes from shapes ----------------------------------------------------------


def tick_bytes(cfg) -> int:
    """Least HBM bytes of one window-full tick of one tenant (float32).

    The backfill reduction reads the whole (W, W) distance block; the
    kernel reads the window's features X (W, dim) and labels y (W,) and
    reads and writes the k-best lists (W, k); the new distance row is
    written once as a row and once as a column of D (2 W)."""
    W, dim, k = cfg["window"], cfg["dim"], cfg["k"]
    return 4 * (W * W + W * dim + W + 2 * W * k + 2 * W)


def kernel_bytes(cfg) -> int:
    """Least HBM bytes of one ``stream_update`` call for one tenant:
    read X (W, dim), y (W,) and the lists (W, k); write the lists and
    the distance row (W,)."""
    W, dim, k = cfg["window"], cfg["dim"], cfg["k"]
    return 4 * (W * dim + W + 2 * W * k + W)


# -- the plain reference --------------------------------------------------------


def _best_same(D, y, k):
    """Each row's k smallest same-label distances, ascending (inf pads
    a label rarer than k + 1). ``D`` holds inf on its diagonal."""
    out = np.full((len(y), k), np.inf)
    for lab in np.unique(y):
        idx = np.flatnonzero(y == lab)
        kk = min(k, len(idx) - 1)
        if kk <= 0:
            continue
        sub = D[np.ix_(idx, idx)]
        out[idx, :kk] = np.sort(np.partition(sub, kk - 1, axis=1)[:, :kk],
                                axis=1)
    return out


def _window(Dspan, lo, hi):
    Dw = Dspan[lo:hi, lo:hi].copy()
    np.fill_diagonal(Dw, np.inf)
    return Dw


def _tick_out(Dspan, y, tau, i, W, k, p_served):
    """Reference range of the tick p-value of stream point ``i``, priced
    against the W - 1 points before it, and the served value's distance
    outside it."""
    lo = max(0, i - W + 1)
    Dw = _window(Dspan, lo, i)
    yw = y[lo:i]
    best = _best_same(Dw, yw, k)
    d = Dspan[i, lo:i]
    same = yw == y[i]
    alpha = float(np.sum(np.sort(d[same])[:k]))
    alphas = np.where(same, best[:, :-1].sum(1) + np.minimum(d, best[:, -1]),
                      best.sum(1))
    lo_p, hi_p = checks.p_range(alphas, alpha, float(tau[i]),
                                np.zeros(len(yw), bool),
                                checks.SCORE_RTOL * abs(alpha))
    return checks.outside(p_served, lo_p, hi_p)


def _read_out(Dspan, Xs, y, i_end, W, k, xq, labels, ans):
    """Largest distance of a read's p-values outside their ranges: the
    window is the W stream points before ``i_end``."""
    lo = i_end - W
    Dw = _window(Dspan, lo, i_end)
    yw = y[lo:i_end]
    best = _best_same(Dw, yw, k)
    base = best[:, :-1].sum(1)
    worst = 0.0
    d_all = checks.dists(xq, Xs[lo:i_end])
    for j in range(len(xq)):
        d = d_all[j]
        for lab in range(labels):
            same = yw == lab
            alpha = float(np.sum(np.sort(d[same])[:k]))
            alphas = np.where(same, base + np.minimum(d, best[:, -1]),
                              best.sum(1))
            lo_p, hi_p = checks.count_range(
                alphas, alpha, checks.SCORE_RTOL * abs(alpha), W)
            worst = max(worst, checks.outside(float(ans[j, lab]), lo_p,
                                              hi_p))
    return worst


def check(cfg, mix, served) -> dict:
    """The compared numbers of one run (see ``LIMITS``).

    ``served`` holds, per checked tenant, its stream (``X``, ``y``,
    ``tau`` over ticks ``t0`` onward), the sampled ticks with their
    served p-values, its reads, and its final state's leaves."""
    W, k = cfg["window"], cfg["k"]
    out = dict.fromkeys(LIMITS, 0.0)
    for ten in served["tenants"]:
        X, y, tau, t0 = ten["X"], ten["y"], ten["tau"], ten["t0"]
        Dspan = checks.dists(X, X)
        for t, p in ten["ticks"]:
            out["tick_p_out"] = max(out["tick_p_out"], _tick_out(
                Dspan, y, tau, t - t0, W, k, p))
        for xq, t_done, ans in ten["reads"]:
            out["read_p_out"] = max(out["read_p_out"], _read_out(
                Dspan, X, y, t_done - t0, W, k, xq, cfg["labels"], ans))
        st = ten["state"]
        n = int(st["n"])
        if n != W:
            out["dist_err"] = out["knn_err"] = checks.MISMATCH
            continue
        slots = checks.arrival_slots(int(st["head"]), n, int(st["wrap"]))
        end = ten["t_end"] - t0
        Dref = _window(Dspan, end - W, end)
        off = ~np.eye(W, dtype=bool)
        D = np.asarray(st["D"], np.float64)[np.ix_(slots, slots)]
        best = np.asarray(st["best"], np.float64)[slots]
        bref = _best_same(Dref, y[end - W:end], k)
        out["dist_err"] = max(out["dist_err"],
                              checks.rel_err(D[off], Dref[off]))
        out["knn_err"] = max(out["knn_err"], checks.rel_err(best, bref))
    return out
