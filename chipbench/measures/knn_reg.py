"""k-NN regression CP (paper Section 8.1), served by
``RegressionServingEngine``.

A point's score is |y_i - mean of its k nearest neighbours' labels|,
neighbours ordered by distance with ties toward the earliest arrival.
A tick prices the incoming point's observed label with a smoothed
p-value against the window, evicting the oldest point first once the
window is full; a read (``intervals``) returns the full-CP prediction
interval at miscoverage ``epsilon`` for each query point.

This file holds what belongs to the measure: its traffic, how to build
the engine and issue a read, the bytes a tick must move, and the plain
float64 reference that decides ``correct``. The reference imports
nothing of the system under test.
"""
from __future__ import annotations

import numpy as np

from chipbench import checks

TICK_PROGRAM = "jit_chunk"
#: the engine jits its interval read from a lambda
READ_PROGRAM = "jit__lambda"

#: limits of the compared numbers; PERF.md gives the readings behind each
LIMITS = {
    "tick_p_out": 2e-2,    # served tick p-value outside its reference range
    "dist_err": 1e-4,      # final distance block and k-NN distances, relative
    "label_err": 2e-2,     # final k-NN label means, in label units
    "iv_err": 1e-3,        # served interval endpoints, relative
}

EPSILON = 0.1  # miscoverage of the served intervals
NOISE = 0.1


def point(key, t, s, cfg, mix):
    """One tenant's point at tick ``t``: y = <w_s, x> + noise, where each
    tenant has its own weights w_s; odd tenants' labels shift by
    ``mix["drift"]`` in every other period of ``mix["drift_period"]``."""
    import jax
    import jax.numpy as jnp

    dim = cfg["dim"]
    # the weights' stream: a fold no tick index reaches
    wkey = jax.random.fold_in(key, jnp.uint32(0xFFFFFFFF))
    w = jax.random.normal(jax.random.fold_in(wkey, s), (dim,), jnp.float32)
    k = jax.random.fold_in(jax.random.fold_in(key, t), s)
    kx, kn, kt = jax.random.split(k, 3)
    x = jax.random.normal(kx, (dim,), jnp.float32)
    shifted = (s % 2 == 1) & ((t // mix["drift_period"]) % 2 == 1)
    y = (jnp.sum(w * x) + NOISE * jax.random.normal(kn, (), jnp.float32)
         + jnp.where(shifted, jnp.float32(mix["drift"]), jnp.float32(0)))
    tau = jax.random.uniform(kt, (), jnp.float32)
    return x, y, tau


def queries(rng, cfg, tenant: int, q: int) -> np.ndarray:
    return rng.standard_normal((q, cfg["dim"])).astype(np.float32)


def engine(cfg, *, dtype, shards):
    from repro.regression import RegressionServingEngine
    from repro.telemetry.metrics import MetricsRegistry

    return RegressionServingEngine(
        n_sessions=cfg["tenants"], capacity=cfg["window"], dim=cfg["dim"],
        k=cfg["k"], window=cfg["window"], dtype=dtype, instrument=True,
        metrics=MetricsRegistry(), shards=shards)


def read(eng, state, Xq, mix):
    """One read dispatch over every tenant: (S, m, 2) intervals."""
    return eng.intervals(state, Xq, epsilon=EPSILON)


def state_leaves(state) -> dict:
    return {"X": state.X, "y": state.y, "D": state.D, "nbr_d": state.nbr_d,
            "nbr_y": state.nbr_y, "n": state.n, "head": state.head,
            "wrap": state.wrap}


# -- bytes from shapes ----------------------------------------------------------


def tick_bytes(cfg) -> int:
    """Least HBM bytes of one window-full tick of one tenant (float32).

    As for classification (the (W, W) distance block read by the
    backfill, X and y read, the D row and column written), plus the
    regression state's three parallel k-NN lists (distances, labels,
    arrival ids), each read and written."""
    W, dim, k = cfg["window"], cfg["dim"], cfg["k"]
    return 4 * (W * W + W * dim + W + 2 * 3 * W * k + 2 * W)


def kernel_bytes(cfg) -> int:
    """Least HBM bytes of one ``stream_update`` call for one tenant:
    read X (W, dim), y (W,) and the distance and label lists (W, k);
    write both lists and the distance row (W,)."""
    W, dim, k = cfg["window"], cfg["dim"], cfg["k"]
    return 4 * (W * dim + W + 4 * W * k + W)


# -- the plain reference --------------------------------------------------------


def _neighbours(Dw, y, k):
    """Per row: (a_prime, kth distance, kth label, k nearest distances,
    and the alternatives rounding could pick). Neighbours are ordered by
    distance, ties toward the earliest arrival (lower index). Where a
    row's k-th distance ties the (k+1)-th within the float32 margin, its
    alternative neighbour set swaps those two; where it ties the
    (k-1)-th, its alternative k-th label is the (k-1)-th's. ``alt`` maps
    such a row to its (a_prime, kth label) under the swap."""
    n = len(y)
    rows = np.arange(n)[:, None]
    cand = np.argpartition(Dw, k, axis=1)[:, :k + 1]
    dc = Dw[rows, cand]
    order = np.lexsort((cand, dc), axis=1)
    cand, dc = cand[rows, order], dc[rows, order]
    lab = y[cand]
    kth = dc[:, k - 1]
    a_prime = y - lab[:, :k].sum(1) / k
    margin = 2 * checks.DIST_RTOL * kth
    alt = {}
    for i in np.flatnonzero(dc[:, k] - kth <= margin):
        alt[i] = (a_prime[i] + (lab[i, k - 1] - lab[i, k]) / k, lab[i, k])
    for i in np.flatnonzero(kth - dc[:, k - 2] <= margin):
        alt.setdefault(i, (a_prime[i], lab[i, k - 2]))
    return a_prime, kth, lab[:, k - 1], alt, dc[:, :k]


def _own(d, y, k):
    """The candidate's a = -mean label of its k nearest (earliest on
    ties), and its alternative where its k-th and (k+1)-th distances tie
    (else None)."""
    order = np.lexsort((np.arange(len(d)), d))
    ds, ys = d[order], y[order]
    a = -ys[:k].sum() / k
    if ds[k] - ds[k - 1] <= 2 * checks.DIST_RTOL * ds[k - 1]:
        return a, a + (ys[k - 1] - ys[k]) / k
    return a, None


def _ab(d, a_prime, kth, kth_label, k):
    enters = d < kth
    a_vec = np.where(enters, a_prime + kth_label / k, a_prime)
    b_vec = np.where(enters, -1.0 / k, 0.0)
    return a_vec, b_vec


def _tick_out(Dspan, y, tau, i, W, k, p_served):
    """Distance of a served tick p-value outside its reference range,
    priced against the W - 1 points before stream point ``i``. Rows
    whose neighbour set or entry rests on a distance tie count as
    ambiguous; a tie in the candidate's own neighbours widens the range
    to both of its readings."""
    lo = max(0, i - W + 1)
    Dw = Dspan[lo:i, lo:i].copy()
    np.fill_diagonal(Dw, np.inf)
    yw = y[lo:i]
    a_prime, kth, kth_label, alt, _ = _neighbours(Dw, yw, k)
    d = Dspan[i, lo:i]
    a, a_alt = _own(d, yw, k)
    a_vec, b_vec = _ab(d, a_prime, kth, kth_label, k)
    amb = np.abs(d - kth) <= checks.DIST_RTOL * kth
    amb[list(alt)] = True
    t = float(y[i])
    tol = checks.SCORE_ATOL * (1.0 + float(np.max(np.abs(yw))))
    alphas = np.abs(a_vec + b_vec * t)
    ranges = [checks.p_range(alphas, abs(c + t), float(tau[i]), amb, tol)
              for c in ((a,) if a_alt is None else (a, a_alt))]
    return checks.outside(p_served, min(r[0] for r in ranges),
                          max(r[1] for r in ranges))


def _interval_ge(a_i, b_i, a, eps=1e-12):
    """[lo, hi] of {t : |a_i + b_i t| >= |a + t|}, per row (float64)."""
    A2 = b_i * b_i - 1.0
    B1 = a_i * b_i - a
    C0 = a_i * a_i - a * a
    disc = B1 * B1 - A2 * C0
    sq = np.sqrt(np.maximum(disc, 0.0))
    den = np.where(np.abs(A2) < eps, 1.0, A2)
    r1, r2 = (-B1 + sq) / den, (-B1 - sq) / den
    quad_lo = np.where(disc >= 0.0, np.minimum(r1, r2), np.inf)
    quad_hi = np.where(disc >= 0.0, np.maximum(r1, r2), -np.inf)
    t0 = -C0 / np.where(np.abs(B1) < eps, 1.0, 2.0 * B1)
    lin_lo = np.where(B1 > eps, t0, np.where(
        B1 < -eps, -np.inf, np.where(C0 >= 0.0, -np.inf, np.inf)))
    lin_hi = np.where(B1 > eps, np.inf, np.where(
        B1 < -eps, t0, np.where(C0 >= 0.0, np.inf, -np.inf)))
    quad = np.abs(A2) >= eps
    return np.where(quad, quad_lo, lin_lo), np.where(quad, quad_hi, lin_hi)


def _interval(a_vec, b_vec, a, n, eps):
    """Convex hull of {t : p(t) > eps}: a sweep over the sorted interval
    ends, +1 entering at lo (before any -1 at the same point), -1
    leaving after hi. (nan, nan) where no label qualifies."""
    lo, hi = _interval_ge(a_vec, b_vec, a)
    keep = lo <= hi
    pts = np.concatenate([lo[keep], hi[keep]])
    dl = np.concatenate([np.ones(keep.sum()), -np.ones(keep.sum())])
    order = np.lexsort((-dl, pts))
    pts, runs = pts[order], np.cumsum(dl[order])
    ok = runs > eps * (n + 1.0) - 1.0
    if not np.any(ok & np.isfinite(pts)):
        return np.nan, np.nan
    nxt = np.concatenate([pts[1:], [np.inf]])
    return float(np.min(pts[ok])), float(np.max(nxt[ok]))


def _variants(d, a_prime, kth, kth_label, alt, a, a_alt, k):
    """(a_vec, b_vec, a) of the reference, then of each single reading
    that float32 rounding could pick instead: one tied row's neighbour
    set, one row's entry at a tied distance, or the candidate's own
    tied neighbour set."""
    a_vec, b_vec = _ab(d, a_prime, kth, kth_label, k)
    out = [(a_vec, b_vec, a)]
    if a_alt is not None:
        out.append((a_vec, b_vec, a_alt))
    flips = set(np.flatnonzero(np.abs(d - kth)
                               <= checks.DIST_RTOL * kth).tolist())
    for i in set(alt) | flips:
        ap, kl = alt.get(i, (a_prime[i], kth_label[i]))
        enters = (d[i] < kth[i]) != (i in flips)
        av, bv = a_vec.copy(), b_vec.copy()
        av[i] = ap + kl / k if enters else ap
        bv[i] = -1.0 / k if enters else 0.0
        out.append((av, bv, a))
    return out


def _read_err(Dspan, X, y, i_end, W, k, xq, ans):
    """Largest gap of a read's interval ends outside the span of the
    reference's readings (``_variants``), relative to 1 + |end|; the
    window is the W stream points before ``i_end``."""
    lo = i_end - W
    Dw = Dspan[lo:i_end, lo:i_end].copy()
    np.fill_diagonal(Dw, np.inf)
    yw = y[lo:i_end]
    a_prime, kth, kth_label, alt, _ = _neighbours(Dw, yw, k)
    worst = 0.0
    for d, iv in zip(checks.dists(xq, X[lo:i_end]), ans):
        a, a_alt = _own(d, yw, k)
        refs = np.array([_interval(av, bv, c, W, EPSILON) for av, bv, c in
                         _variants(d, a_prime, kth, kth_label, alt, a, a_alt,
                                   k)])
        iv = np.asarray(iv, np.float64)
        same = np.all(np.isfinite(refs) == np.isfinite(iv), axis=1)
        if not same.any():
            worst = checks.MISMATCH
            continue
        if not np.all(np.isfinite(iv)):
            continue
        refs = refs[same]
        lo_r, hi_r = refs.min(0), refs.max(0)
        gap = np.maximum(0.0, np.maximum(lo_r - iv, iv - hi_r))
        worst = max(worst, float(np.max(gap / (1.0 + np.abs(refs[0])))))
    return worst


def check(cfg, mix, served) -> dict:
    """The compared numbers of one run (see ``LIMITS``); ``served`` as
    in ``knn_class.check``."""
    W, k = cfg["window"], cfg["k"]
    out = dict.fromkeys(LIMITS, 0.0)
    for ten in served["tenants"]:
        X, y, tau, t0 = ten["X"], ten["y"], ten["tau"], ten["t0"]
        y = np.asarray(y, np.float64)
        Dspan = checks.dists(X, X)
        for t, p in ten["ticks"]:
            out["tick_p_out"] = max(out["tick_p_out"], _tick_out(
                Dspan, y, tau, t - t0, W, k, p))
        for xq, t_done, ans in ten["reads"]:
            out["iv_err"] = max(out["iv_err"], _read_err(
                Dspan, X, y, t_done - t0, W, k, xq, ans))
        st = ten["state"]
        n = int(st["n"])
        if n != W:
            out["dist_err"] = out["label_err"] = checks.MISMATCH
            continue
        slots = checks.arrival_slots(int(st["head"]), n, int(st["wrap"]))
        end = ten["t_end"] - t0
        Dref = Dspan[end - W:end, end - W:end].copy()
        np.fill_diagonal(Dref, np.inf)
        yw = y[end - W:end]
        a_ref, _, _, alt, dk = _neighbours(Dref, yw, k)
        off = ~np.eye(W, dtype=bool)
        D = np.asarray(st["D"], np.float64)[np.ix_(slots, slots)]
        nbr_d = np.asarray(st["nbr_d"], np.float64)[slots]
        nbr_y = np.asarray(st["nbr_y"], np.float64)[slots]
        ys = np.asarray(st["y"], np.float64)[slots]
        out["dist_err"] = max(out["dist_err"],
                              checks.rel_err(D[off], Dref[off]),
                              checks.rel_err(nbr_d, dk))
        a_srv = ys - nbr_y.sum(1) / k
        scale = 1.0 + float(np.max(np.abs(yw)))
        tie = np.zeros(W, bool)
        tie[list(alt)] = True
        gap = np.abs(a_srv - a_ref)[~tie] / scale
        out["label_err"] = max(out["label_err"],
                               float(gap.max()) if gap.size else 0.0)
    return out
