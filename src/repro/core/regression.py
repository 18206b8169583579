"""Full k-NN CP regression (paper Section 8.1) — standard + optimized paths.

Full CP regression cannot enumerate Y. Instead every score is affine in the
candidate label t = y~:

    alpha_i(t) = |a_i + b_i t|          (training points, b_i in {0, -1/k})
    alpha(t)   = |a  + b  t|,  b = 1    (test point)

where, writing y_(j)(x_i) for the label of x_i's j-th nearest neighbour in
Z \\ {(x_i, y_i)}:

    if x is among x_i's k NNs:  a_i = y_i - (1/k) sum_{j<k} y_(j)(x_i),  b_i = -1/k
    else:                       a_i = y_i - (1/k) sum_{j<=k} y_(j)(x_i), b_i = 0
    test:                       a   = -(1/k) sum_{j<=k} y_(j)(x),        b   = 1

The p-value p(t) = (#{i: alpha_i(t) >= alpha(t)} + 1) / (n+1) is piecewise
constant; each i contributes a *set* S_i = {t : |a_i + b_i t| >= |a + t|}
whose boundary points come from (a_i + b_i t)^2 = (a + t)^2 — at most two
roots. With |b_i| < 1, S_i is a closed interval (possibly empty); with
|b_i| = 1 (k = 1) it is a half-line or all of R. A sorted sweep over the
<= 2n critical points yields exact p-values and prediction intervals in
O(n log n).

Two paths, exactness-tested against each other:

* standard (Papadopoulos et al. 2011): per test point recompute every
  training point's k NNs in the augmented set — O(n^2 + 2n log 2n) each.
* optimized (the paper's contribution): fit() precomputes each training
  point's k-NN label sums, k-th neighbour label and k-th distance — O(n^2)
  once; per test point only an O(n) distance row + O(1)-per-point update is
  needed before the same sweep — O(2n log 2n) each.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

BIG = 1e30
INF = jnp.inf


def _dists(A, B):
    return jnp.sqrt(jnp.maximum(kops.sq_dists(A, B), 0.0))


# ---------------------------------------------------------------------------
# shared: interval geometry + sweep
# ---------------------------------------------------------------------------


def _interval_ge(a_i, b_i, a, eps=1e-12):
    """Interval [lo, hi] of {t : |a_i + b_i t| >= |a + t|} (b = 1).

    g(t) = (a_i + b_i t)^2 - (a + t)^2 = (b_i^2-1) t^2 + 2(a_i b_i - a) t
           + (a_i^2 - a^2) >= 0.
    For |b_i| < 1 the parabola opens down: solution is between the roots
    (empty if no real roots). For |b_i| = 1 it is linear. Returns
    (lo, hi) with +-inf sentinels; empty intervals return (inf, -inf).
    """
    A2 = b_i * b_i - 1.0
    B1 = a_i * b_i - a
    C0 = a_i * a_i - a * a
    disc = B1 * B1 - A2 * C0

    # quadratic branch (A2 < 0): roots (-B1 +- sqrt(disc)) / A2
    sq = jnp.sqrt(jnp.maximum(disc, 0.0))
    r1 = (-B1 + sq) / jnp.where(jnp.abs(A2) < eps, 1.0, A2)
    r2 = (-B1 - sq) / jnp.where(jnp.abs(A2) < eps, 1.0, A2)
    qlo = jnp.minimum(r1, r2)
    qhi = jnp.maximum(r1, r2)
    quad_lo = jnp.where(disc >= 0.0, qlo, INF)
    quad_hi = jnp.where(disc >= 0.0, qhi, -INF)

    # linear branch (A2 ~ 0): 2 B1 t + C0 >= 0
    t0 = -C0 / jnp.where(jnp.abs(B1) < eps, 1.0, 2.0 * B1)
    lin_lo = jnp.where(B1 > eps, t0, jnp.where(B1 < -eps, -INF, jnp.where(C0 >= 0.0, -INF, INF)))
    lin_hi = jnp.where(B1 > eps, INF, jnp.where(B1 < -eps, t0, jnp.where(C0 >= 0.0, INF, -INF)))

    is_quad = jnp.abs(A2) >= eps
    return (jnp.where(is_quad, quad_lo, lin_lo),
            jnp.where(is_quad, quad_hi, lin_hi))


def pvalue_at(a_vec, b_vec, a, t_query):
    """Exact p-values at explicit query labels t_query: (nq,).

    Reference semantics for the sweep; also used to probe arbitrary labels.
    """
    n = a_vec.shape[0]
    ai = jnp.abs(a_vec[None, :] + b_vec[None, :] * t_query[:, None])
    at = jnp.abs(a + t_query)[:, None]
    cnt = jnp.sum(ai >= at, axis=1)
    return (cnt + 1.0) / (n + 1.0)


def hull_sweep(lo, hi, empty, thresh):
    """Convex hull of {t : #{i : t in [lo_i, hi_i]} > thresh} — the sweep.

    Shared by ``prediction_interval`` (exact-shape) and the capacity-padded
    streaming read path (``repro.regression.session``): padded rows enter as
    ``empty`` and contribute neutral (+inf, delta 0) events, which sort after
    every finite event and leave the finite prefix sums — and therefore the
    hull — bit-identical to the unpadded sweep.
    """
    # event sweep over sorted bounds: +1 at lo (inclusive), -1 after hi.
    # Empty intervals (lo > hi) are neutralized (delta 0) so they cannot
    # perturb counts at the infinity event cluster.
    pts = jnp.concatenate([jnp.where(empty, INF, lo),
                           jnp.where(empty, INF, hi)])
    deltas = jnp.concatenate([jnp.where(empty, 0.0, 1.0),
                              jnp.where(empty, 0.0, -1.0)])
    # order ties so that +1 events at a point apply before -1 events leave:
    # sort by (point, -delta) -> stable count at closed endpoints. The
    # values are sorted themselves (no permutation to gather through):
    # the same stable sort ``lexsort`` runs, so the same bits come out.
    pts_s, neg_s = jax.lax.sort((pts, -deltas), num_keys=2)
    runs = jnp.cumsum(-neg_s)
    ok = runs > thresh
    any_ok = jnp.any(ok & jnp.isfinite(pts_s))
    lo_out = jnp.min(jnp.where(ok, pts_s, INF))
    # the run [pts_s[j], pts_s[j+1]) has count runs[j]; interval closes at the
    # next event point after the last ok run
    nxt = jnp.concatenate([pts_s[1:], jnp.array([INF])])
    hi_out = jnp.max(jnp.where(ok, nxt, -INF))
    return jnp.where(any_ok, lo_out, jnp.nan), jnp.where(any_ok, hi_out, jnp.nan)


def prediction_interval(a_vec, b_vec, a, epsilon):
    """Smallest interval containing {t : p(t) > eps} via critical-point sweep.

    Counts N(t) = #{i : t in S_i} change by +1 at lo_i and -1 past hi_i.
    Since the test point's own score always >= itself, p(t) =
    (N(t) + 1)/(n + 1) > eps <=> N(t) > eps (n+1) - 1. The set {p > eps} is
    a finite union of intervals; full CP regression conventionally reports
    its convex hull (Vovk et al. 2005). Runs in O(n log n).
    """
    n = a_vec.shape[0]
    lo, hi = jax.vmap(_interval_ge, in_axes=(0, 0, None))(a_vec, b_vec, a)
    thresh = epsilon * (n + 1.0) - 1.0
    return hull_sweep(lo, hi, lo > hi, thresh)


# ---------------------------------------------------------------------------
# standard path (Papadopoulos et al. 2011): O(n^2) per test point
# ---------------------------------------------------------------------------


def _knn_stats_augmented(X, y, x_t, k):
    """Per-training-point (a_i, b_i) with the test object x_t inserted.

    Recomputes every training point's k NNs in (Z \\ {i}) u {x}. O(n^2).
    """
    n = X.shape[0]
    D = _dists(X, X)
    D = jnp.where(jnp.eye(n, dtype=bool), BIG, D)
    d_t = _dists(x_t[None], X)[0]  # (n,) distances x_i -> x

    Da = jnp.concatenate([D, d_t[:, None]], axis=1)  # (n, n+1); col n == test
    ya = jnp.concatenate([y, jnp.zeros((1,), dtype=y.dtype)])  # test label unused

    _, idx = jax.lax.top_k(-Da, k)  # k nearest per row (distances unused)
    is_test = idx == n
    labels = ya[idx]  # (n, k); bogus where is_test
    test_in = jnp.any(is_test, axis=1)

    sum_no_test = jnp.sum(jnp.where(is_test, 0.0, labels), axis=1)
    a_i = y - sum_no_test / k
    b_i = jnp.where(test_in, -1.0 / k, 0.0)
    return a_i, b_i


@functools.partial(jax.jit, static_argnames=("k",))
def ab_standard(X, y, x_t, *, k):
    """(a_vec, b_vec, a) for one test object — standard path."""
    a_vec, b_vec = _knn_stats_augmented(X, y, x_t, k)
    d_t = _dists(x_t[None], X)[0]
    neg, idx = jax.lax.top_k(-d_t, k)
    a = -jnp.sum(y[idx]) / k
    return a_vec, b_vec, a


@functools.partial(jax.jit, static_argnames=("k",))
def pvalues_standard(X, y, X_test, t_query, *, k):
    """p-values at query labels for each test point: (m, nq)."""

    def per_test(x_t):
        a_vec, b_vec, a = ab_standard(X, y, x_t, k=k)
        return pvalue_at(a_vec, b_vec, a, t_query)

    return jax.lax.map(per_test, X_test)


@functools.partial(jax.jit, static_argnames=("k", "epsilon"))
def intervals_standard(X, y, X_test, *, k, epsilon):
    def per_test(x_t):
        a_vec, b_vec, a = ab_standard(X, y, x_t, k=k)
        return jnp.stack(prediction_interval(a_vec, b_vec, a, epsilon))

    return jax.lax.map(per_test, X_test)


# ---------------------------------------------------------------------------
# optimized path (the paper): O(n^2) fit once, O(n log n) per test point
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclass
class KnnRegState:
    """Provisional per-point neighbour statistics (test object unknown).

    a_prime[i] = y_i - (1/k) sum_{j<=k} y_(j)(x_i)   (b'_i = 0 implicitly)
    kth_dist[i] = Delta_i^k; kth_label[i] = y_(k)(x_i): dropping the k-th
    neighbour when the test object enters gives the updated a_i in O(1).
    """

    X: jnp.ndarray  # (n, p)
    y: jnp.ndarray  # (n,)
    a_prime: jnp.ndarray  # (n,)
    kth_dist: jnp.ndarray  # (n,)
    kth_label: jnp.ndarray  # (n,)

    def tree_flatten(self):
        return ((self.X, self.y, self.a_prime, self.kth_dist,
                 self.kth_label), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@functools.partial(jax.jit, static_argnames=("k",))
def fit(X, y, *, k) -> KnnRegState:
    """O(n^2): pairwise distances + per-point k-NN label statistics."""
    n = X.shape[0]
    D = _dists(X, X)
    D = jnp.where(jnp.eye(n, dtype=bool), BIG, D)
    neg, idx = jax.lax.top_k(-D, k)
    # top_k sorts -D descending, so -neg is ascending (nearest first) and
    # ties break toward the lower index — asserted by
    # tests/test_regression_stream.py::test_topk_negation_is_ascending
    knn_d = -neg
    labels = y[idx]  # (n, k) neighbour labels, nearest first
    a_prime = y - jnp.sum(labels, axis=1) / k
    return KnnRegState(X, y, a_prime, knn_d[:, -1], labels[:, -1])


@functools.partial(jax.jit, static_argnames=("k",))
def ab_optimized(state: KnnRegState, x_t, *, k):
    """(a_vec, b_vec, a) for one test object — O(n) + one local top-k."""
    d_t = _dists(x_t[None], state.X)[0]
    enters = d_t < state.kth_dist  # x displaces the k-th neighbour of x_i
    a_vec = jnp.where(
        enters, state.a_prime + state.kth_label / k, state.a_prime)
    b_vec = jnp.where(enters, -1.0 / k, 0.0)
    neg, idx = jax.lax.top_k(-d_t, k)
    a = -jnp.sum(state.y[idx]) / k
    return a_vec, b_vec, a


@functools.partial(jax.jit, static_argnames=("k",))
def pvalues_optimized(state: KnnRegState, X_test, t_query, *, k):
    def per_test(x_t):
        a_vec, b_vec, a = ab_optimized(state, x_t, k=k)
        return pvalue_at(a_vec, b_vec, a, t_query)

    return jax.lax.map(per_test, X_test)


@functools.partial(jax.jit, static_argnames=("k", "epsilon"))
def intervals_optimized(state: KnnRegState, X_test, *, k, epsilon):
    def per_test(x_t):
        a_vec, b_vec, a = ab_optimized(state, x_t, k=k)
        return jnp.stack(prediction_interval(a_vec, b_vec, a, epsilon))

    return jax.lax.map(per_test, X_test)


# ---------------------------------------------------------------------------
# ICP regression baseline (Papadopoulos et al. 2002)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k", "t", "epsilon"))
def icp_intervals(X, y, X_test, *, k, t, epsilon):
    """k-NN ICP regression: |y - knn_mean| scores on the calibration set.

    Interval = knn_mean(x) +- the ceil((1-eps)(n_cal+1))-th smallest score.
    """
    X_tr, y_tr = X[:t], y[:t]
    X_cal, y_cal = X[t:], y[t:]

    def knn_mean(x):
        d = _dists(x[None], X_tr)[0]
        _, idx = jax.lax.top_k(-d, k)
        return jnp.mean(y_tr[idx])

    mu_cal = jax.lax.map(knn_mean, X_cal)
    scores = jnp.abs(y_cal - mu_cal)
    n_cal = scores.shape[0]
    # quantile index per ICP: smallest q with (#{score <= q}+1)/(n_cal+1) >= 1-eps
    rank = jnp.ceil((1.0 - epsilon) * (n_cal + 1)).astype(jnp.int32) - 1
    rank = jnp.clip(rank, 0, n_cal - 1)
    qhat = jnp.sort(scores)[rank]

    mu_test = jax.lax.map(knn_mean, X_test)
    return jnp.stack([mu_test - qhat, mu_test + qhat], axis=1)


__all__ = [
    "pvalue_at", "hull_sweep", "prediction_interval",
    "ab_standard", "pvalues_standard", "intervals_standard",
    "KnnRegState", "fit", "ab_optimized", "pvalues_optimized",
    "intervals_optimized", "icp_intervals",
]
