"""k-NN CP regression (paper Section 8.1): optimized == standard; interval
sweep == brute-force grid evaluation; ICP regression covers.
"""
import numpy as np
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import regression as reg
from repro.data.synthetic import make_regression


def _data(n, seed):
    X, y = make_regression(n_samples=n, n_features=5, seed=seed)
    return X.astype(np.float32), y.astype(np.float32)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), k=st.integers(1, 7))
def test_regression_optimized_equals_standard(seed, k):
    X, y = _data(50, seed)
    Xt, _ = _data(6, seed + 1)
    # irrational-ish offset: grid points must not coincide with
    # critical points (measure-zero f32 ties; see below)
    tq = jnp.linspace(float(y.min()) - 5, float(y.max()) + 5, 21) \
        + 0.0137039
    p_std = np.asarray(reg.pvalues_standard(X, y, Xt, tq, k=k))
    st_ = reg.fit(X, y, k=k)
    p_opt = np.asarray(reg.pvalues_optimized(st_, Xt, tq, k=k))
    # both paths are exact; the only permitted discrepancy is a query point
    # landing within f32 epsilon of a critical point (measure-zero tie),
    # where the rank count may flip by a unit or two
    d = np.abs(p_std - p_opt)
    n = X.shape[0]
    assert (d > 1e-6).mean() <= 0.05, d.max()
    assert d.max() <= 3.5 / (n + 1), d.max()


def test_interval_matches_grid_bruteforce():
    """Sweep-derived interval == hull of {t on a fine grid : p(t) > eps}."""
    X, y = _data(60, 0)
    Xt, _ = _data(4, 1)
    k, eps = 5, 0.15
    st_ = reg.fit(X, y, k=k)
    iv = np.asarray(reg.intervals_optimized(st_, Xt, k=k, epsilon=eps))
    grid = jnp.linspace(float(y.min()) - 50, float(y.max()) + 50, 4001)
    pg = np.asarray(reg.pvalues_optimized(st_, Xt, grid, k=k))
    g = np.asarray(grid)
    for i in range(Xt.shape[0]):
        ok = g[pg[i] > eps]
        assert ok.size, "grid found empty set but sweep nonempty?"
        lo, hi = ok.min(), ok.max()
        step = g[1] - g[0]
        assert abs(iv[i, 0] - lo) <= 2 * step, (iv[i], lo, hi)
        assert abs(iv[i, 1] - hi) <= 2 * step, (iv[i], lo, hi)


def test_interval_coverage():
    """Intervals cover the true label >= 1 - eps of the time."""
    hits, total = 0, 0
    for seed in range(4):
        X, y = _data(120, seed)
        st_ = reg.fit(X[:90], y[:90], k=7)
        iv = np.asarray(reg.intervals_optimized(
            st_, X[90:120], k=7, epsilon=0.2))
        yt = y[90:120]
        hits += int(np.sum((yt >= iv[:, 0]) & (yt <= iv[:, 1])))
        total += 30
    assert hits / total >= 0.8 - 0.08, hits / total


def test_icp_regression_coverage():
    X, y = _data(200, 5)
    iv = np.asarray(reg.icp_intervals(
        jnp.asarray(X[:160]), jnp.asarray(y[:160]), jnp.asarray(X[160:]),
        k=7, t=100, epsilon=0.2))
    yt = y[160:]
    cov = np.mean((yt >= iv[:, 0]) & (yt <= iv[:, 1]))
    assert cov >= 0.8 - 0.12, cov


def test_pvalue_at_boundary_cases():
    """b_i = -1/k with k = 1 exercises the |b_i| = |b| linear branch.

    The query grid is offset by an irrational-ish epsilon: a grid point
    landing exactly ON a critical point is a measure-zero tie where f32
    rounding legitimately differs between the two (exact) paths."""
    X, y = _data(30, 2)
    Xt, _ = _data(3, 3)
    tq = jnp.linspace(-100.0, 100.0, 41) + 0.0137039
    p_std = np.asarray(reg.pvalues_standard(X, y, Xt, tq, k=1))
    st_ = reg.fit(X, y, k=1)
    p_opt = np.asarray(reg.pvalues_optimized(st_, Xt, tq, k=1))
    d = np.abs(p_std - p_opt)
    assert (d > 1e-6).mean() <= 0.02, d.max()
    assert d.max() <= 2.5 / (X.shape[0] + 1), d.max()


# ---------------------------------------------------------------------------
# the hull sweep sorts its events as values == the lexsort-and-gather form
# ---------------------------------------------------------------------------


def _hull_sweep_gather(lo, hi, empty, thresh):
    """``hull_sweep`` as a permutation from ``lexsort`` and gathers
    through it (the reference form)."""
    INF = jnp.inf
    pts = jnp.concatenate([jnp.where(empty, INF, lo),
                           jnp.where(empty, INF, hi)])
    deltas = jnp.concatenate([jnp.where(empty, 0.0, 1.0),
                              jnp.where(empty, 0.0, -1.0)])
    order = jnp.lexsort((-deltas, pts))
    pts_s = pts[order]
    runs = jnp.cumsum(deltas[order])
    ok = runs > thresh
    any_ok = jnp.any(ok & jnp.isfinite(pts_s))
    lo_out = jnp.min(jnp.where(ok, pts_s, INF))
    nxt = jnp.concatenate([pts_s[1:], jnp.array([INF])])
    hi_out = jnp.max(jnp.where(ok, nxt, -INF))
    return jnp.where(any_ok, lo_out, jnp.nan), jnp.where(any_ok, hi_out,
                                                         jnp.nan)


_HULL_CAP, _HULL_ROWS = 24, 40


def _hull_events(case, rng):
    """(R, cap) interval ends and empty masks; rows mix the case's
    feature with random ones."""
    R, cap = _HULL_ROWS, _HULL_CAP
    a = rng.normal(size=(R, cap)).astype(np.float32)
    b = rng.normal(size=(R, cap)).astype(np.float32)
    if case == "duplicated":  # few distinct endpoints, lo == hi often
        a = np.round(a * 2) / 2
        b = np.where(rng.rand(R, cap) < 0.3, a, np.round(b * 2) / 2)
    elif case == "signed-zeros":  # +0.0 beside -0.0 at both ends
        z = rng.choice(np.float32([-0.0, 0.0]), size=(2, R, cap))
        pick = rng.rand(2, R, cap) < 0.5
        a, b = np.where(pick[0], z[0], a), np.where(pick[1], z[1], b)
    elif case == "inf-ends":
        a = np.where(rng.rand(R, cap) < 0.3, -np.inf, a)
        b = np.where(rng.rand(R, cap) < 0.3, np.inf, b)
        b = np.where(rng.rand(R, cap) < 0.05, -np.inf, b)
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    # some intervals empty as given (lo > hi), some masked empty
    swap = rng.rand(R, cap) < 0.1
    lo, hi = np.where(swap, hi, lo), np.where(swap, lo, hi)
    empty = (lo > hi) | (rng.rand(R, cap) < 0.1)
    empty[:3] = True  # empty rows
    empty[3, 1:] = True  # a single live event pair
    return (jnp.asarray(lo, jnp.float32), jnp.asarray(hi, jnp.float32),
            jnp.asarray(empty))


# thresholds below every count, at the counts' ends and past them
_HULL_THRESH = (-np.inf, -1.0, -0.5, 0.0, 0.5, 1.0, _HULL_CAP - 1.0,
                float(_HULL_CAP), np.inf)


@pytest.mark.parametrize("form", ["single", "vmap"])
@pytest.mark.parametrize("case", ["random", "duplicated", "signed-zeros",
                                  "inf-ends"])
def test_hull_sweep_sorts_like_lexsort_gather(case, form):
    """The hull from one stable sort over the event values is
    bit-identical to the lexsort permutation and its gathers, on every
    row and threshold: empty rows (NaN), ties at an endpoint, +0.0
    beside -0.0, infinite ends."""
    import jax

    rng = np.random.RandomState(["random", "duplicated", "signed-zeros",
                                 "inf-ends"].index(case))
    lo, hi, empty = _hull_events(case, rng)
    for thresh in map(jnp.float32, _HULL_THRESH):
        fns = [jax.jit(f) for f in (reg.hull_sweep, _hull_sweep_gather)]
        if form == "vmap":  # the read path's (rows, 2 cap) batch
            new, ref = (jnp.stack(jax.vmap(f, (0, 0, 0, None))(
                lo, hi, empty, thresh)) for f in fns)
        else:
            new, ref = (jnp.stack([jnp.stack(f(lo[r], hi[r], empty[r],
                                               thresh))
                                   for r in range(8)]) for f in fns)
        assert np.asarray(new).tobytes() == np.asarray(ref).tobytes(), \
            (case, float(thresh))
