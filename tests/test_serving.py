"""repro.serving: exactness + engine + snapshot + registry.

The acceptance-critical properties:
* decremental eviction (+ incremental re-add) is BIT-exact against
  fit-from-scratch on the same window;
* N vmapped engine sessions produce BIT-identical p-values to N
  sequential ``core.online.run_stream`` calls.
"""
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import online
from repro.core.measures import kde as kde_m
from repro.core.measures import knn as knn_m
from repro.core.measures import lssvm as lssvm_m
from repro.data.synthetic import make_classification
from repro.serving import (ConformalPredictor, ServingEngine, SessionStore,
                           registry)
from repro.serving import session as sm

K, DIM = 5, 6


def _stream(T, seed, dim=DIM):
    X, y = make_classification(n_samples=T, n_features=dim, seed=seed)
    taus = jax.random.uniform(jax.random.PRNGKey(seed), (T,),
                              dtype=jnp.float32)
    return jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.int32), taus


def _fill(sess, X, y, taus, lo=0, hi=None):
    ps = []
    for t in range(lo, hi if hi is not None else X.shape[0]):
        sess, p = sm.observe(sess, X[t], y[t], taus[t], k=K)
        ps.append(float(p))
    return sess, ps


# ---------------------------------------------------------------------------
# session exactness
# ---------------------------------------------------------------------------


def test_session_observe_matches_run_stream_bitwise():
    T, cap = 40, 64
    X, y, taus = _stream(T, seed=0)
    want, _ = online.run_stream(X, y, k=K, key=jax.random.PRNGKey(0),
                                capacity=cap)
    _, got = _fill(sm.init(cap, DIM, K), X, y, taus)
    np.testing.assert_array_equal(np.asarray(want),
                                  np.array(got, np.float32))


def _assert_linear_equal(a, b):
    """Leaf-for-leaf bitwise equality after ring normalization."""
    for la, lb in zip(jax.tree_util.tree_leaves(sm.to_linear(a)),
                      jax.tree_util.tree_leaves(sm.to_linear(b))):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@pytest.mark.parametrize("seed,evictions", [(1, 1), (2, 9), (3, 17)])
def test_evict_plus_readd_equals_fit_from_scratch(seed, evictions):
    """Eviction then incremental re-add == fresh fit on the same window
    (leaf-for-leaf through the ring normalization, D and aid included)."""
    T, cap = 36, 64
    X, y, taus = _stream(T, seed=seed)
    sess, _ = _fill(sm.init(cap, DIM, K), X, y, taus, hi=T - 5)
    for _ in range(evictions):
        sess = sm.evict_oldest(sess, k=K)
    sess, _ = _fill(sess, X, y, taus, lo=T - 5)  # incremental re-add

    scratch, _ = _fill(sm.init(cap, DIM, K), X, y, taus, lo=evictions)
    n = int(sess.knn.n)
    assert n == T - evictions == int(scratch.knn.n)
    _assert_linear_equal(sess, scratch)
    # and the *next* smoothed p-value agrees bitwise
    xq, yq, tq = X[0], y[0], jnp.float32(0.37)
    _, pa = sm.observe(sess, xq, yq, tq, k=K)
    _, pb = sm.observe(scratch, xq, yq, tq, k=K)
    assert float(pa) == float(pb)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_evict_oldest_tie_heavy_bit_exact(seed):
    """Binary-grid features force many exactly-equal distances: the
    O(k)-surgery evict_oldest must match fit-from-scratch bitwise."""
    T = 22
    rng = np.random.RandomState(seed)
    X = jnp.asarray(rng.randint(0, 2, size=(T, DIM)), jnp.float32)
    y = jnp.asarray(rng.randint(0, 2, size=T), jnp.int32)
    taus = jnp.full((T,), 0.5, jnp.float32)
    sess, _ = _fill(sm.init(32, DIM, K), X, y, taus)
    for e in range(T - K - 1):
        sess = sm.evict_oldest(sess, k=K)
        scratch, _ = _fill(sm.init(32, DIM, K), X, y, taus, lo=e + 1)
        _assert_linear_equal(sess, scratch)


def test_sliding_window_equals_refit_each_window():
    T, cap, w = 40, 64, 12
    X, y, taus = _stream(T, seed=4)
    sl = sm.init(cap, DIM, K)
    for t in range(T):
        sl, _ = sm.observe_sliding(sl, X[t], y[t], taus[t], jnp.int32(w),
                                   k=K)
    ref, _ = _fill(sm.init(cap, DIM, K), X, y, taus, lo=T - w)
    assert int(sl.knn.n) == w
    assert int(sl.head) == T - w  # eviction = head advance, no shift
    _assert_linear_equal(sl, ref)


def test_grow_preserves_state_bitwise():
    T, cap = 20, 32
    X, y, taus = _stream(T, seed=5)
    sess, _ = _fill(sm.init(cap, DIM, K), X, y, taus)
    g = sm.grow(sess)
    assert g.capacity == 2 * cap and int(g.knn.n) == T
    _, pa = sm.observe(g, X[0], y[0], jnp.float32(0.5), k=K)
    _, pb = sm.observe(sess, X[0], y[0], jnp.float32(0.5), k=K)
    assert float(pa) == float(pb)


def test_predict_pvalues_matches_optimized_knn():
    T, cap = 40, 64
    X, y, taus = _stream(T, seed=6)
    sess, _ = _fill(sm.init(cap, DIM, K), X, y, taus)
    Xt, _, _ = _stream(8, seed=60)
    got = sm.predict_pvalues(sess, Xt, k=K, n_labels=2)
    st = knn_m.fit(X, y, k=K)
    want = knn_m.pvalues_optimized(st, Xt, k=K, simplified=True, n_labels=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("rare_count", [K - 2, K, K + 1])
def test_predict_pvalues_exact_with_rare_label(rare_count):
    """Labels rarer than (or equal to) k: the BIG-padded neighbour lists
    must not go through the kernel's cancellation-prone update."""
    T, cap = 24, 32
    X, _, taus = _stream(T, seed=11)
    y = jnp.asarray([1 if t < rare_count else 0 for t in range(T)],
                    jnp.int32)
    sess = sm.init(cap, DIM, K)
    for t in range(T):
        sess, _ = sm.observe(sess, X[t], y[t], taus[t], k=K)
    Xt, _, _ = _stream(6, seed=12)
    got = sm.predict_pvalues(sess, Xt, k=K, n_labels=2)
    st = knn_m.fit(X, y, k=K)
    want = knn_m.pvalues_optimized(st, Xt, k=K, simplified=True, n_labels=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


def test_engine_vmapped_equals_sequential_run_stream_bitwise():
    """N concurrent engine sessions == N independent run_stream calls."""
    S, T = 4, 30
    streams = [_stream(T, seed=100 + s) for s in range(S)]
    eng = ServingEngine(n_sessions=S, capacity=8, dim=DIM, k=K, n_labels=2)
    state = eng.init_state()  # grow mode: auto-doubles 8 -> 32
    got = np.zeros((S, T), np.float32)
    for t in range(T):
        state, p = eng.observe(
            state,
            jnp.stack([st[0][t] for st in streams]),
            jnp.stack([st[1][t] for st in streams]),
            jnp.stack([st[2][t] for st in streams]))
        got[:, t] = np.asarray(p)
    assert state.capacity == 32  # capacity-doubling happened
    for s, (X, y, _) in enumerate(streams):
        want, _ = online.run_stream(X, y, k=K,
                                    key=jax.random.PRNGKey(100 + s),
                                    capacity=T)
        np.testing.assert_array_equal(np.asarray(want), got[s])


def test_engine_sliding_equals_sequential_sessions_bitwise():
    S, T, cap, w = 3, 25, 32, 10
    streams = [_stream(T, seed=200 + s) for s in range(S)]
    eng = ServingEngine(n_sessions=S, capacity=cap, dim=DIM, k=K,
                        n_labels=2, window=w)
    state = eng.init_state()
    got = np.zeros((S, T), np.float32)
    for t in range(T):
        state, p = eng.observe(
            state,
            jnp.stack([st[0][t] for st in streams]),
            jnp.stack([st[1][t] for st in streams]),
            jnp.stack([st[2][t] for st in streams]))
        got[:, t] = np.asarray(p)
    for s, (X, y, taus) in enumerate(streams):
        sl = sm.init(cap, DIM, K)
        for t in range(T):
            sl, p = sm.observe_sliding(sl, X[t], y[t], taus[t],
                                       jnp.int32(w), k=K)
            assert float(p) == got[s, t]


def test_engine_active_masking_freezes_inactive_slots():
    S = 4
    streams = [_stream(3, seed=300 + s) for s in range(S)]
    eng = ServingEngine(n_sessions=S, capacity=16, dim=DIM, k=K, n_labels=2)
    state = eng.init_state()
    active = jnp.array([True, False, True, False])
    state, p = eng.observe(
        state,
        jnp.stack([st[0][0] for st in streams]),
        jnp.stack([st[1][0] for st in streams]),
        jnp.stack([st[2][0] for st in streams]),
        active=active)
    p = np.asarray(p)
    assert not np.isnan(p[0]) and np.isnan(p[1])
    assert list(np.asarray(state.knn.n)) == [1, 0, 1, 0]


def test_engine_predict_shapes_and_window_rejection():
    eng = ServingEngine(n_sessions=2, capacity=16, dim=DIM, k=K, n_labels=3,
                        window=16)
    state = eng.init_state()
    X, y, taus = _stream(8, seed=7)
    for t in range(8):
        state, _ = eng.observe(state, jnp.stack([X[t], X[t]]),
                               jnp.stack([y[t], y[t]]),
                               jnp.stack([taus[t], taus[t]]))
    p = eng.predict(state, X[:5])  # (m, dim) broadcast across sessions
    assert p.shape == (2, 5, 3)
    np.testing.assert_array_equal(np.asarray(p[0]), np.asarray(p[1]))
    with pytest.raises(ValueError):
        ServingEngine(n_sessions=1, capacity=8, dim=DIM, k=K, window=9)


# ---------------------------------------------------------------------------
# snapshot
# ---------------------------------------------------------------------------


def test_snapshot_roundtrip_and_engine_restore():
    S, T = 3, 12
    streams = [_stream(T, seed=400 + s) for s in range(S)]
    eng = ServingEngine(n_sessions=S, capacity=16, dim=DIM, k=K,
                        n_labels=2, window=8)
    state = eng.init_state()
    for t in range(T):
        state, _ = eng.observe(
            state,
            jnp.stack([st[0][t] for st in streams]),
            jnp.stack([st[1][t] for st in streams]),
            jnp.stack([st[2][t] for st in streams]))
    with tempfile.TemporaryDirectory() as d:
        SessionStore(d).save(T, state, meta=eng.meta(), blocking=True)
        eng2, state2, step = SessionStore(d).restore_engine()
        assert step == T
        assert (eng2.k, eng2.window, eng2.capacity) == (K, 8, 16)
        for a, b in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves(state2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # restored engine continues bit-identically
        x = jnp.stack([st[0][0] for st in streams])
        y = jnp.stack([st[1][0] for st in streams])
        tau = jnp.stack([st[2][0] for st in streams])
        _, pa = eng.observe(state, x, y, tau)
        _, pb = eng2.observe(state2, x, y, tau)
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


def test_restore_engine_without_meta_raises_clearly():
    eng = ServingEngine(n_sessions=2, capacity=8, dim=DIM, k=K)
    with tempfile.TemporaryDirectory() as d:
        SessionStore(d).save(1, eng.init_state(), blocking=True)  # no meta
        store = SessionStore(d)
        state, step, meta = store.restore()  # plain restore still works
        assert step == 1 and meta == {}
        with pytest.raises(ValueError, match="no engine meta"):
            store.restore_engine()


# ---------------------------------------------------------------------------
# measure registry + decremental measures
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("i", [0, 7, 34, -1])
def test_knn_decremental_remove_exact(i):
    X, y = make_classification(n_samples=35, n_features=DIM, n_classes=3,
                               seed=2)
    X, y = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.int32)
    got = knn_m.decremental_remove(knn_m.fit(X, y, k=K), i, k=K)
    want = knn_m.fit(jnp.delete(X, i, axis=0), jnp.delete(y, i, axis=0),
                     k=K)
    np.testing.assert_array_equal(np.asarray(got.best_same),
                                  np.asarray(want.best_same))
    np.testing.assert_array_equal(np.asarray(got.best_diff),
                                  np.asarray(want.best_diff))


def test_kde_decremental_remove_matches_refit():
    X, y = make_classification(n_samples=30, n_features=DIM, n_classes=3,
                               seed=3)
    X, y = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.int32)
    st = kde_m.fit(X, y, h=1.1, n_labels=3)
    got = kde_m.decremental_remove(st, 3, h=1.1)
    want = kde_m.fit(jnp.delete(X, 3, axis=0), jnp.delete(y, 3, axis=0),
                     h=1.1, n_labels=3)
    np.testing.assert_allclose(np.asarray(got.prelim),
                               np.asarray(want.prelim), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got.class_counts),
                                  np.asarray(want.class_counts))


def test_kde_incremental_add_matches_refit():
    X, y = make_classification(n_samples=25, n_features=DIM, seed=8)
    X, y = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.int32)
    st = kde_m.fit(X[:24], y[:24], h=0.9, n_labels=2)
    got = kde_m.incremental_add(st, X[24], y[24], h=0.9)
    want = kde_m.fit(X, y, h=0.9, n_labels=2)
    np.testing.assert_allclose(np.asarray(got.prelim),
                               np.asarray(want.prelim), atol=1e-5)


def test_lssvm_decremental_remove_matches_refit_and_roundtrip():
    X, y = make_classification(n_samples=30, n_features=DIM, seed=9)
    X = jnp.asarray(X, jnp.float32)
    Y = jnp.asarray(2.0 * y - 1.0, jnp.float32)
    st = lssvm_m.fit(X, Y, 1.0)
    got = lssvm_m.decremental_remove(st, 4)
    want = lssvm_m.fit(jnp.delete(X, 4, axis=0), jnp.delete(Y, 4, axis=0),
                       1.0)
    np.testing.assert_allclose(np.asarray(got.w), np.asarray(want.w),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(got.C), np.asarray(want.C),
                               atol=1e-4)
    up = lssvm_m.incremental_add(st, X[0] * 0.5 + 1.0, jnp.float32(1.0))
    back = lssvm_m.decremental_remove(up, 30)
    np.testing.assert_allclose(np.asarray(back.w), np.asarray(st.w),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(back.C), np.asarray(st.C),
                               atol=1e-4)


@pytest.mark.parametrize("measure", ["knn", "simplified_knn", "kde",
                                     "lssvm"])
def test_conformal_predictor_fit_observe_evict_pvalues(measure):
    X, y = make_classification(n_samples=40, n_features=DIM, seed=1)
    cp = ConformalPredictor(measure).fit(X[:30], y[:30])
    cp.observe(jnp.asarray(X[30], jnp.float32), int(y[30]))
    assert cp.n == 31
    cp.evict(0)
    assert cp.n == 30
    p = cp.pvalues(jnp.asarray(X[31:35], jnp.float32))
    assert p.shape == (4, 2)
    assert float(jnp.min(p)) > 0.0 and float(jnp.max(p)) <= 1.0
    sets = cp.predict_set(jnp.asarray(X[31:35], jnp.float32), eps=0.05)
    assert sets.dtype == bool


def test_lssvm_measure_rejects_multiclass():
    X, y = make_classification(n_samples=20, n_features=DIM, n_classes=3,
                               seed=4)
    with pytest.raises(ValueError, match="binary"):
        ConformalPredictor("lssvm", n_labels=3).fit(X, y)
    with pytest.raises(ValueError, match="labels in \\{0, 1\\}"):
        ConformalPredictor("lssvm").fit(X, y)  # labels {0,1,2}, n_labels=2
    cp = ConformalPredictor("lssvm").fit(X[:10], np.asarray(y[:10]) % 2)
    with pytest.raises(ValueError, match="labels in \\{0, 1\\}"):
        cp.observe(jnp.asarray(X[10], jnp.float32), 2)


def test_engine_grow_keeps_meta_capacity_in_sync():
    eng = ServingEngine(n_sessions=2, capacity=8, dim=DIM, k=K, n_labels=2)
    state = eng.init_state()
    X, y, taus = _stream(20, seed=13)
    for t in range(20):  # forces auto-growth past capacity 8
        state, _ = eng.observe(state, jnp.stack([X[t], X[t]]),
                               jnp.stack([y[t], y[t]]),
                               jnp.stack([taus[t], taus[t]]))
    assert state.capacity > 8
    assert eng.meta()["capacity"] == state.capacity
    assert eng.init_state().capacity == state.capacity
    with pytest.raises(ValueError, match="capacity"):
        ServingEngine(n_sessions=1, capacity=K - 1, dim=DIM, k=K)


# ---------------------------------------------------------------------------
# observe_many chunking + buffer donation
# ---------------------------------------------------------------------------


def _batched_stream(S, T, base_seed):
    streams = [_stream(T, seed=base_seed + s) for s in range(S)]
    xs = jnp.stack([jnp.stack([st[0][t] for st in streams])
                    for t in range(T)])  # (T, S, dim)
    ys = jnp.stack([jnp.stack([st[1][t] for st in streams])
                    for t in range(T)])
    taus = jnp.stack([jnp.stack([st[2][t] for st in streams])
                      for t in range(T)])
    return streams, xs, ys, taus


@pytest.mark.parametrize("chunks", [(24,), (1,) * 24, (5, 18, 1),
                                    (2, 22)])
def test_observe_many_bit_identical_to_per_tick(chunks):
    """Any chunking of the tick stream == the per-tick path, bitwise."""
    S, T, cap, w = 3, 24, 32, 10
    assert sum(chunks) == T
    streams, xs, ys, taus = _batched_stream(S, T, base_seed=600)
    kw = dict(n_sessions=S, capacity=cap, dim=DIM, k=K, n_labels=2,
              window=w)
    ref_eng = ServingEngine(**kw, donate=False)
    st_ref = ref_eng.init_state()
    want = np.zeros((T, S), np.float32)
    for t in range(T):
        st_ref, p = ref_eng.observe(st_ref, xs[t], ys[t], taus[t])
        want[t] = np.asarray(p)

    eng = ServingEngine(**kw)  # donate=True default
    st = eng.init_state()
    got = []
    off = 0
    for c in chunks:
        st, p = eng.observe_many(st, xs[off:off + c], ys[off:off + c],
                                 taus[off:off + c])
        got.append(np.asarray(p))
        off += c
    np.testing.assert_array_equal(np.concatenate(got, axis=0), want)
    for a, b in zip(jax.tree_util.tree_leaves(st),
                    jax.tree_util.tree_leaves(st_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_observe_many_grow_mode_provisions_whole_chunk():
    """Grow mode doubles capacity up front so one dispatch covers T."""
    S, T = 2, 20
    streams, xs, ys, taus = _batched_stream(S, T, base_seed=620)
    eng = ServingEngine(n_sessions=S, capacity=8, dim=DIM, k=K, n_labels=2)
    state, pvals = eng.observe_many(eng.init_state(), xs, ys, taus)
    assert state.capacity == 32  # 8 -> 16 -> 32 before the scan
    assert eng.capacity == 32
    for s, (X, y, _) in enumerate(streams):
        want, _ = online.run_stream(X, y, k=K,
                                    key=jax.random.PRNGKey(620 + s),
                                    capacity=T)
        np.testing.assert_array_equal(np.asarray(want),
                                      np.asarray(pvals)[:, s])


def test_observe_many_active_mask_per_tick():
    S, T = 2, 4
    _, xs, ys, taus = _batched_stream(S, T, base_seed=640)
    eng = ServingEngine(n_sessions=S, capacity=16, dim=DIM, k=K,
                        n_labels=2, window=16)
    active = jnp.asarray([[True, False]] * 2 + [[True, True]] * 2)
    state, p = eng.observe_many(eng.init_state(), xs, ys, taus,
                                active=active)
    p = np.asarray(p)
    assert np.isnan(p[:2, 1]).all() and not np.isnan(p[:, 0]).any()
    assert not np.isnan(p[2:, 1]).any()
    assert list(np.asarray(state.knn.n)) == [4, 2]


def test_donated_observe_matches_undonated_and_consumes_input():
    """Donation is numerically free, and the donated input is dead:
    reusing a pre-donation state raises instead of silently aliasing."""
    S, T, cap, w = 2, 10, 16, 8
    _, xs, ys, taus = _batched_stream(S, T, base_seed=660)
    eng_d = ServingEngine(n_sessions=S, capacity=cap, dim=DIM, k=K,
                          n_labels=2, window=w, donate=True)
    eng_u = ServingEngine(n_sessions=S, capacity=cap, dim=DIM, k=K,
                          n_labels=2, window=w, donate=False)
    st_d, st_u = eng_d.init_state(), eng_u.init_state()
    for t in range(T):
        prev_d = st_d
        st_d, pd = eng_d.observe(st_d, xs[t], ys[t], taus[t])
        st_u, pu = eng_u.observe(st_u, xs[t], ys[t], taus[t])
        np.testing.assert_array_equal(np.asarray(pd), np.asarray(pu))
    for a, b in zip(jax.tree_util.tree_leaves(st_d),
                    jax.tree_util.tree_leaves(st_u)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # undonated inputs stay alive ...
    assert np.asarray(st_u.D).shape == (S, cap, cap)
    # ... donated inputs are deleted; both direct reads and a second
    # observe on the stale state fail loudly
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(prev_d.D)
    with pytest.raises((RuntimeError, ValueError), match="deleted"):
        eng_d.observe(prev_d, xs[0], ys[0], taus[0])


def test_session_donated_step_matches_and_consumes():
    T = 12
    X, y, taus = _stream(T, seed=680)
    a = sm.init(32, DIM, K)
    b = sm.init(32, DIM, K)
    for t in range(T):
        prev = a
        a, pa = sm.observe_sliding_donated(a, X[t], y[t], taus[t],
                                           jnp.int32(8), k=K)
        b, pb = sm.observe_sliding(b, X[t], y[t], taus[t],
                                   jnp.int32(8), k=K)
        assert float(pa) == float(pb)
    np.testing.assert_array_equal(np.asarray(a.knn.best),
                                  np.asarray(b.knn.best))
    np.testing.assert_array_equal(np.asarray(a.D), np.asarray(b.D))
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(prev.D)


# ---------------------------------------------------------------------------
# dtype stability across grow (post-grow re-jit audit)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_engine_dtype_stable_across_grow(dtype):
    """Every state leaf, the p-values and ``taus`` keep the engine dtype
    through grow-mode capacity doubling (sub-f32 dtypes used to drift to
    f32 through the p-value's int promotion, breaking the masked cond)."""
    S = 2
    eng = ServingEngine(n_sessions=S, capacity=8, dim=DIM, k=K,
                        n_labels=2, dtype=dtype)
    tau = eng.taus(jax.random.PRNGKey(0))
    assert tau.dtype == dtype
    state = eng.init_state()
    X, y, _ = _stream(20, seed=700)
    for t in range(20):  # forces 8 -> 16 -> 32 growth
        state, p = eng.observe(
            state, jnp.stack([X[t]] * S).astype(dtype),
            jnp.stack([y[t]] * S), eng.taus(jax.random.PRNGKey(t)))
    assert state.capacity > 8
    assert p.dtype == dtype
    assert state.knn.X.dtype == dtype
    assert state.knn.best.dtype == dtype
    assert state.D.dtype == dtype
    assert state.knn.y.dtype == jnp.int32
    assert eng.taus(jax.random.PRNGKey(9)).dtype == dtype


def test_registry_custom_measure_plugs_in():
    spec = registry.MeasureSpec(
        name="_test_mean_dist",
        fit=lambda X, y, hp: ((X, y), None),
        observe=lambda st, ctx, x, y, hp: (
            jnp.concatenate([st[0], x[None]]),
            jnp.concatenate([st[1], jnp.asarray([y], st[1].dtype)])),
        evict=lambda st, ctx, i, hp: (jnp.delete(st[0], i, axis=0),
                                      jnp.delete(st[1], i, axis=0)),
        pvalues=lambda st, ctx, Xt, hp: jnp.full(
            (Xt.shape[0], hp["n_labels"]), 0.5),
        defaults={"n_labels": 2},
    )
    registry.register(spec)
    try:
        assert "_test_mean_dist" in registry.available()
        cp = ConformalPredictor("_test_mean_dist")
        X, y = make_classification(n_samples=10, n_features=DIM, seed=0)
        cp.fit(X, y)
        cp.observe(jnp.asarray(X[0], jnp.float32), int(y[0]))
        cp.evict(0)
        assert cp.pvalues(jnp.asarray(X[:3], jnp.float32)).shape == (3, 2)
        with pytest.raises(TypeError):
            ConformalPredictor("_test_mean_dist", bogus=1)
    finally:
        registry._REGISTRY.pop("_test_mean_dist", None)


# ---------------------------------------------------------------------------
# one engine over a measure binding: what every family's engine keeps
# ---------------------------------------------------------------------------

FAMILIES = ["classification", "regression"]
#: the module name of each family's read program
READ_MODULE = {"classification": "jit_predict_pvalues",
               "regression": "jit__lambda"}


def _family_engine(family, mode, window=8, **kw):
    from repro.serving.families import ENGINES

    return ENGINES[family](n_sessions=2, capacity=16, dim=3, k=3,
                           window=window if mode == "sliding" else None,
                           **kw)


def _drive(eng, state, T, seed=0):
    """``T`` ticks of 0/1 labels (either family takes them) in one chunk."""
    rng = np.random.default_rng(seed)
    S = eng.n_sessions
    xs = jnp.asarray(rng.standard_normal((T, S, eng.dim)), jnp.float32)
    ys = jnp.asarray(rng.integers(0, 2, (T, S)))
    taus = jnp.asarray(rng.random((T, S)), jnp.float32)
    return eng.observe_many(state, xs, ys, taus)[0]


@pytest.mark.parametrize("mode", ["sliding", "grow"])
@pytest.mark.parametrize("family", FAMILIES)
def test_engine_programs_keep_their_module_names(family, mode):
    eng = _family_engine(family, mode)
    assert eng.lower_tick(2).as_text().startswith("module @jit_chunk")
    assert eng.lower_read(2).as_text().startswith(
        f"module @{READ_MODULE[family]}")


@pytest.mark.parametrize("mode", ["sliding", "grow"])
@pytest.mark.parametrize("family", FAMILIES)
def test_lowered_tick_carries_stage_scopes(family, mode):
    import re

    text = _family_engine(family, mode).lower_tick(2).as_text(
        debug_info=True)
    # grow mode drops the eviction machinery statically where it can
    scopes = (("evict", "learn", "write") if mode == "sliding"
              else ("learn", "write"))
    for scope in scopes:
        assert re.search(rf"/{scope}/|vmap\({scope}\)", text), scope


@pytest.mark.parametrize("mode", ["sliding", "grow"])
@pytest.mark.parametrize("family", FAMILIES)
def test_meta_restores_same_family_engine_bitwise(family, mode):
    from repro.serving.families import ENGINES

    eng = _family_engine(family, mode, donate=False)
    state = _drive(eng, eng.init_state(), 20)  # wraps, or grows past 16
    again = type(eng).from_meta(eng.meta())
    assert again.meta() == eng.meta()
    assert eng.meta()["mode"] == family
    assert (jax.tree_util.tree_structure(state)
            == ENGINES[family].measure.treedef)
    with tempfile.TemporaryDirectory() as d:
        SessionStore(d).save(20, state, meta=eng.meta(), blocking=True)
        eng2, state2, step = SessionStore(d).restore_engine()
    assert step == 20 and type(eng2) is type(eng)
    assert eng2.measure.name == family and eng2.meta() == eng.meta()
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(state2)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", ["over-full", "window-modulus",
                                  "grow-modulus"])
@pytest.mark.parametrize("family", FAMILIES)
def test_engine_rejects_state_outside_its_invariants(family, case):
    if case == "over-full":  # a grow engine's 12 points into window 8
        src = _family_engine(family, "grow")
        eng, match = _family_engine(family, "sliding"), "exceeds"
        T = 12
    elif case == "window-modulus":  # a window-6 ring into window 8
        src = _family_engine(family, "sliding", window=6)
        eng, match = _family_engine(family, "sliding"), "ring modulus"
        T = 4
    else:  # a window-8 ring into a grow engine
        src = _family_engine(family, "sliding")
        eng, match = _family_engine(family, "grow"), "ring modulus"
        T = 4
    state = _drive(src, src.init_state(), T)
    with pytest.raises(ValueError, match=match):
        _drive(eng, state, 1)
