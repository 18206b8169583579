"""Ring-buffer sliding-window layout: exactness + O(cap) eviction.

The acceptance-critical properties of the circular-indexing tentpole:

* any observe/evict interleaving on the ring layout — wrap-around, tie
  runs across the ring seam, inactive lanes, window-confined blocks —
  is BIT-identical (p-values and every normalized state leaf) to the
  historic positional-compaction layout (``_sliding_step_compact``) and
  therefore, transitively through the pre-existing suites, to
  fit-from-scratch on the surviving window;
* the jitted ring sliding step materializes NO (cap, cap)-sized buffer:
  the distance matrix is only read (backfill reductions) and written in
  place at one row + one column (asserted on the optimized HLO via
  ``analysis.hlo.dense_materializations`` — the compact layout is the
  positive control);
* wrapped rings survive ``grow`` and snapshot save/restore, and legacy
  pre-ring (5/6-leaf linear) snapshots still restore and serve.
"""
import functools
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised in bare containers
    HAS_HYPOTHESIS = False

from repro.core import online
from repro.core import regression as reg
from repro.core.online import BIG
from repro.data.synthetic import make_classification, make_regression
from repro.regression import RegressionServingEngine
from repro.regression import session as rsess
from repro.regression import stream as rstream
from repro.serving import ServingEngine, SessionStore
from repro.serving import session as sm

DIM = 5
_STAT = ("k", "evictable", "wmax")
_cstep_ring = functools.partial(jax.jit, static_argnames=_STAT)(
    sm._sliding_step)
_cstep_compact = functools.partial(jax.jit, static_argnames=_STAT)(
    sm._sliding_step_compact)
_rstep_ring = functools.partial(jax.jit, static_argnames=_STAT)(
    rsess._sliding_step)
_rstep_compact = functools.partial(jax.jit, static_argnames=_STAT)(
    rsess._sliding_step_compact)


def _class_stream(T, seed):
    X, y = make_classification(n_samples=T, n_features=DIM, seed=seed)
    taus = jax.random.uniform(jax.random.PRNGKey(seed), (T,), jnp.float32)
    return jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.int32), taus


def _reg_stream(T, seed):
    X, y = make_regression(n_samples=T, n_features=DIM, seed=seed)
    taus = jax.random.uniform(jax.random.PRNGKey(seed), (T,), jnp.float32)
    return (jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32),
            taus)


def _tie_stream(T, seed, classes=2):
    """Integer grids force exactly-equal distances across the ring seam."""
    rng = np.random.RandomState(seed)
    X = jnp.asarray(rng.randint(0, 2, size=(T, DIM)), jnp.float32)
    y = rng.randint(0, classes, size=T)
    taus = jnp.full((T,), 0.5, jnp.float32)
    return X, y, taus


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _run_pair(kind, X, y, taus, *, k, cap, window, wmax, actmod):
    """Drive ring and compact steps over the same stream; p-values must
    agree per tick and the normalized final states leaf-for-leaf."""
    if kind == "class":
        init, ring, compact, lin = (sm.init, _cstep_ring, _cstep_compact,
                                    sm.to_linear)
        cast = lambda v: jnp.asarray(v, jnp.int32)
    else:
        init, ring, compact, lin = (rsess.init, _rstep_ring,
                                    _rstep_compact, rstream.to_linear)
        cast = lambda v: jnp.asarray(v, jnp.float32)
    wm = wmax if wmax is None else max(min(window, cap), k)
    wr = cap if wmax is None else wm
    a = init(cap, DIM, k, wrap=wr)
    b = init(cap, DIM, k, wrap=wr)
    for t in range(X.shape[0]):
        act = jnp.asarray(actmod == 0 or (t % actmod != 0))
        a, pa = ring(a, X[t], cast(y[t]), taus[t], jnp.int32(window), act,
                     k=k, evictable=True, wmax=wm)
        b, pb = compact(b, X[t], cast(y[t]), taus[t], jnp.int32(window),
                        act, k=k, evictable=True, wmax=wm)
        assert (float(pa) == float(pb)
                or (np.isnan(float(pa)) and np.isnan(float(pb)))), t
    _assert_trees_equal(lin(a), lin(b))
    return a


# ---------------------------------------------------------------------------
# ring == compact, property-tested across wrap-around
# ---------------------------------------------------------------------------


if HAS_HYPOTHESIS:
    _ring_cases = lambda f: settings(max_examples=10, deadline=None)(
        given(seed=st.integers(0, 10_000), k=st.integers(1, 6),
              window=st.integers(1, 14), confined=st.booleans(),
              actmod=st.integers(0, 4), ties=st.booleans())(f))
else:  # deterministic fallback grid (hypothesis not installed)
    _ring_cases = pytest.mark.parametrize(
        "seed,k,window,confined,actmod,ties",
        [(0, 5, 12, True, 3, False), (1, 3, 10, False, 0, False),
         (2, 1, 7, True, 0, True), (3, 4, 3, True, 4, False),
         (4, 2, 2, False, 0, True), (5, 6, 13, True, 2, False)])


@pytest.mark.parametrize("kind", ["class", "reg"])
@_ring_cases
def test_ring_equals_compact_any_interleaving(kind, seed, k, window,
                                              confined, actmod, ties):
    """The tentpole exactness property: ring ticks (wrap-around, ties at
    the seam, gated lanes, window-confined blocks) are bit-identical to
    the positional-compaction oracle."""
    T, cap = 40, 32
    if ties:
        X, y, taus = _tie_stream(T, seed, classes=2)
    elif kind == "class":
        X, y, taus = _class_stream(T, seed)
    else:
        X, y, taus = _reg_stream(T, seed)
    window = max(min(window, cap), 1)
    _run_pair(kind, X, y, taus, k=k, cap=cap, window=window,
              wmax=(window if confined else None), actmod=actmod)


def test_ring_wraps_and_matches_refit_classification():
    """A visibly wrapped ring (head > 0, several laps) still equals an
    incremental fit on the surviving window, D and arrival ids included."""
    T, cap, w, k = 50, 16, 16, 5
    X, y, taus = _class_stream(T, seed=7)
    sess = sm.init(cap, DIM, k)
    for t in range(T):
        sess, _ = sm.observe_sliding(sess, X[t], y[t], taus[t],
                                     jnp.int32(w), k=k)
    assert int(sess.head) == (T - w) % cap  # wrapped 2+ laps
    scratch = sm.init(cap, DIM, k)
    for t in range(T - w, T):
        scratch, _ = sm.observe(scratch, X[t], y[t], taus[t], k=k)
    a, b = sm.to_linear(sess), sm.to_linear(scratch)
    np.testing.assert_array_equal(np.asarray(a.knn.best),
                                  np.asarray(b.knn.best))
    np.testing.assert_array_equal(np.asarray(a.D), np.asarray(b.D))
    # predict on the wrapped ring == predict on the fresh state
    pa = sm.predict_pvalues(sess, X[:6], k=k, n_labels=2)
    pb = sm.predict_pvalues(scratch, X[:6], k=k, n_labels=2)
    np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


@pytest.mark.parametrize("i_kind", ["head", "mid", "last"])
def test_reg_evict_index_on_wrapped_ring(i_kind):
    """evict(i) (arrival rank) on a wrapped ring: evict-at-head equals
    evict_oldest's window; mid/last exercise the general recompute."""
    T, cap, k = 26, 32, 4
    X, y, _ = _reg_stream(T, seed=3)
    stt = rstream.init(cap, DIM, k)
    for t in range(T):
        stt, _ = rstream.observe(stt, X[t], y[t], k=k)
    for _ in range(5):  # wrap: free 5 slots, refill them
        stt = rstream.evict_oldest(stt, k=k)
    for t in range(5):
        stt, _ = rstream.observe(stt, X[t], y[t], k=k)
    order = np.concatenate([np.arange(5, T), np.arange(5)])
    i = {"head": 0, "mid": T // 2, "last": T - 1}[i_kind]
    stt = rstream.evict(stt, jnp.int32(i), k=k)
    keep = np.delete(order, i)
    fit = reg.fit(X[keep], y[keep], k=k)
    view = rstream.state_view(stt, k=k)
    n = int(stt.n)
    np.testing.assert_array_equal(np.asarray(view.X)[:n],
                                  np.asarray(X)[keep])
    np.testing.assert_array_equal(np.asarray(view.a_prime)[:n],
                                  np.asarray(fit.a_prime))
    np.testing.assert_array_equal(np.asarray(view.kth_label)[:n],
                                  np.asarray(fit.kth_label))


@pytest.mark.parametrize("kind", ["class", "reg"])
def test_grow_while_wrapped(kind):
    """grow() on a wrapped ring normalizes and keeps serving exactly."""
    T, cap, w, k = 30, 16, 10, 4
    if kind == "class":
        X, y, taus = _class_stream(T, seed=11)
        a = _run_pair(kind, X, y, taus, k=k, cap=cap, window=w, wmax=w,
                      actmod=0)
        g = sm.grow(a)
        assert g.capacity == 2 * cap
        assert int(g.head) == 0 and int(g.wrap) == 2 * cap
        scratch = sm.init(2 * cap, DIM, k)
        for t in range(T - w, T):
            scratch, _ = sm.observe(scratch, X[t], y[t], taus[t], k=k)
        _, pg = sm.observe(g, X[0], y[0], jnp.float32(0.5), k=k)
        _, ps = sm.observe(scratch, X[0], y[0], jnp.float32(0.5), k=k)
        assert float(pg) == float(ps)
    else:
        X, y, taus = _reg_stream(T, seed=12)
        a = _run_pair(kind, X, y, taus, k=k, cap=cap, window=w, wmax=w,
                      actmod=0)
        g = rsess.grow(a)
        assert g.capacity == 2 * cap
        assert int(g.head) == 0 and int(g.wrap) == 2 * cap
        fit = reg.fit(X[T - w:], y[T - w:], k=k)
        view = rstream.state_view(g, k=k)
        np.testing.assert_array_equal(np.asarray(view.a_prime)[:w],
                                      np.asarray(fit.a_prime))


# ---------------------------------------------------------------------------
# engines: compact layout plugs in, wrapped snapshots round-trip
# ---------------------------------------------------------------------------


def _drive(eng, state, xs, ys, taus):
    ps = []
    for t in range(xs.shape[0]):
        state, p = eng.observe(state, xs[t], ys[t], taus[t])
        ps.append(np.asarray(p))
    return state, np.stack(ps)


def test_engine_layouts_bit_identical_classification():
    S, T, cap, w, k = 2, 30, 16, 8, 3
    streams = [_class_stream(T, seed=500 + s) for s in range(S)]
    xs = jnp.stack([jnp.stack([st_[0][t] for st_ in streams])
                    for t in range(T)])
    ys = jnp.stack([jnp.stack([st_[1][t] for st_ in streams])
                    for t in range(T)])
    taus = jnp.stack([jnp.stack([st_[2][t] for st_ in streams])
                      for t in range(T)])
    kw = dict(n_sessions=S, capacity=cap, dim=DIM, k=k, n_labels=2,
              window=w)
    er = ServingEngine(**kw, layout="ring", donate=False)
    ec = ServingEngine(**kw, layout="compact", donate=False)
    sr, pr = _drive(er, er.init_state(), xs, ys, taus)
    sc, pc = _drive(ec, ec.init_state(), xs, ys, taus)
    np.testing.assert_array_equal(pr, pc)
    assert int(jnp.max(sr.head)) > 0  # the ring engines actually wrapped
    assert int(jnp.max(sc.head)) == 0  # the compact ones never move rows
    q = er.predict(sr, xs[0])
    np.testing.assert_array_equal(np.asarray(q),
                                  np.asarray(ec.predict(sc, xs[0])))
    with pytest.raises(ValueError, match="layout"):
        ServingEngine(**kw, layout="spiral")


def test_wrapped_ring_snapshot_roundtrip_both_engines():
    S, T, k, w, cap = 2, 26, 3, 8, 16
    # classification
    streams = [_class_stream(T, seed=600 + s) for s in range(S)]
    eng = ServingEngine(n_sessions=S, capacity=cap, dim=DIM, k=k,
                        n_labels=2, window=w)
    state = eng.init_state()
    for t in range(T):
        state, _ = eng.observe(
            state, jnp.stack([st_[0][t] for st_ in streams]),
            jnp.stack([st_[1][t] for st_ in streams]),
            jnp.stack([st_[2][t] for st_ in streams]))
    assert int(jnp.max(state.head)) > 0  # wrapped before snapshotting
    with tempfile.TemporaryDirectory() as d:
        SessionStore(d).save(T, state, meta=eng.meta(), blocking=True)
        eng2, state2, step = SessionStore(d).restore_engine()
        assert step == T
        _assert_trees_equal(state, state2)
        x = jnp.stack([st_[0][0] for st_ in streams])
        y = jnp.stack([st_[1][0] for st_ in streams])
        tau = jnp.stack([st_[2][0] for st_ in streams])
        _, pa = eng.observe(state, x, y, tau)
        _, pb = eng2.observe(state2, x, y, tau)
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
    # regression
    rstreams = [_reg_stream(T, seed=650 + s) for s in range(S)]
    reng = RegressionServingEngine(n_sessions=S, capacity=cap, dim=DIM,
                                  k=k, window=w)
    rstate = reng.init_state()
    for t in range(T):
        rstate, _ = reng.observe(
            rstate, jnp.stack([st_[0][t] for st_ in rstreams]),
            jnp.stack([st_[1][t] for st_ in rstreams]),
            jnp.stack([st_[2][t] for st_ in rstreams]))
    assert int(jnp.max(rstate.head)) > 0
    with tempfile.TemporaryDirectory() as d:
        SessionStore(d).save(T, rstate, meta=reng.meta(), blocking=True)
        reng2, rstate2, _ = SessionStore(d).restore_engine()
        assert isinstance(reng2, RegressionServingEngine)
        _assert_trees_equal(rstate, rstate2)
        iv = reng.intervals(rstate, rstreams[0][0][:3], epsilon=0.157)
        iv2 = reng2.intervals(rstate2, rstreams[0][0][:3], epsilon=0.157)
        np.testing.assert_array_equal(np.asarray(iv), np.asarray(iv2))


def test_legacy_linear_snapshot_restores_and_serves():
    """Pre-ring snapshots (5-leaf classification / 6-leaf regression
    linear layouts) restore into ring states and keep serving."""
    from repro.checkpoint.store import CheckpointStore

    S, T, cap, w, k = 2, 12, 16, 8, 3
    streams = [_class_stream(T, seed=700 + s) for s in range(S)]
    eng = ServingEngine(n_sessions=S, capacity=cap, dim=DIM, k=k,
                        n_labels=2, window=w)
    state = eng.init_state()
    for t in range(T):
        state, _ = eng.observe(
            state, jnp.stack([st_[0][t] for st_ in streams]),
            jnp.stack([st_[1][t] for st_ in streams]),
            jnp.stack([st_[2][t] for st_ in streams]))
    # fabricate the legacy 5-leaf layout from the normalized state
    lin = jax.vmap(sm.to_linear)(state)
    legacy = [lin.knn.X, lin.knn.y, lin.knn.best, lin.knn.n, lin.D]
    with tempfile.TemporaryDirectory() as d:
        CheckpointStore(d).save(T, legacy, blocking=True,
                                extra=eng.meta())
        eng2, state2, step = SessionStore(d).restore_engine()
        assert step == T and eng2.window == w
        assert int(jnp.max(state2.head)) == 0
        assert int(jnp.min(state2.wrap)) == eng2._wmax  # re-pinned
        x = jnp.stack([st_[0][0] for st_ in streams])
        y = jnp.stack([st_[1][0] for st_ in streams])
        tau = jnp.stack([st_[2][0] for st_ in streams])
        _, pa = eng2.observe(state2, x, y, tau)  # serves without error
        assert np.isfinite(np.asarray(pa)).all()

    # regression legacy (6-leaf): nbr_a is reconstructed from D
    X, y, taus = _reg_stream(T, seed=710)
    stt = rstream.init(cap, DIM, k)
    for t in range(T):
        stt, _ = rstream.observe(stt, X[t], y[t], k=k)
    legacy = [stt.X, stt.y, stt.D, stt.nbr_d, stt.nbr_y, stt.n]
    meta = RegressionServingEngine(
        n_sessions=1, capacity=cap, dim=DIM, k=k).meta()
    with tempfile.TemporaryDirectory() as d:
        CheckpointStore(d).save(T, legacy, blocking=True, extra=meta)
        store = SessionStore(d)
        state2, _, _ = store.restore()
        assert isinstance(state2, rstream.RegStreamState)
        np.testing.assert_array_equal(np.asarray(state2.nbr_a),
                                      np.asarray(stt.nbr_a))
        # and the restored state keeps evicting exactly
        a = rstream.evict_oldest(state2, k=k)
        b = rstream.evict_oldest(stt, k=k)
        _assert_trees_equal(a, b)


@pytest.mark.parametrize("route", ["xla", "kernel"])
@pytest.mark.parametrize("kind", ["class", "reg"])
def test_engine_distance_block_bitwise_symmetric(kind, route, monkeypatch):
    """After 3 windows of evicting ticks (the ring wrapped, some lanes
    inactive) every tenant's D equals its transpose bit for bit: the
    invariant that lets the eviction read the head's column as its row.
    ``kernel`` runs the tick's Pallas kernels in interpret mode."""
    if route == "kernel":
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    S, cap, w, k = 2, 16, 8, 3
    T = 3 * w
    if kind == "class":
        eng = ServingEngine(n_sessions=S, capacity=cap, dim=DIM, k=k,
                            n_labels=2, window=w)
        streams = [_class_stream(T, seed=800 + s) for s in range(S)]
    else:
        eng = RegressionServingEngine(n_sessions=S, capacity=cap, dim=DIM,
                                      k=k, window=w)
        streams = [_reg_stream(T, seed=850 + s) for s in range(S)]
    xs, ys, taus = (jnp.stack([jnp.stack([st_[i][t] for st_ in streams])
                               for t in range(T)]) for i in range(3))
    active = (jnp.arange(T)[:, None] + jnp.arange(S)[None, :]) % 5 != 0
    state, _ = eng.observe_many(eng.init_state(), xs, ys, taus,
                                active=active)
    assert int(jnp.min(state.head)) > 0  # every ring wrapped
    D = np.asarray(state.D)
    np.testing.assert_array_equal(D, np.swapaxes(D, 1, 2))


def test_engine_rejects_mismatched_ring_modulus():
    eng = ServingEngine(n_sessions=1, capacity=16, dim=DIM, k=3,
                        n_labels=2, window=8)
    bad = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (1,) + a.shape),
        sm.init(16, DIM, 3))  # wrap == capacity != window block
    X, y, taus = _class_stream(1, seed=13)
    with pytest.raises(ValueError, match="ring modulus"):
        eng.observe(bad, X[:1], y[:1], taus[:1])
    # the reverse handoff — a window-confined ring into a GROW engine —
    # must be rejected too: the grow engine would keep inserting past
    # the state's smaller modulus and overwrite live slots
    grow_eng = ServingEngine(n_sessions=1, capacity=16, dim=DIM, k=3,
                             n_labels=2)
    confined = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (1,) + a.shape),
        sm.init(16, DIM, 3, wrap=8))
    with pytest.raises(ValueError, match="grow-mode engine's capacity"):
        grow_eng.observe(confined, X[:1], y[:1], taus[:1])


def test_arrival_id_wraparound_is_harmless():
    """The int32 arrival counters may overflow on a long-lived stream;
    every id comparison is a wraparound difference from the oldest live
    id, so a state whose ids straddle INT32_MAX must evict and observe
    exactly like its unshifted twin (tie-heavy data so the id-based
    tie-breaks actually fire)."""
    T, cap, k = 24, 32, 4
    X, y, _ = _tie_stream(T, seed=5, classes=4)
    y = jnp.asarray(y, jnp.float32)
    a = rstream.init(cap, DIM, k)
    for t in range(T):
        a, _ = rstream.observe(a, X[t], y[t], k=k)
    # shift every id (slot counters and neighbour lists) near the wrap
    # point: after ~40 more inserts the raw counters overflow
    off = jnp.int32(2**31 - 40)
    live = np.asarray(rstream.ring_live(cap, a.head, a.n, a.wrap))
    b = rstream.RegStreamState(
        a.X, a.y, a.D, a.nbr_d, a.nbr_y, a.n, a.head,
        jnp.where(jnp.asarray(live), a.aid + off, a.aid), a.wrap,
        jnp.where(a.nbr_d < 1e29, a.nbr_a + off, a.nbr_a))
    for t in range(T):  # interleave evicts with re-adds across the wrap
        a = rstream.evict_oldest(a, k=k)
        b = rstream.evict_oldest(b, k=k)
        a, _ = rstream.observe(a, X[t], y[t], k=k)
        b, _ = rstream.observe(b, X[t], y[t], k=k)
        for nm in ("nbr_d", "nbr_y", "n", "head"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, nm)), np.asarray(getattr(b, nm)),
                err_msg=f"{nm} diverged at tick {t}")
    # the shifted twin's raw counters really did wrap negative
    newest = np.asarray(b.aid)[int(rstream.ring_slots(
        cap, b.head, b.wrap)[int(b.n) - 1])]
    assert newest < 0
    fit = reg.fit(X, y, k=k)
    view = rstream.state_view(b, k=k)
    np.testing.assert_array_equal(np.asarray(view.kth_label)[:T],
                                  np.asarray(fit.kth_label))


# ---------------------------------------------------------------------------
# the tick's arrival-order picks in slot space == the arrival-order gathers
# ---------------------------------------------------------------------------


def _own_list_gather(state, d_row, y2, y_new, *, k):
    """The own list as ``top_k`` over the row gathered into arrival order
    through ``ring_slots`` (the reference form); ``y2`` is the post-learn
    label vector the gathering callers passed."""
    cap = state.capacity
    slots = rstream.ring_slots(cap, state.head, state.wrap)
    pos_live = jnp.arange(cap) < state.n
    d_arr = jnp.where(pos_live, d_row[slots], BIG)
    y_arr = jnp.where(pos_live, y2[slots], 0.0)
    y_pre = jnp.where(pos_live, state.y[slots], 0.0)
    a_arr = jnp.where(pos_live, state.aid[slots], 0)
    own_neg, own_idx = jax.lax.top_k(-d_arr, k)
    own_d = -own_neg
    own_y = jnp.where(own_d >= BIG, y_new, y_arr[own_idx])
    own_a = jnp.where(own_d >= BIG, 0, a_arr[own_idx]).astype(jnp.int32)
    return own_d, own_y, y_pre[own_idx], own_a


def _drop_backfill_gather(L, es, cand, Ds, aff, *, k, Ly, La, ys, aid,
                          aid0, age, slots):
    """The labeled list repair with the pick as a min over arrival rank
    ``age`` and gathers through the rank -> slot permutation ``slots``
    (the reference form)."""
    newL, pos0, cols, b, tprime, _ = online.drop_backfill_core(
        L, es, cand, Ds, k=k)
    cap = L.shape[0]
    aid0 = jnp.asarray(aid0, jnp.int32)
    thr = jnp.where(
        b == tprime,
        jnp.max(jnp.where(L == tprime[:, None], La - aid0, -1), axis=1), -1)
    valid = cand & (Ds == b[:, None]) & ((aid - aid0)[None, :] > thr[:, None])
    amin = jnp.min(jnp.where(valid, age[None, :], cap), axis=1)
    sel = slots[jnp.minimum(amin, cap - 1)]
    yb, ab = ys[sel], aid[sel]
    tail = lambda M, v: jnp.where(
        cols[None, :] < pos0[:, None], M,
        jnp.where(cols[None, :] < k - 1,
                  jnp.concatenate([M[:, 1:], M[:, :1]], axis=1), v[:, None]))
    newLy = jnp.where(newL >= BIG, ys[:, None], tail(Ly, yb))
    newLa = jnp.where(newL >= BIG, 0, tail(La, ab))
    return (jnp.where(aff[:, None], newL, L),
            jnp.where(aff[:, None], newLy, Ly),
            jnp.where(aff[:, None], newLa, La))


def _bits(a):
    a = np.asarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


# (ticks, window, capacity, ring modulus, evictions after, k, data)
_PICK_CASES = {
    "head-at-seam": (32, 16, 16, 16, 0, 4, "grid"),
    "straddles-seam": (31, 16, 16, 16, 0, 4, "grid"),
    "past-seam": (37, 16, 16, 16, 0, 4, "grid"),
    "confined-ring": (37, 16, 24, 16, 0, 4, "grid"),
    "n-below-k": (3, 16, 16, 16, 0, 5, "grid"),
    "drained-below-k": (37, 16, 16, 16, 12, 5, "grid"),
    "n-equals-wrap": (16, 32, 16, 16, 0, 4, "grid"),
    "ids-wrap-int32": (37, 16, 16, 16, 0, 4, "grid-idwrap"),
    "continuous": (37, 16, 16, 16, 0, 4, "continuous"),
}


def _pick_state(case):
    """A ring state from a real sliding stream; grid features force
    distance ties, grid labels include -0.0; optionally its arrival ids
    shifted so that they straddle INT32_MAX."""
    T, window, cap, wrap, drain, k, data = _PICK_CASES[case]
    rng = np.random.RandomState(T + cap + drain)
    if data == "continuous":
        X, y, _ = _reg_stream(T + 1, seed=4)
    else:
        X = jnp.asarray(rng.randint(0, 2, size=(T + 1, DIM)), jnp.float32)
        y = jnp.asarray(rng.choice([-0.0, 0.0, 1.0, 2.0], size=T + 1),
                        jnp.float32)
    st = rstream.init(cap, DIM, k, wrap=wrap)
    for t in range(T):
        if int(st.n) >= window:
            st = rstream.evict_oldest(st, k=k)
        st, _ = rstream.observe(st, X[t], y[t], k=k)
    for _ in range(drain):
        st = rstream.evict_oldest(st, k=k)
    if data.endswith("idwrap"):
        live = rstream.ring_live(cap, st.head, st.n, st.wrap)
        off = jnp.int32(2**31 - 1 - T + int(st.n) // 2)
        st = rstream.RegStreamState(
            st.X, st.y, st.D, st.nbr_d, st.nbr_y, st.n, st.head,
            jnp.where(live, st.aid + off, st.aid), st.wrap,
            jnp.where(st.nbr_d < BIG, st.nbr_a + off, st.nbr_a))
        ids = np.asarray(st.aid)[np.asarray(live)]
        assert ids.min() < 0 < ids.max()  # the live ids wrap
    return st, X[T], y[T], k


@pytest.mark.parametrize("case", list(_PICK_CASES))
def test_own_list_sorts_like_arrival_order_gather(case):
    """The own list from one two-key sort in slot order is bit-identical
    to ``top_k`` over the row gathered into arrival order: distances,
    labels (``-0.0`` kept), selected pre-learn labels, arrival ids."""
    st, x_new, y_new, k = _pick_state(case)
    _, d_row = rstream.observe(st, x_new, y_new, k=k)
    full = int(st.n) == int(st.wrap)
    # a gated caller at a full window learns nothing: its post-learn
    # labels are the pre-learn ones
    idx = (int(st.head) + int(st.n)) % int(st.wrap)
    y2 = st.y if full else st.y.at[idx].set(y_new)
    new = jax.jit(functools.partial(rstream._own_list, k=k))(
        st, d_row, y_new)
    ref = jax.jit(functools.partial(_own_list_gather, k=k))(
        st, d_row, y2, y_new)
    for name, a, b in zip(("own_d", "own_y", "y_sel", "own_a"), new, ref):
        np.testing.assert_array_equal(_bits(a), _bits(b), err_msg=name)


@pytest.mark.parametrize("case", list(_PICK_CASES))
def test_backfill_pick_matches_arrival_rank_gather(case):
    """The labeled backfill's pick as a min over arrival ids that carries
    the label is bit-identical to the min over arrival rank followed by
    gathers through ``ring_slots``: every repaired list, on every row."""
    st, _, _, k = _pick_state(case)
    cap, wrap, head = st.capacity, st.wrap, st.head
    head2 = (head + 1) % wrap
    live2 = rstream.ring_live(cap, head2, st.n - 1, wrap)
    dcol = st.D[:, head]
    aff = live2 & (dcol <= st.nbr_d[:, -1])
    assert bool(aff.any())
    args = (st.nbr_d, dcol, live2[None, :], st.D, aff)
    kw = dict(k=k, Ly=st.nbr_y, La=st.nbr_a, ys=st.y, aid=st.aid,
              aid0=st.aid[head])
    new = jax.jit(functools.partial(online.drop_backfill, **kw))(*args)
    ref = jax.jit(functools.partial(
        _drop_backfill_gather, **kw, age=online.ring_age(cap, head2, wrap),
        slots=online.ring_slots(cap, head2, wrap)))(*args)
    for name, a, b in zip(("nbr_d", "nbr_y", "nbr_a"), new, ref):
        np.testing.assert_array_equal(_bits(a), _bits(b), err_msg=name)


# ---------------------------------------------------------------------------
# the O(cap) eviction claim, on the optimized HLO (via the auditor —
# repro.analysis.audit owns the single definition of this invariant)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["class", "reg"])
def test_ring_sliding_step_never_materializes_cap_sq(kind):
    """No (cap, cap) shift/copy/rebuild per tick in the jitted sliding
    step: the distance matrix may only appear as a parameter, inside
    reductions, and as in-place dynamic-update-slice writes. The compact
    layout is the positive control — its per-tick compaction trips the
    same detector. Asserted through ``audit.dense_tick_violations``,
    the same predicate the CI audit gate runs over the whole matrix."""
    from repro.analysis import audit as audit_m

    S, cap, dim, k, chunk = 2, 64, 8, 5, 4
    min_bytes = S * cap * cap * 4  # a full f32 (S, cap, cap) result
    kw = dict(n_sessions=S, capacity=cap, dim=dim, k=k, window=cap)
    if kind == "class":
        mk = lambda layout: ServingEngine(**kw, n_labels=2, layout=layout)
    else:
        mk = lambda layout: RegressionServingEngine(**kw, layout=layout)
    ring_hlo = mk("ring").lower_tick(chunk).compile().as_text()
    per_tick = audit_m.dense_tick_violations(ring_hlo, min_bytes)
    assert not per_tick, per_tick
    compact_hlo = mk("compact").lower_tick(chunk).compile().as_text()
    assert audit_m.dense_tick_violations(compact_hlo, min_bytes), (
        "positive control: the compaction layout should materialize "
        "(cap, cap) buffers per tick")
    # and the ring tick keeps its donated buffers aliased (no leak)
    assert not audit_m.alias_violations(
        ring_hlo, len(jax.tree_util.tree_leaves(mk("ring").init_state())))
