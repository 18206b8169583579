"""Per-tenant streaming regression-CP session over ``RegStreamState``.

Adds to the raw stream state the three per-tenant behaviours the serving
engine needs, all fixed-shape and vmappable:

* ``observe`` — price the incoming example first (smoothed online
  p-value of its *actual* label against the current window — the
  regression analogue of ``core.online.observe``, feeding the same
  exchangeability martingales), then learn it;
* ``observe_sliding`` — evict-if-full then observe: one sliding-window
  step with a traced per-tenant ``window``. On the ring layout the
  evict half is a head advance + O(cap·k) list repair; the (cap, cap)
  ``D`` is only read (the backfill reductions) and written at one
  row + one column — never shifted or copied (``_sliding_step_compact``
  keeps the historic positional form as the bit-oracle);
* ``intervals`` / ``pvalues`` — capacity-padded read paths, computed on
  the ``arrival_view`` (an O(cap) gather into arrival order, so the
  historic linear-layout expressions — and their bits — are unchanged,
  equal-distance tie order included). ``intervals`` routes the fused
  distance-row + (a_i, b_i) update + critical-point computation through
  ``kernels.ops.interval_sweep`` (the Pallas kernel on TPU) and
  finishes with the shared ``regression.hull_sweep``; padded rows
  contribute neutral events, so results are bit-identical to
  ``regression.intervals_optimized`` on the live window (property-tested;
  the one caveat is an ``epsilon`` sitting exactly on the p == epsilon
  rank boundary, where f32 vs f64 threshold rounding may legitimately
  differ — the same measure-zero tie the batch tests dodge with
  irrational grid offsets).

Read paths require n >= k (the candidate's own k-NN needs k live rows);
early-stream outputs are well-shaped but degenerate, as in the batch path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.engine import Measure
from repro.core.regression import BIG, _interval_ge, hull_sweep
from repro.kernels import ops as kops
from repro.regression import stream
from repro.regression.stream import RegStreamState, _mod_cap, _next_aid
from repro.core.online import cshift, drop_backfill, ring_live, slot_set

init = stream.init


_arrival_stats = stream.arrival_stats


def _ab_padded(state: RegStreamState, X_test, *, k):
    """Padded ``ab_optimized`` for a (m, p) query batch.

    Operates on the arrival-ordered stats (rows in arrival order), so
    bits equal ``regression.ab_optimized`` per live row/test point.
    Returns (a_vec (m, cap), b_vec (m, cap), a (m,), live (cap,)).
    """
    Xg, yg, a_prime, upd, kth, _, live = _arrival_stats(state, k=k)

    d = jnp.sqrt(jnp.maximum(kops.sq_dists(X_test, Xg), 0.0))
    enters = live[None, :] & (d < kth[None, :])
    a_vec = jnp.where(enters, upd[None, :], a_prime[None, :])
    b_vec = jnp.where(enters, -1.0 / k, 0.0)

    dm = jnp.where(live[None, :], d, BIG)
    _, idx = jax.lax.top_k(-dm, k)
    a = -jnp.sum(yg[idx], axis=1) / k
    return a_vec, b_vec, a, live


def _price(d_row, y_sel, y_new, tau, *, k, live, nbr_d, nbr_y, y, n):
    """Smoothed online p-value of label ``y_new`` against the pre-learn
    window statistics (alpha_i = |a_i + b_i y|, alpha = |a + y|,
    smoothed rank with tie-break ``tau``). Layout-free: per-slot scores
    masked by ``live``, integer rank counts, and the candidate's own
    ``a`` from the arrival-ordered top-k labels ``y_sel``.
    """
    kth = nbr_d[:, -1]
    a_prime = y - jnp.sum(nbr_y, axis=1) / k
    enters = live & (d_row < kth)  # d_row is BIG off the live window
    a_vec = jnp.where(enters, a_prime + nbr_y[:, -1] / k, a_prime)
    b_vec = jnp.where(enters, -1.0 / k, 0.0)
    a = -jnp.sum(y_sel) / k

    t = jnp.asarray(y_new, y.dtype)
    alphas = jnp.abs(a_vec + b_vec * t)
    alpha = jnp.abs(a + t)
    gt = jnp.sum(jnp.where(live, alphas > alpha, False))
    eq = jnp.sum(jnp.where(live, alphas == alpha, False))
    # astype: no-op at f32/f64, pins sub-f32 dtypes (see core.online)
    return ((gt + tau * (eq + 1.0)) / (n + 1.0)).astype(y.dtype)


def _observe(state: RegStreamState, x_new, y_new, tau, *, k):
    """Smoothed online p-value of (x_new, y_new), then learn it.

    The p-value tests the *observed label* against the current window
    (conformal test statistic for drift martingales). The distance row
    the learn step computes anyway (``stream.observe``'s second return)
    prices the point — scoring uses the pre-learn statistics, so one
    O(cap) row serves both.
    Precondition: n < capacity.
    """
    cap = state.capacity
    new_state, d_row = stream.observe(state, x_new, y_new, k=k)
    live = ring_live(cap, state.head, state.n, state.wrap)
    _, _, y_sel, _ = stream._own_list(state, d_row, y_new, k=k)
    p = _price(d_row, y_sel, y_new, tau, k=k, live=live,
               nbr_d=state.nbr_d, nbr_y=state.nbr_y, y=state.y,
               n=state.n)
    return new_state, p


observe = functools.partial(jax.jit, static_argnames=("k",))(_observe)
#: Donating form of ``observe``: the (cap, cap) ``D`` row/column insert
#: updates in place instead of copying the matrix. The input state is
#: DELETED by the call. Numerics are identical to ``observe``.
observe_donated = functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(0,))(_observe)


def _sliding_step(state: RegStreamState, x_new, y_new, tau, window, active,
                  *, k, evictable: bool = True, wmax: int | None = None):
    """One fused sliding-window tick: evict-if-full, observe, all gated.

    Regression counterpart of ``serving.session._sliding_step`` — the
    semantics of ``cond(evict_oldest) -> observe`` with an ``active``
    mask, on the ring layout: a gated head advance + the shared labeled
    list repair, then the observe core with arithmetically gated writes
    (inactive lanes rewrite their current values — masked state stays
    bitwise unchanged, p-value NaN). The (cap, cap) ``D`` is only read
    (one fused backfill-reduction pass) and written at one row + one
    column in place under donation. Bit-identical to the historic
    compaction form ``_sliding_step_compact`` (property-tested).
    ``evictable=False`` (static) drops the eviction machinery for the
    grow-mode engine; ``wmax`` (static, the sliding engine's window
    bound on occupancy) confines the ring to the ``[:wmax]`` block of
    every leaf — per-tick cost scales with the window, not the padded
    capacity.
    """
    cap = state.capacity
    # static block bound for the leaf slices; the traced modulus is the
    # state's ``wrap`` (engine invariant: wrap <= wmax)
    w = cap if wmax is None or wmax >= cap else wmax
    wrap = state.wrap
    # slot-space views confined to the ring block (pure reads)
    Xw, yw = state.X[:w], state.y[:w]
    Dw = state.D[:w, :w]
    aidw = state.aid[:w]
    head, n = state.head, state.n
    act = jnp.asarray(active)

    # named scopes (op_name metadata only: same instructions, same
    # bits) split the tick's device time by stage in a profile
    with jax.named_scope("evict"):
        if evictable:
            ev = act & (n >= window)
            s = ev.astype(jnp.int32)
            dcol = Dw[head, :]  # D is bitwise symmetric: the column as a row
            head1 = _mod_cap(head + s, wrap)
            n1 = n - s
            live1 = ring_live(w, head1, n1, wrap)
            affected = ev & live1 & (dcol <= state.nbr_d[:w, -1])
            nbr_d1, nbr_y1, nbr_a1 = drop_backfill(
                state.nbr_d[:w], dcol, live1[None, :], Dw, affected, k=k,
                Ly=state.nbr_y[:w], La=state.nbr_a[:w], ys=yw, aid=aidw,
                aid0=aidw[head])
        else:
            head1, n1 = head, n
            nbr_d1, nbr_y1 = state.nbr_d[:w], state.nbr_y[:w]
            nbr_a1 = state.nbr_a[:w]
            live1 = ring_live(w, head1, n1, wrap)

    # learn (mirrors stream._observe, writes gated on ``active``)
    with jax.named_scope("learn"):
        idx = _mod_cap(head1 + n1, wrap)
        y_new = jnp.asarray(y_new, yw.dtype)
        d_row, nbr_d_m, nbr_y_m = kops.stream_update(
            Xw, yw, nbr_d1, nbr_y1, x_new, y_new, n1, mode="reg",
            head=head1, wrap=wrap)
        sub = RegStreamState(Xw, yw, Dw, nbr_d1, nbr_y1, n1, head1, aidw,
                             wrap, nbr_a1)
        own_d, own_y, y_sel, own_a = stream._own_list(sub, d_row, y_new,
                                                      k=k)
        new_aid = _next_aid(aidw, head1, n1, wrap)
        enters = live1 & (d_row < nbr_d1[:, -1])
        nbr_a_m = stream._merge_aid(nbr_d1, nbr_a1,
                                    jnp.where(enters, d_row, BIG), new_aid,
                                    nbr_d_m)
        # price the observed label against the pre-learn window (mirrors
        # ``_observe``'s p-value block bit-for-bit)
        p = _price(d_row, y_sel, y_new, tau, k=k, live=live1,
                   nbr_d=nbr_d1, nbr_y=nbr_y1, y=yw, n=n1)

    with jax.named_scope("write"):
        row = jnp.where(act, d_row, Dw[idx, :])  # D symmetric: row == col
        # bit-neutral scheduling marker (see
        # serving.session._sliding_step): the D insert depends on every
        # repaired list (each carries backfill reads of D), which keeps
        # the XLA scatter route (CPU) to one copy of D fewer. Distances
        # are finite and >= 0 and labels and ids finite, so the term is
        # exactly +0.0
        row = row + (nbr_d1[0, 0]
                     + (nbr_y1[0, 0] + nbr_a1[0, 0]) * 0.0) * 0.0
        D2 = kops.dist_insert(state.D, row, idx)
        new_state = RegStreamState(
            X=slot_set(state.X, idx, x_new, act),
            y=slot_set(state.y, idx, y_new, act),
            D=D2,
            nbr_d=state.nbr_d.at[:w].set(
                jnp.where(act, slot_set(nbr_d_m, idx, own_d), nbr_d1)),
            nbr_y=state.nbr_y.at[:w].set(
                jnp.where(act, slot_set(nbr_y_m, idx, own_y), nbr_y1)),
            n=n1 + act,
            head=head1,
            aid=state.aid.at[idx].set(
                jnp.where(act, new_aid, state.aid[idx])),
            wrap=wrap,
            nbr_a=state.nbr_a.at[:w].set(
                jnp.where(act, slot_set(nbr_a_m, idx, own_a), nbr_a1)),
        )
        p = jnp.where(act, p, jnp.asarray(jnp.nan, dtype=Xw.dtype))
    return new_state, p


def _sliding_step_compact(state: RegStreamState, x_new, y_new, tau, window,
                          active, *, k, evictable: bool = True,
                          wmax: int | None = None):
    """Historic linear-layout sliding tick — the ring path's bit-oracle.

    Keeps arrival order positionally: eviction compacts every leaf down
    one row (and ``D`` one row AND one column) through a padded dynamic
    slice — the O(cap^2)-traffic form the ring layout replaces. Retained
    for the exactness property tests and as the benchmark baseline
    (``layout="compact"`` on the engine). Precondition: linear layout
    (``head == 0``), which this step preserves.
    """
    cap = state.capacity
    if wmax is not None and wmax < cap:
        sub = RegStreamState(
            state.X[:wmax], state.y[:wmax], state.D[:wmax, :wmax],
            state.nbr_d[:wmax], state.nbr_y[:wmax], state.n, state.head,
            state.aid[:wmax], jnp.minimum(state.wrap, wmax),
            state.nbr_a[:wmax])
        sub2, p = _sliding_step_compact(sub, x_new, y_new, tau, window,
                                        active, k=k, evictable=evictable)
        return RegStreamState(
            X=state.X.at[:wmax].set(sub2.X),
            y=state.y.at[:wmax].set(sub2.y),
            D=state.D.at[:wmax, :wmax].set(sub2.D),
            nbr_d=state.nbr_d.at[:wmax].set(sub2.nbr_d),
            nbr_y=state.nbr_y.at[:wmax].set(sub2.nbr_y),
            n=sub2.n, head=sub2.head,
            aid=state.aid.at[:wmax].set(sub2.aid),
            wrap=state.wrap,
            nbr_a=state.nbr_a.at[:wmax].set(sub2.nbr_a)), p
    act = jnp.asarray(active)
    aid = state.aid
    if evictable:
        ev = act & (state.n >= window)
        s = ev.astype(jnp.int32)
        live = jnp.arange(cap) < state.n
        dcol = state.D[:, 0]
        affected = ev & live & (dcol <= state.nbr_d[:, -1])

        # conditional compaction: pad each leaf by one (the pad value IS
        # the compaction fill) and take one dynamic slice at offset s
        X1 = cshift(state.X, s, 0)
        y1 = cshift(state.y, s, 0)
        L1 = cshift(state.nbr_d, s, BIG)
        Ly1 = cshift(state.nbr_y, s, 0)
        La1 = cshift(state.nbr_a, s, 0)
        aid1 = cshift(aid, s, 0)
        Dp = jnp.pad(state.D, ((0, 1), (0, 1)), constant_values=BIG)
        D1 = jax.lax.dynamic_slice(Dp, (s, s), (cap, cap))
        aff1 = cshift(affected, s, False)
        es1 = cshift(dcol, s, BIG)
        n1 = state.n - s
        live1 = jnp.arange(cap) < n1
        nbr_d1, nbr_y1, nbr_a1 = drop_backfill(
            L1, es1, live1[None, :], D1, aff1, k=k, Ly=Ly1, La=La1,
            ys=y1, aid=aid1, aid0=aid[0])
    else:
        X1, y1, D1 = state.X, state.y, state.D
        nbr_d1, nbr_y1, n1, aid1 = (state.nbr_d, state.nbr_y, state.n,
                                    aid)
        nbr_a1 = state.nbr_a
        live1 = jnp.arange(cap) < n1

    # learn (mirrors stream._observe, writes gated on ``active``).
    # The clamp keeps an inactive lane at an exactly-full window in
    # bounds (idx == cap otherwise — XLA's pad+slice fusion reads the
    # pad fill there instead of clamping); the write is its own value,
    # so the clamp is bit-neutral wherever the step is defined
    idx = jnp.minimum(n1, cap - 1)
    y_new = jnp.asarray(y_new, y1.dtype)
    d_row, nbr_d_m, nbr_y_m = kops.stream_update(
        X1, y1, nbr_d1, nbr_y1, x_new, y_new, n1, mode="reg")
    row = jnp.where(act, d_row, D1[idx, :])  # D symmetric: row == col
    D2 = D1.at[idx, :].set(row).at[:, idx].set(row)
    y2 = y1.at[idx].set(jnp.where(act, y_new, y1[idx]))
    own_neg, own_idx = jax.lax.top_k(-d_row, k)
    own_d = -own_neg
    own_y = y2[own_idx]
    own_y = jnp.where(own_d >= BIG, y_new, own_y)
    new_aid = _next_aid(aid1, jnp.zeros((), jnp.int32), n1,
                        jnp.int32(cap))
    own_a = jnp.where(own_d >= BIG, 0, aid1[own_idx]).astype(jnp.int32)
    enters1 = live1 & (d_row < nbr_d1[:, -1])
    nbr_a_m = stream._merge_aid(nbr_d1, nbr_a1,
                                jnp.where(enters1, d_row, BIG), new_aid,
                                nbr_d_m)
    new_state = RegStreamState(
        X=X1.at[idx].set(jnp.where(act, x_new, X1[idx])),
        y=y2,
        D=D2,
        nbr_d=jnp.where(act, nbr_d_m.at[idx].set(own_d), nbr_d1),
        nbr_y=jnp.where(act, nbr_y_m.at[idx].set(own_y), nbr_y1),
        n=n1 + act,
        head=state.head,
        aid=aid1.at[idx].set(jnp.where(act, new_aid, aid1[idx])),
        wrap=state.wrap,
        nbr_a=jnp.where(act, nbr_a_m.at[idx].set(own_a), nbr_a1),
    )

    # price the observed label against the pre-learn window
    p = _price(d_row, y1[own_idx], y_new, tau, k=k, live=live1,
               nbr_d=nbr_d1, nbr_y=nbr_y1, y=y1, n=n1)
    p = jnp.where(act, p, jnp.asarray(jnp.nan, dtype=X1.dtype))
    return new_state, p


def _observe_sliding(state: RegStreamState, x_new, y_new, tau, window, *, k):
    """Evict-if-full then observe: one fixed-shape sliding-window step.

    ``window`` is a traced scalar (per-tenant window sizes never
    retrace). The fused ``_sliding_step`` with every lane active.
    """
    return _sliding_step(state, x_new, y_new, tau, window, True, k=k)


observe_sliding = functools.partial(
    jax.jit, static_argnames=("k",))(_observe_sliding)
#: Donating form of ``observe_sliding`` — same numerics, input deleted.
observe_sliding_donated = functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(0,))(_observe_sliding)


def repad(state: RegStreamState, capacity: int) -> RegStreamState:
    """The state at a larger ``capacity``, host-side, all live state
    kept.

    Shapes change, so jitted steps retrace — but only O(log n) times
    over a session's lifetime under the capacity-doubling schedule. The
    ring is normalized to linear order first (ring positions are
    modulus-bound, so they cannot survive a capacity change); every leaf
    is then padded with its inert fill. Not jittable.
    """
    extra = capacity - state.capacity
    state = stream.to_linear(state)
    return RegStreamState(
        X=jnp.pad(state.X, ((0, extra), (0, 0))),
        y=jnp.pad(state.y, (0, extra)),
        D=jnp.pad(state.D, ((0, extra), (0, extra)), constant_values=BIG),
        nbr_d=jnp.pad(state.nbr_d, ((0, extra), (0, 0)),
                      constant_values=BIG),
        nbr_y=jnp.pad(state.nbr_y, ((0, extra), (0, 0))),
        n=state.n,
        head=state.head,
        aid=jnp.pad(state.aid, (0, extra)),
        wrap=jnp.int32(capacity),
        nbr_a=jnp.pad(state.nbr_a, ((0, extra), (0, 0))),
    )


def grow(state: RegStreamState, factor: int = 2) -> RegStreamState:
    """Double (by default) capacity host-side: ``repad`` to
    ``factor`` times the capacity."""
    return repad(state, state.capacity * factor)


@functools.partial(jax.jit, static_argnames=("k",))
def intervals(state: RegStreamState, X_test, *, k, epsilon):
    """Prediction intervals (m, 2) at miscoverage ``epsilon``.

    ``epsilon`` is traced (one compile serves every level — it only feeds
    the sweep threshold, and a traced f32 rounds identically to the
    embedded constant). The state is read through its ``arrival_view``
    (O(cap) gather; ``D`` untouched), after which the computation is the
    historic linear one. Where the Pallas kernels are live (TPU, or
    interpret mode), the
    distance row + (a_i, b_i) update + critical points come fused from
    ``kops.interval_sweep``. Elsewhere the computation structurally
    mirrors ``regression.intervals_optimized`` (per-test ``lax.map``,
    vmapped ``_interval_ge``), so XLA emits the very same fused
    arithmetic and the results are bit-identical to the batch optimized
    path on the live window — the fully-batched form differs by ~1 ulp
    in the endpoints through different FMA contraction.
    """
    with jax.named_scope("gather"):
        Xg, yg, a_prime, upd, kth, kth_label, live = _arrival_stats(
            state, k=k)
    thresh = epsilon * (state.n + 1.0) - 1.0

    if kops.pallas_active(state.X.dtype):
        with jax.named_scope("query"):
            d = jnp.sqrt(jnp.maximum(kops.sq_dists(X_test, Xg), 0.0))
            dm = jnp.where(live[None, :], d, BIG)
            _, idx = jax.lax.top_k(-dm, k)
            a_test = -jnp.sum(yg[idx], axis=1) / k
        with jax.named_scope("sweep"):
            lo, hi = kops.interval_sweep(
                Xg, a_prime, kth, kth_label, live, X_test, a_test, k)

        def sweep(lo_r, hi_r):
            return jnp.stack(hull_sweep(lo_r, hi_r, lo_r > hi_r, thresh))

        with jax.named_scope("hull"):
            return jax.vmap(sweep)(lo, hi)

    def per_test(x_t):
        with jax.named_scope("query"):
            d_t = jnp.sqrt(jnp.maximum(
                kops.sq_dists(x_t[None], Xg)[0], 0.0))
            enters = live & (d_t < kth)
            # ``upd`` comes precomputed from the barriered stats block —
            # recomputing a_prime + kth_label/k here re-fuses with the
            # map body and rounds 1 ulp away from the batch path's bits
            a_vec = jnp.where(enters, upd, a_prime)
            b_vec = jnp.where(enters, -1.0 / k, 0.0)
            dm = jnp.where(live, d_t, BIG)
            _, idx = jax.lax.top_k(-dm, k)
            a = -jnp.sum(yg[idx]) / k
        with jax.named_scope("sweep"):
            lo, hi = jax.vmap(_interval_ge, in_axes=(0, 0, None))(
                a_vec, b_vec, a)
        with jax.named_scope("hull"):
            return jnp.stack(hull_sweep(lo, hi, (lo > hi) | ~live, thresh))

    return jax.lax.map(per_test, X_test)


@functools.partial(jax.jit, static_argnames=("k",))
def pvalues(state: RegStreamState, X_test, t_query, *, k):
    """Exact p-values (m, nq) at explicit query labels ``t_query``."""
    a_vec, b_vec, a, live = _ab_padded(state, X_test, k=k)
    ai = jnp.abs(a_vec[:, None, :] + b_vec[:, None, :]
                 * t_query[None, :, None])  # (m, nq, cap)
    at = jnp.abs(a[:, None] + t_query[None, :])  # (m, nq)
    cnt = jnp.sum(jnp.where(live[None, None, :], ai >= at[..., None], False),
                  axis=-1)
    return (cnt + 1.0) / (state.n + 1.0)


def poison_reg(state: RegStreamState):
    """The lanes of a stacked state whose features, labels or neighbour
    distances hold a non-finite value (NaN for the BIG-padded lists)."""
    bad_x = jnp.any(~jnp.isfinite(state.X), axis=(1, 2))
    bad_y = jnp.any(~jnp.isfinite(state.y), axis=1)
    bad_d = jnp.any(jnp.isnan(state.nbr_d), axis=(1, 2))
    return bad_x | bad_y | bad_d


#: the regression binding of ``core.engine.Engine``
MEASURE = Measure(
    name="regression", init=init, step=_sliding_step,
    step_compact=_sliding_step_compact, repad=repad,
    n_of=lambda s: s.n, X_of=lambda s: s.X, y_dtype=None,
    label_ok=lambda ys, n_labels, xp=jnp: xp.isfinite(ys),
    poison=poison_reg)


__all__ = ["RegStreamState", "init", "observe", "observe_donated",
           "observe_sliding", "observe_sliding_donated", "grow", "repad",
           "intervals", "pvalues", "poison_reg", "MEASURE"]
