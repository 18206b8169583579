"""Online CP: exchangeability martingales / IID testing (paper §9, App. C.5).

Vovk et al. (2003): observe a stream x_1, x_2, ...; at step n compute a
smoothed p-value for x_{n+1} against {x_1..x_n} (Algorithm 1), then *learn*
x_{n+1}. Betting functions turn the p-value stream into a martingale M_n
whose growth is evidence against exchangeability (change-point detection,
feature selection (Cherubin et al. 2018)).

Complexity (paper App. C.5): with standard k-NN CP the n-step stream costs
O(n^3); with this module's incremental&decremental k-NN it is O(n^2) —
each step is one O(n) update (the paper's headline online win).

The state is preallocated to a static capacity so the whole stream step is
one fixed-shape jitted function (no retracing as n grows) — the production
serving form of the paper's "adapting our optimizations to this setting is
trivial" remark.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

BIG = 1e30


# ---------------------------------------------------------------------------
# ring-buffer slot arithmetic
# ---------------------------------------------------------------------------
#
# The serving engines store their sliding window in a *circular* layout:
# a scalar ``head`` names the slot of the oldest live point and the live
# window occupies slots ``(head + i) % wrap`` for ``i in [0, n)``. The
# modulus ``wrap`` (<= the padded capacity) is part of the state: a
# sliding engine whose window statically bounds occupancy runs its ring
# inside the leading ``[:wrap]`` block of every leaf, so per-tick cost
# scales with the window while the padded capacity can stay larger.
# Slots at or beyond ``wrap`` are never live. Evicting the oldest point
# is then a head advance (plus an O(cap) list repair) — no positional
# compaction ever moves the (cap, cap) distance matrix. Arrival order,
# which the tie rules rest on, is tracked two ways: the *age* of a slot
# is derived from ``head`` (0 = oldest), and an explicit per-slot
# arrival-id vector ``aid`` (a monotone counter stamped at insert)
# provides the total order the regression tick breaks distance ties
# with (the labeled backfill's pick and the new point's own list).
# ``head == 0`` with no wrap-around is exactly the historic linear
# layout, and every function below degenerates to the old bits there.


def ring_age(cap: int, head, wrap=None):
    """(cap,) arrival age of each slot under a ring at ``head`` with
    modulus ``wrap`` (default: the full capacity): the oldest live slot
    has age 0; ages ``>= n`` are not live; slots ``>= wrap`` get the
    sentinel age ``cap`` (never live, since n <= wrap <= cap). ``head``
    and ``wrap`` may be traced."""
    idx = jnp.arange(cap, dtype=jnp.int32)
    if wrap is None:
        return jnp.where(idx >= head, idx - head, idx - head + cap)
    wrap = jnp.asarray(wrap, jnp.int32)
    raw = jnp.where(idx >= head, idx - head, idx - head + wrap)
    return jnp.where(idx < wrap, raw, cap)


def ring_live(cap: int, head, n, wrap=None):
    """(cap,) live mask of a ring holding ``n`` points at ``head``."""
    return ring_age(cap, head, wrap) < n


def ring_slots(cap: int, head, wrap=None):
    """(cap,) slot index of each arrival rank: entry i is the slot of
    the i-th oldest point, ``(head + i) % wrap`` — the gather
    permutation from ring layout to the historic linear (arrival-order)
    layout. Entries at ranks >= wrap alias earlier slots; callers mask
    everything at rank >= n, so the aliases never surface."""
    s = jnp.arange(cap, dtype=jnp.int32) + jnp.asarray(head, jnp.int32)
    m = jnp.asarray(cap if wrap is None else wrap, jnp.int32)
    return jnp.where(s >= m, s - m, s)


def ring_mod(v, m):
    """``v % m`` for a traced scalar already in ``[0, 2 m)`` — the ring
    steps' head/insert-slot arithmetic (one compare+subtract, no rem)."""
    return jnp.where(v >= m, v - m, v)


def next_aid(aid, head, n, wrap):
    """Arrival id for the next insert: one past the newest live slot's
    (the per-slot counters are strictly increasing with recency, so the
    newest holds the max). An empty window restarts at 0 — ids only
    order the *live* points. The int32 counter is allowed to wrap: every
    consumer compares ids as wraparound *differences* from the oldest
    live id (``drop_backfill``), which stay exact because live ids span
    at most one window of inserts (far below 2^31)."""
    newest = ring_mod(head + n - 1 + wrap * (n == 0).astype(n.dtype), wrap)
    return jnp.where(n > 0, aid[newest] + 1, 0)


def slot_set(a, idx, v, when=True):
    """``a.at[idx].set(v)`` for one slot ``idx`` of the leading (slot)
    axis, as a select (left unchanged where ``when`` is False): the
    same bits, and under the engines' ``vmap`` an elementwise pass in
    whatever layout the leaf has. A per-tenant scatter into a (tenants,
    cap, m) leaf that the chip keeps with the window minor re-tiles the
    whole leaf twice a tick; the select fuses with the tick's gating
    select over the same leaf."""
    hit = (jnp.arange(a.shape[0]) == idx) & when
    return jnp.where(hit.reshape((-1,) + (1,) * (a.ndim - 1)),
                     jnp.asarray(v, a.dtype), a)


def cshift(a, s, fill):
    """Conditionally drop the leading row: shift rows up by ``s`` (a
    traced 0/1 scalar) with ``fill`` entering at the tail — one padded
    dynamic slice, bitwise identity when ``s == 0``. The compaction
    primitive of the serving engines' fused sliding step."""
    pad = [(0, 1)] + [(0, 0)] * (a.ndim - 1)
    ap = jnp.pad(a, pad, constant_values=fill)
    start = (s,) + (jnp.int32(0),) * (a.ndim - 1)
    return jax.lax.dynamic_slice(ap, start, a.shape)


def drop_backfill_core(L, es, cand, Ds, *, k):
    """Shared decremental list repair for the serving engines' eviction.

    For each row: drop the first slot of the ascending k-best list ``L``
    holding the evicted distance ``es`` (the evicted point has the
    lowest arrival index, so on ties it occupies the first slot holding
    its value), then backfill the new k-th best by multiset rank over
    the stored distances: the k-1 survivors hold every remaining
    candidate value below their max t' plus m' occurrences of t' itself,
    so the next value is t' again if the window (``Ds`` masked by
    ``cand``) holds more than m' occurrences of it, else the smallest
    stored distance above t'. Every output is a selected stored value —
    bit-identical to a full re-sort, a fraction of the compute.

    Returns ``(newL, pos0, cols, b, tprime, mprime)`` so label-carrying
    callers (the regression state) can mirror the move on a parallel
    label matrix. Both exactness proofs (classification and regression)
    rest on this one function.
    """
    cap = L.shape[0]
    pos0 = jnp.sum((L < es[:, None]).astype(jnp.int32), axis=1)
    Lup = jnp.concatenate([L[:, 1:], jnp.full_like(L[:, :1], BIG)], axis=1)
    # t' = max of the k-1 survivors; m' = its multiplicity among them
    if k >= 2:
        tprime = jnp.where(pos0 <= k - 2, L[:, k - 1], L[:, k - 2])
    else:
        # empty survivor list: below every distance (distances are >= 0)
        tprime = jnp.full((cap,), -1.0, L.dtype)
    mprime = (jnp.sum((L == tprime[:, None]).astype(jnp.int32), axis=1)
              - (es == tprime).astype(jnp.int32))
    # one variadic reduce computes the count and the min together — a
    # single fused pass over the stored (cap, cap) distances instead of
    # two (integer sum and f32 min are order-free, so the fused pass is
    # bit-identical to separate reductions). This pass is the whole
    # per-tick cost of eviction under the ring layout.
    cnt, gtmin = jax.lax.reduce(
        (jnp.where(cand & (Ds == tprime[:, None]), 1, 0).astype(jnp.int32),
         jnp.where(cand & (Ds > tprime[:, None]), Ds, BIG)),
        (jnp.int32(0), jnp.asarray(BIG, Ds.dtype)),
        lambda acc, x: (acc[0] + x[0], jnp.minimum(acc[1], x[1])),
        (1,))
    b = jnp.where(cnt > mprime, tprime, gtmin)
    cols = jnp.arange(k)
    newL = jnp.where(cols[None, :] < pos0[:, None], L,
                     jnp.where(cols[None, :] < k - 1, Lup, b[:, None]))
    return newL, pos0, cols, b, tprime, mprime


def drop_backfill(L, es, cand, Ds, aff, *, k, Ly=None, La=None, ys=None,
                  aid=None, aid0=None):
    """The one shared decremental list repair of both serving engines.

    For each row flagged in ``aff``: drop the first slot of the ascending
    k-best list ``L`` holding that row's evicted distance ``es`` and
    backfill the new k-th best by multiset rank over the stored distances
    (``drop_backfill_core`` above). Rows not flagged pass through
    bitwise untouched. Classification (``Ly is None``) repairs distances
    only and returns ``newL``.

    The labeled form (regression: pass ``Ly``/``La``/``ys``/``aid``/
    ``aid0``) also repairs the parallel
    neighbour-*label* lists ``Ly`` and the neighbour-*arrival-id* lists
    ``La`` and returns ``(newL, newLy, newLa)``. The backfill label
    must follow fit's ties-toward-*earliest-arrival* order: among the
    candidate columns at the backfill distance b, the occurrences the
    surviving list already holds are the earliest arrivals, so the
    label comes from the next-earliest — the candidate with the
    smallest arrival id above the largest id the list already stores at
    that distance (read from ``La``; -1, i.e. below every live id, when
    the backfill value is new to the list). Arrival order is read from
    the per-slot arrival ids ``aid`` (strictly increasing with recency,
    distinct), NOT from the slot position — under the ring layout the
    two disagree across the wrap-around seam. Every id comparison is a
    wraparound int32 *difference* from ``aid0`` (the evicted — globally
    earliest — live id): live ids span at most one window of inserts,
    far below 2^31, so the differences stay exact even after the raw
    counters overflow on a long-lived stream. The pick needs no sort and
    no gather: ``cand`` holds live slots only, over which the ids grow
    strictly with arrival, so the earliest valid column is the one with
    the smallest id difference. One variadic min over the row finds that
    difference and carries the column's label beside it (a selection,
    so labels keep their bits, ``-0.0`` included); the id is ``aid0``
    plus the difference. The layout never enters: ring and linear
    callers pass the same arguments.
    """
    newL, pos0, cols, b, tprime, mprime = drop_backfill_core(
        L, es, cand, Ds, k=k)
    if Ly is None:
        return jnp.where(aff[:, None], newL, L)

    # largest arrival id the list already holds at the backfill value
    # (as a wraparound difference from the anchor ``aid0``). When
    # b == t', the list's occurrences of t' are the earliest arrivals
    # at that distance, so anything above ``thr`` is new; the dropped
    # (evicted) occurrence may contribute to the max but it rebases to
    # exactly 0, below every surviving id. When b == gtmin the list
    # holds no occurrence of b (gtmin > t' strictly) and the pick is
    # simply the earliest.
    aid0 = jnp.asarray(aid0, jnp.int32)
    rel_La = La.astype(jnp.int32) - aid0  # int32 wrap-subtract
    thr = jnp.where(
        b == tprime,
        jnp.max(jnp.where(L == tprime[:, None], rel_La, -1), axis=1), -1)
    rel_aid = (aid.astype(jnp.int32) - aid0)[None, :]
    valid = cand & (Ds == b[:, None]) & (rel_aid > thr[:, None])
    # the earliest valid column and its label in one pass: the smallest
    # id difference (distinct over live slots) carries its label along
    none = jnp.int32(jnp.iinfo(jnp.int32).max)
    rel_min, yb = jax.lax.reduce(
        (jnp.where(valid, rel_aid, none),
         jnp.where(valid, ys[None, :], jnp.zeros((), ys.dtype))),
        (none, jnp.zeros((), ys.dtype)),
        lambda acc, x: (jnp.minimum(acc[0], x[0]),
                        jnp.where(x[0] < acc[0], x[1], acc[1])),
        (1,))
    ab = aid0 + rel_min  # rows where b >= BIG pick garbage, fixed up below

    Lyup = jnp.concatenate([Ly[:, 1:], Ly[:, :1]], axis=1)
    newLy = jnp.where(cols[None, :] < pos0[:, None], Ly,
                      jnp.where(cols[None, :] < k - 1, Lyup, yb[:, None]))
    Laup = jnp.concatenate([La[:, 1:], La[:, :1]], axis=1)
    newLa = jnp.where(cols[None, :] < pos0[:, None], La,
                      jnp.where(cols[None, :] < k - 1, Laup, ab[:, None]))
    # missing-neighbour slots carry the row's own label (fit convention)
    # and the neutral arrival id 0
    newLy = jnp.where(newL >= BIG, ys[:, None], newLy)
    newLa = jnp.where(newL >= BIG, 0, newLa)
    return (jnp.where(aff[:, None], newL, L),
            jnp.where(aff[:, None], newLy, Ly),
            jnp.where(aff[:, None], newLa, La))


@jax.tree_util.register_pytree_node_class
@dataclass
class OnlineKnnState:
    """Capacity-padded incremental simplified-k-NN CP state.

    Rows >= n are inert: distances to them are BIG, their scores never
    counted. ``best`` holds each live point's k best same-label distances.
    """

    X: jnp.ndarray  # (cap, p)
    y: jnp.ndarray  # (cap,)
    best: jnp.ndarray  # (cap, k) ascending same-label distances, BIG-padded
    n: jnp.ndarray  # () live count

    def tree_flatten(self):
        return ((self.X, self.y, self.best, self.n), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init(capacity: int, p: int, k: int, dtype=jnp.float32) -> OnlineKnnState:
    return OnlineKnnState(
        X=jnp.zeros((capacity, p), dtype=dtype),
        y=jnp.full((capacity,), -1, dtype=jnp.int32),
        best=jnp.full((capacity, k), BIG, dtype=dtype),
        n=jnp.zeros((), dtype=jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("k",))
def observe(state: OnlineKnnState, x_new, y_new, tau, *, k):
    """One online step: smoothed p-value for (x_new, y_new), then learn it.

    Returns (new_state, p_value). O(capacity) — O(n) amortized on TPU since
    inert rows are masked arithmetic, not skipped.
    """
    new_state, p, _ = _observe_impl(state, x_new, y_new, tau, k=k)
    return new_state, p


@functools.partial(jax.jit, static_argnames=("k",))
def observe_with_dists(state: OnlineKnnState, x_new, y_new, tau, *, k,
                       head=None, wrap=None):
    """``observe`` that also returns the live-masked distance vector.

    Identical arithmetic to ``observe`` (same p-value bits); the extra
    return is the (cap,) vector of distances from ``x_new`` to each live
    row, BIG on inert rows — callers that maintain auxiliary per-pair
    state (``repro.serving.session`` keeps the pairwise distance matrix
    for exact decremental eviction) reuse it instead of recomputing.

    ``head`` (traced scalar, default linear layout) switches the state
    to ring-buffer slot semantics: the live window occupies slots
    ``(head + i) % wrap`` (modulus ``wrap``, default the capacity) and
    the new point lands at slot ``(head + n) % wrap`` instead of slot
    ``n``. The p-value is a layout-free reduction over the same live
    multiset, so its bits do not depend on ``head``/``wrap``.
    """
    return _observe_impl(state, x_new, y_new, tau, k=k, head=head,
                         wrap=wrap)


def _observe_impl(state: OnlineKnnState, x_new, y_new, tau, *, k,
                  head=None, wrap=None):
    cap = state.X.shape[0]
    if head is None:
        live = jnp.arange(cap) < state.n
        # the clamp is bit-neutral under the n < cap precondition; it
        # keeps a gated caller's discarded write in bounds at n == cap
        # (an out-of-bounds dynamic-update start is implementation-
        # defined once XLA fuses it with a pad — it can read the fill)
        idx = jnp.minimum(state.n, cap - 1)
        head = jnp.zeros((), jnp.int32)
    else:
        live = ring_live(cap, head, state.n, wrap)
        m = jnp.asarray(cap if wrap is None else wrap, jnp.int32)
        tail = head + state.n
        idx = jnp.where(tail >= m, tail - m, tail)
    # fused distance row + same-label k-best merge: one Pallas pass on
    # TPU; the CPU/f64 reference is expression-identical to the historic
    # inline code, so the stream's p-value bits are unchanged
    d, merged, _ = kops.stream_update(
        state.X, state.y, state.best, None, x_new, y_new, state.n,
        mode="class", head=head, wrap=wrap)
    same = (state.y == y_new) & live

    # candidate score: sum of k best same-label distances
    cand = jnp.where(same, d, BIG)
    alpha = jnp.sum(-jax.lax.top_k(-cand, k)[0])

    # provisional -> updated scores for live points (O(1) each);
    # cancellation-safe base + (kth or d) form, never subtracting BIG
    base = jnp.sum(state.best[:, :-1], axis=1)
    kth = state.best[:, -1]
    upd = same & (d < kth)
    alphas = base + jnp.where(upd, d, kth)

    # smoothed p-value over live points + the candidate itself; the
    # astype is a no-op at f32/f64 but pins sub-f32 state dtypes (the
    # int/float promotion otherwise widens p to f32, which breaks the
    # engine's masked cond whose skip branch is a state-dtype NaN)
    gt = jnp.sum(jnp.where(live, alphas > alpha, False))
    eq = jnp.sum(jnp.where(live, alphas == alpha, False))
    p = ((gt + tau * (eq + 1.0)) / (state.n + 1.0)).astype(state.X.dtype)

    # learn: the merged lists come from the fused pass; the new row's own
    # list is the k best same-label distances seen so far
    own = jnp.sort(-jax.lax.top_k(-cand, k)[0])
    new_state = OnlineKnnState(
        X=slot_set(state.X, idx, x_new),
        y=slot_set(state.y, idx, y_new),
        best=slot_set(merged, idx, own),
        n=state.n + 1,
    )
    return new_state, p, d


# ---------------------------------------------------------------------------
# betting martingales over the p-value stream
# ---------------------------------------------------------------------------


def power_martingale_increment(p, epsilon=0.92):
    """Power betting function: f(p) = eps * p^(eps-1); integral over [0,1]=1."""
    return epsilon * jnp.power(jnp.maximum(p, 1e-12), epsilon - 1.0)


@jax.jit
def simple_mixture_log_martingale(pvals: jnp.ndarray) -> jnp.ndarray:
    """Log of the simple-mixture martingale: integral over eps of the power
    martingale, approximated on a grid (valid as a mixture of martingales).
    Returns log M_n for each prefix n: (T,)."""
    eps_grid = jnp.linspace(0.05, 0.95, 19)
    # log increments per (eps, t)
    logf = (jnp.log(eps_grid)[:, None]
            + (eps_grid[:, None] - 1.0) * jnp.log(jnp.maximum(pvals, 1e-12))[None, :])
    logM = jnp.cumsum(logf, axis=1)  # per-eps martingale paths
    return jax.scipy.special.logsumexp(logM, axis=0) - jnp.log(len(eps_grid))


def run_stream(X, y, *, k, key, capacity=None):
    """Feed a full stream; returns (pvalues (T,), log mixture martingale)."""
    T, p_dim = X.shape
    cap = capacity or T
    state = init(cap, p_dim, k, dtype=X.dtype)
    taus = jax.random.uniform(key, (T,), dtype=X.dtype)

    def step(st, inp):
        x, yv, tau = inp
        st, pv = observe(st, x, yv, tau, k=k)
        return st, pv

    _, pvals = jax.lax.scan(step, state, (X, y, taus))
    return pvals, simple_mixture_log_martingale(pvals)


__all__ = ["OnlineKnnState", "init", "observe", "observe_with_dists",
           "run_stream", "power_martingale_increment",
           "simple_mixture_log_martingale", "ring_age", "ring_live",
           "ring_slots", "slot_set", "cshift", "drop_backfill",
           "drop_backfill_core", "BIG"]
