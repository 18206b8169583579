"""Exact incremental/decremental k-NN regression state (paper Section 8.1).

``core.regression.fit`` precomputes, per training point, the k nearest
neighbour labels (ordered nearest-first), the k-th neighbour distance and
label — the statistics behind the O(1)-per-point ``ab_optimized`` update.
This module maintains those statistics *online*: ``observe`` learns one
point and ``evict`` forgets one, both keeping every derived quantity
**bit-identical** to ``regression.fit`` refit-from-scratch on the live
window (property-tested in ``tests/test_regression_stream.py``).

The trick is the same as ``serving/session.py`` for classification: keep
the live pairwise-distance matrix ``D`` (one row+column per ``observe`` —
the row is needed for the online p-value anyway), so decremental removal
backfills k-best lists from stored exact distances instead of re-deriving
them. Storage is the same **ring buffer**: ``head`` names the slot of the
oldest live point, the window occupies slots ``(head + i) % cap``, and
``evict_oldest`` is a head advance plus an O(cap·k) list repair — the
(cap, cap) ``D`` is never positionally compacted. ``aid`` stamps each
slot with a monotone arrival id; it is the tie-break key wherever
arrival order (not slot order) decides between equal distances.
Bit-exactness additionally needs three invariants special to the
regression measure, where neighbour *labels* (not just distances) enter
the scores:

* ``nbr_d``/``nbr_y`` store each point's k nearest distances and labels in
  ``fit``'s exact order (ascending distance, ties toward the *earliest
  arrival*: a new arrival is inserted strictly below equal distances — a
  stable argsort with the candidate appended last reproduces ``top_k``'s
  tie rule once rows are read in arrival order);
* the label attached to a BIG (missing-neighbour) slot of row i is
  ``y_i`` — exactly what ``fit`` produces at window size n == k, where the
  only BIG entry in a row is its own masked diagonal;
* distance rows/columns are computed with the very ``kops.sq_dists``
  expression ``fit`` uses, which is bitwise row-decomposable and padding-
  invariant on the supported backends (checked by the property tests).

Where a computation on the tick is arrival-order sensitive (the new
point's own top-k list, whose equal-distance neighbours must be taken
oldest-first, and the backfill's pick), it stays in slot space: the
arrival ids order the live window by arrival, so a sort keyed on
(distance, id) or a min over ids breaks the ties, and the (cap,) vectors
are never permuted into arrival order. The read paths still gather
through ``ring_slots`` into arrival order, after which the historic
linear-layout expressions run unchanged.

All arrays are capacity-padded and fixed-shape, so every update is one
jit-stable dispatch and vmaps across tenants (``repro.regression.engine``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.online import (drop_backfill, next_aid as _next_aid,
                               ring_live, ring_mod as _mod_cap,
                               ring_slots, slot_set)
from repro.core.regression import BIG, KnnRegState
from repro.kernels import ops as kops


@jax.tree_util.register_pytree_node_class
@dataclass
class RegStreamState:
    """Capacity-padded streaming k-NN regression state (ring layout).

    Slots ``(head + i) % cap``, ``i in [0, n)`` are live in arrival
    order. Never-written slots hold zeros in ``X``/``y`` (zero rows keep
    ``sq_dists`` padding-invariant) and BIG in ``D``/``nbr_d``; ``D`` is
    BIG on the diagonal, mirroring ``fit``'s self-exclusion mask. Slots
    that have *left* the window may hold stale finite values — every
    reader masks by ring liveness (or gathers the live window into
    arrival order via ``arrival_view``), never by slot position.
    """

    X: jnp.ndarray  # (cap, p)
    y: jnp.ndarray  # (cap,)
    D: jnp.ndarray  # (cap, cap) live pairwise distances, BIG elsewhere
    nbr_d: jnp.ndarray  # (cap, k) k nearest distances, ascending
    nbr_y: jnp.ndarray  # (cap, k) their labels, same order
    n: jnp.ndarray  # () live count
    head: jnp.ndarray  # () slot of the oldest live point (ring start)
    aid: jnp.ndarray  # (cap,) per-slot arrival ids (monotone at insert)
    wrap: jnp.ndarray  # () ring modulus (<= cap; slots >= wrap inert)
    nbr_a: jnp.ndarray  # (cap, k) the neighbours' arrival ids (0 at BIG)

    def tree_flatten(self):
        return ((self.X, self.y, self.D, self.nbr_d, self.nbr_y,
                 self.n, self.head, self.aid, self.wrap,
                 self.nbr_a), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.D.shape[-1]

    @property
    def k(self) -> int:
        return self.nbr_d.shape[-1]


def init(capacity: int, p: int, k: int, dtype=jnp.float32,
         wrap: int | None = None) -> RegStreamState:
    """Fresh empty state. ``wrap`` (default: the capacity) is the ring
    modulus — a sliding engine whose window statically bounds occupancy
    confines the ring to the leading ``[:wrap]`` block of every leaf."""
    if capacity < k:
        raise ValueError(
            f"capacity {capacity} < k {k}: the k-best machinery (top_k) "
            "needs at least k rows")
    return RegStreamState(
        X=jnp.zeros((capacity, p), dtype=dtype),
        y=jnp.zeros((capacity,), dtype=dtype),
        D=jnp.full((capacity, capacity), BIG, dtype=dtype),
        nbr_d=jnp.full((capacity, k), BIG, dtype=dtype),
        nbr_y=jnp.zeros((capacity, k), dtype=dtype),
        n=jnp.zeros((), dtype=jnp.int32),
        head=jnp.zeros((), dtype=jnp.int32),
        aid=jnp.zeros((capacity,), dtype=jnp.int32),
        wrap=jnp.asarray(capacity if wrap is None else wrap, jnp.int32),
        nbr_a=jnp.zeros((capacity, k), dtype=jnp.int32),
    )


def _merge_aid(nbr_d_pre, nbr_a, cand_d, new_aid, merged_d):
    """Mirror the kernel's ordered k-best merge on the arrival-id lists.

    The kernel (``kops.stream_update``) merges the candidate into the
    distance/label lists; the id rider replays the same branch-free
    insert from the pre-merge distances: ``pos = #{j : L[j] <= c}``
    places the candidate strictly after equal values, every slot below
    keeps its id, the insert slot takes the new point's id, everything
    above shifts. BIG (missing-neighbour) slots carry the neutral id 0.
    """
    k = nbr_d_pre.shape[1]
    pos = jnp.sum((nbr_d_pre <= cand_d[:, None]).astype(jnp.int32),
                  axis=1, keepdims=True)
    cols = jnp.arange(k)[None, :]
    Ash = jnp.concatenate([nbr_a[:, :1], nbr_a[:, :k - 1]], axis=1)
    newA = jnp.where(cols < pos, nbr_a,
                     jnp.where(cols == pos,
                               jnp.asarray(new_aid, jnp.int32), Ash))
    return jnp.where(merged_d >= BIG, 0, newA)


def _arrival_leaves(state: RegStreamState):
    """(X, y, nbr_d, nbr_y) gathered into arrival order with the linear
    layout's inert fills (0 / 0 / BIG / 0) beyond ``n`` — bit-identical
    to the historic positional storage, stale slots scrubbed. O(cap·p)
    gathers; ``D`` is deliberately excluded (the read paths never touch
    it, and its gather is the O(cap^2) cost the ring layout avoids)."""
    cap = state.capacity
    slots = ring_slots(cap, state.head, state.wrap)
    live = jnp.arange(cap) < state.n
    X = jnp.where(live[:, None], state.X[slots], 0.0)
    y = jnp.where(live, state.y[slots], 0.0)
    nbr_d = jnp.where(live[:, None], state.nbr_d[slots], BIG)
    nbr_y = jnp.where(live[:, None], state.nbr_y[slots], 0.0)
    return X, y, nbr_d, nbr_y


def arrival_view(state: RegStreamState) -> RegStreamState:
    """The state with every O(cap) leaf in arrival order (head == 0).

    ``D`` is passed through untouched (still ring-indexed!) — callers of
    this view are the read paths, which never consult ``D``. For a full
    linear normalization including ``D`` use ``to_linear``."""
    X, y, nbr_d, nbr_y = _arrival_leaves(state)
    cap = state.capacity
    slots = ring_slots(cap, state.head, state.wrap)
    live = jnp.arange(cap) < state.n
    return RegStreamState(X, y, state.D, nbr_d, nbr_y, state.n,
                          jnp.zeros((), jnp.int32),
                          jnp.where(live, state.aid[slots], 0),
                          jnp.int32(cap),
                          jnp.where(live[:, None], state.nbr_a[slots], 0))


@jax.jit
def to_linear(state: RegStreamState) -> RegStreamState:
    """Full linear-layout normalization, ``D`` included (O(cap^2) gather).

    Leaf-for-leaf bit-identical (arrival ids included: the absolute
    counters are preserved, since the neighbour-id lists ``nbr_a``
    reference them by value) to the same stream served through the
    historic linear layout — the equivalence the exactness tests
    assert. Used by ``grow`` and the tests, never on the serving
    tick."""
    view = arrival_view(state)
    cap = state.capacity
    slots = ring_slots(cap, state.head, state.wrap)
    live = jnp.arange(cap) < state.n
    D = jnp.where(live[:, None] & live[None, :],
                  state.D[slots][:, slots], BIG)
    return RegStreamState(view.X, view.y, D, view.nbr_d, view.nbr_y,
                          state.n, view.head, view.aid, view.wrap,
                          view.nbr_a)


def arrival_stats(state: RegStreamState, *, k):
    """Arrival-ordered (X, y, a_prime, upd, kth, kth_label, live) — the
    one shared gather behind every regression read path.

    The per-row derived statistics are computed *in slot space* on the
    raw leaves — the exact expressions of the historic linear path and
    of ``fit`` — and only then gathered into arrival order. The
    optimization barrier between the arithmetic and the gather pins the
    fusion boundary: XLA compiles the reduce+divide+subtract chain in
    its own small computation (the shape in which its accumulation
    order matches ``fit``'s — a big consumer graph can re-vectorize the
    reduce and round odd lanes 1 ulp apart), and the gathers after the
    barrier are bit-preserving moves. This is what keeps the served
    reads bit-identical to the batch path regardless of the surrounding
    graph (session jit or the engine's mapped jit). Rows beyond ``n``
    carry the linear layout's inert fills.
    """
    cap = state.capacity
    a_prime_s = state.y - jnp.sum(state.nbr_y, axis=1) / k
    upd_s = a_prime_s + state.nbr_y[:, -1] / k
    a_prime_s, upd_s = jax.lax.optimization_barrier((a_prime_s, upd_s))
    slots = ring_slots(cap, state.head, state.wrap)
    live = jnp.arange(cap) < state.n
    X = jnp.where(live[:, None], state.X[slots], 0.0)
    y = jnp.where(live, state.y[slots], 0.0)
    a_prime = jnp.where(live, a_prime_s[slots], 0.0)
    upd = jnp.where(live, upd_s[slots], 0.0)
    kth = jnp.where(live, state.nbr_d[:, -1][slots], BIG)
    kth_label = jnp.where(live, state.nbr_y[:, -1][slots], 0.0)
    return X, y, a_prime, upd, kth, kth_label, live


@functools.partial(jax.jit, static_argnames=("k",))
def state_view(state: RegStreamState, *, k) -> KnnRegState:
    """The capacity-padded ``KnnRegState`` this stream state encodes.

    Rows come out in arrival order (ring gathered); live rows carry
    exactly ``regression.fit``'s bits (once n >= k); rows beyond ``n``
    are inert fills and must be masked by the reader. Jitted on
    purpose: ``fit`` computes ``a_prime`` inside jit, and XLA's fused
    sum/divide/subtract rounds differently from the eager op-by-op
    dispatch — bit-parity needs the same compilation path; see
    ``arrival_stats`` for why the stats are computed in slot space
    behind an optimization barrier.
    """
    X, y, a_prime, _, kth_d, kth_y, _ = arrival_stats(state, k=k)
    return KnnRegState(X, y, a_prime, kth_d, kth_y)


def _total_order_key(v):
    """Signed integers that order like the floats ``v`` under IEEE total
    order (-0.0 before +0.0), the order ``top_k`` compares in; the map
    is its own inverse on the bits."""
    ity = jnp.dtype(f"int{8 * v.dtype.itemsize}")
    bits = jax.lax.bitcast_convert_type(v, ity)
    return bits ^ ((bits >> (8 * v.dtype.itemsize - 1))
                   & jnp.iinfo(ity).max)


def _own_list(state: RegStreamState, d_row, y_new, *, k):
    """The new point's own (distances, labels) k-NN list.

    ``fit`` takes the k nearest in ascending distance with ties toward
    the earliest arrival, as ``top_k`` over the row in arrival order
    does. Here the row stays in slot order: over the live window the
    arrival ids grow strictly with arrival (as wraparound differences
    from the oldest's), so one two-key sort on (distance in ``top_k``'s
    total order, id difference) puts the same elements in the same
    order, carrying each slot's label. Slots off the live window sort
    last as BIG with label 0, the linear path's inert fills (garbage
    labels of stale slots must not leak into the degenerate n < k sums).
    Returns ``(own_d, own_y, y_sel, own_a)`` where ``y_sel`` are the
    selected *pre-learn* labels (the pricing path's ``a`` statistic) and
    ``own_a`` the selected neighbours' arrival ids (0 at BIG slots).
    ``own_y`` is ``y_sel`` with BIG slots relabelled: the new point's
    own slot is rank n, off the window, so its label never enters.
    """
    cap = state.capacity
    live = ring_live(cap, state.head, state.n, state.wrap)
    aid0 = state.aid[state.head]
    key, rel, y_sel = jax.lax.sort(
        (_total_order_key(jnp.where(live, d_row, BIG)),
         jnp.where(live, state.aid - aid0, jnp.iinfo(jnp.int32).max),
         jnp.where(live, state.y, 0.0)),
        num_keys=2, is_stable=False)  # the keys of live slots are distinct
    own_d = jax.lax.bitcast_convert_type(_total_order_key(key[:k]),
                                         d_row.dtype)
    y_sel = y_sel[:k]
    # missing-neighbour slots carry the row's own label (fit convention:
    # at n == k the one BIG entry is the masked self-diagonal) and the
    # neutral arrival id 0
    own_y = jnp.where(own_d >= BIG, y_new, y_sel)
    own_a = jnp.where(own_d >= BIG, 0, aid0 + rel[:k])
    return own_d, own_y, y_sel, own_a


def _observe(state: RegStreamState, x_new, y_new, *, k):
    """Learn one example in O(cap k): the paper's incremental update.

    Returns ``(new_state, d_row)`` — ``d_row`` is the (cap,) vector of
    distances from ``x_new`` to each live slot (BIG elsewhere), for
    callers that price the point before learning it (``session.observe``).
    The new point lands at ring slot ``(head + n) % wrap``.
    Precondition: n < wrap (callers grow or evict first).
    """
    cap = state.capacity
    idx = _mod_cap(state.head + state.n, state.wrap)
    y_new = jnp.asarray(y_new, state.y.dtype)

    # fused distance row + gated ordered merge into every live row's
    # (nbr_d, nbr_y) list — one Pallas pass on TPU; the CPU/f64 reference
    # is expression-identical to the historic inline code (strict d < kth
    # gate, stable-argsort insert-after-equals tie rule, BIG slots carry
    # the row's own label), so streaming bits vs ``fit`` are unchanged
    d_row, nbr_d, nbr_y = kops.stream_update(
        state.X, state.y, state.nbr_d, state.nbr_y, x_new, y_new,
        state.n, mode="reg", head=state.head, wrap=state.wrap)
    new_aid = _next_aid(state.aid, state.head, state.n, state.wrap)
    live = ring_live(cap, state.head, state.n, state.wrap)
    enters = live & (d_row < state.nbr_d[:, -1])
    cand_d = jnp.where(enters, d_row, BIG)
    nbr_a = _merge_aid(state.nbr_d, state.nbr_a, cand_d, new_aid, nbr_d)
    # one row + one column of D: under a donating jit these two updates
    # lower to in-place dynamic-update-slices — O(cap) HBM traffic, not
    # an O(cap^2) copy of the matrix
    D = state.D.at[idx, :].set(d_row).at[:, idx].set(d_row)

    own_d, own_y, _, own_a = _own_list(state, d_row, y_new, k=k)

    new_state = RegStreamState(
        X=slot_set(state.X, idx, x_new),
        y=slot_set(state.y, idx, y_new),
        D=D,
        nbr_d=slot_set(nbr_d, idx, own_d),
        nbr_y=slot_set(nbr_y, idx, own_y),
        n=state.n + 1,
        head=state.head,
        aid=state.aid.at[idx].set(new_aid),
        wrap=state.wrap,
        nbr_a=slot_set(nbr_a, idx, own_a),
    )
    return new_state, d_row


observe = functools.partial(jax.jit, static_argnames=("k",))(_observe)
#: ``observe`` whose input state is donated: the capacity-padded buffers
#: (most importantly the (cap, cap) ``D``) are updated in place instead of
#: copied. The input state is DELETED by the call — reusing it afterwards
#: raises ``RuntimeError: Array has been deleted``. Numerics are identical
#: to ``observe``.
observe_donated = functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(0,))(_observe)


def _evict(state: RegStreamState, i, *, k) -> RegStreamState:
    """Forget the i-th *oldest* live point in O(cap^2) worst case.

    Only rows whose k-NN list contained the evicted point are touched;
    each is recomputed from the stored exact distances, so the result is
    bit-exact vs refitting on the remaining window. The general arbitrary
    -index form keeps the historic full recompute: the survivors are
    gathered into linear arrival order (one O(cap^2) permutation of
    ``D`` — arbitrary mid-window forgetting has no O(cap) repair), so
    the output is a normalized head == 0 state. ``i`` counts arrival
    rank (0 = oldest) and may be traced. Precondition: 0 <= i < n
    (callers guard; under vmap+select the skipped lanes compute
    discarded garbage).
    """
    cap = state.capacity
    i = jnp.asarray(i, jnp.int32)
    slot_i = _mod_cap(state.head + i, state.wrap)

    # rows whose list held the evicted point: d(r, i) <= kth. The evicted
    # point may sit anywhere in arrival order, so on ties we cannot tell
    # membership from the distance alone — recompute conservatively
    # (recompute is exact).
    dcol = state.D[:, slot_i]
    affected = (ring_live(cap, state.head, state.n, state.wrap)
                & (dcol <= state.nbr_d[:, -1]))

    # survivor slots in arrival order, rank i dropped (gather; the last
    # rank maps to itself and is overwritten by the inert fill below)
    ar = jnp.arange(cap, dtype=jnp.int32)
    ar = jnp.minimum(ar + (ar >= i), cap - 1)
    slots = ring_slots(cap, state.head, state.wrap)[ar]
    n2 = state.n - 1
    live2 = jnp.arange(cap) < n2

    Xs = jnp.where(live2[:, None], state.X[slots], 0.0)
    ys = jnp.where(live2, state.y[slots], 0.0)
    Ds = state.D[slots][:, slots]
    Ds = jnp.where(live2[:, None] & live2[None, :], Ds, BIG)
    nbr_ds = jnp.where(live2[:, None], state.nbr_d[slots], BIG)
    nbr_ys = jnp.where(live2[:, None], state.nbr_y[slots], 0.0)
    nbr_as = jnp.where(live2[:, None], state.nbr_a[slots], 0)
    aids = jnp.where(live2, state.aid[slots], 0)
    aff = live2 & affected[slots]

    # backfill affected rows: exact k-best straight from the stored
    # distances (the diagonal and inert entries are already BIG); rows
    # are now in arrival order, so top_k's lowest-index tie rule IS
    # fit's earliest-arrival rule
    neg, idxm = jax.lax.top_k(-Ds, k)
    rec_d = -neg
    rec_y = ys[idxm]
    rec_y = jnp.where(rec_d >= BIG, ys[:, None], rec_y)
    rec_a = jnp.where(rec_d >= BIG, 0, aids[idxm]).astype(jnp.int32)
    return RegStreamState(
        X=Xs, y=ys, D=Ds,
        nbr_d=jnp.where(aff[:, None], rec_d, nbr_ds),
        nbr_y=jnp.where(aff[:, None], rec_y, nbr_ys),
        n=n2,
        head=jnp.zeros((), jnp.int32),
        aid=aids,
        wrap=jnp.int32(cap),
        nbr_a=jnp.where(aff[:, None], rec_a, nbr_as),
    )


evict = functools.partial(jax.jit, static_argnames=("k",))(_evict)
#: Donating form of ``evict`` — same numerics, input state deleted.
evict_donated = functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(0,))(_evict)


def _evict_oldest(state: RegStreamState, *, k) -> RegStreamState:
    """Sliding-window form: forget the oldest live point, O(cap).

    Specialization of ``evict`` that skips both the full top_k recompute
    *and* any positional movement: the evicted point has the EARLIEST
    arrival, so on distance ties it sorts first — if it is in a row's
    k-NN list at all it occupies the first slot holding its distance,
    and the repair is an O(k) drop + one backfill. The backfill value
    comes by multiset rank over the stored distances, and its *label*
    is the next-earliest-arrival candidate at that distance, arrival
    order read from the stored ``aid``s (``core.online.drop_backfill``)
    — exactly fit's ties-toward-earliest order, so the result stays
    bit-exact vs refit (property-tested). The ring head then advances:
    no leaf is shifted, the stale slot is simply masked out of every
    later read.
    Precondition: n >= 1 (guarded by callers; under vmap+select the n=0
    lanes compute garbage that the caller's select discards).
    """
    cap = state.capacity
    head = state.head
    dcol = state.D[:, head]
    kth = state.nbr_d[:, -1]
    head2 = _mod_cap(head + 1, state.wrap)
    n2 = state.n - 1
    live2 = ring_live(cap, head2, n2, state.wrap)  # survivors only
    affected = live2 & (dcol <= kth)

    cand = live2[None, :]  # self-distances are BIG on the diagonal
    nbr_d2, nbr_y2, nbr_a2 = drop_backfill(
        state.nbr_d, dcol, cand, state.D, affected, k=k,
        Ly=state.nbr_y, La=state.nbr_a, ys=state.y, aid=state.aid,
        aid0=state.aid[head])
    return RegStreamState(
        X=state.X, y=state.y, D=state.D, nbr_d=nbr_d2, nbr_y=nbr_y2,
        n=n2, head=head2, aid=state.aid, wrap=state.wrap, nbr_a=nbr_a2)


evict_oldest = functools.partial(
    jax.jit, static_argnames=("k",))(_evict_oldest)
#: Donating form of ``evict_oldest`` — same numerics, input deleted.
evict_oldest_donated = functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(0,))(_evict_oldest)


@functools.partial(jax.jit, static_argnames=("k", "capacity"))
def _replay(X, y, *, k, capacity):
    state = init(capacity, X.shape[1], k, dtype=X.dtype)

    def step(s, xy):
        s2, _ = observe(s, xy[0], xy[1], k=k)
        return s2, None

    state, _ = jax.lax.scan(step, state, (X, y))
    return state


def from_fit(X, y, *, k, capacity: int) -> RegStreamState:
    """Seed a streaming state from batch data by replaying ``observe``.

    One scanned jit (buffers donated across steps, no per-step host
    round-trip) — the incremental construction *is* the fit, bit-exactly,
    so no separate batch loader is needed.
    """
    return _replay(jnp.asarray(X), jnp.asarray(y), k=k,
                   capacity=int(capacity))


__all__ = ["RegStreamState", "init", "state_view", "arrival_stats",
           "observe",
           "observe_donated", "evict", "evict_donated", "evict_oldest",
           "evict_oldest_donated", "from_fit", "arrival_view", "to_linear"]
