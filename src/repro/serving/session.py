"""Per-tenant capacity-padded CP session with exact decremental eviction.

A ``Session`` wraps ``core.online.OnlineKnnState`` (the paper's incremental
simplified-k-NN CP state, Section 9) and adds the one piece the pure
incremental state cannot provide: **exact forgetting**. The paper's
decremental update (Fig. 1 read backwards) removes a training point in
O(n) per affected neighbour list — but a neighbour list that loses its
j-th entry must be backfilled with the (k+1)-th best distance, which the
k-slot state no longer knows. The session therefore maintains the live
pairwise distance matrix ``D`` (built incrementally, one row+column per
``observe`` — the distances are computed once anyway for the p-value), so
eviction backfills from stored exact distances instead of re-deriving
them: bit-exact against fit-from-scratch, no O(n^2 p) recompute.

Storage is a **ring buffer**: a scalar ``head`` names the slot of the
oldest live point and the window occupies slots ``(head + i) % cap``.
Evicting the oldest point is a head advance plus the O(cap·k) list
repair — nothing ever positionally compacts the (cap, cap) ``D`` — so a
full sliding-window tick (evict + observe) is a constant number of
O(cap) in-place writes under donation, matching the paper's App. C.5
per-step bound. The historic linear layout is the ``head == 0`` no-wrap
special case, and ``_sliding_step_compact`` below keeps the old
shift-to-compact implementation alive as the bit-oracle the ring path
is property-tested against.

Invariants (all arrays are capacity-padded, fixed-shape, jit-stable):

* slots ``(head + i) % cap``, ``i in [0, n)`` are live in arrival order;
* ``D[i, j]`` is the Euclidean distance between live slots i and j,
  computed exactly as ``core.online.observe`` computes it
  (``sqrt(max(sum((xi-xj)^2), 0))``); BIG on the diagonal and wherever a
  row/column has never been written. Slots no longer live may hold stale
  values — every reader masks by ring liveness, never by position;
* ``aid`` stamps each slot with a monotone arrival counter at insert
  (the tie-break key of the shared decremental repair,
  ``core.online.drop_backfill``);
* ``knn.best`` rows of live slots always equal what fit-from-scratch on
  the current window would produce (the exactness tests assert this
  bitwise, via the ``to_linear`` normalization).

``observe`` delegates the p-value + learn step to
``core.online.observe_with_dists`` so session p-values are bit-identical
to ``core.online.run_stream``; ``evict_oldest`` is the decremental
update; ``grow`` doubles capacity host-side (retraces only O(log n)
times — the capacity-doubling schedule), normalizing the ring back to
linear order first.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import online
from repro.core.online import (BIG, OnlineKnnState, cshift,
                               next_aid as _next_aid, ring_live,
                               ring_mod as _mod, ring_slots)
from repro.kernels import ops as kops


@jax.tree_util.register_pytree_node_class
@dataclass
class Session:
    """One tenant's sliding-window CP state: k-NN state + live distances."""

    knn: OnlineKnnState  # capacity-padded incremental CP state
    D: jnp.ndarray  # (cap, cap) live pairwise distances, BIG elsewhere
    head: jnp.ndarray  # () slot of the oldest live point (ring start)
    # per-slot arrival ids (monotone at insert). The classification tie
    # rules themselves never consult them (the evicted point is always
    # the earliest arrival, and the backfill value needs only counts and
    # mins) — they are carried for diagnostics, snapshot symmetry with
    # the regression state (whose backfill pick DOES consume them), and
    # plug-in measures that need an explicit arrival order.
    aid: jnp.ndarray  # (cap,)
    wrap: jnp.ndarray  # () ring modulus (<= cap; slots >= wrap inert)

    def tree_flatten(self):
        return ((self.knn, self.D, self.head, self.aid, self.wrap), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.D.shape[-1]


def init(capacity: int, p: int, k: int, dtype=jnp.float32,
         wrap: int | None = None) -> Session:
    """Fresh empty session. ``wrap`` (default: the capacity) is the ring
    modulus — a sliding engine whose window statically bounds occupancy
    confines the ring to the leading ``[:wrap]`` block of every leaf."""
    if capacity < k:
        raise ValueError(
            f"capacity {capacity} < k {k}: the k-best machinery (top_k) "
            "needs at least k rows")
    return Session(
        knn=online.init(capacity, p, k, dtype=dtype),
        D=jnp.full((capacity, capacity), BIG, dtype=dtype),
        head=jnp.zeros((), dtype=jnp.int32),
        aid=jnp.zeros((capacity,), dtype=jnp.int32),
        wrap=jnp.asarray(capacity if wrap is None else wrap, jnp.int32),
    )


def _observe(sess: Session, x_new, y_new, tau, *, k):
    """Smoothed p-value for (x_new, y_new), then learn it — one O(cap) step.

    The p-value is bit-identical to ``core.online.observe`` (it *is* that
    computation); additionally the new point's distance row/column is
    recorded in ``D`` for later exact eviction — two dynamic-update-slices
    that run in place (O(cap) traffic) when the jitted step donates its
    input. The new point lands at ring slot ``(head + n) % wrap``.
    Precondition: n < wrap (callers grow or evict first).
    """
    knn_in = sess.knn
    idx = _mod(sess.head + knn_in.n, sess.wrap)
    knn, p, d = online.observe_with_dists(knn_in, x_new, y_new, tau, k=k,
                                          head=sess.head, wrap=sess.wrap)
    D = sess.D.at[idx, :].set(d).at[:, idx].set(d)
    aid = sess.aid.at[idx].set(
        _next_aid(sess.aid, sess.head, knn_in.n, sess.wrap))
    return Session(knn, D, sess.head, aid, sess.wrap), p


observe = functools.partial(jax.jit, static_argnames=("k",))(_observe)
#: Donating form of ``observe``: the (cap, cap) ``D`` row/column insert
#: updates in place instead of copying the matrix. The input session is
#: DELETED by the call — reusing it afterwards raises ``RuntimeError:
#: Array has been deleted``. Numerics are identical to ``observe``.
observe_donated = functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(0,))(_observe)


def _evict_oldest(sess: Session, *, k) -> Session:
    """Exact decremental update: forget the oldest live point, O(cap).

    Paper's decremental rule: only points whose same-label k-neighbourhood
    contained the evicted point are affected, and each such list needs
    exactly one repair — drop the evicted entry and backfill the new k-th
    best. The evicted point is the OLDEST, so on distance ties it sorts
    first: if it is in a list at all, it occupies the *first* slot holding
    its distance — an O(k) surgery, no re-sort. The backfill value is
    recovered from the maintained ``D`` by multiset rank (two masked row
    reductions; see ``core.online.drop_backfill``) — same bits as a full
    re-sort, a fraction of the compute.

    Under the ring layout nothing moves: the head slot simply leaves the
    live window (``head`` advances, ``n`` drops) and its stale row,
    column and list are masked out of every later read by ring liveness.
    No (cap, cap) buffer is shifted, copied or even written.
    Precondition: n >= 1 (guarded by callers; under vmap+select the n=0
    lanes compute garbage that the caller's select discards).
    """
    knn = sess.knn
    cap = knn.X.shape[0]
    head = sess.head

    # which survivors held the evicted point in their k-best list?
    # d(i, evicted) <= kth <=> it is among i's k smallest same-label
    # distances (exact on ties: the evicted point is the oldest arrival,
    # so it precedes every equal distance in the list order)
    dcol = sess.D[:, head]
    kth = knn.best[:, -1]
    head2 = _mod(head + 1, sess.wrap)
    n2 = knn.n - 1
    live2 = ring_live(cap, head2, n2, sess.wrap)  # survivors only
    affected = (knn.y == knn.y[head]) & live2 & (dcol <= kth)

    cand = (knn.y[:, None] == knn.y[None, :]) & live2[None, :]
    best2 = online.drop_backfill(knn.best, dcol, cand, sess.D, affected,
                                 k=k)
    return Session(OnlineKnnState(knn.X, knn.y, best2, n2), sess.D,
                   head2, sess.aid, sess.wrap)


evict_oldest = functools.partial(
    jax.jit, static_argnames=("k",))(_evict_oldest)
#: Donating form of ``evict_oldest`` — same numerics, input deleted.
evict_oldest_donated = functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(0,))(_evict_oldest)


def _sliding_step(sess: Session, x_new, y_new, tau, window, active, *, k,
                  evictable: bool = True, wmax: int | None = None):
    """One fused sliding-window tick: evict-if-full, observe, all gated.

    The semantics of ``cond(evict_oldest) -> observe`` with an outer
    ``active`` mask, on the ring layout: eviction is a gated head
    advance plus the shared list repair, the observe core writes the new
    point into the freed ring slot, and every state write is gated
    arithmetically (inactive lanes rewrite their current values, so
    masked state stays bitwise unchanged and the p-value is NaN). The
    (cap, cap) ``D`` is only *read* (one fused reduction pass for the
    backfill) and written at one row + one column — never shifted,
    padded or copied — so with donation the whole tick is a constant
    number of O(cap) in-place writes. Bit-identical to the historic
    compaction form ``_sliding_step_compact`` (property-tested).

    ``evictable=False`` (static) removes the eviction machinery — the
    grow-mode engines never evict, so their tick is a pure donated
    observe. ``wmax`` (static) is the caller's promise that occupancy
    never exceeds it (a sliding engine's window bounds n): the ring then
    lives entirely inside the ``[:wmax]`` block of every leaf (modulus
    ``wmax``), and per-tick cost scales with the *window*, not the
    padded capacity.
    """
    knn = sess.knn
    cap = knn.X.shape[0]
    # static block bound for the leaf slices; the traced modulus is the
    # state's ``wrap`` (engine invariant: wrap <= wmax)
    w = cap if wmax is None or wmax >= cap else wmax
    wrap = sess.wrap
    # slot-space views confined to the ring block (pure reads: static
    # slices fuse into their consumers, nothing is materialized)
    Xw, yw, bw = knn.X[:w], knn.y[:w], knn.best[:w]
    Dw = sess.D[:w, :w]
    aidw = sess.aid[:w]
    head = sess.head
    n = knn.n
    act = jnp.asarray(active)

    # named scopes (op_name metadata only: same instructions, same
    # bits) split the tick's device time by stage in a profile
    with jax.named_scope("evict"):
        if evictable:
            ev = act & (n >= window)
            s = ev.astype(jnp.int32)
            # the head's column, read as its row: every write sets row
            # and column idx from one vector, so D is bitwise symmetric,
            # and a row read keeps D row-major
            dcol = Dw[head, :]
            head1 = _mod(head + s, wrap)
            n1 = n - s
            live1 = ring_live(w, head1, n1, wrap)
            affected = (ev & (yw == yw[head]) & live1
                        & (dcol <= bw[:, -1]))
            cand = (yw[:, None] == yw[None, :]) & live1[None, :]
            b1 = online.drop_backfill(bw, dcol, cand, Dw, affected, k=k)
        else:
            head1, n1, b1 = head, n, bw

    # price + learn through the same code path as core.online.run_stream
    with jax.named_scope("learn"):
        knn1 = OnlineKnnState(Xw, yw, b1, n1)
        knn2, p, d = online.observe_with_dists(knn1, x_new, y_new, tau, k=k,
                                               head=head1, wrap=wrap)

    with jax.named_scope("write"):
        # gate on ``active``: the big leaf (D) is written with its own
        # current values on inactive lanes (D is symmetric, so the row at
        # idx equals the column at idx); the small leaves are selects
        idx = _mod(head1 + n1, wrap)
        row = jnp.where(act, d, Dw[idx, :])
        # bit-neutral scheduling marker: list entries are finite and >= 0
        # and so is every value in ``row``, so ``+ b1[0,0] * 0.0`` adds +0.0
        # exactly. It makes the D insert *depend* on the backfill reads of
        # D. Through the TPU kernel the compiled chunk holds no full copy
        # of D with or without it; on the XLA scatter route (CPU) dropping
        # it adds one more full copy of the donated (cap, cap) buffer
        row = row + b1[0, 0] * 0.0
        # row and column idx in place; on TPU the column is a lane-strip
        # kernel, so D stays row-major through the scanned chunk
        D2 = kops.dist_insert(sess.D, row, idx)
        knn3 = OnlineKnnState(
            X=knn.X.at[:w].set(jnp.where(act, knn2.X, Xw)),
            y=knn.y.at[:w].set(jnp.where(act, knn2.y, yw)),
            best=knn.best.at[:w].set(jnp.where(act, knn2.best, b1)),
            n=jnp.where(act, knn2.n, n1),
        )
        new_aid = _next_aid(aidw, head1, n1, wrap)
        aid2 = sess.aid.at[idx].set(jnp.where(act, new_aid, sess.aid[idx]))
        p = jnp.where(act, p, jnp.asarray(jnp.nan, dtype=Xw.dtype))
    return Session(knn3, D2, head1, aid2, wrap), p


def _sliding_step_compact(sess: Session, x_new, y_new, tau, window, active,
                          *, k, evictable: bool = True,
                          wmax: int | None = None):
    """Historic linear-layout sliding tick — the ring path's bit-oracle.

    Keeps arrival order positionally: eviction compacts every leaf down
    one row (and ``D`` one row AND one column) through a padded dynamic
    slice — the O(cap^2)-traffic form the ring layout replaces. Retained
    for the exactness property tests (ring vs compact, leaf for leaf
    after ``to_linear``) and as the benchmark baseline
    (``layout="compact"`` on the engines). Precondition: linear layout
    (``head == 0``), which this step preserves.
    """
    knn = sess.knn
    cap = knn.X.shape[0]
    if wmax is not None and wmax < cap:
        sub = Session(
            OnlineKnnState(knn.X[:wmax], knn.y[:wmax], knn.best[:wmax],
                           knn.n),
            sess.D[:wmax, :wmax], sess.head, sess.aid[:wmax],
            jnp.minimum(sess.wrap, wmax))
        sub2, p = _sliding_step_compact(sub, x_new, y_new, tau, window,
                                        active, k=k, evictable=evictable)
        return Session(
            OnlineKnnState(
                X=knn.X.at[:wmax].set(sub2.knn.X),
                y=knn.y.at[:wmax].set(sub2.knn.y),
                best=knn.best.at[:wmax].set(sub2.knn.best),
                n=sub2.knn.n,
            ),
            D=sess.D.at[:wmax, :wmax].set(sub2.D),
            head=sub2.head,
            aid=sess.aid.at[:wmax].set(sub2.aid),
            wrap=sess.wrap), p
    act = jnp.asarray(active)
    aid = sess.aid
    if evictable:
        ev = act & (knn.n >= window)
        s = ev.astype(jnp.int32)
        live = jnp.arange(cap) < knn.n
        dcol = sess.D[:, 0]
        affected = (ev & (knn.y == knn.y[0]) & live
                    & (dcol <= knn.best[:, -1]))

        # conditional compaction: pad each leaf by one (the pad value IS
        # the compaction fill) and take one dynamic slice at offset
        # s ∈ {0, 1} — identity when s == 0, shift-with-fill when s == 1
        X1 = cshift(knn.X, s, 0)
        y1 = cshift(knn.y, s, -1)
        L1 = cshift(knn.best, s, BIG)
        aid1 = cshift(aid, s, 0)
        Dp = jnp.pad(sess.D, ((0, 1), (0, 1)), constant_values=BIG)
        D1 = jax.lax.dynamic_slice(Dp, (s, s), (cap, cap))
        aff1 = cshift(affected, s, False)
        es1 = cshift(dcol, s, BIG)
        n1 = knn.n - s
        live1 = jnp.arange(cap) < n1
        cand = (y1[:, None] == y1[None, :]) & live1[None, :]
        best1 = online.drop_backfill(L1, es1, cand, D1, aff1, k=k)
    else:
        X1, y1, best1, D1 = knn.X, knn.y, knn.best, sess.D
        aid1, n1 = aid, knn.n

    # price + learn through the same code path as core.online.run_stream
    knn1 = OnlineKnnState(X1, y1, best1, n1)
    knn2, p, d = online.observe_with_dists(knn1, x_new, y_new, tau, k=k)

    # gate on ``active``: the big leaf (D) is written with its own
    # current values on inactive lanes (D is symmetric, so the row at
    # idx equals the column at idx); the small leaves are selects.
    # The clamp keeps an inactive lane at an exactly-full window
    # in bounds (idx == cap otherwise — XLA's pad+slice fusion reads
    # the pad fill there instead of clamping); the write is its own
    # value, so the clamp is bit-neutral wherever the step is defined
    idx = jnp.minimum(n1, cap - 1)
    row = jnp.where(act, d, D1[idx, :])
    D2 = D1.at[idx, :].set(row).at[:, idx].set(row)
    knn3 = OnlineKnnState(
        X=jnp.where(act, knn2.X, X1),
        y=jnp.where(act, knn2.y, y1),
        best=jnp.where(act, knn2.best, best1),
        n=jnp.where(act, knn2.n, n1),
    )
    new_aid = _next_aid(aid1, jnp.zeros((), jnp.int32), n1,
                        jnp.int32(cap))
    aid2 = aid1.at[idx].set(jnp.where(act, new_aid, aid1[idx]))
    p = jnp.where(act, p, jnp.asarray(jnp.nan, dtype=X1.dtype))
    return Session(knn3, D2, sess.head, aid2, sess.wrap), p


def _observe_sliding(sess: Session, x_new, y_new, tau, window, *, k):
    """Evict-if-full then observe: one fixed-shape sliding-window step.

    ``window`` is a traced scalar (per-tenant window sizes never
    retrace). The fused ``_sliding_step`` with every lane active.
    """
    return _sliding_step(sess, x_new, y_new, tau, window, True, k=k)


observe_sliding = functools.partial(
    jax.jit, static_argnames=("k",))(_observe_sliding)
#: Donating form of ``observe_sliding`` — same numerics, input deleted.
observe_sliding_donated = functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(0,))(_observe_sliding)


@jax.jit
def to_linear(sess: Session) -> Session:
    """Normalize a ring session to the linear layout (head == 0).

    Gathers every leaf into arrival order and resets stale slots to the
    linear inert fills (X=0, y=-1, best=BIG, D=BIG), so the result is
    leaf-for-leaf bit-identical to what a fresh linear session fed the
    same surviving window would hold — the equivalence the exactness
    tests assert. Arrival ids are *renumbered* to their canonical
    positional form 0..n-1 (only their relative order carries meaning;
    absolute counters drift with eviction history). O(cap^2) for the
    ``D`` gather; used by ``grow`` and the tests, never on the serving
    tick.
    """
    knn = sess.knn
    cap = knn.X.shape[0]
    slots = ring_slots(cap, sess.head, sess.wrap)
    live = jnp.arange(cap) < knn.n
    X = jnp.where(live[:, None], knn.X[slots], 0)
    y = jnp.where(live, knn.y[slots], -1)
    best = jnp.where(live[:, None], knn.best[slots], BIG)
    D = jnp.where(live[:, None] & live[None, :],
                  sess.D[slots][:, slots], BIG)
    aid = jnp.where(live, jnp.arange(cap, dtype=jnp.int32), 0)
    return Session(OnlineKnnState(X, y, best, knn.n), D,
                   jnp.zeros((), jnp.int32), aid, jnp.int32(cap))


def grow(sess: Session, factor: int = 2) -> Session:
    """Double (by default) capacity host-side, preserving all live state.

    Shapes change, so jitted steps retrace — but only O(log n) times over
    a session's lifetime, the capacity-doubling schedule. The ring is
    normalized to linear order first (ring positions are modulus-bound,
    so they cannot survive a capacity change). Not jittable.
    """
    cap = sess.capacity
    extra = cap * (factor - 1)
    sess = to_linear(sess)
    knn = sess.knn
    return Session(
        knn=OnlineKnnState(
            X=jnp.pad(knn.X, ((0, extra), (0, 0))),
            y=jnp.pad(knn.y, (0, extra), constant_values=-1),
            best=jnp.pad(knn.best, ((0, extra), (0, 0)),
                         constant_values=BIG),
            n=knn.n,
        ),
        D=jnp.pad(sess.D, ((0, extra), (0, extra)), constant_values=BIG),
        head=sess.head,
        aid=jnp.pad(sess.aid, (0, extra)),
        wrap=jnp.int32(cap * factor),
    )


@functools.partial(jax.jit, static_argnames=("k", "n_labels"))
def predict_pvalues(sess: Session, X_test, *, k, n_labels):
    """Read-only full-CP query: p-values (m, n_labels) for every label.

    Hot path: candidate scores via one masked top-k, then the fused
    score-update + count through ``kernels.ops.cp_knn_counts`` (the
    Pallas kernel on TPU). Non-live slots (ring liveness, not position)
    carry a -BIG sentinel so they are never counted regardless of the
    padded capacity. Every reduction here is over a per-slot multiset —
    counts, sums of top-k-sorted values — so the ring layout produces
    the same bits as the linear layout, stale slots masked.

    Rows whose k-best list is not full (label rarer than k in the
    window) are excluded from the kernel and counted caller-side: the
    kernel's ``sums - kth + d`` update would subtract the BIG padding
    sentinel and swallow the finite part in f32. The caller-side path
    uses the cancellation-safe ``base + (kth or d)`` form of
    ``measures.knn._updated_scores``, so rare labels stay exact.
    """
    knn = sess.knn
    cap = knn.X.shape[0]
    live = ring_live(cap, sess.head, knn.n, sess.wrap)

    with jax.named_scope("query"):  # d: (m, cap)
        d = jnp.sqrt(jnp.maximum(kops.sq_dists(X_test, knn.X), 0.0))
        labels = jnp.arange(n_labels, dtype=knn.y.dtype)
        same = (knn.y[None, :] == labels[:, None]) & live[None, :]  # (l, cap)
        dm = jnp.where(same[None], d[:, None, :], BIG)  # (m, l, cap)
        alpha = jnp.sum(-jax.lax.top_k(-dm, k)[0], axis=-1)  # (m, l)

    with jax.named_scope("count"):
        kth = knn.best[:, -1]
        full = live & (kth < BIG)  # k-best list fully populated
        sum_same = jnp.where(full, jnp.sum(knn.best, axis=1), -BIG)
        kth_same = jnp.where(full, kth, -BIG)
        counts = kops.cp_knn_counts(
            knn.X, jnp.where(live, knn.y, -1), sum_same, kth_same, X_test,
            alpha, n_labels)

        deficient = live & (kth >= BIG)
        base = jnp.sum(knn.best[:, :-1], axis=1)  # (cap,)
        upd = same[None] & (d[:, None, :] < kth)  # (m, l, cap)
        scores = base + jnp.where(upd, d[:, None, :], kth)
        ge = (scores >= alpha[..., None]) & deficient[None, None, :]
        counts = counts + jnp.sum(ge.astype(counts.dtype), axis=-1)
    return (counts + 1.0) / (knn.n + 1.0)


__all__ = ["Session", "init", "observe", "observe_donated", "evict_oldest",
           "evict_oldest_donated", "observe_sliding",
           "observe_sliding_donated", "grow", "predict_pvalues",
           "to_linear"]
