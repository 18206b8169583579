"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics of record: each kernel's test sweeps shapes/dtypes and
asserts allclose against these functions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def sq_dists(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """Squared Euclidean distances (m, n) between rows of A (m,p) and B (n,p)."""
    a2 = jnp.sum(A * A, axis=-1, keepdims=True)
    b2 = jnp.sum(B * B, axis=-1, keepdims=True)
    return a2 + b2.T - 2.0 * (A @ B.T)


def kde_rowsums(
    A: jnp.ndarray, B: jnp.ndarray, y_A: jnp.ndarray, y_B: jnp.ndarray,
    h: float, exclude_diag: bool = False,
) -> jnp.ndarray:
    """Masked Gaussian-kernel row sums: out[i] = sum_j K((A_i-B_j)/h) over
    j with y_B[j] == y_A[i] (and j != i when exclude_diag)."""
    d2 = sq_dists(A, B)
    K = jnp.exp(-d2 / (2.0 * h * h))
    mask = y_A[:, None] == y_B[None, :]
    if exclude_diag:
        m, n = d2.shape
        mask = mask & ~jnp.eye(m, n, dtype=bool)
    return jnp.sum(jnp.where(mask, K, 0.0), axis=-1)


def cp_knn_counts(
    X: jnp.ndarray, y: jnp.ndarray, sum_same: jnp.ndarray, kth_same: jnp.ndarray,
    X_test: jnp.ndarray, alpha: jnp.ndarray,
) -> jnp.ndarray:
    """Fused simplified-k-NN CP update + p-value partial counts.

    For each test point t and label l: counts[t, l] =
      #{i : alpha_i(t, l) >= alpha[t, l]}, where alpha_i is the provisional
    score sum_same[i], updated to sum_same[i] - kth_same[i] + d(x_i, x_t)
    when the test point enters i's same-label neighbourhood.

    alpha: (m, l) candidate scores. Returns int32 (m, l).
    """
    d = jnp.sqrt(jnp.maximum(sq_dists(X_test, X), 0.0))  # (m, n)
    n_labels = alpha.shape[1]
    labels = jnp.arange(n_labels, dtype=y.dtype)
    same = y[None, :] == labels[:, None]  # (l, n)
    upd = same[None] & (d[:, None, :] < kth_same[None, None, :])  # (m, l, n)
    alphas = jnp.where(
        upd, (sum_same - kth_same)[None, None, :] + d[:, None, :],
        sum_same[None, None, :],
    )
    return jnp.sum(alphas >= alpha[:, :, None], axis=-1).astype(jnp.int32)


def reg_interval_endpoints(
    X: jnp.ndarray, a_prime: jnp.ndarray, kth_dist: jnp.ndarray,
    kth_label: jnp.ndarray, live: jnp.ndarray, X_test: jnp.ndarray,
    a_test: jnp.ndarray, k: int, eps: float = 1e-12,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused regression-CP critical points (paper Section 8.1).

    For each (test point t, training row i): the distance d(x_i, x_t), the
    O(1) incremental&decremental update of the affine score coefficients
        a_i = a'_i + [d < Delta_i^k] y_(k)(x_i)/k,   b_i in {0, -1/k},
    and the boundary points of S_i = {t : |a_i + b_i t| >= |a_test + t|}
    (the roots of (a_i + b_i t)^2 - (a_test + t)^2, at most two). Returns
    (lo, hi), each (m, n); empty sets (and rows with ``live`` False) are
    the neutral (+inf, -inf). Semantics of record for the Pallas kernel in
    ``interval_sweep.py``; arithmetic mirrors ``regression._interval_ge``
    exactly so the streaming read path stays bit-identical to the batch
    optimized path.
    """
    INF = jnp.inf
    d = jnp.sqrt(jnp.maximum(sq_dists(X_test, X), 0.0))  # (m, n)
    upd = a_prime + kth_label / k
    enters = live[None, :] & (d < kth_dist[None, :])
    a_i = jnp.where(enters, upd[None, :], a_prime[None, :])
    b_i = jnp.where(enters, -1.0 / k, 0.0)
    a = a_test[:, None]  # (m, 1)

    A2 = b_i * b_i - 1.0
    B1 = a_i * b_i - a
    C0 = a_i * a_i - a * a
    disc = B1 * B1 - A2 * C0
    sq = jnp.sqrt(jnp.maximum(disc, 0.0))
    denom = jnp.where(jnp.abs(A2) < eps, 1.0, A2)
    r1 = (-B1 + sq) / denom
    r2 = (-B1 - sq) / denom
    qlo = jnp.minimum(r1, r2)
    qhi = jnp.maximum(r1, r2)
    quad_lo = jnp.where(disc >= 0.0, qlo, INF)
    quad_hi = jnp.where(disc >= 0.0, qhi, -INF)
    t0 = -C0 / jnp.where(jnp.abs(B1) < eps, 1.0, 2.0 * B1)
    lin_lo = jnp.where(B1 > eps, t0,
                       jnp.where(B1 < -eps, -INF,
                                 jnp.where(C0 >= 0.0, -INF, INF)))
    lin_hi = jnp.where(B1 > eps, INF,
                       jnp.where(B1 < -eps, t0,
                                 jnp.where(C0 >= 0.0, INF, -INF)))
    is_quad = jnp.abs(A2) >= eps
    lo = jnp.where(is_quad, quad_lo, lin_lo)
    hi = jnp.where(is_quad, quad_hi, lin_hi)
    lo = jnp.where(live[None, :], lo, INF)
    hi = jnp.where(live[None, :], hi, -INF)
    return lo, hi


_BIG = 1e30  # matches core.online.BIG / core.regression.BIG


def _ring_live(cap: int, head, n, wrap=None) -> jnp.ndarray:
    """(cap,) live mask of a ring window: slot ``(head + i) % wrap`` is
    live for ``i in [0, n)``; slots ``>= wrap`` never are. ``head=None``
    (or 0, full-capacity ``wrap``) is the historic linear layout, where
    this reduces to ``arange(cap) < n`` bit-for-bit. Mirrors
    ``core.online.ring_live`` (not imported here: ``core.online`` sits
    above this module in the import graph)."""
    idx = jnp.arange(cap, dtype=jnp.int32)
    if head is None:
        return idx < n
    m = jnp.asarray(cap if wrap is None else wrap, jnp.int32)
    age = jnp.where(idx >= head, idx - head, idx - head + m)
    return (age < n) & (idx < m)


def stream_update(
    X: jnp.ndarray, y: jnp.ndarray, nbr_d: jnp.ndarray, nbr_y: jnp.ndarray,
    x_new: jnp.ndarray, y_new: jnp.ndarray, n: jnp.ndarray, *, mode: str,
    head=None, wrap=None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused streaming observe front end: distance row + k-best merge.

    One incoming point against a capacity-padded window: computes the
    (cap,) distance row (BIG on inert rows), and merges the point into
    every live row's ordered k-best neighbour list. Two modes, matching
    the two serving engines' maintained statistics bit-for-bit:

    * ``mode="class"`` — the paper's simplified-k-NN classification state
      (``core.online``): distances in the row-difference form
      ``sqrt(sum((x_i - x)^2))``, a row's list admits the candidate iff
      same label; ``nbr_y`` is passed through untouched.
    * ``mode="reg"`` — the Section 8.1 regression state
      (``regression.stream``): distances in the MXU-friendly
      ``a^2 + b^2 - 2ab`` form of ``sq_dists``, a row's list admits the
      candidate iff it beats the current k-th distance (ties keep the
      incumbent); neighbour *labels* ride along, inserted strictly below
      equal distances (fit's stable-argsort tie rule), and BIG slots
      carry the row's own label.

    The caller keeps the new row's own top-k list, the D row/column
    write (``dist_insert``, in place under donation) and the p-value —
    none of which belong in a tiled kernel. Returns
    ``(d_row (cap,), nbr_d' (cap, k), nbr_y' (cap, k))``. Semantics of
    record for the Pallas kernel in ``stream_update.py``; expressions
    mirror ``core.online._observe_impl`` / ``regression.stream.observe``
    exactly, so routing through this oracle keeps the streaming states
    bit-identical to refit-from-scratch.

    ``head`` (traced scalar or None) selects the serving engines'
    ring-buffer slot layout: live slots are ``(head + i) % wrap`` rather
    than ``[0, n)`` (``wrap`` defaults to the capacity). Per-slot
    arithmetic is unchanged — only the live mask moves — so the emitted
    distances/list values are the same bits wherever a slot is live
    under both layouts.
    """
    cap, k = nbr_d.shape
    live = _ring_live(cap, head, n, wrap)
    if mode == "class":
        d = jnp.sqrt(jnp.maximum(
            jnp.sum((X - x_new[None]) ** 2, axis=-1), 0.0))
        d = jnp.where(live, d, _BIG)
        same = (y == y_new) & live
        cand = jnp.where(same, d, _BIG)
        merged = jnp.sort(
            jnp.concatenate([nbr_d, cand[:, None]], axis=1), axis=1)[:, :k]
        return d, merged, nbr_y
    if mode != "reg":
        raise ValueError(f"unknown stream_update mode {mode!r}")
    d = jnp.sqrt(jnp.maximum(sq_dists(x_new[None], X)[0], 0.0))
    d_row = jnp.where(live, d, _BIG)
    enters = live & (d < nbr_d[:, -1])
    cand_d = jnp.where(enters, d, _BIG)
    merged_d = jnp.concatenate([nbr_d, cand_d[:, None]], axis=1)
    merged_y = jnp.concatenate(
        [nbr_y, jnp.full((cap, 1), y_new, nbr_y.dtype)], axis=1)
    order = jnp.argsort(merged_d, axis=1, stable=True)
    nd = jnp.take_along_axis(merged_d, order, axis=1)[:, :k]
    ny = jnp.take_along_axis(merged_y, order, axis=1)[:, :k]
    ny = jnp.where(nd >= _BIG, y[:, None], ny)
    return d_row, nd, ny


def _ordered_insert(L, c):
    """Branch-free ordered insert: candidate ``c`` (cap,) into each
    ascending row of ``L`` (cap, k), strictly after equal values, largest
    entry dropped. Equivalent to ``sort(concat([L, c], 1))[:, :k]`` with
    the stable candidate-last tie rule — every output is a selected
    input value, so the two forms are bit-identical. Returns
    ``(newL, pos, cols)`` so callers can mirror the move on a parallel
    label matrix."""
    k = L.shape[1]
    pos = jnp.sum((L <= c[:, None]).astype(jnp.int32), axis=1,
                  keepdims=True)
    cols = jnp.arange(k)[None, :]
    Lsh = jnp.concatenate([L[:, :1], L[:, :k - 1]], axis=1)
    newL = jnp.where(cols < pos, L,
                     jnp.where(cols == pos, c[:, None], Lsh))
    return newL, pos, cols


def stream_update_fast(
    X: jnp.ndarray, y: jnp.ndarray, nbr_d: jnp.ndarray, nbr_y: jnp.ndarray,
    x_new: jnp.ndarray, y_new: jnp.ndarray, n: jnp.ndarray, *, mode: str,
    head=None, wrap=None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sortless form of ``stream_update`` — the production CPU path.

    Bit-identical to the sort-based oracle above (the ordered insert
    selects the same values the sort would; the parity tests pin the two
    together, ties included) but avoids XLA's comparator sort, which
    dominates the observe tick on CPU at large capacities.
    """
    cap, k = nbr_d.shape
    live = _ring_live(cap, head, n, wrap)
    if mode == "class":
        d = jnp.sqrt(jnp.maximum(
            jnp.sum((X - x_new[None]) ** 2, axis=-1), 0.0))
        d = jnp.where(live, d, _BIG)
        same = (y == y_new) & live
        cand = jnp.where(same, d, _BIG)
        merged, _, _ = _ordered_insert(nbr_d, cand)
        return d, merged, nbr_y
    if mode != "reg":
        raise ValueError(f"unknown stream_update mode {mode!r}")
    d = jnp.sqrt(jnp.maximum(sq_dists(x_new[None], X)[0], 0.0))
    d_row = jnp.where(live, d, _BIG)
    enters = live & (d < nbr_d[:, -1])
    cand_d = jnp.where(enters, d, _BIG)
    newL, pos, cols = _ordered_insert(nbr_d, cand_d)
    Ysh = jnp.concatenate([nbr_y[:, :1], nbr_y[:, :k - 1]], axis=1)
    newY = jnp.where(cols < pos, nbr_y,
                     jnp.where(cols == pos,
                               jnp.asarray(y_new, nbr_y.dtype), Ysh))
    newY = jnp.where(newL >= _BIG, y[:, None], newY)
    return d_row, newL, newY


def dist_insert(D: jnp.ndarray, row: jnp.ndarray, idx) -> jnp.ndarray:
    """Write ``row`` (w,) into row ``idx`` and column ``idx`` of the
    leading (w, w) block of the distance matrix ``D`` (cap, cap)."""
    w = row.shape[-1]
    return D.at[idx, :w].set(row).at[:w, idx].set(row)


def boot_fit_tree(X, y, w, feat_choice, thr_u, n_labels, depth):
    """Numpy oracle for one weighted extra-tree (``boot_forest._fit_one``).

    Semantics of record for the bootstrap measure's base learner: a
    breadth-first extra-tree over multiplicity-weighted rows. All float
    arithmetic stays in f32 and mirrors the jnp kernel expression
    (``t = lo + u * (hi - lo)``), so the parity tests can pin the vmapped
    path to this one exactly.
    """
    X = np.asarray(X, np.float32)
    m = X.shape[0]
    nn = 2 ** (depth + 1) - 1
    n_internal = 2 ** depth - 1
    node_of = np.zeros(m, np.int32)
    feat = np.full(nn, -1, np.int32)
    thresh = np.zeros(nn, np.float32)
    leaf = np.zeros(nn, np.int32)
    inf32 = np.float32(np.inf)
    for node in range(nn):
        mask = (node_of == node) & (w > 0)
        cnt = np.zeros(n_labels, np.int64)
        np.add.at(cnt, y[mask], w[mask])
        leaf[node] = np.argmax(cnt)
        if node < n_internal:
            f = feat_choice[node]
            col = X[:, f]
            lo = np.where(mask, col, inf32).min()
            hi = np.where(mask, col, -inf32).max()
            if int(cnt.sum()) > 1 and hi > lo:
                t = np.float32(lo + thr_u[node] * (hi - lo))
                feat[node], thresh[node] = f, t
                node_of[mask] = np.where(
                    col[mask] > t, 2 * node + 2, 2 * node + 1)
    return feat, thresh, leaf


def boot_predict_tree(feat, thresh, leaf, Xq):
    """Numpy oracle for ``boot_forest.forest_predict`` on one tree."""
    Xq = np.asarray(Xq, np.float32)
    q = Xq.shape[0]
    depth = (len(feat) + 1).bit_length() - 2
    node = np.zeros(q, np.int32)
    for _ in range(depth):
        f = feat[node]
        internal = f >= 0
        xv = Xq[np.arange(q), np.maximum(f, 0)]
        node = np.where(
            internal,
            np.where(xv > thresh[node], 2 * node + 2, 2 * node + 1),
            node).astype(np.int32)
    return leaf[node]


def flash_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    causal: bool = True, window: int | None = None, scale: float | None = None,
    softcap: float | None = None,
) -> jnp.ndarray:
    """Reference attention. q: (B, Sq, H, D); k, v: (B, Skv, Hkv, D).

    GQA: H must be a multiple of Hkv. window: sliding-window size (keys
    within [i-window+1, i] attend), applied with causal. softcap: gemma-style
    tanh logit cap.
    """
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    rep = H // Hkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / jnp.sqrt(D).astype(q.dtype)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    q_pos = jnp.arange(Sq)[:, None] + (Skv - Sq)  # right-aligned
    k_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def chunked_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    causal: bool = True, window: int | None = None, scale: float | None = None,
    softcap: float | None = None, block_q: int = 1024, block_k: int = 1024,
) -> jnp.ndarray:
    """Online-softmax attention with O(S * block) memory, pure jnp.

    Same semantics as ``flash_attention``; the XLA-compiled analogue of the
    Pallas kernel for long sequences off-TPU — a lax.map over query blocks,
    each scanning key blocks with running (max, denom, acc) statistics. This
    is what the 32k/500k dry-run cells lower to on the CPU container.
    """
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    rep = H // Hkv
    scale_f = scale if scale is not None else float(D ** -0.5)

    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_k
    kb = kp.reshape(B, nk, block_k, Hkv, D)
    vb = vp.reshape(B, nk, block_k, Hkv, D)

    def q_block(iq, q_blk):  # q_blk: (B, bq, H, D)
        q_pos = (iq * block_q + jnp.arange(block_q))[:, None] + (Skv - Sq)

        def kv_step(carry, inp):
            m_run, l_run, acc = carry
            ik, k_blk, v_blk = inp  # (B, bk, Hkv, D)
            k_rep = jnp.repeat(k_blk, rep, axis=2)
            v_rep = jnp.repeat(v_blk, rep, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_rep).astype(
                jnp.float32) * scale_f
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            k_pos = (ik * block_k + jnp.arange(block_k))[None, :]
            mask = k_pos < Skv
            if causal:
                mask = mask & (k_pos <= q_pos)
            if window is not None:
                mask = mask & (k_pos > q_pos - window)
            s = jnp.where(mask[None, None], s, -1e30)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_rep.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((B, H, block_q), -1e30, jnp.float32),
            jnp.zeros((B, H, block_q), jnp.float32),
            jnp.zeros((B, H, block_q, D), jnp.float32),
        )
        # checkpoint per kv-step: the backward otherwise saves every
        # (bq, bk) score tile AND boolean mask across the scan — gigabytes
        # per layer at 4k+ context (the Pallas kernel's VJP recomputes
        # tiles the same way on the real TPU)
        (m_f, l_f, acc_f), _ = jax.lax.scan(
            jax.checkpoint(kv_step), init,
            (jnp.arange(nk), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
        out = acc_f / jnp.maximum(l_f, 1e-30)[..., None]
        return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # (B, bq, H, D)

    qb = jnp.moveaxis(qp.reshape(B, nq, block_q, H, D), 1, 0)
    out = jax.lax.map(lambda t: q_block(t[0], t[1]), (jnp.arange(nq), qb))
    out = jnp.moveaxis(out, 0, 1).reshape(B, nq * block_q, H, D)
    return out[:, :Sq]
