"""Dispatching wrappers for the Pallas kernels.

On TPU the compiled Pallas kernels run; elsewhere (this CPU container, unit
tests) the pure-jnp reference semantics from ``ref.py`` are used, with
``REPRO_PALLAS_INTERPRET=1`` forcing the Pallas interpret path so the kernel
bodies themselves are exercised end-to-end. float64 inputs (the CP exactness
path under x64) always use the reference — the MXU has no f64.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1"


def sq_dists(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """Squared Euclidean distance matrix; Pallas-tiled on TPU."""
    if A.dtype == jnp.float64 or B.dtype == jnp.float64:
        return _ref.sq_dists(A, B)
    if _on_tpu() or _interpret():
        from repro.kernels.pairwise_dist import pairwise_sq_dists

        return pairwise_sq_dists(A, B, interpret=not _on_tpu()).astype(A.dtype)
    return _ref.sq_dists(A, B)


def kde_rowsums(A, B, y_A, y_B, h, exclude_diag=False):
    if A.dtype == jnp.float64:
        return _ref.kde_rowsums(A, B, y_A, y_B, h, exclude_diag)
    if _on_tpu() or _interpret():
        from repro.kernels.kde_score import kde_rowsums as _pallas

        return _pallas(
            A, B, y_A, y_B, h=float(h), exclude_diag=exclude_diag,
            interpret=not _on_tpu(),
        ).astype(A.dtype)
    return _ref.kde_rowsums(A, B, y_A, y_B, h, exclude_diag)


def cp_knn_counts(X, y, sum_same, kth_same, X_test, alpha, n_labels):
    if X.dtype == jnp.float64:
        return _ref.cp_knn_counts(X, y, sum_same, kth_same, X_test, alpha)
    if _on_tpu() or _interpret():
        from repro.kernels.cp_update import cp_knn_counts as _pallas

        return _pallas(
            X, y, sum_same, kth_same, X_test, alpha, n_labels=n_labels,
            interpret=not _on_tpu(),
        )
    return _ref.cp_knn_counts(X, y, sum_same, kth_same, X_test, alpha)


def pallas_active(dtype=jnp.float32) -> bool:
    """True when the f32 kernels dispatch to Pallas (TPU or interpret).

    Callers that keep a bit-exact pure-jnp fallback (the streaming
    regression read path) use this to pick the fused route only where it
    actually runs as a kernel.
    """
    return dtype != jnp.float64 and (_on_tpu() or _interpret())


def active_route(dtype=jnp.float32) -> dict:
    """Snapshot of the kernel dispatch route for reports/audits.

    Pure host-side introspection (no compilation, no device work) —
    recorded verbatim in the static-audit JSON report so a pass/fail is
    attributable to the backend that produced the HLO.
    """
    return {
        "backend": jax.default_backend(),
        "on_tpu": _on_tpu(),
        "interpret": _interpret(),
        "pallas_active": pallas_active(dtype),
        "f64_reference": dtype == jnp.float64,
    }


def interval_sweep(X, a_prime, kth_dist, kth_label, live, X_test, a_test, k):
    """Fused regression-CP critical points (lo, hi); Pallas on TPU."""
    if X.dtype == jnp.float64:
        return _ref.reg_interval_endpoints(
            X, a_prime, kth_dist, kth_label, live, X_test, a_test, k)
    if _on_tpu() or _interpret():
        from repro.kernels.interval_sweep import interval_sweep as _pallas

        return _pallas(
            X, a_prime, kth_dist, kth_label, live, X_test, a_test, k=k,
            interpret=not _on_tpu(),
        )
    return _ref.reg_interval_endpoints(
        X, a_prime, kth_dist, kth_label, live, X_test, a_test, k)


def stream_update(X, y, nbr_d, nbr_y, x_new, y_new, n, *, mode, head=None,
                  wrap=None):
    """Fused streaming-observe front end: distance row + gated ordered
    k-best merge for one incoming point; Pallas on TPU.

    ``mode="class"`` (same-label gate, row-difference distances) serves
    ``core.online``; ``mode="reg"`` (k-th-distance gate, ``sq_dists``
    distances, labels ride along) serves ``regression.stream``.
    ``nbr_y=None`` (classification has no label lists) passes zeros
    through. ``head``/``wrap`` (traced scalars or None) select the
    serving engines' ring-buffer slot layout — live slots
    ``(head + i) % wrap`` instead of ``[0, n)``. Returns
    ``(d_row, nbr_d', nbr_y')`` in ``X.dtype``.
    """
    if nbr_y is None:
        nbr_y = jnp.zeros_like(nbr_d)
    if X.dtype == jnp.float64:
        return _ref.stream_update_fast(X, y, nbr_d, nbr_y, x_new, y_new, n,
                                       mode=mode, head=head, wrap=wrap)
    if _on_tpu() or _interpret():
        from repro.kernels.stream_update import stream_update as _pallas

        d, nd, ny = _pallas(X, y, nbr_d, nbr_y, x_new, y_new, n,
                            mode=mode, interpret=not _on_tpu(), head=head,
                            wrap=wrap)
        return (d.astype(X.dtype), nd.astype(nbr_d.dtype),
                ny.astype(nbr_y.dtype))
    # sortless form — bit-identical to _ref.stream_update, much faster
    # on CPU (no comparator sort); the parity tests pin the two together
    return _ref.stream_update_fast(X, y, nbr_d, nbr_y, x_new, y_new, n,
                                   mode=mode, head=head, wrap=wrap)


def dist_insert(D, row, idx):
    """Row and column ``idx`` of the distance matrix ``D`` (cap, cap) set
    to ``row`` (w,) over its leading (w, w) block; on TPU the column is
    the Pallas lane-strip insert, which keeps ``D`` row-major (callable
    under ``vmap``: one kernel call covers every tenant)."""
    if pallas_active(D.dtype):
        from repro.kernels.dist_insert import dist_insert as _pallas

        return _pallas(D, row, idx, interpret=not _on_tpu())
    return _ref.dist_insert(D, row, idx)


def _pow2(v: int, lo: int = 8) -> int:
    n = lo
    while n < v:
        n *= 2
    return n


def boot_fit_forest(X, y, W, feat_choice, thr_u, *, n_labels, depth):
    """Stacked weighted extra-tree fits for the bootstrap measure.

    The production path on every backend is the vmapped jitted kernel in
    ``boot_forest.py`` (one dispatch trains the whole batch); the
    per-tree numpy oracle in ``ref.py`` is the semantics of record
    (``REPRO_BOOT_FOREST=ref`` forces it, e.g. to bisect a parity
    failure). Batch and row dims are padded to power-of-two buckets so
    the streaming updates (whose shapes drift every tick) reuse a handful
    of compiled programs — zero-weight rows and zero-weight trees are
    masked out of the fit, so padding is bit-neutral. Returns numpy
    ``(feat, thresh, leaf)``, each ``(S, n_nodes)`` — the bootstrap
    state lives on the host.
    """
    import numpy as np

    if os.environ.get("REPRO_BOOT_FOREST") == "ref":
        outs = [_ref.boot_fit_tree(X, y, W[s], feat_choice[s], thr_u[s],
                                   n_labels, depth)
                for s in range(W.shape[0])]
        return tuple(np.stack([o[i] for o in outs]) for i in range(3))
    from repro.kernels.boot_forest import fit_forest

    S, m = W.shape
    # tree batches vary tick-to-tick in the streaming updates; a high
    # floor pins the batch bucket so almost nothing ever recompiles
    Sp, mp = _pow2(S, 64), _pow2(m)
    Xp = np.zeros((mp, X.shape[1]), np.float32)
    Xp[:m] = X
    yp = np.zeros(mp, np.int32)
    yp[:m] = y
    Wp = np.zeros((Sp, mp), np.int32)
    Wp[:S, :m] = W
    nn = feat_choice.shape[1]
    fcp = np.zeros((Sp, nn), np.int32)
    fcp[:S] = feat_choice
    up = np.zeros((Sp, nn), np.float32)
    up[:S] = thr_u
    feat, thresh, leaf = fit_forest(
        jnp.asarray(Xp), jnp.asarray(yp), jnp.asarray(Wp),
        jnp.asarray(fcp), jnp.asarray(up), n_labels=n_labels, depth=depth)
    return (np.asarray(feat)[:S], np.asarray(thresh)[:S],
            np.asarray(leaf)[:S])


def boot_forest_predict(feat, thresh, leaf, Xq):
    """Labels (S, q) of S stacked extra-trees on query rows (q, p)."""
    import numpy as np

    if os.environ.get("REPRO_BOOT_FOREST") == "ref":
        return np.stack([_ref.boot_predict_tree(feat[s], thresh[s], leaf[s],
                                                Xq)
                         for s in range(feat.shape[0])])
    from repro.kernels.boot_forest import forest_predict

    S, q = feat.shape[0], Xq.shape[0]
    Sp, qp = _pow2(S, 64), _pow2(q)
    fp = np.full((Sp, feat.shape[1]), -1, np.int32)
    fp[:S] = feat
    tp = np.zeros((Sp, feat.shape[1]), np.float32)
    tp[:S] = thresh
    lp = np.zeros((Sp, feat.shape[1]), np.int32)
    lp[:S] = leaf
    Xp = np.zeros((qp, Xq.shape[1]), np.float32)
    Xp[:q] = Xq
    out = forest_predict(jnp.asarray(fp), jnp.asarray(tp),
                         jnp.asarray(lp), jnp.asarray(Xp))
    return np.asarray(out)[:S, :q]


# past this many score elements per (batch, head), fall back to the chunked
# online-softmax path off-TPU so 32k/500k sequences stay memory-bounded
_DENSE_SCORE_LIMIT = 2048 * 2048


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    softcap=None):
    if _on_tpu() or _interpret():
        from repro.kernels.flash_attention import flash_attention as _pallas

        return _pallas(q, k, v, causal=causal, window=window, scale=scale,
                       softcap=softcap, interpret=not _on_tpu())
    if q.shape[1] * k.shape[1] > _DENSE_SCORE_LIMIT:
        return _ref.chunked_attention(q, k, v, causal=causal, window=window,
                                      scale=scale, softcap=softcap)
    return _ref.flash_attention(q, k, v, causal=causal, window=window,
                                scale=scale, softcap=softcap)
