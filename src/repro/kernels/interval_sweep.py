"""Fused regression-CP interval-sweep front end (Pallas, TPU).

The streaming regression read path (paper Section 8.1 served online) is,
per test point: an O(n) distance row, the O(1)-per-row incremental &
decremental update of the affine score coefficients (a_i, b_i), and the
critical points of S_i = {t : |a_i + b_i t| >= |a + t|} that feed the
O(n log n) hull sweep. The naive sequence round-trips the (m, n) distance
matrix plus the (m, n) coefficient matrices through HBM; this kernel fuses
distances (MXU), the coefficient update and the root computation (VPU)
into one VMEM-resident pass, emitting only the (m, n) critical-point
matrices the sweep needs.

The candidate-score vector ``a_test`` (a top-k over the distance row) and
the sort-based sweep itself stay with the caller — neither belongs in a
tiled kernel. ``live`` masks capacity padding: dead columns emit the
neutral empty interval (+inf, -inf), which the sweep ignores bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pairwise_dist import _pad_to


def _kernel(xt_ref, a_ref, x_ref, ap_ref, kd_ref, kl_ref, live_ref,
            lo_ref, hi_ref, *, k, eps):
    INF = jnp.inf
    xt = xt_ref[...].astype(jnp.float32)  # (bm, p)
    x = x_ref[...].astype(jnp.float32)  # (bn, p)
    ab = jax.lax.dot_general(
        xt, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST
    )
    a2 = jnp.sum(xt * xt, axis=1, keepdims=True)
    b2 = jnp.sum(x * x, axis=1, keepdims=True)
    d = jnp.sqrt(jnp.maximum(a2 + b2.T - 2.0 * ab, 0.0))  # (bm, bn)

    a_prime = ap_ref[...].T  # (1, bn)
    kth = kd_ref[...].T  # (1, bn)
    upd = a_prime + kl_ref[...].T / k
    live = live_ref[...].T > 0.5  # (1, bn)

    enters = live & (d < kth)
    a_i = jnp.where(enters, upd, a_prime)
    b_i = jnp.where(enters, -1.0 / k, 0.0)
    a = a_ref[...]  # (bm, 1) candidate score per test row

    A2 = b_i * b_i - 1.0
    B1 = a_i * b_i - a
    C0 = a_i * a_i - a * a
    disc = B1 * B1 - A2 * C0
    sq = jnp.sqrt(jnp.maximum(disc, 0.0))
    denom = jnp.where(jnp.abs(A2) < eps, 1.0, A2)
    r1 = (-B1 + sq) / denom
    r2 = (-B1 - sq) / denom
    quad_lo = jnp.where(disc >= 0.0, jnp.minimum(r1, r2), INF)
    quad_hi = jnp.where(disc >= 0.0, jnp.maximum(r1, r2), -INF)
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
    lo_ref[...] = jnp.where(live, lo, INF)
    hi_ref[...] = jnp.where(live, hi, -INF)


@functools.partial(
    jax.jit, static_argnames=("k", "block_m", "block_n", "interpret")
)
def interval_sweep(
    X, a_prime, kth_dist, kth_label, live, X_test, a_test, *,
    k: int, block_m: int = 128, block_n: int = 512,
    interpret: bool = False,
):
    """Critical points (lo, hi), each (m, n), for the regression sweep."""
    m = X_test.shape[0]
    n = X.shape[0]
    bm, bn = min(block_m, m), min(block_n, n)
    Xtp = _pad_to(_pad_to(X_test, 1, 128), 0, bm)
    Xp = _pad_to(_pad_to(X, 1, 128), 0, bn)
    app = _pad_to(a_prime.astype(jnp.float32)[:, None], 0, bn)
    kdp = _pad_to(kth_dist.astype(jnp.float32)[:, None], 0, bn)
    klp = _pad_to(kth_label.astype(jnp.float32)[:, None], 0, bn)
    lvp = _pad_to(live.astype(jnp.float32)[:, None], 0, bn)  # pad -> dead
    atp = _pad_to(a_test.astype(jnp.float32)[:, None], 0, bm)
    mp, p = Xtp.shape
    np_, _ = Xp.shape
    kern = functools.partial(_kernel, k=k, eps=1e-12)
    lo, hi = pl.pallas_call(
        kern,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, p), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, p), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, np_), jnp.float32),
            jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        ],
        interpret=interpret,
        name="interval_sweep",
    )(Xtp, atp, Xp, app, kdp, klp, lvp)
    return lo[:m, :n], hi[:m, :n]
