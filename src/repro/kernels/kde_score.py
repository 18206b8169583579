"""Masked Gaussian-kernel row-sum kernel (Pallas, TPU) for KDE CP.

Computes out[i] = sum_{j: y_B[j]==y_A[i], (j!=i)} exp(-||A_i-B_j||^2/(2h^2))
— the KDE provisional scores (paper Section 4.1) — in a single pass: the
distance cross-term runs on the MXU, the exp/mask/reduce on the VPU, and the
(m,) accumulator is revisited across the n-tile grid dimension (TPU grids are
sequential), so the O(n^2) intermediate distance matrix never touches HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pairwise_dist import _pad_to


def _kernel(a_ref, b_ref, ya_ref, yb_ref, o_ref, *, inv2h2, bm, bn,
            n_real, exclude_diag):
    j = pl.program_id(1)
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    ab = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST
    )
    a2 = jnp.sum(a * a, axis=1, keepdims=True)
    b2 = jnp.sum(b * b, axis=1, keepdims=True)
    d2 = a2 + b2.T - 2.0 * ab
    K = jnp.exp(-jnp.maximum(d2, 0.0) * inv2h2)
    mask = ya_ref[...] == yb_ref[...].T  # (bm,1)==(1,bn) -> (bm,bn)
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    mask &= col < n_real
    if exclude_diag:
        i = pl.program_id(0)
        row = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
        mask &= row != col
    partial = jnp.sum(jnp.where(mask, K, 0.0), axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += partial.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("h", "exclude_diag", "block_m", "block_n", "interpret"),
)
def kde_rowsums(
    A, B, y_A, y_B, *, h: float = 1.0, exclude_diag: bool = False,
    block_m: int = 256, block_n: int = 256, interpret: bool = False,
):
    m, _ = A.shape
    n, _ = B.shape
    bm, bn = min(block_m, m), min(block_n, n)
    Ap = _pad_to(_pad_to(A, 1, 128), 0, bm)
    Bp = _pad_to(_pad_to(B, 1, 128), 0, bn)
    # pad labels with distinct sentinels so padded rows/cols never match
    # (real labels map to y+2 on BOTH sides; pads map to 0 vs -1)
    ya = _pad_to(y_A.astype(jnp.int32)[:, None] + 2, 0, bm)  # pad -> 0
    yb = _pad_to(y_B.astype(jnp.int32)[:, None] + 3, 0, bn) - 1  # pad -> -1
    mp, p = Ap.shape
    np_, _ = Bp.shape
    kern = functools.partial(
        _kernel, inv2h2=1.0 / (2.0 * h * h), bm=bm, bn=bn, n_real=n,
        exclude_diag=exclude_diag,
    )
    out = pl.pallas_call(
        kern,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, p), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, p), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        interpret=interpret,
    )(Ap, Bp, ya, yb)
    return out[:m, 0]
