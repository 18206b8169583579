"""Tiled pairwise squared-Euclidean distance kernel (Pallas, TPU).

The paper's optimized-CP training phase is dominated by the O(n^2) pairwise
distance matrix (Section 3.1). On TPU we compute ||a-b||^2 = ||a||^2 +
||b||^2 - 2 a.b so that the cross term runs on the MXU; row norms are
recomputed per tile (P flops/element — negligible next to the matmul).

BlockSpec tiling: A tiles (bm, P) and B tiles (bn, P) stay resident in VMEM
for a (bm, bn) output tile; P is zero-padded to a lane multiple (128) so the
MXU operates on aligned shapes. Accumulation is f32 regardless of input dtype,
and the cross term asks for full f32 passes (``Precision.HIGHEST``) so the
distances match the f32 reference rather than a one-pass bf16 product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, b_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    ab = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST
    )
    a2 = jnp.sum(a * a, axis=1, keepdims=True)
    b2 = jnp.sum(b * b, axis=1, keepdims=True)
    o_ref[...] = (a2 + b2.T - 2.0 * ab).astype(o_ref.dtype)


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret")
)
def pairwise_sq_dists(
    A: jnp.ndarray,
    B: jnp.ndarray,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Squared distances (m, n) between rows of A (m, p) and B (n, p)."""
    m, _ = A.shape
    n, _ = B.shape
    bm, bn = min(block_m, m), min(block_n, n)
    Ap = _pad_to(_pad_to(A, 1, 128), 0, bm)
    Bp = _pad_to(_pad_to(B, 1, 128), 0, bn)
    mp, p = Ap.shape
    np_, _ = Bp.shape
    out = pl.pallas_call(
        _kernel,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, p), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, p), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
        name="pairwise_sq_dists",
    )(Ap, Bp)
    return out[:m, :n]
