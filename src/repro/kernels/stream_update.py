"""Fused streaming-observe front end (Pallas, TPU).

The O(cap) hot path of both serving engines' ``observe`` tick is, per
incoming point: a distance row against the capacity-padded window (MXU
for the regression state's ``a^2+b^2-2ab`` form, VPU for the
classification state's row-difference form), a per-row admission gate,
and an ordered insert into every live row's k-best neighbour list. The
naive sequence round-trips the (cap,) distance row and the (cap, k)
lists through HBM several times (distances, gate, concat, sort, take);
this kernel fuses all of it into one VMEM-resident pass over row blocks.

The ordered insert is branch-free: with an ascending list L and
candidate c, ``pos = #{j : L[j] <= c}`` places the candidate strictly
below equal values — exactly the stable-argsort-with-candidate-last tie
rule the streaming exactness proofs rest on — and the new list is an
elementwise select between L, c, and L shifted right by one. No sort
runs in the kernel.

Stays with the caller (none of it belongs in a tiled kernel):

* the new row's *own* k-best list — a top_k over the emitted distance
  row;
* the write of the distance row into the maintained pairwise matrix
  ``D``'s row idx and column idx (``ops.dist_insert``: a row scatter
  and the lane-strip kernel of ``dist_insert.py``, both in place once
  the jitted step donates its input state);
* the smoothed p-value (an O(cap) reduction over pre-update scores).

``kernels/ref.py::stream_update`` is the semantics of record; the
parity test sweeps both modes against it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pairwise_dist import _pad_to

_BIG = 1e30  # matches core.online.BIG / core.regression.BIG


def _kernel(scal_ref, x_ref, X_ref, y_ref, nd_ref, ny_ref,
            d_ref, ndo_ref, nyo_ref, *, k, mode, block_n):
    n = scal_ref[0, 0]
    y_new = scal_ref[0, 1]
    head = scal_ref[0, 2]  # ring-buffer start slot; 0 == linear layout
    wrap = scal_ref[0, 3]  # ring modulus; == cap in the linear layout
    x = x_ref[...].astype(jnp.float32)  # (1, p)
    X = X_ref[...].astype(jnp.float32)  # (bn, p)
    if mode == "class":
        diff = X - x
        d2 = jnp.sum(diff * diff, axis=1, keepdims=True)  # (bn, 1)
    else:
        ab = jax.lax.dot_general(
            X, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)  # (bn, 1)
        a2 = jnp.sum(X * X, axis=1, keepdims=True)
        b2 = jnp.sum(x * x, axis=1, keepdims=True)  # (1, 1)
        d2 = a2 + b2 - 2.0 * ab
    d = jnp.sqrt(jnp.maximum(d2, 0.0))  # (bn, 1)

    # the TPU iota is integer-only: build row ids in int32, then cast
    rows = (jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
            + block_n * pl.program_id(0)).astype(jnp.float32)
    # ring liveness: slot (head + i) % wrap is live for i < n. Row ids,
    # head, wrap and n are exact in f32 (cap << 2^24); the explicit
    # rows < wrap guard keeps slots beyond the ring modulus (and the
    # block-size padding rows) inert even when the wrap term would hand
    # them a small age.
    age = jnp.where(rows < head, rows - head + wrap, rows - head)
    live = (age < n) & (rows < wrap)
    d_row = jnp.where(live, d, _BIG)

    L = nd_ref[...].astype(jnp.float32)  # (bn, k) ascending, BIG-padded
    yb = y_ref[...].astype(jnp.float32)  # (bn, 1)
    if mode == "class":
        gate = live & (yb == y_new)
        c = jnp.where(gate, d_row, _BIG)
    else:
        gate = live & (d < L[:, k - 1:k])  # strict: ties keep incumbent
        c = jnp.where(gate, d, _BIG)

    # branch-free ordered insert, after equal values (candidate has the
    # largest arrival index); c == BIG lands at pos == k => list unchanged
    pos = jnp.sum((L <= c).astype(jnp.int32), axis=1, keepdims=True)
    cols = jax.lax.broadcasted_iota(jnp.int32, L.shape, 1)
    Lsh = jnp.concatenate([L[:, :1], L[:, :k - 1]], axis=1)
    newL = jnp.where(cols < pos, L, jnp.where(cols == pos, c, Lsh))

    d_ref[...] = d_row
    ndo_ref[...] = newL
    if mode == "reg":
        Y = ny_ref[...].astype(jnp.float32)
        Ysh = jnp.concatenate([Y[:, :1], Y[:, :k - 1]], axis=1)
        newY = jnp.where(cols < pos, Y, jnp.where(cols == pos, y_new, Ysh))
        # missing-neighbour slots carry the row's own label (fit's
        # convention at window size n == k)
        nyo_ref[...] = jnp.where(newL >= _BIG, yb, newY)
    else:
        nyo_ref[...] = ny_ref[...]


@functools.partial(
    jax.jit, static_argnames=("mode", "block_n", "interpret")
)
def stream_update(
    X, y, nbr_d, nbr_y, x_new, y_new, n, *,
    mode: str, block_n: int = 256, interpret: bool = False, head=None,
    wrap=None,
):
    """Fused distance row + gated ordered k-best merge for one new point.

    Returns ``(d_row (cap,), nbr_d' (cap, k), nbr_y' (cap, k))``, all
    f32 — see ``ref.stream_update`` for the exact semantics per mode.
    ``head`` selects the serving engines' ring-buffer slot layout (live
    slots ``(head + i) % wrap``, slots >= wrap inert); None/0 with a
    full-capacity ``wrap`` is the linear layout.
    """
    if mode not in ("class", "reg"):
        raise ValueError(f"unknown stream_update mode {mode!r}")
    cap, _ = X.shape
    k = nbr_d.shape[1]
    bn = min(block_n, cap)
    Xp = _pad_to(_pad_to(X, 1, 128), 0, bn)
    xp = _pad_to(x_new.astype(jnp.float32)[None], 1, 128)
    yp = _pad_to(y.astype(jnp.float32)[:, None], 0, bn)
    ndp = _pad_to(nbr_d.astype(jnp.float32), 0, bn)
    nyp = _pad_to(nbr_y.astype(jnp.float32), 0, bn)
    if head is None:
        head = 0
    if wrap is None:
        wrap = cap
    scal = jnp.stack([jnp.asarray(n, jnp.float32).reshape(()),
                      jnp.asarray(y_new, jnp.float32).reshape(()),
                      jnp.asarray(head, jnp.float32).reshape(()),
                      jnp.asarray(wrap, jnp.float32).reshape(())])[None]
    capp, p = Xp.shape
    kern = functools.partial(_kernel, k=k, mode=mode, block_n=bn)
    d, nd2, ny2 = pl.pallas_call(
        kern,
        grid=(capp // bn,),
        in_specs=[
            pl.BlockSpec((1, 4), lambda j: (0, 0)),
            pl.BlockSpec((1, p), lambda j: (0, 0)),
            pl.BlockSpec((bn, p), lambda j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda j: (j, 0)),
            pl.BlockSpec((bn, k), lambda j: (j, 0)),
            pl.BlockSpec((bn, k), lambda j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda j: (j, 0)),
            pl.BlockSpec((bn, k), lambda j: (j, 0)),
            pl.BlockSpec((bn, k), lambda j: (j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((capp, 1), jnp.float32),
            jax.ShapeDtypeStruct((capp, k), jnp.float32),
            jax.ShapeDtypeStruct((capp, k), jnp.float32),
        ],
        interpret=interpret,
        name="stream_update",
    )(scal, xp, Xp, yp, ndp, nyp)
    return d[:cap, 0], nd2[:cap], ny2[:cap]
