"""Fused streaming-observe front end (Pallas, TPU).

The O(cap) hot path of both serving engines' ``observe`` tick is, per
incoming point: a distance row against the capacity-padded window (the
regression state's ``a^2+b^2-2ab`` form, the classification state's
row-difference form), a per-row admission gate, and an ordered insert
into every live row's k-best neighbour list. The naive sequence
round-trips the (cap,) distance row and the (cap, k) lists through HBM
several times (distances, gate, concat, sort, take); this kernel fuses
all of it into one VMEM-resident pass.

The ordered insert is branch-free: with an ascending list L and
candidate c, ``pos = #{j : L[j] <= c}`` places the candidate strictly
below equal values — exactly the stable-argsort-with-candidate-last tie
rule the streaming exactness proofs rest on — and the new list is an
elementwise select between L, c, and L shifted right by one. No sort
runs in the kernel.

Layout: the window is the lane axis. The kernel reads the features as
(p, tenants, cap) and the lists as (k, tenants, cap), one (tenants,
cap) plane per feature or list entry, and the labels and the emitted
distance row as (tenants, cap) rows. The engines stack their state
(tenants, cap, p) and (tenants, cap, k), which the chip lays out with
the window minor and the short axis major, so each transpose in and out
is a relabelling, not a copy, and no operand is padded to 128 lanes.
Per-tenant scalars (occupancy, label, ring head and modulus) and the
new point ride in one row a tenant, one column broadcast over the lanes
at a time. The op is a ``custom_vmap``: under the engines' ``vmap``
over tenants it calls the kernel once on the stacked tenants, 8 tenants
or more a grid step. Its operands and its k-best lists are held to
HBM, so the kernel's own time covers the bytes it moves; the emitted
distance row, one (tenants, cap) plane of the 62 (classification) or
92 (regression) it moves at k 15 and p 30, is left for XLA to place.

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
from jax.experimental.pallas import tpu as pltpu

_BIG = 1e30  # matches core.online.BIG / core.regression.BIG
# lanes (window slots) and sublanes (tenants) a grid step covers at most
_MAX_LANES = 1024
_STEP_WORDS = 8192


def _kernel(ten_ref, X_ref, y_ref, nd_ref, *rest, k, p, mode, block_n):
    if mode == "reg":
        ny_ref, d_ref, ndo_ref, nyo_ref = rest
    else:
        d_ref, ndo_ref = rest
    # one row a tenant: n, y_new, head, wrap, then the new point
    ten = ten_ref[...]
    n, y_new, head, wrap = (ten[:, i:i + 1] for i in range(4))
    x = ten[:, 4:4 + p]
    # one (tb, bn) plane per feature, summed in feature order
    if mode == "class":
        d2 = None
        for j in range(p):
            diff = X_ref[j].astype(jnp.float32) - x[:, j:j + 1]
            d2 = diff * diff if d2 is None else d2 + diff * diff
    else:
        ab = a2 = b2 = None
        for j in range(p):
            Xj = X_ref[j].astype(jnp.float32)
            xj = x[:, j:j + 1]
            ab = Xj * xj if ab is None else ab + Xj * xj
            a2 = Xj * Xj if a2 is None else a2 + Xj * Xj
            b2 = xj * xj if b2 is None else b2 + xj * xj
        d2 = a2 + b2 - 2.0 * ab
    d = jnp.sqrt(jnp.maximum(d2, 0.0))  # (tb, bn)

    # the TPU iota is integer-only: build slot ids in int32, then cast
    rows = (jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
            + block_n * pl.program_id(1)).astype(jnp.float32)
    # ring liveness: slot (head + i) % wrap is live for i < n. Slot ids,
    # head, wrap and n are exact in f32 (cap << 2^24); the explicit
    # rows < wrap guard keeps slots beyond the ring modulus (and the
    # block padding) inert even when the wrap term would hand them a
    # small age.
    age = jnp.where(rows < head, rows - head + wrap, rows - head)
    live = (age < n) & (rows < wrap)
    d_row = jnp.where(live, d, _BIG)

    yb = y_ref[...].astype(jnp.float32)  # (tb, bn)
    if mode == "class":
        c = jnp.where(live & (yb == y_new), d_row, _BIG)
    else:
        # strict: ties keep the incumbent
        c = jnp.where(live & (d < nd_ref[k - 1]), d, _BIG)

    # branch-free ordered insert, after equal values (candidate has the
    # largest arrival index); c == BIG lands at pos == k => list unchanged
    pos = None
    for i in range(k):
        le = (nd_ref[i] <= c).astype(jnp.int32)
        pos = le if pos is None else pos + le
    d_ref[...] = d_row
    for i in range(k):
        Li = nd_ref[i]
        newL = jnp.where(pos > i, Li,
                         jnp.where(pos == i, c, nd_ref[max(i - 1, 0)]))
        ndo_ref[i] = newL
        if mode == "reg":
            Yi = ny_ref[i]
            newY = jnp.where(pos > i, Yi,
                             jnp.where(pos == i, y_new,
                                       ny_ref[max(i - 1, 0)]))
            # missing-neighbour slots carry the row's own label (fit's
            # convention at window size n == k)
            nyo_ref[i] = jnp.where(newL >= _BIG, yb, newY)


def _blocks(B: int, cap: int, block_n: int | None):
    """Tenant block (sublanes) and lane block (window slots) of one grid
    step: the whole window up to ``_MAX_LANES`` slots, and as many
    tenants (a multiple of 8) as keep a feature plane near
    ``_STEP_WORDS`` words. A block may overhang the array's end: Pallas
    pads what it reads there and drops what it writes."""
    bn = min(cap, block_n or _MAX_LANES)
    if B <= 8:
        return B, bn
    return max(8, min(B, _STEP_WORDS // bn) // 8 * 8), bn


def _batched(X, y, lists, x_new, scal, *, mode, block_n, interpret):
    """The kernel over a leading tenant axis: X (B, cap, p), y (B, cap),
    lists (B, cap, k) each, x_new (B, p), scal (B, 4).

    Operands and the lists are held to HBM: left to place them, XLA
    stages a small window's features and lists in VMEM around the call,
    and the kernel's time would no longer hold the traffic it is there
    to do. The distance row is not: XLA keeps it in VMEM for its
    consumers (the regression tick gathers it element by element in
    ring order, at twice the time from HBM)."""
    B, cap, p = X.shape
    k = lists[0].shape[-1]
    tb, bn = _blocks(B, cap, block_n)
    f32 = jnp.float32
    # (B, cap, m) -> (m, B, cap): the chip's own layout of the stacked
    # leaves, so the transposes are relabellings
    major = lambda a: jnp.transpose(a.astype(f32), (2, 0, 1))
    # per-tenant scalars and the new point in one row a tenant
    ten = jnp.concatenate([scal, x_new.astype(f32)], axis=1)
    operands = [ten, major(X), y] + [major(a) for a in lists]
    if not interpret:
        operands = [pltpu.with_memory_space_constraint(a, pltpu.HBM)
                    for a in operands]
    n_lists = len(lists)
    plane = lambda m: pl.BlockSpec((m, tb, bn), lambda i, j: (0, i, j))
    row = pl.BlockSpec((tb, bn), lambda i, j: (i, j))
    outs = pl.pallas_call(
        functools.partial(_kernel, k=k, p=p, mode=mode, block_n=bn),
        grid=(pl.cdiv(B, tb), pl.cdiv(cap, bn)),
        in_specs=[pl.BlockSpec((tb, 4 + p), lambda i, j: (i, 0)),
                  plane(p), row] + [plane(k)] * n_lists,
        out_specs=[row] + [plane(k)] * n_lists,
        out_shape=[jax.ShapeDtypeStruct((B, cap), f32)]
        + [pltpu.HBM((k, B, cap), f32)] * n_lists,
        interpret=interpret,
        name="stream_update",
    )(*operands)
    return outs[0], tuple(jnp.transpose(a, (1, 2, 0)) for a in outs[1:])


def _flat(X, y, lists, x_new, scal, **kw):
    """``_batched`` over any leading axes (none for one tenant)."""
    lead = X.shape[:-2]
    cap, p = X.shape[-2:]
    k = lists[0].shape[-1]
    flat = lambda a, *tail: a.reshape((-1,) + tail)
    d, out = _batched(flat(X, cap, p), flat(y, cap),
                      tuple(flat(a, cap, k) for a in lists),
                      flat(x_new, p), flat(scal, 4), **kw)
    return (d.reshape(lead + (cap,)),
            tuple(a.reshape(lead + (cap, k)) for a in out))


@functools.cache
def _op(mode: str, block_n: int | None, interpret: bool):
    @jax.custom_batching.custom_vmap
    def op(X, y, lists, x_new, scal):
        return _flat(X, y, lists, x_new, scal, mode=mode, block_n=block_n,
                     interpret=interpret)

    @op.def_vmap
    def _(axis_size, in_batched, *args):
        args = jax.tree_util.tree_map(
            lambda a, b: a if b
            else jnp.broadcast_to(a, (axis_size,) + a.shape),
            args, tuple(in_batched))
        # batched operands carry the vmap axis first; the op takes any
        # leading axes, so a nested vmap lands here again, one axis more
        out = op(*args)
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return op


@functools.partial(
    jax.jit, static_argnames=("mode", "block_n", "interpret")
)
def stream_update(
    X, y, nbr_d, nbr_y, x_new, y_new, n, *,
    mode: str, block_n: int | None = None, interpret: bool = False,
    head=None, wrap=None,
):
    """Fused distance row + gated ordered k-best merge for one new point.

    Returns ``(d_row (cap,), nbr_d' (cap, k), nbr_y' (cap, k))``, all
    f32 — see ``ref.stream_update`` for the exact semantics per mode.
    ``head`` selects the serving engines' ring-buffer slot layout (live
    slots ``(head + i) % wrap``, slots >= wrap inert); None/0 with a
    full-capacity ``wrap`` is the linear layout. ``mode="class"`` passes
    ``nbr_y`` through untouched (it may be None). ``block_n`` caps the
    lane block over the window (default: the whole window up to 1024).
    """
    if mode not in ("class", "reg"):
        raise ValueError(f"unknown stream_update mode {mode!r}")
    cap = X.shape[0]
    scal = jnp.stack([
        jnp.asarray(v, jnp.float32).reshape(())
        for v in (n, y_new, 0 if head is None else head,
                  cap if wrap is None else wrap)])
    lists = (nbr_d, nbr_y) if mode == "reg" else (nbr_d,)
    d, out = _op(mode, block_n, interpret)(X, y, lists, x_new, scal)
    return d, out[0], out[1] if mode == "reg" else nbr_y
