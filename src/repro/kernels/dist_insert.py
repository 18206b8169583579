"""In-place column insert into the maintained distance matrix (Pallas, TPU).

Every sliding tick writes the new point's distance row into row ``idx``
and column ``idx`` of each tenant's (cap, cap) distance block ``D``.
The row is contiguous; the column is one lane of every row. Written as
an XLA scatter, the column makes the TPU compiler keep ``D``
column-major for the whole scanned chunk, and re-lay the full block
(two (S, cap, cap) copies a tick) for every row-major user.

This kernel writes the column without touching the rest of ``D``: per
tenant it reads the (rows, 128) lane strip that holds column ``idx``
(the whole row when cap <= 128), sets the one lane, and writes the
strip back into the same buffer (``input_output_aliases``). The strip's
block index comes from the scalar-prefetched ``idx``, so one call over
the tenant grid moves S x rows x 128 words each way, and ``D`` keeps
the default row-major layout the custom call pins. Where 128 does not
divide cap, the last strip overhangs the row: Pallas pads what it reads
past the edge and drops what it writes there, so every cap takes the
same kernel. The row, contiguous, is an XLA scatter
ahead of the kernel, in place as well.

``pallas_call`` batches scalar-prefetched operands as a loop over the
batch, so the op is a ``custom_vmap``: under ``vmap`` it calls the
kernel once on the stacked tenants.

``kernels/ref.py::dist_insert`` is the semantics of record.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _kernel(idx_ref, row_ref, D_ref, out_ref, *, w, lanes):
    lane = idx_ref[pl.program_id(0)] % lanes
    strip = D_ref[...]  # (rows, lanes)
    rows = strip.shape[0]
    # the row as a column: broadcast over sublanes, then transpose
    col = jnp.transpose(jnp.broadcast_to(row_ref[...], (lanes, rows)))
    r = jax.lax.broadcasted_iota(jnp.int32, strip.shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, strip.shape, 1)
    out_ref[...] = jnp.where((c == lane) & (r < w), col, strip)


def _insert_batched(D, row, idx, *, interpret):
    """Column insert over a leading tenant axis: D (B, cap, cap), row
    (B, w), idx (B,)."""
    B, cap, _ = D.shape
    w = row.shape[-1]
    lanes = min(cap, LANES)  # the last strip may overhang the row
    rows = min(cap, -(-w // 8) * 8)  # sublane-aligned cover of [:w]
    rowp = jnp.pad(row.astype(D.dtype), ((0, 0), (0, rows - w)))[:, None]
    kern = functools.partial(_kernel, w=w, lanes=lanes)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, 1, rows), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((None, rows, lanes),
                             lambda b, i: (b, 0, i[b] // lanes)),
            ],
            out_specs=pl.BlockSpec((None, rows, lanes),
                                   lambda b, i: (b, 0, i[b] // lanes)),
        ),
        out_shape=jax.ShapeDtypeStruct(D.shape, D.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="dist_insert",
    )(idx.astype(jnp.int32), rowp, D)


def _insert(D, row, idx, *, interpret):
    """``D.at[..., idx, :w].set(row).at[..., :w, idx].set(row)`` over any
    leading axes: the row by scatter, the column by the kernel."""
    lead = D.shape[:-2]
    cap = D.shape[-1]
    Db = D.reshape((-1, cap, cap))
    rowb = row.reshape((-1, row.shape[-1]))
    idxb = idx.reshape((-1,))
    w = rowb.shape[-1]
    Db = jax.vmap(lambda d, r, i: d.at[i, :w].set(r))(Db, rowb, idxb)
    Db = _insert_batched(Db, rowb, idxb, interpret=interpret)
    return Db.reshape(lead + (cap, cap))


@functools.cache
def _op(interpret: bool):
    @jax.custom_batching.custom_vmap
    def op(D, row, idx):
        return _insert(D, row, idx, interpret=interpret)

    @op.def_vmap
    def _(axis_size, in_batched, D, row, idx):
        D, row, idx = (
            x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, b in zip((D, row, idx), in_batched))
        # batched operands carry the vmap axis first; the op takes any
        # leading axes, so a nested vmap lands here again, one axis more
        return op(D, row, idx), True

    return op


def dist_insert(D, row, idx, *, interpret: bool = False):
    """Write ``row`` (w,) into row ``idx`` and column ``idx`` of the
    leading (w, w) block of ``D`` (cap, cap), in place under donation.
    Bit-equal to ``ref.dist_insert``."""
    return _op(interpret)(D, row, jnp.asarray(idx, jnp.int32))
