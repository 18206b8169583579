"""Multi-pod full CP — the paper's technique as a sharded serving feature.

The paper's optimized predict phase is, per (test point, label):

    1. an O(n) vector of distances/kernel values to the calibration rows,
    2. an O(1)-per-row incremental&decremental score update,
    3. a rank statistic  #{i : alpha_i >= alpha}.

All three are row-parallel, so the calibration state shards perfectly along
the ("pod", "data") mesh axes: each device holds n/D rows, steps 1-2 are
local, and step 3 is ONE scalar all-reduce per (test, label). The global
candidate score needs the *global* k nearest neighbours of the test point —
a local top-k followed by an all-gather of D*k candidates (k <= 32, so this
collective is tiny next to the count psum).

Test queries shard along the remaining "model" axis: model-parallel groups
serve disjoint query slices, giving data x query 2-D parallelism. On the
2 x 16 x 16 production mesh a 10^9-row calibration set costs ~4M rows/device
per query — the paper's "full CP on large datasets", three orders beyond its
single-host experiments.

Everything here is exact: outputs equal the single-device optimized path
(property-tested), which itself equals naive full CP.

Beyond the calibration-row sharding above, this module also owns the
**tenant-axis** sharding used by the serving engines
(``serving.engine`` / ``regression.engine``): a multi-tenant tick is
embarrassingly parallel across tenants (no cross-tenant communication),
so the stacked session state shards along its leading axis over a 1-D
``("tenants",)`` mesh and a tick runs as ONE shard_map'd dispatch with
**zero collectives** in the body — each device advances its tenant
slice with the exact same per-lane graph as the single-device vmap, so
results are bit-identical leaf-for-leaf (property-tested in
tests/test_distributed.py).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.measures.knn import KnnState

BIG = 1e30

def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# calibration-state sharding
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CpShardingConfig:
    """Mesh-axis assignment for sharded CP serving."""

    row_axes: tuple = ("data",)  # calibration rows shard here
    query_axis: str | None = "model"  # test queries shard here (None = repl.)


def pad_rows(arr: np.ndarray, n_padded: int, fill) -> np.ndarray:
    """Pad axis 0 to n_padded with an inert fill value."""
    pad = n_padded - arr.shape[0]
    if pad == 0:
        return arr
    widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, widths, constant_values=fill)


def shard_knn_state(state: KnnState, mesh, cfg: CpShardingConfig) -> KnnState:
    """Pad rows to the row-shard multiple and place on the mesh.

    Padding rows get label -1 (matches no candidate label) and BIG distance
    lists, so they never enter any count: exactness is preserved.
    """
    shards = int(np.prod([mesh.shape[a] for a in cfg.row_axes]))
    n = state.X.shape[0]
    n_pad = -(-n // shards) * shards
    X = pad_rows(np.asarray(state.X), n_pad, 0.0)
    y = pad_rows(np.asarray(state.y), n_pad, -1)
    bs = pad_rows(np.asarray(state.best_same), n_pad, BIG)
    bd = pad_rows(np.asarray(state.best_diff), n_pad, BIG)
    row_spec = P(cfg.row_axes)
    put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
    return KnnState(
        X=put(X, P(cfg.row_axes, None)),
        y=put(y, row_spec),
        best_same=put(bs, P(cfg.row_axes, None)),
        best_diff=put(bd, P(cfg.row_axes, None)),
    )


# ---------------------------------------------------------------------------
# sharded k-NN CP predict
# ---------------------------------------------------------------------------


def _global_k_best(local_d, mask, k, row_axes):
    """Global k smallest masked distances across the row shards.

    Local top-k (O(n_local)) -> all-gather (D*k values) -> top-k again.
    """
    cand = jnp.where(mask, local_d, BIG)
    # top_k sorts -cand descending, so the negation is ascending (asserted
    # by tests/test_regression_stream.py::test_topk_negation_is_ascending)
    local_best = -jax.lax.top_k(-cand, k)[0]  # (k,) ascending
    gathered = jax.lax.all_gather(local_best, row_axes, tiled=True)  # (D*k,)
    return -jax.lax.top_k(-gathered, k)[0]


def make_knn_pvalues_fn(mesh, *, k: int, simplified: bool, n_labels: int,
                        cfg: CpShardingConfig = CpShardingConfig()):
    """Builds a jitted sharded p-value function: (state, X_test) -> (m, l).

    The returned function expects ``state`` sharded by ``shard_knn_state``
    and X_test sharded along cfg.query_axis (rows) or replicated.
    """
    row_axes = cfg.row_axes

    def local_counts(X, y, best_same, best_diff, X_test):
        """Body run per device: local update + count, then cross-shard
        reductions. X: (n_loc, p); X_test: (m_loc, p)."""
        n_total = jax.lax.psum(
            jnp.sum(y >= 0), row_axes)  # live rows only

        # cancellation-safe: base (k-1 best) + (kth or d); never subtract
        base_same = jnp.sum(best_same[:, :-1], axis=-1)
        kth_same = best_same[:, -1]
        base_diff = jnp.sum(best_diff[:, :-1], axis=-1)
        kth_diff = best_diff[:, -1]

        def per_test(x_t):
            d = jnp.sqrt(jnp.maximum(
                jnp.sum((X - x_t[None]) ** 2, axis=-1), 0.0))

            def per_label(y_hat):
                same = y == y_hat
                # candidate score from GLOBAL k-NN of the test point
                num = jnp.sum(_global_k_best(d, same, k, row_axes))
                if simplified:
                    alpha = num
                else:
                    den = jnp.sum(_global_k_best(d, ~same & (y >= 0), k,
                                                 row_axes))
                    alpha = num / den
                # O(1)-per-row incremental&decremental update (paper Fig. 1)
                upd = same & (d < kth_same)
                a_num = base_same + jnp.where(upd, d, kth_same)
                if simplified:
                    alphas = a_num
                else:
                    updd = (~same) & (y >= 0) & (d < kth_diff)
                    a_den = base_diff + jnp.where(updd, d, kth_diff)
                    alphas = a_num / a_den
                live = y >= 0
                cnt = jax.lax.psum(
                    jnp.sum(jnp.where(live, alphas >= alpha, False)
                            .astype(jnp.int32)),
                    row_axes)
                return (cnt + 1.0) / (n_total + 1.0)

            return jax.vmap(per_label)(
                jnp.arange(n_labels, dtype=y.dtype))

        return jax.lax.map(per_test, X_test)

    in_specs = (
        P(row_axes, None), P(row_axes), P(row_axes, None), P(row_axes, None),
        P(cfg.query_axis, None) if cfg.query_axis else P(None, None),
    )
    out_spec = (P(cfg.query_axis, None) if cfg.query_axis
                else P(None, None))

    sharded = _shard_map(local_counts, mesh, in_specs, out_spec)

    @jax.jit
    def pvalues(state: KnnState, X_test):
        return sharded(state.X, state.y, state.best_same, state.best_diff,
                       X_test)

    return pvalues


# ---------------------------------------------------------------------------
# sharded KDE CP predict
# ---------------------------------------------------------------------------


def make_kde_pvalues_fn(mesh, *, h: float, p_dim: int, n_labels: int,
                        cfg: CpShardingConfig = CpShardingConfig()):
    """Sharded KDE full CP. prelim/class counts shard with the rows; the
    candidate's kernel sum and the rank count are each one psum."""
    row_axes = cfg.row_axes

    def local_counts(X, y, prelim, X_test):
        live = y >= 0
        n_total = jax.lax.psum(jnp.sum(live), row_axes)
        counts_l = jax.vmap(
            lambda lb: jnp.sum((y == lb).astype(jnp.int32)))(
            jnp.arange(n_labels, dtype=y.dtype))
        class_counts = jax.lax.psum(counts_l, row_axes)  # (l,)
        hp = h ** p_dim

        def per_test(x_t):
            d2 = jnp.maximum(jnp.sum((X - x_t[None]) ** 2, axis=-1), 0.0)
            kv = jnp.exp(-d2 / (2.0 * h * h))

            def per_label(y_hat):
                same = (y == y_hat)
                ksum = jax.lax.psum(jnp.sum(jnp.where(same, kv, 0.0)),
                                    row_axes)
                c = class_counts[y_hat.astype(jnp.int32)]
                alpha = -jnp.where(c > 0, ksum / (c * hp), 0.0)
                sums = jnp.where(same, prelim + kv, prelim)
                n_y = (class_counts[jnp.clip(y, 0).astype(jnp.int32)]
                       - 1 + same.astype(class_counts.dtype))
                alphas = -jnp.where(n_y > 0, sums / (n_y * hp), 0.0)
                cnt = jax.lax.psum(
                    jnp.sum(jnp.where(live, alphas >= alpha, False)
                            .astype(jnp.int32)),
                    row_axes)
                return (cnt + 1.0) / (n_total + 1.0)

            return jax.vmap(per_label)(jnp.arange(n_labels, dtype=y.dtype))

        return jax.lax.map(per_test, X_test)

    in_specs = (
        P(row_axes, None), P(row_axes), P(row_axes),
        P(cfg.query_axis, None) if cfg.query_axis else P(None, None),
    )
    out_spec = (P(cfg.query_axis, None) if cfg.query_axis
                else P(None, None))

    sharded = _shard_map(local_counts, mesh, in_specs, out_spec)

    @jax.jit
    def pvalues(X, y, prelim, X_test):
        return sharded(X, y, prelim, X_test)

    return pvalues


# ---------------------------------------------------------------------------
# tenant-axis sharding (the serving engines' multi-device path)
# ---------------------------------------------------------------------------

TENANT_AXIS = "tenants"


def tenant_mesh(shards: int):
    """1-D ``("tenants",)`` mesh over the first ``shards`` devices."""
    from jax.sharding import Mesh

    devs = jax.devices()
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    if shards > len(devs):
        raise ValueError(
            f"shards={shards} exceeds the {len(devs)} visible device(s); "
            "on CPU, force virtual devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "before importing jax")
    return Mesh(np.array(devs[:shards]), (TENANT_AXIS,))


def tenant_spec(leaf) -> P:
    """Leading-axis tenant PartitionSpec for one stacked state leaf."""
    return P(TENANT_AXIS, *([None] * (np.ndim(leaf) - 1)))


def put_tenant_sharded(tree, mesh):
    """Place every leaf of a stacked state pytree with its leading axis
    sharded across the tenant mesh (trailing axes replicated)."""
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, NamedSharding(mesh, tenant_spec(a))),
        tree)


def init_tenant_sharded(build, mesh):
    """``build()``, a stacked state pytree, made in its tenant-sharded
    layout: each device fills only its own tenants' slice, so a state
    larger than one device's memory never lands whole on one device."""
    out = jax.tree_util.tree_map(
        lambda a: NamedSharding(mesh, tenant_spec(a)), jax.eval_shape(build))
    return jax.jit(build, out_shardings=out)()


def place_chunk_args(args, mesh):
    """A chunk's dispatch arguments ``(state, xs, ys, taus, windows,
    actives)`` laid out as ``shard_tenant_chunk`` takes them: the state
    and ``windows`` split on their leading (tenant) axis, the (T, S, ...)
    traffic on axis 1. Arguments already in place stay where they are."""
    state, xs, ys, taus, windows, actives = args
    on = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
    traffic = P(None, TENANT_AXIS)
    return (put_tenant_sharded(state, mesh), on(xs, traffic),
            on(ys, traffic), on(taus, traffic), on(windows, P(TENANT_AXIS)),
            on(actives, traffic))


def pad_tenant_count(n: int, shards: int) -> int:
    """Smallest multiple of ``shards`` >= n (the padded lane count).

    Uneven tenant counts shard by padding with inactive lanes: padded
    lanes stay at their init state (``active`` masks them out of every
    tick), so the live lanes' results are unchanged — the padding-shard
    case is property-tested in tests/test_distributed.py.
    """
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    return -(-n // shards) * shards


def shard_tenant_chunk(chunk, mesh, *, with_stats: bool):
    """shard_map a ``scan_chunk`` tick body over the tenant mesh.

    Inputs follow the engines' dispatch signature
    ``(state, xs, ys, taus, windows, actives)``: state leaves and
    ``windows`` shard their leading (S,) axis, the (T, S, ...) traffic
    arrays shard axis 1. The body contains no collectives — every
    device runs the unmodified chunk on its tenant slice, so the
    composed jit(shard_map(chunk)) keeps buffer donation and
    bit-exactness. With ``with_stats`` the chunk's (len(STAT_KEYS),)
    telemetry vector comes back per shard as a (shards, len) stacked
    array (still no collectives: the cross-shard merge is deferred to
    ``telemetry.device.TickStats.drain``).
    """
    ax = TENANT_AXIS
    in_specs = (P(ax), P(None, ax), P(None, ax), P(None, ax), P(ax),
                P(None, ax))
    if not with_stats:
        return _shard_map(chunk, mesh, in_specs, (P(ax), P(None, ax)))

    # named as the chunk, so a profile names the tick program alike on
    # any number of shards
    @functools.wraps(chunk)
    def body(state, xs, ys, taus, windows, actives):
        out, (ps, st) = chunk(state, xs, ys, taus, windows, actives)
        return out, (ps, st[None])  # (1, len): one stat row per shard

    return _shard_map(body, mesh, in_specs,
                      (P(ax), (P(None, ax), P(ax, None))))


def shard_tenant_fn(fn, mesh, in_tenant, out_spec=None):
    """shard_map a read-path fn whose args are tenant-stacked or global.

    ``in_tenant`` is one bool per positional arg: True shards the arg's
    leading axis across the tenant mesh, False replicates it (query
    grids, traced scalars). The default out_spec shards the leading
    axis of every output.
    """
    in_specs = tuple(P(TENANT_AXIS) if t else P() for t in in_tenant)
    if out_spec is None:
        out_spec = P(TENANT_AXIS)
    return _shard_map(fn, mesh, in_specs, out_spec)


__all__ = [
    "CpShardingConfig", "pad_rows", "shard_knn_state",
    "make_knn_pvalues_fn", "make_kde_pvalues_fn",
    "TENANT_AXIS", "tenant_mesh", "tenant_spec", "put_tenant_sharded",
    "init_tenant_sharded", "place_chunk_args", "pad_tenant_count",
    "shard_tenant_chunk", "shard_tenant_fn",
]
