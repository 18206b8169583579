"""Tenant-axis sharding tests: spec/mesh/padding helpers (single
device) + subprocess bit-exactness properties under 8 virtual devices.

The subprocess tests are the tentpole's correctness contract: a
shard_map'd engine tick must be bit-identical leaf-for-leaf to the
single-device vmap across ragged active masks, for both engines, with
instrumentation on — and an uneven tenant count padded up to the shard
multiple must leave the live lanes' results untouched.
"""
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import distributed as dist


def test_tenant_spec_prefix_broadcast():
    assert dist.tenant_spec(np.zeros((4,))) == P("tenants")
    assert dist.tenant_spec(np.zeros((4, 3))) == P("tenants", None)
    assert dist.tenant_spec(np.zeros((4, 3, 2))) == \
        P("tenants", None, None)


def test_pad_tenant_count():
    assert dist.pad_tenant_count(8, 4) == 8
    assert dist.pad_tenant_count(9, 4) == 12
    assert dist.pad_tenant_count(1, 8) == 8
    assert dist.pad_tenant_count(0, 4) == 0
    with pytest.raises(ValueError, match="shards"):
        dist.pad_tenant_count(8, 0)


def test_tenant_mesh_validation():
    with pytest.raises(ValueError, match="shards must be >= 1"):
        dist.tenant_mesh(0)
    too_many = jax.device_count() + 1
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        dist.tenant_mesh(too_many)
    mesh = dist.tenant_mesh(1)
    assert mesh.axis_names == (dist.TENANT_AXIS,)
    assert mesh.shape[dist.TENANT_AXIS] == 1


def test_put_tenant_sharded_places_leading_axis():
    mesh = dist.tenant_mesh(1)
    tree = {"a": np.arange(8, dtype=np.float32),
            "b": np.zeros((8, 3), np.float32)}
    out = dist.put_tenant_sharded(tree, mesh)
    assert out["a"].sharding.spec == dist.tenant_spec(tree["a"])
    assert out["b"].sharding.spec == dist.tenant_spec(tree["b"])
    np.testing.assert_array_equal(np.asarray(out["b"]), tree["b"])


# --------------------------------------------------------------------------
# subprocess properties (8 virtual devices; child process so the main
# test process keeps its single real device)
# --------------------------------------------------------------------------

_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np, jax, jax.numpy as jnp
    assert jax.device_count() == 8, jax.device_count()

    def leaves_equal(a, b):
        la = jax.tree_util.tree_leaves(a)
        lb = jax.tree_util.tree_leaves(b)
        assert len(la) == len(lb)
        return all(
            np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
            for x, y in zip(la, lb))

    S, T, D, CAP, K, W = 12, 20, 4, 32, 3, 8
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(T, S, D)), jnp.float32)
    ys_cls = jnp.asarray(rng.integers(0, 3, size=(T, S)), jnp.int32)
    ys_reg = jnp.asarray(rng.normal(size=(T, S)), jnp.float32)
    taus = jnp.asarray(rng.uniform(size=(T, S)), jnp.float32)
    act = jnp.asarray(rng.uniform(size=(T, S)) < 0.7)
""")

_CLS_SCRIPT = _PRELUDE + textwrap.dedent("""
    from repro.serving.engine import ServingEngine
    from repro.telemetry import MetricsRegistry
    ref = None
    for shards in (1, 2, 4):
        eng = ServingEngine(n_sessions=S, capacity=CAP, dim=D, n_labels=3,
                            k=K, window=W, instrument=True,
                            metrics=MetricsRegistry(), shards=shards)
        st = eng.init_state()
        st, p = eng.observe_many(st, xs, ys_cls, taus, active=act)
        pv = eng.predict(st, xs[0])
        stats = eng.telemetry.ticks.drain()
        if ref is None:
            ref = (st, p, pv, stats)
        else:
            assert leaves_equal(st, ref[0]), f"state mismatch @{shards}"
            assert np.array_equal(np.asarray(p), np.asarray(ref[1]),
                                  equal_nan=True), f"pvals @{shards}"
            assert np.array_equal(np.asarray(pv), np.asarray(ref[2]),
                                  equal_nan=True), f"predict @{shards}"
            assert stats == ref[3], (shards, stats, ref[3])
            assert len(eng.telemetry.ticks.shard_vals) == shards
    # grow mode: auto-grow retraces per shard, results still identical
    gref = None
    for shards in (1, 4):
        eng = ServingEngine(n_sessions=S, capacity=8, dim=D, n_labels=3,
                            k=K, window=None, shards=shards)
        st = eng.init_state()
        st, p = eng.observe_many(st, xs, ys_cls, taus)  # grows 8 -> 32
        if gref is None:
            gref = (st, p)
        else:
            assert leaves_equal(st, gref[0]), "grow state mismatch"
            assert np.array_equal(np.asarray(p), np.asarray(gref[1]),
                                  equal_nan=True)
            meta = eng.meta()
            assert meta["shards"] == 4
            assert ServingEngine.from_meta(meta).shards == 4
    print("CLS_SHARDED_OK")
""")

_REG_SCRIPT = _PRELUDE + textwrap.dedent("""
    from repro.regression.engine import RegressionServingEngine
    from repro.telemetry import MetricsRegistry
    ref = None
    for shards in (1, 2, 4):
        eng = RegressionServingEngine(n_sessions=S, capacity=CAP, dim=D,
                                      k=K, window=W, instrument=True,
                                      metrics=MetricsRegistry(),
                                      shards=shards)
        st = eng.init_state()
        st, p = eng.observe_many(st, xs, ys_reg, taus, active=act)
        iv = eng.intervals(st, xs[0], epsilon=0.1)
        pv = eng.pvalues(st, xs[0], jnp.linspace(-1, 1, 5))
        stats = eng.telemetry.ticks.drain()
        if ref is None:
            ref = (st, p, iv, pv, stats)
        else:
            assert leaves_equal(st, ref[0]), f"state mismatch @{shards}"
            assert np.array_equal(np.asarray(p), np.asarray(ref[1]),
                                  equal_nan=True), f"pvals @{shards}"
            assert np.array_equal(np.asarray(iv), np.asarray(ref[2]),
                                  equal_nan=True), f"intervals @{shards}"
            assert np.array_equal(np.asarray(pv), np.asarray(ref[3]),
                                  equal_nan=True), f"grid @{shards}"
            assert stats == ref[4], (shards, stats, ref[4])
    print("REG_SHARDED_OK")
""")

_PAD_SCRIPT = _PRELUDE + textwrap.dedent("""
    from repro.core import distributed as dist
    from repro.serving.engine import ServingEngine
    # 10 live tenants, 4 shards: pad to 12 lanes, last 2 never active
    LIVE, SHARDS = 10, 4
    PADDED = dist.pad_tenant_count(LIVE, SHARDS)
    assert PADDED == 12
    ref_eng = ServingEngine(n_sessions=LIVE, capacity=CAP, dim=D,
                            n_labels=3, k=K, window=W)
    rst = ref_eng.init_state()
    rst, rp = ref_eng.observe_many(rst, xs[:, :LIVE], ys_cls[:, :LIVE],
                                   taus[:, :LIVE], active=act[:, :LIVE])
    pad_act = jnp.concatenate(
        [act[:, :LIVE], jnp.zeros((T, PADDED - LIVE), bool)], axis=1)
    eng = ServingEngine(n_sessions=PADDED, capacity=CAP, dim=D,
                        n_labels=3, k=K, window=W, shards=SHARDS)
    st = eng.init_state()
    st, p = eng.observe_many(st, xs[:, :PADDED], ys_cls[:, :PADDED],
                             taus[:, :PADDED], active=pad_act)
    live = jax.tree_util.tree_map(lambda l: l[:LIVE], st)
    assert leaves_equal(live, rst), "live lanes diverged under padding"
    assert np.array_equal(np.asarray(p)[:, :LIVE], np.asarray(rp),
                          equal_nan=True)
    # padded lanes stayed at their init state
    init = jax.tree_util.tree_map(lambda l: l[LIVE:], eng.init_state())
    padded = jax.tree_util.tree_map(lambda l: l[LIVE:], st)
    assert leaves_equal(padded, init), "padding lanes mutated"
    print("PAD_SHARDED_OK")
""")


_MANY_SCRIPT = _PRELUDE + textwrap.dedent("""
    # the many-small-tenants shape: 16 tenants x window 256 on 4 shards,
    # past one whole window, so every ring has wrapped
    from repro.core.measures import knn as knn_m
    from repro.serving import session as sm
    from repro.serving.engine import ServingEngine
    from repro.telemetry import MetricsRegistry
    S, CAP, K, D, T = 16, 256, 3, 5, 300
    rng = np.random.default_rng(5)
    xs = jnp.asarray(rng.normal(size=(T, S, D)), jnp.float32)
    ys = jnp.asarray(rng.integers(0, 2, size=(T, S)), jnp.int32)
    taus = jnp.asarray(rng.uniform(size=(T, S)), jnp.float32)
    runs = {}
    for shards, instrument in ((1, True), (4, True), (4, False)):
        kw = dict(instrument=True, metrics=MetricsRegistry()) \
            if instrument else {}
        eng = ServingEngine(n_sessions=S, capacity=CAP, dim=D, n_labels=2,
                            k=K, window=CAP, shards=shards, **kw)
        st = eng.init_state()
        if shards > 1:
            assert all(len(a.sharding.device_set) == shards
                       for a in jax.tree_util.tree_leaves(st))
        # a profile names the tick program alike on any shard count
        assert eng.lower_tick(2).as_text().startswith("module @jit_chunk")
        ps = []
        for t0 in range(0, T, 20):
            st, p = eng.observe_many(st, xs[t0:t0 + 20], ys[t0:t0 + 20],
                                     taus[t0:t0 + 20])
            ps.append(np.asarray(p))
        runs[shards, instrument] = (st, np.concatenate(ps))
        if instrument:
            got = eng.telemetry.drain()
            leaf = sum(a.size * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(st))
            assert got["state_bytes"] == leaf // shards, (got, leaf)
            assert got["chunk_temp_bytes"] > 0
    ref_st, ref_p = runs[1, True]
    assert int(jnp.min(ref_st.head)) > 0  # every ring wrapped
    for key, (st, p) in runs.items():
        assert leaves_equal(st, ref_st), f"state mismatch @{key}"
        assert np.array_equal(p, ref_p, equal_nan=True), f"pvals @{key}"
    # the fit oracle: a fresh engine fed only the surviving window holds
    # the same k-NN lists and distances, bit for bit, once both rings are
    # laid out in arrival order; and the lists equal a batch refit
    fresh = ServingEngine(n_sessions=S, capacity=CAP, dim=D, n_labels=2,
                          k=K, window=CAP)
    fs, _ = fresh.observe_many(fresh.init_state(), xs[T - CAP:],
                               ys[T - CAP:], taus[T - CAP:])
    a, b = jax.vmap(sm.to_linear)(ref_st), jax.vmap(sm.to_linear)(fs)
    assert np.array_equal(np.asarray(a.knn.best), np.asarray(b.knn.best))
    assert np.array_equal(np.asarray(a.D), np.asarray(b.D))
    for s in (0, 7, 15):
        fit = knn_m.fit(xs[T - CAP:, s], ys[T - CAP:, s], k=K)
        np.testing.assert_allclose(np.asarray(a.knn.best[s]),
                                   np.asarray(fit.best_same), atol=1e-5)
    print("MANY_SHARDED_OK")
""")

_PLACE_SCRIPT = _PRELUDE + textwrap.dedent("""
    # the ``place`` span: a sharded engine's dispatch lays its arguments
    # out on the tenant mesh inside it; one shard has no such span
    import tempfile
    from pathlib import Path
    from jax.profiler import ProfileData
    from repro.serving.engine import ServingEngine

    def spans(eng):
        st = eng.init_state()
        st, _ = eng.observe_many(st, xs, ys_cls, taus)  # compile first
        d = tempfile.mkdtemp()
        jax.profiler.start_trace(d)
        st, p = eng.observe_many(st, xs, ys_cls, taus)
        jax.block_until_ready(p)
        jax.profiler.stop_trace()
        path = sorted(Path(d).rglob("*.xplane.pb"))[-1]
        return sorted({e.name for pl in ProfileData.from_file(
            str(path)).planes for ln in pl.lines for e in ln.events
            if e.name.startswith("repro.")})

    for shards in (1, 4):
        eng = ServingEngine(n_sessions=S, capacity=CAP, dim=D, n_labels=3,
                            k=K, window=W, shards=shards)
        got = spans(eng)
        want = ["repro.launch", "repro.observe_many", "repro.prepare"]
        if shards > 1:
            want = sorted(want + ["repro.place"])
        assert got == want, (shards, got)
    print("PLACE_SPAN_OK")
""")


def test_many_small_tenants_sharded_bit_identical_and_exact():
    """16 tenants x window 256 over 4 shards after a wrapped window:
    bit-identical to one shard, instrumented or not, and to the fit
    oracle; ``state_bytes`` is one shard's share."""
    _run_child(_MANY_SCRIPT, "MANY_SHARDED_OK")


def test_sharded_dispatch_writes_place_span():
    _run_child(_PLACE_SCRIPT, "PLACE_SPAN_OK")


def _run_child(script: str, sentinel: str) -> None:
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=600)
    assert sentinel in r.stdout, r.stdout + r.stderr


def test_sharded_classification_bit_identical():
    _run_child(_CLS_SCRIPT, "CLS_SHARDED_OK")


def test_sharded_regression_bit_identical():
    _run_child(_REG_SCRIPT, "REG_SHARDED_OK")


def test_uneven_tenant_count_pads_cleanly():
    _run_child(_PAD_SCRIPT, "PAD_SHARDED_OK")


# --------------------------------------------------------------------------
# collective-freedom via the auditor: repro.analysis.audit owns the
# single definition of the zero-collective invariant; this child runs
# it against sharded ticks AND proves a smuggled psum is caught.
# --------------------------------------------------------------------------

_AUDIT_SCRIPT = _PRELUDE + textwrap.dedent("""
    from repro.analysis import audit as audit_m
    from repro.analysis import hlo as hlo_m

    # every sharded engine tick in the matrix must be collective-free
    for t in audit_m.engine_matrix(max_shards=8):
        if t.shards == 1:
            continue
        art = audit_m.Artifact(t)
        r = audit_m.CHECKERS["collective-freedom"](t, art)
        assert r["status"] == "pass", (t.name, r["violations"])
        assert sum(r["info"]["collective_bytes"].values()) == 0, t.name

    # sabotage: a psum smuggled into a shard_map'd tick is caught with
    # the offending HLO op named
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(jax.devices(), ("tenants",))
    bad = jax.jit(jax.shard_map(
        lambda x: x + jax.lax.psum(x, "tenants"), mesh=mesh,
        in_specs=P("tenants"), out_specs=P("tenants")))
    text = bad.lower(jnp.ones((8, 4), jnp.float32)).compile().as_text()
    vs = audit_m.collective_violations(text)
    assert vs and "all-reduce" in vs[0]["kind"], vs
    assert "all-reduce" in vs[0]["line"], vs
    print("AUDIT_SHARDED_OK")
""")


def test_audit_collective_freedom_sharded():
    _run_child(_AUDIT_SCRIPT, "AUDIT_SHARDED_OK")
