"""Fleet (capacity-bucketed engine pools) and async sharded saver tests.

The fleet's exactness contract: a tenant served through the fleet —
including bucket migrations, lane reuse after retirement, and sharded
pools — produces the SAME p-value stream and read-path results as a
dedicated single-lane engine fed the same observations, because
repadding to a larger capacity only appends inert fill (capacity
padding is p-value-invariant, the same property the engines' ``grow``
relies on).
"""
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import AsyncShardedSaver, Fleet, ServingEngine
from repro.serving.fleet import pow2_buckets
from repro.serving.snapshot import SessionStore
from repro.telemetry import MetricsRegistry
from repro.telemetry.costmodel import CostModel

D, K = 3, 3


def test_pow2_buckets():
    assert pow2_buckets(8, 64) == [8, 16, 32, 64]
    assert pow2_buckets(8, 8) == [8]
    assert pow2_buckets(8, 100) == [8, 16, 32, 64, 100]


def _streams(rng, tids, T, mode):
    out = {}
    for t in tids:
        x = rng.normal(size=(T, D)).astype(np.float32)
        if mode == "classification":
            y = rng.integers(0, 3, size=T).astype(np.int32)
        else:
            y = rng.normal(size=T).astype(np.float32)
        out[t] = (x, y, rng.uniform(size=T).astype(np.float32))
    return out


def _ref_engine(mode):
    if mode == "classification":
        return ServingEngine(n_sessions=1, capacity=8, dim=D, k=K,
                             n_labels=3, window=None)
    from repro.regression.engine import RegressionServingEngine
    return RegressionServingEngine(n_sessions=1, capacity=8, dim=D, k=K,
                                   window=None)


@pytest.mark.parametrize("mode", ["classification", "regression"])
def test_fleet_matches_dedicated_engines(mode):
    """Fleet p-values == dedicated 1-lane engines across migrations
    and ragged per-tenant activity; reads match too."""
    rng = np.random.default_rng(1)
    tids = [f"t{i}" for i in range(4)]
    T = 28  # crosses cap_min=8 twice for the always-active tenant
    metrics = MetricsRegistry()
    fleet = Fleet(dim=D, k=K, n_labels=3, mode=mode, cap_min=8,
                  cap_max=64, pool_sessions=4, metrics=metrics)
    for t in tids:
        fleet.admit(t)
    refs = {t: _ref_engine(mode) for t in tids}
    ref_state = {t: refs[t].init_state() for t in tids}
    streams = _streams(rng, tids, T, mode)

    for step in range(T):
        items = {}
        for i, t in enumerate(tids):
            if step % (i + 1) == 0:  # tenant i active every i+1 steps
                x, y, tau = streams[t]
                n = fleet.occupancy(t)
                items[t] = (x[n], y[n], tau[n])
        ps = fleet.observe(items)
        for t, (xx, yy, tt) in items.items():
            ref_state[t], pref = refs[t].observe(
                ref_state[t], jnp.asarray(xx)[None], jnp.asarray([yy]),
                jnp.asarray([tt]))
            np.testing.assert_array_equal(
                np.asarray(ps[t]), np.asarray(pref[0]), err_msg=t)

    Xq = jnp.asarray(rng.normal(size=(2, D)).astype(np.float32))
    for t in tids:
        if mode == "classification":
            a = fleet.predict(t, Xq)
            b = refs[t].predict(ref_state[t], Xq)[0]
        else:
            a = fleet.intervals(t, Xq, 0.1)
            b = refs[t].intervals(ref_state[t], Xq, 0.1)[0]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=t)

    # the always-active tenant crossed 8 -> 16 -> 32: migrations fired
    assert metrics.counter("fleet_migrations_total", mode=mode).value >= 2
    assert fleet.occupancy(tids[0]) == T


def test_fleet_retire_readmit_reuses_lane_fresh():
    rng = np.random.default_rng(2)
    fleet = Fleet(dim=D, k=K, n_labels=3, cap_min=8, cap_max=32,
                  pool_sessions=2)  # one pool, 2 lanes: reuse is forced
    fleet.admit("a")
    fleet.admit("b")
    (x, y, tau), = _streams(rng, ["a"], 6, "classification").values()
    for i in range(6):
        fleet.observe({"a": (x[i], y[i], tau[i])})
    fleet.retire("a")
    with pytest.raises(KeyError):
        fleet.occupancy("a")
    fleet.admit("c")  # lands on a's recycled lane
    assert fleet.occupancy("c") == 0
    ref = _ref_engine("classification")
    rst, rp = ref.observe(ref.init_state(), jnp.asarray(x[0])[None],
                          jnp.asarray(y[:1]), jnp.asarray(tau[:1]))
    p = fleet.observe({"c": (x[0], y[0], tau[0])})
    np.testing.assert_array_equal(np.asarray(p["c"]), np.asarray(rp[0]))


def test_fleet_admit_twice_raises():
    fleet = Fleet(dim=D, k=K, cap_min=8, cap_max=16)
    fleet.admit("a")
    with pytest.raises(KeyError):
        fleet.admit("a")


def test_fleet_buckets_from_cost_model():
    """suggest_buckets drives the pool boundaries; pow2 is the
    no-model fallback and the linear-cost special case."""
    lin = CostModel({("classification", "observe_many", c):
                     {"a": 1e-4, "b": 1e-6 * c, "n": 8.0}
                     for c in (64, 256, 1024)})
    quad = CostModel({("classification", "observe_many", c):
                      {"a": 1e-4, "b": 1e-9 * c * c, "n": 8.0}
                      for c in (64, 256, 1024)})
    f_lin = Fleet(dim=D, k=K, cap_min=8, cap_max=64, cost_model=lin)
    assert f_lin.buckets == lin.suggest_buckets(cap_min=8, cap_max=64)
    assert f_lin.buckets == pow2_buckets(8, 64)  # alpha=1 => pow2
    f_quad = Fleet(dim=D, k=K, cap_min=8, cap_max=64, cost_model=quad)
    assert f_quad.buckets == quad.suggest_buckets(cap_min=8, cap_max=64)
    # quadratic cost => denser (sqrt2-spaced) boundaries than pow2
    assert len(f_quad.buckets) > len(f_lin.buckets)
    f_none = Fleet(dim=D, k=K, cap_min=8, cap_max=64)
    assert f_none.buckets == pow2_buckets(8, 64)


def test_async_sharded_saver_matches_blocking_save(tmp_path):
    rng = np.random.default_rng(3)
    eng = ServingEngine(n_sessions=8, capacity=16, dim=D, k=K,
                        n_labels=3, window=8)
    xs = jnp.asarray(rng.normal(size=(6, 8, D)), jnp.float32)
    ys = jnp.asarray(rng.integers(0, 3, size=(6, 8)), jnp.int32)
    ts = jnp.asarray(rng.uniform(size=(6, 8)), jnp.float32)
    state, _ = eng.observe_many(eng.init_state(), xs, ys, ts)

    sync_store = SessionStore(str(tmp_path / "sync"))
    sync_store.save(6, state, meta=eng.meta(), blocking=True)
    async_store = SessionStore(str(tmp_path / "async"))
    saver = AsyncShardedSaver(async_store, shards=4)
    saver.save(6, state, meta=eng.meta())
    saver.close()

    eng_a, st_a, step_a = sync_store.restore_engine()
    eng_b, st_b, step_b = async_store.restore_engine()
    assert step_a == step_b == 6
    assert eng_a.meta() == eng_b.meta()
    import jax
    for la, lb in zip(jax.tree_util.tree_leaves(st_a),
                      jax.tree_util.tree_leaves(st_b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # the saver's copies were real: donating-style mutation of the
    # original state after save() must not corrupt what was written
    assert async_store.latest_step() == 6


def test_async_saver_surfaces_worker_errors(tmp_path):
    class Boom(SessionStore):
        def save(self, *a, **kw):
            raise RuntimeError("disk on fire")

    eng = ServingEngine(n_sessions=4, capacity=8, dim=D, k=K,
                        n_labels=2, window=None)
    saver = AsyncShardedSaver(Boom(str(tmp_path)), shards=2)
    saver.save(1, eng.init_state(), meta=eng.meta())
    with pytest.raises(RuntimeError, match="async snapshot save failed"):
        saver.close()


def test_async_saver_one_shard_survives_donated_state(tmp_path):
    """With one shard the slice spans the whole state; a donating tick
    right after ``save`` deletes the caller's buffers before the worker
    pulls them, and the snapshot must still hold the saved values."""
    import threading

    import jax

    release = threading.Event()

    class Held(SessionStore):  # the worker sits in step 0's commit
        def save(self, step, *a, **kw):
            if step == 0:
                release.wait(timeout=30)
            return super().save(step, *a, **kw)

    eng = ServingEngine(n_sessions=4, capacity=8, dim=D, k=K,
                        n_labels=2, window=None)
    store = Held(str(tmp_path))
    saver = AsyncShardedSaver(store, shards=1)
    saver.save(0, eng.init_state(), meta=eng.meta())
    rng = np.random.default_rng(5)
    state, _ = eng.observe_many(
        eng.init_state(),
        jnp.asarray(rng.normal(size=(3, 4, D)), jnp.float32),
        jnp.asarray(rng.integers(0, 2, size=(3, 4)), jnp.int32),
        jnp.asarray(rng.uniform(size=(3, 4)), jnp.float32))
    want = [np.asarray(a) for a in jax.tree_util.tree_leaves(state)]
    saver.save(1, state, meta=eng.meta())
    for leaf in jax.tree_util.tree_leaves(state):
        leaf.delete()  # what donating it to the next tick does
    release.set()
    saver.close()
    _, got, step = store.restore_engine()
    assert step == 1
    for a, b in zip(want, jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(a, np.asarray(b))


_SHARDED_FLEET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    from repro.serving import Fleet
    rng = np.random.default_rng(7)
    x = rng.normal(size=(20, 3, 3)).astype(np.float32)
    y = rng.integers(0, 3, size=(20, 3)).astype(np.int32)
    tau = rng.uniform(size=(20, 3)).astype(np.float32)
    ref = None
    for shards in (1, 4):
        fleet = Fleet(dim=3, k=3, n_labels=3, cap_min=8, cap_max=32,
                      pool_sessions=8, shards=shards)
        for t in ("a", "b", "c"):
            fleet.admit(t)
        ps_all = []
        for step in range(20):
            ps = fleet.observe({t: (x[step, i], y[step, i], tau[step, i])
                                for i, t in enumerate(("a", "b", "c"))})
            ps_all.append([float(np.asarray(ps[t]))
                           for t in ("a", "b", "c")])
        if ref is None:
            ref = ps_all
        else:
            assert ps_all == ref, "sharded fleet diverged"
    print("FLEET_SHARDED_OK")
""")


def test_sharded_fleet_matches_unsharded():
    r = subprocess.run([sys.executable, "-c", _SHARDED_FLEET],
                       capture_output=True, text=True, timeout=600)
    assert "FLEET_SHARDED_OK" in r.stdout, r.stdout + r.stderr


def test_merge_bench_rows_ownership(tmp_path):
    """bench_kind-prefix row ownership: each bench module replaces only
    its own row family; "" owns exactly the un-kinded rows."""
    import importlib.util
    import json
    import os

    spec = importlib.util.spec_from_file_location(
        "bench_common", os.path.join(os.path.dirname(__file__), os.pardir,
                                     "benchmarks", "common.py"))
    common = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(common)
    out = str(tmp_path / "bench.json")

    common.merge_bench_rows(out, [{"sessions": 8}], owned_prefixes=("",))
    common.merge_bench_rows(
        out, [{"bench_kind": "replay", "workload": "steady"},
              {"bench_kind": "replay_autotune"}],
        owned_prefixes=("replay",))
    common.merge_bench_rows(
        out, [{"bench_kind": "fleet_scaling", "tenants": 64}],
        owned_prefixes=("fleet",))
    rows = json.load(open(out))["results"]
    assert len(rows) == 4

    # fleet rewrite replaces fleet* rows, keeps replay* and un-kinded
    common.merge_bench_rows(
        out, [{"bench_kind": "fleet_scaling", "tenants": 128},
              {"bench_kind": "fleet_lifecycle"}],
        owned_prefixes=("fleet",))
    rows = json.load(open(out))["results"]
    kinds = sorted(str(r.get("bench_kind", "")) for r in rows)
    assert kinds == ["", "fleet_lifecycle", "fleet_scaling", "replay",
                     "replay_autotune"]
    fleet = [r for r in rows if r.get("bench_kind") == "fleet_scaling"]
    assert fleet == [{"bench_kind": "fleet_scaling", "tenants": 128}]

    # "" owns only un-kinded rows: serve_bench-style rewrite keeps both
    # other families
    common.merge_bench_rows(
        out, [{"sessions": 32}, {"bench_kind": "sliding_full_window"}],
        owned_prefixes=("", "sliding_full_window"))
    rows = json.load(open(out))["results"]
    assert {str(r.get("bench_kind", "")) for r in rows} == {
        "", "sliding_full_window", "fleet_scaling", "fleet_lifecycle",
        "replay", "replay_autotune"}
    unkinded = [r for r in rows if "bench_kind" not in r]
    assert unkinded == [{"sessions": 32}]
