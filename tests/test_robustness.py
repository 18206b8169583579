"""Fault-tolerance tests: keyed fault plans, injector semantics, saver
retry / uncommit, restore fallback, guarded ticks + quarantine, fault-
stamped traces, and the chaos property test.

The chaos property is the acceptance contract of the robustness PR:
under ANY injected fault plan (I/O + traffic + timing + state poison),
the surviving tenants' p-values and final state are BIT-identical to a
fault-free run on the same surviving stream, every quarantine / retry /
rejection is counted in metrics, and the guard adds zero new engine
retraces.
"""
import os
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.regression.engine import RegressionServingEngine
from repro.robustness import (VALUE_FAULTS, Fault, FaultInjector, FaultPlan,
                              PermanentWriteError, TickGuard,
                              TransientWriteError, backoff_schedule,
                              corrupt_traffic, flip_byte, poison_state)
from repro.serving import AsyncShardedSaver, ServingEngine, SessionStore
from repro.telemetry import MetricsRegistry
from repro.telemetry.loadgen import generate
from repro.telemetry.replay import replay
from repro.telemetry.tracer import validate_record, validate_trace_file, \
    write_trace

S, CAP, DIM, K, WIN = 6, 32, 4, 3, 16


def _mk(mode):
    if mode == "classification":
        return ServingEngine(n_sessions=S, capacity=CAP, dim=DIM, k=K,
                             n_labels=2, window=WIN)
    return RegressionServingEngine(n_sessions=S, capacity=CAP, dim=DIM,
                                   k=K, window=WIN)


def _traffic(mode, T, seed=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(T, S, DIM)).astype(np.float32)
    if mode == "classification":
        y = rng.integers(0, 2, size=(T, S)).astype(np.int64)
    else:
        y = rng.normal(size=(T, S)).astype(np.float32)
    taus = rng.uniform(size=(T, S)).astype(np.float32)
    return X, y, taus


def _leaves_equal(a, b, rows=None):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if rows is not None:
            x, y = x[rows], y[rows]
        if not np.array_equal(x, y, equal_nan=True):
            return False
    return True


def _metric_sum(metrics, name):
    return sum(m["value"] for m in metrics.to_dict()["metrics"]
               if m["name"] == name)


# --------------------------------------------------------------------------
# fault plans: keyed determinism
# --------------------------------------------------------------------------

def test_fault_plan_keyed_and_deterministic():
    a = FaultPlan.random(9, steps=64, tenants=4, rate=0.2)
    b = FaultPlan.random(9, steps=64, tenants=4, rate=0.2)
    assert a.faults() == b.faults()
    assert len(a) > 0
    # per-cell keying: the decision at step s does not depend on how
    # many steps the plan covers
    wide = FaultPlan.random(9, steps=256, tenants=4, rate=0.2)
    assert [f for f in wide.faults() if f.step < 64] == a.faults()
    # a different seed draws a different schedule
    c = FaultPlan.random(10, steps=64, tenants=4, rate=0.2)
    assert a.faults() != c.faults()


def test_fault_kind_validated():
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault("traffic", 0, "meteor_strike")


def test_plan_lookup_is_positional():
    plan = FaultPlan(0, (Fault("traffic", 3, "nan_feature", tenant=1),))
    assert plan.at("traffic", 3)[0].kind == "nan_feature"
    assert plan.at("traffic", 4) == ()
    assert plan.at("store.write", 3) == ()


# --------------------------------------------------------------------------
# injector: transient vs permanent, attempt counting
# --------------------------------------------------------------------------

def test_injector_transient_clears_after_times():
    metrics = MetricsRegistry()
    plan = FaultPlan(1, (Fault("store.write", 5, "write_fail", times=2),))
    inj = FaultInjector(plan, metrics=metrics)
    for _ in range(2):
        with pytest.raises(TransientWriteError):
            inj.enter("store.write", 5)
    inj.enter("store.write", 5)  # third attempt succeeds
    inj.enter("store.write", 6)  # other steps unaffected
    assert _metric_sum(metrics, "faults_injected_total") == 2


def test_injector_permanent_never_clears():
    plan = FaultPlan(1, (Fault("store.write", 2, "write_fail", times=-1),))
    inj = FaultInjector(plan)
    for _ in range(4):
        with pytest.raises(PermanentWriteError):
            inj.enter("store.write", 2)


def test_backoff_schedule_keyed_and_increasing():
    a = backoff_schedule(3, 7, 4, 0.05)
    assert a == backoff_schedule(3, 7, 4, 0.05)
    assert a != backoff_schedule(3, 8, 4, 0.05)
    assert all(y > x for x, y in zip(a, a[1:]))
    assert all(0.05 * 2 ** i <= d <= 0.05 * 2 ** i * 1.25
               for i, d in enumerate(a))


def test_flip_byte_is_an_involution(tmp_path):
    p = tmp_path / "blob.bin"
    p.write_bytes(bytes(range(64)))
    off = flip_byte(str(p), seed=4)
    assert p.read_bytes() != bytes(range(64))
    flip_byte(str(p), offset=off)
    assert p.read_bytes() == bytes(range(64))


def test_corrupt_traffic_reports_oracle_mask():
    X, y, taus = _traffic("classification", 16)
    plan = FaultPlan(2, (Fault("traffic", 3, "nan_feature", tenant=2),
                         Fault("traffic", 5, "label_out_of_range",
                               tenant=1),
                         Fault("traffic", 9, "tau_out_of_range",
                               tenant=0)))
    hits = corrupt_traffic(plan, X, y, taus, mode="classification",
                           n_labels=2, time_axis=0)
    assert hits == {(3, 2), (5, 1), (9, 0)}
    assert np.isnan(X[3, 2, 0])
    assert y[5, 1] >= 2
    assert taus[9, 0] > 1.0
    # launcher layout: tenant-major with time_axis=1
    Xl = np.transpose(X, (1, 0, 2)).copy()
    yl, tl = y.T.copy(), taus.T.copy()
    hits_l = corrupt_traffic(plan, Xl, yl, tl, mode="classification",
                             n_labels=2, time_axis=1)
    assert hits_l == hits
    assert np.isnan(Xl[2, 3, 0])


# --------------------------------------------------------------------------
# store: restore fallback on corruption (satellite a)
# --------------------------------------------------------------------------

def test_restore_falls_back_to_previous_committed_step(tmp_path):
    metrics = MetricsRegistry()
    eng = _mk("classification")
    state1 = eng.init_state()
    X, y, taus = _traffic("classification", 8)
    state1, _ = eng.observe_many(eng.init_state(), jnp.asarray(X),
                                 jnp.asarray(y), jnp.asarray(taus))
    store = SessionStore(str(tmp_path), metrics=metrics)
    store.save(1, state1, meta=eng.meta(), blocking=True)
    state1 = jax.device_get(state1)  # observe_many donates its input
    state2, _ = eng.observe_many(
        jax.tree_util.tree_map(jnp.asarray, state1), jnp.asarray(X),
        jnp.asarray(y), jnp.asarray(taus))
    store.save(2, state2, meta=eng.meta(), blocking=True)
    step_dir = os.path.join(str(tmp_path), f"step_{2:09d}")
    shard = next(os.path.join(step_dir, f)
                 for f in sorted(os.listdir(step_dir))
                 if f.endswith(".npz"))
    flip_byte(shard, seed=0)

    got, got_step, _meta = store.restore()
    assert got_step == 1
    assert _leaves_equal(got, state1)
    assert _metric_sum(metrics, "restore_fallback_total") >= 1
    # an explicitly requested corrupt step still raises — fallback is
    # only for "give me the latest good one"
    with pytest.raises(Exception):
        store.restore(step=2)


# --------------------------------------------------------------------------
# async saver: retry on transient faults, uncommit on exhaustion
# (satellite b)
# --------------------------------------------------------------------------

def test_saver_retries_transient_write_faults(tmp_path):
    metrics = MetricsRegistry()
    eng = _mk("classification")
    state = eng.init_state()
    plan = FaultPlan(4, (Fault("store.write", 7, "write_fail", times=2),))
    store = SessionStore(str(tmp_path), metrics=metrics,
                         injector=FaultInjector(plan, metrics=metrics))
    saver = AsyncShardedSaver(store, 2, metrics=metrics, retries=3,
                              retry_base_s=0.01, seed=4)
    saver.save(7, state, meta=eng.meta())
    saver.close()
    assert store.latest_step() == 7
    assert _metric_sum(metrics, "snapshot_retries_total") == 2
    got, got_step, _ = store.restore()
    assert got_step == 7 and _leaves_equal(got, state)


def test_saver_uncommits_failed_step(tmp_path):
    metrics = MetricsRegistry()
    eng = _mk("classification")
    state = eng.init_state()
    store = SessionStore(str(tmp_path), metrics=metrics)
    store.save(1, state, meta=eng.meta(), blocking=True)
    plan = FaultPlan(4, (Fault("store.write", 2, "write_fail", times=9),))
    store2 = SessionStore(str(tmp_path), metrics=metrics,
                          injector=FaultInjector(plan))
    saver = AsyncShardedSaver(store2, 1, metrics=metrics, retries=2,
                              retry_base_s=0.01, seed=4)
    saver.save(2, state, meta=eng.meta())
    with pytest.raises(RuntimeError, match="async snapshot save failed"):
        saver.close()
    # the failed step was discarded: latest never points at the
    # half-written snapshot, and restore serves the previous commit
    assert store2.latest_step() == 1
    assert _metric_sum(metrics, "snapshot_failed_steps_total") == 1
    _got, got_step, _ = store2.restore()
    assert got_step == 1


# --------------------------------------------------------------------------
# guard: bit-neutral when clean, admission == oracle mask, quarantine
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["classification", "regression"])
def test_guard_bit_identical_on_clean_traffic(mode):
    X, y, taus = _traffic(mode, 48)
    plain, guarded = _mk(mode), TickGuard(_mk(mode), check_every=1)
    sp, sg = plain.init_state(), guarded.init_state()
    for c in range(3):
        sl = slice(c * 16, (c + 1) * 16)
        args = (jnp.asarray(X[sl]), jnp.asarray(y[sl]),
                jnp.asarray(taus[sl]))
        sp, pp = plain.observe_many(sp, *args)
        sg, pg = guarded.observe_many(sg, *args)
        assert np.array_equal(np.asarray(pp), np.asarray(pg),
                              equal_nan=True)
    sg = guarded.finalize(sg)
    assert _leaves_equal(sp, sg)
    rep = guarded.drain()
    assert sum(rep["rejected"].values()) == 0
    assert rep["quarantines"] == 0 and rep["quarantined_lanes"] == []
    # the guarded path dispatches the same compiled engine step: one
    # cache entry each, zero new retraces
    assert len(guarded.engine._chunks) == 1
    assert len(plain._chunks) == 1


@pytest.mark.parametrize("mode", ["classification", "regression"])
def test_guard_admission_matches_oracle_mask(mode):
    T = 32
    X, y, taus = _traffic(mode, T)
    Xc, yc, tc = X.copy(), y.copy(), taus.copy()
    plan = FaultPlan.random(17, steps=T, tenants=S, rate=0.15,
                            kinds=VALUE_FAULTS)
    hits = corrupt_traffic(plan, X, y, taus, mode=mode, n_labels=2,
                           time_axis=0)
    assert hits, "seed 17 must draw at least one traffic fault"
    mask = np.ones((T, S), dtype=bool)
    for t, lane in hits:
        mask[t, lane] = False

    metrics = MetricsRegistry()
    guarded = TickGuard(_mk(mode), metrics=metrics)
    sg, pg = guarded.observe_many(guarded.init_state(), jnp.asarray(X),
                                  jnp.asarray(y), jnp.asarray(taus))
    sg = guarded.finalize(sg)
    oracle = _mk(mode)
    so, po = oracle.observe_many(oracle.init_state(), jnp.asarray(Xc),
                                 jnp.asarray(yc), jnp.asarray(tc),
                                 active=jnp.asarray(mask))
    # every faulted lane-tick was rejected (NaN p) and the surviving
    # stream is bit-identical to the fault-free masked run
    for t, lane in hits:
        assert np.isnan(np.asarray(pg)[t, lane])
    assert np.array_equal(np.asarray(pg), np.asarray(po), equal_nan=True)
    assert _leaves_equal(sg, so)
    rep = guarded.drain()
    assert sum(rep["rejected"].values()) == len(hits)
    assert _metric_sum(metrics, "guard_rejected_inputs_total") == len(hits)


def test_guard_freezes_poisoned_lane_without_store():
    mode, lane = "classification", 2
    X, y, taus = _traffic(mode, 32)
    guard = TickGuard(_mk(mode), check_every=1)
    state = guard.init_state()
    state, _ = guard.observe_many(state, jnp.asarray(X[:16]),
                                  jnp.asarray(y[:16]),
                                  jnp.asarray(taus[:16]))
    state = poison_state(state, lane)
    state = guard.finalize(state)
    rep_mid = dict(guard.drain())
    assert rep_mid["quarantines"] == 1 and rep_mid["restores"] == 0
    assert rep_mid["quarantined_lanes"] == [lane]
    # the frozen lane is masked out of every subsequent tick: NaN
    # p-values, state bitwise frozen
    before = jax.tree_util.tree_map(
        lambda L: np.asarray(L)[lane].copy(), state)
    state, p = guard.observe_many(state, jnp.asarray(X[16:]),
                                  jnp.asarray(y[16:]),
                                  jnp.asarray(taus[16:]))
    assert np.all(np.isnan(np.asarray(p)[:, lane]))
    after = jax.tree_util.tree_map(
        lambda L: np.asarray(L)[lane], state)
    assert _leaves_equal(before, after)


@pytest.mark.parametrize("mode", ["classification", "regression"])
def test_guard_restores_quarantined_lane_from_snapshot(tmp_path, mode):
    lane = 3
    X, y, taus = _traffic(mode, 32)
    metrics = MetricsRegistry()
    store = SessionStore(str(tmp_path), metrics=metrics)
    eng = _mk(mode)
    guard = TickGuard(eng, store=store, metrics=metrics, check_every=1)
    state = eng.init_state()
    store.save(0, state, meta=eng.meta(), blocking=True)
    snap_lane = jax.tree_util.tree_map(
        lambda L: np.asarray(L)[lane].copy(), state)
    state, _ = guard.observe_many(state, jnp.asarray(X[:16]),
                                  jnp.asarray(y[:16]),
                                  jnp.asarray(taus[:16]))
    state = poison_state(state, lane)
    state = guard.finalize(state)
    rep = guard.drain()
    assert rep["quarantines"] == 1 and rep["restores"] == 1
    assert rep["quarantined_lanes"] == []  # restored, back in service
    got_lane = jax.tree_util.tree_map(
        lambda L: np.asarray(L)[lane], state)
    assert _leaves_equal(snap_lane, got_lane)
    assert _metric_sum(metrics, "guard_restores_total") == 1
    # the restored lane serves again: finite p-values resume
    state, p = guard.observe_many(state, jnp.asarray(X[16:]),
                                  jnp.asarray(y[16:]),
                                  jnp.asarray(taus[16:]))
    assert np.isfinite(np.asarray(p)[:, lane]).any()


# --------------------------------------------------------------------------
# fault-stamped traces (tracer schema v3) + replay dedup / shed
# --------------------------------------------------------------------------

def test_loadgen_stamps_fault_schedule(tmp_path):
    plan = FaultPlan.random(
        13, steps=128, tenants=4, rate=0.2,
        kinds=VALUE_FAULTS + ("duplicate_arrival", "delay"), param=0.002)
    clean = generate("steady", ops=128, tenants=4, capacity=32, seed=1)
    recs = generate("steady", ops=128, tenants=4, capacity=32, seed=1,
                    faults=plan)
    stamped = [r for r in recs if "fault" in r or "delay_s" in r]
    assert stamped, "seed 13 must stamp at least one fault"
    assert any(r.get("fault", {}).get("kind") in VALUE_FAULTS
               for r in recs)
    dups = [r for r in recs
            if r.get("fault", {}).get("kind") == "duplicate_arrival"]
    for d in dups:
        assert d["fault"]["of_seq"] < d["seq"]
    # the base trace is unchanged by the plan: only the stamped fields
    # differ from the fault-free twin
    for a, b in zip(clean, recs):
        sa = {k: v for k, v in b.items() if k not in ("fault", "delay_s")}
        assert a == sa
    # round-trips through the schema validator
    path = str(tmp_path / "faulted.jsonl")
    write_trace(path, recs)
    assert len(validate_trace_file(path)) == 128


def test_trace_schema_v2_still_valid_and_bad_fault_rejected():
    v2 = {"schema": 2, "seq": 0, "t": 0.0, "op": "observe",
          "wall_s": 0.0, "workload": "steady", "seed": 1}
    validate_record(v2)
    bad = {"schema": 3, "seq": 0, "t": 0.0, "op": "observe",
           "wall_s": 0.0, "fault": {"kind": 42}}
    with pytest.raises(ValueError, match="fault"):
        validate_record(bad)
    bad2 = {"schema": 3, "seq": 0, "t": 0.0, "op": "observe",
            "wall_s": 0.0, "delay_s": "soon"}
    with pytest.raises(ValueError, match="delay_s"):
        validate_record(bad2)


def test_replay_drops_duplicate_arrivals():
    plan = FaultPlan(
        21, tuple(Fault("traffic", s, "duplicate_arrival", tenant=0)
                  for s in (20, 40, 60)))
    recs = generate("steady", ops=96, tenants=4, capacity=32, seed=3,
                    faults=plan)
    res = replay(recs, dim=DIM, k=K, capacity=CAP, window=WIN, seed=3)
    assert res.report["duplicates_dropped"] == 3
    # dedup removes the re-delivered events from the driven stream
    clean = [r for r in recs
             if r.get("fault", {}).get("kind") != "duplicate_arrival"]
    oracle = replay(clean, dim=DIM, k=K, capacity=CAP, window=WIN, seed=3)
    assert _leaves_equal(res.state, oracle.state)


def test_replay_shed_defers_but_never_drops_observes():
    recs = generate("steady", ops=128, tenants=4, capacity=32, seed=9)
    base = replay(recs, dim=DIM, k=K, capacity=CAP, window=WIN, seed=9)
    shed = replay(recs, dim=DIM, k=K, capacity=CAP, window=WIN, seed=9,
                  shed_depth=1, defer_flush=8)
    # reads are shed first; observes only defer, and the deferred
    # flush preserves order — the final state is bit-identical
    assert _leaves_equal(base.state, shed.state)
    assert shed.report["shed_depth"] == 1
    assert shed.report["session_steps"] == base.report["session_steps"]


# --------------------------------------------------------------------------
# lint rule: swallowed exceptions in durability layers (satellite e)
# --------------------------------------------------------------------------

def _lint_fixture(tmp_path, rel, src):
    from repro.analysis.lint import lint_paths
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return [v for v in lint_paths([str(p)])
            if v.rule == "swallowed-exception"]


def test_lint_flags_swallowed_exceptions_in_scope(tmp_path):
    vs = _lint_fixture(tmp_path, "repro/serving/bad.py", """
        def f():
            try:
                g()
            except:
                pass
            try:
                g()
            except OSError:
                continue_ = 1
            try:
                g()
            except ValueError:
                pass
    """)
    assert [v.line for v in vs] == [5, 13]


def test_lint_pragma_and_scope_escapes(tmp_path):
    ok = _lint_fixture(tmp_path, "repro/serving/ok.py", """
        def f():
            try:
                g()
            except ValueError:  # audit: allow
                pass
    """)
    assert ok == []
    out_of_scope = _lint_fixture(tmp_path, "repro/models/other.py", """
        def f():
            try:
                g()
            except:
                pass
    """)
    assert out_of_scope == []


def test_lint_clean_over_src_tree():
    from repro.analysis.lint import lint_tree
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    assert [v for v in lint_tree(root)
            if v.rule == "swallowed-exception"] == []


# --------------------------------------------------------------------------
# the chaos property test
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["classification", "regression"])
def test_chaos_surviving_tenants_bit_identical(tmp_path, mode):
    """Randomized keyed fault plan (traffic value faults + I/O write
    faults + a timing delay + an in-memory lane poison) over >= 200
    ticks: unaffected tenants must be bit-identical to a fault-free run
    on the same surviving stream; every rejection / quarantine /
    restore / retry is counted; zero new engine retraces."""
    SEED, T, CH = 23, 224, 8
    # chunk 7 starts at ring head 56 % WIN == 8, so the poisoned slot 0
    # survives the chunk and the deferred sweep's flags catch it before
    # the following chunk's ring pass overwrites the NaN
    POISON_LANE, POISON_CHUNK = 4, 7
    nchunks = T // CH
    assert T >= 200

    X, y, taus = _traffic(mode, T)
    Xc, yc, tc = X.copy(), y.copy(), taus.copy()
    plan = FaultPlan.random(SEED, steps=T, tenants=S, rate=0.06,
                            kinds=VALUE_FAULTS)
    hits = corrupt_traffic(plan, X, y, taus, mode=mode, n_labels=2,
                           time_axis=0)
    assert len(hits) >= 5, "seed 23 must draw a handful of value faults"
    mask = np.ones((T, S), dtype=bool)
    for t, lane in hits:
        mask[t, lane] = False

    metrics = MetricsRegistry()
    io_plan = FaultPlan(SEED, (
        Fault("store.write", 3, "write_fail", times=1),
        Fault("store.commit", 3, "delay", param=0.001),
    ))
    store = SessionStore(str(tmp_path), metrics=metrics,
                         injector=FaultInjector(io_plan, metrics=metrics))
    saver = AsyncShardedSaver(store, 1, metrics=metrics,
                              retry_base_s=0.01, seed=SEED)
    eng = _mk(mode)
    guard = TickGuard(eng, store=store, metrics=metrics, check_every=2)
    state = eng.init_state()
    saver.save(0, state, meta=eng.meta())
    saver.wait()

    pg = []
    for c in range(nchunks):
        if c == POISON_CHUNK:
            state = poison_state(state, POISON_LANE)
        sl = slice(c * CH, (c + 1) * CH)
        state, p = guard.observe_many(state, jnp.asarray(X[sl]),
                                      jnp.asarray(y[sl]),
                                      jnp.asarray(taus[sl]))
        pg.append(np.asarray(p))
        if c == 3:  # mid-run snapshot through the faulted write path
            saver.save(3, state, meta=eng.meta())
    state = guard.finalize(state)
    saver.close()
    rep = guard.drain()

    # fault-free oracle on the surviving stream: clean traffic, the
    # faulted lane-ticks simply never arrive
    oracle = _mk(mode)
    so = oracle.init_state()
    po = []
    for c in range(nchunks):
        sl = slice(c * CH, (c + 1) * CH)
        so, p = oracle.observe_many(so, jnp.asarray(Xc[sl]),
                                    jnp.asarray(yc[sl]),
                                    jnp.asarray(tc[sl]),
                                    active=jnp.asarray(mask[sl]))
        po.append(np.asarray(p))

    keep = np.array([s for s in range(S) if s != POISON_LANE])
    for c in range(nchunks):
        assert np.array_equal(pg[c][:, keep], po[c][:, keep],
                              equal_nan=True), f"chunk {c} diverged"
    for c in range(POISON_CHUNK):  # pre-poison the lane matches too
        assert np.array_equal(pg[c][:, POISON_LANE],
                              po[c][:, POISON_LANE], equal_nan=True)
    assert _leaves_equal(state, so, rows=keep)
    for t, lane in hits:  # every surviving faulted tick was rejected
        if lane != POISON_LANE:
            assert np.isnan(pg[t // CH][t % CH, lane])

    # accounting: every defense that fired left a counter behind
    assert rep["quarantines"] >= 1 and rep["restores"] >= 1
    assert rep["quarantined_lanes"] == []
    n_surviving = sum(1 for _, lane in hits if lane != POISON_LANE)
    assert n_surviving <= sum(rep["rejected"].values()) <= len(hits)
    assert _metric_sum(metrics, "snapshot_retries_total") == 1
    assert _metric_sum(metrics, "guard_quarantines_total") >= 1
    assert _metric_sum(metrics, "guard_restores_total") >= 1
    assert _metric_sum(metrics, "faults_injected_total") >= 2
    assert store.latest_step() == 3  # the retried snapshot committed
    # the guard never changed the engine's dispatch signature
    assert len(eng._chunks) == 1
    assert len(oracle._chunks) == 1
