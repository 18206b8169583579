"""repro.telemetry: metrics math, trace schema, device-stat exactness.

The acceptance-critical properties:
* instrumented engines are BIT-identical to uninstrumented ones
  (state leaf-for-leaf + p-values, both engine families, sliding and
  grow modes) — the device tick stats only read integer bookkeeping;
* the device tick counters equal an offline recomputation from the
  traffic (closed form == per-tick simulation);
* the rolling coverage monitor matches an exact offline recomputation,
  and the drift monitor matches ``core.online``'s mixture martingale;
* ``launch/serve.py --trace-out`` produces a schema-valid trace;
* the tick and read programs carry their stage scopes in their op names,
  and every engine dispatch writes its ``repro.*`` spans into a profile,
  instrumented or not.
"""
import io
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.telemetry import (CoverageMonitor, DriftMonitor, EngineTelemetry,
                             MetricsRegistry, Tracer, UniformityMonitor,
                             capacity_bucket, validate_record,
                             validate_trace_file)
from repro.telemetry.device import STAT_KEYS
from repro.telemetry.metrics import Histogram

# ---------------------------------------------------------------- metrics


def test_counter_gauge_identity_and_labels():
    reg = MetricsRegistry()
    reg.counter("ops_total", op="observe").inc()
    reg.counter("ops_total", op="observe").inc(2)
    reg.counter("ops_total", op="predict").inc()
    assert reg.counter("ops_total", op="observe").value == 3
    assert reg.counter("ops_total", op="predict").value == 1
    with pytest.raises(ValueError):
        reg.counter("ops_total", op="observe").inc(-1)
    reg.gauge("occ").set(7)
    reg.gauge("occ").set(5)
    assert reg.gauge("occ").value == 5


def test_histogram_bucket_math_exact_quantiles():
    h = Histogram("h", (), bounds=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 1.5, 3.0):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(6.5)
    assert h.min == 0.5 and h.max == 3.0
    # rank 2 of 4 lands on the (1, 2] bucket: lo + (hi-lo) * frac with
    # cum=1, c=2, rank=2 -> frac=1/2 -> 1.5 exactly
    assert h.quantile(0.5) == pytest.approx(1.5)
    # estimates are clamped into [min, max] of the true observations
    assert h.quantile(1.0) <= h.max
    assert h.quantile(0.0) >= h.min


def test_histogram_overflow_is_lower_bound():
    h = Histogram("h", (), bounds=(1.0,))
    h.observe(100.0)
    # overflow estimate: max(last finite edge, observed min) — a lower
    # bound on the true quantile, and flagged as such
    assert h.quantile(0.99) == pytest.approx(100.0)
    assert h.quantile_is_lower_bound(0.99)
    h2 = Histogram("h2", (), bounds=(1.0,))
    h2.observe(0.5)
    assert not h2.quantile_is_lower_bound(0.99)


def test_histogram_rejects_bad_bounds_and_quantiles():
    with pytest.raises(ValueError):
        Histogram("h", (), bounds=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("h", (), bounds=())
    h = Histogram("h", (), bounds=(1.0,))
    assert math.isnan(h.quantile(0.5))  # empty
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_empty_histogram_flagged_in_snapshot_and_text():
    reg = MetricsRegistry()
    h = reg.histogram("lat_s")
    s = h.snapshot()
    assert s["empty"] is True
    assert math.isnan(h.quantile(0.5))
    assert "empty=1" in reg.to_text()
    h.observe(1.0)
    assert h.snapshot()["empty"] is False
    assert "empty=1" not in reg.to_text()


def test_label_values_escaped_in_exposition_format():
    reg = MetricsRegistry()
    reg.counter("c_total", path='a"b\\c\nd').inc()
    text = reg.to_text()
    # backslash, quote and newline escape per the exposition format —
    # and the snapshot stays one-line-per-series parseable
    assert r'path="a\"b\\c\nd"' in text
    assert len(text.splitlines()) == 1


def test_histogram_emits_sum_count_series():
    reg = MetricsRegistry()
    h = reg.histogram("lat_s", op="observe")
    h.observe(1.0)
    h.observe(3.0)
    text = reg.to_text()
    assert 'lat_s_count{op="observe"} 2' in text
    assert 'lat_s_sum{op="observe"} 4' in text


def test_registry_export_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a_total", engine="classification").inc(4)
    reg.gauge("b").set(1.25)
    reg.histogram("c_s").observe(0.01)
    text = reg.to_text()
    assert 'a_total{engine="classification"} 4' in text
    assert "c_s count=1" in text
    path = str(tmp_path / "m.json")
    reg.dump(path)
    d = json.load(open(path))
    by_name = {m["name"]: m for m in d["metrics"]}
    assert by_name["a_total"]["value"] == 4
    assert by_name["a_total"]["labels"] == {"engine": "classification"}
    assert by_name["c_s"]["count"] == 1


# ----------------------------------------------------------------- tracer


def test_trace_schema_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    tr = Tracer(path)
    tr.record("observe", 0.001, tenants=4, ticks=1, capacity=100,
              engine="classification")
    with tr.op("observe_many", signature=(64, 256), tenants=8) as ctx:
        ctx.late["ticks"] = 64
    with tr.op("observe_many", signature=(64, 256)):
        pass
    tr.close()
    recs = validate_trace_file(path)
    assert [r["op"] for r in recs] == ["observe", "observe_many",
                                      "observe_many"]
    assert recs[0]["capacity"] == 100 and recs[0]["cap_bucket"] == 128
    assert recs[1]["compile"] is True and recs[1]["ticks"] == 64
    assert recs[2]["compile"] is False  # same (op, signature): steady


def test_trace_validation_rejects_bad_records():
    with pytest.raises(ValueError):
        validate_record({"schema": 1, "seq": 0, "t": 0.0,
                         "op": "not_an_op", "wall_s": 0.0})
    with pytest.raises(ValueError):
        validate_record({"schema": 1, "seq": 0, "t": 0.0, "op": "observe"})
    with pytest.raises(ValueError):  # bool is not an int
        validate_record({"schema": 1, "seq": True, "t": 0.0,
                         "op": "observe", "wall_s": 0.0})
    f = io.StringIO()
    tr = Tracer(f)
    with pytest.raises(ValueError):
        tr.record("nope", 0.0)


def test_capacity_bucket():
    assert [capacity_bucket(c) for c in (1, 2, 3, 128, 129)] == \
        [1, 2, 4, 128, 256]


# --------------------------------------------- engine bit-exactness (CP!)


def _class_traffic(S, T, dim, seed=0):
    key = jax.random.PRNGKey(seed)
    kx, ky, kt = jax.random.split(key, 3)
    return (jax.random.normal(kx, (T, S, dim), jnp.float32),
            jax.random.bernoulli(ky, 0.5, (T, S)).astype(jnp.int32),
            jax.random.uniform(kt, (T, S), dtype=jnp.float32))


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))


@pytest.mark.parametrize("window", [None, 10])
def test_instrumented_serving_engine_bit_identical(window):
    from repro.serving import ServingEngine

    S, T, dim, cap = 3, 26, 5, 32
    xs, ys, taus = _class_traffic(S, T, dim)
    kw = dict(n_sessions=S, capacity=cap, dim=dim, k=5, n_labels=2,
              window=window)
    plain = ServingEngine(**kw)
    inst = ServingEngine(**kw, instrument=True, metrics=MetricsRegistry())
    s1, s2 = plain.init_state(), inst.init_state()
    s1, p1 = plain.observe_many(s1, xs, ys, taus)
    s2, p2 = inst.observe_many(s2, xs, ys, taus)
    assert np.asarray(p1).tobytes() == np.asarray(p2).tobytes()
    # per-tick path on top of the chunked one
    s1, q1 = plain.observe(s1, xs[0], ys[0], taus[0])
    s2, q2 = inst.observe(s2, xs[0], ys[0], taus[0])
    assert np.asarray(q1).tobytes() == np.asarray(q2).tobytes()
    assert _leaves_equal(s1, s2)
    r1 = plain.predict(s1, xs[:2].transpose(1, 0, 2))
    r2 = inst.predict(s2, xs[:2].transpose(1, 0, 2))
    assert np.asarray(r1).tobytes() == np.asarray(r2).tobytes()


@pytest.mark.parametrize("window", [None, 12])
def test_instrumented_regression_engine_bit_identical(window):
    from repro.regression import RegressionServingEngine

    S, T, dim, cap = 3, 30, 4, 32
    key = jax.random.PRNGKey(5)
    kx, ky, kt = jax.random.split(key, 3)
    xs = jax.random.normal(kx, (T, S, dim), jnp.float32)
    ys = jax.random.normal(ky, (T, S), jnp.float32)
    taus = jax.random.uniform(kt, (T, S), dtype=jnp.float32)
    kw = dict(n_sessions=S, capacity=cap, dim=dim, k=5, window=window)
    plain = RegressionServingEngine(**kw)
    inst = RegressionServingEngine(**kw, instrument=True,
                                   metrics=MetricsRegistry())
    s1, s2 = plain.init_state(), inst.init_state()
    s1, p1 = plain.observe_many(s1, xs, ys, taus)
    s2, p2 = inst.observe_many(s2, xs, ys, taus)
    assert np.asarray(p1).tobytes() == np.asarray(p2).tobytes()
    s1, q1 = plain.observe(s1, xs[0], ys[0], taus[0])
    s2, q2 = inst.observe(s2, xs[0], ys[0], taus[0])
    assert np.asarray(q1).tobytes() == np.asarray(q2).tobytes()
    assert _leaves_equal(s1, s2)
    Xq = jax.random.normal(kx, (3, dim), jnp.float32)
    iv1 = plain.intervals(s1, Xq, 0.2)
    iv2 = inst.intervals(s2, Xq, 0.2)
    assert np.asarray(iv1).tobytes() == np.asarray(iv2).tobytes()


def test_instrumented_compact_layout_bit_identical():
    from repro.serving import ServingEngine

    S, T, dim, cap = 2, 20, 4, 16
    xs, ys, taus = _class_traffic(S, T, dim, seed=3)
    kw = dict(n_sessions=S, capacity=cap, dim=dim, k=3, n_labels=2,
              window=8, layout="compact")
    plain = ServingEngine(**kw)
    inst = ServingEngine(**kw, instrument=True, metrics=MetricsRegistry())
    s1, p1 = plain.observe_many(plain.init_state(), xs, ys, taus)
    s2, p2 = inst.observe_many(inst.init_state(), xs, ys, taus)
    assert np.asarray(p1).tobytes() == np.asarray(p2).tobytes()
    assert _leaves_equal(s1, s2)


# ------------------------------------------------------ device tick stats


def _simulate_stats(n0, head0, wrap, windows, actives):
    """Per-tick reference simulation of the closed-form chunk stats."""
    n, head = n0.copy(), head0.copy()
    tot = {k: 0 for k in STAT_KEYS}
    tot["occupancy_max"] = 0
    for act in actives:
        ev = act & (n >= windows)
        tot["ticks"] += int(act.sum())
        tot["evictions"] += int(ev.sum())
        tot["ring_wraps"] += int((ev & (head == wrap - 1)).sum())
        head = np.where(ev, (head + 1) % wrap, head)
        n = np.where(act, np.minimum(n + 1, windows), n)
        tot["occupancy_sum"] += int(n.sum())
        tot["occupancy_max"] = max(tot["occupancy_max"], int(n.max()))
    return tot


def test_device_tick_stats_match_offline_simulation():
    from repro.serving import ServingEngine

    S, dim, cap, w = 4, 4, 16, 6
    reg = MetricsRegistry()
    eng = ServingEngine(n_sessions=S, capacity=cap, dim=dim, k=3,
                        n_labels=2, window=w, instrument=True, metrics=reg)
    state = eng.init_state()
    rng = np.random.default_rng(0)
    total = {k: 0 for k in STAT_KEYS}
    for chunk in (7, 9, 13):  # several chunks, ragged active masks
        xs, ys, taus = _class_traffic(S, chunk, dim, seed=chunk)
        active = jnp.asarray(rng.random((chunk, S)) < 0.8)
        ref = _simulate_stats(
            np.asarray(state.knn.n), np.asarray(state.head),
            np.asarray(state.wrap), np.full(S, w, np.int64),
            np.asarray(active))
        state, _ = eng.observe_many(state, xs, ys, taus, active=active)
        for k in STAT_KEYS:
            if k == "occupancy_max":
                total[k] = max(total[k], ref[k])
            else:
                total[k] += ref[k]
    got = eng.telemetry.drain()
    assert {k: got[k] for k in STAT_KEYS} == total
    # published under engine_* with the run totals
    assert reg.counter("engine_ticks_total",
                       engine="classification").value == total["ticks"]
    assert reg.gauge("engine_occupancy_max",
                     engine="classification").value == \
        total["occupancy_max"]
    # drained: a second drain is empty and totals persist
    again = eng.telemetry.drain()
    assert {k: again[k] for k in STAT_KEYS} == {k: 0 for k in STAT_KEYS}
    assert eng.telemetry.ticks.totals["evictions"] == total["evictions"]


# ---------------------------------- the programs' own scopes and spans


def _op_names(compiled_text):
    import re
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def _under(names, scope, inside=""):
    """Some op's name path holds ``scope`` (bare, or opened under a
    vmap) after ``inside``."""
    parts = (f"/{scope}/", f"/vmap({scope})/")
    return any(inside in n and any(p in n[n.find(inside):] for p in parts)
               for n in names)


def _tiny_engine(kind, **kw):
    from repro.regression import RegressionServingEngine
    from repro.serving import ServingEngine

    if kind == "class":
        return ServingEngine(n_sessions=3, capacity=16, dim=3, k=3,
                             n_labels=2, window=12, **kw)
    return RegressionServingEngine(n_sessions=3, capacity=16, dim=3, k=3,
                                   window=12, **kw)


@pytest.mark.parametrize("kind", ["class", "reg"])
@pytest.mark.parametrize("scope", ["evict", "learn", "write"])
def test_tick_program_carries_stage_scopes(kind, scope):
    eng = _tiny_engine(kind)
    names = _op_names(eng.lower_tick(4).compile().as_text())
    assert _under(names, scope, inside="/while/body/"), sorted(names)[:20]


@pytest.mark.parametrize("kind", ["class", "reg"])
def test_instrumented_tick_stats_run_under_stats_scope(kind):
    eng = _tiny_engine(kind, instrument=True, metrics=MetricsRegistry())
    names = _op_names(eng.lower_tick(4).compile().as_text())
    assert _under(names, "stats")
    # the stats run once per chunk, outside the scanned tick body
    assert not _under(names, "stats", inside="/while/body/")


@pytest.mark.parametrize("route", ["jnp", "pallas"])
@pytest.mark.parametrize("kind", ["class", "reg"])
def test_read_program_carries_stage_scopes(kind, route, monkeypatch):
    if route == "pallas":  # the kernels' route, through interpret mode
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    jax.clear_caches()
    stages = (("query", "count") if kind == "class"
              else ("gather", "query", "sweep", "hull"))
    names = _op_names(_tiny_engine(kind).lower_read(2).compile().as_text())
    jax.clear_caches()
    for stage in stages:
        assert _under(names, stage), (stage, sorted(names)[:20])


def _profile_spans(run, tmp_path):
    """The ``repro.*`` host spans ``run()`` writes into a profile:
    (name, start, end, seq)."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats).get("seq")) for e in line.events
                       if e.name.startswith("repro."))
    return out


def _children(spans, outer):
    name, s, e, seq = outer
    return sorted(n for n, s1, e1, q in spans
                  if q == seq and (n, s1, e1) != (name, s, e)
                  and s <= s1 and e1 <= e)


@pytest.mark.parametrize("instrument", [False, True],
                         ids=["plain", "instrumented"])
def test_engines_write_dispatch_spans(instrument, tmp_path):
    kw = dict(instrument=True, metrics=MetricsRegistry()) if instrument \
        else {}
    cls, reg = _tiny_engine("class", **kw), _tiny_engine("reg", **kw)
    xs, ys, taus = _class_traffic(3, 4, 3, seed=1)
    sc, sr = cls.init_state(), reg.init_state()
    Xq = jnp.zeros((3, 2, 3), jnp.float32)

    def serve(sc, sr):
        sc, _ = cls.observe_many(sc, xs, ys, taus)
        sr, _ = reg.observe_many(sr, xs, ys.astype(jnp.float32), taus)
        jax.block_until_ready((cls.predict(sc, Xq),
                               reg.intervals(sr, Xq, 0.1)))
        return sc, sr

    sc, sr = serve(sc, sr)  # compile outside the profile
    spans = _profile_spans(lambda: serve(sc, sr), tmp_path)
    outer = [sp for sp in spans if sp[0] in (
        "repro.observe_many", "repro.predict", "repro.intervals")]
    assert sorted(sp[0] for sp in outer) == [
        "repro.intervals", "repro.observe_many", "repro.observe_many",
        "repro.predict"]
    # every span of a dispatch carries its sequence number
    assert all(isinstance(sp[3], int) for sp in spans)
    for sp in outer:
        if sp[0] == "repro.observe_many":
            want = ["repro.launch", "repro.prepare"]
            if instrument:
                want = ["repro.fold"] + want
        else:
            want = ["repro.launch"]
        assert _children(spans, sp) == want, sp
    # each engine numbers its own dispatches, two each before the profile
    seqs = [sp[3] for sp in sorted(outer, key=lambda sp: sp[1])]
    assert seqs == [3, 3, 4, 4]


def _chunk_args(eng, state, xs, ys, taus):
    return (state, xs, ys, taus, eng._windows(state),
            jnp.ones(xs.shape[:2], bool))


@pytest.mark.parametrize("kind", ["class", "reg"])
def test_drain_reports_the_dispatched_chunk_program_bytes(kind):
    """``state_bytes`` is the state's leaf bytes (one shard); the
    dispatch calls the executable compiled ahead of time for its shape,
    and ``chunk_temp_bytes`` is that executable's own temporaries: no
    later dispatch of the shape compiles again."""
    eng = _tiny_engine(kind, instrument=True, metrics=MetricsRegistry())
    xs, ys, taus = _class_traffic(3, 4, 3, seed=2)
    ys = ys if kind == "class" else ys.astype(jnp.float32)
    state = eng.init_state()
    leaf_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(state))
    for _ in range(2):  # the second folds the stats: its add compiles
        state, _ = eng.observe_many(state, xs, ys, taus)
    assert len(eng._chunks) == 1
    exe = eng._chunks(_chunk_args(eng, state, xs, ys, taus))
    assert eng.telemetry._noted is exe
    mem = eng.telemetry.memory
    compiles = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        state, _ = eng.observe_many(state, xs, ys, taus)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    got = eng.telemetry.drain()
    assert got["state_bytes"] == leaf_bytes
    assert got["chunk_temp_bytes"] == \
        exe.memory_analysis().temp_size_in_bytes == mem["chunk_temp_bytes"]
    # the same program the engine's jitted chunk compiles to
    again = eng._step_many.lower(
        *_chunk_args(eng, state, xs, ys, taus)).compile()
    assert got["chunk_temp_bytes"] == \
        again.memory_analysis().temp_size_in_bytes


def test_engine_telemetry_without_accessors_is_timing_only():
    tele = EngineTelemetry(engine="registry", metrics=MetricsRegistry())
    assert tele.stats_fn is None
    with tele.timed("fit", signature="knn", tenants=1):
        pass
    assert tele.drain() == {}
    assert tele.metrics.counter("engine_ops_total", op="fit",
                                engine="registry").value == 1


# ------------------------------------------------------ validity monitors


def test_coverage_monitor_matches_offline_recomputation():
    rng = np.random.default_rng(1)
    S, T, w, eps = 5, 40, 16, 0.2
    p = rng.random((T, S))
    p[rng.random((T, S)) < 0.25] = np.nan  # ragged tenant clocks
    mon = CoverageMonitor(eps, S, window=w)
    for t in range(T):
        mon.update(p[t])
    cov = mon.coverage()
    for s in range(S):
        hist = p[:, s][np.isfinite(p[:, s])]
        kept = hist[-w:]  # the rolling window keeps the suffix
        if kept.size == 0:
            assert math.isnan(cov[s])
        else:
            assert cov[s] == pytest.approx(np.mean(kept > eps))
    assert np.array_equal(
        mon.counts(), [min(np.isfinite(p[:, s]).sum(), w)
                       for s in range(S)])


def test_uniformity_monitor_ks_matches_offline():
    rng = np.random.default_rng(2)
    S, T, w = 3, 30, 30
    p = rng.random((T, S))
    mon = UniformityMonitor(S, window=w)
    mon.update(p)  # (T, S) block form
    ks = mon.ks()
    for s in range(S):
        u = np.sort(p[:, s])
        i = np.arange(1, T + 1)
        ref = max(np.max(i / T - u), np.max(u - (i - 1) / T))
        assert ks[s] == pytest.approx(ref)


def test_drift_monitor_matches_core_martingale():
    from repro.core.online import simple_mixture_log_martingale

    rng = np.random.default_rng(3)
    S, T = 4, 60
    p = rng.random((T, S)).astype(np.float32)
    # tenant 3 drifts: p-values collapse toward 0 halfway through
    p[T // 2:, 3] *= 0.02
    # threshold high enough that exchangeable tenants stay under it
    # (Ville: P(max log M > 6) <= e^-6), low enough that the drifted
    # tenant (log M ~ +40 here) is far past it
    mon = DriftMonitor(S, threshold=6.0)
    running_max = np.full(S, -np.inf)
    for t in range(T):
        mon.update(p[t])
        running_max = np.maximum(running_max, mon.log_m())
    for s in range(S):
        ref = float(simple_mixture_log_martingale(jnp.asarray(p[:, s]))[-1])
        assert mon.log_m()[s] == pytest.approx(ref, rel=1e-4, abs=1e-4)
    assert np.allclose(mon.max_log_m, running_max)
    assert mon.flagged(use_max=True)[3]
    assert not mon.flagged(use_max=True)[:3].any()
    assert mon.log_m()[0] != 0.0 or mon.ticks[0] == 0


def test_drift_monitor_export_has_no_infinities():
    mon = DriftMonitor(2)
    reg = MetricsRegistry()
    mon.export(reg, engine="classification")
    assert reg.gauge("drift_log_m_max", engine="classification").value == 0
    json.dumps(reg.to_dict())  # -inf would not serialize


# ------------------------------------------------------- snapshot timing


def test_snapshot_store_records_timing(tmp_path):
    from repro.serving import ServingEngine, SessionStore

    reg = MetricsRegistry()
    tracef = io.StringIO()
    tr = Tracer(tracef)
    eng = ServingEngine(n_sessions=2, capacity=8, dim=3, k=3, n_labels=2)
    state = eng.init_state()
    store = SessionStore(str(tmp_path / "snap"), metrics=reg, tracer=tr)
    store.save(1, state, meta=eng.meta(), blocking=True)
    _, step, _ = store.restore()
    assert step == 1
    assert reg.histogram("snapshot_save_s").count == 1
    assert reg.histogram("snapshot_restore_s").count == 1
    ops = [json.loads(line)["op"]
           for line in tracef.getvalue().splitlines()]
    assert ops == ["snapshot_save", "snapshot_restore"]


# -------------------------------------------------------- serve.py e2e


def test_serve_classification_e2e_trace_and_metrics(tmp_path):
    from repro.launch import serve

    trace = str(tmp_path / "trace.jsonl")
    mout = str(tmp_path / "metrics.json")
    rc = serve.main([
        "--sessions", "3", "--steps", "16", "--window", "6",
        "--capacity", "16", "--dim", "3", "--k", "3",
        "--snapshot-dir", str(tmp_path / "snap"),
        "--trace-out", trace, "--metrics-out", mout])
    assert rc == 0
    recs = validate_trace_file(trace)
    ops = {r["op"] for r in recs}
    assert {"observe_many", "snapshot_save", "snapshot_restore"} <= ops
    compiles = [r for r in recs if r["op"] == "observe_many" and r["compile"]]
    assert len(compiles) == 1  # one signature -> one compile record
    d = json.load(open(mout))
    names = {m["name"] for m in d["metrics"]}
    assert {"engine_ticks_total", "engine_evictions_total",
            "validity_coverage_mean", "drift_log_m_max",
            "serve_session_steps_per_s"} <= names


def test_serve_regression_e2e(tmp_path):
    from repro.launch import serve

    trace = str(tmp_path / "trace.jsonl")
    rc = serve.main([
        "--sessions", "2", "--regression", "--steps", "20",
        "--window", "8", "--capacity", "16", "--dim", "2", "--k", "3",
        "--trace-out", trace])
    assert rc == 0
    recs = validate_trace_file(trace)
    assert {"observe_many", "intervals"} <= {r["op"] for r in recs}
    assert all(r["engine"] == "regression" for r in recs
               if r["op"] == "observe_many")


@pytest.mark.parametrize("steps,chunk", [(20, 16), (16, 16), (18, 4)])
def test_serve_rejects_steps_not_whole_chunks(steps, chunk):
    from repro.launch import serve

    with pytest.raises(SystemExit, match="multiple of --chunk"):
        serve.main(["--sessions", "2", "--steps", str(steps),
                    "--chunk", str(chunk), "--window", "6",
                    "--capacity", "16", "--dim", "3", "--k", "3"])


@pytest.mark.parametrize("mode", ["classification", "regression"])
def test_serve_chunked_drive_matches_per_tick(mode):
    """``--chunk 4`` serves the same p-values and state as one tick per
    dispatch (the launcher's side of the observe_many property)."""
    from repro.launch import serve

    run = (serve._run_regression if mode == "regression"
           else serve._run_sessions)
    out = []
    for chunk in (1, 4):
        argv = ["--sessions", "3", "--steps", "16", "--window", "6",
                "--capacity", "16", "--dim", "3", "--k", "3",
                "--chunk", str(chunk)]
        args = serve._parser().parse_args(argv)
        out.append(run(args, *serve._telemetry(args)))
    one, many = out
    np.testing.assert_array_equal(one.pvals, many.pvals)
    for a, b in zip(jax.tree_util.tree_leaves(one.state),
                    jax.tree_util.tree_leaves(many.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serve_registry_e2e(tmp_path):
    from repro.launch import serve

    trace = str(tmp_path / "trace.jsonl")
    mout = str(tmp_path / "metrics.json")
    rc = serve.main([
        "--sessions", "2", "--measure", "knn", "--steps", "24",
        "--window", "8", "--dim", "3", "--k", "3",
        "--trace-out", trace, "--metrics-out", mout])
    assert rc == 0
    recs = validate_trace_file(trace)
    assert {"fit", "observe", "pvalues", "evict"} <= \
        {r["op"] for r in recs}
    d = json.load(open(mout))
    names = {m["name"] for m in d["metrics"]}
    assert "validity_coverage_mean" in names


# ----------------------------------------------------- registry merging


def test_counter_and_histogram_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("ops_total", op="observe").inc(3)
    b.counter("ops_total", op="observe").inc(4)
    a.counter("ops_total", op="observe").merge(
        b.counter("ops_total", op="observe"))
    assert a.counter("ops_total", op="observe").value == 7

    ha, hb = Histogram("h", (), bounds=(1.0, 2.0)), \
        Histogram("h", (), bounds=(1.0, 2.0))
    for v in (0.5, 1.5):
        ha.observe(v)
    for v in (1.5, 5.0):
        hb.observe(v)
    ha.merge(hb)
    assert ha.count == 4 and ha.counts == [1, 2, 1]
    assert ha.min == 0.5 and ha.max == 5.0 and ha.sum == 8.5


def test_histogram_merge_mismatched_bounds_raises():
    ha = Histogram("h", (), bounds=(1.0, 2.0))
    hb = Histogram("h", (), bounds=(1.0, 4.0, 8.0))
    with pytest.raises(ValueError, match="mismatched bucket"):
        ha.merge(hb)


def test_gauge_merge_policies():
    from repro.telemetry.metrics import Gauge

    def pair(x, y):
        ga, gb = Gauge("g", ()), Gauge("g", ())
        ga.set(x)
        gb.set(y)
        return ga, gb

    for policy, want in (("max", 5.0), ("min", 2.0), ("sum", 7.0),
                         ("last", 2.0)):
        ga, gb = pair(5.0, 2.0)
        ga.merge(gb, policy=policy)
        assert ga.value == want, policy
    # NaN (unset) never clobbers a set value, in either direction
    ga, gb = Gauge("g", ()), Gauge("g", ())
    gb.set(3.0)
    ga.merge(gb)
    assert ga.value == 3.0
    gb.merge(Gauge("g", ()), policy="last")
    assert gb.value == 3.0
    with pytest.raises(ValueError, match="policy"):
        ga.merge(gb, policy="median")


def _populated_registry(seed: int) -> MetricsRegistry:
    r = MetricsRegistry()
    r.counter("ticks_total", engine="c").inc(10 + seed)
    r.gauge("occupancy_max", engine="c").set(4.0 * (seed + 1))
    h = r.histogram("wall_s", op="observe")
    for v in (1e-4 * (seed + 1), 2e-3):
        h.observe(v)
    # a series only this shard owns
    r.counter(f"only_{seed}_total").inc(seed + 1)
    return r


def test_registry_merge_identity_and_commutativity():
    # identity: merging an empty registry changes nothing
    a = _populated_registry(0)
    before = a.to_text()
    a.merge(MetricsRegistry())
    assert a.to_text() == before
    # ... and merging INTO an empty registry copies everything
    e = MetricsRegistry()
    e.merge(_populated_registry(0))
    assert e.to_text() == before

    # commutativity (sum/max/bucket-add are all symmetric)
    ab = _populated_registry(0).merge(_populated_registry(1))
    ba = _populated_registry(1).merge(_populated_registry(0))
    assert ab.to_text() == ba.to_text()
    assert ab.counter("ticks_total", engine="c").value == 21
    assert ab.gauge("occupancy_max", engine="c").value == 8.0
    assert ab.histogram("wall_s", op="observe").count == 4
    assert ab.counter("only_0_total").value == 1
    assert ab.counter("only_1_total").value == 2


def test_registry_merge_gauge_policy_forwarded():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.gauge("occ").set(3.0)
    b.gauge("occ").set(2.0)
    a.merge(b, gauge_policy="sum")
    assert a.gauge("occ").value == 5.0
