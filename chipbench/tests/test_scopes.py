"""The reduction from the program's own scopes and spans to per-scope
device time, engine span time and idle time inside the engine's spans:
on hand-made events with known answers, on a trace recorded on a TPU
v5 lite, and through the metric readers that read it.
``trace.summarize``, which this reading leaves alone, is pinned on its
own recorded trace.

The recorded trace is the first 12 ms of the window of ``scoped.py
--workload class-w1024-ticks --seed 2600000001 --seconds 2 --set
tenants=16 --set window=128 --set capacity=128 --record ...`` (two tick
chunks, one read), op names cut to their labels. Its ``names`` come
from the same programs compiled for a described v5e (``v5e:2x2``, one
chip): every op instruction of the recording is among them."""
import json
from pathlib import Path

import pytest

from chipbench import harness, scopes, trace

DATA = Path(__file__).parent / "data"
TICK = "jit_chunk"


@pytest.mark.parametrize("path,scope", [
    ("jit(chunk)/while/body/closed_call/vmap(evict)/reduce_min", "evict"),
    ("jit(chunk)/while/body/closed_call/vmap(learn)/jit(observe_with_dists)"
     "/jit(stream_update)/stream_update/pallas_call", "learn"),
    ("jit(chunk)/stats/reduce_sum", "stats"),
    ("jit(<lambda>)/while/body/closed_call/jit(intervals)/gather/gather",
     "gather"),
    ("jit(<lambda>)/while/body/closed_call/jit(intervals)/hull/vmap()/gt",
     "hull"),
    ("jit(chunk)/while/body/closed_call/vmap()/scatter", None),
    ("jit(chunk)/while/body/closed_call/vmap(jit(_where))/select_n", None),
    ("jit(chunk)/while", None),
    ("", None),
])
def test_scope_of(path, scope):
    assert scopes.scope_of(path) == scope


def _ev():
    """One tick program (a loop with a scoped body, a scoped op before
    it, an op of no scope and a 5 ns hole) and one read program, under a
    window; the engine's spans of two dispatches around them; and the
    programs' ``op_name`` by instruction."""
    ops = [("%s = s32[] reduce()", 0, 10), ("%w = (s32[]) while()", 10, 60),
           ("%a", 12, 30), ("%b", 30, 40), ("%c", 40, 55),
           ("%copy", 65, 70), ("%q", 100, 120), ("%k", 120, 125)]
    body = "jit(chunk)/while/body/closed_call/vmap({})/op"
    names = {"tick": {"s": "jit(chunk)/stats/reduce_sum",
                      "w": "jit(chunk)/while", "a": body.format("evict"),
                      "b": body.format("learn"), "c": body.format("write")},
             "read": {"q": "jit(p)/query/dot", "k": "jit(p)/count/add"}}
    return {"devices": {0: {
        "ops": ops,
        "modules": [("jit_chunk(1)", 0, 70), ("jit_predict(2)", 100, 125)]}},
        "spans": [("window", 0, 200), ("chunk_dispatch", 60, 100)],
        "engine": [("observe_many", 62, 90, 7), ("prepare", 63, 80, 7),
                   ("launch", 80, 85, 7), ("fold", 86, 89, 7),
                   ("predict", 130, 150, 8), ("launch", 131, 140, 8)],
        "names": names}


def _summary(ev, read="jit_predict"):
    return scopes.summarize(ev, TICK, read, names=ev["names"])


def test_op_names_from_compiled_text():
    body = "jit(chunk)/while/body/closed_call/vmap"
    text = (
        "  %copy.96 = f32[8,8]{1,0} copy(%p), backend_config={}\n"
        "  ROOT %copy.99 = f32[8,8]{0,1} copy(%f), metadata={op_name="
        f'"{body}(write)/scatter" stack_frame_id=3}}\n'
        "  %select_reduce_fusion.2 = (s32[8]{0}, f32[8]{0}) fusion(%a), "
        f'metadata={{op_name="{body}(evict)/reduce"}}\n')
    names = scopes.op_names(text)
    assert names == {
        "copy.96": "",
        "copy.99": "jit(chunk)/while/body/closed_call/vmap(write)/scatter",
        "select_reduce_fusion.2":
            "jit(chunk)/while/body/closed_call/vmap(evict)/reduce"}
    assert scopes.instruction(
        "%copy.99 = f32[8,8]{0,1} copy(f32[8,8] %f)") == "copy.99"


def test_known_answers():
    s = _summary(_ev())
    tick, read = s["scopes"]["tick"], s["scopes"]["read"]
    # nested self time: the loop's own time is not its body's
    assert tick == pytest.approx({"stats": 10e-9, "evict": 18e-9,
                                  "learn": 10e-9, "write": 15e-9,
                                  "unscoped": 17e-9})
    assert read == pytest.approx({"query": 20e-9, "count": 5e-9,
                                  "unscoped": 0.0})
    assert s["program"]["observe_many"] == pytest.approx(
        {"count": 1, "total_s": 28e-9, "self_s": 3e-9})
    assert s["program"]["observe_many/prepare"]["total_s"] == \
        pytest.approx(17e-9)
    assert s["program"]["predict/launch"]["count"] == 1
    # a dispatch the window's end cuts counts in none of its spans
    ev = _ev()
    ev["engine"] += [("observe_many", 190, 210, 9), ("prepare", 191, 199, 9)]
    assert _summary(ev)["program"] == s["program"]
    # idle gaps: 60-65 and 70-100 go mostly to prepare (63-80, their
    # innermost span), 125-200 mostly to predict's own time (130-131,
    # 140-150) over predict/launch (131-140)
    assert s["idle_program"] == pytest.approx({
        "observe_many/prepare": 35e-9, "predict": 75e-9})
    assert s["scope_ops"]["tick/evict"] == [["%a", pytest.approx(18e-9)]]
    assert s["scope_ops"]["tick/stats"] == [["%s reduce s32[]",
                                             pytest.approx(10e-9)]]


def test_scopes_sum_to_the_program_time_and_idle_to_the_idle_time():
    ev = _ev()
    s = _summary(ev)
    t = trace.summarize(ev, TICK, "jit_predict")
    for key in ("tick", "read"):
        assert sum(s["scopes"][key].values()) == pytest.approx(
            t["program_s"][key])
    assert sum(s["idle_program"].values()) == pytest.approx(
        t["window_s"] - t["busy_s"])


def test_an_idle_gap_outside_every_engine_span():
    ev = _ev()
    ev["engine"] = [("observe_many", 62, 64, 7)]
    s = _summary(ev)
    assert s["idle_program"] == pytest.approx({"outside": 105e-9,
                                               "observe_many": 5e-9})


def test_a_trace_without_scopes_or_spans_reads_nothing():
    """What the program wrote before it had scopes and spans: every
    device time is unscoped and no reader finds its metric."""
    ev = _ev()
    ev["engine"] = []
    s = scopes.summarize(ev, TICK, "jit_predict")  # no op_name anywhere
    assert set(s["scopes"]["tick"]) == {"unscoped"}
    assert s["program"] == {} and set(s["idle_program"]) == {"outside"}
    rec = {"ticks_in_window": 16, "trace": {"window_s": 2e-7, **s}}
    assert harness.read_metrics(rec, [
        "backfill_ms", "learn_ms", "write_ms", "dispatch_host_ms",
        "engine_idle_share.ticks", "engine_idle_share.reads"]) == {}


def test_readers():
    s = _summary(_ev())
    rec = {"ticks_in_window": 2, "trace": {"window_s": 200e-9, **s}}
    got = harness.read_metrics(rec, [
        "backfill_ms", "learn_ms", "write_ms", "dispatch_host_ms",
        "engine_idle_share.ticks", "engine_idle_share.reads"])
    v = {k: m["value"] for k, m in got.items()}
    assert v["backfill_ms"] == pytest.approx(1e3 * 18e-9 / 2)
    assert v["learn_ms"] == pytest.approx(1e3 * 10e-9 / 2)
    assert v["write_ms"] == pytest.approx(1e3 * 15e-9 / 2)
    assert v["dispatch_host_ms"] == pytest.approx(28e-6)
    assert v["engine_idle_share.ticks"] == pytest.approx(100 * 110 / 200)
    assert v["engine_idle_share.reads"] == v["engine_idle_share.ticks"]


def _recorded(name):
    d = json.loads((DATA / name).read_text())
    return {"devices": {int(k): {kk: [tuple(x) for x in vv]
                                 for kk, vv in v.items()}
                        for k, v in d["devices"].items()},
            "spans": [tuple(x) for x in d["spans"]],
            "engine": [tuple(x) for x in d.get("engine", [])],
            "names": d.get("names", {})}


def test_trace_summary_unchanged_on_its_recorded_trace():
    ev = _recorded("class_w1024_trace.json")
    got = trace.summarize(ev, "jit_chunk", "jit_predict_pvalues")
    want = json.loads((DATA / "class_w1024_summary.json").read_text())
    assert json.loads(json.dumps(got)) == want


SCOPED = "class_w128_scoped_trace.json"


def _paint(n, lo, intervals):
    """Per nanosecond of [lo, lo + n), the index of the last interval
    painted over it (-1: none); intervals painted outermost first."""
    import numpy as np

    out = np.full(n, -1, np.int32)
    for i, (s, e) in intervals:
        out[max(int(s) - lo, 0):max(min(int(e) - lo, n), 0)] = i
    return out


def test_recorded_trace_against_brute_force():
    """A trace recorded on the chip: the scopes and ``unscoped`` sum to
    the tick program's time; each scope's self time equals the
    nanoseconds in which its op is the innermost one running; each idle
    gap goes to the engine span that is innermost over most of it."""
    import numpy as np

    ev = _recorded(SCOPED)
    s = scopes.summarize(ev, "jit_chunk", "jit_predict_pvalues",
                         names=ev["names"])
    t = trace.summarize(ev, "jit_chunk", "jit_predict_pvalues")
    assert {"evict", "learn", "write"} <= set(s["scopes"]["tick"])
    # every op of the two programs is an instruction of their text
    dev = ev["devices"][0]
    for key, prefix in (("tick", "jit_chunk"),
                        ("read", "jit_predict_pvalues")):
        runs = [(a, b) for m, a, b in dev["modules"] if m.startswith(prefix)]
        ops = [op for op in dev["ops"] if any(a <= op[1] < b
                                              for a, b in runs)]
        assert ops and all(scopes.instruction(op[0]) in ev["names"][key]
                           for op in ops)
    for key in s["scopes"]:
        assert sum(s["scopes"][key].values()) == pytest.approx(
            t["program_s"][key], rel=1e-12)

    lo, hi = [(int(a), int(b)) for n, a, b in ev["spans"]
              if n == "window"][0]
    n = hi - lo
    ops = sorted(ev["devices"][0]["ops"], key=lambda x: (x[1], -x[2]))
    top = _paint(n, lo, [(i, (a, b)) for i, (_, a, b) in enumerate(ops)])
    mods = _paint(n, lo, [(i, (a, b)) for i, (_, a, b) in
                          enumerate(ev["devices"][0]["modules"])])
    tick = np.array([m.startswith("jit_chunk")
                     for m, _, _ in ev["devices"][0]["modules"]] + [False])
    in_tick = tick[mods]  # index -1 reads the trailing False
    tick_names = ev["names"]["tick"]
    names = [scopes.scope_of(tick_names.get(scopes.instruction(op[0]), ""))
             for op in ops]
    for scope in ("evict", "learn", "write"):
        mine = np.array([x == scope for x in names] + [False])
        brute = np.sum(mine[top] & in_tick) * 1e-9
        assert s["scopes"]["tick"][scope] == pytest.approx(brute, abs=2e-9)

    # the innermost engine span at each idle nanosecond, span by span
    paths = scopes._engine_paths(ev["engine"])
    order = sorted(range(len(paths)),
                   key=lambda i: (paths[i][1], -paths[i][2]))
    inner = _paint(n, lo, [(i, paths[i][1:3]) for i in order])
    busy = top >= 0
    want: dict = {}
    edges = np.flatnonzero(np.diff(np.r_[True, busy, True]))
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        got = inner[a:b]
        counts = np.bincount(got[got >= 0], minlength=len(paths))
        by_name: dict = {}
        for i, c in enumerate(counts):
            if c:
                by_name[paths[i][0]] = by_name.get(paths[i][0], 0) + c
        name = max(by_name, key=by_name.get) if by_name else "outside"
        want[name] = want.get(name, 0) + (b - a) * 1e-9
    assert set(want) == set(s["idle_program"])
    for name, v in want.items():
        assert s["idle_program"][name] == pytest.approx(v, abs=2e-9)
