"""The reduction from profiler events to busy time, idle share, program
time and idle gaps: on hand-made events with known answers, and on a
slice of a trace recorded on a TPU v5 lite (class-w1024-ticks: the end
of one tick chunk, the p-value sync, a read batch and the next
dispatch) against a brute-force count of its nanoseconds."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace

DATA = Path(__file__).parent / "data" / "class_w1024_trace.json"


def test_known_answers():
    ev = {"devices": {0: {
        "ops": [("a", 0, 10), ("b", 5, 15), ("b2", 6, 8), ("c", 20, 30)],
        "modules": [("jit_chunk(1)", 0, 15), ("jit_predict(2)", 20, 30)]}},
        "spans": [("window", 0, 40), ("pvalue_sync", 10, 25),
                  ("traffic", 30, 40)]}
    s = trace.summarize(ev, "jit_chunk", "jit_predict")
    assert s["busy_s"] == pytest.approx(25e-9)
    assert s["window_s"] == pytest.approx(40e-9)
    assert s["program_s"] == pytest.approx({"tick": 15e-9, "read": 10e-9})
    assert s["idle"] == pytest.approx({"pvalue_sync": 5e-9,
                                       "traffic": 10e-9})
    # self time: b2 nests inside b
    assert s["ops"]["b"] == pytest.approx(8e-9)
    assert s["ops"]["b2"] == pytest.approx(2e-9)


def test_clipped_to_the_window_and_averaged_over_chips():
    ops = [("x", -10, 10), ("y", 30, 50)]
    ev = {"devices": {0: {"ops": ops, "modules": []},
                      1: {"ops": [("z", 0, 40)], "modules": []}},
          "spans": [("window", 0, 40)]}
    s = trace.summarize(ev, "p", "q", chips=2)
    assert s["busy_s"] == pytest.approx((20 + 40) / 2 * 1e-9)
    assert s["idle"] == pytest.approx({"untraced": 20e-9})


def test_op_labels():
    assert trace.op_label("%copy.9 = f32[4,8]{1,0:T(8,128)} copy(f32[4,8]"
                          "{0,1} %p)") == "%copy.9 copy f32[4,8]"
    assert trace.op_label("%w.2 = (s32[], f32[3]{0}) while(%t), "
                          "condition=%c") == "%w.2 while s32[]"
    assert trace.op_label("%fusion.81") == "%fusion.81"


def _recorded():
    d = json.loads(DATA.read_text())
    return {"devices": {int(k): {kk: [tuple(x) for x in vv]
                                 for kk, vv in v.items()}
                        for k, v in d["devices"].items()},
            "spans": [tuple(x) for x in d["spans"]]}


def test_recorded_trace_against_brute_force():
    ev = _recorded()
    s = trace.summarize(ev, "jit_chunk", "jit_predict_pvalues")
    lo, hi = [(a, b) for n, a, b in ev["spans"] if n == "window"][0]
    lo, hi = int(lo), int(hi)
    busy = np.zeros(hi - lo, bool)
    for _, a, b in ev["devices"][0]["ops"]:
        busy[max(int(a), lo) - lo:max(min(int(b), hi) - lo, 0)] = True
    assert s["busy_s"] == pytest.approx(busy.sum() * 1e-9, abs=1e-8)
    assert s["window_s"] == pytest.approx((hi - lo) * 1e-9)
    idle = sum(s["idle"].values())
    assert idle == pytest.approx(s["window_s"] - s["busy_s"], abs=1e-9)
    tick = sum(min(b, hi) - max(a, lo) for n, a, b in
               ev["devices"][0]["modules"]
               if n.startswith("jit_chunk") and b > lo and a < hi)
    assert s["program_s"]["tick"] == pytest.approx(tick * 1e-9)
    # between two chunks the device waits on the host: the p-value sync,
    # the read batch, the traffic and the next dispatch
    assert set(s["idle"]) <= {"pvalue_sync", "read_batch", "traffic",
                              "chunk_dispatch", "untraced"}
    assert len(s["device_ops"]) == 10
    assert 0 < s["busy_s"] < s["window_s"]
