"""From a profiler trace to device busy time, program time and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three kinds of event, each as ``(name, start_ns, end_ns)``:

* per device (planes ``/device:TPU:<i>``): the ops that ran (line
  ``XLA Ops``) and the programs they belong to (line ``XLA Modules``);
* on the host: the harness's own spans, ``TraceAnnotation``s named
  ``chipbench.<what>`` (``window`` around the whole measured window).

``summarize`` reduces those to numbers over the window: busy time (the
union of op intervals), device time per program and per op, and the
idle gaps, each attributed to the host span it overlaps most. All of it
is plain arithmetic on the event lists, checked in
``tests/test_trace.py`` on a small recorded trace.
"""
from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "chipbench."


def load(trace_dir) -> dict:
    """Events of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return events(ProfileData.from_file(str(files[-1])))


def events(profile) -> dict:
    """``{"devices": {index: {"ops": [...], "modules": [...]}},
    "spans": [...]}`` from a ``ProfileData``; events are
    ``(name, start_ns, end_ns)``."""
    devices: dict = {}
    spans = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            dev = devices.setdefault(idx, {"ops": [], "modules": []})
            for line in plane.lines:
                kind = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if kind is None:
                    continue
                dev[kind].extend((e.name, e.start_ns, e.start_ns
                                  + e.duration_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name[len(SPAN_PREFIX):], e.start_ns,
                              e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def op_label(name: str) -> str:
    """A short label for an op event, whose name on the TPU is the HLO
    instruction: ``%copy.99 = f32[8,8]{1,0} copy(...)`` reads
    ``%copy.99 copy f32[8,8]``."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    kind = re.search(r"[}\])] ([a-z][\w-]*)\(", rest)
    return " ".join(x for x in (head, kind and kind.group(1),
                                shape and shape.group(1)) if x)


def self_times(evs) -> dict[str, float]:
    """Time per op label less the time of the ops nested inside it (a
    loop's body ops sit inside the loop's own event on the same line)."""
    out: dict[str, float] = collections.defaultdict(float)
    stack: list[list] = []  # [label, end, time of children, start]
    for name, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            lab, end, kids, start = stack.pop()
            out[lab] += end - start - kids
        if stack:
            stack[-1][2] += e - s
        stack.append([op_label(name), e, 0.0, s])
    while stack:
        lab, end, kids, start = stack.pop()
        out[lab] += end - start - kids
    return dict(out)


def union(intervals) -> list[tuple[float, float]]:
    """Sorted disjoint union of (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(evs, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if e > lo and s < hi]


def gaps(busy, lo, hi) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] outside the sorted disjoint ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def attribute(gap_list, spans) -> dict[str, float]:
    """Idle nanoseconds per host activity: each gap goes to the span
    (other than ``window``) that overlaps it most, or to ``untraced``."""
    spans = sorted((s, e, n) for n, s, e in spans if n != "window")
    starts = [s for s, _, _ in spans]
    out: dict[str, float] = collections.defaultdict(float)
    for g0, g1 in gap_list:
        over: dict[str, float] = collections.defaultdict(float)
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(spans) and spans[i][0] < g1:
            s, e, n = spans[i]
            ov = min(e, g1) - max(s, g0)
            if ov > 0:
                over[n] += ov
            i += 1
        name = max(over, key=over.get) if over else "untraced"
        out[name] += g1 - g0
    return dict(out)


def summarize(ev: dict, tick_program: str, read_program: str,
              chips: int = 1) -> dict:
    """Numbers of the window (the host span ``chipbench.window``), in
    seconds, averaged over the first ``chips`` devices:
    ``busy_s``, ``window_s``, ``program_s`` ({"tick", "read"}: device
    time of the programs whose names start with those prefixes),
    ``ops`` (device self time per op label), ``idle`` (idle time per host
    activity, device 0) and the top-10 lists for the breakdown."""
    wins = [(s, e) for n, s, e in ev["spans"] if n == "window"]
    if not wins:
        raise ValueError("the trace holds no chipbench.window span")
    lo, hi = wins[-1]
    devs = [ev["devices"].get(i, {"ops": [], "modules": []})
            for i in range(chips)]
    busy, prog = 0.0, collections.Counter()
    ops: collections.Counter = collections.Counter()
    idle: dict[str, float] = {}
    for i, dev in enumerate(devs):
        op_evs = _clip(dev["ops"], lo, hi)
        cover = union((s, e) for _, s, e in op_evs)
        busy += sum(e - s for s, e in cover)
        ops.update(self_times(op_evs))
        for name, s, e in _clip(dev["modules"], lo, hi):
            for key, prefix in (("tick", tick_program),
                                ("read", read_program)):
                if name.startswith(prefix):
                    prog[key] += e - s
        if i == 0:
            idle = attribute(gaps(cover, lo, hi), ev["spans"])
    n = float(len(devs))
    s = 1e-9 / n
    top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10]]
    return dict(
        busy_s=busy * s, window_s=(hi - lo) * 1e-9,
        program_s={k: v * s for k, v in prog.items()},
        ops={k: v * s for k, v in ops.items()},
        idle={k: v * 1e-9 for k, v in idle.items()},
        device_ops=top({k: v * s for k, v in ops.items()}),
        idle_gaps=top({k: v * 1e-9 for k, v in idle.items()}))
