"""From a profiler trace to device time per named scope, and the engines'
own host spans with the device idle time inside them.

``trace.py`` reads the harness's side of a trace: busy time, program
time, op time and idle gaps by ``chipbench.*`` span. This module reads
what the program itself writes:

* the named scopes. A TPU op event names its compiled instruction
  (``%fusion.81 = ...``) and carries no name path (on the v5e its stats
  are ``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
  Multiplier``), so ``op_names`` reads each instruction's ``op_name``
  metadata (``jit(chunk)/while/body/closed_call/vmap(evict)/...``) from
  the compiled program's text (``lower_tick`` / ``lower_read`` of the
  engine, ``.compile().as_text()``: the same program that ran). An op
  XLA adds itself (a copy it inserts to protect a buffer) has none;
* on the host, the engines' spans (``repro.<op>`` around a dispatch,
  ``repro.prepare``, ``repro.launch``, ``repro.fold`` inside it), each
  with the dispatch's sequence number ``seq``.

``summarize`` reduces those, over the window (the host span
``chipbench.window``), to:

* ``scopes``: per program (``tick``, ``read``), device self time per
  top-level named scope (``evict``, ``learn``, ``write``, ``stats``;
  ``query``, ``count``, ``gather``, ``sweep``, ``hull``), and
  ``unscoped``: the rest of the program's time, ops under no scope and
  time between its ops, so the values sum to the program's time;
* ``program``: count, total and self time per engine span, named by its
  path (``observe_many``, ``observe_many/launch``, ``predict/launch``),
  over the dispatches wholly inside the window;
* ``idle_program``: device 0's idle time, each gap given to the
  innermost engine span that overlaps it most, or to ``outside``;
* ``scope_ops``: the largest ops of each scope, by label.

The host and device clocks of a v5e profile are aligned to within about
a millisecond (a program's device events can start before the host span
that launched it), so a gap's split between neighbouring spans is that
coarse. Checked in ``tests/test_scopes.py`` on hand-made events and on
a recorded trace.
"""
from __future__ import annotations

import bisect
import collections
import re

from chipbench import trace

ENGINE_PREFIX = "repro."
UNSCOPED = "unscoped"
OUTSIDE = "outside"

# components of a name path that JAX writes for its own structure, not
# for a ``jax.named_scope``
_STRUCTURE = frozenset({"while", "body", "cond", "closed_call", "remat",
                        "checkpoint", "core_call", "shard_map"})
_CALL = re.compile(r"([\w-]+)\((.*)\)$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def events(profile) -> dict:
    """``trace.events`` of a ``ProfileData``, and ``"engine"``: the
    engines' spans as ``(name, start_ns, end_ns, seq)``, names without
    their prefix."""
    ev = trace.events(profile)
    ev["engine"] = [
        (e.name[len(ENGINE_PREFIX):], e.start_ns,
         e.start_ns + e.duration_ns, dict(e.stats).get("seq"))
        for plane in profile.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith(ENGINE_PREFIX)]
    return ev


def op_names(compiled_text: str) -> dict[str, str]:
    """Every instruction of a compiled program's text, by name, with its
    ``op_name`` metadata ("" where it has none)."""
    return {name: (m.group(1) if (m := _OP_NAME.search(rest)) else "")
            for name, rest in _INSTRUCTION.findall(compiled_text)}


def instruction(op_event_name: str) -> str:
    """``%copy.99 = f32[...] copy(...)`` reads ``copy.99``."""
    return op_event_name.split(" ", 1)[0].lstrip("%")


def scope_of(path: str) -> str | None:
    """The outermost named scope in an op's name path, or None. The last
    component is the op itself; ``jit(f)`` is a call and ``vmap(s)``
    holds the scope ``s`` opened inside a ``vmap``."""
    for part in path.split("/")[:-1]:
        while (m := _CALL.match(part)) and m.group(1) not in ("jit",
                                                               "pjit"):
            part = m.group(2)
        if part and not _CALL.match(part) and part not in _STRUCTURE:
            return part
    return None


def _self_ns(evs) -> list[tuple[tuple, float]]:
    """Each event with its time less that of the events nested in it
    (a loop's body ops sit inside the loop's own event on the line)."""
    out = []
    stack: list[list] = []  # [event, end, time of children, start]
    for ev in sorted(evs, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= ev[1]:
            top, end, kids, start = stack.pop()
            out.append((top, end - start - kids))
        if stack:
            stack[-1][2] += ev[2] - ev[1]
        stack.append([ev, ev[2], 0.0, ev[1]])
    out.extend((top, end - start - kids) for top, end, kids, start
               in reversed(stack))
    return out


def _engine_paths(engine) -> list[tuple[str, float, float, object]]:
    """Engine spans named by their path: the spans of one dispatch share
    its ``seq``, and the longest of them holds the others."""
    by_seq = collections.defaultdict(list)
    for sp in engine:
        by_seq[sp[3]].append(sp)
    out = []
    for seq, group in by_seq.items():
        outer = max(group, key=lambda sp: (sp[2] - sp[1], -sp[1]))
        for sp in group:
            name = sp[0] if sp is outer else f"{outer[0]}/{sp[0]}"
            out.append((name, sp[1], sp[2], seq))
    return out


def _exclusive(paths) -> list[tuple[str, float, float]]:
    """Each span less the spans under it (of its own dispatch): the
    stretches of host time in which it is the innermost engine span."""
    by_seq = collections.defaultdict(list)
    for sp in paths:
        by_seq[sp[3]].append(sp)
    out = []
    for group in by_seq.values():
        for name, s, e, _ in group:
            inner = trace.union((s1, e1) for n1, s1, e1, _ in group
                                if n1.startswith(name + "/"))
            out.extend((name, a, b) for a, b in trace.gaps(inner, s, e)
                       if b > a)
    return out


def summarize(ev: dict, tick_program: str, read_program: str,
              chips: int = 1, names: dict | None = None) -> dict:
    """``scopes``, ``program``, ``idle_program`` and ``scope_ops`` over
    the window, in seconds; device numbers averaged over the first
    ``chips`` devices, idle time from device 0 (as ``trace.summarize``).
    ``names`` maps ``tick`` and ``read`` to their programs' ``op_names``;
    without them every op is unscoped."""
    wins = [(s, e) for n, s, e in ev["spans"] if n == "window"]
    if not wins:
        raise ValueError("the trace holds no chipbench.window span")
    lo, hi = wins[-1]
    names = names or {}
    prefixes = {"tick": tick_program, "read": read_program}
    scoped: dict = collections.defaultdict(collections.Counter)
    by_op: dict = collections.defaultdict(collections.Counter)
    prog_ns: collections.Counter = collections.Counter()
    idle: dict = {}
    for i in range(chips):
        dev = ev["devices"].get(i, {"ops": [], "modules": []})
        mods = sorted(((next((k for k, p in prefixes.items()
                              if n.startswith(p)), None), s, e)
                       for n, s, e in trace._clip(dev["modules"], lo, hi)),
                      key=lambda m: m[1])
        starts = [s for _, s, _ in mods]
        ops = trace._clip(dev["ops"], lo, hi)
        for (name, s, _), t in _self_ns(ops):
            j = bisect.bisect_right(starts, s) - 1
            key = mods[j][0] if j >= 0 and s < mods[j][2] else None
            if key is None:
                continue
            scope = scope_of(names.get(key, {}).get(instruction(name), ""))
            if scope is not None:
                scoped[key][scope] += t
                by_op[(key, scope)][trace.op_label(name)] += t
        for key, s, e in mods:
            if key is not None:
                prog_ns[key] += e - s
        if i == 0:
            cover = trace.union((s, e) for _, s, e in ops)
            inner = _exclusive([(n, max(s, lo), min(e, hi), q)
                                for n, s, e, q
                                in _engine_paths(ev.get("engine", []))
                                if e > lo and s < hi])
            idle = trace.attribute(trace.gaps(cover, lo, hi), inner)
    k = 1e-9 / float(chips)
    scopes = {}
    for key, t in prog_ns.items():
        scopes[key] = {n: v * k for n, v in scoped[key].items()}
        scopes[key][UNSCOPED] = (t - sum(scoped[key].values())) * k
    return dict(
        scopes=scopes,
        program=_program_table(ev.get("engine", []), lo, hi),
        idle_program={(OUTSIDE if n == "untraced" else n): v * 1e-9
                      for n, v in idle.items()},
        scope_ops={f"{key}/{scope}": [[lab, v * k] for lab, v in
                                      c.most_common(5)]
                   for (key, scope), c in sorted(by_op.items())})


def _program_table(engine, lo, hi) -> dict:
    """Count, total and self time per engine span path, over the
    dispatches whose spans lie wholly inside [lo, hi]."""
    paths = _engine_paths(engine)
    cut = {q for _, s, e, q in paths if s < lo or e > hi}
    out: dict = {}
    for n, s, e, q in paths:
        if q in cut:
            continue
        row = out.setdefault(n, {"count": 0, "total_s": 0.0})
        row["count"] += 1
        row["total_s"] += (e - s) * 1e-9
    for n, row in out.items():
        kids = sum(r["total_s"] for m, r in out.items()
                   if m.startswith(n + "/") and "/" not in m[len(n) + 1:])
        row["self_s"] = row["total_s"] - kids
    return out
