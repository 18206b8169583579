"""Compiled-artifact invariant auditor: static gates over jaxprs + HLO.

``python -m repro.analysis.audit`` traces and compiles every engine
configuration in the serving matrix (classification/regression x
grow/sliding x ring/compact x shards 1/8) plus the registry measures
(knn, simplified_knn, kde, lssvm, bootstrap, knn_regression) and runs a
registered suite of checkers against the *artifacts* — no tick is
executed (the retrace auditor alone runs a tiny scripted lifecycle,
since retracing is a runtime property). It emits a JSON report with
per-check pass/fail and the offending HLO op lines, and exits nonzero
on any violation; CI runs it as a blocking gate.

Checkers (name -> invariant -> introducing PR):

* ``donation-alias`` — every donated state leaf must alias an output in
  the compiled module (``input_output_alias`` header) and no per-tick
  full-leaf ``copy``/``copy-start`` may touch the donated buffers. This
  is the O(cap) in-place distance-matrix contract of PR 3, and the
  double-copy regression class PR 5's scheduling marker eliminated.
* ``collective-freedom`` — ``collective_bytes == 0`` for every
  shard_map'd tick: PR 8's tenant-sharded dispatch is embarrassingly
  parallel by construction, so any collective is a lowering bug.
* ``dense-budget`` — declarative per-target byte budgets on fresh
  per-tick materializations (``dense_materializations`` with
  ``mult > 1``): ring layouts budget ZERO full-size writes (PR 5's
  O(cap)-eviction claim); the compact sliding layout carries a
  documented waiver (it IS the O(cap^2) baseline/oracle).
* ``retrace`` — a scripted session lifecycle (observe, observe_many,
  read path, then the identical lifecycle again) must add zero
  compilations on the repeat pass, and the first pass must stay within
  the declared shape-bucket budget (PR 1's no-retrace-as-windows-slide
  contract; ``jax.monitoring`` compile events are recorded as a
  secondary signal).
* ``source-lint`` — AST pass over ``src/`` (``repro.analysis.lint``):
  keyed randomness only (PR 4), no host syncs in jit-reachable helpers,
  no Python loops over the tenant axis in engine modules (PR 1-3), and
  ``_donated``/``donate=False`` copy-semantics consistency (PR 3).

Known waiver: at ONE tenant lane per device (``n_sessions == shards``)
XLA-CPU reintroduces a per-tick double copy of the donated (1, cap,
cap) distance carry — a degenerate-batch scheduling artifact, not a
code regression (>= 2 lanes/device compiles clean; real deployments
batch many lanes per shard). The audit matrix therefore uses >= 2
lanes per device; keep fleets above one lane per shard.

IMPORTANT: this module must stay importable WITHOUT importing jax —
``main()`` re-execs with ``XLA_FLAGS=--xla_force_host_platform_device_
count=8`` (CPU hosts only) before jax first loads so the sharded
targets can compile. Everything jax-touching imports lazily.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass, field

from repro.analysis import hlo as hlo_m
from repro.analysis import lint as lint_m

_REEXEC_SENTINEL = "REPRO_AUDIT_REEXEC"

#: engine-matrix shape: >= 2 tenant lanes per device at max shards (see
#: the lanes-per-device waiver in the module docstring)
_S, _CAP, _DIM, _K, _CHUNK = 16, 32, 4, 3, 4

MEASURES = ("knn", "simplified_knn", "kde", "lssvm", "bootstrap",
            "knn_regression")


@dataclass
class AuditTarget:
    """One audited configuration with its declarative budgets."""

    name: str
    kind: str                    # "engine" | "measure"
    family: str = ""             # classification | regression
    mode: str = ""               # sliding | grow
    layout: str = "ring"
    shards: int = 1
    measure: str = ""
    n_sessions: int = _S
    capacity: int = _CAP
    dim: int = _DIM
    k: int = _K
    window: int | None = _CAP
    chunk: int = _CHUNK
    donate: bool = True
    # budgets: a non-empty waiver string replaces the zero budget
    dense_waiver: str = ""
    copy_waiver: str = ""
    max_collective_bytes: float = 0.0
    retrace_budget: dict = field(
        default_factory=lambda: {"step": 2, "read": 1})

    def describe(self) -> dict:
        d = {"name": self.name, "kind": self.kind, "shards": self.shards}
        if self.kind == "engine":
            d.update(family=self.family, mode=self.mode,
                     layout=self.layout, n_sessions=self.n_sessions,
                     capacity=self.capacity, donate=self.donate)
        else:
            d["measure"] = self.measure
        return d


# ---------------------------------------------------------------------------
# the invariants as pure functions over HLO text (single definitions —
# tests/test_ring_layout.py and tests/test_distributed.py consume THESE)
# ---------------------------------------------------------------------------


def dense_tick_violations(hlo_text: str, min_bytes: int) -> list:
    """Fresh writes >= min_bytes that execute once PER TICK (mult > 1).

    The PR 5 ring-layout invariant: a sliding tick never shifts /
    copies / rebuilds a (cap, cap)-sized buffer. One-time (mult == 1)
    setup at the entry is tolerated."""
    return [d for d in hlo_m.dense_materializations(hlo_text, min_bytes)
            if d["mult"] > 1]


def collective_violations(hlo_text: str) -> list:
    """Collective ops (any multiplicity) with their source lines."""
    info = hlo_m.computation_multiplicities(hlo_text)
    out = []
    for cname, m in info["mult"].items():
        for op in info["comps"][cname].ops:
            kind = op.kind[:-len("-start")] \
                if op.kind.endswith("-start") else op.kind
            if kind in hlo_m.COLLECTIVES:
                out.append({"computation": cname, "mult": float(m),
                            "kind": op.kind, "name": op.name,
                            "bytes": op.result_bytes,
                            "line": op.line.strip()})
    return out


def alias_violations(hlo_text: str, expected_aliases: int) -> list:
    """Donated-buffer leaks: fewer aliased params than donated leaves."""
    aliases = hlo_m.input_output_aliases(hlo_text)
    if len(aliases) >= expected_aliases:
        return []
    return [{"kind": "missing-alias",
             "line": f"input_output_alias covers "
                     f"{len(aliases)}/{expected_aliases} donated state "
                     f"leaves: {sorted(aliases.values())}"}]


# ---------------------------------------------------------------------------
# artifacts (lazily traced/compiled, shared across checkers)
# ---------------------------------------------------------------------------


class Artifact:
    """Compiled view of one target. Nothing here executes a tick."""

    def __init__(self, target: AuditTarget):
        self.target = target
        self._engine = None
        self._hlo = None
        self._n_leaves = None

    def build_engine(self, **overrides):
        t = self.target
        kw = dict(n_sessions=t.n_sessions, capacity=t.capacity,
                  dim=t.dim, k=t.k,
                  window=t.window if t.mode == "sliding" else None,
                  layout=t.layout, donate=t.donate, shards=t.shards)
        kw.update(overrides)
        from repro.serving.families import ENGINES
        return ENGINES[t.family](**kw)

    def engine(self):
        if self._engine is None:
            self._engine = self.build_engine()
        return self._engine

    def n_state_leaves(self) -> int:
        if self._n_leaves is None:
            import jax
            self._n_leaves = len(
                jax.tree_util.tree_leaves(self.engine().init_state()))
        return self._n_leaves

    def hlo(self) -> str:
        """Optimized HLO of the compiled observe_many tick (engine
        targets) or of the jitted p-value read path (measure targets)."""
        if self._hlo is None:
            if self.target.kind == "engine":
                lowered = self.engine().lower_tick(self.target.chunk)
                self._hlo = lowered.compile().as_text()
            else:
                self._hlo = _measure_hlo(self.target)
        return self._hlo

    def big_bytes(self) -> int:
        """Per-device full-size (lanes, cap, cap) f32 leaf bytes — the
        threshold above which a fresh write counts as 'dense'."""
        t = self.target
        lanes = t.n_sessions // t.shards
        return lanes * t.capacity * t.capacity * 4

    def trip_fallbacks(self) -> int:
        return hlo_m.computation_multiplicities(
            self.hlo())["trip_fallbacks"]


def _measure_hlo(t: AuditTarget) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving import registry

    rng = np.random.default_rng(0)
    n = 24
    X = jnp.asarray(rng.normal(size=(n, t.dim)), jnp.float32)
    hp: dict = {}
    if t.measure == "knn_regression":
        y = jnp.asarray(rng.normal(size=n), jnp.float32)
        hp = {"k": t.k, "t_query": np.linspace(-1.0, 1.0, 5)}
    else:
        y = jnp.asarray(rng.integers(0, 2, size=n), jnp.int32)
        if t.measure in ("knn", "simplified_knn"):
            hp = {"k": t.k}
    cp = registry.ConformalPredictor(t.measure, **hp).fit(X, y)
    Xq = X[:4]
    fn = lambda st, q: cp.spec.pvalues(st, cp._ctx, q, cp.hp)
    return jax.jit(fn).lower(cp._state, Xq).compile().as_text()


# ---------------------------------------------------------------------------
# checker registry
# ---------------------------------------------------------------------------

CHECKERS: dict = {}


def checker(name: str):
    def deco(fn):
        CHECKERS[name] = fn
        return fn
    return deco


def _result(name, target, status, violations=None, info=None) -> dict:
    return {"check": name, "target": target.name if target else "src",
            "status": status, "violations": violations or [],
            "info": info or {}}


@checker("donation-alias")
def check_donation(target: AuditTarget, art: Artifact) -> dict:
    if target.kind != "engine":
        return _result("donation-alias", target, "skipped",
                       info={"reason": "nothing donated on the "
                                       "registry read path"})
    if not target.donate:
        return _result("donation-alias", target, "skipped",
                       info={"reason": "donate=False copy semantics"})
    text = art.hlo()
    vs = alias_violations(text, art.n_state_leaves())
    info = {"aliased": len(hlo_m.input_output_aliases(text)),
            "state_leaves": art.n_state_leaves()}
    if target.copy_waiver:
        info["copy_waiver"] = target.copy_waiver
    else:
        copies = hlo_m.big_copies(text, art.big_bytes(), min_mult=1.5)
        vs += copies
        info["per_tick_big_copies"] = len(copies)
    return _result("donation-alias", target,
                   "fail" if vs else "pass", vs, info)


@checker("collective-freedom")
def check_collectives(target: AuditTarget, art: Artifact) -> dict:
    text = art.hlo()
    cb = hlo_m.collective_bytes(text)
    total = sum(cb.values())
    vs = collective_violations(text) \
        if total > target.max_collective_bytes else []
    return _result("collective-freedom", target,
                   "fail" if vs else "pass", vs,
                   {"collective_bytes": cb, "shards": target.shards})


@checker("dense-budget")
def check_dense(target: AuditTarget, art: Artifact) -> dict:
    text = art.hlo()
    info = {"min_bytes": art.big_bytes(),
            "trip_fallbacks": art.trip_fallbacks()}
    vs = dense_tick_violations(text, art.big_bytes())
    if target.dense_waiver:
        info.update(waiver=target.dense_waiver, measured=len(vs))
        return _result("dense-budget", target, "waived", [], info)
    return _result("dense-budget", target, "fail" if vs else "pass",
                   vs, info)


@checker("retrace")
def check_retrace(target: AuditTarget, art: Artifact) -> dict:
    if target.kind != "engine":
        return _result("retrace", target, "skipped",
                       info={"reason": "registry predictors are the "
                                       "exact-shape API (one retrace "
                                       "per size by design)"})
    if target.shards > 1:
        return _result("retrace", target, "skipped",
                       info={"reason": "lifecycle executed on the "
                                       "shards=1 twin (same step fn)"})
    import jax
    import jax.numpy as jnp

    compile_events = [0]

    def _listener(event: str, **kw):
        if "compil" in event:
            compile_events[0] += 1

    jax.monitoring.register_event_listener(_listener)

    eng = art.build_engine()  # fresh engine: empty jit caches
    t = target

    def lifecycle(state):
        for i in range(3):
            x = jnp.full((t.n_sessions, t.dim), 0.1 * (i + 1),
                         jnp.float32)
            y = jnp.zeros((t.n_sessions,), eng.y_dtype)
            tau = jnp.full((t.n_sessions,), 0.5, jnp.float32)
            state, _ = eng.observe(state, x, y, tau)
        xs = jnp.zeros((t.chunk, t.n_sessions, t.dim), jnp.float32)
        ys = jnp.zeros((t.chunk, t.n_sessions), eng.y_dtype)
        ts = jnp.full((t.chunk, t.n_sessions), 0.5, jnp.float32)
        state, _ = eng.observe_many(state, xs, ys, ts)
        eng.read(state, jnp.zeros((2, t.dim), jnp.float32))
        return state

    state = lifecycle(eng.init_state())
    first = eng.program_counts()
    events_first = compile_events[0]
    lifecycle(state)  # identical shapes: must add ZERO compilations
    second = eng.program_counts()
    events_second = compile_events[0] - events_first

    vs = []
    for key, budget in t.retrace_budget.items():
        if first[key] > budget:
            vs.append({"kind": "retrace-budget", "op": key,
                       "line": f"{key}: {first[key]} compiled "
                               f"shape-buckets > budget {budget}"})
        if second[key] != first[key]:
            vs.append({"kind": "steady-state-retrace", "op": key,
                       "line": f"{key}: repeat lifecycle recompiled "
                               f"({first[key]} -> {second[key]})"})
    info = {"first_pass": first, "second_pass": second,
            "budget": t.retrace_budget,
            "monitoring_compile_events": {
                "first_pass": events_first, "second_pass": events_second}}
    return _result("retrace", target, "fail" if vs else "pass", vs, info)


def check_source_lint(src_root: str) -> dict:
    vs = [v.as_dict() for v in lint_m.lint_tree(src_root)]
    return {"check": "source-lint", "target": "src",
            "status": "fail" if vs else "pass", "violations": vs,
            "info": {"rules": list(lint_m.RULE_NAMES),
                     "root": src_root}}


# ---------------------------------------------------------------------------
# the audited matrix
# ---------------------------------------------------------------------------


def engine_matrix(max_shards: int, quick: bool = False) -> list:
    """Engine targets: family x mode x layout x shards."""
    targets = []
    shard_grid = (1,) if max_shards < 8 else (1, 8)
    from repro.serving.families import ENGINES

    for family in ENGINES:
        for mode in ("sliding", "grow"):
            for layout in ("ring", "compact"):
                for shards in shard_grid:
                    if quick and (mode, layout) == ("grow", "compact"):
                        continue
                    if quick and shards > 1:
                        continue
                    t = AuditTarget(
                        name=f"{family}-{mode}-{layout}-s{shards}",
                        kind="engine", family=family, mode=mode,
                        layout=layout, shards=shards)
                    if mode == "sliding" and layout == "compact":
                        t.dense_waiver = (
                            "compact positional layout IS the O(cap^2) "
                            "compaction baseline (PR 5 oracle)")
                        t.copy_waiver = t.dense_waiver
                    targets.append(t)
    return targets


def measure_matrix(quick: bool = False) -> list:
    names = ("knn", "lssvm", "bootstrap") if quick else MEASURES
    return [AuditTarget(name=f"measure-{m}", kind="measure", measure=m,
                        donate=False)
            for m in names]


def run_audit(max_shards: int = 8, checks=None, quick: bool = False,
              src_root: str | None = None) -> dict:
    """Run the checker suite over the matrix; returns the JSON report."""
    import jax

    t0 = time.time()
    max_shards = min(max_shards, jax.device_count())
    if src_root is None:
        src_root = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))))
    targets = engine_matrix(max_shards, quick) + measure_matrix(quick)
    selected = set(checks) if checks else set(CHECKERS) | {"source-lint"}

    results = []
    if "source-lint" in selected:
        results.append(check_source_lint(src_root))
    for t in targets:
        if t.kind == "measure" and t.measure == "bootstrap":
            # host-side numpy measure: no jitted artifact to audit; its
            # keyed-draw invariant is covered by source-lint
            for name in CHECKERS:
                if name in selected:
                    results.append(_result(
                        name, t, "skipped",
                        info={"reason": "host-side measure (keyed "
                                        "draws gated by source-lint)"}))
            continue
        art = Artifact(t)
        for name, fn in CHECKERS.items():
            if name in selected:
                results.append(fn(t, art))

    summary = {"pass": 0, "fail": 0, "waived": 0, "skipped": 0}
    for r in results:
        summary[r["status"]] += 1
    summary["trip_fallbacks"] = sum(
        r["info"].get("trip_fallbacks", 0) for r in results)

    from repro.kernels import ops as ops_m
    report = {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "route": ops_m.active_route(),
        "matrix": {"engine_targets": sum(
                       1 for t in targets if t.kind == "engine"),
                   "measure_targets": sum(
                       1 for t in targets if t.kind == "measure"),
                   "max_shards": max_shards, "quick": quick},
        "targets": [t.describe() for t in targets],
        "checks": results,
        "summary": summary,
        "elapsed_s": round(time.time() - t0, 3),
        "ok": summary["fail"] == 0,
    }
    return report


def format_summary(report: dict) -> str:
    s = report["summary"]
    lines = [f"audit: {s['pass']} pass, {s['fail']} fail, "
             f"{s['waived']} waived, {s['skipped']} skipped "
             f"({report['matrix']['engine_targets']} engine + "
             f"{report['matrix']['measure_targets']} measure targets, "
             f"max_shards={report['matrix']['max_shards']}, "
             f"{report['elapsed_s']:.1f}s)"]
    if s.get("trip_fallbacks"):
        lines.append(f"  warning: {s['trip_fallbacks']} while op(s) "
                     f"missing known_trip_count metadata (heuristic "
                     f"trip counts)")
    for r in report["checks"]:
        if r["status"] != "fail":
            continue
        lines.append(f"  FAIL {r['check']} @ {r['target']}")
        for v in r["violations"][:4]:
            lines.append(f"    {v.get('line', v)}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _maybe_reexec(args, argv) -> None:
    """Re-exec with 8 virtual CPU devices so sharded targets compile.

    Only when: sharded targets requested, jax not yet imported, no
    device-count flag present, and the platform is (defaulting to) CPU —
    never override a real accelerator topology."""
    if args.no_reexec or args.max_shards <= 1:
        return
    if _REEXEC_SENTINEL in os.environ or "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    if os.environ.get("JAX_PLATFORMS", "cpu") != "cpu":
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count="
                f"{args.max_shards}").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ[_REEXEC_SENTINEL] = "1"
    os.execv(sys.executable,
             [sys.executable, "-m", "repro.analysis.audit"] + list(argv))


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.audit",
        description="static invariant audit over the compiled engine "
                    "matrix (see module docstring)")
    ap.add_argument("--out", default="audit_report.json",
                    help="JSON report path")
    ap.add_argument("--max-shards", type=int, default=8,
                    help="audit sharded targets up to this shard count "
                         "(clamped to jax.device_count())")
    ap.add_argument("--quick", action="store_true",
                    help="reduced matrix (CI smoke / unit tests)")
    ap.add_argument("--checks", default="",
                    help="comma-separated checker subset "
                         f"(default: all of {sorted(CHECKERS) if CHECKERS else ''} + source-lint)")
    ap.add_argument("--no-reexec", action="store_true",
                    help="never re-exec for virtual devices; sharded "
                         "targets are clamped to the devices present")
    ap.add_argument("--print", dest="print_json", action="store_true",
                    help="dump the full JSON report to stdout")
    args = ap.parse_args(argv)

    _maybe_reexec(args, argv)

    checks = [c for c in args.checks.split(",") if c] or None
    report = run_audit(max_shards=args.max_shards, checks=checks,
                       quick=args.quick)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(format_summary(report))
    print(f"report -> {args.out}")
    if args.print_json:
        print(json.dumps(report, indent=1, sort_keys=True))
    return 0 if report["ok"] else 1


__all__ = ["AuditTarget", "Artifact", "CHECKERS", "MEASURES",
           "engine_matrix", "measure_matrix", "run_audit",
           "dense_tick_violations", "collective_violations",
           "alias_violations", "format_summary", "main"]


if __name__ == "__main__":
    raise SystemExit(main())
