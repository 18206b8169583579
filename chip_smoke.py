"""Smoke run of the multi-tenant CP serving path on a TPU chip.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the tenant-sharded tick on four

On one chip, three phases run through the launcher's own serving code
(``repro.launch.serve``, the code behind ``python -m repro.launch.serve
--sessions N``). They use the paper's Section 7.1 widths (30 features,
k 15, 2 labels) at a deployment's size: 256 tenants, each with a
sliding window of 1024.

* classification: ``ServingEngine`` serves window + 64 ticks in
  ``observe_many`` chunks of 16, so every tenant's ring fills and wraps;
* snapshot: a ``SessionStore`` save and restore of that final state,
  checked leaf for leaf;
* regression: ``RegressionServingEngine`` at the same widths, then one
  ``intervals`` read (the ``interval_sweep`` kernel).

Each serving phase prints its first served chunk's seconds (compile
included), its steady-state session-steps/s and the device's peak
memory so far; then the served engine's chunk is lowered and compiled
once more and must hold the Pallas kernel (``tpu_custom_call``). For a
few tenants, the served p-values and the final k-NN state are checked
against the repository's from-scratch oracle (``core.measures.knn.fit``,
``core.regression.fit``), refitted on the live window in float64 on the
host CPU. The tolerances below say what may differ and why. The sizes
are the constants below; a smaller rehearsal passes its own namespace
(see ``_sizes``) to the phase functions.

With ``--chips 4`` only the sharded path runs. The classification engine
is sharded over a ``("tenants",)`` mesh of the four chips at four times
the tenants, so each chip holds as many as in the one-chip run. It is
then compared bit for bit with the same traffic served on one chip, and
its snapshot must restore onto four devices.

The last line of standard output is one JSON object naming the device.
Any failed phase or check exits nonzero without it, and so does a run
that finds no TPU: nothing falls back to the CPU. All data comes from
``--seed``; nothing is read from outside this checkout.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import tempfile
import time
import traceback
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parent

# -- sizes --------------------------------------------------------------------
# The paper's Section 7.1 widths (configs/paper.py) at a deployment's size.
TENANTS = 256  # per chip
WINDOW = 1024  # = capacity
DIM = 30
K = 15
CHUNK = 16  # ticks per observe_many dispatch
TICKS = WINDOW + 64  # every ring fills, then wraps; a multiple of CHUNK

# -- oracle tolerances --------------------------------------------------------
# The chip computes in float32; the oracle refits the same window in
# float64. A distance is the square root of a sum of 30 squared
# differences: float32 rounding bounds its relative error to a few ulp,
# and DIST_RTOL leaves a wide margin over that.
DIST_RTOL = 1e-5
# A classification score is a sum of k distances, so it inherits the
# same relative bound. Regression scores are label-valued (|a_i + b_i y|);
# SCORE_ATOL is their float32 rounding margin in units of the largest
# label magnitude in the window plus one.
SCORE_RTOL = 1e-5
SCORE_ATOL = 1e-5
# P-values and neighbour sets are discrete. Where two values lie within
# the margins above, the chip and the oracle may legitimately order them
# apart, so such pairs are "ambiguous": a p-value must lie within the
# range its ambiguous comparisons allow (exact when there are none), and
# a regression row's label mean must match wherever its k-th nearest
# neighbour is not tied with the (k+1)-th. P_EPS is float32 rounding of
# the p-value ratio itself.
P_EPS = 1e-6
# Interval endpoints are roots of per-row quadratics in label units.
IV_RTOL, IV_ATOL = 1e-4, 1e-3
# miscoverage level of the served intervals
EPS = 0.1


class CheckFailed(Exception):
    pass


def _say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def _require_chip(chips: int):
    """The device JAX serves on, or exit: no TPU, no run."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"[smoke] FAIL: no TPU found (JAX runs on "
                 f"{devs[0].platform}); this script does not fall back")
    if len(devs) < chips:
        sys.exit(f"[smoke] FAIL: --chips {chips} but JAX sees "
                 f"{len(devs)} device(s)")
    from repro.kernels import ops

    route = ops.active_route()
    if not route["on_tpu"] or route["interpret"]:
        sys.exit(f"[smoke] FAIL: kernels would not run compiled on the "
                 f"chip: {route}")
    return devs[0]


def _peak_gb() -> float:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", float("nan")) / 1e9


def _check_kernel(name, run, chunk: int) -> None:
    """Lower and compile the served engine's ``observe_many`` chunk and
    fail unless the program holds a Pallas kernel."""
    t0 = time.perf_counter()
    text = run.eng.lower_tick(chunk).compile().as_text()
    has = "tpu_custom_call" in text
    _say(f"{name}: served chunk lowered + compiled again in "
         f"{time.perf_counter() - t0:.3f} s, tpu_custom_call "
         f"{'yes' if has else 'NO'}")
    _check(has, f"{name}: compiled chunk holds no Pallas kernel")


def _sizes(seed: int, chips: int) -> SimpleNamespace:
    """The run's sizes. A rehearsal at a smaller size passes its own
    namespace to the phase functions."""
    return SimpleNamespace(seed=seed, chips=chips, tenants=TENANTS,
                           window=WINDOW, dim=DIM, k=K, chunk=CHUNK,
                           ticks=TICKS)


def _serve_args(a, *, sessions, shards=1, regression=False,
                snapshot_dir=""):
    from repro.launch import serve

    argv = ["--sessions", str(sessions), "--steps", str(a.ticks),
            "--window", str(a.window), "--capacity", str(a.window),
            "--dim", str(a.dim), "--k", str(a.k), "--chunk", str(a.chunk),
            "--seed", str(a.seed), "--shards", str(shards),
            "--eps", str(EPS)]
    if regression:
        argv.append("--regression")
    if snapshot_dir:
        argv += ["--snapshot-dir", snapshot_dir]
    return serve._parser().parse_args(argv)


def _report(name, run) -> None:
    _say(f"{name}: first served chunk (compile included) "
         f"{run.warmup_s:.3f} s, {run.steps_per_s:.1f} session-steps/s, "
         f"peak device memory {_peak_gb():.3f} GB")


# -- host oracle --------------------------------------------------------------


@contextlib.contextmanager
def _host_f64():
    """float64 on the host CPU: ``kernels.ops`` sends float64 to its
    reference path on every backend, so no kernel route is involved."""
    import jax

    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        yield


def _tenant(state, s: int):
    import jax

    return jax.tree_util.tree_map(lambda a: a[s], state)


def _check_tenants(S: int) -> list[int]:
    """A few tenants: both ends of the center ramp, drifted and not."""
    return sorted({0, 1, S // 2, S - 1})


def _p_range(alphas, alpha, tau, amb_extra, tol):
    """Range of the smoothed p-value (gt + tau (eq + 1)) / (n + 1) over
    every ordering of the ambiguous comparisons: rows flagged in
    ``amb_extra`` or whose score is within ``tol`` of the candidate's."""
    import numpy as np

    n = alphas.shape[0]
    amb = amb_extra | (np.abs(alphas - alpha) <= tol)
    g0 = int(np.sum((alphas > alpha) & ~amb))
    a = int(np.sum(amb))
    return (g0 + tau) / (n + 1), (g0 + a + tau) / (n + 1), a


def _tick_windows(T: int, W: int, k: int) -> list[int]:
    """Ticks whose p-values are checked: half-full, the fill edge, the
    first evictions, and the last ticks (rings wrapped)."""
    return sorted({t for t in (W // 2, W - 1, W, W + 1, *range(T - 12, T))
                   if 2 * k < t < T})


def _window_before(t: int, W: int) -> slice:
    """The live window a tick at ``t`` is priced against (evict-then-
    observe: the last W - 1 points once the window is full)."""
    return slice(t - W + 1, t) if t >= W else slice(0, t)


def _check_p(name, s, t, served, lo, hi, a, stats) -> None:
    stats["checked"] += 1
    stats["ambiguous"] += a > 0
    ok = lo - P_EPS <= served <= hi + P_EPS
    _check(ok, f"{name}: tenant {s} tick {t} p-value {served!r} outside "
               f"the oracle's [{lo!r}, {hi!r}] ({a} ambiguous)")
    stats["exact"] += abs(served - lo) <= P_EPS and a == 0


def _rel_err(got, want, mask):
    import numpy as np

    return float(np.max(np.abs(got[mask] - want[mask])
                        / np.maximum(np.abs(want[mask]), 1.0)))


def _oracle_classification(a, run) -> None:
    """Served p-values and final k-NN state vs ``knn.fit`` on the live
    window, for a few tenants."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.measures import knn as knn_m
    from repro.serving import session as sess_m

    X, y, taus = (np.asarray(v) for v in (run.X, run.y, run.taus))
    S, T = y.shape
    W, k = a.window, a.k
    p_all = run.pvals
    _check(bool(np.all((p_all > 0) & (p_all <= 1))),
           "classification: a served p-value is outside (0, 1]")
    worst_d = 0.0
    stats = dict(checked=0, exact=0, ambiguous=0)
    for s in _check_tenants(S):
        lin = sess_m.to_linear(_tenant(run.state, s))
        n = int(lin.knn.n)
        _check(n == W, f"classification: tenant {s} holds {n} != {W}")
        Xw, yw = X[s, T - W:], y[s, T - W:]
        _check(np.array_equal(np.asarray(lin.knn.X)[:W], Xw)
               and np.array_equal(np.asarray(lin.knn.y)[:W], yw),
               f"classification: tenant {s} window rows differ from its "
               "traffic")
        best = np.asarray(lin.knn.best)[:W].astype(np.float64)
        D = np.asarray(lin.D)[:W, :W].astype(np.float64)
        with _host_f64():
            X64 = jnp.asarray(Xw, jnp.float64)
            ref = knn_m.fit(X64, jnp.asarray(yw), k=k)
            Dref = np.asarray(knn_m._dists_to_train(X64, X64))
        off = ~np.eye(W, dtype=bool)
        _check(bool(np.all(D[~off] >= 1e29)),
               f"classification: tenant {s} D diagonal is not inert")
        err = max(_rel_err(best, np.asarray(ref.best_same), best < 1e29),
                  _rel_err(D, Dref, off))
        worst_d = max(worst_d, err)
        _check(err <= DIST_RTOL,
               f"classification: tenant {s} distances off by {err:.3g} "
               f"relative (> {DIST_RTOL})")
        for t in _tick_windows(T, W, k):
            w = _window_before(t, W)
            Xa = np.concatenate([X[s, w], X[s, t][None]])
            ya = np.concatenate([y[s, w], y[s, t][None]])
            with _host_f64():
                st = knn_m.fit(jnp.asarray(Xa, jnp.float64),
                               jnp.asarray(ya), k=k)
                sc = np.asarray(jnp.sum(st.best_same, axis=1))
            lo, hi, amb = _p_range(sc[:-1], sc[-1], float(taus[s, t]),
                                   np.zeros(sc.shape[0] - 1, bool),
                                   SCORE_RTOL * abs(sc[-1]))
            _check_p("classification", s, t, float(p_all[s, t]), lo, hi,
                     amb, stats)
    _say(f"classification oracle: {stats['checked']} p-values "
         f"({stats['exact']} exact, {stats['ambiguous']} with ambiguous "
         f"ties), k-NN lists and D within {worst_d:.3g} relative")


def _oracle_regression(a, run) -> None:
    """Served p-values, final state and one interval read vs
    ``regression.fit`` on the live window, for a few tenants."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import regression as reg_m
    from repro.regression import stream as stream_m

    X, y, taus = (np.asarray(v) for v in (run.X, run.y, run.taus))
    S, T = y.shape
    W, k = a.window, a.k
    p_all = run.pvals
    _check(bool(np.all((p_all > 0) & (p_all <= 1))),
           "regression: a served p-value is outside (0, 1]")
    Xq = np.asarray(run.Xq)
    worst_d = worst_iv = 0.0
    amb_rows = 0
    stats = dict(checked=0, exact=0, ambiguous=0, unresolved=0)

    def ref_window(Xw, yw):
        """Oracle fit, its distances and each row's k-th/(k+1)-th tie."""
        with _host_f64():
            X64 = jnp.asarray(Xw, jnp.float64)
            st = reg_m.fit(X64, jnp.asarray(yw, jnp.float64), k=k)
            Dref = np.array(reg_m._dists(X64, X64))
        np.fill_diagonal(Dref, np.inf)
        near = np.sort(np.partition(Dref, k, axis=1)[:, :k + 1], axis=1)
        tie = near[:, k] - near[:, k - 1] <= 2 * DIST_RTOL * near[:, k - 1]
        return st, Dref, near[:, :k], tie

    for s in _check_tenants(S):
        lin = stream_m.to_linear(_tenant(run.state, s))
        n = int(lin.n)
        _check(n == W, f"regression: tenant {s} holds {n} != {W}")
        Xw, yw = X[s, T - W:], y[s, T - W:]
        _check(np.array_equal(np.asarray(lin.X)[:W], Xw)
               and np.array_equal(np.asarray(lin.y)[:W], yw),
               f"regression: tenant {s} window rows differ from its "
               "traffic")
        st, Dref, near, tie = ref_window(Xw, yw)
        D = np.asarray(lin.D)[:W, :W].astype(np.float64)
        nbr_d = np.asarray(lin.nbr_d)[:W].astype(np.float64)
        off = ~np.eye(W, dtype=bool)
        err = max(_rel_err(nbr_d, near, np.ones_like(near, bool)),
                  _rel_err(D, Dref, off))
        worst_d = max(worst_d, err)
        _check(err <= DIST_RTOL,
               f"regression: tenant {s} distances off by {err:.3g} "
               f"relative (> {DIST_RTOL})")
        scale = 1.0 + float(np.max(np.abs(yw)))
        a_srv = (yw.astype(np.float64)
                 - np.asarray(lin.nbr_y)[:W].astype(np.float64).sum(1) / k)
        bad = np.abs(a_srv - np.asarray(st.a_prime)) > SCORE_ATOL * scale
        amb_rows += int(np.sum(tie))
        _check(not np.any(bad & ~tie),
               f"regression: tenant {s} neighbour labels differ on "
               f"{int(np.sum(bad & ~tie))} row(s) without a distance tie")

        with _host_f64():
            iv_ref = np.asarray(reg_m.intervals_optimized(
                st, jnp.asarray(Xq, jnp.float64), k=k, epsilon=EPS))
        iv = run.intervals[s].astype(np.float64)
        _check(np.array_equal(np.isfinite(iv), np.isfinite(iv_ref)),
               f"regression: tenant {s} interval finiteness differs")
        f = np.isfinite(iv_ref)
        if f.any():
            gap = np.abs(iv[f] - iv_ref[f])
            worst_iv = max(worst_iv, float(gap.max()))
            _check(bool(np.all(gap <= IV_ATOL + IV_RTOL * np.abs(iv_ref[f]))),
                   f"regression: tenant {s} intervals {iv.tolist()} vs "
                   f"oracle {iv_ref.tolist()}")

        for t in _tick_windows(T, W, k):
            w = _window_before(t, W)
            st_t, D_t, near_t, tie_t = ref_window(X[s, w], y[s, w])
            with _host_f64():
                a_vec, b_vec, a_c = (np.asarray(v) for v in reg_m.ab_optimized(
                    st_t, jnp.asarray(X[s, t], jnp.float64), k=k))
            d_t = np.sqrt(np.sum((X[s, w].astype(np.float64)
                                  - X[s, t].astype(np.float64)) ** 2, 1))
            own = np.sort(d_t)
            if own[k] - own[k - 1] <= 2 * DIST_RTOL * own[k - 1]:
                stats["unresolved"] += 1  # the candidate's own k-NN ties
                continue
            kth = near_t[:, -1]
            enter_tie = np.abs(d_t - kth) <= DIST_RTOL * kth
            yt = float(y[s, t])
            alphas = np.abs(a_vec + b_vec * yt)
            alpha = abs(float(a_c) + yt)
            sc = 1.0 + float(np.max(np.abs(y[s, w])))
            lo, hi, amb = _p_range(alphas, alpha, float(taus[s, t]),
                                   tie_t | enter_tie, SCORE_ATOL * sc)
            _check_p("regression", s, t, float(p_all[s, t]), lo, hi, amb,
                     stats)
    _say(f"regression oracle: {stats['checked']} p-values "
         f"({stats['exact']} exact, {stats['ambiguous']} with ambiguous "
         f"ties, {stats['unresolved']} skipped on a tied candidate), "
         f"distances within {worst_d:.3g} relative, {amb_rows} tied "
         f"row(s), intervals within {worst_iv:.3g}")


# -- phases -------------------------------------------------------------------


def _phase_classification(a, tel):
    from repro.launch import serve

    run = serve._run_sessions(_serve_args(a, sessions=a.tenants), *tel())
    _report("classification", run)
    _oracle_classification(a, run)
    return run


def _phase_snapshot(a, run, tel) -> None:
    from repro.launch import serve

    with tempfile.TemporaryDirectory() as td:
        args = _serve_args(a, sessions=a.tenants, snapshot_dir=td)
        t0 = time.perf_counter()
        rc = serve._snapshot_roundtrip(args, run.state, run.eng, *tel())
        _say(f"snapshot: save + restore + compare {time.perf_counter() - t0:.3f}"
             f" s, peak device memory {_peak_gb():.3f} GB")
    _check(rc == 0, "snapshot: restore is not bit-exact")


def _phase_regression(a, tel):
    from repro.launch import serve

    run = serve._run_regression(
        _serve_args(a, sessions=a.tenants, regression=True), *tel())
    _report("regression", run)
    _oracle_regression(a, run)
    return run


def _phase_sharded(a, tel) -> None:
    """Four-chip tenant-sharded classification vs the same traffic on
    one chip, bit for bit, plus a 4-shard snapshot restore. The one-chip
    run goes first, while that chip's memory is otherwise empty: at four
    times the tenants it needs most of it."""
    import jax
    import numpy as np

    from repro.launch import serve

    S = a.tenants * a.chips
    run1 = serve._run_sessions(_serve_args(a, sessions=S), *tel())
    ref = [np.asarray(x) for x in jax.tree_util.tree_leaves(run1.state)]
    p1 = run1.pvals
    _say(f"one chip: {S} tenants, first chunk {run1.warmup_s:.3f} s, "
         f"{run1.steps_per_s:.1f} session-steps/s, peak device memory "
         f"{_peak_gb():.3f} GB")
    del run1
    gc.collect()
    with tempfile.TemporaryDirectory() as td:
        args4 = _serve_args(a, sessions=S, shards=a.chips, snapshot_dir=td)
        run4 = serve._run_sessions(args4, *tel())
        leaves = jax.tree_util.tree_leaves(run4.state)
        spans = {len(x.sharding.device_set) for x in leaves}
        same = all(np.array_equal(x, np.asarray(y), equal_nan=True)
                   for x, y in zip(ref, leaves))
        same_p = np.array_equal(p1, run4.pvals, equal_nan=True)
        _say(f"sharded: {S} tenants over {a.chips} chips, first chunk "
             f"{run4.warmup_s:.3f} s, {run4.steps_per_s:.1f} "
             f"session-steps/s; state leaves span {sorted(spans)} "
             f"device(s); state {'bit-identical' if same else 'DIFFERS'}"
             f" and p-values {'bit-identical' if same_p else 'DIFFER'} "
             f"vs one chip")
        _check(spans == {a.chips},
               f"sharded: state leaves span {spans}, not {a.chips}")
        _check(same and same_p, "sharded: 4-chip result differs from 1 chip")
        rc = serve._snapshot_roundtrip(args4, run4.state, run4.eng, *tel())
        _check(rc == 0, "sharded: the 4-shard snapshot did not restore "
                        "bit-exact onto 4 devices")


def _run(name, fn, failures):
    t0 = time.perf_counter()
    try:
        out = fn()
    except Exception as e:  # noqa: BLE001 - every phase failure is reported
        traceback.print_exc()
        _say(f"{name}: FAIL ({type(e).__name__}: {e})")
        failures.append(name)
        return None
    _say(f"{name}: pass ({time.perf_counter() - t0:.1f} s)")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tenant-sharded path and its "
                         "one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args(argv)
    a = _sizes(opts.seed, opts.chips)

    if not (ROOT / "src" / "repro").is_dir():
        sys.exit("[smoke] FAIL: the repro package is not next to this "
                 "script; run it from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    # the host oracle needs the CPU backend beside the chip
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    dev = _require_chip(a.chips)
    import jax

    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache

    _say(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())},"
         f" jax {jax.__version__}, compile cache "
         f"{enable_compile_cache() or 'off'}")

    def tel():
        return serve._telemetry(_serve_args(a, sessions=a.tenants))

    def on_chip(name, phase):
        """Run a serving phase, then check its compiled chunk."""
        def go():
            run = phase(a, tel)
            _check_kernel(name, run, a.chunk)
            return run
        return go

    failures: list[str] = []
    if a.chips > 1:
        _run("sharded", lambda: _phase_sharded(a, tel), failures)
    else:
        run = _run("classification",
                   on_chip("classification", _phase_classification),
                   failures)
        if run is None:
            failures.append("snapshot")
        else:
            _run("snapshot", lambda: _phase_snapshot(a, run, tel), failures)
        del run
        gc.collect()
        _run("regression", on_chip("regression", _phase_regression),
             failures)
    if failures:
        _say(f"FAILED: {', '.join(failures)}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
