"""Compile-only checks for a TPU v5e chip that is described, not attached.

Each conformal-measure and serving kernel (``stream_update``,
``cp_update``, ``interval_sweep``, ``pairwise_dist``, ``kde_score``),
and one whole ``observe_many`` chunk of each serving engine, is lowered
and compiled for one chip of a ``v5e:2x2`` topology at the paper's
Section 7.1 widths (capacity 1024,
30 features, k 15). The TPU compiler refuses here what interpret mode
cannot see (integer-only ops, tiling, memory), so these run on every
change without a chip. Each compiled program must hold the kernel as a
``tpu_custom_call``.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

CAP, DIM, K, M = 1024, 30, 15, 8
TENANTS, CHUNK = 256, 16


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_route(monkeypatch):
    """Send ``kernels.ops`` down its TPU branch while tracing, with no
    trace cached from (or left for) the CPU route."""
    from repro.kernels import ops
    jax.clear_caches()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    yield
    jax.clear_caches()


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args, **kw) -> str:
    text = fn.lower(*args, **kw).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("mode", ["class", "reg"])
@pytest.mark.parametrize("ring", [False, True], ids=["linear", "ring"])
def test_stream_update_compiles(one_chip, mode, ring):
    from repro.kernels.stream_update import stream_update

    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)
    y = s((CAP,), jnp.int32 if mode == "class" else jnp.float32)
    y_new = s((), jnp.int32 if mode == "class" else jnp.float32)
    ring_kw = (dict(head=s((), jnp.int32), wrap=s((), jnp.int32))
               if ring else {})
    _compiled_text(stream_update, s((CAP, DIM)), y, s((CAP, K)),
                   s((CAP, K)), s((DIM,)), y_new, s((), jnp.int32),
                   mode=mode, **ring_kw)


def test_cp_update_compiles(one_chip):
    from repro.kernels.cp_update import cp_knn_counts

    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)
    _compiled_text(cp_knn_counts, s((CAP, DIM)), s((CAP,), jnp.int32),
                   s((CAP,)), s((CAP,)), s((M, DIM)), s((M, 2)),
                   n_labels=2)


def test_interval_sweep_compiles(one_chip):
    from repro.kernels.interval_sweep import interval_sweep

    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)
    _compiled_text(interval_sweep, s((CAP, DIM)), s((CAP,)), s((CAP,)),
                   s((CAP,)), s((CAP,), jnp.bool_), s((M, DIM)), s((M,)),
                   k=K)


@pytest.mark.parametrize("m", [M, CAP])
def test_pairwise_dist_compiles(one_chip, m):
    from repro.kernels.pairwise_dist import pairwise_sq_dists

    _compiled_text(pairwise_sq_dists, _spec(one_chip, (m, DIM)),
                   _spec(one_chip, (CAP, DIM)))


def test_kde_score_compiles(one_chip):
    from repro.kernels.kde_score import kde_rowsums

    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)
    _compiled_text(kde_rowsums, s((M, DIM)), s((CAP, DIM)),
                   s((M,), jnp.int32), s((CAP,), jnp.int32), h=1.0)


def _compile_chunk(one_chip, family, cap, tenants=TENANTS, chunk=CHUNK):
    """One served ``observe_many`` chunk of ``family``'s engine at
    ``tenants`` x window ``cap``, compiled for one v5e chip; with its
    state's shapes."""
    if family == "classification":
        from repro.serving import ServingEngine as Engine
        extra, ydt = dict(n_labels=2), jnp.int32
    else:
        from repro.regression import RegressionServingEngine as Engine
        extra, ydt = {}, jnp.float32
    eng = Engine(n_sessions=tenants, capacity=cap, dim=DIM, k=K,
                 window=cap, instrument=True, **extra)
    state = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(eng.init_state))
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)
    compiled = eng._step_many.lower(
        state, s((chunk, tenants, DIM)), s((chunk, tenants), ydt),
        s((chunk, tenants)), s((tenants,), jnp.int32),
        s((chunk, tenants), jnp.bool_)).compile()
    return compiled, state


def _state_bytes(state) -> int:
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(state))


@pytest.mark.parametrize("family", ["classification", "regression"])
def test_engine_observe_chunk_compiles(one_chip, tpu_route, family):
    """One served ``observe_many`` chunk at 256 tenants x window 1024:
    the observe kernel is inside, and the donated state aliases."""
    compiled, state = _compile_chunk(one_chip, family, CAP)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    state_bytes = _state_bytes(state)
    assert mem.alias_size_in_bytes >= state_bytes  # donation holds
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 * 2 ** 30)  # fits one v5e chip's HBM


@pytest.mark.parametrize("family,cap", [("classification", 1024),
                                        ("regression", 512),
                                        ("classification", 200)])
def test_engine_chunk_keeps_distance_block_row_major(one_chip, tpu_route,
                                                     family, cap):
    """The compiled chunk's ticks never re-lay the (S, cap, cap) distance
    block: no copy or transpose whose result is the whole block inside
    the scanned body, and no column-major layout of it anywhere (the
    column insert is the ``dist_insert`` kernel, in place). At the
    benchmark cells' sizes the chunk copies the block nowhere. Where 128
    does not divide cap, the chip's default layout of the argument is
    not row-major ({0,2,1} at 200), so the chunk converts it once on
    entry and once on exit, and no more."""
    compiled, _ = _compile_chunk(one_chip, family, cap)
    text = compiled.as_text()
    block = re.escape(f"f32[{TENANTS},{cap},{cap}]")
    relaid = {"entry": [], "body": []}
    part = None
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            part = "entry"
        elif line.startswith("%"):  # any other computation
            part = "body"
        m = re.search(rf"%\S+ = {block}\{{[^}}]*\}} (?:copy|transpose)\(",
                      line)
        if m:
            relaid[part].append(m.group(0))
    assert not relaid["body"], relaid
    assert len(relaid["entry"]) == (0 if cap % 128 == 0 else 2), relaid
    assert not re.search(rf"{block}\{{1,2,0", text)
    assert "dist_insert" in text


def test_regression_tick_gathers_no_slot_vector(one_chip, tpu_route):
    """The regression tick never permutes a (window,) vector into arrival
    order: the compiled chunk at 256 tenants x window 512 holds no gather
    with slice sizes all 1 over tenants x window indices (with unit
    slices, a gather's result has one element per index). The own list
    and the backfill pick order by arrival id in slot space; gathers of
    a scalar or a whole row per tenant remain."""
    cap = 512
    compiled, _ = _compile_chunk(one_chip, "regression", cap)
    gathers = re.findall(r"= [a-z]+\d+\[([\d,]*)\]\S* gather\(.*?"
                         r"slice_sizes=\{([\d,]*)\}", compiled.as_text())
    assert gathers  # the pattern still reads the compiled text
    per_element = [
        (shape, sizes) for shape, sizes in gathers
        if set(sizes.split(",")) == {"1"}
        and np.prod([int(d) for d in shape.split(",") if d]) == TENANTS * cap]
    assert not per_element, per_element


def _lane_padded(text: str, tenants: int, cap: int) -> list[str]:
    """Buffers of a (tenants, cap, m) array with m of 1, 15, 30 or 128 in
    the row-major layout, whose minor m the chip pads to 128 lanes (or
    that are such a padding). Values inside a fusion are never stored,
    so the fused computations' own instructions are skipped."""
    fused = set(re.findall(r"fusion\(.*calls=(%[\w.-]+)", text))
    pat = re.compile(rf"%\S+ = [fs]32\[{tenants},{cap},(1|15|30|128)\]"
                     r"\{2,1,0")
    out, skip = [], False
    for line in text.splitlines():
        if not line.startswith(" "):  # a computation's header
            skip = line.split(" ")[0] in fused
        elif not skip:
            out += [m.group(0) for m in pat.finditer(line)]
    return out


def test_many_tenant_chunk_fits_one_chip(one_chip, tpu_route):
    """The tick of 16,384 tenants x window 256 (one chip's share of the
    many-tenant deployment), one 4-tick chunk: it compiles for one v5e
    chip with temporaries no larger than the state it carries, and no
    per-slot (tenants, window, m) array is padded to 128 lanes: the
    kernel reads features and lists window-minor, in the state's own
    layout, and the per-slot vectors as (tenants, window) rows."""
    tenants, cap = 16384, 256
    compiled, state = _compile_chunk(one_chip, "classification", cap,
                                     tenants=tenants, chunk=4)
    text = compiled.as_text()
    assert "stream_update" in text and "dist_insert" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= _state_bytes(state)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30
    assert not _lane_padded(text, tenants, cap)


@pytest.mark.parametrize("family,cap,most", [
    ("classification", 1024, 1.08e9),  # the class cell's chunk before
    ("regression", 512, 0.746e9),      # the reg cell's chunk before
])
def test_engine_chunk_temporaries_bounded(one_chip, tpu_route, family, cap,
                                          most):
    """At the benchmark cells' sizes the chunk's temporaries stay at or
    under what they were with the kernel reading lane-padded copies of
    X and the lists, and none of those copies is left."""
    compiled, _ = _compile_chunk(one_chip, family, cap)
    assert compiled.memory_analysis().temp_size_in_bytes <= most
    assert not _lane_padded(compiled.as_text(), TENANTS, cap)


@pytest.mark.parametrize("family,cap", [("classification", 1024),
                                        ("regression", 512)])
def test_stream_update_moves_its_bytes_from_hbm(one_chip, tpu_route, family,
                                                cap):
    """The kernel's operands and k-best lists live in HBM (no ``S(1)``,
    the chip's VMEM) at the cells' sizes: staged in VMEM by XLA ahead of
    the call, a small window's features and lists would be read at VMEM
    speed, and the kernel's time would leave out the HBM traffic its
    roofline share counts. The emitted distance row stays in VMEM, where
    the tick's later passes read it."""
    compiled, _ = _compile_chunk(one_chip, family, cap)
    text = compiled.as_text()
    calls = re.findall(r"%stream_update\.\d+ = (\(.*?\)) custom-call\(([^)]*)\)",
                       text)
    assert calls
    for results, operands in calls:
        row, *lists = re.findall(r"f32\[[^\]]*\]\{[^}]*\}", results)
        assert row.startswith(f"f32[{TENANTS},{cap}]") and "S(1)" in row
        assert lists and not any("S(1)" in a for a in lists), results
        for name in re.findall(r"%[\w.-]+", operands):
            define = re.search(rf"{re.escape(name)} = (\S+)", text)
            assert "S(1)" not in define.group(1), define.group(0)
