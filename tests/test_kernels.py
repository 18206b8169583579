"""Per-kernel allclose sweeps: Pallas kernel (interpret=True on CPU) vs the
pure-jnp oracle in kernels/ref.py, across shapes and dtypes.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.cp_update import cp_knn_counts as cp_pallas
from repro.kernels.dist_insert import dist_insert as di_pallas
from repro.kernels.interval_sweep import interval_sweep as iv_pallas
from repro.kernels.kde_score import kde_rowsums as kde_pallas
from repro.kernels.pairwise_dist import pairwise_sq_dists
from repro.kernels.flash_attention import flash_attention as fa_pallas
from repro.kernels.stream_update import stream_update as su_pallas


@pytest.mark.parametrize("m,n,p", [(8, 8, 4), (65, 33, 7), (128, 256, 30),
                                   (257, 130, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_dist_sweep(m, n, p, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(m * n))
    A = jax.random.normal(k1, (m, p), dtype)
    B = jax.random.normal(k2, (n, p), dtype)
    got = pairwise_sq_dists(A, B, block_m=64, block_n=64, interpret=True)
    want = ref.sq_dists(A.astype(jnp.float32), B.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("m,n", [(16, 16), (65, 128), (130, 70)])
@pytest.mark.parametrize("exclude_diag", [False, True])
def test_kde_rowsums_sweep(m, n, exclude_diag):
    if exclude_diag and m != n:
        pytest.skip("diag only for square")
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(m + n), 3)
    A = jax.random.normal(k1, (m, 6), jnp.float32)
    B = A if exclude_diag else jax.random.normal(k2, (n, 6), jnp.float32)
    yA = jax.random.randint(k3, (m,), 0, 3, jnp.int32)
    yB = yA if exclude_diag else jax.random.randint(
        jax.random.PRNGKey(9), (n,), 0, 3, jnp.int32)
    got = kde_pallas(A, B, yA, yB, h=1.3, exclude_diag=exclude_diag,
                     interpret=True)
    want = ref.kde_rowsums(A, B, yA, yB, 1.3, exclude_diag)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("n,m,l", [(64, 4, 2), (130, 7, 3)])
def test_cp_knn_counts_sweep(n, m, l):
    ks = jax.random.split(jax.random.PRNGKey(n), 6)
    X = jax.random.normal(ks[0], (n, 5), jnp.float32)
    y = jax.random.randint(ks[1], (n,), 0, l, jnp.int32)
    Xt = jax.random.normal(ks[2], (m, 5), jnp.float32)
    sum_same = jax.random.uniform(ks[3], (n,), jnp.float32, 1.0, 4.0)
    kth = jax.random.uniform(ks[4], (n,), jnp.float32, 0.5, 2.0)
    alpha = jax.random.uniform(ks[5], (m, l), jnp.float32, 1.0, 3.0)
    got = cp_pallas(X, y, sum_same, kth, Xt, alpha, n_labels=l,
                    interpret=True)
    want = ref.cp_knn_counts(X, y, sum_same, kth, Xt, alpha)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,m,k", [(64, 4, 5), (130, 7, 1), (200, 33, 7)])
@pytest.mark.parametrize("dead_tail", [0, 17])
def test_interval_sweep_matches_ref(n, m, k, dead_tail):
    """Fused distance + (a_i, b_i) update + critical points vs oracle.

    Finite endpoints agree to f32 tolerance; infinity/empty sentinels
    (including the ``live`` capacity padding) agree exactly.
    """
    ks = jax.random.split(jax.random.PRNGKey(n + k), 6)
    X = jax.random.normal(ks[0], (n, 6), jnp.float32)
    a_prime = jax.random.normal(ks[1], (n,), jnp.float32)
    kth_dist = jax.random.uniform(ks[2], (n,), jnp.float32, 0.5, 4.0)
    kth_label = jax.random.normal(ks[3], (n,), jnp.float32)
    Xt = jax.random.normal(ks[4], (m, 6), jnp.float32)
    a_test = jax.random.normal(ks[5], (m,), jnp.float32)
    live = (jnp.arange(n) < n - dead_tail)
    got_lo, got_hi = iv_pallas(X, a_prime, kth_dist, kth_label, live, Xt,
                               a_test, k=k, block_m=64, block_n=64,
                               interpret=True)
    want_lo, want_hi = ref.reg_interval_endpoints(
        X, a_prime, kth_dist, kth_label, live, Xt, a_test, k)
    for got, want in [(got_lo, want_lo), (got_hi, want_hi)]:
        got, want = np.asarray(got), np.asarray(want)
        assert got.shape == (m, n)
        np.testing.assert_array_equal(np.isfinite(got), np.isfinite(want))
        f = np.isfinite(want)
        np.testing.assert_array_equal(got[~f], want[~f])  # +-inf pattern
        np.testing.assert_allclose(got[f], want[f], atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("cap,p,k,n", [(64, 5, 5, 40), (70, 6, 1, 70),
                                       (300, 16, 7, 123), (32, 3, 4, 0)])
@pytest.mark.parametrize("mode", ["class", "reg"])
def test_stream_update_matches_ref(cap, p, k, n, mode):
    """Fused distance row + gated ordered k-best merge vs oracle.

    Covers non-tile-aligned capacities, k=1, an empty window (n=0, all
    rows inert) and both gate modes."""
    ks = jax.random.split(jax.random.PRNGKey(cap + k), 6)
    X = jax.random.normal(ks[0], (cap, p), jnp.float32)
    y = jax.random.randint(ks[1], (cap,), 0, 3, jnp.int32)
    nbr_d = jnp.sort(
        jax.random.uniform(ks[2], (cap, k), jnp.float32, 0.1, 3.0), axis=1)
    nbr_y = jax.random.normal(ks[3], (cap, k), jnp.float32)
    x_new = jax.random.normal(ks[4], (p,), jnp.float32)
    if mode == "class":
        y_in, y_new = y, jnp.int32(1)
    else:
        y_in, y_new = jax.random.normal(ks[5], (cap,), jnp.float32), \
            jnp.float32(0.25)
    nn = jnp.int32(n)
    got = su_pallas(X, y_in, nbr_d, nbr_y, x_new, y_new, nn, mode=mode,
                    block_n=64, interpret=True)
    want = ref.stream_update(X, y_in, nbr_d, nbr_y, x_new, y_new, nn,
                             mode=mode)
    for g, w, name in zip(got, want, ["d_row", "nbr_d", "nbr_y"]):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape, name
        big = w >= 1e29
        np.testing.assert_array_equal(g[big], w[big], err_msg=name)
        np.testing.assert_allclose(g[~big], w[~big], atol=1e-5, rtol=1e-5,
                                   err_msg=name)
    # the sortless CPU production path is bit-identical to the oracle
    fast = ref.stream_update_fast(X, y_in, nbr_d, nbr_y, x_new, y_new, nn,
                                  mode=mode)
    for f, w, name in zip(fast, want, ["d_row", "nbr_d", "nbr_y"]):
        np.testing.assert_array_equal(np.asarray(f), np.asarray(w),
                                      err_msg="fast " + name)


@pytest.mark.parametrize("cap,k,n,head,wrap,block_n", [
    (64, 5, 40, 30, 64, 32),   # wrapped over the full capacity
    (64, 3, 20, 15, 24, 32),   # window-confined ring: slots >= wrap inert
    (70, 4, 24, 23, 24, 32),   # full confined ring, head mid-block
    (32, 2, 0, 7, 16, 32),     # empty ring, nonzero head
    # the paper's widths (k 15) at caps 21, 200, 256 and 1024, in the
    # lane blocks the chip takes (the whole window)
    (21, 15, 21, 13, 21, None),
    (200, 15, 200, 151, 200, None),
    (256, 15, 256, 255, 256, None),
    (1024, 15, 1000, 900, 1024, None),
])
@pytest.mark.parametrize("mode", ["class", "reg"])
def test_stream_update_ring_mode_matches_ref(cap, k, n, head, wrap, block_n,
                                             mode):
    """Ring-slot liveness (head/wrap) in the fused kernel vs the oracle:
    the live window is slots (head + i) % wrap, everything else inert."""
    p = 6
    ks = jax.random.split(jax.random.PRNGKey(3 * cap + head), 6)
    X = jax.random.normal(ks[0], (cap, p), jnp.float32)
    y = jax.random.randint(ks[1], (cap,), 0, 3, jnp.int32)
    nbr_d = jnp.sort(
        jax.random.uniform(ks[2], (cap, k), jnp.float32, 0.1, 3.0), axis=1)
    nbr_y = jax.random.normal(ks[3], (cap, k), jnp.float32)
    x_new = jax.random.normal(ks[4], (p,), jnp.float32)
    if mode == "class":
        y_in, y_new = y, jnp.int32(1)
    else:
        y_in, y_new = jax.random.normal(ks[5], (cap,), jnp.float32), \
            jnp.float32(0.25)
    args = (X, y_in, nbr_d, nbr_y, x_new, y_new, jnp.int32(n))
    kw = dict(mode=mode, head=jnp.int32(head), wrap=jnp.int32(wrap))
    got = su_pallas(*args, block_n=block_n, interpret=True, **kw)
    want = ref.stream_update(*args, **kw)
    fast = ref.stream_update_fast(*args, **kw)
    for g, f, w, name in zip(got, fast, want, ["d_row", "nbr_d", "nbr_y"]):
        g, f, w = np.asarray(g), np.asarray(f), np.asarray(w)
        np.testing.assert_array_equal(f, w, err_msg="fast " + name)
        big = w >= 1e29
        np.testing.assert_array_equal(g[big], w[big], err_msg=name)
        np.testing.assert_allclose(g[~big], w[~big], atol=1e-5, rtol=1e-5,
                                   err_msg=name)
    # liveness itself: exactly n slots carry finite distances
    assert int(np.sum(np.asarray(want[0]) < 1e29)) == n


@pytest.mark.parametrize("cap,block_n", [(21, None), (256, None),
                                         (200, 64)])
@pytest.mark.parametrize("mode", ["class", "reg"])
def test_stream_update_batched_matches_ref(cap, block_n, mode):
    """Under ``vmap`` over tenants the kernel runs once on the stacked
    tenants (window on the lanes, tenants on the sublanes, a tenant
    block overhanging the end): each tenant's outputs match the oracle
    run on that tenant alone, rings wrapped at their own heads."""
    B, p, k = 19, 30, 15
    ks = jax.random.split(jax.random.PRNGKey(cap + B), 8)
    X = jax.random.normal(ks[0], (B, cap, p), jnp.float32)
    nbr_d = jnp.sort(jax.random.uniform(ks[2], (B, cap, k), jnp.float32,
                                        0.1, 9.0), axis=-1)
    nbr_y = jax.random.normal(ks[3], (B, cap, k), jnp.float32)
    x_new = jax.random.normal(ks[4], (B, p), jnp.float32)
    if mode == "class":
        y = jax.random.randint(ks[1], (B, cap), 0, 2, jnp.int32)
        y_new = jax.random.randint(ks[5], (B,), 0, 2, jnp.int32)
    else:
        y = jax.random.normal(ks[1], (B, cap), jnp.float32)
        y_new = jax.random.normal(ks[5], (B,), jnp.float32)
    n = jax.random.randint(ks[6], (B,), 0, cap + 1, jnp.int32)
    head = jax.random.randint(ks[7], (B,), 0, cap, jnp.int32)
    wrap = jnp.full((B,), cap, jnp.int32)
    args = (X, y, nbr_d, nbr_y, x_new, y_new, n)
    got = jax.vmap(lambda *a: su_pallas(
        *a[:7], mode=mode, block_n=block_n, interpret=True, head=a[7],
        wrap=a[8]))(*args, head, wrap)
    for b in range(B):
        want = ref.stream_update(*(a[b] for a in args), mode=mode,
                                 head=head[b], wrap=wrap[b])
        for g, w, name in zip(got, want, ["d_row", "nbr_d", "nbr_y"]):
            g, w = np.asarray(g[b]), np.asarray(w)
            big = w >= 1e29
            np.testing.assert_array_equal(g[big], w[big], err_msg=name)
            np.testing.assert_allclose(g[~big], w[~big], atol=1e-5,
                                       rtol=1e-5, err_msg=name)


@pytest.mark.parametrize("mode", ["class", "reg"])
def test_stream_update_tie_rule_exact(mode):
    """Distance ties: the kernel's branch-free insert-after-equals must
    reproduce the oracle's stable-sort tie rule bit-for-bit.

    One-hot rows at distance exactly 1.0 from the zero query, neighbour
    lists stuffed with exact 1.0 entries — every value in play is exact
    in f32, so the comparison is equality, not allclose."""
    cap, p, k, n = 16, 8, 3, 12
    X = jnp.eye(cap, p, dtype=jnp.float32)  # d(x_new=0, X_i) == 1.0 exactly
    x_new = jnp.zeros((p,), jnp.float32)
    # lists already containing the candidate distance (and BIG padding)
    base = jnp.asarray([0.5, 1.0, 1.0], jnp.float32)
    nbr_d = jnp.tile(base, (cap, 1))
    nbr_d = nbr_d.at[5].set(jnp.asarray([1.0, 1.0, 2.0], jnp.float32))
    nbr_d = nbr_d.at[6].set(jnp.asarray([0.25, 0.5, 1e30], jnp.float32))
    nbr_y = jnp.arange(cap * k, dtype=jnp.float32).reshape(cap, k)
    if mode == "class":
        y, y_new = jnp.zeros((cap,), jnp.int32), jnp.int32(0)
    else:
        y, y_new = jnp.linspace(-1.0, 1.0, cap).astype(jnp.float32), \
            jnp.float32(9.0)
    got = su_pallas(X, y, nbr_d, nbr_y, x_new, y_new, jnp.int32(n),
                    mode=mode, block_n=8, interpret=True)
    want = ref.stream_update(X, y, nbr_d, nbr_y, x_new, y_new,
                             jnp.int32(n), mode=mode)
    fast = ref.stream_update_fast(X, y, nbr_d, nbr_y, x_new, y_new,
                                  jnp.int32(n), mode=mode)
    for g, f, w, name in zip(got, fast, want, ["d_row", "nbr_d", "nbr_y"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
        np.testing.assert_array_equal(np.asarray(f), np.asarray(w),
                                      err_msg="fast " + name)


@pytest.mark.parametrize("cfg", [
    dict(B=1, Sq=64, Skv=64, H=4, Hkv=4, D=16, causal=True, window=None),
    dict(B=2, Sq=63, Skv=63, H=4, Hkv=1, D=32, causal=True, window=None),
    dict(B=1, Sq=128, Skv=128, H=2, Hkv=2, D=16, causal=True, window=17),
    dict(B=1, Sq=64, Skv=64, H=4, Hkv=2, D=16, causal=False, window=None),
    dict(B=1, Sq=16, Skv=80, H=2, Hkv=1, D=16, causal=True, window=None),
])
@pytest.mark.parametrize("softcap", [None, 30.0])
def test_flash_attention_sweep(cfg, softcap):
    ks = jax.random.split(jax.random.PRNGKey(cfg["Sq"]), 3)
    q = jax.random.normal(ks[0], (cfg["B"], cfg["Sq"], cfg["H"], cfg["D"]),
                          jnp.float32)
    k = jax.random.normal(ks[1], (cfg["B"], cfg["Skv"], cfg["Hkv"],
                                  cfg["D"]), jnp.float32)
    v = jax.random.normal(ks[2], k.shape, jnp.float32)
    got = fa_pallas(q, k, v, causal=cfg["causal"], window=cfg["window"],
                    softcap=softcap, block_q=32, block_k=32, interpret=True)
    want = ref.flash_attention(q, k, v, causal=cfg["causal"],
                               window=cfg["window"], softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("Sq,Skv,window", [(96, 96, None), (100, 100, 13),
                                           (64, 160, None)])
def test_chunked_attention_matches_dense(Sq, Skv, window):
    ks = jax.random.split(jax.random.PRNGKey(Sq + Skv), 3)
    q = jax.random.normal(ks[0], (2, Sq, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, Skv, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], k.shape, jnp.float32)
    got = ref.chunked_attention(q, k, v, causal=True, window=window,
                                block_q=32, block_k=32)
    want = ref.flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("cap,w,idx", [
    (64, 64, (0, 63, 17)),        # one strip: the whole row
    (64, 40, (0, 39, 5)),         # window block inside the capacity
    (1024, 1024, (3, 1020, 128)),  # first and last lane block, a boundary
    (1024, 600, (127, 599, 256)),  # w < cap across lane blocks
    (200, 200, (0, 199, 128)),    # 128 does not divide cap: the last
    (200, 150, (127, 149, 130)),  # strip overhangs the row
    (215, 215, (214, 3, 200)),    # ... and rows not a multiple of 8
])
def test_dist_insert_matches_ref(cap, w, idx):
    """The lane-strip column insert is bit-equal to the two scatters, on
    a stack of random symmetric D under vmap (one kernel call) and on one
    D alone; a lane whose row is D's own row (an inactive tenant) keeps
    D bitwise unchanged."""
    B = len(idx)
    A = jax.random.uniform(jax.random.PRNGKey(cap + w), (B, cap, cap),
                           jnp.float32)
    D = A + jnp.swapaxes(A, 1, 2)  # symmetric, bitwise
    idx = jnp.asarray(idx, jnp.int32)
    row = jax.random.uniform(jax.random.PRNGKey(w), (B, w), jnp.float32)
    row = row.at[B - 1].set(D[B - 1, idx[B - 1], :w])  # inactive lane
    want = jax.vmap(ref.dist_insert)(D, row, idx)  # the two scatters
    got = jax.vmap(lambda d, r, i: di_pallas(d, r, i, interpret=True))(
        D, row, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got[B - 1]),
                                  np.asarray(D[B - 1]))
    one = di_pallas(D[0], row[0], idx[0], interpret=True)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(want[0]))


def test_ops_dispatch_interpret(monkeypatch):
    """REPRO_PALLAS_INTERPRET=1 exercises kernel bodies via ops.py."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    from repro.kernels import ops
    A = jax.random.normal(jax.random.PRNGKey(0), (33, 7), jnp.float32)
    got = ops.sq_dists(A, A)
    want = ref.sq_dists(A, A)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
