"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


def _arr(*s, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(s), dtype=dtype)


@pytest.mark.parametrize("S,hd,H,KV,causal,dtype", [
    (128, 64, 2, 2, True, jnp.float32),
    (256, 64, 4, 2, True, jnp.float32),
    (256, 128, 2, 1, True, jnp.bfloat16),
    (128, 64, 2, 2, False, jnp.float32),
    (512, 64, 2, 2, True, jnp.float32),
])
def test_flash_attention(S, hd, H, KV, causal, dtype):
    B = 2
    q, k, v = _arr(B, S, H, hd, dtype=dtype), _arr(B, S, KV, hd, dtype=dtype), \
        _arr(B, S, KV, hd, dtype=dtype)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    kk = jnp.repeat(k, H // KV, axis=2)
    vv = jnp.repeat(v, H // KV, axis=2)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    want = ref.flash_attention(fold(q), fold(kk), fold(vv), causal=causal)
    want = want.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("S,hd,chunk,dtype", [
    (64, 16, 16, jnp.float32),
    (128, 32, 64, jnp.float32),
    (128, 16, 32, jnp.bfloat16),
])
def test_wkv6(S, hd, chunk, dtype):
    B, H = 2, 3
    r, k, v = _arr(B, S, H, hd, dtype=dtype), _arr(B, S, H, hd, dtype=dtype), \
        _arr(B, S, H, hd, dtype=dtype)
    w = jnp.asarray(RNG.uniform(0.8, 0.99, (B, S, H, hd)), dtype)
    u = _arr(H, hd, dtype=dtype)
    out = ops.wkv6(r, k, v, w, u, chunk=chunk)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    ub = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, hd)
    want = ref.wkv6(fold(r), fold(k), fold(v), fold(w), ub)
    want = want.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_wkv6_matches_model_semantics():
    """Kernel == the model's recurrence (repro.models.recurrent.wkv6)."""
    from repro.models.recurrent import wkv6 as model_wkv6
    B, S, H, hd = 2, 64, 4, 16
    r, k, v = _arr(B, S, H, hd), _arr(B, S, H, hd), _arr(B, S, H, hd)
    w = jnp.asarray(RNG.uniform(0.8, 0.99, (B, S, H, hd)), jnp.float32)
    u = _arr(H, hd)
    out = ops.wkv6(r, k, v, w, u, chunk=16)
    want, _ = model_wkv6(r, k, v, w, u, jnp.zeros((B, H, hd, hd), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("S,R,dtype", [
    (64, 64, jnp.float32), (256, 512, jnp.float32), (128, 128, jnp.bfloat16),
])
def test_rglru(S, R, dtype):
    B = 2
    a = jnp.asarray(RNG.uniform(0.7, 0.999, (B, S, R)), dtype)
    g = _arr(B, S, R, dtype=dtype)
    out = ops.rglru(a, g)
    want = ref.rglru_scan(a, g)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("N,f,k", [(1024, 8, 4), (2048, 16, 8), (512, 6, 3)])
def test_kmeans_assign(N, f, k):
    x, c = _arr(N, f), _arr(k, f)
    lab, dist = ops.kmeans_assign(x, c)
    wl, wd = ref.kmeans_assign(x, c)
    assert int(jnp.sum(lab != wl)) == 0
    np.testing.assert_allclose(np.asarray(dist), np.asarray(wd), atol=1e-3)


@pytest.mark.parametrize("N,f,k", [(1024, 8, 4), (2048, 16, 8), (512, 6, 3),
                                   (4096, 6, 6)])
def test_kmeans_lloyd_step_fused(N, f, k):
    """Fused labels+sums+counts pass == assignment + one-hot reduction."""
    x, c = _arr(N, f), _arr(k, f)
    lab, dist, sums, cnt = ops.kmeans_lloyd_step(x, c)
    wl, wd, ws, wc = ref.kmeans_lloyd_step(x, c)
    assert int(jnp.sum(lab != wl)) == 0
    np.testing.assert_allclose(np.asarray(dist), np.asarray(wd), atol=1e-3)
    np.testing.assert_allclose(np.asarray(cnt), np.asarray(wc), rtol=0)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(ws),
                               atol=1e-3, rtol=1e-5)
    assert float(jnp.sum(cnt)) == N   # every point lands in exactly one cluster


def test_kmeans_lloyd_step_multiblock_accumulation():
    """Accumulation across grid steps: one-block and four-block launches of
    the same problem must agree exactly on sums/counts."""
    from repro.kernels import kmeans as km
    x, c = _arr(512, 8), _arr(4, 8)
    lab1, d1, s1, c1 = km.kmeans_lloyd_step(x, c, block_n=512, interpret=True)
    lab4, d4, s4, c4 = km.kmeans_lloyd_step(x, c, block_n=128, interpret=True)
    assert int(jnp.sum(lab1 != lab4)) == 0
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c4), rtol=0)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s4), atol=1e-4)


# ------------------------------------------------- ensemble scan helpers
# numpy mirrors of the engine expressions these kernels must match
# bit-for-bit (f64 under jax.enable_x64 inside the ensemble scan; here the
# comparison runs in f64 numpy on both sides).


def test_ensemble_node_rates_matches_engine_math():
    from repro.kernels import ensemble_step as ks
    rng = np.random.default_rng(0)
    R, N = 4, 7
    cores = rng.choice([4.0, 6.0, 8.0, 16.0], N)
    free = np.floor(rng.uniform(0, cores, (R, N)))
    nrun = rng.integers(0, 5, (R, N))
    cpu_base = rng.uniform(300, 600, N)
    mem_base = rng.uniform(1e4, 2e4, N)
    beta, cap, smt = 0.35, 2.5, 0.25
    mem_denom = np.minimum(1.0 + beta * np.maximum(0.0, nrun - 1.0), cap)
    occ = 1.0 - free / cores
    want_cpu = cpu_base * (1.0 - smt * np.maximum(0.0, occ - 0.5) / 0.5)
    want_mem = mem_base / mem_denom
    with jax.enable_x64(True):
        cpu, mem = ks.node_rates(jnp.asarray(free), jnp.asarray(mem_denom),
                                 jnp.asarray(cpu_base), jnp.asarray(mem_base),
                                 jnp.asarray(cores), smt)
        np.testing.assert_array_equal(np.asarray(cpu), want_cpu)
        np.testing.assert_array_equal(np.asarray(mem), want_mem)


def test_ensemble_time_left_and_advance_match_numpy():
    from repro.kernels import ensemble_step as ks
    rng = np.random.default_rng(1)
    R, N, C = 3, 4, 2
    rem = [rng.uniform(0, 100, (R, N, C)) for _ in range(3)]
    rates = [rng.uniform(1, 10, (R, N)) for _ in range(3)]
    want_tl = sum(r / s[:, :, None] for r, s in zip(rem, rates))
    dt = rng.uniform(0, 5, R)
    scale = 1.0 - np.minimum(dt[:, None, None] / want_tl, 1.0)
    with jax.enable_x64(True):
        tl = ks.time_left(*[jnp.asarray(r) for r in rem],
                          *[jnp.asarray(s) for s in rates])
        np.testing.assert_array_equal(np.asarray(tl), want_tl)
        adv = ks.advance(*[jnp.asarray(r) for r in rem], jnp.asarray(want_tl),
                         jnp.asarray(dt))
        for got, r in zip(adv, rem):
            np.testing.assert_array_equal(np.asarray(got), r * scale)


def test_ensemble_first_min_breaks_ties_by_start_order():
    from repro.kernels import ensemble_step as ks
    vals = jnp.asarray([[5.0, 2.0, 9.0, 2.0, 2.0]])
    order = jnp.asarray([[0, 7, 1, 3, 9]], dtype=jnp.int32)
    active = jnp.asarray([[True, True, True, True, False]])
    m, idx = ks.first_min_by_order(vals, order, active)
    assert float(m[0]) == 2.0
    assert int(idx[0]) == 3          # order 3 < 7; inactive order-9 ignored
    # all-inactive row: min is +inf, index readable (not an error)
    m2, _ = ks.first_min_by_order(vals, order, jnp.zeros_like(active))
    assert np.isinf(float(m2[0]))


def test_ensemble_blocked_argmin_matches_flat_argmin():
    from repro.kernels import ensemble_step as ks
    rng = np.random.default_rng(2)
    R, T, B = 5, 256, 64
    key = rng.integers(0, 50, (R, T)).astype(np.int32)  # dense ties
    key[0, :] = int(ks.INT_SENTINEL)                    # empty row
    got = ks.blocked_argmin_i32(jnp.asarray(key), B)
    np.testing.assert_array_equal(np.asarray(got), key.argmin(axis=1))


@pytest.mark.parametrize("R,TT,K", [(3, 64, 1), (5, 128, 7), (256, 320, 34)])
@pytest.mark.parametrize("form", ["TTxK", "RxK", "RxDxK"])
def test_ensemble_rank_of_names_matches_fancy_indexing(R, TT, K, form):
    """The one-hot lookup is ``rank[r, name]`` exactly, in the three forms
    the sjfn step uses: the task panel shared by every replica, one task
    per replica, and D dependents per replica."""
    from repro.kernels import ensemble_step as ks
    rng = np.random.default_rng(K)
    rank = rng.integers(0, 1 << 20, (R, K)).astype(np.int32)
    name_idx = rng.integers(0, K, TT).astype(np.int32)
    name_idx[TT - TT // 4:] = 0                       # padded task rows
    rows = np.arange(R)
    if form == "TTxK":
        idx, want = name_idx[None, :], rank[:, name_idx]
    elif form == "RxK":
        idx = name_idx[rng.integers(0, TT, R)]
        want = rank[rows, idx]
    else:
        idx = name_idx[rng.integers(0, TT, (R, 3))]
        want = rank[rows[:, None], idx]
    onehot = idx[..., None] == np.arange(K)
    got = ks.rank_of_names(jnp.asarray(rank), jnp.asarray(onehot))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("shape", [(3, 64), (256, 320), (64, 2176),
                                   (3, 5, 4), (256, 15, 8), (64, 256, 4)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("dtype", [np.float64, np.int32])
def test_ensemble_one_hot_take_and_put_match_fancy_indexing(shape, dtype):
    """``take_one_hot`` is ``a[r, idx[r]]`` and ``put_one_hot`` is a gated
    ``a[r, idx[r]] = v[r]``, bit for bit, over [R, W] task panels and
    [R, N, CAP] slot panels (the forecast cells' shapes and a small one):
    rows gated off are left as they are, the last position is reachable,
    and f64 ``-0.0`` and ``inf`` read back as stored."""
    from repro.kernels import ensemble_step as ks
    take, put = ks.take_one_hot, ks.put_one_hot
    rng = np.random.default_rng(sum(shape))
    R, rest = shape[0], shape[1:]
    if dtype == np.float64:
        a = rng.standard_normal(shape)
        value = rng.standard_normal(R)
        value[:2] = [-0.0, np.inf]
    else:
        a = rng.integers(-(1 << 30), 1 << 30, shape).astype(np.int32)
        value = rng.integers(-(1 << 30), 1 << 30, R).astype(np.int32)
    idx = tuple(rng.integers(0, n, R).astype(np.int32) for n in rest)
    for i, n in zip(idx, rest):
        i[0], i[-1] = n - 1, 0                    # the last and first slots
    rows = np.arange(R)
    a[(rows[:3],) + tuple(i[:3] for i in idx)] = np.asarray(
        [-0.0, np.inf, -np.inf] if dtype == np.float64 else [0, -1, 1 << 30],
        dtype)
    gate = rng.random(R) < 0.5
    gate[:3] = True, True, False
    bits = (lambda x: x.view(np.int64)) if dtype == np.float64 else np.asarray
    with jax.enable_x64(True):
        ja, jidx = jnp.asarray(a), tuple(jnp.asarray(i) for i in idx)
        got = np.asarray(take(ja, jidx))
        assert got.dtype == dtype
        np.testing.assert_array_equal(bits(got), bits(a[(rows,) + idx]))
        # a panel every replica shares
        shared = np.asarray(take(ja[:1], jidx))
        np.testing.assert_array_equal(
            bits(shared), bits(a[(np.zeros(R, int),) + idx]))
        # the leading axes alone: a row of the slot panel per replica
        got_row = np.asarray(take(ja, jidx[0]))
        np.testing.assert_array_equal(bits(got_row), bits(a[rows, idx[0]]))
        out = np.asarray(put(ja, jidx, jnp.asarray(gate), jnp.asarray(value)))
        # a value of the panel's own shape writes its own entries
        out_full = np.asarray(put(ja, jidx, jnp.asarray(gate), ja + ja))
    at = (rows[gate],) + tuple(i[gate] for i in idx)
    want = a.copy()
    want[at] = value[gate]
    np.testing.assert_array_equal(bits(out), bits(want))
    want = a.copy()
    want[at] = (a + a)[at]
    np.testing.assert_array_equal(bits(out_full), bits(want))
