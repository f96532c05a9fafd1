"""Per-kernel allclose tests vs the pure-jnp oracles, swept over shapes and
dtypes, all in interpret mode on CPU.  The randomized sweeps run as seeded
``pytest.mark.parametrize`` cases (formerly hypothesis property tests) so
the suite collects offline with stdlib + jax only — see tests/conftest.py."""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.jacquard_gemv import jacquard_gemv, jacquard_gemv_ref
from repro.kernels.paged_attention import (paged_decode_attention,
                                           paged_decode_attention_ref)
from repro.kernels.paged_attention.kernel import pages_per_group
from repro.kernels.pascal_matmul import pascal_matmul, pascal_matmul_ref
from repro.kernels.pavlov_lstm import pavlov_lstm, pavlov_lstm_ref
from repro.kernels.pavlov_rglru import pavlov_rglru, pavlov_rglru_ref
from repro.kernels.pavlov_ssm import pavlov_ssm, pavlov_ssm_ref


def _rand(key, *shape, dtype=jnp.float32, scale=1.0):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _tol(dtype):
    return dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------- pascal_matmul
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (64, 128, 64, 32, 32, 64),
    (100, 96, 50, 64, 32, 32),      # padding path
    (8, 256, 512, 8, 128, 128),
    (1, 64, 33, 8, 16, 64),         # degenerate M
])
def test_pascal_matmul(dtype, m, k, n, bm, bn, bk):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = _rand(k1, m, k, dtype=dtype)
    w = _rand(k2, k, n, dtype=dtype)
    out = pascal_matmul(x, w, block_m=bm, block_n=bn, block_k=bk)
    ref = pascal_matmul_ref(x, w)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_pascal_matmul_batched_lead_dims():
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x = _rand(k1, 2, 3, 32, 64)
    w = _rand(k2, 64, 48)
    out = pascal_matmul(x, w, block_m=16, block_n=16, block_k=32)
    np.testing.assert_allclose(out, jnp.einsum("abmk,kn->abmn", x, w),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("seed", range(15))
def test_pascal_matmul_property(seed):
    rng = random.Random(seed)
    m = rng.randint(1, 40)
    k = rng.choice([32, 64, 96])
    n = rng.randint(1, 70)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = _rand(k1, m, k)
    w = _rand(k2, k, n)
    out = pascal_matmul(x, w, block_m=16, block_n=16, block_k=32)
    np.testing.assert_allclose(out, pascal_matmul_ref(x, w),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------- jacquard_gemv
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [(1, 256, 512), (4, 1024, 300), (8, 96, 64)])
def test_jacquard_gemv(dtype, m, k, n):
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    x = _rand(k1, m, k, dtype=dtype)
    w = _rand(k2, k, n, dtype=dtype)
    out = jacquard_gemv(x, w, block_n=128, block_k=256)
    if dtype == jnp.bfloat16:
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(jacquard_gemv_ref(x, w), np.float32), **_tol(dtype))
        return
    # float32: kernel and reference sum K products in different orders.
    # Each order's rounding error is about sqrt(K) * eps * sum|x_k w_k|
    # (the random-walk model of f32 accumulation), so the exact float64
    # product is the reference and that bound, per element, the tolerance.
    x64, w64 = np.asarray(x, np.float64), np.asarray(w, np.float64)
    bound = np.sqrt(k) * np.finfo(np.float32).eps * (np.abs(x64) @ np.abs(w64))
    err = np.abs(np.asarray(out, np.float64) - x64 @ w64)
    assert np.all(err <= bound), float(np.max(err / bound))


# --------------------------------------------------------------- pavlov_lstm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h", [(2, 8, 16), (1, 20, 32), (4, 5, 64)])
def test_pavlov_lstm(dtype, b, t, h):
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    xg = _rand(k1, b, t, 4 * h, dtype=dtype, scale=0.5)
    wh = _rand(k2, h, 4 * h, dtype=dtype, scale=0.3)
    out = pavlov_lstm_fused(xg, wh)
    ref = pavlov_lstm_ref(xg, wh)
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


def pavlov_lstm_fused(xg, wh):
    from repro.kernels.pavlov_lstm.kernel import pavlov_lstm_raw
    from repro.kernels.common import use_interpret
    return pavlov_lstm_raw(xg, wh, interpret=use_interpret())


def test_pavlov_lstm_full_layer_matches_model_lstm():
    """ops.pavlov_lstm (decoupled GEMM + kernel) == models.recurrent.lstm_layer."""
    from repro.models.recurrent import init_lstm_layer, lstm_layer
    key = jax.random.PRNGKey(4)
    p = init_lstm_layer(key, 24, 16)
    x = _rand(jax.random.PRNGKey(5), 2, 10, 24, scale=0.5)
    ref, _ = lstm_layer(p, x)
    # model lstm adds +1.0 forget bias inside; kernel does the same
    out = pavlov_lstm(x, p["w_x"], p["w_h"], p["b"])
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


# -------------------------------------------------------------- pavlov_rglru
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,e,bt,be", [
    (2, 32, 64, 8, 32), (1, 16, 128, 16, 128), (3, 64, 32, 16, 32)])
def test_pavlov_rglru(dtype, b, t, e, bt, be):
    k1, k2 = jax.random.split(jax.random.PRNGKey(6))
    a = jax.nn.sigmoid(_rand(k1, b, t, e)).astype(dtype)   # decay in (0,1)
    bb = _rand(k2, b, t, e, dtype=dtype, scale=0.5)
    out = pavlov_rglru(a, bb, block_t=bt, block_e=be)
    ref = pavlov_rglru_ref(a, bb)
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


@pytest.mark.parametrize("seed", range(15))
def test_pavlov_rglru_property(seed):
    rng = random.Random(1000 + seed)
    b = rng.randint(1, 3)
    t = rng.choice([8, 24, 48])
    e = rng.choice([16, 64])
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.nn.sigmoid(_rand(k1, b, t, e))
    bb = _rand(k2, b, t, e, scale=0.5)
    out = pavlov_rglru(a, bb, block_t=8, block_e=16)
    np.testing.assert_allclose(out, pavlov_rglru_ref(a, bb),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------- pavlov_ssm
@pytest.mark.parametrize("b,t,d,n,bt,bd", [
    (2, 16, 32, 4, 8, 16), (1, 32, 64, 8, 16, 64), (2, 8, 16, 16, 8, 16)])
def test_pavlov_ssm(b, t, d, n, bt, bd):
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    delta = jax.nn.softplus(_rand(ks[0], b, t, d, scale=0.5))
    x = _rand(ks[1], b, t, d, scale=0.5)
    bc = _rand(ks[2], b, t, n, scale=0.5)
    cc = _rand(ks[3], b, t, n, scale=0.5)
    a = -jax.nn.softplus(_rand(ks[4], d, n))        # negative (stable)
    dskip = _rand(ks[5], d)
    out = pavlov_ssm(delta, x, bc, cc, a, dskip, block_t=bt, block_d=bd)
    ref = pavlov_ssm_ref(delta, x, bc, cc, a, dskip)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_pavlov_ssm_matches_model_mamba_core():
    """Kernel == the mamba_ssm inner recurrence used by falcon-mamba."""
    from repro.models.recurrent import mamba_ssm, init_mamba_block
    key = jax.random.PRNGKey(8)
    d_model, d_inner, d_state, dt_rank = 16, 32, 4, 4
    p = init_mamba_block(key, d_model, d_inner, d_state, 4, dt_rank)
    x = _rand(jax.random.PRNGKey(9), 2, 12, d_inner, scale=0.5)
    ref, _ = mamba_ssm(p, x, dt_rank, d_state, chunk=4)
    # recompute the kernel inputs exactly as mamba_ssm does
    xf = x.astype(jnp.float32)
    proj = jnp.einsum("bsd,dr->bsr", xf, p["x_proj"].astype(jnp.float32))
    dt_in, b_in, c_in = jnp.split(proj, [dt_rank, dt_rank + d_state], axis=-1)
    delta = jax.nn.softplus(
        jnp.einsum("bsr,rd->bsd", dt_in, p["dt_proj"].astype(jnp.float32))
        + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    out = pavlov_ssm(delta, xf, b_in, c_in, a, p["d_skip"].astype(jnp.float32),
                     block_t=4, block_d=16)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------- flash_attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sq,skv,h,kvh,hd,bq,bk,window", [
    (32, 32, 4, 4, 16, 16, 16, 0),
    (32, 32, 8, 2, 16, 8, 16, 0),       # GQA
    (64, 64, 4, 1, 32, 32, 32, 16),     # MQA + sliding window
    (16, 48, 4, 2, 16, 16, 16, 0),      # q continues a cache
])
def test_flash_kernel(dtype, sq, skv, h, kvh, hd, bq, bk, window):
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    q = _rand(ks[0], 2, sq, h, hd, dtype=dtype)
    k = _rand(ks[1], 2, skv, kvh, hd, dtype=dtype)
    v = _rand(ks[2], 2, skv, kvh, hd, dtype=dtype)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=bq, block_kv=bk)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("seed", range(15))
def test_flash_kernel_property(seed):
    rng = random.Random(2000 + seed)
    s = rng.choice([16, 32, 64])
    h, kvh = rng.choice([(4, 4), (4, 2), (8, 1)])
    hd = rng.choice([8, 16])
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _rand(ks[0], 1, s, h, hd)
    k = _rand(ks[1], 1, s, kvh, hd)
    v = _rand(ks[2], 1, s, kvh, hd)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_kv=16)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------- paged_attention
def _owned_tables(rng, n, nb, bs, lengths):
    """Each slot owns distinct physical blocks for exactly its live positions
    0..lengths[b]; every table entry past them is the sentinel ``n``."""
    perm = rng.permutation(n)
    table = np.full((len(lengths), nb), n, np.int32)
    off = 0
    for b, length in enumerate(lengths):
        owned = length // bs + 1
        table[b, :owned] = perm[off:off + owned]
        off += owned
    assert off <= n
    return table


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,kvh,hd,n,bs,nb,live", [
    pytest.param(4, 4, 16, 8, 8, 4, None, id="4-4-16-8-8-4"),
    pytest.param(4, 2, 16, 10, 8, 4, None, id="4-2-16-10-8-4"),     # GQA
    pytest.param(8, 1, 8, 6, 16, 2, None, id="8-1-8-6-16-2"),       # MQA
    # many small blocks
    pytest.param(2, 2, 32, 12, 4, 8, None, id="2-2-32-12-4-8"),
    # P = 3 pages of 8: nb 7 leaves a last group of one page
    pytest.param(4, 2, 16, 24, 8, 7, (55, 20, 50), id="nb-not-multiple-of-P"),
    # k*P*bs - 1 ends a group, k*P*bs opens one
    pytest.param(4, 2, 16, 24, 8, 7, (23, 24, 47), id="group-edges"),
    # empty slots (length 0) around a one-block prefix
    pytest.param(4, 2, 16, 24, 8, 7, (0, 5, 0), id="empty-and-short"),
    # qwen3-0.6b's heads and blocks, P = 3
    pytest.param(16, 8, 128, 16, 16, 6, (47, 48, 95), id="qwen3-heads"),
])
def test_paged_decode_kernel(dtype, h, kvh, hd, n, bs, nb, live):
    """Block-table gather kernel vs the pure-jnp paged reference: scattered
    pools, ragged per-slot lengths, sentinel (unallocated) table entries.
    ``live`` fixes the lengths, each slot owning exactly its live blocks;
    None draws them."""
    B = 3
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    q = _rand(ks[0], B, 1, h, hd, dtype=dtype)
    nk = _rand(ks[1], B, 1, kvh, hd, dtype=dtype)
    nv = _rand(ks[2], B, 1, kvh, hd, dtype=dtype)
    kp = _rand(ks[3], n, bs, kvh, hd, dtype=dtype)
    vp = _rand(ks[4], n, bs, kvh, hd, dtype=dtype)
    rng = np.random.RandomState(7)
    if live is not None:
        lengths = np.asarray(live, np.int32)
        table = _owned_tables(rng, n, nb, bs, lengths)
    else:
        # distinct physical blocks per slot, rest sentinel (= n, "no block")
        perm = rng.permutation(n)
        table = np.full((B, nb), n, np.int32)
        lengths = np.zeros((B,), np.int32)
        off = 0
        for b in range(B):
            owned = rng.randint(1, nb + 1)
            owned = min(owned, n - off)
            table[b, :owned] = perm[off:off + owned]
            off += owned
            lengths[b] = rng.randint(0, owned * bs)   # write pos in coverage
    out, k2, v2 = paged_decode_attention(q, nk, nv, kp, vp,
                                         jnp.asarray(table),
                                         jnp.asarray(lengths))
    outr, k2r, v2r = paged_decode_attention_ref(q, nk, nv, kp, vp,
                                                jnp.asarray(table),
                                                jnp.asarray(lengths))
    np.testing.assert_array_equal(np.asarray(k2), np.asarray(k2r))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v2r))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(outr, np.float32), **_tol(dtype))


@pytest.mark.parametrize("seed", range(16))
def test_paged_decode_kernel_property(seed):
    """Seeds 8-15 draw odd table widths (a short last page group) and
    lengths on the groups' edges, 0 included."""
    rng = random.Random(3000 + seed)
    h, kvh = rng.choice([(4, 4), (4, 2), (8, 1)])
    hd = rng.choice([8, 16])
    bs = rng.choice([4, 8])
    nb = rng.choice([2, 4])
    B = rng.choice([1, 2, 4])
    if seed >= 8:
        nb = rng.choice([3, 5, 7])
    n = B * nb + rng.choice([0, 3])
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = _rand(ks[0], B, 1, h, hd)
    nk = _rand(ks[1], B, 1, kvh, hd)
    nv = _rand(ks[2], B, 1, kvh, hd)
    kp = _rand(ks[3], n, bs, kvh, hd)
    vp = _rand(ks[4], n, bs, kvh, hd)
    nrng = np.random.RandomState(seed)
    table = nrng.permutation(n)[:B * nb].reshape(B, nb).astype(np.int32)
    lengths = nrng.randint(0, nb * bs, size=B).astype(np.int32)
    if seed >= 8:
        edge = pages_per_group(bs, nb) * bs
        edges = [0, edge - 1, edge, 2 * edge - 1, 2 * edge]
        lengths = np.minimum(nrng.choice(edges, size=B),
                             nb * bs - 1).astype(np.int32)
    out, k2, v2 = paged_decode_attention(q, nk, nv, kp, vp,
                                         jnp.asarray(table),
                                         jnp.asarray(lengths))
    outr, k2r, v2r = paged_decode_attention_ref(q, nk, nv, kp, vp,
                                                jnp.asarray(table),
                                                jnp.asarray(lengths))
    np.testing.assert_array_equal(np.asarray(k2), np.asarray(k2r))
    np.testing.assert_allclose(np.asarray(out), np.asarray(outr),
                               atol=1e-4, rtol=1e-4)


def test_paged_decode_kernel_reads_no_dead_page():
    """Every pool block outside the slots' live ranges is NaN, the block the
    sentinels clamp to (n - 1) among them: the kernel never fetches one, so
    its output is finite and equals the reference over the clean pool."""
    h, kvh, hd, n, bs, nb = 4, 2, 16, 24, 8, 7          # P = 3 pages
    lengths = np.asarray([0, 23, 50, 5], np.int32)
    B = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q = _rand(ks[0], B, 1, h, hd)
    nk = _rand(ks[1], B, 1, kvh, hd)
    nv = _rand(ks[2], B, 1, kvh, hd)
    kp = _rand(ks[3], n, bs, kvh, hd)
    vp = _rand(ks[4], n, bs, kvh, hd)
    table = _owned_tables(np.random.RandomState(9), n - 1, nb, bs, lengths)
    table[table == n - 1] = n                         # sentinel: "no block"
    dead = np.setdiff1d(np.arange(n), table[table < n])
    assert n - 1 in dead
    kp_nan = kp.at[dead].set(jnp.nan)
    vp_nan = vp.at[dead].set(jnp.nan)
    tbl, lens = jnp.asarray(table), jnp.asarray(lengths)
    out, _, _ = paged_decode_attention(q, nk, nv, kp_nan, vp_nan, tbl, lens)
    outr, _, _ = paged_decode_attention_ref(q, nk, nv, kp, vp, tbl, lens)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(outr),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------ block tiling
@pytest.mark.parametrize("n,target,align,want", [
    (200, 128, 8, 40),       # 100 divides 200 but is not a multiple of 8
    (1, 128, 8, 1),          # the whole axis is always a legal block
    (16, 128, 8, 16),
    (2560, 512, 128, 512),
    (1280, 512, 128, 256),   # 320 divides 1280 but is not a lane multiple
    (255, 128, 8, 255),      # no aligned divisor: the whole axis
])
def test_divisor_block_respects_tpu_tiling(n, target, align, want):
    from repro.models.recurrent import _divisor_block
    got = _divisor_block(n, target, align)
    assert got == want
    assert n % got == 0 and (got % align == 0 or got == n)
