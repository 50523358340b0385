"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles,
swept over shapes and dtypes, plus hypothesis property tests."""

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.key(0)


def rnd(i, shape, dtype=jnp.float32):
    x = jax.random.normal(jax.random.fold_in(KEY, i), shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------- matmul


@pytest.mark.parametrize("m,k,n", [(256, 512, 256), (512, 1024, 128),
                                   (128, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_matmul_matches_ref(m, k, n, dtype):
    a, b = rnd(1, (m, k), dtype), rnd(2, (k, n), dtype)
    out = ops.matmul(a, b, impl="interpret", out_dtype=jnp.float32,
                     block_m=128, block_n=128, block_k=128)
    want = ref.matmul_ref(a, b, jnp.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(out, want, rtol=tol, atol=tol * 8)


def test_matmul_fp8_storage():
    a = rnd(3, (128, 256)).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    b = rnd(4, (256, 128)).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    out = ops.matmul(a, b, impl="interpret", out_dtype=jnp.float32,
                     block_m=128, block_n=128, block_k=128)
    want = ref.matmul_ref(a, b, jnp.float32)
    np.testing.assert_allclose(out, want, rtol=1e-2, atol=0.5)


# ------------------------------------------------------- flash attention


@pytest.mark.parametrize("bh,s,d", [(4, 256, 64), (2, 128, 112),
                                    (1, 512, 64)])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None)])
def test_flash_attention_matches_ref(bh, s, d, causal, window):
    q, k, v = (rnd(i, (bh, s, d), jnp.bfloat16) for i in (5, 6, 7))
    out = ops.flash_attention(q, k, v, impl="interpret", causal=causal,
                              window=window, block_q=128, block_k=128)
    want = ops.flash_attention(q, k, v, impl="ref", causal=causal,
                               window=window)
    np.testing.assert_allclose(
        out.astype(np.float32), want.astype(np.float32),
        rtol=2e-2, atol=2e-2)


def test_flash_attention_causality():
    """Changing future keys must not change past outputs."""
    q, k, v = (rnd(i, (2, 256, 64)) for i in (8, 9, 10))
    out1 = ops.flash_attention(q, k, v, impl="interpret")
    k2 = k.at[:, 200:].set(99.0)
    v2 = v.at[:, 200:].set(-99.0)
    out2 = ops.flash_attention(q, k2, v2, impl="interpret")
    np.testing.assert_allclose(out1[:, :200], out2[:, :200],
                               rtol=1e-5, atol=1e-5)


# -------------------------------------------------------- decode attention


@pytest.mark.parametrize("h,kv", [(8, 2), (4, 4), (16, 8)])
@pytest.mark.parametrize("pos", [5, 128, 200])
def test_decode_attention_matches_ref(h, kv, pos):
    b, d, w = 2, 64, 128
    q = rnd(11, (b, h, d))
    kc, vc = rnd(12, (b, w, kv, d)), rnd(13, (b, w, kv, d))
    p = jnp.full((b,), pos, jnp.int32)
    out = ops.decode_attention(q, kc, vc, p, impl="interpret", block_k=64)
    want = ops.decode_attention(q, kc, vc, p, impl="ref")
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


def test_decode_attention_sliding_window():
    b, h, kv, d, w = 1, 4, 2, 32, 64
    q = rnd(14, (b, h, d))
    kc, vc = rnd(15, (b, w, kv, d)), rnd(16, (b, w, kv, d))
    p = jnp.full((b,), 64, jnp.int32)
    out = ops.decode_attention(q, kc, vc, p, impl="interpret",
                               window=16, block_k=32)
    want = ops.decode_attention(q, kc, vc, p, impl="ref", window=16)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------- rwkv


@pytest.mark.parametrize("s,hd,chunk", [(64, 16, 16), (128, 32, 16),
                                        (48, 16, 8)])
def test_rwkv_wkv_matches_serial_ref(s, hd, chunk):
    bh = 3
    r, k, v = (rnd(i, (bh, s, hd)) for i in (17, 18, 19))
    lw = jnp.clip(-jnp.exp(rnd(20, (bh, s, hd))), -4.0, 0.0)
    u = rnd(21, (bh, hd)) * 0.5
    out = ops.rwkv_wkv(r, k, v, lw, u, impl="interpret", chunk=chunk)
    want = ops.rwkv_wkv(r, k, v, lw, u, impl="ref")
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)


@hypothesis.given(
    decay=st.floats(min_value=-4.0, max_value=-0.01),
    s=st.sampled_from([16, 32, 64]),
)
@hypothesis.settings(max_examples=10, deadline=None)
def test_rwkv_constant_decay_is_ema(decay, s):
    """With constant decay, r=e_i, k=e_i the WKV reduces to a 1-channel
    exponentially weighted sum — closed form check."""
    hd = 8
    r = jnp.zeros((1, s, hd)).at[:, :, 0].set(1.0)
    k = jnp.zeros((1, s, hd)).at[:, :, 0].set(1.0)
    v = jnp.ones((1, s, hd))
    lw = jnp.full((1, s, hd), decay)
    u = jnp.zeros((1, hd))
    out = np.asarray(ops.rwkv_wkv(r, k, v, lw, u, impl="ref"))
    # out_t = sum_{j<t} exp(decay*(t-1-j)) ... geometric series
    t = np.arange(s)
    w = np.exp(decay)
    expected = (1 - w**t) / (1 - w)
    np.testing.assert_allclose(out[0, :, 0], expected, rtol=1e-4, atol=1e-4)


def test_rwkv_chunked_kernel_invariant_to_chunk_size():
    bh, s, hd = 2, 96, 16
    r, k, v = (rnd(i, (bh, s, hd)) for i in (22, 23, 24))
    lw = jnp.clip(-jnp.exp(rnd(25, (bh, s, hd))), -4.0, 0.0)
    u = rnd(26, (bh, hd)) * 0.5
    a = ops.rwkv_wkv(r, k, v, lw, u, impl="interpret", chunk=8)
    b = ops.rwkv_wkv(r, k, v, lw, u, impl="interpret", chunk=16)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


# --------------------------------------------------------- sparse gather


@pytest.mark.parametrize("v,d,n,bag", [(128, 64, 16, 4), (512, 128, 8, 8)])
def test_sparse_gather_matches_ref(v, d, n, bag):
    tbl = rnd(27, (v, d))
    idx = jax.random.randint(jax.random.fold_in(KEY, 28), (n, bag), 0, v)
    w = rnd(29, (n, bag))
    out = ops.sparse_gather_sum(tbl, idx, w, impl="interpret")
    want = ops.sparse_gather_sum(tbl, idx, w, impl="ref")
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


@hypothesis.given(st.integers(min_value=0, max_value=126))
@hypothesis.settings(max_examples=8, deadline=None)
def test_sparse_gather_one_hot(i):
    """bag of one index with weight 1 == that table row."""
    tbl = rnd(30, (127, 32))
    idx = jnp.full((1, 1), i, jnp.int32)
    w = jnp.ones((1, 1))
    out = ops.sparse_gather_sum(tbl, idx, w, impl="interpret")
    np.testing.assert_allclose(out[0], tbl[i], rtol=1e-6, atol=1e-6)


# --------------------------------------------------------- grouped GEMM


@pytest.mark.parametrize("block_m,d,f", [(8, 32, 64), (16, 64, 128)])
def test_grouped_matmul_matches_ref(block_m, d, f):
    gids = jnp.array([0, 0, 1, -1, 2, 3, 3, -1], jnp.int32)
    x = rnd(40, (gids.shape[0] * block_m, d))
    w = rnd(41, (4, d, f))
    out = ops.grouped_matmul(x, w, gids, impl="interpret", block_f=f)
    want = ops.grouped_matmul(x, w, gids, impl="ref")
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


@hypothesis.given(st.integers(min_value=0, max_value=3))
@hypothesis.settings(max_examples=4, deadline=None)
def test_grouped_matmul_single_tile_is_plain_matmul(e):
    """one m-tile routed to expert e == x @ w[e]."""
    x = rnd(42, (16, 32))
    w = rnd(43, (4, 32, 64))
    gids = jnp.full((1,), e, jnp.int32)
    out = ops.grouped_matmul(x, w, gids, impl="interpret", block_f=64)
    np.testing.assert_allclose(out, x @ w[e], rtol=1e-4, atol=1e-4)
