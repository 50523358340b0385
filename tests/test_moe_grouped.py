"""Sort-based dropless MoE: dispatch-plan property battery + kernel
parity against the jnp oracle and the dense capacity path.

The grouped pipeline is pure bookkeeping (sort -> pad -> GEMM ->
unpermute) around one kernel, so correctness decomposes into invariants
the property tests pin down exhaustively:

  * the sorted buffer is a padded permutation (every token appears
    exactly k times, pad rows nowhere touched),
  * group offsets are monotone and sum to T*k,
  * unpermute inverts permute,
  * combine weights equal the dense-softmax renormalized top-k,

plus end-to-end parity: grouped(impl=ref|interpret) == capacity
dispatch with an un-droppable buffer (capacity_factor -> inf), in both
bf16/int8 weights and swiglu/gelu stacks, on the mixtral and kimi
smoke configs.
"""

import dataclasses

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke
from repro.kernels import moe_gemm, ops as kops, ref
from repro.models.moe import (grouped_combine, grouped_dispatch_plan,
                              grouped_permute, moe_ffn, moe_param_specs,
                              quantize_moe_params)
from repro.models.params import init_params

KEY = jax.random.key(0)


def rnd(i, shape, dtype=jnp.float32):
    x = jax.random.normal(jax.random.fold_in(KEY, i), shape, jnp.float32)
    return x.astype(dtype)


def random_routing(seed: int, t: int, k: int, e: int):
    """(T, k) expert ids, distinct per token like top-k produces."""
    key = jax.random.fold_in(KEY, seed)
    scores = jax.random.normal(key, (t, e))
    _, idx = jax.lax.top_k(scores, min(k, e))
    return idx.astype(jnp.int32)


# ----------------------------------------------------- plan properties


@hypothesis.given(st.integers(min_value=0, max_value=1 << 20),
                  st.integers(min_value=1, max_value=24),
                  st.integers(min_value=1, max_value=4),
                  st.integers(min_value=1, max_value=8),
                  st.sampled_from([4, 8]))
@hypothesis.settings(max_examples=30, deadline=None)
def test_plan_is_padded_permutation(seed, t, k, e, bm):
    k = min(k, e)
    gate_idx = random_routing(seed, t, k, e)
    plan = grouped_dispatch_plan(gate_idx, n_experts=e, block_m=bm)
    row_src = np.asarray(plan.row_src)
    dest = np.asarray(plan.dest)
    # dest is injective into the padded buffer and row_src inverts it:
    # slot dest[a] holds assignment a's source token.
    assert len(set(dest.tolist())) == t * k
    assert np.all((dest >= 0) & (dest < plan.padded_rows))
    np.testing.assert_array_equal(row_src[dest], np.arange(t * k) // k)
    # every token referenced exactly k times; pad rows are -1
    tokens, counts = np.unique(row_src[row_src >= 0], return_counts=True)
    np.testing.assert_array_equal(tokens, np.arange(t))
    np.testing.assert_array_equal(counts, np.full(t, k))
    assert np.sum(row_src < 0) == plan.padded_rows - t * k


@hypothesis.given(st.integers(min_value=0, max_value=1 << 20),
                  st.integers(min_value=1, max_value=24),
                  st.integers(min_value=1, max_value=4),
                  st.integers(min_value=1, max_value=8),
                  st.sampled_from([4, 8]))
@hypothesis.settings(max_examples=30, deadline=None)
def test_plan_offsets_and_tiles(seed, t, k, e, bm):
    k = min(k, e)
    gate_idx = random_routing(seed, t, k, e)
    plan = grouped_dispatch_plan(gate_idx, n_experts=e, block_m=bm)
    counts = np.asarray(plan.counts)
    offsets = np.asarray(plan.offsets)
    # offsets = monotone cumsum of counts, summing to T*k
    assert offsets.shape == (e + 1,)
    assert np.all(np.diff(offsets) >= 0)
    np.testing.assert_array_equal(np.diff(offsets), counts)
    assert offsets[-1] == t * k
    # padded group starts are block-aligned and ordered
    starts = np.asarray(plan.padded_starts)
    assert np.all(starts % bm == 0)
    assert np.all(np.diff(starts) >= 0)
    # each m-tile is single-expert: every assignment's dest tile carries
    # that assignment's expert id; tiles past the data are the sentinel
    flat_e = np.asarray(gate_idx).reshape(-1)
    tiles = np.asarray(plan.block_experts)
    np.testing.assert_array_equal(tiles[np.asarray(plan.dest) // bm],
                                  flat_e)
    assert np.all((tiles >= -1) & (tiles < e))
    assert (tiles >= 0).sum() == -(-counts // bm).sum()


@hypothesis.given(st.integers(min_value=0, max_value=1 << 20),
                  st.integers(min_value=1, max_value=16),
                  st.integers(min_value=1, max_value=4),
                  st.integers(min_value=1, max_value=8))
@hypothesis.settings(max_examples=25, deadline=None)
def test_unpermute_inverts_permute(seed, t, k, e):
    k = min(k, e)
    d = 16
    gate_idx = random_routing(seed, t, k, e)
    xt = rnd(seed + 1, (t, d))
    plan = grouped_dispatch_plan(gate_idx, n_experts=e, block_m=4)
    xs = grouped_permute(xt, plan, jnp.float32)
    # gathering back through dest recovers each token's row k times
    back = np.asarray(xs)[np.asarray(plan.dest)].reshape(t, k, d)
    np.testing.assert_array_equal(back,
                                  np.repeat(np.asarray(xt)[:, None], k, 1))
    # pad rows stay zero (psum identity under expert parallelism)
    pads = np.asarray(xs)[np.asarray(plan.row_src) < 0]
    np.testing.assert_array_equal(pads, np.zeros_like(pads))
    # combine with uniform gates averages the k copies back to the token
    gate_w = jnp.full((t, k), 1.0 / k)
    out = grouped_combine(xs, plan, gate_w, t, k)
    np.testing.assert_allclose(out, xt, rtol=1e-6, atol=1e-6)


@hypothesis.given(st.integers(min_value=0, max_value=1 << 20),
                  st.integers(min_value=1, max_value=12))
@hypothesis.settings(max_examples=20, deadline=None)
def test_combine_weights_match_dense_softmax(seed, t):
    """Grouped output == sum_k renorm(softmax(logits))[top-k] * expert(x),
    computed densely per token — the routing contract both dispatch
    modes share."""
    d, e, k = 16, 4, 2
    cfg = dataclasses.replace(get_smoke("mixtral_8x22b"), d_model=d,
                              d_ff=24, n_experts=e, experts_per_token=k)
    p = init_params(jax.random.fold_in(KEY, seed), moe_param_specs(cfg))
    x = rnd(seed + 7, (1, t, d))
    out, _ = moe_ffn(p, x, cfg, jnp.float32, dispatch="grouped",
                     impl="ref")
    # dense per-token oracle
    logits = np.asarray(x.reshape(t, d) @ np.asarray(p["router"]))
    probs = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    gw, gi = jax.lax.top_k(probs, k)
    gw = np.asarray(gw / gw.sum(-1, keepdims=True))
    want = np.zeros((t, d), np.float32)
    from repro.models.ops import swiglu
    for ti in range(t):
        for j in range(k):
            ex = int(gi[ti, j])
            up = x.reshape(t, d)[ti] @ p["w_up"][ex]
            h = swiglu(x.reshape(t, d)[ti] @ p["w_gate"][ex], up)
            want[ti] += gw[ti, j] * np.asarray(h @ p["w_down"][ex])
    np.testing.assert_allclose(out[0], want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t,k,e,case", [
    (6, 2, 4, "all_one"),   # every assignment routed to expert 1
    (3, 2, 8, "t_lt_e"),    # fewer tokens than experts
    (1, 2, 4, "single"),    # T=1
    (1, 1, 1, "minimal"),   # one token, one expert, k=1
])
def test_plan_degenerate_cases(t, k, e, case):
    if case == "all_one":
        gate_idx = jnp.full((t, k), 1, jnp.int32)
    else:
        gate_idx = random_routing(99, t, k, e)
    plan = grouped_dispatch_plan(gate_idx, n_experts=e, block_m=8)
    dest = np.asarray(plan.dest)
    assert len(set(dest.tolist())) == t * k
    np.testing.assert_array_equal(np.asarray(plan.row_src)[dest],
                                  np.arange(t * k) // k)
    assert np.asarray(plan.offsets)[-1] == t * k
    d = 8
    xt = rnd(5, (t, d))
    xs = grouped_permute(xt, plan, jnp.float32)
    out = grouped_combine(xs, plan, jnp.full((t, k), 1.0 / k), t, k)
    np.testing.assert_allclose(out, xt, rtol=1e-6, atol=1e-6)
    if case == "all_one":
        tiles = np.asarray(plan.block_experts)
        assert set(tiles[tiles >= 0].tolist()) == {1}


# ------------------------------------------- kernel == oracle parity


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("with_scale", [False, True])
def test_grouped_matmul_interpret_matches_ref(dtype, with_scale):
    m, d, f, e, bm = 64, 32, 48, 4, 8
    gids = jnp.array([0, 0, 1, -1, 2, 3, 3, -1], jnp.int32)
    x = rnd(11, (m, d), dtype)
    if with_scale:
        w8 = jnp.clip(jnp.round(rnd(12, (e, d, f)) * 40), -127, 127)
        w = w8.astype(jnp.int8)
        scale = jnp.abs(rnd(13, (e,))) + 0.1
    else:
        w, scale = rnd(12, (e, d, f), dtype), None
    out = moe_gemm.grouped_matmul(x, w, gids, w_scale=scale,
                                  interpret=True, block_f=16)
    want = ref.grouped_matmul_ref(x, w, gids, w_scale=scale)
    if dtype == jnp.bfloat16:
        rtol = atol = 2e-2
    else:
        # f32: kernel and oracle sum the same d products in different
        # orders (the kernel scales after the dot, the oracle may not), so
        # they may differ by up to d * max|x| * max|w| * scale * eps_f32.
        # int8 weights reach 127, which puts that bound near 5e-4: a fixed
        # 1e-5 would sit below f32 rounding at that magnitude.
        mag = float(jnp.max(jnp.abs(x))) * float(
            jnp.max(jnp.abs(w.astype(jnp.float32))))
        if scale is not None:
            mag *= float(jnp.max(scale))
        rtol, atol = 1e-5, d * mag * float(jnp.finfo(jnp.float32).eps)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=atol)
    # sentinel tiles are exactly zero in both
    np.testing.assert_array_equal(
        np.asarray(out).reshape(len(gids), bm, f)[np.asarray(gids) < 0],
        0.0)


@pytest.mark.parametrize("arch", ["mixtral_8x22b", "kimi_k2_1t_a32b"])
@pytest.mark.parametrize("act", ["swiglu", "gelu"])
@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_grouped_ffn_matches_capacity_dropless(arch, act, impl):
    """Grouped dispatch == capacity dispatch with an un-droppable buffer
    (capacity_factor -> inf == dropless) on real smoke configs."""
    cfg = dataclasses.replace(get_smoke(arch), mlp_act=act)
    p = init_params(jax.random.fold_in(KEY, 3), moe_param_specs(cfg))
    x = rnd(21, (2, 5, cfg.d_model))
    got, aux_g = moe_ffn(p, x, cfg, jnp.float32, dispatch="grouped",
                         impl=impl)
    want, aux_c = moe_ffn(p, x, cfg, jnp.float32, dropless=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name in aux_g:
        np.testing.assert_allclose(aux_g[name], aux_c[name], rtol=1e-6)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_grouped_ffn_int8_matches_capacity(impl):
    """int8 expert weights: in-kernel post-dot dequant == capacity's
    eager pre-dot dequant (exact for scalar scales, up to fp rounding)."""
    cfg = get_smoke("mixtral_8x22b")
    p = quantize_moe_params(
        init_params(jax.random.fold_in(KEY, 4), moe_param_specs(cfg)))
    assert p["w_up"].dtype == jnp.int8 and "w_up_scale" in p
    x = rnd(22, (1, 7, cfg.d_model))
    got, _ = moe_ffn(p, x, cfg, jnp.float32, dispatch="grouped",
                     impl=impl)
    want, _ = moe_ffn(p, x, cfg, jnp.float32, dropless=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_grouped_decode_matches_prefill_packing():
    """Chunk invariance: each token's grouped output is independent of
    what else shares the dispatch (the dropless serving contract) — a
    7-token prefill equals seven 1-token decode dispatches."""
    cfg = get_smoke("kimi_k2_1t_a32b")
    p = init_params(jax.random.fold_in(KEY, 5), moe_param_specs(cfg))
    x = rnd(23, (1, 7, cfg.d_model))
    full, _ = moe_ffn(p, x, cfg, jnp.float32, dispatch="grouped",
                      impl="ref")
    for t in range(7):
        step, _ = moe_ffn(p, x[:, t:t + 1], cfg, jnp.float32,
                          dispatch="grouped", impl="ref")
        np.testing.assert_allclose(step[0, 0], full[0, t],
                                   rtol=1e-6, atol=1e-6)


def test_grouped_matmul_expert_parallel_psum(subproc):
    """shard_map EP wrapper: experts sharded over "data" == single-host,
    including int8 scales riding the expert shard."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_default_matmul_precision", "highest")
from repro.kernels import ops as kops, ref
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
key = jax.random.key(0)
m, d, f, e = 32, 16, 24, 8
gids = jnp.array([0, 1, 3, -1], jnp.int32)
x = jax.random.normal(jax.random.fold_in(key, 1), (m, d))
w = jax.random.normal(jax.random.fold_in(key, 2), (e, d, f))
scale = jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (e,))) + .1
for sc in (None, scale):
    ws = w.astype(jnp.int8) if sc is not None else w
    got = kops.grouped_matmul(x, ws, gids, w_scale=sc, impl="ref",
                              mesh=mesh, expert_axis="data")
    want = ref.grouped_matmul_ref(x, ws, gids, w_scale=sc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
# non-divisible fallback: 8 experts on a 3-way axis -> replicated compute
mesh3 = make_mesh((3,), ("data",))
got = kops.grouped_matmul(x, w, gids, impl="ref", mesh=mesh3,
                          expert_axis="data")
np.testing.assert_allclose(np.asarray(got),
                           np.asarray(ref.grouped_matmul_ref(x, w, gids)),
                           rtol=1e-6, atol=1e-6)
print("EP-OK")
""", devices=8)
