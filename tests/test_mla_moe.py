"""The DeepSeek-V3 block's pieces on the CPU: the flash kernels at two head
widths, the grouped GEMM's custom VJP, an expert layer that holds a
share of the experts, the trainer's expert-row counters, and the serving
path's refusal of latent attention.

Kernels run in interpret mode against ``kernels/ref`` (the gradients
against jnp autodiff of the reference), under "highest" matmul
precision, so they differ by the order of f32 accumulation only."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke
from repro.kernels import flash_attention, moe_gemm, ref
from repro.models.moe import (dense_ffn, grouped_dispatch_plan,
                              grouped_permute, moe_ffn, moe_param_specs)
from repro.models.params import init_params


def rnd(i, shape, scale=1.0):
    return scale * jax.random.normal(jax.random.key(i), shape, jnp.float32)


@pytest.mark.parametrize("d,d_v", [(48, 32), (64, 64)])
def test_flash_two_widths_forward_and_gradients(d, d_v):
    """q/k at one width and v/o at another (MLA's 192/128, scaled down),
    and the qwen2 case of one width: the forward and the gradients of q,
    k and v through the custom VJP equal the reference's."""
    bh, s = 2, 256
    q, k = rnd(1, (bh, s, d)), rnd(2, (bh, s, d))
    v, do = rnd(3, (bh, s, d_v)), rnd(4, (bh, s, d_v))
    with jax.default_matmul_precision("highest"):
        o, back = jax.vjp(lambda q, k, v: flash_attention.flash_attention(
            q, k, v, block_q=128, block_k=128, interpret=True), q, k, v)
        o_ref, back_ref = jax.vjp(ref.flash_attention_ref, q, k, v)
        grads, grads_ref = back(do), back_ref(do)
    assert o.shape == (bh, s, d_v)
    np.testing.assert_allclose(o, o_ref, rtol=1e-5, atol=1e-5)
    for g, g_ref in zip(grads, grads_ref):
        np.testing.assert_allclose(g, g_ref, rtol=1e-4,
                                   atol=1e-4 * float(jnp.abs(g_ref).max()))


def test_flash_block_counts_both_widths():
    """Equal widths size the blocks as before (the qwen2 kernels do not
    change); MLA's widths at 8k fit 512-row blocks."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    assert flash_attention.flash_block(4096, 64, f32, bf16) == 1024
    assert flash_attention.flash_block(4096, 64, f32, bf16, 64) == 1024
    assert flash_attention.flash_block(8192, 192, f32, bf16, 128) == 512


def _routed_rows(block_m=8):
    """Sorted rows of a plan over 6 experts of which 3 (2, 3, 4) are held:
    expert 3 gets no row, tokens routed elsewhere take none, and the
    buffer ends in sentinel tiles."""
    gate_idx = jnp.array([[2, 0], [4, 5], [2, 4], [1, 0], [4, 2], [5, 1],
                          [2, 0]], jnp.int32)
    plan = grouped_dispatch_plan(gate_idx, n_experts=6, block_m=block_m,
                                 first=2, held=3)
    x = rnd(5, (gate_idx.shape[0], 32))
    return plan, grouped_permute(x, plan, jnp.float32)


def test_grouped_gemm_vjp_matches_reference():
    plan, xs = _routed_rows()
    gids = plan.block_experts
    assert int(plan.counts[1]) == 0  # expert 3: empty
    assert int((gids < 0).sum()) > 0  # sentinel tiles
    assert int(plan.counts.sum()) == 7  # of 14 assignments, 7 held
    w = rnd(6, (3, 32, 40))
    dy = rnd(7, (xs.shape[0], 40))
    with jax.default_matmul_precision("highest"):
        y, back = jax.vjp(lambda x, w: moe_gemm.grouped_matmul(
            x, w, gids, interpret=True), xs, w)
        y_ref, back_ref = jax.vjp(
            lambda x, w: ref.grouped_matmul_ref(x, w, gids), xs, w)
        (dx, dw), (dx_ref, dw_ref) = back(dy), back_ref(dy)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    # dx rows of sentinel tiles are zero, as the reference's
    np.testing.assert_allclose(dx, dx_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dw, dw_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(dw[1], 0.0)  # the empty expert


def test_dw_walk_visits_each_expert_once_in_order():
    gids = jnp.array([0, 0, 2, 2, 2, -1, -1], jnp.int32)
    ex, tile, flags = moe_gemm._dw_walk(gids, 4)
    ex, tile, flags = map(np.asarray, (ex, tile, flags))
    live = (flags & (moe_gemm.FIRST | moe_gemm.LAST | moe_gemm.COMPUTE)) > 0
    assert list(ex[live]) == [0, 0, 1, 2, 2, 2, 3]
    assert list(tile[(flags & moe_gemm.COMPUTE) > 0]) == [0, 1, 2, 3, 4]
    assert list(np.nonzero(flags & moe_gemm.FIRST)[0]) == [0, 2, 3, 6]
    assert list(np.nonzero(flags & moe_gemm.LAST)[0]) == [1, 2, 5, 6]
    # the steps that compute nothing keep the previous step's tile
    assert list(tile[7:]) == [4] * 4


def _kimi():
    return get_smoke("kimi_k2_1t_a32b")  # MLA, sigmoid router, shared


def _held(cfg, params, first, held):
    part = dict(params)
    for n in ("w_up", "w_gate", "w_down"):
        part[n] = params[n][first:first + held]
    return dataclasses.replace(cfg, experts_held=held,
                               first_expert=first), part


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_expert_shares_add_up_to_the_uncut_layer(impl):
    """The partial outputs of every share of the experts, with the shared
    experts counted once, add up to the layer that holds all of them."""
    cfg = _kimi()
    p = init_params(jax.random.key(8), moe_param_specs(cfg))
    p["router_bias"] = rnd(9, p["router_bias"].shape, 0.05)
    x = rnd(10, (2, 9, cfg.d_model))
    kw = dict(dispatch="grouped", impl=impl)
    whole, _ = moe_ffn(p, x, cfg, jnp.float32, **kw)
    parts, rows = [], 0
    for first in range(0, cfg.n_experts, 2):
        c, part = _held(cfg, p, first, 2)
        out, aux = moe_ffn(part, x, c, jnp.float32, with_counts=True, **kw)
        parts.append(out)
        rows += int(aux["moe_held_rows"])
    shared = dense_ffn(p["shared"], x, cfg, jnp.float32)
    total = sum(parts) - (len(parts) - 1) * shared
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    assert rows == x.shape[0] * x.shape[1] * cfg.experts_per_token


def test_held_share_grouped_matches_capacity():
    """The held share through the grouped path equals the capacity
    oracle's, counts included."""
    cfg = _kimi()
    c, p = _held(cfg, init_params(jax.random.key(11), moe_param_specs(cfg)),
                 3, 4)
    x = rnd(12, (1, 11, cfg.d_model))
    got, aux = moe_ffn(p, x, c, jnp.float32, dispatch="grouped",
                       impl="interpret", with_counts=True)
    want, aux_c = moe_ffn(p, x, c, jnp.float32, dropless=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(aux["seq_balance"], aux_c["seq_balance"],
                               rtol=1e-6)
    assert 0 < int(aux["moe_held_rows"]) <= int(aux["moe_padded_rows"])
    assert int(aux["moe_padded_rows"]) % 8 == 0


def test_chunked_dispatch_matches_one_dispatch(monkeypatch):
    """The dispatch run a chunk of tokens at a time gives the layer, its
    gradient and its counts as one dispatch does."""
    from repro.models import moe
    cfg = _kimi()
    p = init_params(jax.random.key(13), moe_param_specs(cfg))
    x = rnd(14, (2, 12, cfg.d_model))

    def run(p, tokens):
        monkeypatch.setattr(moe, "GROUPED_DISPATCH_TOKENS", tokens)
        out, aux = moe_ffn(p, x, cfg, jnp.float32, dispatch="grouped",
                           impl="interpret", with_counts=True)
        return jnp.sum(out * x) + aux["seq_balance"], aux

    (v1, a1), g1 = jax.value_and_grad(run, has_aux=True)(p, 8)
    (v2, a2), g2 = jax.value_and_grad(run, has_aux=True)(p, 24)
    np.testing.assert_allclose(v1, v2, rtol=1e-6)
    assert int(a1["moe_held_rows"]) == int(a2["moe_held_rows"]) == 48
    # each chunk pads each expert's group to the 8-row tile on its own
    plans = [grouped_dispatch_plan(idx, n_experts=cfg.n_experts)
             for idx in _route_chunks(p, x, cfg, 8)]
    assert int(a1["moe_padded_rows"]) == sum(
        int((-(-pl.counts // 8) * 8).sum()) for pl in plans)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _route_chunks(p, x, cfg, tokens):
    from repro.models.moe import _route
    xt = x.reshape(-1, cfg.d_model)
    idx = _route(p, xt, cfg, jnp.float32)[3]
    return [idx[i:i + tokens] for i in range(0, len(idx), tokens)]


def test_trainer_counts_rows_routed_to_held_experts(tmp_path):
    """The step's metrics carry the expert-row counts and the trainer
    adds them to its registry; a dense model counts nothing."""
    from repro.launch.train import build_trainer
    cfg = _kimi()
    trainer, state = build_trainer(cfg, batch=2, seq=16, microbatches=2,
                                   ckpt_dir=str(tmp_path / "moe"),
                                   checkpoint_every=100)
    trainer.run(state, 2)
    snap = trainer.metrics.snapshot()
    moe_layers = cfg.n_layers - cfg.first_dense_layers
    assert snap["moe_held_rows"] == 2 * 2 * 16 * cfg.experts_per_token \
        * moe_layers
    assert snap["moe_padded_rows"] >= snap["moe_held_rows"]
    dense, state = build_trainer(get_smoke("qwen2_0_5b"), batch=2, seq=16,
                                 ckpt_dir=str(tmp_path / "dense"),
                                 checkpoint_every=100)
    dense.run(state, 1)
    assert "moe_held_rows" not in dense.metrics.snapshot()


def test_serving_refuses_latent_attention():
    from repro.launch.serve import build_engine
    from repro.models import api
    cfg = _kimi()
    assert not api.supports_paged_decode(cfg)
    with pytest.raises(ValueError, match="latent KV cache"):
        build_engine(cfg, window=64)
