"""The model's xla attention path and the Pallas flash-attention kernel
path (interpret mode) must agree end-to-end through a full model forward —
the kernel is a drop-in for the perf-critical layer, not a side artifact."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_smoke
from repro.models import api
from repro.models.blocks import ModelContext
from repro.models.params import init_params
from repro.obs.metrics import MetricsRegistry


def test_flash_kernel_path_matches_xla_in_model():
    # 128-token sequence so the kernel path engages (128-aligned blocks)
    cfg = dataclasses.replace(get_smoke("qwen2_5_3b"))
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    b, s = 2, 128
    toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    outs = {}
    for impl in ("xla", "pallas_interpret"):
        ctx = ModelContext(compute_dtype=jnp.float32, q_chunk=64,
                           attn_impl=impl)
        outs[impl], _ = api.loss_fn(params, batch, cfg, ctx)
    np.testing.assert_allclose(float(outs["xla"]),
                               float(outs["pallas_interpret"]),
                               rtol=2e-5)


def test_flash_kernel_path_swa_model():
    cfg = dataclasses.replace(get_smoke("mixtral_8x22b"), sliding_window=64)
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    b, s = 1, 128
    toks = jax.random.randint(jax.random.key(2), (b, s), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    vals = []
    for impl in ("xla", "pallas_interpret"):
        ctx = ModelContext(compute_dtype=jnp.float32, q_chunk=64,
                           attn_impl=impl)
        loss, _ = api.loss_fn(params, batch, cfg, ctx)
        vals.append(float(loss))
    np.testing.assert_allclose(vals[0], vals[1], rtol=2e-5)


def test_flash_kernel_path_train_grads_match_xla():
    """The train step's loss and every parameter gradient of a smoke Qwen2
    agree between jnp attention and the flash kernels' custom VJP (the
    training route on a TPU), through the per-layer remat scan."""
    cfg = get_smoke("qwen2_0_5b")
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    b, s = 2, 128
    toks = jax.random.randint(jax.random.key(3), (b, s), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    out, reg = {}, MetricsRegistry()
    for impl in ("xla", "pallas_interpret"):
        ctx = ModelContext(compute_dtype=jnp.float32, attn_impl=impl,
                           metrics=reg)
        out[impl] = jax.value_and_grad(
            lambda p: api.loss_fn(p, batch, cfg, ctx)[0])(params)
    # one attention lowering each: the kernel path really ran
    assert reg.counter("attn_xla_compiles").value == 1
    assert reg.counter("attn_flash_compiles").value == 1
    (loss_x, grads_x), (loss_p, grads_p) = out["xla"], out["pallas_interpret"]
    np.testing.assert_allclose(float(loss_x), float(loss_p), rtol=2e-5)
    flat_x = jax.tree_util.tree_leaves_with_path(grads_x)
    flat_p = jax.tree.leaves(grads_p)
    for (path, gx), gp in zip(flat_x, flat_p):
        scale = float(jnp.max(jnp.abs(gx)))
        np.testing.assert_allclose(
            gp, gx, rtol=1e-5, atol=1e-5 * scale,
            err_msg=jax.tree_util.keystr(path))
