"""Encoder-decoder transformer (Whisper backbone).

The audio frontend is a STUB per the assignment: ``input_specs`` feeds
precomputed frame embeddings (B, encoder_seq, d_model) — what Whisper's two
conv layers would produce. Encoder: bidirectional attention + GELU MLP +
LayerNorm + learned positions. Decoder: causal self-attention + cross
attention over encoder states + GELU MLP.

Both stacks scan over stacked per-layer params; decode caches hold the
self-attention ring buffer plus the (static after prefill) cross-attention
k/v.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.attention import decode_attention, full_attention
from repro.models.blocks import ModelContext, _project_qkv, attn_param_specs
from repro.models.config import ModelConfig
from repro.models.moe import dense_ffn, dense_ffn_specs
from repro.models.ops import embed_lookup, layer_norm, softmax_cross_entropy
from repro.models.params import ParamSpec, normal_init, ones_init, zeros_init

Array = jax.Array

MAX_DEC_POSITIONS = 32768  # mechanical ceiling for the assigned shapes


def _ln_specs(d: int) -> Dict[str, ParamSpec]:
    return {"scale": ParamSpec((d,), ("embed",), init=ones_init()),
            "bias": ParamSpec((d,), ("embed",), init=zeros_init())}


def _ln(p, x, eps):
    return layer_norm(x, p["scale"], p["bias"], eps)


def enc_layer_specs(cfg: ModelConfig) -> Dict[str, Any]:
    return {"ln1": _ln_specs(cfg.d_model), "attn": attn_param_specs(cfg),
            "ln2": _ln_specs(cfg.d_model), "mlp": dense_ffn_specs(cfg)}


def dec_layer_specs(cfg: ModelConfig) -> Dict[str, Any]:
    return {"ln1": _ln_specs(cfg.d_model), "attn": attn_param_specs(cfg),
            "lnx": _ln_specs(cfg.d_model), "xattn": attn_param_specs(cfg),
            "ln2": _ln_specs(cfg.d_model), "mlp": dense_ffn_specs(cfg)}


def encdec_specs(cfg: ModelConfig) -> Dict[str, Any]:
    from repro.models.blocks import stack_specs
    d, v = cfg.d_model, cfg.vocab_size
    return {
        "embed": ParamSpec((v, d), ("vocab", "embed")),
        "enc_pos": ParamSpec((cfg.encoder_seq, d), (None, "embed"),
                             init=normal_init(0.01)),
        "dec_pos": ParamSpec((MAX_DEC_POSITIONS, d), (None, "embed"),
                             init=normal_init(0.01)),
        "enc_blocks": stack_specs(enc_layer_specs(cfg), cfg.encoder_layers),
        "dec_blocks": stack_specs(dec_layer_specs(cfg), cfg.n_layers),
        "enc_norm": _ln_specs(d),
        "final_norm": _ln_specs(d),
        "lm_head": ParamSpec((d, v), ("embed", "vocab")),
    }


def _self_attn(p, x, cfg, ctx, attn_type):
    dtype = ctx.compute_dtype
    q, k, v = _project_qkv(p, x, cfg, dtype)
    out = full_attention(q, k, v, cfg, q_chunk=ctx.q_chunk,
                         attn_type=attn_type)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dtype))


def _cross_attn(p, x, enc_kv, cfg, ctx):
    dtype = ctx.compute_dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dtype)
    k, v = enc_kv
    out = full_attention(q, k, v, cfg, q_chunk=ctx.q_chunk,
                         attn_type="bidirectional", window=None)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dtype))


def _enc_kv(p, enc_out, cfg, ctx):
    dtype = ctx.compute_dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(dtype))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    return k, v


def encode(params, enc_feats: Array, cfg: ModelConfig,
           ctx: ModelContext) -> Array:
    x = enc_feats.astype(ctx.compute_dtype) + \
        params["enc_pos"].astype(ctx.compute_dtype)
    x = ctx.shard(x, ("batch", "act_seq", "embed"))

    def body(x, lp):
        h = _ln(lp["ln1"], x, cfg.norm_eps)
        x = x + _self_attn(lp["attn"], h, cfg, ctx, "bidirectional")
        h = _ln(lp["ln2"], x, cfg.norm_eps)
        x = x + dense_ffn(lp["mlp"], h, cfg, ctx.compute_dtype)
        x = ctx.shard(x, ("batch", "act_seq", "embed"))
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["enc_blocks"])
    return _ln(params["enc_norm"], x, cfg.norm_eps)


def encdec_loss(params, batch, cfg: ModelConfig, ctx: ModelContext,
                remat_head: bool = False) -> Tuple[Array, Dict[str, Array]]:
    """``remat_head`` runs the final norm, the logits and the cross
    entropy under a ``jax.checkpoint`` of their own, as ``lm.lm_loss``
    does."""
    tokens, labels = batch["tokens"], batch["labels"]
    b, s = tokens.shape
    enc_out = encode(params, batch["enc_feats"], cfg, ctx)
    x = embed_lookup(params["embed"], tokens, ctx.compute_dtype)
    x = x + params["dec_pos"][:s].astype(ctx.compute_dtype)
    x = ctx.shard(x, ("batch", "act_seq", "embed"))

    def body(x, lp):
        h = _ln(lp["ln1"], x, cfg.norm_eps)
        x = x + _self_attn(lp["attn"], h, cfg, ctx, "causal")
        h = _ln(lp["lnx"], x, cfg.norm_eps)
        x = x + _cross_attn(lp["xattn"], h,
                            _enc_kv(lp["xattn"], enc_out, cfg, ctx),
                            cfg, ctx)
        h = _ln(lp["ln2"], x, cfg.norm_eps)
        x = x + dense_ffn(lp["mlp"], h, cfg, ctx.compute_dtype)
        x = ctx.shard(x, ("batch", "act_seq", "embed"))
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["dec_blocks"])
    mask = batch.get("loss_mask")

    def head(hp, x):
        x = _ln(hp["final_norm"], x, cfg.norm_eps)
        logits = ctx.shard(x @ hp["lm_head"].astype(ctx.compute_dtype),
                           ("batch", "seq", "vocab"))
        return softmax_cross_entropy(logits, labels, mask)

    hp = {k: params[k] for k in ("final_norm", "lm_head")}
    loss, count = (jax.checkpoint(head) if remat_head else head)(hp, x)
    return loss, {"xent": loss, "loss": loss, "tokens": count}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def encdec_cache_spec(cfg: ModelConfig, batch: int, window: int,
                      ctx: ModelContext) -> Dict[str, Any]:
    hd = cfg.resolved_head_dim
    cdt = ctx.cache_dtype
    per_layer = {
        "k": jax.ShapeDtypeStruct(
            (cfg.n_layers, batch, window, cfg.n_kv_heads, hd), cdt),
        "v": jax.ShapeDtypeStruct(
            (cfg.n_layers, batch, window, cfg.n_kv_heads, hd), cdt),
        "xk": jax.ShapeDtypeStruct(
            (cfg.n_layers, batch, cfg.encoder_seq, cfg.n_kv_heads, hd), cdt),
        "xv": jax.ShapeDtypeStruct(
            (cfg.n_layers, batch, cfg.encoder_seq, cfg.n_kv_heads, hd), cdt),
    }
    return {"blocks": per_layer,
            "pos": jax.ShapeDtypeStruct((batch,), jnp.int32)}


def encdec_prefill(params, batch, cfg: ModelConfig, ctx: ModelContext,
                   window: int):
    """Encode audio, prefill decoder tokens. Returns (logits, cache)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    enc_out = encode(params, batch["enc_feats"], cfg, ctx)
    x = embed_lookup(params["embed"], tokens, ctx.compute_dtype)
    x = x + params["dec_pos"][:s].astype(ctx.compute_dtype)
    x = ctx.shard(x, ("batch", "act_seq", "embed"))

    def body(x, lp):
        dtype = ctx.compute_dtype
        h = _ln(lp["ln1"], x, cfg.norm_eps)
        q, k, v = _project_qkv(lp["attn"], h, cfg, dtype)
        out = full_attention(q, k, v, cfg, q_chunk=ctx.q_chunk,
                             attn_type="causal", window=None)
        x = x + jnp.einsum("bshk,hkd->bsd", out,
                           lp["attn"]["wo"].astype(dtype))
        h = _ln(lp["lnx"], x, cfg.norm_eps)
        xk, xv = _enc_kv(lp["xattn"], enc_out, cfg, ctx)
        x = x + _cross_attn(lp["xattn"], h, (xk, xv), cfg, ctx)
        h = _ln(lp["ln2"], x, cfg.norm_eps)
        x = x + dense_ffn(lp["mlp"], h, cfg, dtype)
        x = ctx.shard(x, ("batch", "act_seq", "embed"))
        w = window
        kk = jnp.zeros((b, w, cfg.n_kv_heads, cfg.resolved_head_dim),
                       ctx.cache_dtype)
        vv = jnp.zeros_like(kk)
        take = min(w, s)
        kk = kk.at[:, :take].set(k[:, s - take:].astype(ctx.cache_dtype))
        vv = vv.at[:, :take].set(v[:, s - take:].astype(ctx.cache_dtype))
        cache = {"k": kk, "v": vv, "xk": xk.astype(ctx.cache_dtype),
                 "xv": xv.astype(ctx.cache_dtype)}
        return x, cache

    x, caches = jax.lax.scan(jax.checkpoint(body), x, params["dec_blocks"])
    x = _ln(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = x @ params["lm_head"].astype(ctx.compute_dtype)
    pos = jnp.full((b,), s, jnp.int32)
    return logits, {"blocks": caches, "pos": pos}


def encdec_decode_step(params, token, cache, cfg: ModelConfig,
                       ctx: ModelContext):
    pos = cache["pos"]
    b = token.shape[0]
    dtype = ctx.compute_dtype
    x = embed_lookup(params["embed"], token, dtype)
    # per-request positions (continuous batching decodes mixed lengths)
    x = x + jnp.take(params["dec_pos"], pos, axis=0)[:, None].astype(dtype)

    def body(x, xs):
        lp, bc = xs
        h = _ln(lp["ln1"], x, cfg.norm_eps)
        q, k, v = _project_qkv(lp["attn"], h, cfg, dtype)
        w = bc["k"].shape[1]
        bidx = jnp.arange(b)
        slot = pos % w  # (B,)
        newk = bc["k"].at[bidx, slot].set(k[:, 0].astype(ctx.cache_dtype))
        newv = bc["v"].at[bidx, slot].set(v[:, 0].astype(ctx.cache_dtype))
        out = decode_attention(q, newk.astype(dtype), newv.astype(dtype),
                               pos + 1, cfg)
        x = x + jnp.einsum("bshk,hkd->bsd", out,
                           lp["attn"]["wo"].astype(dtype))
        h = _ln(lp["lnx"], x, cfg.norm_eps)
        qx = jnp.einsum("bsd,dhk->bshk", h, lp["xattn"]["wq"].astype(dtype))
        if cfg.qkv_bias:
            qx = qx + lp["xattn"]["bq"].astype(dtype)
        enc_len = bc["xk"].shape[1]
        xout = decode_attention(
            qx, bc["xk"].astype(dtype), bc["xv"].astype(dtype),
            jnp.full((b,), enc_len, jnp.int32), cfg, window=None)
        # cross attention attends to ALL encoder positions
        x = x + jnp.einsum("bshk,hkd->bsd", xout,
                           lp["xattn"]["wo"].astype(dtype))
        h = _ln(lp["ln2"], x, cfg.norm_eps)
        x = x + dense_ffn(lp["mlp"], h, cfg, dtype)
        return x, {"k": newk, "v": newv, "xk": bc["xk"], "xv": bc["xv"]}

    x, new_blocks = jax.lax.scan(body, x, (params["dec_blocks"],
                                           cache["blocks"]))
    x = _ln(params["final_norm"], x, cfg.norm_eps)
    logits = x @ params["lm_head"].astype(dtype)
    return logits, {"blocks": new_blocks, "pos": pos + 1}
