"""Family-agnostic model API: specs, loss, prefill, decode, input specs.

Everything downstream (trainer, server, dry-run, tests) goes through these
five functions; encoder-decoder vs decoder-only dispatch happens here.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import encdec, lm
from repro.models.blocks import ModelContext
from repro.models.config import ModelConfig

Array = jax.Array


def model_specs(cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.is_encoder_decoder:
        return encdec.encdec_specs(cfg)
    return lm.lm_specs(cfg)


def loss_fn(params, batch, cfg: ModelConfig, ctx: ModelContext,
            remat_head: bool = False):
    """``remat_head``: the head's logits are recomputed in the backward,
    never kept as residuals (``lm.lm_loss``, ``encdec.encdec_loss``)."""
    if cfg.is_encoder_decoder:
        return encdec.encdec_loss(params, batch, cfg, ctx,
                                  remat_head=remat_head)
    return lm.lm_loss(params, batch, cfg, ctx, remat_head=remat_head)


def cache_spec(cfg: ModelConfig, batch: int, window: int,
               ctx: ModelContext) -> Dict[str, Any]:
    if cfg.is_encoder_decoder:
        return encdec.encdec_cache_spec(cfg, batch, window, ctx)
    return lm.lm_cache_spec(cfg, batch, window, ctx)


def prefill_fn(params, batch, cfg: ModelConfig, ctx: ModelContext,
               window: int, logits_at=None, pad_left=None):
    """``logits_at`` (B,): index of the position whose logits to return
    (decoder-only; lets servers pad prompts to one compile length).
    ``pad_left`` (B,): leading pad count for front-padded state-family
    prompts (see lm_prefill). ``batch["positions"]`` (3,B,S) explicit
    mrope rows are honored exactly as the training loss honors them."""
    if cfg.is_encoder_decoder:
        if logits_at is not None or pad_left is not None:
            raise NotImplementedError(
                "logits_at/pad_left require a decoder-only model")
        return encdec.encdec_prefill(params, batch, cfg, ctx, window)
    return lm.lm_prefill(params, batch["tokens"], cfg, ctx, window,
                         logits_at=logits_at, pad_left=pad_left,
                         mrope_positions=batch.get("positions"))


def decode_fn(params, token, cache, cfg: ModelConfig, ctx: ModelContext):
    if cfg.is_encoder_decoder:
        return encdec.encdec_decode_step(params, token, cache, cfg, ctx)
    return lm.lm_decode_step(params, token, cache, cfg, ctx)


def decode_span_fn(params, tokens, cache, cfg: ModelConfig,
                   ctx: ModelContext, logits_at=None,
                   mrope_positions=None):
    """T-token span decode against dense per-slot caches — the
    chunked-prefill datapath for hybrid (attention + state) stacks.
    ``cache["pos"]`` may be negative: positions < 0 are the dead front
    padding of a right-aligned first chunk (see lm.lm_decode_span).
    ``logits_at`` (B,) gathers one position's logits before the lm head.
    ``mrope_positions`` (3,B,T) carries explicit multimodal rope rows for
    the span (None = text default)."""
    if cfg.is_encoder_decoder:
        raise ValueError(f"{cfg.name}: span decode requires decoder-only")
    return lm.lm_decode_span(params, tokens, cache, cfg, ctx,
                             logits_at=logits_at,
                             mrope_positions=mrope_positions)


def supports_paged_decode(cfg: ModelConfig) -> bool:
    """Paged KV applies to pure-attention decoder-only stacks of one
    scanned layer stack; SSM/RWKV sublayers carry O(1) state,
    encoder-decoder keeps cross-KV, and latent attention (MLA) needs
    latent pages (kv_lora_rank + qk_rope_dim a token) the pool does not
    have."""
    return (not cfg.is_encoder_decoder and not cfg.mla
            and not cfg.first_dense_layers
            and set(cfg.sublayer_kinds()) == {"attn"})


def paged_state_spec(cfg: ModelConfig, num_pages: int, page_size: int,
                     max_batch: int, max_pages_per_seq: int,
                     ctx: ModelContext) -> Dict[str, Any]:
    if not supports_paged_decode(cfg):
        raise ValueError(f"{cfg.name}: no paged decode for this family")
    return lm.lm_paged_state_spec(cfg, num_pages, page_size, max_batch,
                                  max_pages_per_seq, ctx)


def decode_span_paged_fn(params, tokens, state, cfg: ModelConfig,
                         ctx: ModelContext, valid=None, logits_at=None,
                         mrope_positions=None):
    """T-token span decode against the paged pool: one batched paged-
    attention call scores T consecutive tokens per request (plain decode
    at T = 1; speculative draft-verify; suffix/chunked prefill).
    ``logits_at`` (B,) gathers a single position's logits before the lm
    head (prefill chunks); ``mrope_positions`` (3,B,T) carries explicit
    multimodal rope rows (None = text default); ``pos`` in the returned
    state is unchanged — the caller owns acceptance/rollback (see
    lm.lm_decode_span_paged)."""
    if not supports_paged_decode(cfg):
        raise ValueError(f"{cfg.name}: no paged decode for this family")
    return lm.lm_decode_span_paged(params, tokens, state, cfg, ctx,
                                   valid=valid, logits_at=logits_at,
                                   mrope_positions=mrope_positions)


def train_batch_specs(cfg: ModelConfig, batch: int,
                      seq: int) -> Dict[str, jax.ShapeDtypeStruct]:
    specs = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    if cfg.is_encoder_decoder:
        specs["enc_feats"] = jax.ShapeDtypeStruct(
            (batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
    if cfg.pos_emb == "mrope":
        specs["positions"] = jax.ShapeDtypeStruct((3, batch, seq), jnp.int32)
    return specs


BATCH_LOGICAL = {
    "tokens": ("batch", "seq"),
    "labels": ("batch", "seq"),
    "loss_mask": ("batch", "seq"),
    "enc_feats": ("batch", None, "embed"),
    "positions": (None, "batch", "seq"),
}
