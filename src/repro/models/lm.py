"""Decoder-only language model: specs, train loss, prefill, decode.

Layers run under ``lax.scan`` over stacked block parameters with
``jax.checkpoint`` (remat) around the body — the paper-era recipe for
training big models on HBM-limited accelerators. Leading dense layers
(``cfg.first_dense_layers``, DeepSeek-V3's first_k_dense_replace) are a
stack of their own, ``dense_blocks``, run before the scanned ``blocks``
under their own ``jax.checkpoint``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.blocks import (ModelContext, block_apply,
                                 block_cache_spec, block_specs,
                                 paged_block_cache_spec, stack_specs,
                                 sublayer_decode, sublayer_decode_span,
                                 sublayer_decode_span_paged, sublayer_forward,
                                 sublayer_prefill)
from repro.models.config import ModelConfig
from repro.models.ops import embed_lookup, rms_norm, softmax_cross_entropy
from repro.models.params import ParamSpec, ones_init

Array = jax.Array

AUX_WEIGHTS = {"load_balance": 0.01, "router_z": 0.001}
# DeepSeek-V3's sequence-wise balance loss, weighted by its alpha and
# summed over the expert layers (arXiv:2412.19437 §2.1.2)
SEQ_BALANCE_ALPHA = 1e-4
# rows of the expert layers, summed over layers: metrics, not losses
MOE_COUNTERS = ("moe_held_rows", "moe_padded_rows")


def layer_stacks(cfg: ModelConfig) -> List[Tuple[str, ModelConfig]]:
    """(parameter key, configuration) of each layer stack, in order: the
    leading dense layers, then the scanned blocks."""
    out = [("dense_blocks", cfg.leading_dense())] \
        if cfg.first_dense_layers else []
    return out + [("blocks", cfg)]


def lm_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, v = cfg.d_model, cfg.vocab_size
    specs: Dict[str, Any] = {
        "embed": ParamSpec((v, d), ("vocab", "embed")),
        "final_norm": ParamSpec((d,), ("embed",), init=ones_init()),
    }
    for name, c in layer_stacks(cfg):
        specs[name] = stack_specs(block_specs(c), c.n_blocks)
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((d, v), ("embed", "vocab"))
    return specs


def _logits(params: Dict[str, Any], x: Array, cfg: ModelConfig,
            ctx: ModelContext) -> Array:
    if cfg.tie_embeddings:
        head = params["embed"].astype(ctx.compute_dtype).T
    else:
        head = params["lm_head"].astype(ctx.compute_dtype)
    logits = x @ head
    return ctx.shard(logits, ("batch", "seq", "vocab"))


def lm_loss(params: Dict[str, Any], batch: Dict[str, Array],
            cfg: ModelConfig, ctx: ModelContext, remat_head: bool = False
            ) -> Tuple[Array, Dict[str, Array]]:
    """``remat_head`` runs the final norm, the logits and the cross
    entropy under a ``jax.checkpoint`` of their own, so that the logits
    are recomputed in the backward and never kept as residuals (the
    train step asks for it when it keeps each microbatch's layer
    inputs: with more than one microbatch)."""
    tokens, labels = batch["tokens"], batch["labels"]
    mrope = batch.get("positions")
    x = embed_lookup(params["embed"], tokens, ctx.compute_dtype)
    x = ctx.shard(x, ("batch", "act_seq", "embed"))

    keys = (*AUX_WEIGHTS, "seq_balance", *MOE_COUNTERS)

    def stack_body(c: ModelConfig):
        def body(x, bp):
            x, aux = block_apply(
                lambda p, x, _, i: sublayer_forward(p, x, c, ctx, i, mrope),
                bp, x, c)
            return x, {k: jnp.asarray(aux.get(k, 0.0), jnp.float32)
                       for k in keys}
        return body

    for name, c in layer_stacks(cfg):
        x, auxs = jax.lax.scan(jax.checkpoint(stack_body(c)), x,
                               params[name])
    mask = batch.get("loss_mask")

    def head(hp, x):
        x = rms_norm(x, hp["final_norm"], cfg.norm_eps)
        return softmax_cross_entropy(_logits(hp, x, cfg, ctx), labels, mask)

    hp = {k: params[k] for k in
          ("final_norm", "embed" if cfg.tie_embeddings else "lm_head")}
    loss, count = (jax.checkpoint(head) if remat_head else head)(hp, x)
    metrics = {"xent": loss, "tokens": count}
    total = loss
    for key, w in AUX_WEIGHTS.items():
        val = auxs[key].mean()
        metrics[key] = val
        total = total + w * val
    if cfg.n_experts:
        if cfg.router == "sigmoid":
            metrics["seq_balance"] = auxs["seq_balance"].sum()
            total = total + SEQ_BALANCE_ALPHA * metrics["seq_balance"]
        for key in MOE_COUNTERS:
            metrics[key] = auxs[key].sum()
    metrics["loss"] = total
    return total, metrics


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def lm_cache_spec(cfg: ModelConfig, batch: int, window: int,
                  ctx: ModelContext) -> Dict[str, Any]:
    out: Dict[str, Any] = {
        "pos": jax.ShapeDtypeStruct((batch,), jnp.int32)}
    for name, c in layer_stacks(cfg):
        out[name] = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((c.n_blocks, *s.shape), s.dtype),
            block_cache_spec(c, batch, window, ctx))
    return out


def lm_prefill(params: Dict[str, Any], tokens: Array, cfg: ModelConfig,
               ctx: ModelContext, window: int,
               logits_at: Optional[Array] = None,
               pad_left: Optional[Array] = None,
               mrope_positions: Optional[Array] = None
               ) -> Tuple[Array, Dict[str, Any]]:
    """Full-sequence prefill. Returns (last-token logits, cache).

    ``logits_at`` (B,) selects the position whose logits are returned
    (default: the last). Servers that pad prompts to a fixed compile
    length pass the true last-token index per request here; under causal
    attention the padded tail never influences the valid prefix.

    ``pad_left`` (B,) declares the first N positions to be padding for
    *state-family* stacks (mamba/rwkv): their embeddings are zeroed and
    the recurrent state provably stays at its zero initial value through
    the pad prefix, so servers can pad prompts up to a bucketed compile
    length from the front. Attention sublayers reject it (front padding
    would shift their positions).

    ``mrope_positions`` (3,B,S): explicit multimodal rope rows, the same
    contract the training loss uses (None = text default)."""
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens, ctx.compute_dtype)
    live = None
    if pad_left is not None:
        live = jnp.arange(s)[None, :] >= pad_left[:, None]  # (B, S)
        x = x * live[..., None].astype(x.dtype)
    x = ctx.shard(x, ("batch", "act_seq", "embed"))
    caches: Dict[str, Any] = {}
    for name, c in layer_stacks(cfg):
        cache0 = jax.tree.map(
            lambda sd: jnp.zeros(sd.shape, sd.dtype),
            block_cache_spec(c, b, window, ctx))

        def body(x, bp, c=c, cache0=cache0):
            return block_apply(
                lambda p, x, sc, i: sublayer_prefill(
                    p, x, sc, c, ctx, i, mrope_positions, seq_mask=live),
                bp, x, c, cache0)

        x, caches[name] = jax.lax.scan(jax.checkpoint(body), x,
                                       params[name])
    if logits_at is None:
        xl = x[:, -1:]
        pos = jnp.full((b,), s, jnp.int32)
    else:
        idx = jnp.broadcast_to(logits_at[:, None, None], (b, 1, x.shape[-1]))
        xl = jnp.take_along_axis(x, idx, axis=1)
        pos = logits_at.astype(jnp.int32) + 1
    xl = rms_norm(xl, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, xl, cfg, ctx)
    return logits, {**caches, "pos": pos}


def lm_decode_step(params: Dict[str, Any], token: Array,
                   cache: Dict[str, Any], cfg: ModelConfig,
                   ctx: ModelContext) -> Tuple[Array, Dict[str, Any]]:
    """token: (B, 1) int32. Returns (logits (B,1,V), new cache)."""
    pos = cache["pos"]
    x = embed_lookup(params["embed"], token, ctx.compute_dtype)
    x = ctx.shard(x, ("batch", None, "embed"))

    new: Dict[str, Any] = {}
    for name, c in layer_stacks(cfg):
        def body(x, xs, c=c):
            bp, bc = xs
            return block_apply(
                lambda p, x, sc, i: sublayer_decode(p, x, sc, pos, c, ctx, i),
                bp, x, c, bc)

        x, new[name] = jax.lax.scan(body, x, (params[name], cache[name]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x, cfg, ctx)
    return logits, {**new, "pos": pos + 1}


def _span_logits_slice(x: Array, logits_at: Optional[Array]) -> Array:
    """Prefill chunks only need ONE position's logits: gather it before
    the lm head so the vocab projection is (B,1,V), not (B,T,V) —
    spec verify passes ``logits_at=None`` and keeps the whole span."""
    if logits_at is None:
        return x
    b = x.shape[0]
    idx = jnp.broadcast_to(logits_at[:, None, None], (b, 1, x.shape[-1]))
    return jnp.take_along_axis(x, idx, axis=1)


def lm_decode_span(params: Dict[str, Any], tokens: Array,
                   cache: Dict[str, Any], cfg: ModelConfig,
                   ctx: ModelContext,
                   logits_at: Optional[Array] = None,
                   mrope_positions: Optional[Array] = None
                   ) -> Tuple[Array, Dict[str, Any]]:
    """T-token span decode against dense per-slot caches (all sublayer
    families) — the chunked-prefill datapath for hybrid (jamba) stacks.

    tokens: (B,T) int32 at absolute positions ``pos .. pos+T-1`` where
    ``pos = cache["pos"]`` may be negative: positions < 0 are dead
    (the front padding of a right-aligned prompt's first chunk) — their
    embeddings are zeroed, their cache writes dropped, and the residual
    stream stays exactly 0 there, so the recurrent state of mamba/rwkv
    sublayers passes through untouched. Attention caches must hold
    absolute slots (window >= total length; no ring wrap).
    ``logits_at`` (B,): return only that position's logits (B,1,V).
    ``mrope_positions`` (3,B,T): explicit multimodal rope rows for the
    span (None = text default, broadcast from absolute positions).
    Returns (logits, new cache with ``pos`` UNCHANGED — the caller owns
    position bookkeeping, exactly like the paged span path)."""
    pos = cache["pos"]
    b, t = tokens.shape
    posn = pos[:, None] + jnp.arange(t)[None, :]
    live = posn >= 0  # (B, T)
    x = embed_lookup(params["embed"], tokens, ctx.compute_dtype)
    x = x * live[..., None].astype(x.dtype)
    x = ctx.shard(x, ("batch", None, "embed"))

    new: Dict[str, Any] = {}
    for name, c in layer_stacks(cfg):
        def body(x, xs, c=c):
            bp, bc = xs
            return block_apply(
                lambda p, x, sc, i: sublayer_decode_span(
                    p, x, sc, pos, live, c, ctx, i, mrope_positions),
                bp, x, c, bc)

        x, new[name] = jax.lax.scan(body, x, (params[name], cache[name]))
    x = _span_logits_slice(x, logits_at)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x, cfg, ctx)
    return logits, {**new, "pos": pos}


# -- paged serving state ----------------------------------------------------


def lm_paged_state_spec(cfg: ModelConfig, num_pages: int, page_size: int,
                        max_batch: int, max_pages_per_seq: int,
                        ctx: ModelContext) -> Dict[str, Any]:
    """ShapeDtypeStructs for the paged decode state (see blocks.py)."""
    per_block = paged_block_cache_spec(cfg, num_pages, page_size, ctx)
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((cfg.n_blocks, *s.shape), s.dtype),
        per_block)
    return {
        "pages": stacked,
        "page_table": jax.ShapeDtypeStruct(
            (max_batch, max_pages_per_seq), jnp.int32),
        "pos": jax.ShapeDtypeStruct((max_batch,), jnp.int32),
    }


def lm_decode_span_paged(params: Dict[str, Any], tokens: Array,
                         state: Dict[str, Any], cfg: ModelConfig,
                         ctx: ModelContext,
                         valid: Optional[Array] = None,
                         logits_at: Optional[Array] = None,
                         mrope_positions: Optional[Array] = None
                         ) -> Tuple[Array, Dict[str, Any]]:
    """T-token span decode against the paged pool (decode as a span of
    one / speculative verify / suffix prefill / chunked cold prefill).

    tokens: (B,T) int32 at absolute positions ``pos .. pos+T-1``;
    ``valid`` (B,): number of real tokens in the span (default all T) —
    padded tail slots write to the trash page and their logits are
    garbage the caller must ignore. ``logits_at`` (B,): return only
    that position's logits, (B,1,V) — what a prefill chunk wants; spec
    verify keeps the full (B,T,V). ``mrope_positions`` (3,B,T): explicit
    multimodal rope rows for the span (None = text default — broadcast
    absolute positions, exactly what text-only mrope prompts want).
    Returns (logits, new state with
    ``pos`` UNCHANGED — acceptance/rollback bookkeeping is the
    caller's: accepted tokens advance the position frontier, rejected
    ones are simply never covered by it)."""
    pos = state["pos"]
    table = state["page_table"]
    t = tokens.shape[1]
    live = (None if valid is None
            else jnp.arange(t)[None, :] < valid[:, None])  # (B, T)
    x = embed_lookup(params["embed"], tokens, ctx.compute_dtype)
    x = ctx.shard(x, ("batch", None, "embed"))

    def body(x, xs):
        bp, layer_pages = xs
        return block_apply(
            lambda p, x, lp, i: sublayer_decode_span_paged(
                p, x, lp, table, pos, live, cfg, ctx, i, mrope_positions),
            bp, x, cfg, layer_pages)

    x, new_pages = jax.lax.scan(body, x, (params["blocks"], state["pages"]))
    x = _span_logits_slice(x, logits_at)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x, cfg, ctx)
    return logits, {"pages": new_pages, "page_table": table, "pos": pos}
