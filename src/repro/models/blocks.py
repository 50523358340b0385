"""Block assembly: sublayer specs, forward, and KV/state caches.

A *block* is the scan unit: ``block_len`` sublayers, each
(norm -> core -> residual, norm -> mlp/moe -> residual) where core is
attention, Mamba, or RWKV time-mix per ``cfg.sublayer_kinds()``. Parameters
for all blocks are stacked on a leading n_blocks axis and consumed by
``lax.scan`` — keeping the compiled HLO one-block-sized regardless of depth
(61-layer models compile as fast as 2-layer ones; the roofline analyzer
scales costs by the known trip count).

Caches: every sublayer owns a dict cache (attention: ring-buffered k/v;
mamba: conv window + ssm state; rwkv: token-shift + wkv state). Cache trees
are stacked across blocks and scanned jointly with the parameters during
prefill/decode.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.attention import (apply_positional, decode_attention,
                                    decode_span_attention, full_attention)
from repro.models.config import ModelConfig
from repro.models.mamba import (mamba_decode_step, mamba_forward,
                                mamba_param_specs)
from repro.models.moe import (dense_ffn, dense_ffn_specs, moe_ffn,
                              moe_param_specs)
from repro.models.ops import rms_norm
from repro.models.params import ParamSpec, ones_init, zeros_init
from repro.models.rwkv6 import (rwkv_channel_mix, rwkv_channel_specs,
                                rwkv_param_specs, rwkv_time_mix)

Array = jax.Array
ShardFn = Callable[[Array, Tuple[Optional[str], ...]], Array]


def _identity_shard(x: Array, logical: Tuple[Optional[str], ...]) -> Array:
    return x


class ModelContext:
    """Runtime knobs threaded through forwards (not traced)."""

    def __init__(self, *, compute_dtype=jnp.bfloat16, q_chunk: int = 2048,
                 shard: ShardFn = _identity_shard, mamba_chunk: int = 256,
                 rwkv_chunk: int = 16, attn_impl: str = "xla",
                 decode_cache_dtype=None, full_cache_window: bool = False,
                 mesh=None, data_axis: str = "data",
                 model_axis: str = "model",
                 moe_dispatch: str = "grouped",
                 moe_impl: Optional[str] = None,
                 metrics=None):
        self.compute_dtype = compute_dtype
        self.q_chunk = q_chunk
        self.shard = shard
        self.mamba_chunk = mamba_chunk
        self.rwkv_chunk = rwkv_chunk
        self.attn_impl = attn_impl
        self.decode_cache_dtype = decode_cache_dtype  # None -> compute dtype
        # keep absolute (non-ring) KV slots even for sliding-window archs;
        # paged serving scatters prefill caches into append-only pages and
        # relies on the attention mask (not the ring) to bound the window
        self.full_cache_window = full_cache_window
        # serving mesh: when set, the paged kernel wrappers shard_map over
        # (data_axis, model_axis) so each shard streams its local KV-head
        # slice of the page pool (see kernels/ops.py)
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        # serving MoE dispatch: "grouped" (sort-based dropless through the
        # m-grouped GEMM kernel; the default) or "capacity" (the legacy
        # dense dropless buffer). Training forwards always use capacity
        # dispatch. moe_impl=None derives the kernel impl from attn_impl.
        self.moe_dispatch = moe_dispatch
        self.moe_impl = moe_impl
        # optional obs.metrics registry: the trace-time counters of which
        # attention path a compiled program lowered through
        self.metrics = metrics

    @property
    def cache_dtype(self):
        return self.decode_cache_dtype or self.compute_dtype

    def moe_kwargs(self) -> Dict[str, Any]:
        """Serving-path moe_ffn kwargs for this context (dropless)."""
        if self.moe_dispatch != "grouped":
            return {"dropless": True}
        impl = self.moe_impl or {"pallas": "pallas",
                                 "pallas_interpret": "interpret"}.get(
                                     self.attn_impl, "ref")
        return {"dispatch": "grouped", "impl": impl, "mesh": self.mesh,
                "expert_axis": self.data_axis}


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def attn_param_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, h, kv, hd = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                    cfg.resolved_head_dim)
    specs = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((h, hd), ("heads", "head_dim"),
                                init=zeros_init())
        specs["bk"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"),
                                init=zeros_init())
        specs["bv"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"),
                                init=zeros_init())
    return specs


def sublayer_specs(cfg: ModelConfig, idx: int) -> Dict[str, Any]:
    kind = cfg.sublayer_kinds()[idx]
    d = cfg.d_model
    out: Dict[str, Any] = {
        "ln1": ParamSpec((d,), ("embed",), init=ones_init()),
        "ln2": ParamSpec((d,), ("embed",), init=ones_init()),
    }
    if kind == "attn":
        out["core"] = attn_param_specs(cfg)
    elif kind == "mamba":
        out["core"] = mamba_param_specs(cfg)
    elif kind == "rwkv":
        out["core"] = rwkv_param_specs(cfg)
    else:
        raise ValueError(kind)
    if kind == "rwkv":
        out["mlp"] = rwkv_channel_specs(cfg)
    elif cfg.sublayer_has_moe(idx):
        out["mlp"] = moe_param_specs(cfg)
    else:
        out["mlp"] = dense_ffn_specs(cfg)
    return out


def block_specs(cfg: ModelConfig) -> Dict[str, Any]:
    return {f"sl{i}": sublayer_specs(cfg, i) for i in range(cfg.block_len)}


def stack_specs(specs: Any, n: int) -> Any:
    """Add a leading stacking dim (logical axis None) to every leaf."""

    def stack(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n, *s.shape), (None, *s.logical), s.init, s.dtype)

    return jax.tree.map(stack, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# Forward (training / no cache)
# ---------------------------------------------------------------------------


def _project_qkv(p: Dict[str, Array], x: Array, cfg: ModelConfig,
                 dtype) -> Tuple[Array, Array, Array]:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    return q, k, v


def attn_forward(p: Dict[str, Array], x: Array, cfg: ModelConfig,
                 ctx: ModelContext,
                 positions: Optional[Array] = None,
                 mrope_positions: Optional[Array] = None,
                 attn_type: Optional[str] = None) -> Array:
    b, s, _ = x.shape
    dtype = ctx.compute_dtype
    q, k, v = _project_qkv(p, x, cfg, dtype)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k = apply_positional(q, k, cfg, positions, mrope_positions)
    q = ctx.shard(q, ("batch", "seq", "heads", None))
    k = ctx.shard(k, ("batch", "seq", "kv_heads", None))
    v = ctx.shard(v, ("batch", "seq", "kv_heads", None))
    out = full_attention(q, k, v, cfg, q_chunk=ctx.q_chunk,
                         attn_type=attn_type, impl=ctx.attn_impl,
                         metrics=ctx.metrics)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dtype))


def sublayer_forward(p: Dict[str, Any], x: Array, cfg: ModelConfig,
                     ctx: ModelContext, idx: int,
                     mrope_positions: Optional[Array] = None
                     ) -> Tuple[Array, Dict[str, Array]]:
    kind = cfg.sublayer_kinds()[idx]
    dtype = ctx.compute_dtype
    aux: Dict[str, Array] = {}
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "attn":
        core = attn_forward(p["core"], h, cfg, ctx,
                            mrope_positions=mrope_positions)
    elif kind == "mamba":
        core = mamba_forward(p["core"], h, cfg, dtype,
                             chunk=ctx.mamba_chunk)
    else:  # rwkv
        core = rwkv_time_mix(p["core"], h, cfg, dtype, chunk=ctx.rwkv_chunk)
    x = x + core
    x = ctx.shard(x, ("batch", "act_seq", "embed"))
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if kind == "rwkv":
        mlp = rwkv_channel_mix(p["mlp"], h, cfg, dtype)
    elif cfg.sublayer_has_moe(idx):
        mlp, aux = moe_ffn(p["mlp"], h, cfg, dtype, shard=ctx.shard)
    else:
        mlp = dense_ffn(p["mlp"], h, cfg, dtype)
    x = x + mlp
    x = ctx.shard(x, ("batch", "act_seq", "embed"))
    return x, aux


def block_forward(block_params: Dict[str, Any], x: Array, cfg: ModelConfig,
                  ctx: ModelContext,
                  mrope_positions: Optional[Array] = None
                  ) -> Tuple[Array, Dict[str, Array]]:
    aux_total: Dict[str, Array] = {}
    for i in range(cfg.block_len):
        x, aux = sublayer_forward(block_params[f"sl{i}"], x, cfg, ctx, i,
                                  mrope_positions)
        for key, val in aux.items():
            aux_total[key] = aux_total.get(key, 0.0) + val
    return x, aux_total


# ---------------------------------------------------------------------------
# Caches (prefill / decode)
# ---------------------------------------------------------------------------


def sublayer_cache_spec(cfg: ModelConfig, idx: int, batch: int,
                        window: int, ctx: ModelContext) -> Dict[str, Any]:
    kind = cfg.sublayer_kinds()[idx]
    hd = cfg.resolved_head_dim
    cdt = ctx.cache_dtype
    if kind == "attn":
        w = window
        if cfg.sliding_window is not None and not ctx.full_cache_window:
            w = min(window, cfg.sliding_window)
        return {
            "k": jax.ShapeDtypeStruct((batch, w, cfg.n_kv_heads, hd), cdt),
            "v": jax.ShapeDtypeStruct((batch, w, cfg.n_kv_heads, hd), cdt),
        }
    if kind == "mamba":
        return {
            "conv": jax.ShapeDtypeStruct(
                (batch, cfg.ssm_conv_width - 1, cfg.d_inner),
                ctx.compute_dtype),
            "ssm": jax.ShapeDtypeStruct(
                (batch, cfg.d_inner, cfg.ssm_state_dim), jnp.float32),
        }
    # rwkv
    return {
        "tok": jax.ShapeDtypeStruct((batch, 1, cfg.d_model),
                                    ctx.compute_dtype),
        "wkv": jax.ShapeDtypeStruct(
            (batch, cfg.rwkv_heads, cfg.rwkv_head_dim, cfg.rwkv_head_dim),
            jnp.float32),
        "cm_tok": jax.ShapeDtypeStruct((batch, 1, cfg.d_model),
                                       ctx.compute_dtype),
    }


CACHE_LOGICAL = {
    "k": ("batch", "kv_seq", "kv_heads", None),
    "v": ("batch", "kv_seq", "kv_heads", None),
    "conv": ("batch", None, "mlp"),
    "ssm": ("batch", "mlp", None),
    "tok": ("batch", None, "embed"),
    "wkv": ("batch", "heads", None, None),
    "cm_tok": ("batch", None, "embed"),
    "xk": ("batch", None, "kv_heads", None),
    "xv": ("batch", None, "kv_heads", None),
}


def block_cache_spec(cfg: ModelConfig, batch: int, window: int,
                     ctx: ModelContext) -> Dict[str, Any]:
    return {f"sl{i}": sublayer_cache_spec(cfg, i, batch, window, ctx)
            for i in range(cfg.block_len)}


def init_cache(spec: Any) -> Any:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


# -- prefill: run full-sequence forward, produce filled caches --------------


def sublayer_prefill(p, x, cache, cfg: ModelConfig, ctx: ModelContext, idx,
                     mrope_positions=None, seq_mask=None):
    """Like sublayer_forward but writes the cache. x: (B,S,D).

    ``seq_mask`` (B,S) marks live positions when the server front-pads a
    prompt to a bucketed length (state families only): with zeroed
    embeddings the residual stream is exactly 0 through the pad prefix
    (every projection here is bias-free and every core output is gated
    by a zero), so masking the one biased intermediate — mamba's conv —
    keeps the recurrent state untouched until the first live token."""
    kind = cfg.sublayer_kinds()[idx]
    dtype = ctx.compute_dtype
    b, s, _ = x.shape
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "attn":
        if seq_mask is not None:
            raise ValueError(
                "seq_mask (front padding) requires a state-family stack; "
                "attention positions would shift")
        q, k, v = _project_qkv(p["core"], h, cfg, dtype)
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        q, k = apply_positional(q, k, cfg, positions, mrope_positions)
        out = full_attention(q, k, v, cfg, q_chunk=ctx.q_chunk)
        core = jnp.einsum("bshk,hkd->bsd", out, p["core"]["wo"].astype(dtype))
        w = cache["k"].shape[1]
        if w >= s:
            newk = jnp.zeros_like(cache["k"]).at[:, :s].set(
                k.astype(ctx.cache_dtype))
            newv = jnp.zeros_like(cache["v"]).at[:, :s].set(
                v.astype(ctx.cache_dtype))
        else:  # keep last w (ring start aligned so slot = pos % w)
            start = s - w
            shift = start % w
            tailk = jnp.roll(k[:, start:], shift, axis=1)
            tailv = jnp.roll(v[:, start:], shift, axis=1)
            newk = tailk.astype(ctx.cache_dtype)
            newv = tailv.astype(ctx.cache_dtype)
        new_cache = {"k": newk, "v": newv}
    elif kind == "mamba":
        core, (conv, ssm) = mamba_forward(
            p["core"], h, cfg, dtype, chunk=ctx.mamba_chunk,
            return_state=True, seq_mask=seq_mask)
        new_cache = {"conv": conv, "ssm": ssm}
    else:
        core, (tok, wkv) = rwkv_time_mix(
            p["core"], h, cfg, dtype, chunk=ctx.rwkv_chunk,
            return_state=True)
        new_cache = {"tok": tok, "wkv": wkv}
    x = x + core
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if kind == "rwkv":
        mlp, cm_tok = rwkv_channel_mix(p["mlp"], h, cfg, dtype,
                                       return_state=True)
        new_cache["cm_tok"] = cm_tok
    elif cfg.sublayer_has_moe(idx):
        mlp, _ = moe_ffn(p["mlp"], h, cfg, dtype, shard=ctx.shard,
                         **ctx.moe_kwargs())
    else:
        mlp = dense_ffn(p["mlp"], h, cfg, dtype)
    x = x + mlp
    x = ctx.shard(x, ("batch", "act_seq", "embed"))
    return x, new_cache


# -- decode: one token against caches ---------------------------------------


def sublayer_decode(p, x, cache, pos, cfg: ModelConfig, ctx: ModelContext,
                    idx, mrope_positions=None):
    """x: (B,1,D); pos: (B,) valid-token count BEFORE this token.

    ``pos`` is per-request: a continuous-batching server decodes requests
    of different lengths in one lockstep batch, so each row writes its own
    ring slot and masks its own validity window."""
    kind = cfg.sublayer_kinds()[idx]
    dtype = ctx.compute_dtype
    b = x.shape[0]
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "attn":
        q, k, v = _project_qkv(p["core"], h, cfg, dtype)
        q, k = apply_positional(q, k, cfg, pos[:, None], mrope_positions)
        w = cache["k"].shape[1]
        bidx = jnp.arange(b)
        slot = pos % w  # (B,) per-request ring slot
        newk = cache["k"].at[bidx, slot].set(
            k[:, 0].astype(ctx.cache_dtype))
        newv = cache["v"].at[bidx, slot].set(
            v[:, 0].astype(ctx.cache_dtype))
        out = decode_attention(q, newk.astype(dtype), newv.astype(dtype),
                               pos + 1, cfg)
        core = jnp.einsum("bshk,hkd->bsd", out,
                          p["core"]["wo"].astype(dtype))
        new_cache = {"k": newk, "v": newv}
    elif kind == "mamba":
        core, (conv, ssm) = mamba_decode_step(
            p["core"], h, (cache["conv"], cache["ssm"]), cfg, dtype)
        new_cache = {"conv": conv, "ssm": ssm}
    else:
        core, (tok, wkv) = rwkv_time_mix(
            p["core"], h, cfg, dtype, chunk=1,
            init_state=(cache["tok"], cache["wkv"]), return_state=True)
        new_cache = {"tok": tok, "wkv": wkv}
    x = x + core
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if kind == "rwkv":
        mlp, cm_tok = rwkv_channel_mix(p["mlp"], h, cfg, dtype,
                                       prev=cache["cm_tok"],
                                       return_state=True)
        new_cache["cm_tok"] = cm_tok
    elif cfg.sublayer_has_moe(idx):
        mlp, _ = moe_ffn(p["mlp"], h, cfg, dtype, shard=ctx.shard,
                         **ctx.moe_kwargs())
    else:
        mlp = dense_ffn(p["mlp"], h, cfg, dtype)
    x = x + mlp
    return x, new_cache


def block_prefill(block_params, x, cache, cfg, ctx, mrope_positions=None,
                  seq_mask=None):
    new_cache = {}
    for i in range(cfg.block_len):
        x, new_cache[f"sl{i}"] = sublayer_prefill(
            block_params[f"sl{i}"], x, cache[f"sl{i}"], cfg, ctx, i,
            mrope_positions, seq_mask)
    return x, new_cache


def block_decode(block_params, x, cache, pos, cfg, ctx,
                 mrope_positions=None):
    new_cache = {}
    for i in range(cfg.block_len):
        x, new_cache[f"sl{i}"] = sublayer_decode(
            block_params[f"sl{i}"], x, cache[f"sl{i}"], pos, cfg, ctx, i,
            mrope_positions)
    return x, new_cache


# -- dense span decode: T consecutive tokens against per-slot caches --------
#
# The dense-backend counterpart of the paged span path, and the datapath
# behind *chunked prefill* for hybrid (attention + state) stacks: a prompt
# is processed in fixed-size spans at absolute positions, so attention
# never needs front padding to bucket (positions are explicit), while the
# recurrent state of mamba/rwkv sublayers threads through the chunks.
# ``live`` marks real positions: a right-aligned prompt front-pads only its
# FIRST chunk, and dead positions are proven inert — their embeddings are
# zeroed by the caller, their cache writes are dropped, and every sublayer
# output is re-masked so the residual stream stays exactly 0 there (the
# recurrent state passes through a dead prefix untouched; see
# mamba_forward's seq_mask contract).


def sublayer_decode_span(p, x, cache, pos, live, cfg: ModelConfig,
                         ctx: ModelContext, idx, mrope_positions=None):
    """T-token span decode against dense per-slot caches (all families).

    x: (B,T,D) at absolute positions ``pos .. pos+T-1`` (already zeroed
    at dead positions); live: (B,T) bool. Attention caches must be
    append-only views (window >= total length — no ring wrap): k/v write
    at their absolute slot, dead writes are dropped.
    ``mrope_positions`` (3,B,T): explicit multimodal rope rows for this
    span (None = text default, broadcast from the absolute positions)."""
    kind = cfg.sublayer_kinds()[idx]
    dtype = ctx.compute_dtype
    b, t, _ = x.shape
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "attn":
        q, k, v = _project_qkv(p["core"], h, cfg, dtype)
        posn = pos[:, None] + jnp.arange(t)[None, :]  # (B, T)
        q, k = apply_positional(q, k, cfg, posn, mrope_positions)
        w = cache["k"].shape[1]
        bidx = jnp.arange(b)[:, None]
        # dead positions write out of bounds and are dropped
        slot = jnp.where(live, posn, w)
        newk = cache["k"].at[bidx, slot].set(
            k.astype(ctx.cache_dtype), mode="drop")
        newv = cache["v"].at[bidx, slot].set(
            v.astype(ctx.cache_dtype), mode="drop")
        out = decode_span_attention(q, newk.astype(dtype),
                                    newv.astype(dtype), pos, cfg)
        core = jnp.einsum("bshk,hkd->bsd", out,
                          p["core"]["wo"].astype(dtype))
        new_cache = {"k": newk, "v": newv}
    elif kind == "mamba":
        core, (conv, ssm) = mamba_forward(
            p["core"], h, cfg, dtype, chunk=ctx.mamba_chunk,
            init_state=(cache["conv"], cache["ssm"]), return_state=True,
            seq_mask=live)
        new_cache = {"conv": conv, "ssm": ssm}
    else:  # rwkv
        core, (tok, wkv) = rwkv_time_mix(
            p["core"], h, cfg, dtype, chunk=ctx.rwkv_chunk,
            init_state=(cache["tok"], cache["wkv"]), return_state=True)
        new_cache = {"tok": tok, "wkv": wkv}
    # dead positions must stay exactly 0 in the residual stream: a dead
    # query's attention output is garbage (all-masked softmax) and would
    # otherwise leak into the next sublayer's conv window
    core = jnp.where(live[..., None], core, 0.0).astype(dtype)
    x = x + core
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if kind == "rwkv":
        mlp, cm_tok = rwkv_channel_mix(p["mlp"], h, cfg, dtype,
                                       prev=cache["cm_tok"],
                                       return_state=True)
        new_cache["cm_tok"] = cm_tok
    elif cfg.sublayer_has_moe(idx):
        mlp, _ = moe_ffn(p["mlp"], h, cfg, dtype, shard=ctx.shard,
                         **ctx.moe_kwargs())
    else:
        mlp = dense_ffn(p["mlp"], h, cfg, dtype)
    x = x + jnp.where(live[..., None], mlp, 0.0).astype(dtype)
    return x, new_cache


def block_decode_span(block_params, x, cache, pos, live, cfg, ctx,
                      mrope_positions=None):
    new_cache = {}
    for i in range(cfg.block_len):
        x, new_cache[f"sl{i}"] = sublayer_decode_span(
            block_params[f"sl{i}"], x, cache[f"sl{i}"], pos, live, cfg,
            ctx, i, mrope_positions)
    return x, new_cache


# -- paged decode: block/paged KV cache (serving) ---------------------------
#
# Pages are a shared pool per layer: k/v of shape (num_pages, page_size,
# KV, D), plus page-aligned scale pages (num_pages, page_size, KV) when the
# cache dtype is int8 — scale pages DMA through the same scalar-prefetched
# page table as the KV pages, so the Pallas kernels dequantize in VMEM and
# quantized caches never pay a gather materialization. A request owns a
# list of page ids (its ``page_table`` row, padded with the reserved trash
# page 0); token ``p`` lives in page ``table[p // page_size]`` at slot
# ``p % page_size``. Only attention sublayers have paged state —
# state-space/RWKV layers carry O(1) state and gain nothing from paging.


def paged_quantize(x: Array, dtype) -> Tuple[Array, Optional[Array]]:
    """Per-(token, kv-head) symmetric int8 quantization hook.

    x: (..., KV, D). Returns (stored, scale or None); scale shape
    (..., KV) in bf16 — the storage dtype of the scale pages — and the
    values are quantized against that rounded scale so dequantization
    inverts exactly."""
    if dtype != jnp.int8:
        return x.astype(dtype), None
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-8) / 127.0
    scale = scale.astype(jnp.bfloat16)
    q = jnp.clip(jnp.round(x / scale[..., None].astype(jnp.float32)),
                 -127, 127).astype(jnp.int8)
    return q, scale


def paged_dequantize(x: Array, scale: Optional[Array], dtype) -> Array:
    if scale is None:
        return x.astype(dtype)
    return (x.astype(jnp.float32) * scale[..., None]).astype(dtype)


def paged_sublayer_cache_spec(cfg: ModelConfig, num_pages: int,
                              page_size: int, ctx: ModelContext
                              ) -> Dict[str, Any]:
    hd, kv = cfg.resolved_head_dim, cfg.n_kv_heads
    cdt = ctx.cache_dtype
    spec = {
        "k": jax.ShapeDtypeStruct((num_pages, page_size, kv, hd), cdt),
        "v": jax.ShapeDtypeStruct((num_pages, page_size, kv, hd), cdt),
    }
    if cdt == jnp.int8:
        # bf16 scale pages: ample precision for a max-abs/127 scale, and
        # the pool stays well under half the bf16 cache's bytes — the
        # capacity lever the int8 page stream exists for
        spec["k_scale"] = jax.ShapeDtypeStruct(
            (num_pages, page_size, kv), jnp.bfloat16)
        spec["v_scale"] = jax.ShapeDtypeStruct(
            (num_pages, page_size, kv), jnp.bfloat16)
    return spec


def paged_block_cache_spec(cfg: ModelConfig, num_pages: int, page_size: int,
                           ctx: ModelContext) -> Dict[str, Any]:
    kinds = set(cfg.sublayer_kinds())
    if kinds != {"attn"}:
        raise ValueError(
            f"paged KV serving requires a pure-attention stack, got {kinds}")
    return {f"sl{i}": paged_sublayer_cache_spec(cfg, num_pages, page_size,
                                                ctx)
            for i in range(cfg.block_len)}


# per-layer page-pool logical axes: pool and scale pages shard on the KV
# head axis (over "model"); page/slot axes stay replicated so the host page
# table addresses every shard identically
PAGE_LOGICAL: Dict[str, Tuple[Optional[str], ...]] = {
    "k": (None, None, "kv_heads", None),
    "v": (None, None, "kv_heads", None),
    "k_scale": (None, None, "kv_heads"),
    "v_scale": (None, None, "kv_heads"),
}


def _constrain_pages(pages: Dict[str, Array],
                     ctx: ModelContext) -> Dict[str, Array]:
    """Pin freshly-written page pools to their logical sharding so scatter
    updates (and jit donation) keep the KV-head partition stable."""
    return {name: ctx.shard(arr, PAGE_LOGICAL[name])
            for name, arr in pages.items()}


def _paged_gather(pages: Dict[str, Array], page_table: Array, dtype
                  ) -> Tuple[Array, Array]:
    """Materialize each request's KV view: (B, M*P, KV, D) in ``dtype``."""
    _, p, kv, hd = pages["k"].shape
    b, m = page_table.shape
    ks, vs = pages.get("k_scale"), pages.get("v_scale")
    kg = paged_dequantize(pages["k"][page_table],
                          None if ks is None else ks[page_table], dtype)
    vg = paged_dequantize(pages["v"][page_table],
                          None if vs is None else vs[page_table], dtype)
    shape = (b, m * p, kv, hd)
    return kg.reshape(shape), vg.reshape(shape)


def sublayer_decode_paged(p, x, pages, page_table, pos, cfg: ModelConfig,
                          ctx: ModelContext, idx):
    """One-token decode against the paged pool. x: (B,1,D); pos: (B,)."""
    dtype = ctx.compute_dtype
    b = x.shape[0]
    page_size = pages["k"].shape[1]
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _project_qkv(p["core"], h, cfg, dtype)
    q, k = apply_positional(q, k, cfg, pos[:, None], None)
    bidx = jnp.arange(b)
    pid = page_table[bidx, pos // page_size]  # (B,) owning page
    slot = pos % page_size
    kq, ks = paged_quantize(k[:, 0], ctx.cache_dtype)
    vq, vs = paged_quantize(v[:, 0], ctx.cache_dtype)
    new_pages = dict(pages)
    new_pages["k"] = pages["k"].at[pid, slot].set(kq)
    new_pages["v"] = pages["v"].at[pid, slot].set(vq)
    if ks is not None:
        new_pages["k_scale"] = pages["k_scale"].at[pid, slot].set(ks)
        new_pages["v_scale"] = pages["v_scale"].at[pid, slot].set(vs)
    new_pages = _constrain_pages(new_pages, ctx)
    if ctx.attn_impl in ("pallas", "pallas_interpret"):
        # stream pages straight through the scalar-prefetch Pallas kernel
        # — no HBM materialization of a contiguous per-request cache.
        # int8 pages stream natively: the (N, P, KV) scale pages ride the
        # same table entry and dequantize in VMEM, so quantized caches
        # read half the bytes per token instead of paying a gather.
        from repro.kernels import ops as kops
        out = kops.paged_decode_attention(
            q[:, 0], new_pages["k"], new_pages["v"], page_table, pos + 1,
            k_scale=new_pages.get("k_scale"),
            v_scale=new_pages.get("v_scale"),
            impl=("interpret" if ctx.attn_impl == "pallas_interpret"
                  else "pallas"),
            window=cfg.sliding_window, mesh=ctx.mesh,
            data_axis=ctx.data_axis, model_axis=ctx.model_axis)[:, None]
    else:
        # jnp gather-dequant oracle (the correctness contract for the
        # kernel route; materializes a contiguous per-request view)
        kg, vg = _paged_gather(new_pages, page_table, dtype)
        out = decode_attention(q, kg, vg, pos + 1, cfg)
    core = jnp.einsum("bshk,hkd->bsd", out, p["core"]["wo"].astype(dtype))
    x = x + core
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.sublayer_has_moe(idx):
        mlp, _ = moe_ffn(p["mlp"], h, cfg, dtype, shard=ctx.shard,
                         **ctx.moe_kwargs())
    else:
        mlp = dense_ffn(p["mlp"], h, cfg, dtype)
    x = x + mlp
    return x, new_pages


def block_decode_paged(block_params, x, pages, page_table, pos, cfg, ctx):
    new_pages = {}
    for i in range(cfg.block_len):
        x, new_pages[f"sl{i}"] = sublayer_decode_paged(
            block_params[f"sl{i}"], x, pages[f"sl{i}"], page_table, pos,
            cfg, ctx, i)
    return x, new_pages


# -- paged span decode: T consecutive tokens in one batched call ------------
#
# The datapath behind speculative decoding, prefix-cache suffix prefill,
# AND chunked cold prefill (every paged serving path is a page-stream now):
# a span of T tokens per request is scored in ONE paged-attention call —
# the span's k/v are scattered into the pages first (append-only), then
# query t attends causally through absolute position pos + t. Rolling back
# rejected draft tokens is just a position rewind: their k/v stay in the
# pool as garbage beyond the validity frontier and are overwritten before
# the frontier ever reaches them (the paper's hardware-replay framing —
# a deterministic datapath plus a replayable frontier beats bespoke undo).


def sublayer_decode_span_paged(p, x, pages, page_table, pos, live,
                               cfg: ModelConfig, ctx: ModelContext, idx,
                               mrope_positions=None):
    """T-token span decode against the paged pool.

    x: (B,T,D) at absolute positions ``pos .. pos+T-1``; live: (B,T)
    bool — False marks padded span slots whose writes are routed to the
    trash page (suffix prefills pad to a bucketed compile length).
    ``mrope_positions`` (3,B,T): explicit multimodal rope rows for the
    span (None = text default)."""
    dtype = ctx.compute_dtype
    b, t, _ = x.shape
    page_size = pages["k"].shape[1]
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _project_qkv(p["core"], h, cfg, dtype)
    posn = pos[:, None] + jnp.arange(t)[None, :]  # (B, T)
    q, k = apply_positional(q, k, cfg, posn, mrope_positions)
    bidx = jnp.arange(b)[:, None]
    # page-table reads beyond the row clamp; dead slots write to trash 0
    pid = jnp.where(live, page_table[bidx, posn // page_size], 0)
    slot = posn % page_size
    kq, ks = paged_quantize(k, ctx.cache_dtype)  # (B, T, KV, D)
    vq, vs = paged_quantize(v, ctx.cache_dtype)
    new_pages = dict(pages)
    new_pages["k"] = pages["k"].at[pid, slot].set(kq)
    new_pages["v"] = pages["v"].at[pid, slot].set(vq)
    if ks is not None:
        new_pages["k_scale"] = pages["k_scale"].at[pid, slot].set(ks)
        new_pages["v_scale"] = pages["v_scale"].at[pid, slot].set(vs)
    new_pages = _constrain_pages(new_pages, ctx)
    if ctx.attn_impl in ("pallas", "pallas_interpret"):
        # same page stream as single-token decode: int8 scale pages DMA
        # through the table, dequantize in VMEM — no gather oracle
        from repro.kernels import ops as kops
        out = kops.paged_decode_span_attention(
            q, new_pages["k"], new_pages["v"], page_table, pos,
            k_scale=new_pages.get("k_scale"),
            v_scale=new_pages.get("v_scale"),
            impl=("interpret" if ctx.attn_impl == "pallas_interpret"
                  else "pallas"),
            window=cfg.sliding_window, mesh=ctx.mesh,
            data_axis=ctx.data_axis, model_axis=ctx.model_axis)
    else:
        kg, vg = _paged_gather(new_pages, page_table, dtype)
        out = decode_span_attention(q, kg, vg, pos, cfg)
    core = jnp.einsum("bshk,hkd->bsd", out, p["core"]["wo"].astype(dtype))
    x = x + core
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.sublayer_has_moe(idx):
        mlp, _ = moe_ffn(p["mlp"], h, cfg, dtype, shard=ctx.shard,
                         **ctx.moe_kwargs())
    else:
        mlp = dense_ffn(p["mlp"], h, cfg, dtype)
    x = x + mlp
    return x, new_pages


def block_decode_span_paged(block_params, x, pages, page_table, pos, live,
                            cfg, ctx, mrope_positions=None):
    new_pages = {}
    for i in range(cfg.block_len):
        x, new_pages[f"sl{i}"] = sublayer_decode_span_paged(
            block_params[f"sl{i}"], x, pages[f"sl{i}"], page_table, pos,
            live, cfg, ctx, i, mrope_positions)
    return x, new_pages
