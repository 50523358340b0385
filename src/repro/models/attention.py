"""GQA attention: training/prefill (full-sequence, q-chunked) and decode.

Implementations are selectable (``impl``):
  "xla"     — pure jnp, exact, q-chunked so the score matrix never exceeds
              (chunk x S) per head; the CPU path and the dry-run path
              (clean HLO).
  "pallas"  — the flash-attention Pallas kernels
              (kernels/flash_attention.py), differentiable through their
              custom VJP, so training and dense prefill both run them on
              a TPU ("pallas_interpret" runs the same kernel bodies on
              the CPU). A sequence the kernels cannot tile (not a
              multiple of 128 tokens) falls back to "xla".

Masks are computed from positions, never materialized at (S x S) outside the
chunk: causal, sliding-window (Mixtral), bidirectional (Whisper encoder),
and decode (cache validity window).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.ops import apply_mrope, apply_rope

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnDims:
    n_heads: int
    n_kv_heads: int
    head_dim: int


def _positions(batch_shape, seq: int) -> jax.Array:
    return jnp.broadcast_to(jnp.arange(seq), (*batch_shape, seq))


def apply_positional(q: jax.Array, k: jax.Array, cfg: ModelConfig,
                     positions: jax.Array,
                     mrope_positions: Optional[jax.Array]) -> Tuple[
                         jax.Array, jax.Array]:
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.pos_emb == "mrope":
        mp = (mrope_positions if mrope_positions is not None
              else jnp.broadcast_to(positions[None], (3, *positions.shape)))
        q = apply_mrope(q, mp, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, mp, cfg.rope_theta, cfg.mrope_sections)
    return q, k


def _mask_bias(qpos: jax.Array, kpos: jax.Array, attn_type: str,
               window: Optional[int]) -> jax.Array:
    """(..., Q, K) additive bias in fp32. qpos: (...,Q), kpos: (...,K)."""
    if attn_type == "bidirectional":
        allowed = jnp.ones(qpos.shape[:-1] + (qpos.shape[-1],
                                              kpos.shape[-1]), bool)
    else:
        allowed = qpos[..., :, None] >= kpos[..., None, :]
    if window is not None:
        allowed &= (qpos[..., :, None] - kpos[..., None, :]) < window
    return jnp.where(allowed, 0.0, NEG_INF).astype(jnp.float32)


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B,S,KV,D) -> (B,S,H,D) by repeating each KV head over its group.

    A static-index gather: under tensor parallelism each model shard slices
    the KV heads it needs locally — this keeps GSPMD from the degenerate
    reshard that a fused (kv, group) einsum formulation provokes."""
    b, s, kv, d = k.shape
    if kv == n_heads:
        return k
    idx = jnp.repeat(jnp.arange(kv), n_heads // kv)
    return jnp.take(k, idx, axis=2)


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
          bias: jax.Array) -> jax.Array:
    """q: (B,Q,H,D), k/v: (B,K,KV,D), bias: (B,Q,K).
    Returns (B,Q,H,D). fp32 softmax, bf16 matmuls with fp32 accum."""
    b, qlen, h, d = q.shape
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (d ** -0.5)
    scores = scores + bias[:, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1)
    # bf16 dot: the MXU accumulates in fp32 internally; forcing f32 HLO
    # output would make every weight cotangent f32 (2x scan-carry memory).
    out = jnp.einsum("bhqs,bshd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def full_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, cfg: ModelConfig,
    *,
    q_chunk: int = 2048,
    attn_type: Optional[str] = None,
    window: Optional[int] = None,
    impl: str = "xla",
    metrics=None,
) -> jax.Array:
    """Training/prefill attention. q: (B,S,H,D); k,v: (B,S,KV,D).

    impl="pallas"/"pallas_interpret" routes through the flash-attention
    kernels (kernels/flash_attention.py) when the sequence tiles: heads
    fold into the grid's batch dim, KV heads expand to full heads first
    (GQA). ``metrics`` (an obs.metrics registry) counts, at trace time,
    which path the compiled program took: ``attn_flash`` or ``attn_xla``."""
    b, s, h, d = q.shape
    atype = attn_type or cfg.attn_type
    win = window if window is not None else cfg.sliding_window
    flash = (impl in ("pallas", "pallas_interpret") and s % 128 == 0
             and k.shape[1] == s)
    if metrics is not None:
        metrics.compile_event("attn_flash" if flash else "attn_xla")
    if flash:
        from repro.kernels import ops as kops
        kf = _expand_kv(k, h)
        vf = _expand_kv(v, h)
        fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
        out = kops.flash_attention(
            fold(q), fold(kf), fold(vf),
            impl="interpret" if impl == "pallas_interpret" else "pallas",
            causal=atype != "bidirectional", window=win)
        return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    kpos = _positions((b,), k.shape[1])
    if s % q_chunk:  # largest divisor of s that fits the requested chunk
        from repro.models.mamba import fit_chunk
        q_chunk = fit_chunk(s, q_chunk)
    if s <= q_chunk:
        qpos = _positions((b,), s)
        bias = _mask_bias(qpos, kpos, atype, win)
        return _sdpa(q, k, v, bias)
    n_chunks = s // q_chunk

    def body(carry, xs):
        qc, start = xs
        qpos = start[:, None] + _positions((b,), q_chunk)
        bias = _mask_bias(qpos, kpos, atype, win)
        return carry, _sdpa(qc, k, v, bias)

    q_chunks = q.reshape(b, n_chunks, q_chunk, h, d).transpose(1, 0, 2, 3, 4)
    starts = (jnp.arange(n_chunks) * q_chunk)[:, None].repeat(b, 1)
    _, out = jax.lax.scan(body, None, (q_chunks, starts))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)


def decode_span_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    cache_pos: jax.Array, cfg: ModelConfig,
    *,
    window: Optional[int] = None,
) -> jax.Array:
    """T-token span attention against an append-only (non-ring) cache.

    q: (B,T,H,D) — T consecutive tokens of one request (a speculative
    draft-verify span, or a suffix prefill behind a cached prefix);
    caches: (B,S,KV,D) at absolute slots (the paged gather view).
    cache_pos: (B,) valid token count BEFORE the span; the span's own
    k/v must already be written, query t (absolute position
    cache_pos + t) attends causally through its own position."""
    b, s, kv, d = k_cache.shape
    t = q.shape[1]
    win = window if window is not None else cfg.sliding_window
    qpos = cache_pos[:, None] + jnp.arange(t)[None, :]  # (B, T)
    spos = jnp.arange(s)[None, None, :]
    valid = spos <= qpos[..., None]  # (B, T, S)
    if win is not None:
        valid &= spos > qpos[..., None] - win
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    return _sdpa(q, k_cache, v_cache, bias)


def decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    cache_pos: jax.Array, cfg: ModelConfig,
    *,
    window: Optional[int] = None,
) -> jax.Array:
    """One-token attention against a KV cache.

    q: (B,1,H,D); caches: (B,W,KV,D) where W is the cache window (full
    seq_len, or sliding window size for SWA archs — ring-buffered).
    cache_pos: (B,) int32 — number of valid tokens (the new token's k/v must
    already be written). For ring buffers, slot i holds absolute position
    p = i + W*floor((cache_pos-1-i)/W) — validity is handled via the
    absolute-position map below.
    """
    b, w, kv, d = k_cache.shape
    h = q.shape[2]
    win = window if window is not None else cfg.sliding_window
    slot = jnp.arange(w)
    # absolute position held by each slot under ring addressing
    wraps = jnp.maximum(cache_pos[:, None] - 1 - slot[None, :], 0) // w
    abs_pos = slot[None, :] + wraps * w
    valid = abs_pos < cache_pos[:, None]
    if win is not None:
        valid &= abs_pos >= (cache_pos[:, None] - win)
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)  # (B,W)
    kf = _expand_kv(k_cache, h)
    vf = _expand_kv(v_cache, h)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, kf,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    scores = scores + bias[:, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", probs.astype(vf.dtype), vf)
    return out.astype(q.dtype)
