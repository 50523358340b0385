"""Elementary model ops: norms, activations, embeddings, RoPE/M-RoPE, loss.

All ops compute in fp32 where numerics matter (norms, softmax, loss) and
return the caller's compute dtype, mirroring TPU practice (bf16 MXU inputs,
fp32 accumulation — the paper's BF16 story).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(dtype)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x.astype(jnp.float32)).astype(x.dtype)


def embed_lookup(table: jax.Array, tokens: jax.Array,
                 compute_dtype) -> jax.Array:
    """Token embedding lookup. With a vocab-sharded table, XLA turns this
    into the SparseCore-style gather + cross-shard combine."""
    return jnp.take(table, tokens, axis=0).astype(compute_dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)  # (half,)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope_interleaved(x: jax.Array, positions: jax.Array,
                           theta: float) -> jax.Array:
    """RoPE over interleaved pairs, as DeepSeek-V3's
    ``apply_rotary_pos_emb`` applies it: the (even, odd) lanes are first
    gathered into halves, then rotated as ``apply_rope`` does. The result
    stays in the halves layout (q and k alike, so q.k is unchanged)."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, theta)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Tuple[int, ...]) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): the rotary half-dim is split into
    sections (temporal, height, width), each rotated by its own position
    stream. positions: (3, ..., S). For text, all three streams coincide and
    M-RoPE reduces to RoPE."""
    half = x.shape[-1] // 2
    if sum(sections) != half:
        raise ValueError(f"mrope sections {sections} != half dim {half}")
    freqs = rope_freqs(x.shape[-1], theta)  # (half,)
    # build per-frequency position selector
    sel = []
    for i, s in enumerate(sections):
        sel.extend([i] * s)
    sel_arr = jnp.asarray(sel)  # (half,) in {0,1,2}
    # positions: (3, ..., S) -> (..., S, half)
    pos = jnp.moveaxis(positions, 0, -1).astype(jnp.float32)  # (..., S, 3)
    pos_per_freq = jnp.take(pos, sel_arr, axis=-1)  # (..., S, half)
    angles = pos_per_freq[..., None, :] * freqs  # (..., S, 1, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def softmax_cross_entropy(
    logits: jax.Array, labels: jax.Array,
    mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Stable token-mean cross entropy in fp32 over (possibly vocab-sharded)
    logits. Returns (mean_loss, token_count)."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        nll = nll * mask
        count = jnp.maximum(mask.sum(), 1.0)
    else:
        count = jnp.asarray(nll.size, jnp.float32)
    return nll.sum() / count, count
