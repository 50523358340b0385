"""Decode attention Pallas kernel: one query token vs a long KV cache.

Memory-bound by design (the roofline term that dominates decode cells):
the kernel's job is to stream the KV cache through VMEM exactly once at
full HBM bandwidth while the (tiny) query stays resident. Blockwise over
the cache length with an online-softmax running state, GQA-aware: the
query block carries all heads of one sequence; each KV head is used by
n_heads/n_kv query heads via in-VMEM reshape (no HBM duplication —
SparseCore-style "read once, use many").

Validity masking supports ring buffers: slot i holds absolute position
i + W*wraps (see models/attention.decode_attention, the jnp oracle).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import BUDGET_BYTES, largest_block, tile_bytes

Array = jax.Array
NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *,
                   block_k: int, n_k: int, window: Optional[int],
                   cache_len: int, scale: float, groups: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # per-request valid token count (prefetched); continuous batching
    # serves different sequence lengths in one lockstep batch
    pos = pos_ref[pl.program_id(0)]
    q = q_ref[0].astype(jnp.float32) * scale  # (H, d)
    k = k_ref[0].astype(jnp.float32)          # (bk, KV, d)
    bk, kv, d = k.shape
    h = q.shape[0]
    # GQA: fold q heads into (KV, groups) so scores come from one batched dot
    qg = q.reshape(kv, groups, d)
    scores = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)  # (KV, groups, bk)

    slot = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk), 2)
    wraps = jnp.maximum(pos - 1 - slot, 0) // cache_len
    abs_pos = slot + wraps * cache_len
    valid = abs_pos < pos
    if window is not None:
        valid &= abs_pos >= pos - window
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_ref[...]  # (KV, groups)
    m_new = jnp.maximum(m_prev, scores.max(axis=-1))
    p = jnp.exp(scores - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    v_f = v_ref[0].astype(jnp.float32)  # (bk, KV, d)
    pv = jax.lax.dot_general(
        p, v_f, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)  # (KV, groups, d)
    acc_ref[...] = acc_ref[...] * corr[..., None] + pv
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _store():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        out = (acc_ref[...] / denom).reshape(h, d)
        o_ref[0, ...] = out.astype(o_ref.dtype)


def dense_block_k(w: int, kv: int, d: int, dtype) -> int:
    """Cache rows per grid step: the largest divisor of the window whose
    blocks fit the VMEM budget. Counted: the k and v blocks, each double-
    buffered, plus their f32 working copies (upcast and relayout), all at
    padded tile size — kv=2 heads pad to a full 8-row sublane tile."""
    def fits(bk: int) -> bool:
        stream = 4 * tile_bytes((bk, kv, d), dtype)
        work = 4 * tile_bytes((bk, kv, d), jnp.float32)
        return stream + work <= BUDGET_BYTES
    return largest_block(w, fits, align=8)


def decode_attention(
    q: Array, k_cache: Array, v_cache: Array, pos: Array, *,
    window: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> Array:
    """q: (B, H, D); caches: (B, W, KV, D); pos: (B,) int32 (uniform).
    ``block_k`` None sizes the cache block from VMEM (``dense_block_k``).
    Returns (B, H, D)."""
    b, h, d = q.shape
    _, w, kv, _ = k_cache.shape
    groups = h // kv
    if block_k is None:
        block_k = dense_block_k(w, kv, d, k_cache.dtype)
    block_k = min(block_k, w)
    if w % block_k:
        raise ValueError(f"cache window {w} % block {block_k}")
    n_k = w // block_k
    grid = (b, n_k)
    scale = d ** -0.5
    return pl.pallas_call(
        functools.partial(
            _decode_kernel, block_k=block_k, n_k=n_k, window=window,
            cache_len=w, scale=scale, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, h, d), lambda i, j, pos_ref: (i, 0, 0)),
                pl.BlockSpec((1, block_k, kv, d),
                             lambda i, j, pos_ref: (i, j, 0, 0)),
                pl.BlockSpec((1, block_k, kv, d),
                             lambda i, j, pos_ref: (i, j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, h, d),
                                   lambda i, j, pos_ref: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((kv, groups), jnp.float32),
                pltpu.VMEM((kv, groups), jnp.float32),
                pltpu.VMEM((kv, groups, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(pos, q, k_cache, v_cache)


# ---------------------------------------------------------------------------
# Paged variant: KV lives in a shared (N, P, KV, D) page pool; each grid
# step DMAs one *page* selected through the scalar-prefetched page table —
# the BlockSpec index_map reads ``table[b, j]``, so the gather happens at
# DMA-issue time with no HBM materialization of a contiguous cache
# (vLLM-style paged attention as a Pallas grid). int8 pools are
# quantization-native: the page-aligned (N, P, KV) scale pages ride the
# same table entry as their KV page and dequantize in VMEM, so a
# quantized cache streams half the HBM bytes per token instead of paying
# a gather-dequant materialization (the Ironwood int8-KV memory lever).
# ---------------------------------------------------------------------------


def _paged_decode_kernel(pos_ref, table_ref, q_ref, k_ref, v_ref, *rest,
                         page_size: int, n_pages: int,
                         window: Optional[int], scale: float, groups: int,
                         quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    ib, ij = pl.program_id(0), pl.program_id(1)

    @pl.when(ij == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[ib]
    q = q_ref[0].astype(jnp.float32) * scale  # (H, d)
    k = k_ref[0].astype(jnp.float32)          # (P, KV, d)
    if quantized:
        # in-VMEM dequant: int8 page bytes streamed from HBM, scale page
        # (P, KV) DMA'd through the same table entry
        k = k * ks_ref[0].astype(jnp.float32)[..., None]
    p, kv, d = k.shape
    h = q.shape[0]
    qg = q.reshape(kv, groups, d)
    scores = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)  # (KV, groups, P)

    # pages are append-only (no ring): absolute position == global slot
    abs_pos = ij * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, p), 2)
    valid = abs_pos < pos
    if window is not None:
        valid &= abs_pos >= pos - window
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1))
    pr = jnp.exp(scores - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + pr.sum(axis=-1)
    v_f = v_ref[0].astype(jnp.float32)
    if quantized:
        v_f = v_f * vs_ref[0].astype(jnp.float32)[..., None]
    pv = jax.lax.dot_general(
        pr, v_f, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[..., None] + pv
    m_ref[...] = m_new

    @pl.when(ij == n_pages - 1)
    def _store():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0, ...] = (acc_ref[...] / denom).reshape(h, d).astype(
            o_ref.dtype)


def _paged_span_kernel(pos_ref, table_ref, q_ref, k_ref, v_ref, *rest,
                       page_size: int, n_pages: int,
                       window: Optional[int], scale: float, groups: int,
                       quantized: bool):
    """k-token-query variant of ``_paged_decode_kernel``.

    The query block carries ``span`` consecutive tokens of one sequence
    (speculative draft-verify, a suffix prefill behind a cached prefix,
    or one chunk of a cold chunked prefill). Query t sits at absolute
    position ``pos + t`` and is masked causally against the streamed
    pages — the online-softmax state gains a span axis, everything else
    is the one-pass page stream."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    ib, ij = pl.program_id(0), pl.program_id(1)

    @pl.when(ij == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[ib]
    q = q_ref[0].astype(jnp.float32) * scale  # (T, H, d)
    k = k_ref[0].astype(jnp.float32)          # (P, KV, d)
    if quantized:
        k = k * ks_ref[0].astype(jnp.float32)[..., None]
    p, kv, d = k.shape
    t, h = q.shape[0], q.shape[1]
    qg = q.reshape(t, kv, groups, d)
    # batch over KV heads, contract d: (KV, T, groups, P) in one dot
    scores = jax.lax.dot_general(
        qg, k, (((3,), (2,)), ((1,), (1,))),
        preferred_element_type=jnp.float32)

    # pages are append-only: absolute position == global slot. Query t
    # (position pos + t) sees positions <= its own.
    t_iota = jax.lax.broadcasted_iota(jnp.int32, (1, t, 1, p), 1)
    abs_pos = ij * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, t, 1, p), 3)
    qpos = pos + t_iota
    valid = abs_pos <= qpos
    if window is not None:
        valid &= abs_pos > qpos - window
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_ref[...]  # (KV, T, groups)
    m_new = jnp.maximum(m_prev, scores.max(axis=-1))
    pr = jnp.exp(scores - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + pr.sum(axis=-1)
    v_f = v_ref[0].astype(jnp.float32)  # (P, KV, d)
    if quantized:
        v_f = v_f * vs_ref[0].astype(jnp.float32)[..., None]
    pv = jax.lax.dot_general(
        pr, v_f, (((3,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)  # (KV, T, groups, d)
    acc_ref[...] = acc_ref[...] * corr[..., None] + pv
    m_ref[...] = m_new

    @pl.when(ij == n_pages - 1)
    def _store():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        out = jnp.swapaxes(acc_ref[...] / denom, 0, 1)  # (T, KV, groups, d)
        o_ref[0, ...] = out.reshape(t, h, d).astype(o_ref.dtype)


def _scale_specs(quantized: bool, p: int, kv: int):
    """BlockSpecs for the (N, P, KV) scale pages: one (1, P, KV) scale
    block rides the same scalar-prefetched table entry as its KV page."""
    if not quantized:
        return []
    return [pl.BlockSpec((1, p, kv),
                         lambda i, j, pos_ref, tab_ref:
                         (tab_ref[i, j], 0, 0))] * 2


def paged_decode_span_attention(
    q: Array, k_pages: Array, v_pages: Array, page_table: Array,
    pos: Array, *,
    k_scale: Optional[Array] = None,
    v_scale: Optional[Array] = None,
    window: Optional[int] = None,
    interpret: bool = False,
) -> Array:
    """q: (B, T, H, D) — T consecutive query tokens per sequence at
    absolute positions ``pos .. pos + T - 1`` (the span's own k/v must
    already be written to the pages). Other args as
    ``paged_decode_attention``. Returns (B, T, H, D)."""
    b, t, h, d = q.shape
    n, p, kv, _ = k_pages.shape
    m = page_table.shape[1]
    groups = h // kv
    grid = (b, m)
    scale = d ** -0.5
    quantized = k_scale is not None
    operands = (q, k_pages, v_pages) + (
        (k_scale, v_scale) if quantized else ())
    return pl.pallas_call(
        functools.partial(
            _paged_span_kernel, page_size=p, n_pages=m,
            window=window, scale=scale, groups=groups,
            quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, t, h, d),
                             lambda i, j, pos_ref, tab_ref: (i, 0, 0, 0)),
                pl.BlockSpec((1, p, kv, d),
                             lambda i, j, pos_ref, tab_ref:
                             (tab_ref[i, j], 0, 0, 0)),
                pl.BlockSpec((1, p, kv, d),
                             lambda i, j, pos_ref, tab_ref:
                             (tab_ref[i, j], 0, 0, 0)),
                *_scale_specs(quantized, p, kv),
            ],
            out_specs=pl.BlockSpec((1, t, h, d),
                                   lambda i, j, pos_ref, tab_ref:
                                   (i, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((kv, t, groups), jnp.float32),
                pltpu.VMEM((kv, t, groups), jnp.float32),
                pltpu.VMEM((kv, t, groups, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, h, d), q.dtype),
        interpret=interpret,
    )(pos, page_table, *operands)


def paged_decode_attention(
    q: Array, k_pages: Array, v_pages: Array, page_table: Array,
    pos: Array, *,
    k_scale: Optional[Array] = None,
    v_scale: Optional[Array] = None,
    window: Optional[int] = None,
    interpret: bool = False,
) -> Array:
    """q: (B, H, D); pages: (N, P, KV, D); page_table: (B, M) int32 page
    ids (unused entries point at the reserved trash page 0); pos: (B,)
    per-request valid token count. Returns (B, H, D).

    int8 pages stream natively: pass the page-aligned ``k_scale`` /
    ``v_scale`` pools (N, P, KV) and the kernel DMAs each scale page
    through the same table entry as its KV page, dequantizing in VMEM —
    half the HBM bytes per token, no gather materialization.
    """
    b, h, d = q.shape
    n, p, kv, _ = k_pages.shape
    m = page_table.shape[1]
    groups = h // kv
    grid = (b, m)
    scale = d ** -0.5
    quantized = k_scale is not None
    operands = (q, k_pages, v_pages) + (
        (k_scale, v_scale) if quantized else ())
    return pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, page_size=p, n_pages=m, window=window,
            scale=scale, groups=groups, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, h, d),
                             lambda i, j, pos_ref, tab_ref: (i, 0, 0)),
                pl.BlockSpec((1, p, kv, d),
                             lambda i, j, pos_ref, tab_ref:
                             (tab_ref[i, j], 0, 0, 0)),
                pl.BlockSpec((1, p, kv, d),
                             lambda i, j, pos_ref, tab_ref:
                             (tab_ref[i, j], 0, 0, 0)),
                *_scale_specs(quantized, p, kv),
            ],
            out_specs=pl.BlockSpec((1, h, d),
                                   lambda i, j, pos_ref, tab_ref: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((kv, groups), jnp.float32),
                pltpu.VMEM((kv, groups), jnp.float32),
                pltpu.VMEM((kv, groups, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(pos, page_table, *operands)
