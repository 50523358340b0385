"""Jit'd public wrappers for the Pallas kernels.

``impl`` selects: "pallas" (TPU target), "interpret" (CPU validation of the
kernel body), "ref" (pure-jnp oracle). Model code calls these through
ModelContext.attn_impl-style switches; tests sweep impl x shapes x dtypes.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pl
from repro.kernels.decode_attention import \
    paged_decode_attention as _paged_decode_pl
from repro.kernels.decode_attention import \
    paged_decode_span_attention as _paged_span_pl
from repro.kernels.flash_attention import flash_attention as _flash_pl
from repro.kernels.matmul import matmul as _matmul_pl
from repro.kernels.moe_gemm import grouped_matmul as _grouped_pl
from repro.kernels.rwkv_scan import rwkv_wkv as _wkv_pl
from repro.kernels.sparse_gather import sparse_gather_sum as _gather_pl

Array = jax.Array


@partial(jax.jit, static_argnames=("impl", "out_dtype", "block_m",
                                   "block_n", "block_k"))
def matmul(a: Array, b: Array, *, impl: str = "pallas", out_dtype=None,
           block_m: int = 256, block_n: int = 256,
           block_k: int = 512) -> Array:
    if impl == "ref":
        return ref.matmul_ref(a, b, out_dtype)
    return _matmul_pl(a, b, out_dtype=out_dtype, block_m=block_m,
                      block_n=block_n, block_k=block_k,
                      interpret=impl == "interpret")


@partial(jax.jit, static_argnames=("impl", "causal", "window",
                                   "block_q", "block_k"))
def flash_attention(q: Array, k: Array, v: Array, *, impl: str = "pallas",
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128) -> Array:
    """(BH, S, D) in/out."""
    if impl == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal,
                                       window=window)
    return _flash_pl(q, k, v, causal=causal, window=window,
                     block_q=block_q, block_k=block_k,
                     interpret=impl == "interpret")


@partial(jax.jit, static_argnames=("impl", "window", "block_k"))
def decode_attention(q: Array, k_cache: Array, v_cache: Array, pos: Array,
                     *, impl: str = "pallas",
                     window: Optional[int] = None,
                     block_k: Optional[int] = None) -> Array:
    if impl == "ref":
        return ref.decode_attention_ref(q, k_cache, v_cache, pos,
                                        window=window)
    return _decode_pl(q, k_cache, v_cache, pos, window=window,
                      block_k=block_k, interpret=impl == "interpret")


# -- paged attention: single-host impls + shard_map mesh wiring -------------
#
# On a serving (data, model) mesh the paged kernels stay PER-SHARD: shard_map
# splits queries on the head axis over "model" (and batch over "data"), each
# shard streaming its local KV-head slice of the page pool through the
# unchanged kernel body. KV placement follows the GQA divisibility story:
#   * kv % model_size == 0  — pool sharded on the KV-head axis (true TP);
#   * otherwise             — pool replicated (the AxisRules fallback) and
#     each shard dynamic-slices the KV groups its local Q heads map to,
#     provided the per-shard head block stays group-aligned;
#   * irregular splits      — heads replicated too (no model partition).
# The host page table and positions are broadcast (or batch-sharded), so
# every shard addresses pages identically and CoW/prefix logic is untouched.


def _paged_decode_local(q, k_pages, v_pages, page_table, pos, k_scale,
                        v_scale, impl, window):
    if impl == "ref":
        kg = ref.paged_gather_dequant_ref(k_pages, page_table, k_scale,
                                          q.dtype)
        vg = ref.paged_gather_dequant_ref(v_pages, page_table, v_scale,
                                          q.dtype)
        return ref.decode_attention_ref(q, kg, vg, pos, window=window)
    return _paged_decode_pl(q, k_pages, v_pages, page_table, pos,
                            k_scale=k_scale, v_scale=v_scale,
                            window=window, interpret=impl == "interpret")


def _paged_span_local(q, k_pages, v_pages, page_table, pos, k_scale,
                      v_scale, impl, window):
    if impl == "ref":
        kg = ref.paged_gather_dequant_ref(k_pages, page_table, k_scale,
                                          q.dtype)
        vg = ref.paged_gather_dequant_ref(v_pages, page_table, v_scale,
                                          q.dtype)
        return ref.decode_span_attention_ref(q, kg, vg, pos, window=window)
    return _paged_span_pl(q, k_pages, v_pages, page_table, pos,
                          k_scale=k_scale, v_scale=v_scale,
                          window=window, interpret=impl == "interpret")


def _mesh_axis_size(mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def _paged_partition(mesh, data_axis, model_axis, b, h, kv):
    """Static plan for splitting paged attention over a (data, model) mesh.

    Returns (data_spec_axis, head_spec_axis, kv_spec_axis, slice_kv,
    model_size). ``slice_kv`` marks the replicated-KV GQA fallback where
    each shard dynamic-slices its local KV groups out of the full pool."""
    d = _mesh_axis_size(mesh, data_axis)
    m = _mesh_axis_size(mesh, model_axis)
    db = data_axis if (d > 1 and b % d == 0) else None
    hm = model_axis if (m > 1 and h % m == 0) else None
    kvm = None
    slice_kv = False
    if hm is not None:
        if kv % m == 0:
            kvm = model_axis  # KV pool shards with the Q heads (true TP)
        else:
            h_local, g = h // m, h // kv
            if h_local % g == 0 or g % h_local == 0:
                slice_kv = True  # replicated pool, group-aligned local view
            else:
                hm = None  # irregular group split: replicate heads too
    return db, hm, kvm, slice_kv, m


def _local_kv_slice(arrs, model_axis, h, kv, m):
    """Inside shard_map with replicated pools: slice the KV-head groups
    that shard ``axis_index(model_axis)``'s local Q heads map to. Local
    head j then sees local KV head j // (h_local // kv_local), matching
    the global GQA grouping because the head block is group-aligned."""
    idx = jax.lax.axis_index(model_axis)
    h_local, g = h // m, h // kv
    kv_local = max(1, h_local // g)
    start = (idx * h_local) // g
    return [None if a is None else
            jax.lax.dynamic_slice_in_dim(a, start, kv_local, axis=2)
            for a in arrs]


def _paged_sharded(local_fn, mesh, data_axis, model_axis, head_axis, q,
                   k_pages, v_pages, page_table, pos, k_scale, v_scale):
    b, h, kv = q.shape[0], q.shape[head_axis], k_pages.shape[2]
    db, hm, kvm, slice_kv, m = _paged_partition(
        mesh, data_axis, model_axis, b, h, kv)
    if db is None and hm is None:
        return local_fn(q, k_pages, v_pages, page_table, pos, k_scale,
                        v_scale)
    qaxes = [db] + [None] * (q.ndim - 1)
    qaxes[head_axis] = hm
    qspec = P(*qaxes)
    pspec, sspec = P(None, None, kvm, None), P(None, None, kvm)
    operands = [q, k_pages, v_pages, page_table, pos]
    specs = [qspec, pspec, pspec, P(db, None), P(db)]
    has_scale = k_scale is not None
    if has_scale:
        operands += [k_scale, v_scale]
        specs += [sspec, sspec]

    def body(*xs):
        ql, kp, vp, tab, posl = xs[:5]
        ks, vs = (xs[5], xs[6]) if has_scale else (None, None)
        if slice_kv:
            kp, vp, ks, vs = _local_kv_slice([kp, vp, ks, vs],
                                             model_axis, h, kv, m)
        return local_fn(ql, kp, vp, tab, posl, ks, vs)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(specs),
                         out_specs=qspec, check_vma=False)(*operands)


@partial(jax.jit, static_argnames=("impl", "window", "mesh", "data_axis",
                                   "model_axis"))
def paged_decode_attention(q: Array, k_pages: Array, v_pages: Array,
                           page_table: Array, pos: Array, *,
                           k_scale: Optional[Array] = None,
                           v_scale: Optional[Array] = None,
                           impl: str = "pallas",
                           window: Optional[int] = None,
                           mesh=None, data_axis: str = "data",
                           model_axis: str = "model") -> Array:
    """q: (B,H,D); pages (N,P,KV,D); page_table (B,M); pos (B,).

    int8 pages stream natively when the (N,P,KV) ``k_scale``/``v_scale``
    pools are passed: the kernel dequantizes in VMEM, page by page.
    "ref" gathers (and dequantizes) the pages and reuses the dense ring
    oracle (no wraps: every absolute position is < M*P by construction).
    ``mesh``: when set, shard_map the call over (data_axis, model_axis) —
    heads split over "model", batch over "data", KV pool sharded or
    replicate-and-sliced per the GQA plan above."""
    local = partial(_paged_decode_local, impl=impl, window=window)
    if mesh is not None:
        return _paged_sharded(local, mesh, data_axis, model_axis, 1, q,
                              k_pages, v_pages, page_table, pos, k_scale,
                              v_scale)
    return local(q, k_pages, v_pages, page_table, pos, k_scale, v_scale)


@partial(jax.jit, static_argnames=("impl", "window", "mesh", "data_axis",
                                   "model_axis"))
def paged_decode_span_attention(q: Array, k_pages: Array, v_pages: Array,
                                page_table: Array, pos: Array, *,
                                k_scale: Optional[Array] = None,
                                v_scale: Optional[Array] = None,
                                impl: str = "pallas",
                                window: Optional[int] = None,
                                mesh=None, data_axis: str = "data",
                                model_axis: str = "model") -> Array:
    """k-token-query paged decode. q: (B,T,H,D) — T consecutive tokens
    per sequence at absolute positions ``pos .. pos+T-1`` (speculative
    verify / suffix prefill / chunked cold prefill); pages (N,P,KV,D);
    page_table (B,M); pos (B,) valid count BEFORE the span. int8 pages
    stream natively via ``k_scale``/``v_scale``. ``mesh`` shard_maps the
    call exactly like paged_decode_attention (head axis 2 here).
    Returns (B,T,H,D)."""
    local = partial(_paged_span_local, impl=impl, window=window)
    if mesh is not None:
        return _paged_sharded(local, mesh, data_axis, model_axis, 2, q,
                              k_pages, v_pages, page_table, pos, k_scale,
                              v_scale)
    return local(q, k_pages, v_pages, page_table, pos, k_scale, v_scale)


# -- grouped MoE GEMM: single-host impl + shard_map expert parallelism -----


def _grouped_local(x, w, group_ids, w_scale, impl, block_f):
    if impl == "ref":
        return ref.grouped_matmul_ref(x, w, group_ids, w_scale=w_scale)
    return _grouped_pl(x, w, group_ids, w_scale=w_scale, block_f=block_f,
                       interpret=impl == "interpret")


@partial(jax.jit, static_argnames=("impl", "block_f", "mesh",
                                   "expert_axis"))
def grouped_matmul(x: Array, w: Array, group_ids: Array, *,
                   w_scale: Optional[Array] = None,
                   impl: str = "pallas", block_f: Optional[int] = None,
                   mesh=None, expert_axis: str = "data") -> Array:
    """m-grouped contiguous GEMM over expert-sorted token rows.

    x: (M, D) sorted+padded rows; w: (E, D, F); group_ids (M // block_m,)
    expert id per m-tile (-1 = pad tile -> zero rows). ``w_scale`` (E,)
    dequantizes int8 expert weights inside the kernel.

    ``mesh``: when set, shard_map the call with experts sharded over
    ``expert_axis`` (the "data" mesh axis, matching AxisRules' "expert"
    placement): each shard keeps its contiguous E/ep slice of ``w``,
    rewrites global tile ids into its local range (-1 elsewhere, so
    non-local tiles produce zeros), and a psum restores the full (M, F)
    output — every tile is owned by exactly one shard. Experts that
    don't divide the axis fall back to replicated weights (the same
    divisibility story as the paged-attention GQA fallback)."""
    e = w.shape[0]
    local = partial(_grouped_local, impl=impl, block_f=block_f)
    ep = _mesh_axis_size(mesh, expert_axis) if mesh is not None else 1
    if ep <= 1 or e % ep:
        return local(x, w, group_ids, w_scale)
    e_local = e // ep
    has_scale = w_scale is not None

    def body(xl, wl, gids, *rest):
        sl = rest[0] if has_scale else None
        lo = jax.lax.axis_index(expert_axis) * e_local
        g = gids - lo
        g = jnp.where((g >= 0) & (g < e_local), g, -1)
        out = local(xl, wl, g, sl)
        return jax.lax.psum(out, expert_axis)

    operands = [x, w, group_ids]
    specs = [P(None, None), P(expert_axis, None, None), P(None)]
    if has_scale:
        operands.append(w_scale)
        specs.append(P(expert_axis))
    return jax.shard_map(body, mesh=mesh, in_specs=tuple(specs),
                         out_specs=P(None, None), check_vma=False)(*operands)


@partial(jax.jit, static_argnames=("impl", "chunk"))
def rwkv_wkv(r: Array, k: Array, v: Array, logw: Array, u: Array, *,
             impl: str = "pallas", chunk: int = 16) -> Array:
    if impl == "ref":
        return ref.rwkv_wkv_ref(r, k, v, logw, u)
    return _wkv_pl(r, k, v, logw, u, chunk=chunk,
                   interpret=impl == "interpret")


@partial(jax.jit, static_argnames=("impl",))
def sparse_gather_sum(table: Array, indices: Array, weights: Array, *,
                      impl: str = "pallas") -> Array:
    if impl == "ref":
        return ref.sparse_gather_sum_ref(table, indices, weights)
    return _gather_pl(table, indices, weights,
                      interpret=impl == "interpret")
