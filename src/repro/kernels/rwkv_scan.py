"""RWKV-6 chunked WKV Pallas kernel (data-dependent-decay linear attention).

TPU adaptation of the recurrence S_t = diag(w_t) S_{t-1} + k_t^T v_t,
out_t = r_t (S_{t-1} + diag(u) k_t^T v_t): instead of a token-serial loop
(VPU-bound, no MXU work), the sequence is processed in chunks whose
intra-chunk interactions are (chunk x chunk) MXU matmuls with bounded
exponents (per-step log-decay clamped, matching models/rwkv6.DECAY_CLAMP),
while the (hd x hd) state matrix lives in VMEM scratch across the
sequential chunk grid dimension. One grid step = one chunk: stream
r/k/v/decay chunks HBM->VMEM, two small matmuls + state update, emit the
chunk's outputs. Layout (B*H, S, hd); fp32 throughout (the state is a
running sum — range matters, the paper's BF16 lesson in reverse).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_ref, *,
                chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0]  # (c, hd) fp32
    k = k_ref[0]
    v = v_ref[0]
    lw = lw_ref[0]  # per-step log decay, <= 0, clamped
    u = u_ref[0]  # (1, hd) bonus

    # (c, hd) within-chunk inclusive prefix sum, as a lower-triangular
    # matmul (Mosaic lowers no cumsum); HIGHEST keeps it f32, since the
    # sums feed exp()
    t_row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    t_col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    cum = jax.lax.dot_general(
        (t_row >= t_col).astype(jnp.float32), lw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    total = jnp.sum(lw, axis=0, keepdims=True)  # (1, hd)
    cum_excl = cum - lw

    # inter-chunk: r_t reads state decayed from chunk start to t-1
    r_in = r * jnp.exp(cum_excl)
    inter = jax.lax.dot_general(
        r_in, s_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # (c, hd_v)

    # intra-chunk: scores[t,s] = sum_d r_t k_s exp(cum_excl[t]-cum[s]), s<t
    k_neg = k * jnp.exp(-cum)  # bounded by exp(chunk*|clamp|)
    scores = jax.lax.dot_general(
        r_in, k_neg, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (c, c)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(t_idx > s_idx, scores, 0.0)
    intra = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # current-token bonus: (r_t . (u*k_t)) v_t
    bonus = jnp.sum(r * k * u, axis=-1, keepdims=True)  # (c, 1)
    o_ref[0, ...] = inter + intra + bonus * v

    # state update: S' = diag(exp(total)) S + sum_s (k_s exp(total-cum_s))^T v_s
    k_out = k * jnp.exp(total - cum)
    delta = jax.lax.dot_general(
        k_out, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # (hd_k, hd_v)
    # the same total as a (hd_k, 1) column, to scale the state's rows
    total_col = jax.lax.dot_general(
        lw, jnp.ones((chunk, 1), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    s_ref[...] = s_ref[...] * jnp.exp(total_col) + delta


def rwkv_wkv(
    r: Array, k: Array, v: Array, logw: Array, u: Array, *,
    chunk: int = 16,
    interpret: bool = False,
) -> Array:
    """r,k,v,logw: (BH, S, hd) fp32; u: (BH, hd). Returns (BH, S, hd).

    logw must already be clamped to >= DECAY_CLAMP (the wrapper does it)."""
    bh, s, hd = r.shape
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"seq {s} % chunk {chunk}")
    grid = (bh, s // chunk)
    return pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda h, c: (h, c, 0)),
            # (1, 1, hd): a block's last two dims must be (8k, 128k) or
            # the array's own, so u carries a unit axis
            pl.BlockSpec((1, 1, hd), lambda h, c: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), lambda h, c: (h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u[:, None, :])
