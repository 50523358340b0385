"""Flash attention Pallas kernel (training/prefill; causal / SWA / bidir).

Online-softmax blockwise attention: q blocks stay resident in VMEM while
k/v blocks stream HBM->VMEM; the running (max, sum, acc) state lives in
VMEM scratch across the kv grid dimension. Scores are computed on the MXU
(q@k^T as a (block_q x d) x (d x block_k) matmul, fp32 accumulation),
masking is positional (no (S x S) mask tensor ever exists — the paper's
software-managed-memory discipline).

Layout: (B*H, S, D) — heads flattened into the grid's leading dimension.
Block sizes default to 128 (MXU-aligned); head_dim rides as the minor
dimension (Mosaic pads to lanes).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import dot_precision

Array = jax.Array
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q: int, block_k: int, n_k: int, causal: bool,
                  window: Optional[int], scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
    k = k_ref[0].astype(jnp.float32)  # (bk, d)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (bq, bk)

    qpos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    allowed = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        allowed &= qpos >= kpos
    if window is not None:
        allowed &= (qpos - kpos) < window
    scores = jnp.where(allowed, scores, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1))
    p = jnp.exp(scores - m_new[:, None])
    correction = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * correction + p.sum(axis=-1)
    pv = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        precision=dot_precision(v_ref.dtype),
        preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * correction[:, None] + pv
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _store():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, ...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q: Array, k: Array, v: Array, *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> Array:
    """q,k,v: (BH, S, D) -> (BH, S, D)."""
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq {s} not divisible by blocks "
                         f"({block_q},{block_k})")
    scale = d ** -0.5
    n_k = s // block_k
    grid = (bh, s // block_q, n_k)
    return pl.pallas_call(
        functools.partial(
            _flash_kernel, block_q=block_q, block_k=block_k, n_k=n_k,
            causal=causal, window=window, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
