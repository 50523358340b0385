"""Flash attention Pallas kernels (training/prefill; causal / SWA / bidir),
differentiable through a custom VJP.

Online-softmax blockwise attention: q blocks stay resident in VMEM while
k/v blocks stream HBM->VMEM; the running (max, sum, acc) state lives in
VMEM scratch across the kv grid dimension. Masking is positional (no
(S x S) mask tensor ever exists — the paper's software-managed-memory
discipline). The forward also writes each row's logsumexp, the one
residual the backward needs besides q, k, v and o. Two backward kernels
recompute ``P = exp(S - lse)`` block by block:

  flash_bwd_dq   q block resident, k/v blocks stream: dQ = dS K;
  flash_bwd_dkv  k/v block resident, q blocks stream in a fixed order:
                 dV = P^T dO, dK = dS^T Q (bit-identical run to run).

with ``dS = P * (dP - D)`` and ``D = rowsum(dO * O)`` computed once in XLA.
The dkv kernel works on transposed scores (keys on rows), so lse and D
are read as lane-dense (1, block) rows there; the dq kernel turns its q
block's rows into columns once, when the block starts.

Blocks wholly above the causal diagonal or outside the sliding window are
skipped: each kernel's grid walks a scalar-prefetched table of the useful
(q block, k block) pairs (``block_table``), so a skipped block costs
neither a grid step nor a DMA, and consecutive steps that keep a block
keep it in VMEM. Only blocks that straddle a mask boundary build a mask.
Bidirectional attention skips nothing.

Layout: (B*H, S, D) — heads flattened into the grid's leading dimension.
Block sizes come from the VMEM budget (``flash_block``); head_dim rides as
the minor dimension (Mosaic pads it to 128 lanes).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import (BUDGET_BYTES, dot_precision,
                                  largest_block, mxu_dtype, tile_bytes)

Array = jax.Array
NEG_INF = -1e30
LANES = 128
_TRANS_B = (((1,), (1,)), ((), ()))  # x @ y.T
_PLAIN = (((1,), (0,)), ((), ()))  # x @ y


def flash_block(s: int, d: int, dtype, mxu) -> int:
    """Rows of q and of k per grid step: the largest divisor of ``s``, a
    multiple of 128, whose working set fits the VMEM budget. Counted for
    the heaviest kernel (dkv), at padded tile size: its six (block, d)
    streams in ``dtype`` double-buffered (q, dO, k, v in; dK, dV out),
    the lse and D rows, two f32 (block, d) accumulators, and a step's two
    (block, block) dot operands (P and dS) in the MXU's ``mxu`` dtype.
    Against the v5e compiler (S 4096, d 64, f32 blocks): bf16 operands
    at 1024 x 1024 need 9.4 MiB (counted 11.1, taken); f32 operands need
    17.9 MiB there (counted 15.1, refused) and 5.7 at 512 x 512."""
    def fits(b: int) -> bool:
        stream = 2 * (6 * tile_bytes((b, d), dtype)
                      + 2 * tile_bytes((1, b), jnp.float32))
        acc = 2 * tile_bytes((b, d), jnp.float32)
        work = 2 * tile_bytes((b, b), mxu)
        return stream + acc + work <= BUDGET_BYTES
    return largest_block(s, fits, align=LANES)


FIRST, LAST, MASKED = 1, 2, 4  # block-table flags


def block_table(n_q: int, n_k: int, block_q: int, block_k: int,
                causal: bool, window: Optional[int], *,
                kv_major: bool = False) -> np.ndarray:
    """The grid's walk over the blocks: (3, n) int32 rows of q block, k
    block and flags, one column per pair of blocks that holds an allowed
    (query, key) pair; blocks wholly above the causal diagonal or outside
    the window are left out. Ordered q-major (k-major for ``kv_major``),
    ascending, so each output block is one run of steps: FIRST marks a
    run's first step, LAST its last, MASKED a block that also holds a
    disallowed pair."""
    cols = []
    outer, inner = (n_k, n_q) if kv_major else (n_q, n_k)
    for a in range(outer):
        run = []
        for b in range(inner):
            i, j = (b, a) if kv_major else (a, b)
            # q - k over the block spans [lo, hi]
            lo = i * block_q - ((j + 1) * block_k - 1)
            hi = (i + 1) * block_q - 1 - j * block_k
            some = ((not causal or hi >= 0)
                    and (window is None or lo < window))
            every = ((not causal or lo >= 0)
                     and (window is None or hi < window))
            if some:
                run.append([i, j, 0 if every else MASKED])
        if not run:
            raise ValueError(f"window {window} leaves a block with no key")
        run[0][2] |= FIRST
        run[-1][2] |= LAST
        cols += run
    return np.asarray(cols, np.int32).T


def _run_step(flags, step, causal: bool, window: Optional[int]) -> None:
    """``step(masked)`` with the mask built only where the block needs
    one."""
    if not causal and window is None:
        step(False)
        return
    masked = (flags & MASKED) != 0
    pl.when(masked)(lambda: step(True))
    pl.when(jnp.logical_not(masked))(lambda: step(False))


def _block_allowed(i, j, shape, block_q: int, block_k: int, causal: bool,
                   window: Optional[int], keys_on_rows: bool = False):
    """The allowed (query, key) pairs of block (i, j) as a ``shape`` tile
    (queries on rows, or keys for ``keys_on_rows``)."""
    q_axis, k_axis = (1, 0) if keys_on_rows else (0, 1)
    qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, k_axis)
    return _allowed(qpos, kpos, causal, window)


def _allowed(qpos: Array, kpos: Array, causal: bool,
             window: Optional[int]) -> Array:
    allowed = jnp.ones(jnp.broadcast_shapes(qpos.shape, kpos.shape),
                       jnp.bool_)
    if causal:
        allowed &= qpos >= kpos
    if window is not None:
        allowed &= (qpos - kpos) < window
    return allowed


def _dot(x: Array, y: Array, dims, mxu) -> Array:
    """One MXU contraction of VMEM blocks, in ``mxu`` operands with f32
    accumulation."""
    return jax.lax.dot_general(
        x.astype(mxu), y.astype(mxu), dims,
        precision=dot_precision(mxu), preferred_element_type=jnp.float32)


def _lanes(x: Array, n: int) -> Array:
    """A (rows, 128) lane-replicated column, widened to ``n`` lanes."""
    return x if n == LANES else jnp.tile(x, (1, n // LANES))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(qi_ref, kj_ref, fl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, block_q: int, block_k: int,
                causal: bool, window: Optional[int], scale: float, mxu):
    t = pl.program_id(1)
    i, j, flags = qi_ref[t], kj_ref[t], fl_ref[t]

    @pl.when((flags & FIRST) != 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked: bool):
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
        s = _dot(q, k_ref[0], _TRANS_B, mxu)  # (bq, bk)
        if masked:
            allowed = _block_allowed(i, j, s.shape, block_q, block_k,
                                     causal, window)
            s = jnp.where(allowed, s, NEG_INF)
        m_prev = m_ref[...]  # (bq, 128), lane-replicated
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, block_k))
        if masked and window is not None:
            # a row whose window starts past this block has no key yet:
            # keep its p at 0, not exp(NEG_INF - NEG_INF)
            p = jnp.where(allowed, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        pv = _dot(p, v_ref[0], _PLAIN, mxu)  # (bq, d)
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + pv

    _run_step(flags, step, causal, window)

    @pl.when((flags & LAST) != 0)
    def _store():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
        lse = m_ref[...] + jnp.log(l)  # (bq, 128), lane-replicated
        lse_ref[0] = lse.T[:1]  # one lane-dense (1, bq) row


def _specs(block_q: int, block_k: int, d: int):
    """BlockSpecs of a q-side block, a k-side block and a (1, block_q)
    statistics row, each placed by the scalar-prefetched block table."""
    q = pl.BlockSpec((1, block_q, d), lambda h, t, qi, kj, fl: (h, qi[t], 0))
    k = pl.BlockSpec((1, block_k, d), lambda h, t, qi, kj, fl: (h, kj[t], 0))
    row = pl.BlockSpec((1, 1, block_q),
                       lambda h, t, qi, kj, fl: (h, 0, qi[t]))
    return q, k, row


def _call(kernel, table: np.ndarray, bh: int, in_specs, out_specs,
          out_shape, scratch, interpret: bool, name: str):
    """One walk of ``table`` per head: grid (heads, table columns)."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(bh, table.shape[1]),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )


def _forward(q: Array, k: Array, v: Array, *, causal: bool,
             window: Optional[int], block_q: int, block_k: int,
             interpret: bool) -> Tuple[Array, Array]:
    bh, s, d = q.shape
    table = block_table(s // block_q, s // block_k, block_q, block_k,
                        causal, window)
    q_spec, k_spec, row = _specs(block_q, block_k, d)
    return _call(
        functools.partial(
            _fwd_kernel, block_q=block_q, block_k=block_k, causal=causal,
            window=window, scale=d ** -0.5,
            mxu=mxu_dtype(q.dtype, interpret)),
        table, bh, [q_spec, k_spec, k_spec], [q_spec, row],
        [jax.ShapeDtypeStruct((bh, s, d), q.dtype),
         jax.ShapeDtypeStruct((bh, 1, s), jnp.float32)],
        [pltpu.VMEM((block_q, LANES), jnp.float32),
         pltpu.VMEM((block_q, LANES), jnp.float32),
         pltpu.VMEM((block_q, d), jnp.float32)],
        interpret, "flash_fwd",
    )(*table, q, k, v)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _dq_kernel(qi_ref, kj_ref, fl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               di_ref, dq_ref, lse_col, di_col, acc_ref, *, block_q: int,
               block_k: int, causal: bool, window: Optional[int],
               scale: float, mxu):
    t = pl.program_id(1)
    i, j, flags = qi_ref[t], kj_ref[t], fl_ref[t]

    @pl.when((flags & FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # the q block's lse and D rows, once, as lane-replicated columns
        lse_col[...] = jnp.broadcast_to(lse_ref[0], (LANES, block_q)).T
        di_col[...] = jnp.broadcast_to(di_ref[0], (LANES, block_q)).T

    def step(masked: bool):
        q = q_ref[0].astype(jnp.float32) * scale
        s = _dot(q, k_ref[0], _TRANS_B, mxu)  # (bq, bk)
        p = jnp.exp(s - _lanes(lse_col[...], block_k))
        if masked:
            p = jnp.where(_block_allowed(i, j, s.shape, block_q, block_k,
                                         causal, window), p, 0.0)
        dp = _dot(do_ref[0], v_ref[0], _TRANS_B, mxu)  # (bq, bk)
        ds = p * (dp - _lanes(di_col[...], block_k))
        acc_ref[...] += _dot(ds, k_ref[0], _PLAIN, mxu)

    _run_step(flags, step, causal, window)

    @pl.when((flags & LAST) != 0)
    def _store():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(qi_ref, kj_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, di_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                block_q: int, block_k: int, causal: bool,
                window: Optional[int], scale: float, mxu):
    t = pl.program_id(1)
    i, j, flags = qi_ref[t], kj_ref[t], fl_ref[t]

    @pl.when((flags & FIRST) != 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked: bool):
        q = q_ref[0].astype(jnp.float32) * scale
        do = do_ref[0]
        st = _dot(k_ref[0], q, _TRANS_B, mxu)  # (bk, bq): keys on rows
        pt = jnp.exp(st - lse_ref[0])  # lse as a (1, bq) row
        if masked:
            pt = jnp.where(_block_allowed(
                i, j, st.shape, block_q, block_k, causal, window,
                keys_on_rows=True), pt, 0.0)
        dv_acc[...] += _dot(pt, do, _PLAIN, mxu)  # (bk, d)
        dpt = _dot(v_ref[0], do, _TRANS_B, mxu)  # (bk, bq)
        dst = pt * (dpt - di_ref[0])
        dk_acc[...] += _dot(dst, q, _PLAIN, mxu)

    _run_step(flags, step, causal, window)

    @pl.when((flags & LAST) != 0)
    def _store():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _backward(q, k, v, o, lse, do, *, causal: bool, window: Optional[int],
              block_q: int, block_k: int, interpret: bool):
    bh, s, d = q.shape
    n_q, n_k = s // block_q, s // block_k
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1)[:, None, :]  # (bh, 1, s)
    kw = dict(block_q=block_q, block_k=block_k, causal=causal,
              window=window, scale=d ** -0.5,
              mxu=mxu_dtype(q.dtype, interpret))
    q_spec, k_spec, row = _specs(block_q, block_k, d)
    ins = [q_spec, k_spec, k_spec, q_spec, row, row]
    table = block_table(n_q, n_k, block_q, block_k, causal, window)
    dq = _call(
        functools.partial(_dq_kernel, **kw), table, bh, ins, q_spec,
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((block_q, LANES), jnp.float32),
         pltpu.VMEM((block_q, LANES), jnp.float32),
         pltpu.VMEM((block_q, d), jnp.float32)],
        interpret, "flash_bwd_dq",
    )(*table, q, k, v, do, lse, di)
    # k-major walk: each k block sums its q blocks in one fixed order
    table = block_table(n_q, n_k, block_q, block_k, causal, window,
                        kv_major=True)
    dk, dv = _call(
        functools.partial(_dkv_kernel, **kw), table, bh, ins,
        [k_spec, k_spec],
        [jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((block_k, d), jnp.float32),
         pltpu.VMEM((block_k, d), jnp.float32)],
        interpret, "flash_bwd_dkv",
    )(*table, q, k, v, do, lse, di)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Differentiable entry point
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, block_q, block_k, interpret):
    return _forward(q, k, v, causal=causal, window=window, block_q=block_q,
                    block_k=block_k, interpret=interpret)[0]


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    o, lse = _forward(q, k, v, causal=causal, window=window,
                      block_q=block_q, block_k=block_k, interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, block_q, block_k, interpret, res, do):
    return _backward(*res, do, causal=causal, window=window,
                     block_q=block_q, block_k=block_k, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: Array, k: Array, v: Array, *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> Array:
    """q,k,v: (BH, S, D) -> (BH, S, D), differentiable in q, k and v.
    ``block_q``/``block_k`` None size the blocks from VMEM
    (``flash_block``)."""
    bh, s, d = q.shape
    block = flash_block(s, d, q.dtype, mxu_dtype(q.dtype, interpret))
    block_q = min(block_q or block, s)
    block_k = min(block_k or block, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq {s} not divisible by blocks "
                         f"({block_q},{block_k})")
    return _flash(q, k, v, causal, window, block_q, block_k, interpret)
