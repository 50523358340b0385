"""m-grouped contiguous GEMM Pallas kernels for sort-based dropless MoE,
differentiable through a custom VJP.

The dropless dispatch sorts token rows by routed expert and pads each
expert's group to a ``block_m`` boundary, so every m-tile of the sorted
buffer belongs to exactly ONE expert. The per-tile expert id array is
scalar-prefetched (the same BlockSpec discipline as decode_attention's
page table): the weight BlockSpec's index_map reads ``group_ids[i]`` at
DMA time and pulls that expert's (D, block_f) weight tile into VMEM —
no (E, T, D) capacity buffer ever exists.

Tiles whose id is the sentinel ``-1`` (pad-only, or non-local under
expert parallelism) write zeros and do nothing else: their row and
weight blocks are pinned to those of the last tile that computed, so
they start no DMA, and their dot is skipped. The work follows the rows
actually routed here. The combine step never reads pad rows, and zeros
are the psum identity for the EP wrapper in kernels/ops.py.

The backward (``jax.custom_vjp``; megablox's ``gmm``/``tgmm`` in
``jax.experimental.pallas.ops.tpu.megablox`` are the public model):

  dx  the forward kernel on the transposed weights, over the same tiles;
  dw  ``gmm_dw``, a transposed grouped kernel that walks each expert's
      tiles in order and accumulates x^T dy in VMEM, one (block_d,
      block_f) output block at a time; an expert with no rows gets zeros.

The kernels are named ``gmm`` (forward and dx) and ``gmm_dw`` in the
compiled program and the device trace.

int8 expert weights stream natively: pass per-expert scalar ``w_scale``
(E,) and the kernel applies it to the fp32 accumulator after the dot
(exact for a scalar scale: ``s * dot(x, w) == dot(x, s * w)``); that
serving-only path has no VJP.

Compiled for TPU via Mosaic; validated on CPU with interpret=True
against kernels/ref.grouped_matmul_ref.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import (BUDGET_BYTES, dot_precision, largest_block,
                                  mxu_dtype, tile_bytes)

Array = jax.Array
FIRST, LAST, COMPUTE = 1, 2, 4  # gmm_dw walk flags


def _dot(x: Array, w: Array, mxu) -> Array:
    return jnp.dot(x.astype(mxu), w.astype(mxu),
                   precision=dot_precision(mxu),
                   preferred_element_type=jnp.float32)


def _grouped_kernel(gid_ref, src_ref, x_ref, w_ref, o_ref, *, mxu):
    g = gid_ref[pl.program_id(0)]

    @pl.when(g >= 0)
    def _compute():
        o_ref[...] = _dot(x_ref[...], w_ref[0], mxu).astype(o_ref.dtype)

    @pl.when(g < 0)
    def _zeros():
        o_ref[...] = jnp.zeros_like(o_ref)


def _grouped_kernel_scaled(gid_ref, src_ref, scale_ref, x_ref, w_ref, o_ref,
                           *, mxu):
    g = gid_ref[pl.program_id(0)]

    @pl.when(g >= 0)
    def _compute():
        acc = jnp.dot(x_ref[...].astype(jnp.float32),
                      w_ref[0].astype(jnp.float32),
                      preferred_element_type=jnp.float32)
        o_ref[...] = (acc * scale_ref[g]).astype(o_ref.dtype)

    @pl.when(g < 0)
    def _zeros():
        o_ref[...] = jnp.zeros_like(o_ref)


def grouped_block_f(block_m: int, d: int, f: int, x_dtype, w_dtype,
                    out_dtype, mxu) -> int:
    """Output columns per grid step: the largest 128-multiple divisor of
    F whose double-buffered x, weight and output blocks, and their casts
    to the MXU's ``mxu`` dtype, fit the VMEM budget (a full-depth
    (D, block_f) weight tile dominates)."""
    def fits(bf: int) -> bool:
        n = 2 * (tile_bytes((block_m, d), x_dtype)
                 + tile_bytes((d, bf), w_dtype)
                 + tile_bytes((block_m, bf), out_dtype))
        if jnp.dtype(mxu) != jnp.dtype(x_dtype):
            n += tile_bytes((block_m, d), mxu) + tile_bytes((d, bf), mxu)
        return n <= BUDGET_BYTES
    return largest_block(f, fits, align=128)


def _last_computed(group_ids: Array) -> Array:
    """Per tile, the latest tile at or before it that computes (0 where
    none): a sentinel tile's blocks are pinned to that tile's."""
    nb = group_ids.shape[0]
    own = jnp.where(group_ids >= 0, jnp.arange(nb, dtype=jnp.int32), -1)
    return jnp.maximum(jax.lax.cummax(own, axis=0), 0)


def _gmm_call(x: Array, w: Array, group_ids: Array,
              w_scale: Optional[Array], block_f: Optional[int], out_dtype,
              interpret: bool) -> Array:
    m, d = x.shape
    e, d2, f = w.shape
    if d != d2:
        raise ValueError(f"contraction mismatch {x.shape} @ {w.shape}")
    nb = group_ids.shape[0]
    if nb == 0 or m % nb:
        raise ValueError(f"M={m} not divisible into {nb} m-tiles")
    block_m = m // nb
    out_dtype = out_dtype or x.dtype
    mxu = mxu_dtype(x.dtype, interpret)
    if block_f is None:
        block_f = grouped_block_f(block_m, d, f, x.dtype, w.dtype,
                                  out_dtype, mxu)
    block_f = min(block_f, f)
    if f % block_f:
        raise ValueError(f"F={f} not divisible by block_f={block_f}")
    nf = f // block_f
    group_ids = group_ids.astype(jnp.int32)
    src = _last_computed(group_ids)

    if w_scale is None:
        kernel = functools.partial(_grouped_kernel, mxu=mxu)
        operands = (group_ids, src, x, w)
    else:
        kernel = functools.partial(_grouped_kernel_scaled, mxu=mxu)
        operands = (group_ids, src, w_scale.astype(jnp.float32), x, w)

    def x_map(i, j, gid, src, *_):
        return src[i], 0

    def w_map(i, j, gid, src, *_):
        # the tile's expert; a sentinel tile keeps the block the last
        # computing tile ended on, so nothing is fetched for it
        return (jnp.maximum(gid[src[i]], 0), 0,
                jnp.where(gid[i] >= 0, j, nf - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(operands) - 2,
        grid=(nb, nf),
        in_specs=[pl.BlockSpec((block_m, d), x_map),
                  pl.BlockSpec((1, d, block_f), w_map)],
        out_specs=pl.BlockSpec((block_m, block_f),
                               lambda i, j, *_: (i, j)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, f), out_dtype),
        interpret=interpret,
        name="gmm",
    )(*operands)


# ---------------------------------------------------------------------------
# dw: the transposed grouped kernel
# ---------------------------------------------------------------------------


def grouped_dw_blocks(block_m: int, d: int, f: int, x_dtype, dy_dtype,
                      out_dtype, mxu) -> Tuple[int, int]:
    """(block_d, block_f) of the dw output: of the 128-multiple divisors
    whose f32 accumulator, double-buffered x, dy and output blocks and
    casts fit the VMEM budget, those that read the rows least often
    (x once per column block, dy once per row block)."""
    def cands(n):
        return [b for b in range(n, 0, -1)
                if n % b == 0 and (b % 128 == 0 or b == n)]

    best = None
    for bd in cands(d):
        for bf in cands(f):
            need = (tile_bytes((bd, bf), jnp.float32)
                    + 2 * (tile_bytes((bd, bf), out_dtype)
                           + tile_bytes((block_m, bd), x_dtype)
                           + tile_bytes((block_m, bf), dy_dtype))
                    + tile_bytes((bd, block_m), x_dtype)
                    + tile_bytes((bd, block_m), mxu)
                    + tile_bytes((block_m, bf), mxu))
            if need > BUDGET_BYTES:
                continue
            key = ((f // bf) * d + (d // bd) * f, -bd * bf)
            if best is None or key < best[0]:
                best = (key, (bd, bf))
    return best[1] if best else (cands(d)[-1], cands(f)[-1])


def _dw_walk(group_ids: Array, n_experts: int):
    """The dw grid's walk: (expert, tile, flags) rows, one per tile of
    each expert in order, one for an expert with none (it stores zeros),
    then steps that do nothing, to the static length nb + E. Tiles of the
    steps that compute nothing repeat the previous step's."""
    nb, e = group_ids.shape[0], n_experts
    eq = group_ids[None, :] == jnp.arange(e, dtype=jnp.int32)[:, None]
    n_t = eq.sum(axis=1).astype(jnp.int32)  # tiles per expert
    start = jnp.argmax(eq, axis=1).astype(jnp.int32)
    steps = jnp.maximum(n_t, 1)
    ends = jnp.cumsum(steps)
    offs = ends - steps
    j = jnp.arange(nb + e, dtype=jnp.int32)
    ex = jnp.minimum(jnp.searchsorted(ends, j, side="right"),
                     e - 1).astype(jnp.int32)
    r = j - offs[ex]
    live = j < ends[-1]
    compute = live & (n_t[ex] > 0)
    tile = jnp.where(compute, start[ex] + r, -1)
    tile = jnp.maximum(jax.lax.cummax(tile, axis=0), 0)
    flags = (jnp.where(live & (r == 0), FIRST, 0)
             | jnp.where(live & (r == steps[ex] - 1), LAST, 0)
             | jnp.where(compute, COMPUTE, 0)).astype(jnp.int32)
    return ex, tile, flags


def _dw_kernel(ex_ref, tile_ref, fl_ref, x_ref, dy_ref, o_ref, acc_ref, *,
               mxu):
    flags = fl_ref[pl.program_id(2)]

    @pl.when((flags & FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((flags & COMPUTE) != 0)
    def _accumulate():
        acc_ref[...] += _dot(x_ref[...].T, dy_ref[...], mxu)

    @pl.when((flags & LAST) != 0)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul_dw(x: Array, dy: Array, group_ids: Array, n_experts: int,
                      *, out_dtype=jnp.float32,
                      interpret: bool = False) -> Array:
    """dw[e] = x[rows of e]^T dy[rows of e]: x (M, D), dy (M, F) sorted
    rows, group_ids (M // block_m,) as for ``grouped_matmul`` ->
    (n_experts, D, F); an expert with no tile gets zeros."""
    m, d = x.shape
    f = dy.shape[1]
    nb = group_ids.shape[0]
    block_m = m // nb
    mxu = mxu_dtype(x.dtype, interpret)
    bd, bf = grouped_dw_blocks(block_m, d, f, x.dtype, dy.dtype, out_dtype,
                               mxu)
    ex, tile, flags = _dw_walk(group_ids.astype(jnp.int32), n_experts)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(d // bd, f // bf, nb + n_experts),
        in_specs=[
            pl.BlockSpec((block_m, bd), lambda a, b, t, ex, tl, fl:
                         (tl[t], a)),
            pl.BlockSpec((block_m, bf), lambda a, b, t, ex, tl, fl:
                         (tl[t], b)),
        ],
        out_specs=pl.BlockSpec((1, bd, bf), lambda a, b, t, ex, tl, fl:
                               (ex[t], a, b)),
        scratch_shapes=[pltpu.VMEM((bd, bf), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_dw_kernel, mxu=mxu),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_experts, d, f), out_dtype),
        interpret=interpret,
        name="gmm_dw",
    )(ex, tile, flags, x, dy)


# ---------------------------------------------------------------------------
# Differentiable entry point
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gmm(x, w, group_ids, block_f, out_dtype, interpret):
    return _gmm_call(x, w, group_ids, None, block_f, out_dtype, interpret)


def _gmm_fwd(x, w, group_ids, block_f, out_dtype, interpret):
    out = _gmm_call(x, w, group_ids, None, block_f, out_dtype, interpret)
    return out, (x, w, group_ids)


def _gmm_bwd(block_f, out_dtype, interpret, res, dy):
    x, w, group_ids = res
    dx = _gmm_call(dy, jnp.swapaxes(w, 1, 2), group_ids, None, None,
                   x.dtype, interpret)
    dw = grouped_matmul_dw(x, dy, group_ids, w.shape[0],
                           out_dtype=w.dtype, interpret=interpret)
    return dx, dw, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(x: Array, w: Array, group_ids: Array, *,
                   w_scale: Optional[Array] = None,
                   block_f: Optional[int] = None,
                   out_dtype=None,
                   interpret: bool = False) -> Array:
    """x: (M, D) sorted+padded token rows; w: (E, D, F) expert weights;
    group_ids: (M // block_m,) int32 expert id per m-tile (-1 sentinel
    for pad-only tiles). block_m is implied by M // len(group_ids).
    ``w_scale`` (E,) fp32 dequantizes int8 ``w`` per expert. ``block_f``
    None sizes the column block from VMEM (``grouped_block_f``). -> (M, F),
    differentiable in x and w when ``w_scale`` is None.
    """
    if w_scale is not None:
        return _gmm_call(x, w, group_ids, w_scale, block_f, out_dtype,
                         interpret)
    return _gmm(x, w, group_ids, block_f, out_dtype, interpret)
