"""m-grouped contiguous GEMM Pallas kernel for sort-based dropless MoE.

The dropless dispatch sorts token rows by routed expert and pads each
expert's group to a ``block_m`` boundary, so every m-tile of the sorted
buffer belongs to exactly ONE expert. The per-tile expert id array is
scalar-prefetched (the same BlockSpec discipline as decode_attention's
page table): the weight BlockSpec's index_map reads ``group_ids[i]`` at
DMA time and pulls that expert's (D, block_f) weight tile into VMEM —
no (E, T, D) capacity buffer ever exists.

Tiles whose id is the sentinel ``-1`` (pad-only, or non-local under
expert parallelism) write zeros; the combine step never reads pad rows,
and zeros are the psum identity for the EP wrapper in kernels/ops.py.

int8 expert weights stream natively: pass per-expert scalar ``w_scale``
(E,) and the kernel applies it to the fp32 accumulator after the dot
(exact for a scalar scale: ``s * dot(x, w) == dot(x, s * w)``).

Compiled for TPU via Mosaic; validated on CPU with interpret=True
against kernels/ref.grouped_matmul_ref.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import (BUDGET_BYTES, dot_precision, largest_block,
                                  tile_bytes)

Array = jax.Array


def _grouped_kernel(gid_ref, x_ref, w_ref, o_ref):
    i = pl.program_id(0)
    g = gid_ref[i]
    acc = jnp.dot(x_ref[...], w_ref[0],
                  precision=dot_precision(x_ref.dtype),
                  preferred_element_type=jnp.float32)
    # Pad-only / non-local tile: the weight DMA fetched expert 0's tile
    # (index_map clamps the sentinel); discard it and write zeros.
    o_ref[...] = jnp.where(g >= 0, acc, 0.0).astype(o_ref.dtype)


def _grouped_kernel_scaled(gid_ref, scale_ref, x_ref, w_ref, o_ref):
    i = pl.program_id(0)
    g = gid_ref[i]
    acc = jnp.dot(x_ref[...].astype(jnp.float32),
                  w_ref[0].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    acc = acc * scale_ref[jnp.maximum(g, 0)]
    o_ref[...] = jnp.where(g >= 0, acc, 0.0).astype(o_ref.dtype)


def grouped_block_f(block_m: int, d: int, f: int, x_dtype, w_dtype,
                    out_dtype) -> int:
    """Output columns per grid step: the largest 128-multiple divisor of
    F whose double-buffered x, weight and output blocks fit the VMEM
    budget (a full-depth (D, block_f) weight tile dominates)."""
    def fits(bf: int) -> bool:
        return 2 * (tile_bytes((block_m, d), x_dtype)
                    + tile_bytes((d, bf), w_dtype)
                    + tile_bytes((block_m, bf), out_dtype)) <= BUDGET_BYTES
    return largest_block(f, fits, align=128)


def grouped_matmul(x: Array, w: Array, group_ids: Array, *,
                   w_scale: Optional[Array] = None,
                   block_f: Optional[int] = None,
                   out_dtype=None,
                   interpret: bool = False) -> Array:
    """x: (M, D) sorted+padded token rows; w: (E, D, F) expert weights;
    group_ids: (M // block_m,) int32 expert id per m-tile (-1 sentinel
    for pad-only tiles). block_m is implied by M // len(group_ids).
    ``w_scale`` (E,) fp32 dequantizes int8 ``w`` per expert. ``block_f``
    None sizes the column block from VMEM (``grouped_block_f``). -> (M, F).
    """
    m, d = x.shape
    e, d2, f = w.shape
    if d != d2:
        raise ValueError(f"contraction mismatch {x.shape} @ {w.shape}")
    nb = group_ids.shape[0]
    if nb == 0 or m % nb:
        raise ValueError(f"M={m} not divisible into {nb} m-tiles")
    block_m = m // nb
    out_dtype = out_dtype or x.dtype
    if block_f is None:
        block_f = grouped_block_f(block_m, d, f, x.dtype, w.dtype, out_dtype)
    block_f = min(block_f, f)
    if f % block_f:
        raise ValueError(f"F={f} not divisible by block_f={block_f}")
    group_ids = group_ids.astype(jnp.int32)

    if w_scale is None:
        kernel = _grouped_kernel
        nsp = 1
        operands = (group_ids, x, w)
    else:
        kernel = _grouped_kernel_scaled
        nsp = 2
        operands = (group_ids, w_scale.astype(jnp.float32), x, w)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=nsp,
        grid=(nb, f // block_f),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j, *refs: (i, 0)),
            # The scalar-prefetched tile->expert table drives the weight
            # gather at DMA time (clamp the -1 sentinel to a valid row).
            pl.BlockSpec(
                (1, d, block_f),
                lambda i, j, gid_ref, *refs:
                    (jnp.maximum(gid_ref[i], 0), 0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_f),
                               lambda i, j, *refs: (i, j)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, f), out_dtype),
        interpret=interpret,
    )(*operands)
