"""MXU-tiled matmul Pallas kernel (bf16/fp8 inputs, fp32 accumulation).

The paper's MXU story in kernel form: inputs stream HBM->VMEM in
(block_m x block_k) / (block_k x block_n) tiles sized for the 128x128
(bf16) / 256x256+ (Ironwood) systolic arrays — every block dim is a
multiple of 128. Accumulation is fp32 in a VMEM scratch accumulator across
the K grid dimension (grid iterates K innermost), exactly the
multiply-bf16/accumulate-fp32 discipline the paper credits to TPU v2.

Compiled for TPU via Mosaic; validated on CPU with interpret=True against
kernels/ref.py.
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


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], precision=dot_precision(a_ref.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(
    a: Array,
    b: Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> Array:
    """a: (M, K), b: (K, N) -> (M, N). Block dims must divide the operands
    (pad upstream if needed); all blocks MXU-aligned (multiples of 128 for
    bf16, which also satisfies the fp8 512-lane arrays)."""
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} @ {b.shape}")
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"shape ({m},{k})x({k},{n}) not divisible by blocks "
            f"({block_m},{block_k},{block_n})")
    out_dtype = out_dtype or a.dtype
    n_k = k // block_k
    grid = (m // block_m, n // block_n, n_k)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(a, b)
