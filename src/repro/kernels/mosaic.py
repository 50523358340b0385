"""What the TPU kernel compiler (Mosaic) accepts: block sizes that fit
the scoped VMEM, and the precision of in-kernel dots.

A kernel's blocks live in VMEM, where the last two dims of every block pad
to the (sublane, 128-lane) tile: a (block, kv=2, d=64) KV block takes four
times its bytes. The sizing below counts those padded bytes, so a caller
never has to pick a block size that the compiler will refuse.
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

# The compiler's default scoped-VMEM limit per kernel on v5e (v6e allows
# 32 MiB; sizing for the smaller limit compiles on both).
SCOPED_VMEM_BYTES = 16 << 20
# Share of that limit the counted blocks may take: the compiler's own
# relayouts and temporaries come on top (a 16 MiB estimate of the dense
# decode kernel's blocks was measured at 18.4 MiB by the v5e compiler).
BUDGET_BYTES = SCOPED_VMEM_BYTES * 3 // 4


def tile_bytes(shape: Sequence[int], dtype) -> int:
    """VMEM bytes of one block: the minor dim pads to 128 lanes, the
    second-minor to the sublane count (8 rows of 32 bits, so 16 of bf16
    and 32 of int8)."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, cols = (1,) * (2 - len(shape)) + tuple(shape)
    sub = 8 * 4 // itemsize
    return (math.prod(lead) * -(-rows // sub) * sub
            * -(-cols // 128) * 128 * itemsize)


def largest_block(n: int, fits: Callable[[int], bool],
                  align: int = 1) -> int:
    """Largest divisor of ``n`` that is a multiple of ``align`` (or ``n``
    itself) and ``fits``; the smallest such divisor when none fits."""
    cands = [b for b in range(n, 0, -1)
             if n % b == 0 and (b % align == 0 or b == n)]
    return next((b for b in cands if fits(b)), cands[-1])


# Process matmul precisions (``jax.default_matmul_precision``) under which
# XLA takes f32 dot operands on a TPU in one bf16 MXU pass.
_ONE_BF16_PASS = (None, "default", "bfloat16", "fastest")


def mxu_dtype(dtype, interpret: bool = False):
    """Operand dtype of an in-kernel dot on ``dtype`` blocks, matching what
    XLA's own dot would do at the process's matmul precision. On a TPU at
    the default precision XLA multiplies f32 operands in one bf16 MXU pass
    with f32 accumulation, so a kernel casts its f32 VMEM blocks to bf16
    right before the dot (nothing is stored in bf16). Under a finer
    precision ("highest", ...) they stay f32, and so they do in interpret
    mode, where the dot is the CPU's, exact in f32 like XLA's there."""
    dtype = jnp.dtype(dtype)
    if (dtype == jnp.float32 and not interpret
            and jax.config.jax_default_matmul_precision in _ONE_BF16_PASS):
        return jnp.dtype(jnp.bfloat16)
    return dtype


def dot_precision(dtype) -> Optional[jax.lax.Precision]:
    """Precision for an in-kernel dot on ``dtype`` operands. f32 follows
    the process (``jax.default_matmul_precision``); narrower operands are
    exact on the MXU and pin DEFAULT, because Mosaic refuses fp32
    contract precision ("highest") on them."""
    if jnp.dtype(dtype) == jnp.float32:
        return None
    return jax.lax.Precision.DEFAULT
