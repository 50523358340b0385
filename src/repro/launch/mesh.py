"""Mesh construction: every mesh in the repo is built here.

Single pod: (16, 16) = 256 chips (data, model) — one TPU v5e pod.
Multi-pod: (2, 16, 16) = 512 chips (pod, data, model) — two pods; the
"pod" axis carries synchronous data parallelism exactly as the paper's
multi-pod Gemini training does.

Axes are ``Auto``: ``jax.make_mesh`` defaults to ``Explicit`` axes, under
which the serving code's ``with_sharding_constraint`` annotations are
rejected. The constructors are functions (not module constants), so
importing this module never touches jax device state.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Mesh of ``prod(shape)`` devices with ``Auto`` axis types."""
    import jax
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def mesh_for_devices(n: int, *, multi_pod: bool = False):
    """Scaled-down mesh with the production axis structure for n devices."""
    if multi_pod:
        if n % 2:
            raise ValueError("multi-pod mesh needs even device count")
        side = int(np.sqrt(n // 2))
        if 2 * side * side != n:
            raise ValueError(f"cannot square {n//2} devices")
        return make_mesh((2, side, side), ("pod", "data", "model"))
    side = int(np.sqrt(n))
    if side * side != n:
        raise ValueError(f"cannot square {n} devices")
    return make_mesh((side, side), ("data", "model"))
