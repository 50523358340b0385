"""Compressed cross-pod gradient exchange (beyond-paper optimization).

The paper's multi-pod recipe is synchronous DP with a full-precision
gradient all-reduce across pods — the slowest links in the system (DCN, not
ICI). This module replaces that exchange with int8 quantization + error
feedback: each pod quantizes (grad + residual) to int8 with a per-tensor
scale, all-gathers the quantized tensors over the "pod" axis (1 byte/elem
vs 4), dequantizes and averages locally, and keeps the quantization error
as state for the next step (error feedback makes the compression unbiased
over time; classic 1-bit-Adam/PowerSGD-era machinery).

Implementation: ``shard_map`` over the pod axis only — inside, params are
replicated w.r.t. pods and the data/model axes stay under GSPMD (``auto``),
so the whole train step still compiles as one SPMD program.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array
PyTree = Any


def quantize_int8(x: Array) -> Tuple[Array, Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: Array, scale: Array) -> Array:
    return q.astype(jnp.float32) * scale


def _compressed_mean_one(g: Array, err: Array, axis: str
                         ) -> Tuple[Array, Array]:
    """Int8 error-feedback mean over a named axis. Returns (mean, new_err)."""
    compensated = g.astype(jnp.float32) + err
    q, scale = quantize_int8(compensated)
    new_err = compensated - dequantize_int8(q, scale)
    q_all = jax.lax.all_gather(q, axis)          # (n, ...) int8 on the wire
    s_all = jax.lax.all_gather(scale, axis)      # (n,) f32
    mean = jnp.mean(
        q_all.astype(jnp.float32)
        * s_all.reshape((-1,) + (1,) * g.ndim), axis=0)
    return mean.astype(g.dtype), new_err


def init_error_state(grads_like: PyTree) -> PyTree:
    return jax.tree.map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads_like)


def make_compressed_grad_fn(
    loss_fn: Callable[[PyTree, Dict[str, Array]], Tuple[Array, Dict]],
    mesh: Mesh,
    batch_specs: Dict[str, P],
) -> Callable[[PyTree, Dict[str, Array], PyTree],
              Tuple[Tuple[Array, Dict], PyTree, PyTree]]:
    """Wrap a loss into a per-pod grad + compressed-exchange function.

    Requires params replicated over the pod axis (the paper-faithful
    baseline rules). batch_specs: pod-axis sharding per batch key.
    """
    if "pod" not in mesh.axis_names:
        raise ValueError("mesh has no 'pod' axis")
    vg = jax.value_and_grad(loss_fn, has_aux=True)

    def local(params, batch, err):
        (loss, metrics), g = vg(params, batch)
        flat, treedef = jax.tree.flatten(g)
        eflat = treedef.flatten_up_to(err)
        out = [_compressed_mean_one(gi, ei, "pod")
               for gi, ei in zip(flat, eflat)]
        g_mean = jax.tree.unflatten(treedef, [o[0] for o in out])
        new_err = jax.tree.unflatten(treedef, [o[1] for o in out])
        loss = jax.lax.pmean(loss, "pod")
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, "pod"), metrics)
        return (loss, metrics), g_mean, new_err

    in_specs = (P(), batch_specs, P())
    out_specs = ((P(), P()), P(), P())
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False,
                         axis_names={"pod"})


def wire_bytes_per_step(n_params: int, pods: int,
                        compressed: bool) -> float:
    """Cross-pod bytes per device per step (for the roofline note):
    fp32 ring all-reduce moves 2(n-1)/n * 4B/elem; int8 all-gather moves
    (n-1) * 1B/elem (each device receives n-1 remote shards) + scales."""
    if compressed:
        return (pods - 1) * n_params * 1.0
    return 2.0 * (pods - 1) / pods * n_params * 4.0
