"""Train-step factory: microbatched grad accumulation, clipping, optimizer.

Microbatching is implemented *inside the differentiated function*: the loss
scans over microbatches, so scan-AD itself accumulates parameter gradients
in a single buffer (measured: the manual accumulate-outside-grad
formulation kept 3 fp32 grad trees alive in the loop carry on this XLA
build — 3x the memory).

Each layer runs under its own ``jax.checkpoint`` (``models.lm``,
``models.encdec``), so a microbatch's residuals are its layer inputs. The
step keeps them from the forward pass, and the head's logits are
recomputed under a checkpoint of their own: every layer runs forward twice
a step. The microbatch loop is unrolled two microbatches at a time: rolled,
the v5e compiler's buffers for a two-microbatch 8k-token MoE step
(Moonlight's block) took 1.16 GiB more, past the chip's 16 GB. Two is the
only count measured; a longer loop keeps two copies of the body.

Gradient dtype = accumulation dtype is controlled by casting parameters at
the loss boundary (forward compute casts to bf16 at use regardless), so
fp32 accumulation costs one params-sized fp32 tree, sharded like the params.

Cross-pod data parallelism is implicit in the shardings (batch split over
the "pod" axis) — GSPMD inserts the cross-pod gradient all-reduce exactly
as the paper's multi-pod synchronous training. The beyond-paper
``compress_pod_grads`` path replaces it with an int8 error-feedback
exchange (repro/train/compress.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import api
from repro.models.blocks import ModelContext
from repro.models.config import ModelConfig
from repro.models.lm import MOE_COUNTERS
from repro.optim.optimizers import Optimizer, clip_by_global_norm

Array = jax.Array
PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainSettings:
    microbatches: int = 1
    grad_clip: float = 1.0
    accum_dtype: Any = jnp.float32
    compress_pod_grads: bool = False


def _batch_axis(key: str) -> int:
    return 1 if key == "positions" else 0


def split_microbatches(batch: Dict[str, Array], n: int) -> Dict[str, Array]:
    """Reshape each entry's batch axis B -> (n, B/n), microbatch axis front."""
    out = {}
    for key, val in batch.items():
        ax = _batch_axis(key)
        b = val.shape[ax]
        if b % n:
            raise ValueError(f"batch {b} not divisible by {n} microbatches")
        new_shape = val.shape[:ax] + (n, b // n) + val.shape[ax + 1:]
        v = val.reshape(new_shape)
        out[key] = jnp.moveaxis(v, ax, 0)
    return out


def init_train_state(key: jax.Array, cfg: ModelConfig, optimizer: Optimizer,
                     param_dtype=jnp.float32) -> Dict[str, Any]:
    from repro.models.params import init_params
    params = init_params(key, api.model_specs(cfg), param_dtype)
    return {"params": params, "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def make_train_step(
    cfg: ModelConfig,
    ctx: ModelContext,
    optimizer: Optimizer,
    settings: TrainSettings = TrainSettings(),
    grad_shard: Optional[Callable[[Any], Any]] = None,
) -> Callable[[Dict[str, Any], Dict[str, Array]],
              Tuple[Dict[str, Any], Dict[str, Array]]]:
    """``grad_shard``: optional tree-map applying the params' sharding
    constraints to grad-shaped trees (keeps accumulation sharded like the
    parameters rather than whatever propagation picks).

    The step's metrics are the loss, cross entropy, token count and
    gradient norm; an expert model's add ``moe_held_rows`` (assignments
    routed to the held experts) and ``moe_padded_rows`` (rows the grouped
    kernels computed for them), summed over layers and microbatches."""
    if grad_shard is None:
        grad_shard = lambda tree: tree  # noqa: E731
    n = settings.microbatches
    counted = MOE_COUNTERS if cfg.n_experts else ()

    def total_loss(params_acc, batch):
        # params_acc: params cast to accum dtype — grads inherit this dtype.
        if n == 1:
            loss, metrics = api.loss_fn(params_acc, batch, cfg, ctx)
            return loss, metrics

        mbs = split_microbatches(batch, n)

        def body(acc, mb):
            loss, metrics = api.loss_fn(params_acc, mb, cfg, ctx,
                                        remat_head=True)
            m = {"loss": metrics["loss"] / n, "xent": metrics["xent"] / n,
                 "tokens": metrics["tokens"]}
            m.update({k: metrics[k] for k in counted})
            return acc + loss / n, m

        loss, ms = jax.lax.scan(body, jnp.zeros((), jnp.float32), mbs,
                                unroll=min(n, 2))
        return loss, {k: v.sum() for k, v in ms.items()}

    grad_fn = jax.value_and_grad(total_loss, has_aux=True)

    def train_step(state, batch):
        params = state["params"]
        params_acc = grad_shard(jax.tree.map(
            lambda p: p.astype(settings.accum_dtype)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, params))
        (_, metrics), grads = grad_fn(params_acc, batch)
        grads = grad_shard(grads)
        grads, gnorm = clip_by_global_norm(grads, settings.grad_clip)
        # Barrier: the optimizer upcasts params to fp32 leaf-by-leaf; without
        # this, XLA hoists those converts above the whole fwd/bwd (they only
        # depend on params), keeping a full fp32 param copy live through
        # every loop (+8 GiB/device measured on the 1T-param cell).
        grads, params_upd, opt_in = jax.lax.optimization_barrier(
            (grads, params, state["opt"]))
        new_params, new_opt = optimizer.update(
            grads, opt_in, params_upd, state["step"])
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        out_metrics = {"loss": metrics["loss"], "xent": metrics["xent"],
                       "tokens": metrics["tokens"], "grad_norm": gnorm}
        out_metrics.update({k: metrics[k] for k in counted})
        return new_state, out_metrics

    return train_step
