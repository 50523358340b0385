"""Cell builder: resolve an (arch x shape x mesh) cell into a jit-able
step function + fully-sharded input ShapeDtypeStructs.

This is the shared machinery of the dry-run, the trainer, and the server:
everything here works purely from specs (no allocation), so lowering a
1T-parameter cell is cheap.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.registry import Cell, CellSettings, ShapeSpec, get_cell
from repro.models import api
from repro.models.blocks import CACHE_LOGICAL, ModelContext
from repro.models.config import ModelConfig
from repro.models.lm import MOE_COUNTERS
from repro.models.params import axes_tree, shapes_tree
from repro.optim.optimizers import Optimizer, adafactor, adamw, \
    cosine_schedule
from repro.sharding.axes import AxisRules, RULE_SETS, logical_constraint, \
    logical_sharding, resolve_spec
from repro.train.step import TrainSettings, make_train_step

DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "int8": jnp.int8,
    "float8_e4m3fn": jnp.float8_e4m3fn,
}


@dataclasses.dataclass
class BuiltCell:
    cell: Cell
    mesh: Mesh
    fn: Callable  # jit-able step function
    args: Tuple[Any, ...]  # ShapeDtypeStructs (sharded)
    in_shardings: Any
    out_shardings: Any
    donate_argnums: Tuple[int, ...]
    scan_trips: int  # layer-stack trip count hint
    dropped_rules: List[Tuple[str, int]]
    kind: str


def _ctx_for(cell: Cell, mesh: Mesh, rules: AxisRules) -> ModelContext:
    cache_dtype = DTYPES[cell.settings.cache_dtype]

    def shard(x, logical):
        return logical_constraint(x, logical, mesh, rules)

    return ModelContext(
        compute_dtype=jnp.bfloat16,
        q_chunk=cell.settings.q_chunk,
        shard=shard,
        decode_cache_dtype=cache_dtype,
    )


def make_optimizer(name: str, total_steps: int = 10000) -> Optimizer:
    lr = cosine_schedule(3e-4, 200, total_steps)
    if name == "adamw":
        return adamw(lr)
    if name == "adafactor":
        return adafactor(lr)
    raise ValueError(name)


def _sharded_specs(shapes, axes, mesh, rules, dropped):
    """Attach NamedShardings to a tree of ShapeDtypeStructs."""
    def one(sds: jax.ShapeDtypeStruct, logical):
        sh = logical_sharding(logical, sds.shape, mesh, rules, dropped)
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sh)
    return jax.tree.map(
        one, shapes, axes,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _shardings_of(tree):
    return jax.tree.map(
        lambda s: s.sharding, tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def opt_state_axes(optimizer_name: str, param_axes, param_shapes):
    """Logical axes for optimizer state, derived from param axes."""
    if optimizer_name == "adamw":
        return {"m": param_axes, "v": param_axes}
    # adafactor: factored stats drop one dim
    def leaf(axes, sds):
        shape = sds.shape
        factored = (len(shape) >= 2 and shape[-1] >= 128
                    and shape[-2] >= 128)
        if factored:
            return {"vr": tuple(axes[:-1]), "vc": tuple(axes[:-2]) +
                    (axes[-1],)}
        return {"v": tuple(axes)}
    return jax.tree.map(
        leaf, param_axes, param_shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def opt_state_shapes(optimizer_name: str, param_shapes):
    def leaf(sds: jax.ShapeDtypeStruct):
        shape = sds.shape
        if optimizer_name == "adamw":
            return {"m": jax.ShapeDtypeStruct(shape, jnp.float32),
                    "v": jax.ShapeDtypeStruct(shape, jnp.float32)}
        factored = (len(shape) >= 2 and shape[-1] >= 128
                    and shape[-2] >= 128)
        if factored:
            return {"vr": jax.ShapeDtypeStruct(shape[:-1], jnp.float32),
                    "vc": jax.ShapeDtypeStruct(shape[:-2] + shape[-1:],
                                               jnp.float32)}
        return {"v": jax.ShapeDtypeStruct(shape, jnp.float32)}
    if optimizer_name == "adamw":
        m = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                         param_shapes,
                         is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        return {"m": m, "v": m}
    return jax.tree.map(leaf, param_shapes,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def cache_axes_tree(cache_shapes):
    """Logical axes for a cache tree, keyed by leaf names."""
    def walk(tree):
        out = {}
        for key, val in tree.items():
            if isinstance(val, dict):
                out[key] = walk(val)
            elif key == "pos":
                out[key] = ("batch",)
            else:
                logical = CACHE_LOGICAL[key]
                rank = len(val.shape)
                if rank == len(logical) + 1:  # stacked over blocks/layers
                    out[key] = (None, *logical)
                else:
                    out[key] = tuple(logical)
        return out
    return walk(cache_shapes)


def build_cell(arch: str, shape: str, mesh: Mesh,
               total_steps: int = 10000) -> Optional[BuiltCell]:
    cell = get_cell(arch, shape)
    if cell.skip_reason is not None:
        return None
    cfg = cell.config
    rules = RULE_SETS[cell.settings.rules]
    ctx = _ctx_for(cell, mesh, rules)
    param_dtype = DTYPES[cell.settings.param_dtype]
    dropped: List[Tuple[str, int]] = []

    specs = api.model_specs(cfg)
    p_axes = axes_tree(specs)
    p_shapes = shapes_tree(specs, param_dtype)
    p_sds = _sharded_specs(p_shapes, p_axes, mesh, rules, dropped)

    spec_kind = cell.shape.kind
    b, s = cell.shape.global_batch, cell.shape.seq_len

    if spec_kind == "train":
        optimizer = make_optimizer(cell.settings.optimizer, total_steps)
        settings = TrainSettings(
            microbatches=cell.settings.microbatches,
            accum_dtype=DTYPES[cell.settings.accum_dtype])

        def grad_shard(tree):
            return jax.tree.map(
                lambda g, la: logical_constraint(g, la, mesh, rules),
                tree, p_axes,
                is_leaf=lambda x: isinstance(x, jax.Array))

        step = make_train_step(cfg, ctx, optimizer, settings,
                               grad_shard=grad_shard)
        batch_shapes = api.train_batch_specs(cfg, b, s)
        batch_axes = {k: api.BATCH_LOGICAL[k] for k in batch_shapes}
        batch_sds = _sharded_specs(batch_shapes, batch_axes, mesh, rules,
                                   dropped)
        o_shapes = opt_state_shapes(cell.settings.optimizer, p_shapes)
        o_axes = opt_state_axes(cell.settings.optimizer, p_axes, p_shapes)
        o_sds = _sharded_specs(o_shapes, o_axes, mesh, rules, dropped)
        repl = NamedSharding(mesh, PartitionSpec())
        step_sds = jax.ShapeDtypeStruct((), jnp.int32, sharding=repl)
        state_sds = {"params": p_sds, "opt": o_sds, "step": step_sds}
        args = (state_sds, batch_sds)
        in_sh = (_shardings_of(state_sds), _shardings_of(batch_sds))
        counted = MOE_COUNTERS if cfg.n_experts else ()
        metrics_sh = {k: repl for k in
                      ("loss", "xent", "tokens", "grad_norm", *counted)}
        out_sh = (_shardings_of(state_sds), metrics_sh)
        trips = cfg.n_blocks * cell.settings.microbatches
        return BuiltCell(cell, mesh, step, args, in_sh, out_sh,
                         donate_argnums=(0,), scan_trips=trips,
                         dropped_rules=dropped, kind="train")

    if spec_kind == "prefill":
        def prefill(params, batch):
            return api.prefill_fn(params, batch, cfg, ctx, window=s)
        batch_shapes = api.train_batch_specs(cfg, b, s)
        batch_shapes.pop("labels")
        batch_axes = {k: api.BATCH_LOGICAL[k] for k in batch_shapes}
        batch_sds = _sharded_specs(batch_shapes, batch_axes, mesh, rules,
                                   dropped)
        cache_shapes = api.cache_spec(cfg, b, s, ctx)
        cache_sds = _sharded_specs(cache_shapes, cache_axes_tree(cache_shapes),
                                   mesh, rules, dropped)
        repl = NamedSharding(mesh, PartitionSpec())
        logits_sh = logical_sharding(
            ("batch", None, "vocab"), (b, 1, cfg.vocab_size), mesh, rules)
        args = (p_sds, batch_sds)
        in_sh = (_shardings_of(p_sds), _shardings_of(batch_sds))
        out_sh = (logits_sh, _shardings_of(cache_sds))
        return BuiltCell(cell, mesh, prefill, args, in_sh, out_sh,
                         donate_argnums=(), scan_trips=cfg.n_blocks,
                         dropped_rules=dropped, kind="prefill")

    # decode
    def decode(params, token, cache):
        return api.decode_fn(params, token, cache, cfg, ctx)

    cache_shapes = api.cache_spec(cfg, b, s, ctx)
    cache_sds = _sharded_specs(cache_shapes, cache_axes_tree(cache_shapes),
                               mesh, rules, dropped)
    tok_sds = _sharded_specs(
        {"t": jax.ShapeDtypeStruct((b, 1), jnp.int32)},
        {"t": ("batch", None)}, mesh, rules, dropped)["t"]
    logits_sh = logical_sharding(
        ("batch", None, "vocab"), (b, 1, cfg.vocab_size), mesh, rules)
    args = (p_sds, tok_sds, cache_sds)
    in_sh = (_shardings_of(p_sds), tok_sds.sharding,
             _shardings_of(cache_sds))
    out_sh = (logits_sh, _shardings_of(cache_sds))
    return BuiltCell(cell, mesh, decode, args, in_sh, out_sh,
                     donate_argnums=(2,), scan_trips=cfg.n_blocks,
                     dropped_rules=dropped, kind="decode")


def lower_cell(built: BuiltCell):
    jitted = jax.jit(built.fn, in_shardings=built.in_shardings,
                     out_shardings=built.out_shardings,
                     donate_argnums=built.donate_argnums)
    return jitted.lower(*built.args)
