"""Mixture-of-Experts FFN: sort-based dropless dispatch (training and
serving) and capacity dispatch (the tests' oracle).

The dispatch pattern is the paper's SparseCore story at the framework level:
fine-grained sort/scatter of per-token vectors (vs the dense AllReduce of
parameter tensors). Two dispatch modes share one router:

* ``dispatch="capacity"`` — GShard-style dispatch: top-k routing
  -> position-in-expert via one-hot cumsum (top-1 assignments take priority
  over top-2, etc.) -> scatter into an (E, capacity, D) buffer (overflow
  tokens drop, ``mode="drop"``) -> batched expert matmuls -> gather back
  and combine with renormalized gate weights. ``dropless=True`` sizes the
  buffer so nothing can drop — correct, but it burns an (E, T, D) buffer.

* ``dispatch="grouped"`` — sort-based dropless dispatch: stable-
  argsort the (T*k) assignments by expert, pad each expert's group to a
  ``block_m`` boundary, run the m-grouped contiguous GEMM Pallas kernel
  (kernels/moe_gemm.py) over the sorted rows with a scalar-prefetched
  tile->expert table, then unpermute and combine with the renormalized
  gate weights. No capacity buffer, no drops: the working set is
  M_pad = round_up(T*k + E*(block_m-1), block_m) rows instead of E*T.
  int8 expert weights (``quantize_moe_params``) dequantize inside the
  kernel via per-expert scales; experts shard over the "data" mesh axis
  through the shard_map wrapper in kernels/ops.py (expert parallelism).

A layer may hold a share of the experts (``cfg.experts_held`` from
``cfg.first_expert``, the chip's share under expert parallelism): it
routes over all ``n_experts``, its held experts' rows go through the
grouped GEMM, and assignments to the others sort past the held groups
and are never computed. Shared experts (one MLP of ``n_shared_experts``
times the expert width) are added once; the routed part is scaled by
``routed_scaling``. What the absent experts would add is left out.

Two routers: "softmax" (renormalised top-k of softmax probabilities) and
"sigmoid" (DeepSeek-V3's ``noaux_tc``: gating in float32, top-k of the
sigmoid scores plus a correction bias that picks experts but takes no
gradient, gates renormalised over the chosen k).

Aux losses (returned, weighted by the trainer), identical across
dispatch modes: the softmax router's Switch-style load-balance loss and
router z-loss; the sigmoid router's sequence-wise balance loss
(DeepSeek-V3, arXiv:2412.19437 §2.1.2) over all ``n_experts``. The
grouped path also returns, as ``moe_held_rows`` and ``moe_padded_rows``,
the assignments routed to held experts and the rows the grouped kernels
compute for them (block padding included).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models.config import ModelConfig
from repro.models.ops import swiglu, gelu
from repro.models.params import ParamSpec, zeros_init

Array = jax.Array


def moe_param_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    """The router over all experts, the held experts' weights, the
    sigmoid router's correction bias and the shared experts."""
    d, f, e = cfg.d_model, cfg.expert_width, cfg.n_held
    specs = {
        "router": ParamSpec((d, cfg.n_experts), ("embed", None)),
        "w_up": ParamSpec((e, d, f), ("expert", "embed", "expert_mlp")),
        "w_down": ParamSpec((e, f, d), ("expert", "expert_mlp", "embed")),
    }
    if cfg.mlp_act == "swiglu":
        specs["w_gate"] = ParamSpec((e, d, f),
                                    ("expert", "embed", "expert_mlp"))
    if cfg.router == "sigmoid":
        specs["router_bias"] = ParamSpec((cfg.n_experts,), (None,),
                                         init=zeros_init())
    if cfg.n_shared_experts:
        specs["shared"] = _mlp_specs(d, cfg.n_shared_experts * f,
                                     cfg.mlp_act)
    return specs


def capacity(tokens: int, cfg: ModelConfig) -> int:
    c = math.ceil(tokens * cfg.experts_per_token / cfg.n_experts
                  * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to 8


# Bound the dispatch working set for either mode: capacity dispatch
# materializes (E, C, D); grouped dispatch materializes the sorted
# M_pad = round_up(T*k + E*(block_m-1), block_m) row buffer. Chunks above
# this token count scan in sequence-chunks, so M_pad (like C) is per-chunk
# and the grouped buffer never exceeds ~chunk_tokens * k rows.
MOE_CHUNK_TOKENS = 65536

# Tokens per grouped dispatch on one device: the router sees the whole
# sequence (its balance loss is the sequence's), then the sorted buffer,
# sized for the worst case of each chunk, is built and run one chunk of
# tokens at a time, each chunk rematerialised in the backward, so the
# buffers of one chunk are live at a time.
GROUPED_DISPATCH_TOKENS = 2048

# Default m-tile for the grouped GEMM. CI exercises interpret mode at
# smoke scale, where a small tile keeps padding (≤ E*(block_m-1) wasted
# rows) negligible; the compiled kernel takes the MXU-aligned 128.
GROUPED_BLOCK_M = 8
PALLAS_BLOCK_M = 128

_EXPERT_WEIGHTS = ("w_up", "w_gate", "w_down")


def _noshard(x, logical):
    return x


def quantize_moe_params(params: Dict[str, Array]) -> Dict[str, Array]:
    """Symmetric per-expert int8 quantization of the expert weights.

    Each of w_up/w_gate/w_down becomes int8 with a fp32 per-expert scalar
    scale under ``<name>_scale`` (E,) — extending the serving stack's
    quantization-native story from KV pages to weights. The router stays
    full precision (its logits feed top-k; quantization there changes
    routing, not just values). Both dispatch modes consume the quantized
    dict: grouped dequantizes inside the kernel, capacity dequantizes
    eagerly per einsum."""
    out = dict(params)
    for name in _EXPERT_WEIGHTS:
        if name not in params:
            continue
        w = params[name].astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(w), axis=(1, 2)) / 127.0,
                            1e-12)
        q = jnp.clip(jnp.round(w / scale[:, None, None]), -127, 127)
        out[name] = q.astype(jnp.int8)
        out[name + "_scale"] = scale
    return out


def _weight(params: Dict[str, Array], name: str, compute_dtype) -> Array:
    """Expert weight in compute dtype, dequantizing int8 if scaled."""
    w = params[name]
    scale = params.get(name + "_scale")
    if scale is None:
        return w.astype(compute_dtype)
    return (w.astype(jnp.float32)
            * scale[:, None, None]).astype(compute_dtype)


def _route(params: Dict[str, Array], xt: Array, cfg: ModelConfig,
           compute_dtype):
    """Shared router over all ``n_experts``: fp32 logits and scores, the
    chosen k experts (T, k) and their gates, renormalised over the k
    and scaled by ``routed_scaling``."""
    k = cfg.experts_per_token
    if cfg.router == "sigmoid":
        # DeepSeek-V3 computes its gating in float32
        logits = jnp.dot(xt.astype(jnp.float32),
                         params["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.sigmoid(logits)  # (T, E)
        bias = jax.lax.stop_gradient(params["router_bias"])
        _, gate_idx = jax.lax.top_k(probs + bias.astype(jnp.float32), k)
        gate_w = jnp.take_along_axis(probs, gate_idx, axis=-1)
        gate_w = gate_w / (gate_w.sum(-1, keepdims=True) + 1e-20)
    else:
        logits = (xt @ params["router"].astype(compute_dtype)).astype(
            jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
        gate_w, gate_idx = jax.lax.top_k(probs, k)  # (T, k)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    return logits, probs, gate_w * cfg.routed_scaling, gate_idx


def _aux_losses(logits: Array, probs: Array, gate_idx: Array,
                cfg: ModelConfig, b: int, s: int) -> Dict[str, Array]:
    k, e = cfg.experts_per_token, cfg.n_experts
    if cfg.router == "sigmoid":
        # sequence-wise balance: per row, E/(k S) * assignments per
        # expert times the expert's mean normalised score
        p = probs.reshape(b, s, e)
        p = (p / p.sum(-1, keepdims=True)).mean(axis=1)  # (B, E)
        rows = jnp.arange(b)[:, None]
        f = jnp.zeros((b, e), jnp.float32).at[
            rows, gate_idx.reshape(b, s * k)].add(e / (k * s))
        return {"seq_balance": jnp.mean(jnp.sum(f * p, axis=-1))}
    t = b * s
    me = probs.mean(axis=0)  # mean router prob per expert
    ce = jnp.zeros((e,), jnp.float32).at[gate_idx.reshape(-1)].add(
        1.0 / (t * k))  # fraction of assignments per expert
    load_balance = e * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return {"load_balance": load_balance, "router_z": z_loss}


def _held(gate_idx: Array, cfg: ModelConfig) -> Tuple[Array, Array]:
    """(held expert's local id, or 0; whether the expert is held) of
    each assignment."""
    local = gate_idx - cfg.first_expert
    mine = (local >= 0) & (local < cfg.n_held)
    return jnp.where(mine, local, 0), mine


# ---------------------------------------------------------------------------
# Sort-based dropless dispatch (grouped)
# ---------------------------------------------------------------------------


class GroupedDispatch(NamedTuple):
    """Static-shape plan for the sort-based dropless dispatch.

    ``row_src`` (M_pad,): source token row per sorted slot (-1 = pad row).
    ``dest`` (T*k,): sorted slot of each token-major assignment, i.e. the
    inverse permutation the combine gathers through (-1: an expert not
    held here).
    ``block_experts`` (M_pad // block_m,): expert id per m-tile (-1 =
    pad-only tile) — the scalar-prefetched kernel metadata.
    ``counts`` (E,): assignments per held expert; ``offsets`` (E+1,):
    their cumsum (monotone; offsets[-1] == T*k when every expert is
    held); ``padded_starts`` (E,): each expert's block-aligned group
    start in the sorted buffer.
    """
    row_src: Array
    dest: Array
    block_experts: Array
    counts: Array
    offsets: Array
    padded_starts: Array

    @property
    def padded_rows(self) -> int:
        return self.row_src.shape[0]


def grouped_dispatch_plan(gate_idx: Array, *, n_experts: int,
                          block_m: int = GROUPED_BLOCK_M,
                          first: int = 0, held: Optional[int] = None
                          ) -> GroupedDispatch:
    """Build the sorted, block-aligned dispatch plan from (T, k) routing
    over ``n_experts``, of which ``held`` (default all) from ``first``
    are held here; the rest sort past the held groups and get no row.

    All shapes are static: the sorted buffer is sized at the worst case
    round_up(T*min(k, held) + held*(block_m-1), block_m) — every held
    expert's group padded to a block_m boundary — so each m-tile maps to
    exactly one expert."""
    t, k = gate_idx.shape
    tk = t * k
    e = n_experts if held is None else held
    bm = block_m
    m_pad = -(-(t * min(k, e) + e * (bm - 1)) // bm) * bm
    nb = m_pad // bm

    local = gate_idx.reshape(-1).astype(jnp.int32) - first  # token-major
    mine = (local >= 0) & (local < e)
    flat_e = jnp.where(mine, local, e)  # the others sort last
    order = jnp.argsort(flat_e)  # stable: ties keep token order
    sorted_e = flat_e[order]
    counts = jnp.zeros((e + 1,), jnp.int32).at[flat_e].add(1)[:e]
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)])
    padded = -(-counts // bm) * bm
    pad_ends = jnp.cumsum(padded)
    padded_starts = pad_ends - padded

    # Rank of each sorted assignment within its expert group, then its
    # destination slot in the block-aligned buffer (m_pad: none).
    held_e = jnp.minimum(sorted_e, e - 1)
    rank = jnp.arange(tk, dtype=jnp.int32) - offsets[held_e]
    dest_sorted = jnp.where(sorted_e < e, padded_starts[held_e] + rank,
                            m_pad)
    dest = jnp.zeros((tk,), jnp.int32).at[order].set(dest_sorted)
    dest = jnp.where(dest < m_pad, dest, -1)
    row_src = jnp.full((m_pad,), -1, jnp.int32).at[dest_sorted].set(
        order // k, mode="drop")

    tile_starts = jnp.arange(nb, dtype=jnp.int32) * bm
    block_experts = jnp.searchsorted(pad_ends, tile_starts,
                                     side="right").astype(jnp.int32)
    block_experts = jnp.where(tile_starts < pad_ends[-1],
                              block_experts, -1)
    return GroupedDispatch(row_src, dest, block_experts, counts, offsets,
                           padded_starts)


def grouped_permute(xt: Array, plan: GroupedDispatch, dtype) -> Array:
    """Gather token rows (T, D) into sorted order (M_pad, D); pad rows
    are zero (never read by the combine; psum identity under EP)."""
    src = jnp.maximum(plan.row_src, 0)
    xs = xt[src].astype(dtype)
    return jnp.where(plan.row_src[:, None] >= 0, xs,
                     jnp.zeros((), dtype))


def grouped_combine(y: Array, plan: GroupedDispatch, gate_w: Array,
                    t: int, k: int) -> Array:
    """Unpermute (M_pad, D) expert outputs back to token order and
    combine the k assignments with renormalized gate weights -> (T, D);
    an assignment to an expert not held adds nothing."""
    held = plan.dest[:, None] >= 0
    gathered = y[jnp.maximum(plan.dest, 0)]  # (T*k, D) token-major
    weights = gate_w.reshape(-1).astype(y.dtype)  # (T*k,)
    d = y.shape[-1]
    part = jnp.where(held, gathered * weights[:, None], 0)
    return part.reshape(t, k, d).sum(axis=1)


def _moe_ffn_grouped(params: Dict[str, Array], x: Array, cfg: ModelConfig,
                     compute_dtype, shard, impl: str, block_m: int, mesh,
                     expert_axis: str, with_counts: bool
                     ) -> Tuple[Array, Dict[str, Array]]:
    b, s, d = x.shape
    t = b * s
    k, e = cfg.experts_per_token, cfg.n_held
    xt = x.reshape(t, d)

    logits, probs, gate_w, gate_idx = _route(params, xt, cfg, compute_dtype)
    aux = _aux_losses(logits, probs, gate_idx, cfg, b, s)

    names = [n for n in _EXPERT_WEIGHTS if n in params]
    has_scale = any(n + "_scale" in params for n in names)
    ws, scales = [], []
    for n in names:
        sc = params.get(n + "_scale")
        ws.append(params[n] if sc is not None
                  else params[n].astype(compute_dtype))
        scales.append(sc)

    # Expert parallelism: shard experts over ``expert_axis`` when they
    # divide it, else fall back to fully-replicated compute on the mesh.
    ep = 1
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if expert_axis in sizes and e % sizes[expert_axis] == 0:
            ep = sizes[expert_axis]
    e_local = e // ep

    # The whole sorted-dispatch pipeline — plan, permute, grouped GEMM,
    # unpermute/combine — runs inside ONE shard_map on serving meshes.
    # The plan's integer sort/scatter/searchsorted math must compile
    # per-device: left to GSPMD, sharding propagation through the decode
    # scan partitions those scatters and the computed plan (hence the
    # routed outputs) silently diverges from the single-host program.
    # shard_map replicates the (small) token activations, shards only the
    # expert dim of the weights, and psums the expert-partial rows — pad
    # rows and non-local tiles are zero, the psum identity.
    def run(xt_, gate_w_, gate_idx_, *wx):
        ws_ = wx[:len(names)]
        scs_ = wx[len(names):] if has_scale else (None,) * len(names)
        plan = grouped_dispatch_plan(gate_idx_, n_experts=cfg.n_experts,
                                     block_m=block_m,
                                     first=cfg.first_expert, held=e)
        xs = grouped_permute(xt_, plan, compute_dtype)
        gids = plan.block_experts
        if ep > 1:
            lo = jax.lax.axis_index(expert_axis) * e_local
            g = gids - lo
            gids = jnp.where((g >= 0) & (g < e_local), g, -1)
        by = dict(zip(names, zip(ws_, scs_)))

        def gm(rows, name):
            w, sc = by[name]
            return kops.grouped_matmul(rows, w, gids, w_scale=sc,
                                       impl=impl)

        up = gm(xs, "w_up")
        h = swiglu(gm(xs, "w_gate"), up) if cfg.mlp_act == "swiglu" \
            else gelu(up)
        down = gm(h.astype(compute_dtype), "w_down")
        if ep > 1:
            down = jax.lax.psum(down, expert_axis)
        return (grouped_combine(down, plan, gate_w_, xt_.shape[0], k),
                plan.counts)

    args = [xt, gate_w, gate_idx] + ws + (scales if has_scale else [])
    dispatch_tokens = GROUPED_DISPATCH_TOKENS
    if mesh is None and t > dispatch_tokens and t % dispatch_tokens == 0:
        n = t // dispatch_tokens

        def chunk(_, rows):
            return None, run(*rows, *args[3:])

        _, (outs, counts) = jax.lax.scan(
            jax.checkpoint(chunk), None,
            tuple(a.reshape(n, dispatch_tokens, *a.shape[1:])
                  for a in args[:3]))
        out = outs.reshape(t, d)  # counts: (chunks, E), padded per chunk
    elif mesh is None:
        out, counts = run(*args)
    else:
        P = jax.sharding.PartitionSpec
        wspec = P(expert_axis) if ep > 1 else P()
        in_specs = [P(), P(), P()] + [wspec] * len(names) * (
            2 if has_scale else 1)
        out, counts = jax.shard_map(run, mesh=mesh,
                                    in_specs=tuple(in_specs),
                                    out_specs=(P(), P()),
                                    check_vma=False)(*args)
    if with_counts:
        aux["moe_held_rows"] = counts.sum()
        aux["moe_padded_rows"] = (-(-counts // block_m) * block_m).sum()
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Capacity dispatch (GShard) + the public entry point
# ---------------------------------------------------------------------------


def moe_ffn(params: Dict[str, Array], x: Array, cfg: ModelConfig,
            compute_dtype,
            chunk_tokens: int = MOE_CHUNK_TOKENS,
            shard=_noshard,
            dropless: bool = False,
            dispatch: str = "capacity",
            impl: str = "ref",
            block_m: int = GROUPED_BLOCK_M,
            mesh=None,
            expert_axis: str = "data",
            with_counts: bool = False
            ) -> Tuple[Array, Dict[str, Array]]:
    """x: (B, S, D) -> (out, aux).

    Token count above ``chunk_tokens`` is processed in sequence-chunks
    (scan), bounding the dispatch working set — (E, C, D) for capacity,
    the sorted M_pad row buffer for grouped. Capacity is then per-chunk,
    which is the standard serving/prefill trade-off; grouped results are
    chunk-invariant (each row's GEMM is independent of group packing).

    ``dispatch="capacity"`` is GShard's dispatch, kept as the oracle of
    the grouped path. ``dropless=True`` sizes its buffer so no
    assignment can overflow (capacity = chunk token count): each token's
    output becomes independent of the rest of the batch. Without it,
    overflowing assignments drop.

    ``dispatch="grouped"`` is the sort-based dropless path of training
    and serving (see module docstring): dropless by construction, routed
    through the m-grouped GEMM kernel. ``impl`` selects the kernel body
    ("pallas" / "interpret" / "ref"); ``mesh`` + ``expert_axis`` enable
    the expert-parallel shard_map wrapper; ``with_counts`` adds the
    ``moe_held_rows`` and ``moe_padded_rows`` counts to ``aux``; off a
    mesh, the dispatch runs ``GROUPED_DISPATCH_TOKENS`` tokens at a time.

    The output holds the held experts' part of the routed result, scaled
    by ``routed_scaling``, plus the shared experts."""
    b, s, d = x.shape
    if dispatch == "grouped":
        flat = partial(_moe_ffn_grouped, compute_dtype=compute_dtype,
                       shard=shard, impl=impl, block_m=block_m, mesh=mesh,
                       expert_axis=expert_axis, with_counts=with_counts)
    elif dispatch == "capacity":
        flat = partial(_moe_ffn_flat, compute_dtype=compute_dtype,
                       shard=shard, dropless=dropless)
    else:
        raise ValueError(f"unknown MoE dispatch {dispatch!r}")
    if b * s > chunk_tokens and (b * s) % chunk_tokens == 0 and \
            s % (b * s // chunk_tokens) == 0:
        n_chunks = b * s // chunk_tokens
        sc = s // n_chunks
        xc = x.reshape(b, n_chunks, sc, d).transpose(1, 0, 2, 3)

        def body(_, xi):
            out, aux = flat(params, xi, cfg)
            return None, (out, aux)

        _, (outs, auxs) = jax.lax.scan(body, None, xc)
        out = outs.transpose(1, 0, 2, 3).reshape(b, s, d)
        # losses average over the chunks, row counts add up
        aux = {k: a.sum(0) if k.startswith("moe_") else a.mean(0)
               for k, a in auxs.items()}
    else:
        out, aux = flat(params, x, cfg)
    if cfg.n_shared_experts:
        out = out + dense_ffn(params["shared"], x, cfg, compute_dtype)
    return out, aux


def _moe_ffn_flat(params: Dict[str, Array], x: Array, cfg: ModelConfig,
                  compute_dtype, shard=_noshard, dropless: bool = False
                  ) -> Tuple[Array, Dict[str, Array]]:
    b, s, d = x.shape
    t = b * s
    k, e = cfg.experts_per_token, cfg.n_held
    xt = x.reshape(t, d)

    logits, probs, gate_w, gate_idx = _route(params, xt, cfg, compute_dtype)
    local, mine = _held(gate_idx, cfg)

    # An expert receives at most one assignment per token (top-k indices are
    # distinct), so capacity = t can never drop.
    cap = t if dropless else capacity(t, cfg)
    # Priority order: all top-1 assignments, then top-2, ... (GShard).
    flat_idx = local.T.reshape(-1)  # (k*T,)
    flat_mine = mine.T.reshape(-1)
    onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.int32) \
        * flat_mine[:, None]  # (kT, E)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos_in_e = jnp.take_along_axis(pos, flat_idx[:, None], axis=1)[:, 0]
    keep = flat_mine & (pos_in_e < cap)

    xk = jnp.broadcast_to(xt[None], (k, t, d)).reshape(k * t, d)
    xk = jnp.where(keep[:, None], xk, jnp.zeros((), compute_dtype))
    xk = shard(xk, ("batch", "embed"))
    slot = jnp.where(keep, pos_in_e, cap)  # cap: dropped
    dispatched = jnp.zeros((e, cap, d), compute_dtype).at[
        flat_idx, slot].add(xk, mode="drop")
    dispatched = shard(dispatched, ("expert", "exp_cap", None))

    # Expert matmuls: E sharded over data (EP), hidden over model (TP).
    up = jnp.einsum("ecd,edf->ecf", dispatched,
                    _weight(params, "w_up", compute_dtype))
    if cfg.mlp_act == "swiglu":
        gate = jnp.einsum("ecd,edf->ecf", dispatched,
                          _weight(params, "w_gate", compute_dtype))
        h = swiglu(gate, up)
    else:
        h = gelu(up)
    h = shard(h, ("expert", "exp_cap", "expert_mlp"))
    down = jnp.einsum("ecf,efd->ecd", h,
                      _weight(params, "w_down", compute_dtype))
    down = shard(down, ("expert", "exp_cap", None))

    gathered = down.at[flat_idx, slot].get(
        mode="fill", fill_value=0)  # (kT, D)
    gathered = shard(gathered, ("batch", "embed"))
    gathered = jnp.where(keep[:, None], gathered,
                         jnp.zeros((), compute_dtype))
    weights = (gate_w.T.reshape(-1) * keep).astype(compute_dtype)  # (kT,)
    out = (gathered * weights[:, None]).reshape(k, t, d).sum(axis=0)

    aux = _aux_losses(logits, probs, gate_idx, cfg, b, s)
    return out.reshape(b, s, d), aux


def dense_ffn(params: Dict[str, Array], x: Array, cfg: ModelConfig,
              compute_dtype) -> Array:
    up = x @ params["w_up"].astype(compute_dtype)
    if cfg.mlp_act == "swiglu":
        h = swiglu(x @ params["w_gate"].astype(compute_dtype), up)
    else:
        h = gelu(up)
    return h @ params["w_down"].astype(compute_dtype)


def _mlp_specs(d: int, f: int, act: str) -> Dict[str, ParamSpec]:
    specs = {
        "w_up": ParamSpec((d, f), ("embed", "mlp")),
        "w_down": ParamSpec((f, d), ("mlp", "embed")),
    }
    if act == "swiglu":
        specs["w_gate"] = ParamSpec((d, f), ("embed", "mlp"))
    return specs


def dense_ffn_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    return _mlp_specs(cfg.d_model, cfg.d_ff, cfg.mlp_act)
