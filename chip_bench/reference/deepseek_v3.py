"""Plain DeepSeek-V3 block in jax.numpy (Moonlight, Kimi K2): weights from
a seed, the training loss and its gradient, and AdamW. Nothing here
imports the program under test.

It follows DeepSeek-V3 (arXiv:2412.19437) and its ``modeling_deepseek.py``
modelling code:

- RMSNorm before attention and before the MLP; a final RMSNorm; an
  untied head.
- Multi-head latent attention: q from x (or through a ``q_lora_rank``
  latent and its RMSNorm); the joint ``kv_a`` projection to a
  ``kv_lora_rank`` latent and one rotary key of ``qk_rope_head_dim``
  shared by all heads; the latent's RMSNorm (eps 1e-6, the norm's
  default); ``kv_b`` up to per-head k_nope and v. A score is
  q_nope.k_nope + q_rope.k_rope over sqrt(qk_nope + qk_rope); rotary
  embeddings rotate interleaved pairs (``apply_rotary_pos_emb``).
- ``first_k_dense_replace`` dense SwiGLU layers, then MoE layers: a
  float32 router over ``router_experts`` experts with sigmoid scores,
  the top ``num_experts_per_tok`` of the scores plus the correction bias
  (selection only, no gradient), gates renormalised over the chosen and
  scaled by ``routed_scaling_factor``; ``n_shared_experts`` always-on
  experts as one SwiGLU MLP of that many times the expert width.
- The chip's share: only the ``n_routed_experts`` experts held here,
  from ``first_held_expert``, add to the output. Each is applied to
  every token and weighted by its gate, zero where it was not chosen.
- The sequence-wise balance loss (§2.1.2) of each MoE layer over all
  router experts, alpha 0.0001, added to the mean cross entropy.

``dtype`` is the precision the reference computes in: float32 at
"highest" matmul precision is the reference; the controls are bfloat16
throughout and FP8 (every matrix product's operands rounded to
float8_e4m3fn). The router is float32 at "highest" in all of them, as
DeepSeek-V3 computes its gating. Work is done one sequence at a time,
with queries in blocks, layers, attention blocks and experts
rematerialised and the vocabulary projection in blocks of rows, so that
the reference fits on one chip beside nothing else.

The weights use the program's nested layout (the leading dense layers
stacked under ``dense_blocks``, the MoE layers under ``blocks``), so the
benchmark can hand the tree it made to the program and make it again
here from the seed.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chip_bench.reference import qwen2
from chip_bench.reference.qwen2 import (FP8, Q_BLOCK, V_BLOCK,  # noqa: F401
                                        _dot, _rms, _rope, flat_floats,
                                        global_norm, leaf_diff_norms,
                                        leaf_norms, lr_at, storage)

BALANCE_ALPHA = 1e-4
LATENT_EPS = 1e-6


def dims(conf: Dict[str, Any]) -> Dict[str, int]:
    held = conf["n_routed_experts"]
    L, Ld = conf["num_hidden_layers"], conf["first_k_dense_replace"]
    dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    return {"L": L, "Ld": Ld, "Lm": L - Ld, "D": conf["hidden_size"],
            "H": conf["num_attention_heads"], "dn": dn, "dr": dr,
            "dqk": dn + dr, "dv": conf["v_head_dim"],
            "R": conf["kv_lora_rank"], "QL": conf["q_lora_rank"] or 0,
            "F": conf["intermediate_size"],
            "Fe": conf["moe_intermediate_size"],
            "E": conf.get("router_experts", held), "Eh": held,
            "E0": conf.get("first_held_expert", 0),
            "K": conf["num_experts_per_tok"],
            "Fs": conf["n_shared_experts"] * conf["moe_intermediate_size"],
            "V": conf["vocab_size"]}


def mla_matmul_params(n: Dict[str, int]) -> int:
    """Weights of one layer's MLA projections."""
    d, h = n["D"], n["H"]
    q = (d * n["QL"] + n["QL"] * h * n["dqk"]) if n["QL"] \
        else d * h * n["dqk"]
    return (q + d * (n["R"] + n["dr"]) + n["R"] * h * (n["dn"] + n["dv"])
            + h * n["dv"] * d)


def train_flops(conf: Dict[str, Any], seq: int, tokens: int,
                counters: Dict[str, float]) -> float:
    """Forward and backward FLOPs (three times the forward; ``flops.py``'s
    rules) of ``tokens`` trained tokens in sequences of ``seq``. The
    routed experts' work is read from the program's count of the
    assignments routed to the held experts over those steps
    (``counters["moe_held_rows"]``); everything else follows from the
    shapes. Nothing recomputed or padded is counted."""
    n = dims(conf)
    d = n["D"]
    per_token = (2 * mla_matmul_params(n) * n["L"]
                 + 2 * 3 * d * n["F"] * n["Ld"]
                 + (2 * d * n["E"] + 2 * 3 * d * n["Fs"]) * n["Lm"]
                 + 2 * d * n["V"])
    keys_per_seq = seq * (seq + 1) // 2
    attention = (2 * n["H"] * (n["dqk"] + n["dv"]) * keys_per_seq * n["L"]
                 * tokens / seq)
    routed = 2 * 3 * d * n["Fe"] * counters.get("moe_held_rows", 0.0)
    return 3.0 * (per_token * tokens + attention + routed)


STATIC_KEYS = ("hidden_size", "num_attention_heads", "num_hidden_layers",
               "first_k_dense_replace", "intermediate_size",
               "moe_intermediate_size", "n_routed_experts", "router_experts",
               "first_held_expert", "num_experts_per_tok",
               "n_shared_experts", "kv_lora_rank", "q_lora_rank",
               "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
               "vocab_size", "rms_norm_eps", "rope_theta",
               "routed_scaling_factor")


def _static(conf: Dict[str, Any]) -> Tuple:
    """The configuration's numbers the equations read, hashable."""
    return tuple((k, conf[k]) for k in STATIC_KEYS if k in conf)


def _mla_shapes(n, L):
    d, h = n["D"], n["H"]
    core = {"wkv_a": (L, d, n["R"] + n["dr"]), "kv_norm": (L, n["R"]),
            "wkv_b": (L, n["R"], h, n["dn"] + n["dv"]),
            "wo": (L, h, n["dv"], d)}
    if n["QL"]:
        core.update(wq_a=(L, d, n["QL"]), q_norm=(L, n["QL"]),
                    wq_b=(L, n["QL"], h, n["dqk"]))
    else:
        core["wq"] = (L, d, h, n["dqk"])
    return core


def _mlp_shapes(L, d, f):
    return {"w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)}


def weight_shapes(conf: Dict[str, Any]) -> Dict[str, Any]:
    """Shapes of every weight, in the program's layout."""
    n = dims(conf)
    d, Ld, Lm, Eh, Fe = n["D"], n["Ld"], n["Lm"], n["Eh"], n["Fe"]
    moe = {"router": (Lm, d, n["E"]), "router_bias": (Lm, n["E"]),
           "w_gate": (Lm, Eh, d, Fe), "w_up": (Lm, Eh, d, Fe),
           "w_down": (Lm, Eh, Fe, d)}
    if n["Fs"]:
        moe["shared"] = _mlp_shapes(Lm, d, n["Fs"])
    out = {"embed": (n["V"], d), "final_norm": (d,), "lm_head": (d, n["V"]),
           "blocks": {"sl0": {"ln1": (Lm, d), "ln2": (Lm, d),
                              "core": _mla_shapes(n, Lm), "mlp": moe}}}
    if Ld:
        out["dense_blocks"] = {"sl0": {
            "ln1": (Ld, d), "ln2": (Ld, d), "core": _mla_shapes(n, Ld),
            "mlp": _mlp_shapes(Ld, d, n["F"])}}
    return out


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


NORMS = ("ln", "final_norm", "q_norm", "kv_norm")


def make_weights(conf: Dict[str, Any], key: jax.Array, dtype=jnp.float32):
    """Random weights, all made on the device in one jitted call.

    Matrices, the embedding and the correction bias: normal with the
    configuration's ``initializer_range``. Norm scales: 1 + 0.1 * normal,
    so they are not all ones."""
    shapes = weight_shapes(conf)
    std = conf.get("initializer_range", 0.02)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)

    def build(key):
        keys = jax.random.split(key, len(paths))
        leaves = []
        for k, (path, shape) in zip(keys, paths):
            name = str(path[-1].key)
            z = jax.random.normal(k, shape, jnp.float32)
            if name.startswith(NORMS):
                leaves.append((1.0 + 0.1 * z).astype(dtype))
            else:
                leaves.append((std * z).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(key)


# ---------------------------------------------------------------------------
# The forward pass
# ---------------------------------------------------------------------------


def _rope_pairs(x, pos, theta):
    """x (S, heads, d): ``apply_rotary_pos_emb``'s layout, the lanes
    (0, 2, ..., 1, 3, ...) regrouped into halves, then rotated."""
    s, h, d = x.shape
    x = x.reshape(s, h, d // 2, 2).swapaxes(-1, -2).reshape(s, h, d)
    return _rope(x, pos, theta)


def _attention(x, cw, pos, n, theta, dtype):
    """MLA over a whole sequence x (S, D) -> (S, D)."""
    s = x.shape[0]
    dn, R = n["dn"], n["R"]
    if n["QL"]:
        qa = _rms(_dot("sd,dr->sr", x, cw["wq_a"], dtype), cw["q_norm"],
                  LATENT_EPS)
        q = _dot("sr,rhk->shk", qa, cw["wq_b"], dtype)
    else:
        q = _dot("sd,dhk->shk", x, cw["wq"], dtype)
    kv = _dot("sd,dr->sr", x, cw["wkv_a"], dtype)
    latent = _rms(kv[:, :R], cw["kv_norm"], LATENT_EPS)
    kvb = _dot("sr,rhk->shk", latent, cw["wkv_b"], dtype)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    q_nope = q[..., :dn]
    q_rope = _rope_pairs(q[..., dn:], pos, theta)
    k_rope = _rope_pairs(kv[:, None, R:], pos, theta)[:, 0]  # (S, dr)
    qb = min(Q_BLOCK, s)
    scale = 1.0 / math.sqrt(n["dqk"])

    @jax.checkpoint
    def block(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * qb, qb, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i * qb, qb, 0)
        sc = (_dot("qhk,shk->hqs", qn, k_nope, dtype)
              + _dot("qhk,sk->hqs", qr, k_rope, dtype)) * scale
        qpos = i * qb + jnp.arange(qb)
        allowed = qpos[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(allowed[None], sc, jnp.asarray(-1e30, sc.dtype))
        p = jax.nn.softmax(sc, axis=-1)
        return _dot("hqs,shk->qhk", p, v, dtype)

    att = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, n["H"], n["dv"])
    return _dot("shk,hkd->sd", att, cw["wo"], dtype)


def _swiglu(h, mw, dtype):
    gate = _dot("sd,df->sf", h, mw["w_gate"], dtype)
    up = _dot("sd,df->sf", h, mw["w_up"], dtype)
    return _dot("sf,fd->sd", jax.nn.silu(gate) * up, mw["w_down"], dtype)


def _moe(h, mw, n, scaling, dtype):
    """The held experts' part of an MoE layer and the shared experts
    over a sequence h (S, D); returns (out, balance loss)."""
    s = h.shape[0]
    logits = jnp.einsum("sd,de->se", h.astype(jnp.float32),
                        mw["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)  # (S, E)
    bias = jax.lax.stop_gradient(mw["router_bias"].astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + bias, n["K"])  # (S, K)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scaling
    held = n["E0"] + jnp.arange(n["Eh"])
    gates = jnp.sum(jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0),
                    axis=1)  # (S, Eh): zero where not chosen

    @jax.checkpoint
    def expert(acc, xs):
        ew, g = xs
        return acc + g[:, None].astype(h.dtype) * _swiglu(h, ew, dtype), None

    experts = {k: mw[k] for k in ("w_gate", "w_up", "w_down")}
    out, _ = jax.lax.scan(expert, jnp.zeros_like(h), (experts, gates.T))
    if n["Fs"]:
        out = out + _swiglu(h, mw["shared"], dtype)
    frac = jnp.zeros((n["E"],), jnp.float32).at[idx.reshape(-1)].add(
        n["E"] / (n["K"] * s))
    share = (scores / scores.sum(-1, keepdims=True)).mean(axis=0)
    return out, jnp.sum(frac * share)


def hidden_states(w, conf, tokens, dtype):
    """Final-normed hidden states (S, D) of one sequence and the sum of
    the MoE layers' balance losses. ``tokens``' length must be a
    multiple of the query block (or shorter than it)."""
    n = dims(conf)
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    scaling = conf["routed_scaling_factor"]
    pos = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens]

    @jax.checkpoint
    def dense(x, lw):
        x = x + _attention(_rms(x, lw["ln1"], eps), lw["core"], pos, n,
                           theta, dtype)
        return x + _swiglu(_rms(x, lw["ln2"], eps), lw["mlp"], dtype), None

    @jax.checkpoint
    def moe(x, lw):
        x = x + _attention(_rms(x, lw["ln1"], eps), lw["core"], pos, n,
                           theta, dtype)
        out, bal = _moe(_rms(x, lw["ln2"], eps), lw["mlp"], n, scaling,
                        dtype)
        return x + out, bal

    if n["Ld"]:
        x, _ = jax.lax.scan(dense, x, w["dense_blocks"]["sl0"])
    x, bals = jax.lax.scan(moe, x, w["blocks"]["sl0"])
    return _rms(x, w["final_norm"], eps), jnp.sum(bals)


# ---------------------------------------------------------------------------
# Training: loss, gradient, clipping and AdamW
# ---------------------------------------------------------------------------


def seq_loss(w, conf, tokens, labels, dtype):
    """Mean next-token cross entropy of one sequence, with the vocabulary
    projection and its softmax in blocks of rows, plus alpha times the
    MoE layers' balance losses."""
    x, bal = hidden_states(w, conf, tokens, dtype)
    s = x.shape[0]
    rb = min(V_BLOCK, s)

    @jax.checkpoint
    def block(i):
        xi = jax.lax.dynamic_slice_in_dim(x, i * rb, rb, 0)
        li = jax.lax.dynamic_slice_in_dim(labels, i * rb, rb, 0)
        logits = _dot("nd,dv->nv", xi, w["lm_head"], dtype).astype(
            jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    xent = jnp.sum(jax.lax.map(block, jnp.arange(s // rb))) / s
    return xent + BALANCE_ALPHA * bal


@functools.partial(jax.jit, static_argnums=(1, 4))
def _batch_value_and_grad(w, conf_items, tokens, labels, dtype):
    conf = dict(conf_items)

    def batch_loss(w):
        def row(total, xs):
            return total + seq_loss(w, conf, xs[0], xs[1], dtype), None
        total, _ = jax.lax.scan(jax.checkpoint(row),
                                jnp.zeros((), jnp.float32), (tokens, labels))
        return total / tokens.shape[0]

    return jax.value_and_grad(batch_loss)(w)


def batch_value_and_grad(w, conf, tokens: np.ndarray, labels: np.ndarray,
                         dtype=jnp.float32):
    """Row-mean loss and gradient of a batch of equal-length rows, the
    rows in sequence inside one program (one gradient buffer)."""
    loss, grad = _batch_value_and_grad(w, _static(conf), jnp.asarray(tokens),
                                       jnp.asarray(labels), dtype)
    return float(loss), grad


# AdamW with the old weights, moments and gradient given up to the new
# ones: two copies of each would not fit beside the chip's one
_adamw = jax.jit(qwen2._adamw, static_argnums=(5,),
                 donate_argnums=(0, 1, 2, 3))


def train_steps(conf, opt: Dict[str, Any], batches: List[Tuple], key,
                dtype=jnp.float32):
    """Run ``len(batches)`` optimizer steps from the seeded weights.

    Returns (losses, per-leaf norms of the first clipped gradient,
    per-leaf norms of the weights' change over all the steps). The
    starting weights are made again from the seed for the change, so
    that only one copy of the weights is held while the steps run."""
    w = make_weights(conf, key, storage(dtype))
    m = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), w)
    v = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), w)
    hyper = (opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"])
    losses, first_grad = [], None
    for step, (tokens, labels) in enumerate(batches):
        loss, g = batch_value_and_grad(w, conf, tokens, labels, dtype)
        losses.append(loss)
        gn = float(global_norm(g))
        clip = min(1.0, opt["grad_clip"] / max(gn, 1e-9))
        if step == 0:
            first_grad = flat_floats(leaf_norms(
                jax.tree.map(lambda x: x.astype(jnp.float32) * clip, g)))
        scal = jnp.asarray([lr_at(step, opt), step + 1.0, clip],
                           jnp.float32)
        w, m, v = _adamw(w, m, v, g, scal, hyper)
        del g
    del m, v
    change = flat_floats(leaf_diff_norms(w, make_weights(
        conf, key, storage(dtype))))
    return losses, first_grad, change
