"""Plain Qwen2 in jax.numpy: weights from a seed, the forward pass, the
training loss and its gradient, and AdamW. Nothing here imports the
program under test.

It follows the published Qwen2 equations (arXiv:2407.10671; the
``Qwen2ForCausalLM`` modelling code): RMSNorm before attention and
before the MLP, grouped-query attention with biases on q, k and v,
rotary embeddings over the whole head (the two halves rotated
together), causal softmax attention, a SwiGLU MLP, a final RMSNorm and
a head tied to the embedding when ``tie_word_embeddings`` is set.

``dtype`` is the precision the reference computes in: float32 at
"highest" matmul precision is the reference. The controls are
bfloat16 throughout, and FP8 (every matrix product's operands rounded to
float8_e4m3fn, products and everything else in float32).
Work is done one sequence at a time, with queries in blocks, layers
rematerialised and the vocabulary projection in blocks of rows, so that
the reference fits on one chip beside nothing else.

The weights use the same nested layout as the program's parameter tree
(stacked over layers), so the benchmark can hand the tree it made to the
program and make it again here from the seed.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chip_bench import flops

Q_BLOCK = 512  # query rows per attention block
V_BLOCK = 1024  # rows per vocabulary-projection block


def dims(conf: Dict[str, Any]) -> Dict[str, int]:
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return {"L": conf["num_hidden_layers"], "D": d, "H": h,
            "KV": conf["num_key_value_heads"],
            "hd": conf.get("head_dim") or d // h,
            "F": conf["intermediate_size"], "V": conf["vocab_size"]}


def train_flops(conf: Dict[str, Any], seq: int, tokens: int,
                counters: Dict[str, float]) -> float:
    """Forward and backward FLOPs of ``tokens`` trained tokens in
    sequences of ``seq`` (three times the forward; ``flops.py``). The
    work of a dense model follows from its shapes alone, so the
    program's ``counters`` over those steps are not read."""
    return flops.train_token_flops(dims(conf), seq) * tokens


STATIC_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
               "num_hidden_layers", "intermediate_size", "vocab_size",
               "head_dim", "rms_norm_eps", "rope_theta",
               "tie_word_embeddings")


def _static(conf: Dict[str, Any]) -> Tuple:
    """The configuration's numbers the equations read, hashable."""
    return tuple((k, conf[k]) for k in STATIC_KEYS if k in conf)


def weight_shapes(conf: Dict[str, Any]) -> Dict[str, Any]:
    """Shapes of every weight, in the program's layout."""
    n = dims(conf)
    L, D, H, KV, hd, F, V = (n[k] for k in ("L", "D", "H", "KV", "hd",
                                            "F", "V"))
    sl = {"ln1": (L, D), "ln2": (L, D),
          "core": {"wq": (L, D, H, hd), "wk": (L, D, KV, hd),
                   "wv": (L, D, KV, hd), "wo": (L, H, hd, D),
                   "bq": (L, H, hd), "bk": (L, KV, hd), "bv": (L, KV, hd)},
          "mlp": {"w_gate": (L, D, F), "w_up": (L, D, F),
                  "w_down": (L, F, D)}}
    out = {"embed": (V, D), "final_norm": (D,), "blocks": {"sl0": sl}}
    if not conf["tie_word_embeddings"]:
        out["lm_head"] = (D, V)
    return out


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make_weights(conf: Dict[str, Any], key: jax.Array, dtype=jnp.float32):
    """Random weights, all made on the device in one jitted call.

    Matrices and the embedding: normal with the configuration's
    ``initializer_range``. Biases: normal at the same scale, so the bias
    path carries signal. Norm scales: 1 + 0.1 * normal, so they are not
    all ones."""
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
            if name.startswith(("ln", "final_norm")):
                leaves.append((1.0 + 0.1 * z).astype(dtype))
            else:
                leaves.append((std * z).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(key)


# ---------------------------------------------------------------------------
# The forward pass
# ---------------------------------------------------------------------------


FP8 = "float8_e4m3fn"  # the FP8 control's ``dtype``


def storage(dtype):
    """The dtype the weights and activations are held in."""
    return jnp.float32 if dtype == FP8 else dtype


def _dot(eq, a, b, dtype):
    """One matrix product in the reference's precision."""
    if dtype == FP8:
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    prec = (jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)
    return jnp.einsum(eq, a, b, precision=prec)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, pos, theta):
    """x (S, heads, hd); rotate the two halves of each head together."""
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lw, pos, n, eps, theta, dtype):
    """One decoder layer over a whole sequence x (S, D)."""
    s = x.shape[0]
    h = _rms(x, lw["ln1"], eps)
    q = _dot("sd,dhk->shk", h, lw["core"]["wq"], dtype)
    k = _dot("sd,dhk->shk", h, lw["core"]["wk"], dtype)
    v = _dot("sd,dhk->shk", h, lw["core"]["wv"], dtype)
    q = _rope(q + lw["core"]["bq"], pos, theta)
    k = _rope(k + lw["core"]["bk"], pos, theta)
    v = v + lw["core"]["bv"]
    group = n["H"] // n["KV"]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    qb = min(Q_BLOCK, s)
    scale = 1.0 / math.sqrt(n["hd"])

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        sc = _dot("qhk,shk->hqs", qi, k, dtype) * scale
        qpos = i * qb + jnp.arange(qb)
        allowed = qpos[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(allowed[None], sc, jnp.asarray(-1e30, sc.dtype))
        p = jax.nn.softmax(sc, axis=-1)
        return _dot("hqs,shk->qhk", p, v, dtype)

    att = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, n["H"], n["hd"])
    x = x + _dot("shk,hkd->sd", att, lw["core"]["wo"], dtype)
    h = _rms(x, lw["ln2"], eps)
    gate = _dot("sd,df->sf", h, lw["mlp"]["w_gate"], dtype)
    up = _dot("sd,df->sf", h, lw["mlp"]["w_up"], dtype)
    return x + _dot("sf,fd->sd", jax.nn.silu(gate) * up,
                    lw["mlp"]["w_down"], dtype)


def hidden_states(w, conf, tokens, dtype, remat=False):
    """Final-normed hidden states (S, D) of one sequence. ``tokens``'
    length must be a multiple of the query block (or shorter than it)."""
    n = dims(conf)
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    pos = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens]

    def body(x, lw):
        return _layer(x, lw, pos, n, eps, theta, dtype), None

    if remat:
        body = jax.checkpoint(body)
    layers = w["blocks"]["sl0"]
    x, _ = jax.lax.scan(body, x, layers)
    return _rms(x, w["final_norm"], eps)


def _head(w, conf):
    return w["embed"].T if conf["tie_word_embeddings"] else w["lm_head"]


@functools.partial(jax.jit, static_argnums=(1, 4))
def _served_logits(w, conf_items, tokens, rows, dtype):
    conf = dict(conf_items)
    x = hidden_states(w, conf, tokens, dtype)
    return _dot("nd,dv->nv", x[rows], _head(w, conf),
                dtype).astype(jnp.float32)


def served_logits(w, conf, tokens: np.ndarray, rows: np.ndarray,
                  pad_to: int, rows_to: int, dtype=jnp.float32):
    """Logits (len(rows), V) at positions ``rows`` of ``tokens``.

    The sequence is padded at the end to ``pad_to`` (causal attention
    keeps the padding out of every earlier position) and ``rows`` to
    ``rows_to``, so one compiled program serves every request of a
    cell."""
    t = np.zeros(pad_to, np.int32)
    t[:len(tokens)] = tokens
    r = np.zeros(rows_to, np.int32)
    r[:len(rows)] = rows
    out = _served_logits(w, _static(conf), jnp.asarray(t),
                         jnp.asarray(r), dtype)
    return np.asarray(out[:len(rows)])


# ---------------------------------------------------------------------------
# Training: loss, gradient, clipping and AdamW
# ---------------------------------------------------------------------------


def seq_loss(w, conf, tokens, labels, dtype):
    """Mean next-token cross entropy of one sequence, with the vocabulary
    projection and its softmax in blocks of rows."""
    x = hidden_states(w, conf, tokens, dtype, remat=True)
    head = _head(w, conf)
    s = x.shape[0]
    rb = min(V_BLOCK, s)

    @jax.checkpoint
    def block(i):
        xi = jax.lax.dynamic_slice_in_dim(x, i * rb, rb, 0)
        li = jax.lax.dynamic_slice_in_dim(labels, i * rb, rb, 0)
        logits = _dot("nd,dv->nv", xi, head, dtype).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    return jnp.sum(jax.lax.map(block, jnp.arange(s // rb))) / s


@functools.partial(jax.jit, static_argnums=(1, 4))
def _seq_value_and_grad(w, conf_items, tokens, labels, dtype):
    return jax.value_and_grad(seq_loss)(w, dict(conf_items), tokens,
                                        labels, dtype)


@jax.jit
def _axpy(a, x, y):
    return jax.tree.map(lambda xi, yi: a * xi + yi, x, y)


def batch_value_and_grad(w, conf, tokens: np.ndarray, labels: np.ndarray,
                         dtype=jnp.float32):
    """Token-mean loss and gradient of a batch of equal-length rows,
    one row at a time."""
    items = _static(conf)
    b = tokens.shape[0]
    loss, grad = 0.0, None
    for i in range(b):
        li, gi = _seq_value_and_grad(w, items, jnp.asarray(tokens[i]),
                                     jnp.asarray(labels[i]), dtype)
        loss += float(li) / b
        grad = gi if grad is None else _axpy(1.0, gi, grad)
        del gi
    return loss, jax.tree.map(lambda g: g / b, grad)


def lr_at(step: int, opt: Dict[str, Any]) -> float:
    """Linear warm-up, then cosine decay to ``final_fraction`` of the
    peak."""
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], \
        opt["total_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    ff = opt["final_fraction"]
    return peak * (ff + (1 - ff) * 0.5 * (1 + math.cos(math.pi * prog)))


@functools.partial(jax.jit, static_argnums=(5,))
def _adamw(w, m, v, g, scal, hyper):
    b1, b2, eps, wd = hyper
    lr, t, clip = scal[0], scal[1], scal[2]

    def upd(p, mi, vi, gi):
        gi = gi.astype(jnp.float32) * clip
        mi = b1 * mi + (1 - b1) * gi
        vi = b2 * vi + (1 - b2) * gi * gi
        delta = (mi / (1 - b1 ** t)) / (jnp.sqrt(vi / (1 - b2 ** t)) + eps)
        pf = p.astype(jnp.float32)
        return (pf - lr * (delta + wd * pf)).astype(p.dtype), mi, vi

    out = jax.tree.map(upd, w, m, v, g)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


@jax.jit
def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.linalg.norm(
        x.astype(jnp.float32).ravel()), tree)


@jax.jit
def leaf_diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.linalg.norm(
        (x.astype(jnp.float32) - y.astype(jnp.float32)).ravel()), a, b)


def flat_floats(tree) -> Dict[str, float]:
    """{"blocks/sl0/core/wq": value, ...} of a tree of scalars."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(p, "key", p)) for p in path): float(x)
            for path, x in leaves}


def train_steps(conf, opt: Dict[str, Any], batches: List[Tuple], key,
                dtype=jnp.float32):
    """Run ``len(batches)`` optimizer steps from the seeded weights.

    Returns (losses, per-leaf norms of the first clipped gradient,
    per-leaf norms of the weights' change over all the steps)."""
    w0 = make_weights(conf, key, storage(dtype))
    w = w0
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
        w_new, m, v = _adamw(w, m, v, g, scal, hyper)
        del g
        if w is not w0:
            del w
        w = w_new
    change = flat_floats(leaf_diff_norms(w, w0))
    return losses, first_grad, change
