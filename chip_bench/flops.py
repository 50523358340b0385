"""Operations and bytes that the algorithm needs, from shapes alone.

Model FLOPs count two per multiply-add of every matrix product the
model needs (the q/k/v/o projections, the MLP, attention's scores and
its weighted sum over each live context, and the vocabulary projection
where a token's logits are used). Elementwise work, norms, softmax and
rotary embeddings are not counted. Nothing padded, re-fetched or
recomputed is counted, so the numbers are the same whatever implements
the work.

Kernel bytes count what the kernel has to read or write once: the
queries, the keys and values of each live context, once per layer, and
the output. ``cache_bytes`` is the size of one stored key or value
element (4 for float32 pages).
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple


def layer_matmul_params(n: Dict[str, int]) -> int:
    """Weights of one layer's projections and MLP (biases and norms are
    not matrix products)."""
    d, h, kv, hd, f = n["D"], n["H"], n["KV"], n["hd"], n["F"]
    return d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f


def attention_flops(n: Dict[str, int], keys_sum: int) -> int:
    """Scores and weighted sum for query rows whose live contexts hold
    ``keys_sum`` keys in all, over every layer."""
    return 2 * 2 * n["H"] * n["hd"] * keys_sum * n["L"]


def forward_token_flops(n: Dict[str, int], context: int,
                        logits: bool) -> int:
    """One token's forward pass at a position with ``context`` live keys
    (itself included)."""
    f = 2 * layer_matmul_params(n) * n["L"]
    f += attention_flops(n, context)
    if logits:
        f += 2 * n["D"] * n["V"]
    return f


def span_keys_sum(start: int, length: int) -> int:
    """Keys summed over queries at positions start..start+length-1 under
    causal attention: query i sees start + i + 1 keys."""
    return length * start + length * (length + 1) // 2


def serve_request_flops(n: Dict[str, int], prompt: int, cached: int,
                        generated: int) -> Tuple[int, int]:
    """(prefill FLOPs, decode FLOPs) one served request needs.

    Prefill runs the prompt positions not served from the prefix cache,
    with logits for the last prompt position only (they give the first
    token). Decode runs one step per further token: generated token j
    (j >= 2) comes from the step at position prompt + j - 2, which sees
    prompt + j - 1 keys and needs its logits."""
    p_tok = prompt - cached
    pre = 2 * layer_matmul_params(n) * n["L"] * p_tok
    pre += attention_flops(n, span_keys_sum(cached, p_tok))
    pre += 2 * n["D"] * n["V"]
    steps = max(generated - 1, 0)
    dec = (2 * layer_matmul_params(n) * n["L"] + 2 * n["D"] * n["V"]) * steps
    dec += attention_flops(n, span_keys_sum(prompt, steps))
    return pre, dec


def train_token_flops(n: Dict[str, int], seq: int) -> float:
    """Forward and backward FLOPs per trained token (three times the
    forward), for sequences of ``seq`` tokens with causal attention and
    logits at every position. Rematerialisation is not counted."""
    per_seq = (2 * layer_matmul_params(n) * n["L"] * seq
               + attention_flops(n, span_keys_sum(0, seq))
               + 2 * n["D"] * n["V"] * seq)
    return 3 * per_seq / seq


def flash_train_work(n: Dict[str, int], seq: int, sequences: int,
                     act_bytes: int = 4) -> Tuple[int, int]:
    """(FLOPs, bytes) of the training flash-attention kernels (forward
    and backward) over ``sequences`` causal sequences of ``seq`` tokens,
    over every layer. FLOPs: three times the forward's scores and
    weighted sum; the forwards that rematerialisation runs again are not
    counted. Bytes: q, o and their gradients at the query heads, k, v and
    their gradients at the KV heads, each read or written once."""
    flops = 3 * attention_flops(n, span_keys_sum(0, seq)) * sequences
    per_layer = 4 * (n["H"] + n["KV"]) * n["hd"] * seq * act_bytes
    return flops, per_layer * n["L"] * sequences


# -- the paged attention kernels ---------------------------------------------


def paged_decode_work(n: Dict[str, int], contexts: Iterable[int],
                      cache_bytes: int, act_bytes: int = 4
                      ) -> Tuple[int, int]:
    """(FLOPs, bytes) of the paged decode kernel over single-query steps
    whose live contexts are ``contexts`` keys each, over every layer."""
    flops = byts = 0
    kvb = 2 * n["KV"] * n["hd"] * cache_bytes  # one position's k and v
    qob = 2 * n["H"] * n["hd"] * act_bytes  # the query row and its output
    for c in contexts:
        flops += 2 * 2 * n["H"] * n["hd"] * c
        byts += c * kvb + qob
    return flops * n["L"], byts * n["L"]


def paged_span_work(n: Dict[str, int], spans: Iterable[Tuple[int, int]],
                    cache_bytes: int, act_bytes: int = 4
                    ) -> Tuple[int, int]:
    """(FLOPs, bytes) of the paged span kernel over prefill chunks given
    as (start, length): the chunk's queries see start + i + 1 keys; the
    keys and values of start + length positions are read once."""
    flops = byts = 0
    kvb = 2 * n["KV"] * n["hd"] * cache_bytes
    for start, length in spans:
        flops += 2 * 2 * n["H"] * n["hd"] * span_keys_sum(start, length)
        byts += ((start + length) * kvb
                 + 2 * length * n["H"] * n["hd"] * act_bytes)
    return flops * n["L"], byts * n["L"]


def prefill_spans(prompt: int, cached: int, chunk: int):
    """The (start, length) chunks a prompt's uncached part is prefilled
    in, ``chunk`` tokens at a time."""
    out, i = [], cached
    while i < prompt:
        t = min(chunk, prompt - i)
        out.append((i, t))
        i += t
    return out


def decode_contexts(prompt: int, generated: int):
    """Live contexts of the decode steps a request needs (see
    ``serve_request_flops``)."""
    return range(prompt + 1, prompt + generated)
