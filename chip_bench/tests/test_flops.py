"""FLOP and byte counts against hand counts at smoke sizes, and the
peaks table."""

from __future__ import annotations

import json
import math

import pytest

from chip_bench import flops, peaks
from chip_bench.reference import qwen2
from chip_bench.tests.helpers import ROOT, load

N = {"L": 2, "D": 64, "H": 4, "KV": 2, "hd": 16, "F": 128, "V": 8192}


def test_dims_of_the_smoke_config():
    assert qwen2.dims(load("qwen2-smoke.json")) == N


def test_layer_params_match_the_weight_shapes():
    # q and o: 64 x 4 x 16 each; k and v: 64 x 2 x 16 each; the MLP's
    # three 64 x 128 matrices
    assert flops.layer_matmul_params(N) == 2 * 4096 + 2 * 2048 + 3 * 8192
    shapes = qwen2.weight_shapes(load("qwen2-smoke.json"))["blocks"]["sl0"]
    mats = [shapes["core"][k] for k in ("wq", "wk", "wv", "wo")] + \
        list(shapes["mlp"].values())
    assert sum(math.prod(s[1:]) for s in mats) == \
        flops.layer_matmul_params(N)


def test_forward_token_by_hand():
    # 2 layers x 2 x 36864, attention 2 x 2 x 4 x 16 x 10 keys x 2
    # layers, head 2 x 64 x 8192
    want = 2 * 36864 * 2 + 4 * 4 * 16 * 10 * 2 + 2 * 64 * 8192
    assert flops.forward_token_flops(N, 10, logits=True) == want
    assert flops.forward_token_flops(N, 10, logits=False) == \
        want - 2 * 64 * 8192


def test_serve_request_is_the_sum_of_its_tokens():
    p, g = 37, 9
    pre, dec = flops.serve_request_flops(N, p, 0, g)
    want_pre = sum(flops.forward_token_flops(N, i + 1, logits=i == p - 1)
                   for i in range(p))
    want_dec = sum(flops.forward_token_flops(N, c, logits=True)
                   for c in flops.decode_contexts(p, g))
    assert (pre, dec) == (want_pre, want_dec)
    # a prefix-cache hit of 16 tokens leaves 21 positions to prefill
    pre16, _ = flops.serve_request_flops(N, p, 16, g)
    assert pre16 == sum(flops.forward_token_flops(N, i + 1,
                                                  logits=i == p - 1)
                        for i in range(16, p))


def test_decode_contexts_follow_the_engine():
    # token 1 comes from prefill; token j >= 2 from the step at position
    # p + j - 2, which sees p + j - 1 keys
    assert list(flops.decode_contexts(5, 4)) == [6, 7, 8]
    assert list(flops.decode_contexts(5, 1)) == []


def test_paged_decode_work_by_hand():
    f, b = flops.paged_decode_work(N, [5, 7], cache_bytes=4)
    assert f == 2 * 2 * 4 * 16 * (5 + 7) * 2
    assert b == ((5 + 7) * 2 * 2 * 16 * 4 + 2 * (2 * 4 * 16 * 4)) * 2


def test_paged_span_work_by_hand():
    # a 16-token chunk behind 16 cached positions: query i sees 17 + i
    # keys; 32 positions of k and v are read once
    f, b = flops.paged_span_work(N, [(16, 16)], cache_bytes=4)
    assert f == 2 * 2 * 4 * 16 * sum(17 + i for i in range(16)) * 2
    assert b == (32 * 2 * 2 * 16 * 4 + 2 * 16 * 4 * 16 * 4) * 2
    assert flops.prefill_spans(40, 0, 16) == [(0, 16), (16, 16), (32, 8)]
    assert flops.prefill_spans(40, 32, 16) == [(32, 8)]


def test_train_token_is_three_forwards():
    seq = 32
    fwd = sum(flops.forward_token_flops(N, i + 1, logits=True)
              for i in range(seq))
    assert flops.train_token_flops(N, seq) == pytest.approx(3 * fwd / seq)


def test_qwen2_train_flops_is_the_dense_formula():
    # the cell's shape: 4 traced steps of 2 x 4096 tokens
    conf = json.loads((ROOT / "chip_bench" / "configs"
                       / "qwen2-0.5b.json").read_text())
    seq, tokens = 4096, 4 * 2 * 4096
    got = qwen2.train_flops(conf, seq, tokens, {"train_steps": 7.0})
    want = flops.train_token_flops(qwen2.dims(conf), seq) * tokens
    assert got.hex() == want.hex()


def test_flash_train_work_by_hand():
    # 3 sequences of 8: causal keys 1 + ... + 8 = 36 a sequence; the
    # forward's scores and weighted sum 2 x 2 x 4 heads x 16 x 36 a
    # layer, three times over; q, o, dq, do at 4 heads and k, v, dk, dv
    # at 2, 8 rows of 16 each, float32
    f, b = flops.flash_train_work(N, 8, 3)
    assert f == 3 * (2 * 2 * 4 * 16 * 36) * 2 * 3
    assert b == (4 * 4 + 4 * 2) * 8 * 16 * 4 * 2 * 3
    assert flops.flash_train_work(N, 8, 3, act_bytes=2)[1] == b // 2


def test_peaks_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["source"]
    with pytest.raises(KeyError, match="known"):
        peaks.peaks_for("cpu")
    t, bound = peaks.least_time_s(197e12, 1.0, v5e)
    assert (t, bound) == (1.0, "compute")
    t, bound = peaks.least_time_s(1.0, 819e9, v5e)
    assert (t, bound) == (1.0, "bytes")
