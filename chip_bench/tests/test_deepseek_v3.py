"""The DeepSeek-V3 reference agrees with the program's model code on seeded
weights at smoke width (with q LoRA off and on), counts its training work
from the program's expert-row counter, and the cell's three readers read
a stand-in reading.

Both compute in float32 at "highest" matmul precision on the CPU, so
they differ only in the order of accumulation; the tolerances are those
of ``test_reference.py`` for qwen2, for the same reasons. The smoke
configuration holds 4 of 8 router experts from expert 2, so the share's
partial layer is what both compare."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_bench import common
from chip_bench import trace as tr
from chip_bench.reference import deepseek_v3 as ref
from chip_bench.tests.helpers import ROOT, load

SEED = 2 ** 31 + 11


@pytest.fixture(scope="module", params=[None, 24], ids=["q", "q_lora"])
def setup(request):
    from chip_bench import program
    conf = dict(load("deepseek-smoke.json"), q_lora_rank=request.param)
    w = ref.make_weights(conf, common.seed_key(SEED))
    return conf, program.model_config(conf), w


def test_layout_matches_the_program(setup):
    from repro.models import api
    conf, cfg, w = setup
    spec = jax.tree.map(lambda s: s.shape, api.model_specs(cfg),
                        is_leaf=lambda s: hasattr(s, "logical"))
    assert jax.tree.map(lambda x: x.shape, w) == spec
    assert cfg.mla and cfg.n_held == 4 and cfg.n_experts == 8


def test_loss_and_gradient_match_the_program(setup):
    from repro.models import api
    from repro.models.blocks import ModelContext
    conf, cfg, w = setup
    rng = np.random.default_rng(1)
    toks = rng.integers(0, conf["vocab_size"], (2, 65)).astype(np.int32)
    tokens, labels = toks[:, :-1], toks[:, 1:]
    ctx = ModelContext(compute_dtype=jnp.float32, q_chunk=2048)
    with jax.default_matmul_precision("highest"):
        (loss, metrics), grad = jax.value_and_grad(
            api.loss_fn, has_aux=True)(
            w, {"tokens": jnp.asarray(tokens),
                "labels": jnp.asarray(labels)}, cfg, ctx)
    rloss, rgrad = ref.batch_value_and_grad(w, conf, tokens, labels)
    assert rloss == pytest.approx(float(loss), rel=2e-5)
    assert float(metrics["seq_balance"]) > 0
    got = ref.flat_floats(ref.leaf_norms(grad))
    want = ref.flat_floats(ref.leaf_norms(rgrad))
    assert want.pop("blocks/sl0/mlp/router_bias") == 0.0  # no gradient
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-4, abs=1e-9), k
    for a, b in zip(jax.tree.leaves(grad), jax.tree.leaves(rgrad)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, atol=1e-3 * max(np.abs(b).max(),
                                                         1e-12))


def test_train_flops_reads_the_held_rows():
    """The routed experts' work comes from ``moe_held_rows``; the rest
    from the shapes: at the cell's configuration and 8k context, with
    the rows random routing sends to 8 of 64 experts, 878 MFLOP a token
    forward (the sizing of the cell)."""
    conf = json.loads((ROOT / "chip_bench" / "configs"
                              / "moonlight-16b-a3b-l6.json").read_text())
    n = ref.dims(conf)
    seq, tokens = 8192, 2 * 8192
    bare = ref.train_flops(conf, seq, tokens, {})
    rows = 1000.0
    assert ref.train_flops(conf, seq, tokens, {"moe_held_rows": rows}) \
        - bare == pytest.approx(3 * 2 * 3 * n["D"] * n["Fe"] * rows)
    expected_rows = tokens * n["K"] * n["Eh"] / n["E"] * n["Lm"]
    fwd = ref.train_flops(conf, seq, tokens,
                          {"moe_held_rows": expected_rows}) / 3 / tokens
    assert fwd == pytest.approx(878e6, rel=5e-3)


def _reading(conf, ops, counters):
    summary = tr.Summary(window_s=2.0, busy_s=1.9, programs={}, ops=ops,
                         gaps=[], spans={})
    return {"trace": summary, "peaks": {"bf16_flops": 197e12,
                                        "hbm_bytes_per_s": 819e9},
            "chips": 1, "conf": conf, "dims": ref.dims(conf), "seq": 8192,
            "steps": 4, "sequences": 8, "counters_window": counters}


def test_the_cell_readers_on_a_stand_in_reading():
    conf = json.loads((ROOT / "chip_bench" / "configs"
                              / "moonlight-16b-a3b-l6.json").read_text())
    bench = {"per_layer": [{"name": n, "unit": "%"} for n in (
        "mla_flash_roofline", "moe_gemm_roofline.train", "moe_pad_share")]}
    readers = {k: read for k, (read, _) in
               common.per_layer_readers(bench, "x").items()}
    ops = {"%flash_fwd.1 = f32[16,8192,128] custom-call()": 0.6,
           "%flash_bwd_dq.2 = f32[16,8192,192] custom-call()": 0.3,
           "%flash_bwd_dkv.3 = (f32[]) custom-call()": 0.3,
           "%gmm.4 = f32[8,8] custom-call()": 0.5,
           "%gmm_dw.5 = f32[8,8] custom-call()": 0.25}
    rows = 4 * 5 * 2 * 6144.0
    r = _reading(conf, ops, {"moe_held_rows": rows,
                             "moe_padded_rows": rows / 0.8})
    n = ref.dims(conf)
    attn = 3 * 2 * 16 * (192 + 128) * (8192 * 8193 // 2) * 6 * 8
    assert readers["mla_flash_roofline"](r) == pytest.approx(
        100 * attn / 197e12 / 1.2)
    gemm = 9 * 2 * n["D"] * n["Fe"] * rows
    assert readers["moe_gemm_roofline.train"](r) == pytest.approx(
        100 * gemm / 197e12 / 0.75)
    assert readers["moe_pad_share"](r) == pytest.approx(20.0)
    for v in (readers["mla_flash_roofline"](r),
              readers["moe_gemm_roofline.train"](r)):
        assert 0 < v <= 100
    # a program without these kernels or counters gives nothing to read
    empty = _reading(conf, {}, {})
    assert all(read(empty) is None for read in readers.values())
