"""The plain reference agrees with the program's model code
(``repro.models``) on seeded weights at smoke width.

Both compute in float32 at "highest" matmul precision on the CPU, so
they differ only in the order of accumulation: about 1e-7 relative per
operation, a few times that through two layers. The tolerances (2e-5
relative on logits and the loss, 1e-4 on each gradient leaf's norm
and 1e-3 on each gradient element against that leaf's largest) are
about a hundred times that and far below any error of the equations
(a missing bias, a rotary half swapped, a head mapped to the wrong KV
group each move the result by more than 1e-2 here)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_bench import common
from chip_bench.reference import qwen2
from chip_bench.tests.helpers import load

SEED = 2 ** 31 + 7


@pytest.fixture(scope="module")
def setup():
    from chip_bench import program
    conf = load("qwen2-smoke.json")
    cfg = program.model_config(conf)
    w = qwen2.make_weights(conf, common.seed_key(SEED))
    return conf, cfg, w


def test_layout_matches_the_program(setup):
    from repro.models import api
    conf, cfg, w = setup
    spec = jax.tree.map(lambda s: s.shape, api.model_specs(cfg),
                        is_leaf=lambda s: hasattr(s, "logical"))
    assert jax.tree.map(lambda x: x.shape, w) == spec


@pytest.mark.parametrize("tied", [True, False])
def test_logits_match_the_program(setup, tied):
    from chip_bench import program
    from repro.models import lm
    from repro.models.blocks import ModelContext
    conf, _, _ = setup
    conf = dict(conf, tie_word_embeddings=tied)
    cfg = program.model_config(conf)
    w = qwen2.make_weights(conf, common.seed_key(SEED))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, conf["vocab_size"], 40).astype(np.int32)
    rows = np.array([0, 7, 21, 39])
    ctx = ModelContext(compute_dtype=jnp.float32, q_chunk=1024)
    with jax.default_matmul_precision("highest"):
        got, _ = lm.lm_prefill(w, jnp.asarray(np.tile(toks, (4, 1))), cfg,
                               ctx, window=40,
                               logits_at=jnp.asarray(rows, jnp.int32))
    want = qwen2.served_logits(w, conf, toks, rows, 64, 4)
    np.testing.assert_allclose(np.asarray(got[:, 0]), want, rtol=2e-5,
                               atol=2e-5 * np.abs(want).max())


def test_loss_and_gradient_match_the_program(setup):
    from repro.models import api
    from repro.models.blocks import ModelContext
    conf, cfg, w = setup
    rng = np.random.default_rng(1)
    toks = rng.integers(0, conf["vocab_size"], (2, 33)).astype(np.int32)
    tokens, labels = toks[:, :-1], toks[:, 1:]
    ctx = ModelContext(compute_dtype=jnp.float32, q_chunk=2048)
    with jax.default_matmul_precision("highest"):
        (loss, _), grad = jax.value_and_grad(api.loss_fn, has_aux=True)(
            w, {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)},
            cfg, ctx)
    rloss, rgrad = qwen2.batch_value_and_grad(w, conf, tokens, labels)
    assert rloss == pytest.approx(float(loss), rel=2e-5)
    got = qwen2.flat_floats(qwen2.leaf_norms(grad))
    want = qwen2.flat_floats(qwen2.leaf_norms(rgrad))
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-4, abs=1e-9), k
    for a, b in zip(jax.tree.leaves(grad), jax.tree.leaves(rgrad)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, atol=1e-3 * max(np.abs(b).max(),
                                                         1e-12))


def test_adamw_matches_the_program():
    from repro.launch.cells import make_optimizer
    opt = load("train-smoke.json")["optimizer"]
    prog = make_optimizer("adamw", total_steps=opt["total_steps"])
    for step in (0, 1, 150, 5000):
        assert qwen2.lr_at(step, opt) == pytest.approx(
            float(_program_lr(step, opt)), rel=1e-6)
    p = {"a": jnp.linspace(-1.0, 1.0, 8)}
    g = {"a": jnp.linspace(0.5, -0.3, 8)}
    m = {"a": jnp.zeros(8)}
    v = {"a": jnp.zeros(8)}
    state = prog.init(p)
    for step in range(3):
        new_p, state = prog.update(g, state, p, jnp.int32(step))
        scal = jnp.asarray([qwen2.lr_at(step, opt), step + 1.0, 1.0],
                           jnp.float32)
        ref_p, m, v = qwen2._adamw(p, m, v, g, scal, (
            opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]))
        np.testing.assert_allclose(np.asarray(new_p["a"]),
                                   np.asarray(ref_p["a"]), rtol=1e-6)
        p = new_p


def _program_lr(step, opt):
    from repro.optim.optimizers import cosine_schedule
    sched = cosine_schedule(opt["peak_lr"], opt["warmup_steps"],
                            opt["total_steps"], opt["final_fraction"])
    return sched(jnp.asarray(step))
