"""Training substrate: determinism, microbatch equivalence, optimizer
behavior, checkpoint roundtrip/corruption/async, failure recovery."""

import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import get_smoke
from repro.core.hlo_analysis import analyze_compiled_text
from repro.core.sdc import FBIST, FaultModel, faulty_wrap
from repro.data.pipeline import DataConfig, DataPipeline
from repro.launch.train import build_trainer
from repro.models import api
from repro.models.blocks import ModelContext
from repro.optim.optimizers import adafactor, adamw, clip_by_global_norm, \
    cosine_schedule
from repro.train.step import TrainSettings, init_train_state, \
    make_train_step

CTX = ModelContext(compute_dtype=jnp.float32, q_chunk=64, mamba_chunk=8,
                   rwkv_chunk=4)


def small_setup(arch="qwen2_0_5b", micro=1):
    cfg = get_smoke(arch)
    opt = adamw(cosine_schedule(1e-3, 10, 1000))
    step = jax.jit(make_train_step(cfg, CTX, opt,
                                   TrainSettings(microbatches=micro)))
    state = init_train_state(jax.random.key(0), cfg, opt)
    pipe = DataPipeline(DataConfig(global_batch=4, seq_len=32,
                                   vocab_size=cfg.vocab_size), cfg)
    return cfg, step, state, pipe


def to_jax(batch):
    return {k: jnp.asarray(v) for k, v in batch.items()}


def test_loss_decreases():
    cfg, step, state, pipe = small_setup()
    losses = []
    for i in range(20):
        state, m = step(state, to_jax(pipe.batch_for_step(i)))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.1, losses[:3] + losses[-3:]
    assert all(np.isfinite(losses))


def test_determinism_bitwise():
    """Same seed => bit-identical loss trajectory (paper's strict
    deterministic repeatability)."""
    traces = []
    for _ in range(2):
        cfg, step, state, pipe = small_setup()
        tr = []
        for i in range(5):
            state, m = step(state, to_jax(pipe.batch_for_step(i)))
            tr.append(float(m["loss"]))
        traces.append(tr)
    assert traces[0] == traces[1]


def test_microbatch_equivalence():
    """mb=1 and mb=4 give (nearly) the same gradient step."""
    _, step1, state1, pipe = small_setup(micro=1)
    _, step4, state4, _ = small_setup(micro=4)
    batch = to_jax(pipe.batch_for_step(0))
    s1, m1 = step1(state1, batch)
    s4, m4 = step4(state4, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=1e-5)
    l1 = jax.tree.leaves(s1["params"])
    l4 = jax.tree.leaves(s4["params"])
    for a, b in zip(l1, l4):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def _dot_flops(fn, *args):
    """Executed dot FLOPs of ``fn`` compiled for ``args`` (loop trips
    counted)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return analyze_compiled_text(text, (1,), ("data",)).flops


@pytest.mark.parametrize("arch",
                         ["qwen2_0_5b", "kimi_k2_1t_a32b", "whisper_small"])
def test_microbatches_keep_layer_inputs(arch):
    """Two microbatches give the one-microbatch step's loss and updated
    parameters, and run each layer's forward no more often: beside the
    one-microbatch step they add the head's recompute, well under half a
    forward pass, where rerunning each microbatch's forward in its
    backward adds a whole one."""
    cfg = get_smoke(arch)
    opt = adamw(cosine_schedule(1e-3, 10, 1000))
    state = init_train_state(jax.random.key(0), cfg, opt)
    pipe = DataPipeline(DataConfig(global_batch=4, seq_len=32,
                                   vocab_size=cfg.vocab_size), cfg)
    batch = to_jax(pipe.batch_for_step(0))
    out = {}
    for n in (1, 2):
        step = make_train_step(cfg, CTX, opt, TrainSettings(microbatches=n))
        new, m = jax.jit(step)(state, batch)
        out[n] = (_dot_flops(step, state, batch), float(m["loss"]),
                  jax.tree.leaves(new["params"]))
    forward = _dot_flops(lambda p, b: api.loss_fn(p, b, cfg, CTX)[0],
                         state["params"], batch)
    (f1, loss1, params1), (f2, loss2, params2) = out[1], out[2]
    np.testing.assert_allclose(loss2, loss1, rtol=1e-5)
    for a, b in zip(params1, params2):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-3, atol=2e-4)
    assert f2 - f1 < 0.5 * forward, (f1, f2, forward)


def test_grad_clip():
    grads = {"a": jnp.full((4,), 100.0), "b": jnp.full((3,), -100.0)}
    clipped, gn = clip_by_global_norm(grads, 1.0)
    total = sum(float(jnp.sum(g**2)) for g in jax.tree.leaves(clipped))
    assert total == pytest.approx(1.0, rel=1e-4)
    assert float(gn) == pytest.approx(np.sqrt(7 * 100.0**2), rel=1e-5)


def test_adafactor_factored_state_is_small():
    opt = adafactor(cosine_schedule(1e-3, 10, 1000))
    params = {"w": jnp.zeros((256, 512)), "b": jnp.zeros((8,))}
    state = opt.init(params)
    assert set(state["w"]) == {"vr", "vc"}
    assert state["w"]["vr"].shape == (256,)
    assert state["w"]["vc"].shape == (512,)
    assert set(state["b"]) == {"v"}
    # a step moves params
    grads = {"w": jnp.ones((256, 512)), "b": jnp.ones((8,))}
    new_p, _ = opt.update(grads, state, params, jnp.asarray(5, jnp.int32))
    assert float(jnp.max(jnp.abs(new_p["w"]))) > 0


# ------------------------------------------------------------ checkpoint


def test_checkpoint_roundtrip_and_gc():
    tmp = tempfile.mkdtemp()
    try:
        mgr = CheckpointManager(tmp, keep=2)
        state = {"params": {"w": jnp.arange(12.0).reshape(3, 4)},
                 "step": jnp.asarray(7)}
        for s in (1, 2, 3):
            mgr.save(s, state, blocking=True)
        assert mgr.all_steps() == [2, 3]  # gc kept 2
        out = mgr.restore(3, state)
        np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                      np.asarray(state["params"]["w"]))
    finally:
        shutil.rmtree(tmp)


def test_checkpoint_detects_corruption():
    tmp = tempfile.mkdtemp()
    try:
        mgr = CheckpointManager(tmp)
        state = {"w": jnp.ones((8, 8))}
        mgr.save(1, state, blocking=True)
        # corrupt the leaf file
        leaf = os.path.join(tmp, "step_00000001", "w.npy")
        arr = np.load(leaf)
        arr[0, 0] = 999.0
        np.save(leaf, arr)
        with pytest.raises(IOError, match="checksum"):
            mgr.restore(1, state)
    finally:
        shutil.rmtree(tmp)


def test_checkpoint_corruption_falls_back_to_previous_step():
    """A corrupt latest checkpoint (truncated leaf file) must not kill
    the restore when an older complete checkpoint exists: the corrupt
    step is quarantined (renamed ``.corrupt``, invisible to all_steps)
    and the previous manifest restored, with ``last_restored_step``
    re-anchoring the caller's replay range."""
    tmp = tempfile.mkdtemp()
    try:
        mgr = CheckpointManager(tmp, keep=3)
        for s in (1, 2):
            mgr.save(s, {"w": jnp.full((4, 4), float(s))}, blocking=True)
        leaf = os.path.join(tmp, "step_00000002", "w.npy")
        with open(leaf, "r+b") as fh:  # truncate mid-payload
            fh.truncate(os.path.getsize(leaf) // 2)
        out = mgr.restore(2, {"w": jnp.zeros((4, 4))})
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.full((4, 4), 1.0))
        assert mgr.last_restored_step == 1
        assert mgr.all_steps() == [1]
        assert os.path.isdir(os.path.join(tmp, "step_00000002.corrupt"))
        assert not os.path.isdir(os.path.join(tmp, "step_00000002"))
    finally:
        shutil.rmtree(tmp)


def test_checkpoint_async_and_shape_mismatch():
    tmp = tempfile.mkdtemp()
    try:
        mgr = CheckpointManager(tmp)
        state = {"w": jnp.ones((4, 4))}
        mgr.save(5, state)  # async
        mgr.wait()
        assert mgr.latest_step() == 5
        with pytest.raises(ValueError, match="shape"):
            mgr.restore(5, {"w": jnp.ones((2, 2))})
    finally:
        shutil.rmtree(tmp)


def test_checkpoint_spans_and_bytes(tmp_path):
    """A traced save is gather, write, crc and commit on the ``ckpt``
    row, nested in order; an async save's file work runs on the writer
    lane; ``train_ckpt_bytes`` counts the bytes gathered; the manifest's
    checksums still restore."""
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.trace import SpanTracer, validate_chrome_trace
    tracer, reg = SpanTracer(), MetricsRegistry()
    mgr = CheckpointManager(str(tmp_path), tracer=tracer, metrics=reg)
    state = {"w": jnp.arange(24.0).reshape(4, 6), "b": jnp.ones((3,))}
    mgr.save(1, state, blocking=True)
    mgr.save(2, state)  # async
    mgr.wait()
    pid = tracer.process("ckpt")
    begun = [(e["name"], e["tid"]) for e in tracer.events
             if e["ph"] == "B" and e["pid"] == pid]
    assert begun == [("gather", 0), ("write", 0), ("crc", 0),
                     ("commit", 0), ("gather", 0), ("write", 1),
                     ("crc", 1), ("commit", 1)]
    assert validate_chrome_trace(tracer.chrome_trace()) == []
    assert reg.counter("train_ckpt_bytes").value == 2 * (24 + 3) * 4
    out = mgr.restore(2, state)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(state["w"]))


# -------------------------------------------------- failure recovery


def test_failure_recovery_matches_uninterrupted_run():
    """A run with an injected failure + restore must reproduce the exact
    loss trajectory of an uninterrupted run (determinism + checkpointing
    + replay = the paper's resilience contract)."""
    tmp1, tmp2 = tempfile.mkdtemp(), tempfile.mkdtemp()
    try:
        cfg = get_smoke("internlm2_1_8b")
        tr1, st1 = build_trainer(cfg, batch=4, seq=32, ckpt_dir=tmp1,
                                 checkpoint_every=5)
        _, led1, losses1 = tr1.run(st1, 14)
        tr2, st2 = build_trainer(cfg, batch=4, seq=32, ckpt_dir=tmp2,
                                 checkpoint_every=5, failures={9: 3})
        _, led2, losses2 = tr2.run(st2, 14)
        assert losses1 == losses2
        assert led2.totals().get("rework", 0) > 0
        assert led2.goodput < 1.0
        assert led1.goodput > led2.goodput
    finally:
        shutil.rmtree(tmp1)
        shutil.rmtree(tmp2)


def test_fbist_catches_marginal_device_in_train_path():
    fb = FBIST(m=64, k=64, n=64, n_patterns=5)
    assert fb.run(lambda a, b: a @ b).passed
    bad = faulty_wrap(lambda a, b: a @ b,
                      FaultModel(rate=1.0, magnitude=0.5, seed=1))
    assert not fb.run(bad).passed


# --------------------------------------------------------------- data


def test_pipeline_deterministic_and_step_indexed():
    cfg = DataConfig(global_batch=4, seq_len=16, vocab_size=101, seed=3)
    p1, p2 = DataPipeline(cfg), DataPipeline(cfg)
    b1, b2 = p1.batch_for_step(42), p2.batch_for_step(42)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = p1.batch_for_step(43)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # labels are next-token shifted
    full1 = p1.batch_for_step(7)
    assert full1["tokens"].shape == (4, 16)
    assert (full1["tokens"] < 101).all()


def test_token_file_source():
    tmp = tempfile.mkdtemp()
    try:
        path = os.path.join(tmp, "tokens.bin")
        np.arange(4 * 17 * 3, dtype=np.int32).tofile(path)
        cfg = DataConfig(global_batch=4, seq_len=16, vocab_size=1 << 30,
                         token_file=path)
        pipe = DataPipeline(cfg)
        b = pipe.batch_for_step(0)
        assert b["tokens"].shape == (4, 16)
        b2 = pipe.batch_for_step(0)
        np.testing.assert_array_equal(b["tokens"], b2["tokens"])
    finally:
        shutil.rmtree(tmp)
