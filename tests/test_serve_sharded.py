"""Sharded serving: mesh-parallel paged decode parity, GQA KV-replication
fallback, prefill/decode disaggregation, mrope through the span paths,
and the dropped-rule report.

Mesh tests run in the ``subproc`` fixture (jax locks the device count at
first init, so anything needing > 1 device gets a fresh process with
forced fake host devices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.models.blocks import ModelContext
from repro.models.params import init_params
from repro.serve.engine import PageTransferModel, ServeEngine
from repro.serve.scheduler import (ContinuousBatchingScheduler,
                                   PrefillWorkerPool, Request)
from repro.sharding.axes import RULE_SETS, summarize_dropped

jax.config.update("jax_default_matmul_precision", "highest")

CTX = ModelContext(compute_dtype=jnp.float32, q_chunk=64, mamba_chunk=8,
                   rwkv_chunk=4)


# --------------------------------------------------- mesh parity (subproc)


def test_sharded_decode_matches_single_host(subproc):
    """(4, 2) mesh (true tensor parallelism: kv=2 divides model=2) must be
    token-identical to the single-host engine — f32, bf16 and int8 pools,
    with speculation on and a second run decoding off prefix-cache
    hits."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_default_matmul_precision", "highest")
from repro.configs.registry import get_smoke
from repro.models import api
from repro.models.blocks import ModelContext
from repro.models.params import init_params
from repro.launch.mesh import make_mesh
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import Request

cfg = get_smoke("qwen2_0_5b")
params = init_params(jax.random.key(0), api.model_specs(cfg))
rng = np.random.default_rng(1)
ps = [rng.integers(0, cfg.vocab_size, int(rng.integers(8, 15)))
      for _ in range(4)]
reqs = lambda: [Request(rid=i, prompt=p, max_new=8)
                for i, p in enumerate(ps)]
mesh = make_mesh((4, 2), ("data", "model"))
for cdt in (None, jnp.bfloat16, jnp.int8):
    ctx = ModelContext(compute_dtype=jnp.float32, q_chunk=64,
                       decode_cache_dtype=cdt)
    solo = ServeEngine(cfg, ctx, window=48, max_batch=2, chunk=4,
                       page_size=8, draft_k=2)
    shard = ServeEngine(cfg, ctx, window=48, max_batch=2, chunk=4,
                        page_size=8, draft_k=2, mesh=mesh)
    assert shard.sharding_report["mesh"] == {"data": 4, "model": 2}
    for r in range(2):
        so, sh = solo.run(params, reqs()), shard.run(params, reqs())
        for i in range(4):
            np.testing.assert_array_equal(so[i], sh[i])
    assert shard.prefix_hit_rate > 0, "run 2 must hit the prefix cache"
print("SHARDED-PARITY-OK")
""", devices=8)
    assert "SHARDED-PARITY-OK" in out


def test_gqa_kv_fallback_sharded_parity(subproc):
    """mixtral smoke (h=8, kv=2) on a (2, 4) mesh: kv does not divide
    model=4, so the KV pool replicates (dropped rule reported) and each
    shard slices its local groups — output still token-identical, SWA
    page trimming included."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_default_matmul_precision", "highest")
from repro.configs.registry import get_smoke
from repro.models import api
from repro.models.blocks import ModelContext
from repro.models.params import init_params
from repro.launch.mesh import make_mesh
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import Request

cfg = get_smoke("mixtral_8x22b")
assert cfg.n_heads == 8 and cfg.n_kv_heads == 2
params = init_params(jax.random.key(0), api.model_specs(cfg))
ctx = ModelContext(compute_dtype=jnp.float32, q_chunk=64)
rng = np.random.default_rng(2)
ps = [rng.integers(0, cfg.vocab_size, int(rng.integers(6, 12)))
      for _ in range(3)]
reqs = lambda: [Request(rid=i, prompt=p, max_new=6)
                for i, p in enumerate(ps)]
solo = ServeEngine(cfg, ctx, window=32, max_batch=2, chunk=4, page_size=4)
mesh = make_mesh((2, 4), ("data", "model"))
shard = ServeEngine(cfg, ctx, window=32, max_batch=2, chunk=4,
                    page_size=4, mesh=mesh)
drops = " ; ".join(shard.sharding_report["dropped_rules"])
assert "kv_heads=2" in drops, drops
so, sh = solo.run(params, reqs()), shard.run(params, reqs())
for i in range(3):
    np.testing.assert_array_equal(so[i], sh[i])
print("GQA-FALLBACK-OK")
""", devices=8)
    assert "GQA-FALLBACK-OK" in out


# ------------------------------------------------------- disaggregation


@pytest.fixture(scope="module")
def qwen():
    cfg = get_smoke("qwen2_0_5b")
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    return cfg, params


def _reqs(cfg, n=4, seed=1, max_new=8, arrivals=None):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(8, 15))),
                    max_new=max_new,
                    arrival=0 if arrivals is None else arrivals[i])
            for i in range(n)]


def test_disaggregated_matches_colocated(qwen):
    """Disaggregated greedy output == co-located, on both modeled links,
    with nonzero transfer traffic and per-role queue-depth stats."""
    cfg, params = qwen
    co = ServeEngine(cfg, CTX, window=48, max_batch=2, chunk=4,
                     page_size=8)
    want = co.run(params, _reqs(cfg))
    for link in ("ici", "dcn"):
        dis = ServeEngine(cfg, CTX, window=48, max_batch=2, chunk=4,
                          page_size=8, disaggregate=True,
                          prefill_workers=2, transfer_link=link)
        got = dis.run(params, _reqs(cfg))
        for i in range(4):
            np.testing.assert_array_equal(want[i], got[i])
        ts = dis.transfer_stats()
        assert ts["link"] == link
        assert ts["transfers"] == 4
        assert ts["transfer_bytes"] > 0
        assert ts["transfer_stall_boundaries"] >= 1
        assert ts["prefill_depth_peak"] >= 1
        assert dis.prefill_pool.stats["placed"] == 4


def test_disaggregated_with_speculation_and_arrivals(qwen):
    """Parked-slot freezing composes with spec decode and staggered
    arrivals: the frozen slot's span writes are idempotent, so delayed
    activation stays token-identical."""
    cfg, params = qwen
    arrivals = [0, 3, 9, 9]
    co = ServeEngine(cfg, CTX, window=48, max_batch=2, chunk=4,
                     page_size=8, draft_k=2)
    want = co.run(params, _reqs(cfg, arrivals=arrivals))
    dis = ServeEngine(cfg, CTX, window=48, max_batch=2, chunk=4,
                      page_size=8, draft_k=2, disaggregate=True)
    got = dis.run(params, _reqs(cfg, arrivals=arrivals))
    for i in range(4):
        np.testing.assert_array_equal(want[i], got[i])


def test_disaggregate_requires_paged():
    cfg = get_smoke("rwkv6_1_6b")
    with pytest.raises(ValueError, match="disaggregation requires"):
        ServeEngine(cfg, CTX, window=32, max_batch=2, disaggregate=True)


def test_transfer_model_scales_with_link():
    """DCN pays more latency and less bandwidth than ICI for the same
    pages, so its transfers span at least as many decode boundaries."""
    mk = lambda link: PageTransferModel(page_bytes=1 << 14, chunk=8,
                                        resident_bytes=1 << 22, link=link)
    ici, dcn = mk("ici"), mk("dcn")
    assert dcn.transfer_s(4) > ici.transfer_s(4)
    assert dcn.delay_boundaries(4) >= ici.delay_boundaries(4)
    assert ici.delay_boundaries(1) >= 1
    with pytest.raises(ValueError, match="transfer link"):
        mk("rdma")


def test_prefill_worker_pool_queueing():
    """Least-loaded placement, FIFO readiness, prefill_done lifecycle
    (set by pop_ready, reset by preemption)."""
    pool = PrefillWorkerPool(2, span_len=4, chunk=4)
    rs = [Request(rid=i, prompt=np.arange(6), max_new=2) for i in range(3)]
    for r in rs:
        pool.place(r, clock=0)  # 6 tokens -> 2 spans -> 8 clock units
    assert pool.depths() == [2, 1]  # third request joins the shallower q
    assert pool.pending()
    assert pool.pop_ready(0) == []
    ready = pool.pop_ready(8)
    assert [r.rid for r in ready] == [0, 1]  # heads of both queues
    assert all(r.prefill_done for r in ready)
    assert pool.pop_ready(100) == [rs[2]]  # queued behind rid 0
    assert not pool.pending()
    sched = ContinuousBatchingScheduler(2)
    sched.add(rs[0])
    sched.admit(rs[0], 0)
    sched.preempt(rs[0])
    assert not rs[0].prefill_done, "preemption must force re-prefill"


# ------------------------------------------------------------- mrope


def _vl_positions(b, s):
    """Vision-style (3, B, S) rows: a 4-token 2x2 image patch block
    (temporal/height/width rows differ) then a text tail — laid out so
    max(positions) == s - 1 and the text continuation both backends use
    for decode agrees."""
    t = [0, 0, 0, 0]
    h = [0, 0, 1, 1]
    w = [0, 1, 0, 1]
    tail = list(range(2, 2 + s - 4))
    pos = np.stack([t + tail, h + tail, w + tail]).astype(np.int32)
    return np.broadcast_to(pos[:, None, :], (3, b, s)).copy()


def test_mrope_chunked_prefill_matches_dense(qwen):
    """qwen2_vl rides the paged chunked span prefill with its explicit
    mrope rows: tokens must match the dense full-prompt oracle across
    chunk sizes (including a chunk size that splits the image block)."""
    cfg = get_smoke("qwen2_vl_7b")
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    rng = np.random.default_rng(3)
    s = 12
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, s)), jnp.int32),
        "positions": jnp.asarray(_vl_positions(2, s))}
    oracle = ServeEngine(cfg, CTX, window=32, max_batch=2, chunk=4)
    want = oracle.generate_pertoken(params, batch, max_new=6)
    for prefill_chunk in (3, 5, 128):
        eng = ServeEngine(cfg, CTX, window=32, max_batch=2, chunk=4,
                          page_size=8, prefill_chunk=prefill_chunk)
        assert eng.paged
        got = eng.generate(params, batch, max_new=6)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert eng.counters["prefill_span_calls"] >= (
            2 * -(-s // min(prefill_chunk, 32)))


def test_mrope_requests_bypass_prefix_cache():
    """Two requests with the SAME tokens but different position rows hold
    different KV: explicit-position admissions must neither publish nor
    adopt content-addressed prefix pages."""
    cfg = get_smoke("qwen2_vl_7b")
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    rng = np.random.default_rng(4)
    s = 12
    toks = rng.integers(0, cfg.vocab_size, (1, s))
    batch = {"tokens": jnp.asarray(toks, jnp.int32),
             "positions": jnp.asarray(_vl_positions(1, s))}
    text_pos = np.broadcast_to(np.arange(s, dtype=np.int32), (3, 1, s))
    batch_text = {"tokens": jnp.asarray(toks, jnp.int32),
                  "positions": jnp.asarray(text_pos.copy())}
    eng = ServeEngine(cfg, CTX, window=32, max_batch=1, chunk=4,
                      page_size=4)
    out_vl = eng.generate(params, batch, max_new=6)
    out_text = eng.generate(params, batch_text, max_new=6)
    assert eng.kv.counters["prefix_hit_tokens"] == 0
    assert eng.kv.counters["pages_published"] == 0
    # same tokens, different geometry -> different prefill logits (the
    # aliasing the bypass prevents); greedy argmax may still coincide on
    # the smoke model, so the check is at the logits level
    l_vl, _ = api.prefill_fn(params, batch, cfg, CTX, window=32)
    l_text, _ = api.prefill_fn(params, batch_text, cfg, CTX, window=32)
    assert np.abs(np.asarray(l_vl) - np.asarray(l_text)).max() > 1e-6
    # oracle agreement for both runs
    for b, out in ((batch, out_vl), (batch_text, out_text)):
        want = eng.generate_pertoken(params, b, max_new=6)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_mrope_dense_chunked_prefill():
    """The dense span path threads mrope too: force qwen2_vl onto the
    dense backend and check chunked == full-prompt oracle."""
    cfg = get_smoke("qwen2_vl_7b")
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    rng = np.random.default_rng(5)
    s = 10
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (1, s)), jnp.int32),
        "positions": jnp.asarray(_vl_positions(1, s))}
    eng = ServeEngine(cfg, CTX, window=24, max_batch=1, chunk=4,
                      paged=False, prefill_chunk=4)
    assert eng.chunk_prefill
    want = eng.generate_pertoken(params, batch, max_new=5)
    got = eng.generate(params, batch, max_new=5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert eng.counters["prefill_span_calls"] >= 2


# ------------------------------------------------- dropped-rule reporting


def test_summarize_dropped_renders_fallbacks():
    mesh = make_mesh((1, 1), ("data", "model"))
    lines = summarize_dropped([("kv_heads", 2), ("kv_heads", 2),
                               ("vocab", 211)],
                              mesh, RULE_SETS["baseline_dp_tp"])
    assert len(lines) == 2  # deduped
    assert "kv_heads=2" in lines[0] and "replicated" in lines[0]
    assert "vocab=211" in lines[1]


def test_engine_reports_dropped_rules_once(subproc):
    """dropped_rules is populated at construction (KV pool placement) and
    extended by shard_params, without duplicate lines."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.configs.registry import get_smoke
from repro.models import api
from repro.models.blocks import ModelContext
from repro.models.params import init_params
from repro.launch.mesh import make_mesh
from repro.serve.engine import ServeEngine

cfg = get_smoke("mixtral_8x22b")
params = init_params(jax.random.key(0), api.model_specs(cfg))
ctx = ModelContext(compute_dtype=jnp.float32, q_chunk=64)
mesh = make_mesh((2, 4), ("data", "model"))
eng = ServeEngine(cfg, ctx, window=32, max_batch=2, chunk=4, page_size=4,
                  mesh=mesh)
before = list(eng.sharding_report["dropped_rules"])
assert any("kv_heads=2" in ln for ln in before), before
eng.shard_params(params)
after = eng.sharding_report["dropped_rules"]
assert len(after) == len(set(after)), "duplicate fallback lines"
assert set(before) <= set(after)
single = ServeEngine(cfg, ctx, window=32, max_batch=2, chunk=4,
                     page_size=4)
assert single.sharding_report == {"mesh": None, "rules": "baseline_dp_tp",
                                  "dropped_rules": []}
print("DROPPED-REPORT-OK")
""", devices=8)
    assert "DROPPED-REPORT-OK" in out
