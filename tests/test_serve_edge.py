"""Serving edge cases around disaggregation and telemetry: prefill-pool
construction bounds, all-slots-parked boundary accounting vs
``transfer_stats()``, the preempt-during-park lifecycle
(``prefill_done`` reset + re-prefill), and the golden SLO snapshot —
``ServeEngine.slo_summary()`` under an injected deterministic clock,
plus the pinned ``bench_serve`` arrivals-row schema."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke
from repro.models import api
from repro.models.blocks import ModelContext
from repro.models.params import init_params
from repro.obs.trace import SpanTracer
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import (ContinuousBatchingScheduler,
                                   PrefillWorkerPool, Request)

sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                       / "benchmarks"))
import bench_serve  # noqa: E402  (ARRIVALS_SLO_ROWS schema pin)

jax.config.update("jax_default_matmul_precision", "highest")

CTX = ModelContext(compute_dtype=jnp.float32, q_chunk=64, mamba_chunk=8,
                   rwkv_chunk=4)


@pytest.fixture(scope="module")
def qwen():
    cfg = get_smoke("qwen2_0_5b")
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    return cfg, params


def _reqs(cfg, n, *, seed=1, lo=8, hi=14, max_new=8):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(lo, hi + 1))),
                    max_new=max_new)
            for i in range(n)]


# ------------------------------------------------ prefill pool bounds


def test_prefill_pool_rejects_zero_workers():
    with pytest.raises(ValueError, match="n_workers must be >= 1"):
        PrefillWorkerPool(0, span_len=16, chunk=4)
    cfg = get_smoke("qwen2_0_5b")
    with pytest.raises(ValueError, match="prefill_workers must be >= 1"):
        ServeEngine(cfg, CTX, window=32, max_batch=2, chunk=4,
                    page_size=8, disaggregate=True, prefill_workers=0)


def test_prefill_pool_least_loaded_placement_and_fifo():
    pool = PrefillWorkerPool(2, span_len=8, chunk=4)
    reqs = [Request(rid=i, prompt=np.arange(12), max_new=4)
            for i in range(3)]
    # 12 tokens / span 8 => 2 spans * chunk 4 = 8 boundaries each
    assert pool.place(reqs[0], clock=0) == 8
    assert pool.place(reqs[1], clock=0) == 8   # second worker, parallel
    assert pool.place(reqs[2], clock=0) == 16  # queued behind one of them
    assert sorted(pool.depths()) == [1, 2]
    assert pool.pop_ready(7) == []
    ready = pool.pop_ready(8)
    assert {r.rid for r in ready} == {0, 1}
    assert all(r.prefill_done and r.state == "waiting" for r in ready)
    assert pool.pending()
    assert [r.rid for r in pool.pop_ready(16)] == [2]
    assert not pool.pending()


# ------------------------------------- all-slots-parked accounting


def test_all_slots_parked_stall_accounting(qwen):
    """With a single decode slot, every boundary spent waiting on a page
    transfer has ALL running slots parked, so the decode-idle count must
    equal the transfer-stall count exactly — and the run must still be
    token-identical to the co-located engine."""
    cfg, params = qwen
    co = ServeEngine(cfg, CTX, window=48, max_batch=1, chunk=4,
                     page_size=8)
    want = co.run(params, _reqs(cfg, 3))
    dis = ServeEngine(cfg, CTX, window=48, max_batch=1, chunk=4,
                      page_size=8, disaggregate=True, transfer_link="dcn")
    got = dis.run(params, _reqs(cfg, 3))
    for i in range(3):
        np.testing.assert_array_equal(want[i], got[i])
    ts = dis.transfer_stats()
    assert ts["transfers"] == 3
    assert ts["transfer_stall_boundaries"] >= 1
    assert ts["decode_idle_boundaries"] == ts["transfer_stall_boundaries"]
    # parked != running: a frozen slot never counts as decode occupancy
    assert ts["decode_depth_peak"] >= 1


# ------------------------------------------- preempt during park


def test_scheduler_preempt_resets_prefill_done():
    s = ContinuousBatchingScheduler(max_slots=2)
    req = Request(rid=0, prompt=np.arange(8), max_new=4)
    req.prefill_done = True  # as set by PrefillWorkerPool.pop_ready
    s.add(req)
    s.admit(req, slot=0)
    s.preempt(req)
    assert req.prefill_done is False  # pages dropped: must re-prefill
    assert req.state == "waiting" and req.slot == -1
    assert req.preemptions == 1
    assert req in s.waiting


def test_preempt_during_park_re_prefills_and_completes(qwen):
    """Page pressure that evicts requests in a disaggregated engine: the
    victim (possibly mid-transfer) loses its pages, is re-placed on the
    prefill pool (pool placements exceed the request count), and still
    finishes with the same greedy tokens as a solo run."""
    cfg, params = qwen
    eng = ServeEngine(cfg, CTX, window=64, max_batch=3, chunk=4,
                      page_size=8, num_pages=12, disaggregate=True,
                      prefill_workers=2)
    reqs = _reqs(cfg, 5, max_new=14)
    out = eng.run(params, reqs)
    stats = eng.scheduler.stats
    assert stats["preemptions"] >= 1, "pool sized to force eviction"
    assert stats["completions"] == 5
    assert eng.prefill_pool.stats["placed"] >= 5 + stats["preemptions"]
    victim = next(r for r in eng.scheduler.finished if r.preemptions)
    solo = ServeEngine(cfg, CTX, window=64, max_batch=1, chunk=4,
                       page_size=8)
    want = solo.run(params, [Request(rid=0, prompt=victim.prompt,
                                     max_new=14)])[0]
    np.testing.assert_array_equal(out[victim.rid], want)


# ---------------------------------------------- golden SLO snapshot


class _FakeClock:
    """Deterministic monotonic clock: one second per observation."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


_SLO_KEYS = ("requests", "ttft_p50_s", "ttft_p95_s", "tpot_p50_s",
             "tpot_p95_s", "queue_wait_p50_steps", "prefill_time_s",
             "decode_time_s", "prefill_tok_s", "decode_tok_s")


def _golden_run(cfg, params, traced=True):
    eng = ServeEngine(cfg, CTX, window=32, max_batch=2, chunk=4,
                      page_size=8, tracer=SpanTracer(clock=_FakeClock(),
                                                     enabled=traced))
    eng.run(params, _reqs(cfg, 4, lo=8, hi=12, max_new=8))
    return eng.slo_summary()


# The snapshot under the unit-step clock: every wall-derived metric is
# a deterministic count of the engine's observation points (p95 values
# interpolate inside a histogram bucket), and, with the tracer enabled,
# of the clock samples of its spans (two per span: schedule, prefill,
# table_upload, chunk and drain). A change here means the engine moved a
# measurement point or a span — update deliberately.
_GOLDEN_SLO = {
    "requests": 4.0,
    "ttft_p50_s": 30.0,
    "ttft_p95_s": 49.8,
    "tpot_p50_s": 11.0 / 7.0,
    "tpot_p95_s": 11.0 / 7.0,
    "queue_wait_p50_steps": 0.0,
    "prefill_time_s": 16.0,
    "decode_time_s": 24.0,
    "prefill_tok_s": 2.625,
    "decode_tok_s": 4.0 / 3.0,
}

# The same run with the tracer disabled: the engine's own observation
# points alone, which spans must never move.
_GOLDEN_SLO_UNTRACED = {
    "requests": 4.0,
    "ttft_p50_s": 10.0,
    "ttft_p95_s": 15.4,
    "tpot_p50_s": 3.0 / 7.0,
    "tpot_p95_s": 3.0 / 7.0,
    "queue_wait_p50_steps": 0.0,
    "prefill_time_s": 4.0,
    "decode_time_s": 4.0,
    "prefill_tok_s": 10.5,
    "decode_tok_s": 8.0,
}


def test_slo_summary_golden_snapshot(qwen):
    """Under an injected unit-step clock the SLO summary is an exact,
    reproducible snapshot: the schema, the measurement points, and the
    byte-identical double run are all pinned."""
    cfg, params = qwen
    slo = _golden_run(cfg, params)
    assert tuple(slo) == _SLO_KEYS
    assert slo == pytest.approx(_GOLDEN_SLO)
    again = _golden_run(cfg, params)
    assert json.dumps(slo, sort_keys=True) == \
        json.dumps(again, sort_keys=True)


def test_slo_summary_golden_snapshot_untraced(qwen):
    """A disabled tracer samples no clock for its spans: the snapshot
    counts the engine's measurement points alone."""
    cfg, params = qwen
    slo = _golden_run(cfg, params, traced=False)
    assert slo == pytest.approx(_GOLDEN_SLO_UNTRACED)


def test_bench_serve_arrivals_rows_pinned(qwen):
    """The bench_serve section-2 row schema is a module constant; every
    row maps to a real slo_summary key, and the pinned tuple is exactly
    what the golden snapshot (and run.py --json consumers) rely on."""
    assert bench_serve.ARRIVALS_SLO_ROWS == (
        ("serve/ttft_p50_s", "ttft_p50_s"),
        ("serve/ttft_p95_s", "ttft_p95_s"),
        ("serve/tpot_p50_s", "tpot_p50_s"),
        ("serve/tpot_p95_s", "tpot_p95_s"),
        ("serve/queue_wait_p50_steps", "queue_wait_p50_steps"),
        ("serve/prefill_time_s", "prefill_time_s"),
        ("serve/decode_time_s", "decode_time_s"),
    )
    cfg, params = qwen
    slo = _golden_run(cfg, params)
    for row, key in bench_serve.ARRIVALS_SLO_ROWS:
        assert row.startswith("serve/")
        assert key in slo
