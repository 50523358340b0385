"""Fault-injected serving: the deterministic chaos harness and every
recovery path it exercises.

Covers the tier-1 resilience contract end to end: the fault schedule is
a seed-keyed pure function (byte-identical across runs, independent of
traffic and query order), survivors of an injected schedule emit
byte-identical tokens to the fault-free run with the full feature stack
live (disaggregation + prefix cache + speculation + int8 pages),
corrupted prefix hashes are quarantined and never re-adopted, a disabled
injector leaves the engine bit-identical to one without the harness,
the scheduler's aged-priority and terminal-failure edges, SLO-aware
admission shedding, and the ``startup_bist`` kernel self-test
(``launch/serve.py --bist``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke
from repro.core.sdc import FaultModel, faulty_wrap
from repro.models import api
from repro.models.blocks import ModelContext
from repro.models.params import init_params
from repro.serve.admission import AdmissionController, AdmissionPolicy
from repro.serve.engine import ServeEngine
from repro.serve.faults import FaultInjector, FaultPlan, startup_bist
from repro.serve.scheduler import (ContinuousBatchingScheduler,
                                   PrefillWorkerPool, Request)

import hypothesis
import hypothesis.strategies as st

jax.config.update("jax_default_matmul_precision", "highest")

CTX = ModelContext(compute_dtype=jnp.float32, q_chunk=64, mamba_chunk=8,
                   rwkv_chunk=4)


@pytest.fixture(scope="module")
def qwen():
    cfg = get_smoke("qwen2_0_5b")
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    return cfg, params


def _reqs(cfg, n, *, seed=1, lo=9, hi=14, max_new=6):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(lo, hi + 1))),
                    max_new=max_new)
            for i in range(n)]


CHAOS = FaultPlan(seed=7, worker_fail_rate=0.25, page_flip_rate=0.25,
                  transfer_drop_rate=0.2, straggler_rate=0.2)


# ----------------------------------------------------- schedule purity


def test_fault_plan_validation():
    with pytest.raises(ValueError, match="must be in"):
        FaultPlan(page_flip_rate=1.5)
    with pytest.raises(ValueError, match="horizon"):
        FaultPlan(horizon_boundaries=0)
    with pytest.raises(ValueError, match="delays"):
        FaultPlan(straggler_extra_boundaries=0)


def test_fault_schedule_is_seed_deterministic():
    a, b = FaultInjector(CHAOS), FaultInjector(CHAOS)
    assert a.schedule_digest() == b.schedule_digest()
    assert FaultInjector(
        FaultPlan(**{**CHAOS.__dict__, "seed": 8})).schedule_digest() \
        != a.schedule_digest()


def test_fault_schedule_independent_of_query_order():
    """Queries are pure reads: interleaving kinds, repeating boundaries,
    or querying out of order never changes any answer — the property
    that makes the schedule independent of traffic and policy."""
    a, b = FaultInjector(CHAOS), FaultInjector(CHAOS)
    fwd = [(a.worker_failure(i), a.page_flip(i), a.transfer_drop(i),
            a.straggler(i)) for i in range(64)]
    for i in reversed(range(64)):  # reversed + repeated reads
        assert b.straggler(i) == fwd[i][3]
        assert b.page_flip(i) == fwd[i][1]
        assert b.page_flip(i) == fwd[i][1]
        assert b.worker_failure(i) == fwd[i][0]
        assert b.transfer_drop(i) == fwd[i][2]
    assert b.schedule_digest() == a.schedule_digest()
    # past the horizon the schedule is silent
    assert a.worker_failure(CHAOS.horizon_boundaries) is None
    assert a.straggler(-1) == 0


@hypothesis.given(seed=st.integers(min_value=0, max_value=1 << 20))
@hypothesis.settings(max_examples=10, deadline=None)
def test_fault_schedule_digest_property(seed):
    plan = FaultPlan(seed=seed, worker_fail_rate=0.3, page_flip_rate=0.1,
                     transfer_drop_rate=0.2, straggler_rate=0.4,
                     horizon_boundaries=256)
    assert FaultInjector(plan).schedule_digest() == \
        FaultInjector(plan).schedule_digest()


@hypothesis.given(rate=st.floats(min_value=0.05, max_value=0.95),
                  boundary=st.integers(min_value=0, max_value=255))
@hypothesis.settings(max_examples=10, deadline=None)
def test_fault_kinds_draw_from_independent_streams(rate, boundary):
    """Each kind's stream is keyed by crc32(kind): changing one kind's
    rate never perturbs another kind's hit pattern (the per-kind RNG
    split that keeps the schedule policy-independent)."""
    base = FaultInjector(FaultPlan(seed=3, horizon_boundaries=256,
                                   straggler_rate=0.5))
    other = FaultInjector(FaultPlan(seed=3, horizon_boundaries=256,
                                    straggler_rate=0.5,
                                    worker_fail_rate=rate,
                                    page_flip_rate=rate))
    assert base.straggler(boundary) == other.straggler(boundary)


# ------------------------------------------------ engine under faults


def _build(cfg, *, faults=None, admission=None, retry_budget=3, spec=True,
           int8=False, disagg=True):
    ctx = ModelContext(compute_dtype=jnp.float32, q_chunk=64,
                       decode_cache_dtype=jnp.int8 if int8 else None)
    return ServeEngine(cfg, ctx, window=32, max_batch=2, chunk=2,
                       page_size=8, draft_k=2 if spec else 0,
                       disaggregate=disagg,
                       prefill_workers=2 if disagg else 1,
                       faults=faults, admission=admission,
                       retry_budget=retry_budget)


@pytest.mark.parametrize("int8", [False, True])
def test_survivor_token_parity_under_full_fault_schedule(qwen, int8):
    """The fatal tier-1 gate at full feature depth: disaggregated
    prefill + prefix cache + speculation (+ int8 pages) under worker
    kills, page flips, transfer drops and stragglers — every survivor's
    token stream byte-identical to the fault-free run, nonzero
    detections, and quarantined prefix hashes never re-adopted."""
    cfg, params = qwen
    base = _build(cfg, int8=int8).run(params, _reqs(cfg, 3))
    eng = _build(cfg, faults=FaultInjector(CHAOS), int8=int8)
    out = eng.run(params, _reqs(cfg, 3))
    fs = eng.fault_stats
    assert fs["fault_detections"] > 0
    assert fs["fault_worker_failures"] > 0
    assert fs["fault_page_corruptions"] > 0
    assert len(out) >= 2  # the schedule must not wipe out the batch
    for rid, toks in out.items():
        np.testing.assert_array_equal(toks, base[rid])
    # quarantine is sticky: a poisoned prefix hash leaves the index and
    # can never be re-adopted by a later admission
    assert fs["fault_pages_quarantined"] > 0
    assert eng.kv._quarantined
    assert not (eng.kv._quarantined & set(eng.kv._index))


def test_disabled_injector_is_bit_identical_to_no_harness(qwen):
    """faults=None must leave the engine byte-identical to pre-harness
    behavior, and an all-zero-rate injector must match as well (CRC
    stamping is observability, not behavior)."""
    cfg, params = qwen
    plain = _build(cfg).run(params, _reqs(cfg, 3))
    silent = _build(cfg, faults=FaultInjector(FaultPlan(seed=7)))
    out = silent.run(params, _reqs(cfg, 3))
    assert set(out) == set(plain)
    for rid in out:
        np.testing.assert_array_equal(out[rid], plain[rid])
    assert sum(silent.fault_stats.values()) == 0


def test_retry_budget_exhaustion_fails_deterministically(qwen):
    """retry_budget=0 + certain page corruption: the first detected
    fault on a request is terminal (state="failed"), the run still
    completes, and any survivors still match the fault-free tokens."""
    cfg, params = qwen
    plan = FaultPlan(seed=11, page_flip_rate=1.0)
    base = _build(cfg, disagg=False).run(params, _reqs(cfg, 3))
    eng = _build(cfg, faults=FaultInjector(plan), retry_budget=0,
                 disagg=False)
    out = eng.run(params, _reqs(cfg, 3))
    s = eng.scheduler
    assert s.stats["failures"] > 0
    assert s.stats["replays"] == 0  # budget 0: no requeues, only fails
    assert all(r.state == "failed" for r in s.failed)
    assert len(out) + len(s.failed) == 3
    for rid, toks in out.items():
        np.testing.assert_array_equal(toks, base[rid])


# ------------------------------------------------- scheduler edges


def test_aged_request_outranks_fresh_arrivals():
    sched = ContinuousBatchingScheduler(2, aged_priority_after=2)
    old = Request(rid=0, prompt=np.arange(4), max_new=2, arrival=0)
    fresh = Request(rid=1, prompt=np.arange(4), max_new=2, arrival=0)
    sched.add(old)
    sched.add(fresh)
    assert sched.next_admittable(0) is old  # FIFO ties break by rid
    old.preemptions = 1
    old.retries = 1  # preemptions + retries hits the threshold
    fresh.arrival = -1  # even an older arrival loses to an aged request
    assert sched.next_admittable(0) is old


def test_not_before_backoff_gates_admission_and_pool_routing():
    sched = ContinuousBatchingScheduler(2)
    req = Request(rid=0, prompt=np.arange(4), max_new=2, arrival=0)
    sched.add(req)
    sched.admit(req, 0)
    sched.requeue(req, not_before=6)
    assert req.retries == 1 and not req.prefill_done
    assert sched.next_admittable(5) is None
    assert sched.next_admittable(6) is req
    assert sched.stats["replays"] == 1


def test_terminal_failure_from_waiting_and_running():
    sched = ContinuousBatchingScheduler(2)
    a = Request(rid=0, prompt=np.arange(4), max_new=2)
    b = Request(rid=1, prompt=np.arange(4), max_new=2)
    sched.add(a)
    sched.add(b)
    sched.admit(a, 0)
    sched.fail(a)          # from running: slot must free up
    sched.fail(b)          # from waiting: must leave the queue
    assert a.state == b.state == "failed"
    assert not sched.running and not sched.waiting
    assert sched.stats["failures"] == 2
    assert sched.free_slots() == [0, 1]


def test_pool_failover_replaces_onto_survivor():
    pool = PrefillWorkerPool(2, span_len=8, chunk=4)
    reqs = [Request(rid=i, prompt=np.arange(12), max_new=2)
            for i in range(3)]
    for r in reqs:
        pool.place(r, clock=0)
    victim = 0 if pool.queues[0] else 1
    lost = pool.fail_worker(victim, clock=0)
    assert lost  # mid-flight prompts were re-placed
    assert not pool.queues[victim]  # dead worker drained
    assert pool.free_at[victim] == 16  # respawn: 4 boundaries * chunk 4
    assert pool.stats["worker_failures"] == 1
    assert pool.stats["failover_replacements"] == len(lost)
    # sole-worker pool: replays land on the same worker post-respawn
    solo = PrefillWorkerPool(1, span_len=8, chunk=4)
    solo.place(reqs[0], clock=0)
    assert solo.fail_worker(0, clock=0)
    assert len(solo.queues[0]) == 1


# --------------------------------------------------- admission control


def test_admission_policy_validation():
    with pytest.raises(ValueError, match="ttft_deadline_steps"):
        AdmissionPolicy(ttft_deadline_steps=0)
    with pytest.raises(ValueError, match="spec_off_queue_depth"):
        AdmissionPolicy(spec_off_queue_depth=-1)


def test_should_shed_spares_sunk_work():
    ctl = AdmissionController(AdmissionPolicy(ttft_deadline_steps=4))
    kw = dict(chunk=2, span_len=8, disaggregated=False)
    hopeless = Request(rid=0, prompt=np.arange(8), max_new=2, arrival=0)
    assert ctl.should_shed(hopeless, clock=10, **kw)
    fresh = Request(rid=1, prompt=np.arange(8), max_new=2, arrival=10)
    assert not ctl.should_shed(fresh, clock=10, **kw)
    # replayed/preempted/generating requests are never shed: their
    # accrued wait reflects the fault, not their viability
    replayed = Request(rid=2, prompt=np.arange(8), max_new=2, arrival=0)
    replayed.retries = 1
    assert not ctl.should_shed(replayed, clock=10, **kw)
    generating = Request(rid=3, prompt=np.arange(8), max_new=4, arrival=0)
    generating.generated.append(5)
    assert not ctl.should_shed(generating, clock=10, **kw)
    assert not AdmissionController().should_shed(hopeless, clock=10, **kw)


def test_engine_sheds_late_requests_and_preserves_served_tokens(qwen):
    """A TTFT deadline sheds requests that arrive into a hopeless queue;
    the ones actually served still match the no-admission run token for
    token (shedding changes batch composition, which must not change
    per-request tokens)."""
    cfg, params = qwen
    def reqs():
        out = _reqs(cfg, 4, max_new=6)
        for i, r in enumerate(out):
            r.arrival = 0 if i < 2 else 1  # latecomers behind a full batch
        return out
    base = _build(cfg, disagg=False, spec=False).run(params, reqs())
    ctl = AdmissionController(AdmissionPolicy(ttft_deadline_steps=3))
    eng = _build(cfg, disagg=False, spec=False, admission=ctl)
    out = eng.run(params, reqs())
    assert eng.fault_stats["shed_requests"] > 0
    assert eng.scheduler.shed  # state="shed", never admitted
    assert all(r.state == "shed" for r in eng.scheduler.shed)
    assert len(out) + len(eng.scheduler.shed) == 4
    for rid, toks in out.items():
        np.testing.assert_array_equal(toks, base[rid])


def test_queue_pressure_drops_speculation_token_identically(qwen):
    cfg, params = qwen
    base = _build(cfg, disagg=False).run(params, _reqs(cfg, 4))
    ctl = AdmissionController(AdmissionPolicy(spec_off_queue_depth=0))
    eng = _build(cfg, disagg=False, admission=ctl)
    out = eng.run(params, _reqs(cfg, 4))
    assert eng.fault_stats["shed_spec_chunks"] > 0
    assert eng.fault_stats["shed_requests"] == 0  # no deadline set
    assert set(out) == set(base)
    for rid in out:
        np.testing.assert_array_equal(out[rid], base[rid])


# ------------------------------------------------------- startup BIST


def test_startup_bist_passes_on_healthy_kernels():
    res = startup_bist(interpret=True)
    assert res.passed and res.matmul_report.passed and res.paged_decode_ok
    assert res.paged_decode_max_err < 5e-2


def test_startup_bist_catches_injected_kernel_faults():
    bad_mm = faulty_wrap(lambda a, b: a @ b,
                         FaultModel(rate=1.0, magnitude=0.5, seed=1))
    res = startup_bist(interpret=True, matmul_fn=bad_mm)
    assert not res.passed and not res.matmul_report.passed
    res = startup_bist(interpret=True,
                       matmul_fn=lambda a, b: a @ b,
                       decode_fn=lambda q, k, v, t, p, **kw: jnp.zeros(
                           q.shape, q.dtype))
    assert not res.passed and not res.paged_decode_ok
