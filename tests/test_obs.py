"""Unified telemetry: metrics registry semantics, span tracer / Chrome
trace validation, steptrace round-trips, measured step-time models, and
the engine/trainer integration invariants (telemetry must not change
tokens; one merged timeline must validate with serve+train+fleet
categories)."""

import glob
import json
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke
from repro.fleet.perf import MeasuredStepTimeModel, StepTimeModel, \
    job_spec_from_trace
from repro.fleet.sim import FleetConfig, FleetSimulator
from repro.models import api
from repro.models.blocks import ModelContext
from repro.models.params import init_params
from repro.obs.metrics import (CATALOG, CounterDict, Histogram,
                               MetricsRegistry, NULL_METRIC)
from repro.obs.steptrace import StepTrace
from repro.obs.trace import (SpanTracer, merge_chrome_traces,
                             validate_chrome_trace)
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import Request

jax.config.update("jax_default_matmul_precision", "highest")

CTX = ModelContext(compute_dtype=jnp.float32, q_chunk=64, mamba_chunk=8,
                   rwkv_chunk=4)


@pytest.fixture(scope="module")
def qwen():
    cfg = get_smoke("qwen2_0_5b")
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    return cfg, params


class FakeClock:
    """Deterministic injectable clock: each call advances by ``dt``."""

    def __init__(self, dt=1.0):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


# ------------------------------------------------------------- metrics


def test_histogram_bucket_edges():
    h = Histogram("h", edges=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 3.0, 99.0):  # 1.0 lands in its own bucket
        h.observe(v)
    assert h.counts == [2, 1, 1, 1]  # <=1, <=2, <=4, overflow
    assert h.count == 5
    assert h.min == 0.5 and h.max == 99.0
    d = h.to_dict()
    assert d["count"] == 5 and d["edges"] == [1.0, 2.0, 4.0]
    assert 0.5 <= d["p50"] <= 2.0  # interpolated, clamped to observed
    assert d["p99"] <= 99.0


def test_histogram_rejects_unsorted_edges():
    with pytest.raises(ValueError):
        Histogram("bad", edges=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("dup", edges=(1.0, 1.0))


def test_empty_histogram_is_zero():
    h = Histogram("h")
    assert h.mean == 0.0 and h.quantile(0.5) == 0.0
    assert h.to_dict()["min"] == 0.0


def test_disabled_registry_is_null_and_allocates_nothing(tmp_path):
    reg = MetricsRegistry(enabled=False)
    assert reg.counter("a") is NULL_METRIC
    assert reg.gauge("b") is NULL_METRIC
    assert reg.histogram("c") is NULL_METRIC
    reg.counter("a").inc()
    reg.histogram("c").observe(1.0)
    reg.compile_event("f")
    assert reg._metrics == {}  # nothing ever allocated
    assert reg.snapshot() == {}
    out = tmp_path / "m.jsonl"
    reg.to_jsonl(str(out))
    assert not out.exists()  # disabled -> no file touched


def test_registry_snapshot_and_jsonl(tmp_path):
    clk = FakeClock()
    reg = MetricsRegistry(clock=clk)
    reg.counter("serve_chunks").inc(3)
    reg.histogram("serve_ttft_s").observe(0.02)
    snap = reg.snapshot()
    assert snap["serve_chunks"] == 3
    assert snap["serve_ttft_s"]["count"] == 1
    out = tmp_path / "m.jsonl"
    reg.to_jsonl(str(out))
    reg.to_jsonl(str(out))  # appends
    lines = [json.loads(l) for l in out.read_text().splitlines()]
    assert len(lines) == 2
    assert lines[0]["metrics"]["serve_chunks"] == 3
    assert lines[1]["t"] > lines[0]["t"]


def test_counterdict_facade_routes_into_registry():
    reg = MetricsRegistry()
    cd = CounterDict(reg, ("chunks", "host_syncs"), prefix="serve_")
    cd["chunks"] += 2
    cd["host_syncs"] = 7
    cd["host_syncs"] = 0  # bench-style reset
    assert cd["chunks"] == 2
    assert reg.counter("serve_chunks").value == 2
    assert dict(cd) == {"chunks": 2, "host_syncs": 0}
    with pytest.raises(KeyError):
        cd["nope"]
    with pytest.raises(TypeError):
        del cd["chunks"]


def test_compile_event_counts_compiles():
    reg = MetricsRegistry()
    reg.compile_event("serve_span_prefill")
    reg.compile_event("serve_span_prefill")
    assert reg.counter("serve_span_prefill_compiles").value == 2


def test_catalog_names_have_role_prefixes():
    assert all(n.startswith(("serve_", "train_", "attn_")) for n in CATALOG)


def test_attention_path_counters(tmp_path):
    """On the CPU the trainer's compiled step lowers jnp attention, once
    per compile however many steps run; a context on the flash kernels
    (interpret mode) counts ``attn_flash`` instead."""
    from repro.launch.train import build_trainer
    from repro.models.attention import full_attention

    cfg = get_smoke("qwen2_0_5b")
    trainer, state = build_trainer(
        cfg, batch=2, seq=16, ckpt_dir=str(tmp_path / "ckpt"),
        checkpoint_every=100)
    trainer.run(state, 3)
    snap = trainer.metrics.snapshot()
    assert snap["train_steps"] == 3
    assert snap["attn_xla_compiles"] == 1
    assert "attn_flash_compiles" not in snap

    reg = MetricsRegistry()
    q = jnp.ones((1, 128, cfg.n_heads, cfg.resolved_head_dim))
    k = jnp.ones((1, 128, cfg.n_kv_heads, cfg.resolved_head_dim))
    jax.jit(lambda q, k: full_attention(
        q, k, k, cfg, impl="pallas_interpret", metrics=reg)).lower(q, k)
    assert reg.snapshot() == {"attn_flash_compiles": 1}


# --------------------------------------------------------------- trace


def test_span_nesting_and_ordering_with_fake_clock():
    tr = SpanTracer(clock=FakeClock())
    pid = tr.process("serve")
    tr.thread(pid, 0, "slot0")
    tr.begin("req:0", pid=pid, tid=0, cat="serve")
    tr.begin("prefill", pid=pid, tid=0, cat="serve")
    tr.end(pid=pid, tid=0)  # closes prefill
    tr.end(pid=pid, tid=0)  # closes req:0
    names = [e["name"] for e in tr.events if e["ph"] == "E"]
    assert names == ["prefill", "req:0"]  # LIFO close order
    doc = tr.chrome_trace()
    assert validate_chrome_trace(doc, require_cats=["serve"]) == []
    ts = [e["ts"] for e in doc["traceEvents"] if e["ph"] in "BE"]
    assert ts == sorted(ts) and ts[0] == 0.0  # rebased to t=0


def test_validator_flags_unbalanced_and_regressed():
    tr = SpanTracer(clock=FakeClock())
    tr.begin("open", pid=0, tid=0)
    probs = validate_chrome_trace(tr.chrome_trace())
    assert any("unclosed" in p for p in probs)
    tr2 = SpanTracer()
    tr2.emit({"ph": "B", "pid": 0, "tid": 0, "name": "a", "ts": 10.0})
    tr2.emit({"ph": "E", "pid": 0, "tid": 0, "name": "a", "ts": 5.0})
    probs = validate_chrome_trace(tr2.chrome_trace())
    assert any("regressed" in p or "ends before" in p for p in probs)
    tr3 = SpanTracer()
    tr3.emit({"ph": "X", "pid": 0, "tid": 0, "name": "x", "ts": 0.0,
              "dur": -1.0})
    assert any("non-negative" in p
               for p in validate_chrome_trace(tr3.chrome_trace()))
    tr4 = SpanTracer()
    tr4.emit({"ph": "E", "pid": 0, "tid": 0, "name": "a", "ts": 0.0})
    assert any("without open B" in p
               for p in validate_chrome_trace(tr4.chrome_trace()))


def test_disabled_tracer_records_nothing():
    tr = SpanTracer(enabled=False)
    assert tr.process("p") == 0
    tr.begin("a")
    tr.end()
    tr.complete("b", 1.0)
    tr.instant("c")
    tr.counter("d", {"v": 1.0})
    assert tr.events == []


def _host_events(tmp_path, fn):
    """Run ``fn`` under a JAX profiler trace; returns the host plane's
    events as {name: [(start_ns, end_ns, stats)]}."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return out


def test_span_reaches_the_profiler_trace(tmp_path):
    """An enabled span lands in the profiler's host plane as
    ``<process>.<name>``, nested in its parent, with the step number on a
    step span; the tracer's own events keep the bare names."""
    tr = SpanTracer()
    pid = tr.process("train")
    ck = tr.process("ckpt")

    def write():
        with tr.span("write", pid=ck, tid=1, cat="ckpt"):
            pass

    def work():
        with tr.span("step", pid=pid, cat="train", step=7):
            with tr.span("batch", pid=pid, cat="train"):
                pass
            # another thread on a lane of its own, as the ckpt writer
            t = threading.Thread(target=write)
            t.start()
            t.join(timeout=10)
        assert not t.is_alive()

    host = _host_events(tmp_path, work)
    (s0, s1, stats), = host["train.step"]
    assert stats.get("step_num") == 7
    (b0, b1, _), = host["train.batch"]
    assert s0 <= b0 <= b1 <= s1
    (w0, w1, _), = host["ckpt.write"]
    assert b1 <= w0 <= w1 <= s1
    assert validate_chrome_trace(tr.chrome_trace()) == []
    assert [e["name"] for e in tr.events if e["ph"] == "B"
            and e["pid"] == pid] == ["step", "batch"]


def test_disabled_span_adds_nothing_to_the_profiler_trace(tmp_path):
    tr = SpanTracer(enabled=False)
    pid = tr.process("train")

    def work():
        with jax.profiler.TraceAnnotation("marker"):
            with tr.span("step", pid=pid, step=1):
                with tr.span("batch", pid=pid):
                    pass

    host = _host_events(tmp_path, work)
    assert "marker" in host  # the trace was captured
    assert not [n for n in host if n.startswith(("train.", "step", "batch"))]
    assert tr.events == []
    assert tr.span("a") is tr.span("b")  # one shared no-op context


def test_span_lanes_from_many_threads_stay_valid():
    """Threads emitting into one tracer, each on its own lane (as the
    checkpoint writer does beside the trainer), with the interpreter
    switching threads as often as it can: every span is recorded and
    every lane nests."""
    tr = SpanTracer()
    pid = tr.process("p")
    n_threads, n_spans = 16, 200

    def lane(tid):
        for i in range(n_spans):
            with tr.span("outer", pid=pid, tid=tid):
                with tr.span("inner", pid=pid, tid=tid, args={"i": i}):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=lane, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert validate_chrome_trace(tr.chrome_trace()) == []
    assert sum(e["ph"] == "B" for e in tr.events) == \
        2 * n_threads * n_spans


def test_chrome_trace_roundtrip_and_merge(tmp_path):
    a = SpanTracer(clock=FakeClock())
    pa = a.process("serve")
    with a.span("req", pid=pa, cat="serve"):
        a.complete("prefill", 0.5, pid=pa, cat="serve")
    b = SpanTracer(clock=FakeClock())
    pb = b.process("train")
    b.complete("step", 0.1, pid=pb, cat="train")
    path = tmp_path / "t.json"
    a.write(str(path))
    with open(path) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    assert validate_chrome_trace(doc, require_cats=["serve"]) == []
    merged = merge_chrome_traces([doc, b.chrome_trace()])
    assert validate_chrome_trace(
        merged, require_cats=["serve", "train"]) == []
    # pid remap keeps the sources on disjoint process rows
    pids_a = {e["pid"] for e in doc["traceEvents"]}
    pids_b = {e["pid"] for e in merged["traceEvents"]
              if e.get("cat") == "train" or
              (e["ph"] == "M" and e["args"]["name"] == "train")}
    assert pids_a.isdisjoint(pids_b)


# ----------------------------------------------------------- steptrace


def test_steptrace_roundtrip(tmp_path):
    st = StepTrace(source="serve", meta={"arch": "qwen"})
    st.record("prefill", 0.2, tokens=12, batch=1)
    st.record("decode", 0.1, batch=2, steps=4)
    with pytest.raises(ValueError):
        st.record("banana", 1.0)
    path = tmp_path / "st.json"
    st.write(str(path))
    back = StepTrace.read(str(path))
    assert back.source == "serve" and back.meta == {"arch": "qwen"}
    assert len(back) == 2
    assert back.events[0].features == {"tokens": 12.0, "batch": 1.0}
    assert back.durations(("decode",)) == [0.1]
    with pytest.raises(ValueError):
        StepTrace.from_dict({"schema": "nope"})


def test_from_trace_replay_equals_recorded():
    st = StepTrace(source="train")
    for d in (0.5, 0.3, 0.4):
        st.record("step", d)
    st.record("replay", 9.0)  # rework: excluded from effective kinds
    model = StepTimeModel.from_trace(st, cubes_ref=2)
    assert isinstance(model, MeasuredStepTimeModel)
    assert model.replay() == (0.5, 0.3, 0.4)
    assert model.mean_step_s == pytest.approx(0.4)
    assert model(2) == pytest.approx(0.4)  # at the reference size
    assert model(4) == pytest.approx(0.2)  # ideal-linear rescale
    with pytest.raises(ValueError):
        StepTimeModel.from_trace(StepTrace())  # no measured durations


def test_from_trace_drives_fleet_sim():
    st = StepTrace(source="serve")
    for d in (0.02, 0.04, 0.03):
        st.record("decode", d, batch=2)
    spec = job_spec_from_trace("measured", st, chips=64, total_steps=10,
                               checkpoint_every_steps=5)
    assert spec.step_time_s == pytest.approx(0.03)
    sim = FleetSimulator(FleetConfig(tpu="ironwood", total_cubes=2,
                                     host_mtbf_hours=None), [spec])
    sim.run(60.0)
    job = sim.jobs["measured"]
    assert job.state == "done"
    assert job.ledger.goodput == pytest.approx(1.0)


# ------------------------------------------------- engine integration


def _run_engine(cfg, params, ps, **kw):
    eng = ServeEngine(cfg, CTX, window=32, max_batch=2, chunk=2,
                      page_size=8, **kw)
    out = eng.run(params, [Request(rid=i, prompt=p, max_new=4)
                           for i, p in enumerate(ps)])
    return eng, out


def test_engine_telemetry_does_not_change_tokens(qwen):
    """Default engine vs fully-instrumented vs fully-disabled telemetry:
    token-identical outputs (all instrumentation is host-side)."""
    cfg, params = qwen
    rng = np.random.default_rng(0)
    ps = [rng.integers(0, cfg.vocab_size, n) for n in (9, 13, 6)]
    _, base = _run_engine(cfg, params, ps)
    on_eng, on = _run_engine(cfg, params, ps, metrics=MetricsRegistry(),
                             tracer=SpanTracer())
    off_eng, off = _run_engine(cfg, params, ps,
                               metrics=MetricsRegistry(enabled=False),
                               tracer=SpanTracer(enabled=False))
    for i in range(len(ps)):
        np.testing.assert_array_equal(base[i], on[i])
        np.testing.assert_array_equal(base[i], off[i])
    # the instrumented run populated SLO metrics and a valid trace
    snap = on_eng.metrics.snapshot()
    assert snap["serve_requests_admitted"] == len(ps)
    assert snap["serve_requests_finished"] == len(ps)
    assert snap["serve_ttft_s"]["count"] == len(ps)
    assert snap["serve_tpot_s"]["count"] == len(ps)  # max_new>1 for all
    assert snap["serve_generated_tokens"] == sum(
        4 for _ in ps)
    assert validate_chrome_trace(on_eng.tracer.chrome_trace(),
                                 require_cats=["serve"]) == []
    slo = on_eng.slo_summary()
    assert slo["requests"] == len(ps)
    assert slo["ttft_p95_s"] >= slo["ttft_p50_s"] >= 0.0
    assert slo["prefill_time_s"] > 0.0 and slo["decode_time_s"] > 0.0
    # the disabled run allocated no metric state at all
    assert off_eng.metrics.snapshot() == {}
    assert off_eng.tracer.events == []
    # measured steptrace carries both roles' chunk kinds
    kinds = {e.kind for e in on_eng.steptrace.events}
    assert kinds == {"prefill", "decode"}


def _span_seconds(events, pid):
    """{name: [seconds of each B/E span on the process row]}."""
    out, open_ = {}, {}
    for e in events:
        if e.get("pid") != pid or e["ph"] not in "BE":
            continue
        stack = open_.setdefault(e["tid"], [])
        if e["ph"] == "B":
            stack.append(e)
        else:
            b = stack.pop()
            out.setdefault(b["name"], []).append((e["ts"] - b["ts"]) / 1e6)
    return out


def test_engine_spans_stay_off_slot_lanes(qwen):
    """The engine's host work (schedule, prefill, table_upload, chunk,
    drain) is spanned on its device lane; a slot lane holds only its
    requests' lifecycle spans, whose ends mark completions."""
    cfg, params = qwen
    rng = np.random.default_rng(2)
    ps = [rng.integers(0, cfg.vocab_size, n) for n in (9, 13, 6)]
    eng, _ = _run_engine(cfg, params, ps, tracer=SpanTracer())
    begun = [(e["tid"], e["name"]) for e in eng.tracer.events
             if e["ph"] == "B"]
    device = {n for t, n in begun if t == eng._device_tid}
    assert device == {"schedule", "prefill", "table_upload", "chunk",
                      "drain"}
    assert all(n.startswith(("req:", "park")) for t, n in begun
               if t != eng._device_tid)
    assert validate_chrome_trace(eng.tracer.chrome_trace(),
                                 require_cats=["serve"]) == []


def test_request_times_after_preemption():
    """``request_times`` gives each finished request's ready, admit,
    first-token and completion seconds on the tracer's clock; a
    preempted request's admit is its last admission."""
    cfg = get_smoke("qwen2_0_5b")
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               int(rng.integers(8, 15))),
                    max_new=14) for i in range(5)]
    eng = ServeEngine(cfg, CTX, window=64, max_batch=3, chunk=4,
                      page_size=8, num_pages=12,
                      tracer=SpanTracer(clock=FakeClock(), enabled=False))
    eng.run(params, reqs)
    times = eng.request_times()
    assert sorted(times) == [r.rid for r in reqs]
    for r in reqs:
        t = times[r.rid]
        assert set(t) == {"ready", "admit", "first", "last"}
        assert t["ready"] <= t["admit"] and t["ready"] < t["first"]
        assert t["first"] < t["last"]
        if r.preemptions:
            assert t["first"] < t["admit"]  # re-admitted after a token
        else:
            assert t["admit"] < t["first"]
    assert any(r.preemptions for r in reqs)


def test_trainer_telemetry_matches_replay_summary(tmp_path):
    from repro.launch.train import build_trainer
    from repro.resilience.driver import StragglerPolicy

    tracer = SpanTracer()
    trainer, state = build_trainer(
        get_smoke("qwen2_0_5b"), batch=2, seq=16,
        ckpt_dir=str(tmp_path / "ckpt"), checkpoint_every=4,
        failures={5: 0}, tracer=tracer)
    trainer.straggler = StragglerPolicy(threshold=float("inf"))
    # modelled costs far above anything measured here: the ledger
    # charges them, the trace must not show them as spans
    trainer.failure_plan.detect_s = 100.0
    trainer.failure_plan.restore_extra_s = 100.0
    _, ledger, losses = trainer.run(state, 8)
    rs = trainer.replay_summary()
    snap = trainer.metrics.snapshot()
    assert snap["train_steps"] == rs["effective_steps"] == len(losses)
    assert snap["train_replayed_steps"] == rs["replayed_steps"]
    assert snap["train_failures"] == 1
    assert snap["train_restores"] == 1
    assert snap["train_ckpt_saves"] >= 2  # bootstrap + periodic
    assert snap["train_step_s"]["count"] == len(losses)
    assert validate_chrome_trace(tracer.chrome_trace(),
                                 require_cats=["train"]) == []
    pid = tracer.process("train")
    spans = _span_seconds(tracer.events, pid)
    assert {"step", "ckpt", "restore", "replay"} <= set(spans)
    assert not any(e["name"] == "detect" for e in tracer.events)
    fail, = [e for e in tracer.events
             if e["ph"] == "i" and e["name"] == "cube_fail"]
    assert fail["pid"] == pid and fail["args"]["detect_s"] == 100.0
    restore_s, = spans["restore"]
    totals = ledger.totals()
    assert totals["detect"] == 100.0
    assert totals["restore"] == pytest.approx(restore_s + 100.0, abs=1e-2)
    assert restore_s < 100.0  # measured only
    st = trainer.steptrace()
    assert st.durations(("replay",)) and st.durations(("step",))
    assert len(st) == len(trainer.records)


def test_one_timeline_serve_train_fleet(qwen):
    """The acceptance shape: serve request spans, trainer-style step
    spans, and fleet-sim events merged into one validating document."""
    cfg, params = qwen
    rng = np.random.default_rng(1)
    ps = [rng.integers(0, cfg.vocab_size, 7)]
    eng, _ = _run_engine(cfg, params, ps, tracer=SpanTracer())

    shared = SpanTracer(clock=FakeClock(0.01))
    tp = shared.process("train")
    shared.complete("step", 0.1, pid=tp, cat="train")
    st = StepTrace(source="serve")
    st.record("decode", 0.05)
    spec = job_spec_from_trace("measured", st, chips=64, total_steps=4,
                               checkpoint_every_steps=2)
    sim = FleetSimulator(FleetConfig(tpu="ironwood", total_cubes=2,
                                     host_mtbf_hours=None), [spec],
                         tracer=shared)
    sim.run(10.0)
    assert sim.jobs["measured"].state == "done"
    merged = merge_chrome_traces([eng.tracer.chrome_trace(),
                                  shared.chrome_trace()])
    assert validate_chrome_trace(
        merged, require_cats=["serve", "train", "fleet"]) == []
