"""Serve traffic through the fleet simulator: scenario suites as
tests, seeded-arrival determinism properties, grammar stability across
autoscale policies, trace calibration, and the serve power pipeline.

Every ``benchmarks/scenarios/*.json`` runs here as one pytest case (the
same file bench_fleet emits as a row), so a scenario regression fails
tier-1 twice — once as a benchmark MISMATCH, once as a test."""

import json
from pathlib import Path

import pytest
import hypothesis
import hypothesis.strategies as st

from repro.core import hwspec
from repro.fleet import (ArrivalProcess, FleetConfig, FleetSimulator,
                         JobSpec, PowerModel, ServeJobSpec, ServeSLO,
                         ServiceTimeModel, grammar_ok, load_scenario,
                         load_scenario_paths, run_scenario,
                         serve_calibration_check,
                         service_model_from_trace, validate_scenario)
from repro.obs.steptrace import StepTrace

SCENARIO_DIR = Path(__file__).resolve().parent.parent / "benchmarks" \
    / "scenarios"
SCENARIO_PATHS = load_scenario_paths(SCENARIO_DIR)

_SERVICE = dict(prefill_s_per_token=0.001, chunk_base_s=0.08,
                chunk_per_slot_s=0.02, chunk_steps=8)


def _mixed_sim(*, seed=7, rate=2.0, policy="auto", horizon=600.0,
               mtbf_hours=None):
    """A small mixed serve+train pod, the shared fixture for the
    determinism / grammar properties (sub-second per run)."""
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=4,
                      host_mtbf_hours=mtbf_hours, repair_hours=1.0,
                      seed=seed)
    train = JobSpec(name="t0", chips=64, total_steps=10**9,
                    step_time_s=1.0, checkpoint_every_steps=300)
    svc = ServeJobSpec(
        name="chat", chips=64,
        arrivals=ArrivalProcess(rate_rps=rate, prompt_tokens=64,
                                output_tokens=32),
        slo=ServeSLO(ttft_s=2.0, tpot_s=0.05),
        service=ServiceTimeModel(**_SERVICE),
        replicas=1, min_replicas=1, max_replicas=2, max_batch=4,
        scale_policy=policy, spinup_s=10.0, control_interval_s=30.0)
    sim = FleetSimulator(cfg, [train], serve_jobs=[svc])
    sim.run(horizon)
    return sim


def _serve_dump(sim):
    """The full determinism surface of one serve job: the request log,
    the goodput ledger, and both summaries, as one canonical string."""
    rt = sim.serve["chat"]
    return json.dumps({
        "log": rt.request_log,
        "ledger": [(e.kind, round(e.seconds, 9), e.steps, e.note)
                   for e in rt.ledger.events],
        "slo": rt.slo_summary(),
        "fleet": sim.fleet_summary(),
    }, sort_keys=True)


# ----------------------------------------------------- scenario suites


@pytest.mark.parametrize(
    "path", SCENARIO_PATHS, ids=[p.stem for p in SCENARIO_PATHS])
def test_scenario_validates_and_passes(path):
    doc = json.loads(path.read_text())
    assert validate_scenario(doc) == []
    res = run_scenario(doc)
    failed = [c for c in res["checks"] if not c["ok"]]
    assert res["ok"], f"failed expect checks: {failed}"
    assert res["checks"], "scenario must assert something"


def test_scenario_suite_has_required_gates():
    """The suite must contain at least one autoscaling-beats-static
    scenario (baseline ref on slo_goodput) and at least one
    SLO-violation-under-burst scenario."""
    docs = [json.loads(p.read_text()) for p in SCENARIO_PATHS]
    assert any(
        any("ref" in c and "slo_goodput" in c["metric"]
            for c in d.get("expect", []))
        for d in docs if d.get("baseline"))
    assert any(
        d.get("serve_jobs") and any(
            j.get("arrivals", {}).get("burst_x", 1.0) > 1.0
            for j in d["serve_jobs"])
        and any(c["metric"].endswith("ttft_viol")
                for c in d.get("expect", []))
        for d in docs)


def test_run_scenario_with_measured_service_model():
    """run_scenario(service=...) substitutes a measured model into both
    arms — the path the calibration gate uses."""
    doc = json.loads(
        (SCENARIO_DIR / "serve_burst_slo_violation.json").read_text())
    model = ServiceTimeModel(**_SERVICE)
    res = run_scenario(doc, service=model)
    base = run_scenario(doc)
    # identical coefficients => identical metrics, model path exercised
    assert res["metrics"] == base["metrics"]


# ------------------------------------------- validator negative space


def _valid_doc():
    return json.loads(
        (SCENARIO_DIR / "serve_autoscale_vs_static.json").read_text())


def test_validate_rejects_unknown_keys_everywhere():
    for mutate in (
            lambda d: d.update(extra_knob=1),
            lambda d: d["fleet"].update(cooling="liquid"),
            lambda d: d["serve_jobs"][0].update(turbo=True),
            lambda d: d["serve_jobs"][0]["arrivals"].update(ramp=2),
            lambda d: d["serve_jobs"][0]["slo"].update(p99_s=1.0),
            lambda d: d["serve_jobs"][0]["service"].update(source="x"),
            lambda d: d["expect"][0].update(tolerance=0.1),
    ):
        doc = _valid_doc()
        mutate(doc)
        problems = validate_scenario(doc)
        assert any("unknown keys" in p for p in problems), mutate


def test_validate_rejects_non_reproducible_seeds():
    for bad in ("time", None, 1.5, True):
        doc = _valid_doc()
        doc["fleet"]["seed"] = bad
        problems = validate_scenario(doc)
        assert any("non-reproducible seeds are rejected" in p
                   for p in problems), bad


def test_validate_rejects_malformed_expects_and_schema():
    doc = _valid_doc()
    doc["expect"][0]["op"] = "~="
    assert any("op must be one of" in p for p in validate_scenario(doc))
    doc = _valid_doc()
    c = doc["expect"][0]
    c["value"] = 1.0  # now has both value and ref
    assert "ref" in c or "value" in c
    doc["expect"][0] = {"metric": "serve/chat/slo_goodput", "op": ">",
                        "value": 0.5, "ref": "baseline:x"}
    assert any("exactly one of value/ref" in p
               for p in validate_scenario(doc))
    doc = _valid_doc()
    del doc["baseline"]
    assert any("ref used without a baseline" in p
               for p in validate_scenario(doc))
    doc = _valid_doc()
    doc["schema"] = "repro.fleet.scenario/v0"
    assert any("schema must be" in p for p in validate_scenario(doc))
    doc = _valid_doc()
    doc["description"] = ""
    assert any("description" in p for p in validate_scenario(doc))


def test_load_scenario_raises_on_invalid(tmp_path):
    p = tmp_path / "bad.json"
    doc = _valid_doc()
    doc["fleet"]["seed"] = "time"
    p.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="non-reproducible"):
        load_scenario(p)


# ------------------------------------------------ determinism properties


@hypothesis.given(
    rate=st.floats(min_value=0.5, max_value=4.0),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    policy=st.sampled_from(["fixed", "auto"]))
@hypothesis.settings(max_examples=8, deadline=None)
def test_serve_same_seed_byte_identical(rate, seed, policy):
    """Same config + same seed => byte-identical request log, ledger
    event stream, and summaries — the open-loop arrival contract."""
    a = _serve_dump(_mixed_sim(seed=seed, rate=rate, policy=policy))
    b = _serve_dump(_mixed_sim(seed=seed, rate=rate, policy=policy))
    assert a == b


@hypothesis.given(
    rate=st.floats(min_value=0.5, max_value=4.0),
    seed=st.integers(min_value=0, max_value=2**31 - 1))
@hypothesis.settings(max_examples=8, deadline=None)
def test_serve_arrivals_invariant_across_policies(rate, seed):
    """The seeded request trace is a property of (seed, job name,
    arrival process) alone: switching the autoscale policy must not
    move a single arrival, so fixed-vs-auto comparisons (the baseline
    arms in the scenario suites) run on the identical workload."""
    fixed = _mixed_sim(seed=seed, rate=rate, policy="fixed")
    auto = _mixed_sim(seed=seed, rate=rate, policy="auto")
    rf, ra = fixed.serve["chat"], auto.serve["chat"]
    assert rf.arrived == ra.arrived
    arr_f = {(rid, turn): t for (rid, turn, t, *_) in rf.request_log}
    arr_a = {(rid, turn): t for (rid, turn, t, *_) in ra.request_log}
    shared = set(arr_f) & set(arr_a)
    assert shared  # both arms finished plenty of requests
    assert all(arr_f[k] == arr_a[k] for k in shared)


@hypothesis.given(
    rate=st.floats(min_value=0.5, max_value=3.0),
    seed=st.integers(min_value=0, max_value=10**6),
    policy=st.sampled_from(["fixed", "auto"]))
@hypothesis.settings(max_examples=6, deadline=None)
def test_serve_grammar_stable_on_mixed_runs(rate, seed, policy):
    """Mixed serve+train runs with real failures stay inside the pinned
    five-kind ledger grammar for every job, at any rate / policy."""
    sim = _mixed_sim(seed=seed, rate=rate, policy=policy,
                     mtbf_hours=2.0, horizon=1800.0)
    assert all(grammar_ok(j.ledger) for j in sim.jobs.values())
    assert all(grammar_ok(rt.ledger) for rt in sim.serve.values())
    rt = sim.serve["chat"]
    # the ledger accounts every settled second exactly once
    summ = rt.slo_summary()
    assert summ["finished"] <= summ["arrived"]
    assert 0.0 <= summ["slo_goodput"] <= 1.0


# ------------------------------------------------- calibration + power


def _synthetic_trace(slope=0.002, base=0.02, steps=8):
    tr = StepTrace(source="serve")
    for _ in range(6):
        tr.record("prefill", 0.0128, tokens=128, cached=0, batch=1)
        for b in (1, 2, 3, 4):
            tr.record("decode", base + slope * (b - 1),
                      batch=b, steps=steps, tokens=b * steps)
    return tr


def test_service_model_from_trace_recovers_affine_law():
    m = service_model_from_trace(_synthetic_trace())
    assert m.chunk_base_s == pytest.approx(0.02, rel=1e-6)
    assert m.chunk_per_slot_s == pytest.approx(0.002, rel=1e-6)
    assert m.chunk_steps == 8
    assert m.prefill_s_per_token == pytest.approx(1e-4, rel=1e-6)
    assert m.source == "serve"
    # constant-batch trace: falls back to the exact mean
    tr = StepTrace(source="serve")
    for _ in range(5):
        tr.record("decode", 0.03, batch=2, steps=4, tokens=8)
    m2 = service_model_from_trace(tr)
    assert m2.chunk_base_s == pytest.approx(0.03)
    assert m2.chunk_per_slot_s == 0.0


def test_serve_calibration_check_passes_and_guards_sample_size():
    cal = serve_calibration_check(_synthetic_trace())
    assert cal["ok"] == 1.0
    assert cal["steady_admissions"] >= 8
    assert cal["rel_err"] <= 0.25
    # the mixed-batch trace replays at ~4% off the single-batch sim
    # operating point; a tightened tolerance must fail the gate
    tight = serve_calibration_check(_synthetic_trace(), tol=0.01)
    assert tight["ok"] == 0.0 and tight["rel_err"] > 0.01
    # a faster engine shows up directly in the measured side
    fast = serve_calibration_check(
        _synthetic_trace(base=0.01, slope=0.001))
    assert fast["measured_chunk_s"] < cal["measured_chunk_s"]
    assert fast["ok"] == 1.0


def test_power_serve_summary_joules_per_token():
    sim = _mixed_sim(seed=3, rate=2.0, policy="fixed", horizon=600.0)
    rt = sim.serve["chat"]
    pm = PowerModel(hwspec.get("tpu_v4"))
    ss = pm.serve_summary(rt.ledger, rt.spec.chips,
                          good_tokens=rt.good_tokens,
                          total_tokens=rt.total_tokens)
    assert ss["energy_j"] > 0
    assert ss["joules_per_token"] > 0
    assert ss["joules_per_good_token"] >= ss["joules_per_token"]
    assert ss["energy_kwh"] == pytest.approx(ss["energy_j"] / 3.6e6)
    empty = pm.serve_summary(rt.ledger, rt.spec.chips,
                             good_tokens=0, total_tokens=0)
    assert empty["joules_per_token"] == float("inf")


# ------------------------------------------------------- arrival model


def test_arrival_process_diurnal_and_burst_envelope():
    ap = ArrivalProcess(rate_rps=2.0, diurnal_amplitude=0.5,
                        diurnal_period_s=1000.0, burst_x=3.0,
                        burst_every_s=500.0, burst_len_s=50.0)
    rates = [ap.rate_at(t) for t in range(0, 1000, 7)]
    assert all(0.0 < r <= ap.peak_rate + 1e-9 for r in rates)
    assert max(rates) > 2.0  # burst/diurnal peak above the base rate
    in_burst, outside = ap.rate_at(510.0), ap.rate_at(400.0)
    assert in_burst > outside


def test_arrival_process_validation():
    with pytest.raises(ValueError):
        ArrivalProcess(rate_rps=0.0)
    with pytest.raises(ValueError):
        ArrivalProcess(rate_rps=1.0, diurnal_amplitude=1.5)
    with pytest.raises(ValueError):
        ArrivalProcess(rate_rps=1.0, burst_x=0.5)
    with pytest.raises(ValueError):
        ServeJobSpec(name="x", chips=64,
                     arrivals=ArrivalProcess(rate_rps=1.0),
                     slo=ServeSLO(), service=ServiceTimeModel(),
                     scale_policy="bananas")
