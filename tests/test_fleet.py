"""Fleet simulator: event-engine determinism, goodput bounds, scheduler
invariants through reconfigurations, SDC rollback semantics, elastic
re-scale (shrink-on-starvation, grow-back, grammar stability),
roofline-fed step times, checkpoint-write contention + sim-vs-Young/Daly
interval agreement, power/carbon ratios, Chrome-trace export, and the
sim-vs-ResilientTrainer bridge."""

import json

import pytest
import hypothesis
import hypothesis.strategies as st

from repro.core import hwspec
from repro.core.goodput import GoodputLedger, modeled_goodput
from repro.core.sdc import SDCRateModel
from repro.fleet import (GRAMMAR_KINDS, EventEngine, FleetConfig,
                         FleetSimulator, JobSpec, PowerModel,
                         StepTimeModel, TrainWorkload,
                         generation_efficiency_table,
                         generation_step_times, grammar_ok,
                         job_spec_from_roofline,
                         optimal_checkpoint_interval_s,
                         search_checkpoint_interval,
                         sim_checkpoint_interval_sweep,
                         simulate_trainer_plan, sustainability_ratios)


def _ledger_dump(led: GoodputLedger):
    return [(e.kind, round(e.seconds, 9), e.steps) for e in led.events]


# ------------------------------------------------------------ event engine


def test_event_engine_deterministic_order():
    def fill(eng):
        eng.schedule_at(5.0, "a")
        eng.schedule_at(1.0, "b")
        eng.schedule_at(5.0, "c")  # tie with "a": insertion order wins
        eng.schedule_at(3.0, "d", x=1)
        return [(e.time, e.kind) for e in eng.drain_until(10.0)]

    assert fill(EventEngine(0)) == fill(EventEngine(0)) == [
        (1.0, "b"), (3.0, "d"), (5.0, "a"), (5.0, "c")]


def test_event_engine_cancel_and_horizon():
    eng = EventEngine(0)
    ev = eng.schedule_at(2.0, "x")
    eng.schedule_at(4.0, "y")
    eng.schedule_at(20.0, "z")
    eng.cancel(ev)
    got = [e.kind for e in eng.drain_until(10.0)]
    assert got == ["y"]
    assert eng.now == 10.0
    assert eng.peek_time() == 20.0  # beyond-horizon event still queued


def test_event_engine_rejects_past():
    eng = EventEngine(0)
    eng.schedule_at(5.0, "a")
    assert eng.pop().kind == "a"
    with pytest.raises(ValueError):
        eng.schedule_at(1.0, "late")


# -------------------------------------------------- deterministic failure plan


def test_planned_failures_reproduce_trainer_grammar():
    """Hand-derived ResilientTrainer event grammar for ckpt_every=6,
    failures at steps 9 and 14, 18 steps total."""
    led = simulate_trainer_plan(total_steps=18, checkpoint_every=6,
                                failures={9: 0, 14: 1})
    assert led.structure() == [
        ("idle", 0), ("steps", 6), ("idle", 0), ("steps", 3),
        ("detect", 0), ("restore", 0), ("rework", 3),
        ("steps", 3), ("idle", 0), ("steps", 2),
        ("detect", 0), ("restore", 0), ("rework", 2),
        ("steps", 4), ("idle", 0)]
    assert led.effective_steps == 18


def test_sim_determinism_bitwise():
    """Same seed, same config -> identical ledgers, stats and trace."""

    def build():
        cfg = FleetConfig(tpu="ironwood", total_cubes=40,
                          host_mtbf_hours=500.0, repair_hours=2.0,
                          sdc=SDCRateModel(rate_per_chip_hour=2e-5,
                                           screen_interval_s=300.0),
                          seed=7)
        jobs = [JobSpec(name=f"j{i}", chips=512, total_steps=10**9,
                        step_time_s=1.5, checkpoint_every_steps=200)
                for i in range(3)]
        sim = FleetSimulator(cfg, jobs)
        sim.run(86400.0)
        return sim

    a, b = build(), build()
    assert a.stats == b.stats
    for name in a.jobs:
        assert _ledger_dump(a.jobs[name].ledger) == \
            _ledger_dump(b.jobs[name].ledger)
    assert a.trace.chrome_trace() == b.trace.chrome_trace()
    assert a.stats["cube_failures"] > 0  # scenario actually exercised


@hypothesis.given(
    seed=st.integers(min_value=0, max_value=10_000),
    mtbf=st.floats(min_value=50.0, max_value=5000.0),
    njobs=st.integers(min_value=1, max_value=5),
)
@hypothesis.settings(max_examples=15, deadline=None)
def test_goodput_bounds_and_invariants_property(seed, mtbf, njobs):
    """Whatever the failure pattern: every goodput stays in [0, 1], the
    scheduler's no-shared-cube invariant holds through every event
    (checked inside run()), and effective steps never exceed the total."""
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=24,
                      host_mtbf_hours=mtbf, repair_hours=1.0, seed=seed)
    jobs = [JobSpec(name=f"j{i}", chips=256, total_steps=2000,
                    step_time_s=1.0, checkpoint_every_steps=100)
            for i in range(njobs)]
    sim = FleetSimulator(cfg, jobs)
    sim.run(40_000.0)  # check_invariants=True asserts after every event
    for job in sim.jobs.values():
        assert 0.0 <= job.ledger.goodput <= 1.0
        assert job.ledger.effective_steps <= job.spec.total_steps
        if job.state == "done":
            # wall-clock conservation: the ledger partitions exactly the
            # arrival-to-completion span, nothing dropped or doubled
            assert job.ledger.total_seconds == pytest.approx(
                job.completed_at - job.spec.arrival_s)
    fs = sim.fleet_summary()
    assert 0.0 <= fs["min_goodput"] <= 1.0


@pytest.mark.parametrize("policy,seed,mtbf,njobs", [
    ("queue", 8475, 66.0, 3),
    ("shrink", 5027, 177.61362497447726, 1),
])
def test_failure_inside_recovery_window_charges_it_once(policy, seed, mtbf,
                                                        njobs):
    """A second failure that lands while the first one's restore/rework is
    still running cuts that window short: the ledger drops the part that
    never happened, so it still partitions arrival-to-completion."""
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=24,
                      host_mtbf_hours=mtbf, repair_hours=1.0, seed=seed)
    jobs = [JobSpec(name=f"j{i}", chips=256, total_steps=2000,
                    step_time_s=1.0, checkpoint_every_steps=100,
                    scale_policy=policy) for i in range(njobs)]
    sim = FleetSimulator(cfg, jobs)
    sim.run(40_000.0)
    done = [j for j in sim.jobs.values() if j.state == "done"]
    assert done
    for job in done:
        assert job.ledger.total_seconds == pytest.approx(
            job.completed_at - job.spec.arrival_s)
        assert job.ledger.effective_steps == job.spec.total_steps


def test_reconfigs_do_not_starve_with_spares():
    """Ironwood headline: four 2K-chip jobs on 144 cubes ride through
    failures on 16 spares — substitutions happen, nobody starves."""
    cfg = FleetConfig(tpu="ironwood", total_cubes=144,
                      host_mtbf_hours=2000.0, repair_hours=4.0, seed=3)
    jobs = [JobSpec(name=f"job{i}", chips=2048, total_steps=10**9,
                    step_time_s=1.0, checkpoint_every_steps=600)
            for i in range(4)]
    sim = FleetSimulator(cfg, jobs)
    sim.run(3 * 86400.0)
    assert sim.sched.reconfig_count > 0
    assert sim.stats["starvations"] == 0
    assert all(j.state == "running" for j in sim.jobs.values())
    assert sim.fleet_summary()["min_goodput"] > 0.9


def test_fail_host_maps_to_owning_cube():
    """Host-granular failures (the paper's primary hazard) map out the
    whole cube the host serves."""
    from repro.core.ocs import OCSPodScheduler
    sched = OCSPodScheduler(total_cubes=4)
    sched.allocate("j", 128)  # cubes 0, 1
    cube, impacted = sched.fail_host(20)  # 16 hosts/cube -> cube 1
    assert (cube, impacted) == (1, "j")
    cube, impacted = sched.fail_host(3 * 16 + 5)  # idle cube 3
    assert (cube, impacted) == (3, None)
    with pytest.raises(ValueError):
        sched.fail_host(4 * 16)


def test_starvation_queues_and_resumes():
    """With zero spares, the first failure starves the job; the repair
    re-admits it with a restore + rework charge."""
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=2,
                      host_mtbf_hours=None, repair_hours=1.0)
    job = JobSpec(name="j", chips=128, total_steps=10_000, step_time_s=1.0,
                  checkpoint_every_steps=100, failure_steps=((250, 0),))
    sim = FleetSimulator(cfg, [job])
    sim.run(20_000.0)
    jr = sim.jobs["j"]
    assert sim.stats["starvations"] == 1
    assert jr.state == "done"
    kinds = [k for k, _ in jr.ledger.structure()]
    assert "detect" in kinds and "restore" in kinds and "idle" in kinds
    t = jr.ledger.totals()
    # queued from the end of detection until the repair: no overlap
    assert t["idle"] == pytest.approx(3600.0 - sim.cfg.detect_s)
    assert t["rework"] == pytest.approx(50.0)  # 250 - ckpt@200
    # wall-clock conservation: the ledger partitions exactly the span
    # from arrival to completion, with nothing double-charged
    assert jr.ledger.total_seconds == pytest.approx(jr.completed_at)


def test_sdc_starvation_charges_restore_once():
    """Regression: an SDC rollback that starves (no spares) must charge
    detect at the event and restore+rework exactly once, at
    re-admission — and the ledger must still partition wall time."""
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=2, host_mtbf_hours=None,
                      repair_hours=0.5,
                      sdc=SDCRateModel(rate_per_chip_hour=0.05,
                                       screen_interval_s=300.0,
                                       screen_coverage=1.0),
                      seed=4)
    job = JobSpec(name="j", chips=128, total_steps=30_000, step_time_s=1.0,
                  checkpoint_every_steps=100)
    sim = FleetSimulator(cfg, [job])
    sim.run(200_000.0)
    jr = sim.jobs["j"]
    assert sim.stats["sdc_detections"] >= 1
    assert sim.stats["starvations"] == sim.stats["sdc_detections"]
    restores = [e for e in jr.ledger.events if e.kind == "restore"]
    assert len(restores) == sim.stats["sdc_detections"]
    if jr.state == "done":
        assert jr.ledger.total_seconds == pytest.approx(jr.completed_at)


def test_sdc_rolls_back_past_poisoned_checkpoints():
    """A corruption detected late must rework back to the last snapshot
    BEFORE the corruption, not merely the last snapshot."""
    cfg = FleetConfig(tpu="ironwood", total_cubes=4, host_mtbf_hours=None,
                      sdc=SDCRateModel(rate_per_chip_hour=0.5,
                                       screen_interval_s=400.0,
                                       screen_coverage=0.5),
                      seed=11)
    job = JobSpec(name="j", chips=128, total_steps=100_000,
                  step_time_s=1.0, checkpoint_every_steps=100)
    sim = FleetSimulator(cfg, [job])
    sim.run(50_000.0)
    assert sim.stats["sdc_detections"] >= 1
    jr = sim.jobs["j"]
    reworks = [e for e in jr.ledger.events if e.kind == "rework"]
    assert reworks, "sdc detection must charge rework"
    # at least one rollback crossed a checkpoint boundary (rework longer
    # than one full interval means a later snapshot was poisoned)
    assert any(e.steps > 100 for e in reworks)


def test_contiguous_pod_fares_worse_than_ocs():
    """Same fleet, same seed: pre-OCS (contiguous, no substitution)
    scheduling loses more goodput than the OCS pod — the paper's
    resilience argument, measured."""

    def run(contiguous):
        cfg = FleetConfig(tpu="tpu_v4", total_cubes=27,
                          host_mtbf_hours=300.0, repair_hours=2.0,
                          contiguous=contiguous, seed=5)
        jobs = [JobSpec(name=f"j{i}", chips=256, total_steps=10**9,
                        step_time_s=1.0, checkpoint_every_steps=300)
                for i in range(4)]
        sim = FleetSimulator(cfg, jobs)
        sim.run(2 * 86400.0)
        return sim

    ocs, contig = run(False), run(True)
    assert ocs.fleet_summary()["mean_goodput"] > \
        contig.fleet_summary()["mean_goodput"]
    assert contig.stats["starvations"] > 0  # no substitution pre-OCS
    assert ocs.sched.reconfig_count > 0


def test_sdc_survives_failstop_restore_from_poisoned_ckpt():
    """Regression: a fail-stop failure between a corruption and its
    detection restores from a snapshot that may postdate the corruption;
    the corruption then survives the restore and its detection must be
    re-armed, not silently dropped."""
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=8, host_mtbf_hours=None,
                      detect_s=1.0, restore_s=1.0, reconfig_s=0.0,
                      sdc=SDCRateModel(rate_per_chip_hour=3600.0 / 64,
                                       screen_interval_s=5000.0,
                                       screen_coverage=1.0),
                      seed=0)
    # corruption lands within the first ~second of stepping; the planned
    # fail-stop at step 300 restores from ckpt@200 (poisoned: corruption
    # happened before it); detection would only fire at ~5000s
    job = JobSpec(name="j", chips=64, total_steps=20_000, step_time_s=1.0,
                  checkpoint_every_steps=200,
                  failure_steps=((300, -1),))
    sim = FleetSimulator(cfg, [job])
    sim.run(100_000.0)
    jr = sim.jobs["j"]
    assert sim.stats["sdc_corruptions"] >= 1
    assert sim.stats["sdc_detections"] >= 1, \
        "corruption must still be detected after the fail-stop restore"
    assert jr.state == "done"
    assert jr.ledger.total_seconds == pytest.approx(jr.completed_at)


def test_planned_failure_on_foreign_cube_interrupts_owner():
    """Regression: a plan naming another job's cube must fail the real
    owner too, not leave it running on a dead cube."""
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=8, host_mtbf_hours=None)
    # j0 owns cubes {0,1}, j1 owns {2,3}; j0's plan kills cube 2
    jobs = [JobSpec(name="j0", chips=128, total_steps=1000,
                    step_time_s=1.0, checkpoint_every_steps=100,
                    failure_steps=((500, 2),)),
            JobSpec(name="j1", chips=128, total_steps=1000,
                    step_time_s=1.0, checkpoint_every_steps=100)]
    sim = FleetSimulator(cfg, jobs)
    sim.run(10_000.0)
    assert sim.jobs["j0"].state == "done"
    assert sim.jobs["j1"].state == "done"
    # both jobs observed the failure: the owner via impact, the planner
    # via driver semantics
    for name in ("j0", "j1"):
        kinds = [k for k, _ in sim.jobs[name].ledger.structure()]
        assert "detect" in kinds and "restore" in kinds
    assert sim.sched.reconfig_count == 1  # only the owner resubstitutes
    assert 2 in sim.sched.failed_cubes  # repair (4 h) is past the horizon


def test_bridge_horizon_covers_dense_failure_plans():
    """Regression: 3 failures with checkpoint_every > total_steps rework
    nearly the whole history each time; the sim horizon must cover it."""
    led = simulate_trainer_plan(total_steps=18, checkpoint_every=100,
                                failures={15: 0, 16: 1, 17: 2})
    assert led.effective_steps == 18
    rework = sum(s for k, s in led.structure() if k == "rework")
    assert rework == 15 + 16 + 17  # restore always from the bootstrap


# ------------------------------------------------------ elastic re-scale


def _elastic_scenario(policy):
    """j0 (3 cubes) loses a cube at step 1000 on a spare-less pod; the
    2 h repair either re-admits it (queue) or grows it back (shrink)."""
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=4, host_mtbf_hours=None,
                      repair_hours=2.0)
    jobs = [JobSpec(name="j0", chips=3 * 64, total_steps=10**9,
                    step_time_s=1.0, checkpoint_every_steps=300,
                    scale_policy=policy,
                    min_cubes=1 if policy == "shrink" else 0,
                    failure_steps=((1000, -1),)),
            JobSpec(name="j1", chips=64, total_steps=10**9,
                    step_time_s=1.0, checkpoint_every_steps=300)]
    sim = FleetSimulator(cfg, jobs)
    sim.run(4 * 3600.0)
    return sim


def test_elastic_shrink_beats_queue_same_trace():
    """The paper's "reschedule at smaller scale" arm: on the identical
    deterministic failure trace, shrinking wins on goodput AND steps."""
    queue, shrink = _elastic_scenario("queue"), _elastic_scenario("shrink")
    qj, sj = queue.jobs["j0"], shrink.jobs["j0"]
    assert sj.ledger.goodput > qj.ledger.goodput
    assert sj.base_step > qj.base_step
    assert queue.stats["starvations"] == 1 and queue.stats["rescales"] == 0
    assert shrink.stats["starvations"] == 0 and shrink.stats["rescales"] == 1


def test_grow_back_after_repair():
    """The shrunken job returns to full size when the repair frees the
    cube: graceful snapshot (exactly one rework event — the shrink's),
    full-speed stepping afterwards, wall clock still partitioned."""
    sim = _elastic_scenario("shrink")
    j0 = sim.jobs["j0"]
    assert j0.rescales == 1 and j0.grow_backs == 1
    assert j0.cubes == j0.spec.full_cubes == 3
    assert j0.step_time_s == pytest.approx(1.0)  # back to full speed
    reworks = [e for e in j0.ledger.events if e.kind == "rework"]
    assert len(reworks) == 1  # shrink reworks; grow-back must not
    # shrink ran 2 of 3 cubes: rework priced at the shrunken step time
    assert reworks[0].seconds == pytest.approx(reworks[0].steps * 1.5)
    for jr in sim.jobs.values():  # nothing dropped or double-charged
        assert jr.ledger.total_seconds == pytest.approx(4 * 3600.0)


def test_elastic_rescale_deterministic():
    """Same seed, stochastic failures, shrink policy -> bitwise-identical
    ledgers, stats and trace (the event-sequence determinism pin)."""

    def build():
        cfg = FleetConfig(tpu="tpu_v4", total_cubes=10,
                          host_mtbf_hours=100.0, repair_hours=6.0, seed=13)
        jobs = [JobSpec(name=f"j{i}", chips=3 * 64, total_steps=10**9,
                        step_time_s=1.0, checkpoint_every_steps=200,
                        scale_policy="shrink", min_cubes=1)
                for i in range(3)]
        sim = FleetSimulator(cfg, jobs)
        sim.run(2 * 86400.0)
        return sim

    a, b = build(), build()
    assert a.stats == b.stats
    for name in a.jobs:
        assert _ledger_dump(a.jobs[name].ledger) == \
            _ledger_dump(b.jobs[name].ledger)
    assert a.trace.chrome_trace() == b.trace.chrome_trace()
    assert a.stats["rescales"] > 0  # the elastic arm actually fired


def test_elastic_ledger_grammar_stable():
    """Bridge contract: re-scale events never invent ledger vocabulary —
    every event of an elastic run speaks the pinned five kinds."""
    sim = _elastic_scenario("shrink")
    assert set(GRAMMAR_KINDS) == {"steps", "rework", "detect", "restore",
                                  "idle"}
    for jr in sim.jobs.values():
        assert grammar_ok(jr.ledger)
        assert all(k in GRAMMAR_KINDS for k, _ in jr.ledger.structure())


def test_elastic_admission_shrinks_and_respects_min_cubes():
    """A job arriving into a too-small pod admits at the largest
    schedulable slice >= min_cubes; below the floor it queues."""
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=2, host_mtbf_hours=None)
    ok = JobSpec(name="fits", chips=4 * 64, total_steps=1000,
                 step_time_s=1.0, scale_policy="shrink", min_cubes=2)
    sim = FleetSimulator(cfg, [ok])
    sim.run(10.0)
    jr = sim.jobs["fits"]
    assert jr.state == "running" and jr.cubes == 2
    assert jr.step_time_s == pytest.approx(2.0)  # 4 cubes' work on 2
    assert jr.rescales == 1

    floor = JobSpec(name="floor", chips=4 * 64, total_steps=1000,
                    step_time_s=1.0, scale_policy="shrink", min_cubes=3)
    sim = FleetSimulator(cfg, [floor])
    sim.run(10.0)
    assert sim.jobs["floor"].state == "queued"


def test_scale_policy_validation():
    with pytest.raises(ValueError):
        JobSpec(name="j", chips=64, total_steps=10, scale_policy="grow")
    with pytest.raises(ValueError):
        JobSpec(name="j", chips=64, total_steps=10, min_cubes=5)  # > full
    j = JobSpec(name="j", chips=2 * 64, total_steps=10,
                scale_policy="shrink")
    assert j.min_cubes == 1  # shrink defaults the floor to one cube


def test_ocs_grow_and_max_slice_hooks():
    from repro.core.ocs import OCSPodScheduler
    sched = OCSPodScheduler(total_cubes=6)
    sched.allocate("j", 2 * 64)
    assert sched.max_slice_cubes(10) == 4  # capped by idle cubes
    grown = sched.grow("j", 2)
    assert grown is not None and len(grown.cubes) == 4
    assert sched.spare_cubes() == 2
    sched.check_invariants()
    assert sched.grow("j", 3) is None  # only 2 idle left
    with pytest.raises(KeyError):
        sched.grow("nope", 1)
    # pre-OCS pods cannot stitch new cubes into a block
    contig = OCSPodScheduler(total_cubes=8, contiguous=True)
    contig.allocate("j", 2 * 64)
    assert contig.grow("j", 1) is None
    assert contig.max_slice_cubes(8) <= 6


# ------------------------------------------------- roofline-fed step times


def test_step_time_model_tracks_table1_anchors():
    """Per-generation validation: the same workload gets monotonically
    faster v2 -> Ironwood, and the total speedup lands between the
    Table-1 HBM-bandwidth and peak-bf16 ratios (the step is a mix of
    memory, compute and collective terms, so it can't beat peak)."""
    wl = TrainWorkload(n_params=70e9, tokens_per_step=4096 * 4096)
    times = generation_step_times(wl, cubes=8)
    names = [s.name for s in hwspec.GENERATIONS]
    vals = [times[n] for n in names]
    assert vals == sorted(vals, reverse=True)
    ss = hwspec.scaling_summary()
    speedup = times["tpu_v2"] / times["ironwood"]
    assert ss["hbm_bandwidth_x"] <= speedup <= ss["node_peak_bf16_x"] * 1.02


def test_step_time_model_scaling_curve():
    """The elastic arm's curve: more cubes never slower (up to the ring
    factor), ideal-linear while compute-bound, flattening into the
    collective floor — so shrinking a big slice costs less than linear."""
    wl = TrainWorkload(n_params=70e9, tokens_per_step=4096 * 4096)
    m = StepTimeModel("tpu_v4", wl)
    sizes = (4, 8, 16, 32, 64, 128, 256)
    curve = [m(c) for c in sizes]
    assert all(a >= b * (1 - 1e-3) for a, b in zip(curve, curve[1:]))
    assert curve[0] / curve[1] == pytest.approx(2.0, rel=0.01)  # linear
    assert curve[-2] / curve[-1] < 1.5  # collective floor
    assert m.report(256).bound == "collective"
    assert m.report(4).bound == "compute"


def test_job_spec_from_roofline_drives_elastic_sim():
    """A roofline-priced JobSpec: full-size step time equals the model's,
    shrinking follows the curve inside the simulator."""
    wl = TrainWorkload(n_params=8e9, tokens_per_step=1024 * 1024)
    spec = job_spec_from_roofline(
        "r", "tpu_v4", wl, chips=3 * 64, total_steps=10**9,
        checkpoint_every_steps=500, scale_policy="shrink", min_cubes=1)
    m = StepTimeModel("tpu_v4", wl)
    assert spec.step_time_s == pytest.approx(m(3))
    assert spec.step_time_for(2) == pytest.approx(m(2))
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=4, host_mtbf_hours=None,
                      repair_hours=50.0)  # no repair inside the horizon
    spec = JobSpec(**{**spec.__dict__, "failure_steps": ((100, -1),)})
    sim = FleetSimulator(cfg, [spec, JobSpec(
        name="filler", chips=64, total_steps=10**9, step_time_s=1.0)])
    sim.run(m(3) * 100 + 40_000.0)
    jr = sim.jobs["r"]
    assert jr.cubes == 2 and jr.rescales == 1
    assert jr.step_time_s == pytest.approx(m(2))


# ------------------------------------- checkpoint writes: stalls, contention


def test_sync_ckpt_write_stalls_and_interval_tradeoff():
    """Synchronous writes charge idle stalls per snapshot; halving the
    interval doubles the write overhead (the Young/Daly tension the
    sweep optimizes)."""

    def goodput(every):
        cfg = FleetConfig(tpu="tpu_v4", total_cubes=2,
                          host_mtbf_hours=None, ckpt_write_s=30.0)
        job = JobSpec(name="j", chips=64, total_steps=10**9,
                      step_time_s=1.0, checkpoint_every_steps=every)
        sim = FleetSimulator(cfg, [job])
        sim.run(40_000.0)
        jr = sim.jobs["j"]
        stalls = [e for e in jr.ledger.events
                  if e.kind == "idle" and e.note.startswith("ckpt write")]
        assert stalls and all(e.seconds == pytest.approx(30.0)
                              for e in stalls)
        # stalls are booked at write start (same convention as
        # detect/restore), so a write straddling the horizon may overhang
        # it by at most one stall
        assert 40_000.0 <= jr.ledger.total_seconds <= 40_000.0 + 30.0
        return jr.ledger.goodput

    # failure-free: longer intervals strictly win (only write cost)
    assert goodput(200) < goodput(400) < goodput(800)


def test_ckpt_write_contention_multiplies_stall():
    """Two jobs on the same cadence: the second write to start pays the
    shared-bandwidth factor (2x) at the first collision."""
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=4, host_mtbf_hours=None,
                      ckpt_write_s=20.0)
    jobs = [JobSpec(name=f"j{i}", chips=64, total_steps=10**9,
                    step_time_s=1.0, checkpoint_every_steps=100)
            for i in range(2)]
    sim = FleetSimulator(cfg, jobs)
    sim.run(150.0)
    stalls = {n: [e.seconds for e in j.ledger.events
                  if e.kind == "idle" and e.note.startswith("ckpt write")]
              for n, j in sim.jobs.items()}
    assert stalls["j0"] == [pytest.approx(20.0)]
    assert stalls["j1"] == [pytest.approx(40.0)]  # started mid-j0-write


def test_failure_mid_write_rolls_back_to_previous_snapshot():
    """Durability: a snapshot only counts once its write completes. j1's
    planned failure kills j0's cube at t=120, mid j0's write of the
    step-100 snapshot -> j0 reworks all 100 steps from the bootstrap."""
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=3, host_mtbf_hours=None,
                      detect_s=1.0, restore_s=1.0, reconfig_s=0.0,
                      ckpt_write_s=50.0)
    jobs = [JobSpec(name="j0", chips=64, total_steps=10**9,
                    step_time_s=1.0, checkpoint_every_steps=100),
            JobSpec(name="j1", chips=64, total_steps=10**9,
                    step_time_s=1.0, checkpoint_every_steps=10**8,
                    failure_steps=((120, 0),))]  # cube 0 is j0's
    sim = FleetSimulator(cfg, jobs)
    sim.run(500.0)
    j0 = sim.jobs["j0"]
    reworks = [e for e in j0.ledger.events if e.kind == "rework"]
    assert reworks and reworks[0].steps == 100  # not 0: write was lost
    # control: the same failure *after* the write completes reworks only
    # the steps past the (now durable) snapshot
    jobs[1] = JobSpec(name="j1", chips=64, total_steps=10**9,
                      step_time_s=1.0, checkpoint_every_steps=10**8,
                      failure_steps=((170, 0),))
    sim = FleetSimulator(cfg, jobs)
    sim.run(500.0)
    j0 = sim.jobs["j0"]
    reworks = [e for e in j0.ledger.events if e.kind == "rework"]
    assert reworks and 0 < reworks[0].steps <= 30


def test_aborted_write_stops_contending():
    """Regression: a write voided by a failure must release the shared
    filer — a later writer pays the uncontended stall, not 2x."""
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=4, host_mtbf_hours=None,
                      detect_s=1.0, restore_s=1.0, reconfig_s=0.0,
                      ckpt_write_s=50.0)
    jobs = [JobSpec(name="j0", chips=64, total_steps=10**9,
                    step_time_s=1.0, checkpoint_every_steps=100),
            JobSpec(name="j1", chips=64, total_steps=10**9,
                    step_time_s=1.0, checkpoint_every_steps=10**8,
                    failure_steps=((120, 0),)),  # kills j0 mid-write
            JobSpec(name="j2", chips=64, total_steps=10**9,
                    step_time_s=1.0, checkpoint_every_steps=130)]
    sim = FleetSimulator(cfg, jobs)
    sim.run(140.0)
    # j0's write (100..150) was aborted at t=120; j2's write at t=130
    # must see an idle filer
    stalls = [e.seconds for e in sim.jobs["j2"].ledger.events
              if e.kind == "idle" and e.note.startswith("ckpt write")]
    assert stalls == [pytest.approx(50.0)]


def test_pre_grow_snapshot_contends_and_is_durable_on_completion():
    """The grow-back snapshot is a synchronous write like any other:
    with ckpt_write_s set it stalls the job and only becomes durable at
    completion (ckpt_write_end is armed, last_ckpt_step is not yet)."""
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=3, host_mtbf_hours=None,
                      repair_hours=1.0, detect_s=1.0, restore_s=1.0,
                      reconfig_s=0.0, ckpt_write_s=40.0)
    job = JobSpec(name="j", chips=3 * 64, total_steps=10**9,
                  step_time_s=1.0, checkpoint_every_steps=10**8,
                  scale_policy="shrink", min_cubes=1,
                  failure_steps=((500, -1),))
    sim = FleetSimulator(cfg, [job])
    sim.run(6000.0)  # repair (and grow-back) lands at t=4100
    jr = sim.jobs["j"]
    assert jr.rescales == 1 and jr.grow_backs == 1
    pre_grow = [e for e in jr.ledger.events
                if e.kind == "idle" and "(pre-grow)" in e.note]
    assert len(pre_grow) == 1
    assert pre_grow[0].seconds >= 40.0  # write stall (+ partial step)
    # the snapshot settled after completion: rollback point advanced
    assert jr.last_ckpt_step > 0 or jr.ckpt_write_end is not None


def test_sim_interval_optimum_matches_model_search():
    """The acceptance pin for layer 3: the simulator's optimal
    checkpoint interval lands within one grid bucket of the
    closed-form ``search_checkpoint_interval`` family optimum."""
    out = sim_checkpoint_interval_sweep(points=7, mean_failures=20)
    assert out["agree_within_one_bucket"], out
    # and the curve is a real hump: the optimum beats both ends
    best = out["sim_goodput"][out["sim_best_index"]]
    assert best > out["sim_goodput"][0]
    assert best > out["sim_goodput"][-1]


# --------------------------------------------------- incremental deployment


def test_incremental_install_admits_jobs_as_cubes_land():
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=8, host_mtbf_hours=None,
                      install_schedule=((0.0, 4), (1000.0, 8)))
    jobs = [JobSpec(name=f"j{i}", chips=4 * 64, total_steps=10**9,
                    step_time_s=1.0, checkpoint_every_steps=500)
            for i in range(2)]
    sim = FleetSimulator(cfg, jobs)
    sim.run(2000.0)
    assert sim.jobs["j0"].first_admitted_at == pytest.approx(0.0)
    assert sim.jobs["j1"].first_admitted_at == pytest.approx(1000.0)
    with pytest.raises(ValueError):
        FleetConfig(install_schedule=((0.0, 4), (10.0, 2)))  # shrinking
    with pytest.raises(ValueError):
        FleetConfig(total_cubes=4, install_schedule=((0.0, 8),))


# ------------------------------------------------------- checkpoint policy


def test_checkpoint_interval_search_matches_young_daly():
    mtbf_h, write_s = 6.0, 30.0
    yd = optimal_checkpoint_interval_s(mtbf_h * 3600.0, write_s)
    best_t, best_g = search_checkpoint_interval(
        mtbf_hours=mtbf_h, detect_s=0.0, restore_s=0.0,
        checkpoint_write_s=write_s)
    assert best_t == pytest.approx(yd, rel=0.15)
    assert 0.0 < best_g < 1.0
    # the searched optimum beats a clearly-off interval
    off = modeled_goodput(mtbf_hours=mtbf_h, detect_s=0.0, restore_s=0.0,
                          checkpoint_interval_s=yd * 20,
                          checkpoint_write_s=write_s)
    assert best_g > off


# ------------------------------------------------------------ power/carbon


def test_sustainability_ratio_matches_paper():
    r = sustainability_ratios()
    # anchored-TDP derivation must land on the paper's ~29.3x perf/Watt
    assert r["joules_per_flop_improvement_x"] == \
        pytest.approx(r["paper_perf_per_watt_x"], rel=0.02)
    assert r["co2e_per_flop_improvement_x"] == \
        r["joules_per_flop_improvement_x"]
    table = generation_efficiency_table()
    names = [s.name for s in hwspec.GENERATIONS]
    vals = [table[n] for n in names]
    assert vals == sorted(vals, reverse=True), \
        "J/FLOP must improve monotonically v2 -> Ironwood"


def test_power_model_integrates_ledger():
    led = GoodputLedger()
    led.record_steps(3600.0, steps=1800)
    led.record_restore(3600.0)
    pm = PowerModel(hwspec.get("ironwood"), mfu=0.5,
                    idle_power_fraction=0.2)
    s = pm.job_summary(led, chips=256)
    chip_w = hwspec.chip_tdp_watts(hwspec.get("ironwood"))
    assert s["energy_j"] == pytest.approx(
        256 * chip_w * 3600.0 * (1.0 + 0.2))
    assert s["effective_eflops"] == pytest.approx(
        3600.0 * 256 * hwspec.get("ironwood").peak_tflops * 1e12 * 0.5
        / 1e18)
    assert s["gco2e_total"] > s["gco2e_operational"] > 0.0


def test_tdp_anchor_reproduces_relative_row():
    v2 = hwspec.pod_tdp_watts(hwspec.TPU_V2)
    iw = hwspec.pod_tdp_watts(hwspec.IRONWOOD)
    assert v2 == pytest.approx(256 * 280.0)
    assert iw / v2 == pytest.approx(hwspec.IRONWOOD.rel_pod_tdp)
    assert hwspec.pod_tdp_watts(hwspec.TPU_V5E) is None


# ------------------------------------------------------------------- trace


def test_chrome_trace_export(tmp_path):
    cfg = FleetConfig(tpu="tpu_v4", total_cubes=8, host_mtbf_hours=100.0,
                      seed=2)
    sim = FleetSimulator(cfg, [JobSpec(name="j", chips=256,
                                       total_steps=5000, step_time_s=1.0,
                                       checkpoint_every_steps=500)])
    sim.run(20_000.0)
    path = tmp_path / "trace.json"
    sim.trace.write(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert all({"ph", "pid", "name"} <= set(e) for e in evs)
    phases = {e["name"] for e in evs if e["ph"] == "X"}
    assert "train" in phases
    insts = {e["name"] for e in evs if e["ph"] == "i"}
    assert {"cube_fail", "ocs_reconfig"} & insts
    # X events carry microsecond ts/dur
    xs = [e for e in evs if e["ph"] == "X"]
    assert all(e["dur"] > 0 for e in xs)


# ------------------------------------------------------------------ bridge


def test_bridge_sim_matches_resilient_trainer():
    """The acceptance pin: a real ResilientTrainer run and the simulator,
    driven by the same failure plan, produce the same goodput-ledger
    structure event-for-event."""
    from repro.fleet import run_bridge
    out = run_bridge(steps=18, checkpoint_every=6, failures={9: 0, 14: 1})
    assert out["match"], (out["real_structure"], out["sim_structure"])
    assert out["effective_steps"] == 18
    assert out["replay_summary"]["replayed_steps"] == 5  # 3 + 2
    assert 0.0 < out["sim_goodput"] <= 1.0
    assert 0.0 < out["real_goodput"] <= 1.0
