"""The training reading on the CPU: the program's counters over the
window, and the room a new configuration needs. A stand-in reference
module, found by its ``model_type`` like any other, counts its training
work from a counter that a stand-in step increments; its model FLOPs
and the counter's change reach the readers with no edit to the
harness."""

from __future__ import annotations

import sys
import types

import pytest

from chip_bench import common, program, train
from chip_bench import trace as tr
from chip_bench.tests.helpers import make_run

ROWS_PER_STEP = 48  # the stand-in's "rows routed to the held experts"
FLOPS_PER_ROW = 7.5


class StandInTrainer:
    """``trainer.run`` as ``train.window`` drives it: each step counts
    its routed rows into the registry, as a program's step would."""

    def __init__(self, registry):
        self.rows = registry.counter("standin_routed_rows")
        self.records = []

    def run(self, state, num_steps):
        while state["step"] < num_steps:
            self.rows.inc(ROWS_PER_STEP)
            state = {"step": state["step"] + 1}
            self.records.append(types.SimpleNamespace(duration_s=0.01))
        return state, None, []


def standin_reference():
    mod = types.ModuleType("chip_bench.reference.standin")
    mod.dims = lambda conf: {"L": conf["num_hidden_layers"]}
    mod.train_flops = (lambda conf, seq, tokens, counters:
                       FLOPS_PER_ROW * counters["standin_routed_rows"])
    return mod


def test_counter_deltas_over_the_window(tmp_path):
    from repro.obs.metrics import MetricsRegistry
    registry = MetricsRegistry()
    registry.counter("before").inc(5)
    trainer = StandInTrainer(registry)
    run = make_run("train", tmp_path, seconds=0.05)
    _, win = train.window(run, trainer, {"step": train.CHECK_STEPS},
                          registry)
    assert win["steps"] == len(trainer.records) >= 1
    assert win["counters0"]["before"] == win["counters"]["before"] == 5
    assert win["counters"]["standin_routed_rows"] == \
        ROWS_PER_STEP * win["steps"]
    # histograms are not counters
    assert "train_step_s" not in program.counters(registry)


def test_a_new_configuration_brings_its_own_work(tmp_path, monkeypatch):
    from repro.obs.metrics import MetricsRegistry
    monkeypatch.setitem(sys.modules, "chip_bench.reference.standin",
                        standin_reference())
    registry = MetricsRegistry()
    trainer = StandInTrainer(registry)
    run = make_run("train", tmp_path, seconds=0.05)
    run.conf = {"model_type": "standin", "num_hidden_layers": 6}
    registry.counter("standin_routed_rows").inc(1000)  # set-up's steps
    _, win = train.window(run, trainer, {"step": train.CHECK_STEPS},
                          registry)
    summary = tr.Summary(window_s=1.0, busy_s=0.9, programs={}, ops={},
                         gaps=[], spans={"train.batch": [0.002, 4]})
    r = train.reading(run, summary, win,
                      {"train.ckpt": [23.6, 1]})
    rows = ROWS_PER_STEP * win["steps"]
    assert r["counters_window"]["standin_routed_rows"] == rows
    assert r["counters"]["standin_routed_rows"] == rows + 1000
    assert r["model_flops"] == FLOPS_PER_ROW * rows
    assert r["dims"] == {"L": 6}
    assert r["spans"] == {"train.batch": [0.002, 4]}
    assert r["setup_spans"]["train.ckpt"][0] == pytest.approx(23.6)
    # the cell's readers read it; one that finds nothing returns nothing
    names = ["mfu.train", "flash_train_roofline", "ckpt_stall_s",
             "input_wait_ms"]
    bench = {"per_layer": [{"name": n, "unit": "-"} for n in names]}
    got = {n: read(r) for n, (read, _) in
           common.per_layer_readers(bench, run.name).items()}
    assert got == {"mfu.train": pytest.approx(100 * r["model_flops"] / 1e12),
                   "flash_train_roofline": None,
                   "ckpt_stall_s": pytest.approx(23.6),
                   "input_wait_ms": pytest.approx(2.0 / win["steps"])}
