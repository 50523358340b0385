"""The training harness at smoke size on the CPU: a sound run is
correct; a step that returns its state unchanged, half of each batch
left out, and the bfloat16 control are not."""

from __future__ import annotations

import json

import pytest

from chip_bench.tests.helpers import execute, make_run


def test_sound_run_is_correct(tmp_path):
    res = execute(make_run("train", tmp_path))
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"train_tok_s", "setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_faults_are_not_correct(tmp_path, monkeypatch, fault):
    import jax
    import jax.numpy as jnp
    from chip_bench import program

    real_build = program.build_trainer

    def build(*a, **kw):
        trainer, state, registry = real_build(*a, **kw)
        step = trainer.train_step

        def broken(state, batch):
            if fault == "state_unchanged":
                _, m = step(jax.tree.map(jnp.copy, state), batch)
                return state, m
            h = batch["tokens"].shape[0] // 2
            batch = {k: jnp.concatenate([v[:h], v[:h]])
                     for k, v in batch.items()}
            return step(state, batch)

        trainer.train_step = broken
        return trainer, state, registry

    monkeypatch.setattr(program, "build_trainer", build)
    res = execute(make_run("train", tmp_path))
    assert not res["correct"], res["compared"]


def test_bfloat16_control_is_not_correct(tmp_path):
    """The controls: the reference computed in bfloat16 (or FP8) in the
    program's place fails at least one of the three numbers; so does
    half of each batch left out, planted in the reference."""
    run = make_run("train", tmp_path, calibrate=1)
    from chip_bench import run as entry
    line = json.loads(entry.execute(run, {"per_layer": []}))
    assert all(line["program"][k] <= run.limits[k] for k in run.limits)
    for name in ("control_bfloat16", "control_float8_e4m3fn"):
        assert any(line[name][k] > run.limits[k] for k in run.limits)
    assert any(line["half"][k] > run.limits[k] for k in run.limits)
