"""The serving harness at smoke size on the CPU: a sound run is correct;
a token altered where the engine produces it, a request left
unfinished, and the bfloat16 control are not."""

from __future__ import annotations

import json

import pytest

from chip_bench.tests.helpers import execute, make_run


def test_sound_run_is_correct(tmp_path):
    res = execute(make_run("serve", tmp_path))
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"gen_tok_s", "ttft_p95_s", "tpot_p95_s",
                                   "setup_s"}


@pytest.mark.parametrize("fault", ["altered_token", "dropped_request"])
def test_faults_are_not_correct(tmp_path, monkeypatch, fault):
    """``altered_token``: every decode chunk emits each live slot's first
    token of the chunk plus one, while the model carries on from the
    true token. ``dropped_request``: a round's first request never comes
    back."""
    import jax.numpy as jnp
    from repro.serve.engine import PAD_TOKEN, ServeEngine
    real_build, real_run = ServeEngine._build_jitted, ServeEngine.run

    def build(self):
        real_build(self)
        chunk = self._run_chunk

        def altered(*args):
            *rest, toks = chunk(*args)
            bad = jnp.where(toks[:, 0] == PAD_TOKEN, PAD_TOKEN,
                            (toks[:, 0] + 1) % self.cfg.vocab_size)
            return (*rest, toks.at[:, 0].set(bad))

        self._run_chunk = altered

    def dropped(self, params, requests, **kw):
        out = real_run(self, params, requests, **kw)
        if requests and requests[0].rid < 10 ** 9:
            out.pop(requests[0].rid, None)
        return out

    if fault == "altered_token":
        monkeypatch.setattr(ServeEngine, "_build_jitted", build)
    else:
        monkeypatch.setattr(ServeEngine, "run", dropped)
    res = execute(make_run("serve", tmp_path))
    assert not res["correct"], res["compared"]


def test_bfloat16_control_is_not_correct(tmp_path, capsys):
    """The controls: at each served position, the token a bfloat16 (or
    FP8) reference puts first, read under the float32 reference. At
    smoke size each flips a near-tie on this seed, which the program
    (float32) never does; so does each request's first decoded token
    altered."""
    run = make_run("serve", tmp_path, calibrate=1)
    from chip_bench import run as entry
    line = json.loads(entry.execute(run, {"per_layer": []}))
    limits = run.limits
    assert all(line[k] <= limits[k] for k in ("max_logit_gap",
                                               "mean_logit_gap"))
    for name in ("control_bfloat16", "control_float8_e4m3fn",
                 "fault_altered"):
        assert any(line[name][k] > limits[k] for k in ("max_logit_gap",
                                                        "mean_logit_gap"))
