"""chip_smoke.py off the chip: it refuses to run without a TPU (or without
the repository next to it), and its serve, train and comparison logic
holds at smoke size on the CPU, with the Pallas kernels interpreted."""

import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.registry import get_smoke
from repro.models import api
from repro.models.params import init_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu(tmp_path):
    proc = _run(SCRIPT, tmp_path)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_refuses_without_repository(tmp_path):
    alone = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run(str(alone), tmp_path)
    assert proc.returncode != 0
    assert "no repository" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_serve_phase_kernels_match_reference(smoke):
    """Interpreted Pallas engine == jnp engine, token for token."""
    cfg = get_smoke("qwen2_0_5b")
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    serve = dict(n_requests=3, prompt_lo=5, prompt_hi=20, max_new=6,
                 max_batch=2, chunk=4, page_size=8, prefill_chunk=8)
    got, prompts = smoke.phase_serve(smoke.CompileClock(), cfg, params, 0,
                                     impl="pallas_interpret", serve=serve)
    assert sorted(got) == sorted(prompts) == [0, 1, 2]
    assert all(len(v) == 6 for v in got.values())


def test_train_phase_replays_exactly(smoke, tmp_path):
    smoke.phase_train(smoke.CompileClock(), get_smoke("qwen2_0_5b"), 0,
                      tmp_path,
                      train=dict(batch=2, seq=16, steps=4, fail_at=3))
    assert not (tmp_path / "ckpt").exists()


@pytest.mark.parametrize("gap,ok", [(1e-4, True), (0.5, False)])
def test_compare_streams_accepts_only_near_ties(smoke, gap, ok):
    want = {0: np.array([5, 6, 7]), 1: np.array([1, 2, 3])}
    got = {0: np.array([5, 6, 9]), 1: np.array([1, 2, 3])}
    logits = np.zeros(16)
    logits[7], logits[9] = 1.0, 1.0 - gap

    def logits_fn(tokens):
        assert list(tokens) == [4, 5, 6]  # prompt + common prefix
        return logits

    if ok:
        ties = smoke.compare_streams(got, want, {0: [4], 1: [0]}, logits_fn)
        assert [(r, i) for r, i, _ in ties] == [(0, 2)]
    else:
        with pytest.raises(smoke.SmokeFailure, match="no near-tie"):
            smoke.compare_streams(got, want, {0: [4], 1: [0]}, logits_fn)


def test_four_chip_phase_on_fake_devices(subproc):
    """The --four-chips phase's mesh, placement and comparison at smoke
    size on four fake CPU devices (the chip-only checks are skipped)."""
    out = subproc(f"""
import importlib.util, jax
spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro.configs.registry import get_smoke
from repro.models import api
from repro.models.params import init_params
cfg = get_smoke("qwen2_0_5b")
params = init_params(jax.random.key(0), api.model_specs(cfg))
serve = dict(n_requests=3, prompt_lo=5, prompt_hi=20, max_new=6,
             max_batch=2, chunk=4, page_size=8, prefill_chunk=8)
smoke.phase_four_chips(smoke.CompileClock(), cfg, params, 0,
                       jax.devices()[:4], serve=serve)
""", devices=4)
    assert "parity: 3/3 streams identical" in out


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the entry
    points use the checkout's fixed .jax_cache/ (in a child process:
    tests themselves set no cache)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    code = ("import jax; from repro.launch.runtime import use_compile_cache;"
            "print(use_compile_cache()); "
            "print(jax.config.jax_compilation_cache_dir)")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    want = (str(tmp_path / "cc") if from_env
            else os.path.join(REPO, ".jax_cache"))
    assert proc.stdout.split() == [want, want]
