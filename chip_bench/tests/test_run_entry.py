"""The command refuses to run, printing no result, where it cannot
measure: beside no program, or without a TPU."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "qwen2-0.5b.train", "--seed", str(2 ** 31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_bench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_result_beside_no_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chip_bench", tmp_path / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run(tmp_path)
    assert res.returncode != 0 and res.stdout == ""
    assert "program is missing" in res.stderr


def test_no_result_without_a_tpu():
    res = _run(ROOT)
    assert res.returncode != 0 and res.stdout == ""
    assert "no TPU" in res.stderr
