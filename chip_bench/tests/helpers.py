"""Smoke-size runs of the harness on the CPU: the cell's files come from
``tests/data``, the device check is skipped, and nothing is timed."""

from __future__ import annotations

import json
import time
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"
ROOT = DATA.parents[2]

# Smoke-size limits. On the CPU the program and the reference both
# compute in float32 at full precision, so a sound run reads 0 (served
# tokens) or differences of accumulation order, ~1e-7 relative (losses,
# norms); the limits sit far above those and far below what a fault or
# the bfloat16 control reads at this size.
SERVE_LIMITS = {"max_logit_gap": 1e-6, "mean_logit_gap": 1e-7,
                "unfinished": 0}
TRAIN_LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-4, "change_gap": 1e-3}


def load(name: str):
    with open(DATA / name) as f:
        return json.load(f)


def make_run(kind: str, tmp_path, seed: int = 11, seconds: float = 0.3,
             calibrate: int = 0):
    import jax
    from chip_bench import common
    limits = SERVE_LIMITS if kind == "serve" else TRAIN_LIMITS
    return common.Run(
        name=f"smoke.{kind}", cell={"chips": 1},
        conf=load("qwen2-smoke.json"), mix=load(f"{kind}-smoke.json"),
        limits=dict(limits), seed=seed, seconds=seconds, trace=False,
        t_start=time.perf_counter(), devices=jax.devices()[:1],
        peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
        out_dir=Path(tmp_path), calibrate=calibrate)


def execute(run) -> dict:
    """Drive the rest of a run and parse its result line."""
    from chip_bench import run as entry
    return json.loads(entry.execute(run, {"per_layer": []}))
