"""What both kinds of cell share: the run's context, the compile counter,
device memory, the check's comparison table and the result line."""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def seed_key(seed: int):
    """The JAX key of a seed of any size: 32 bits drawn from it."""
    import jax
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.key(word)


def reference_module(conf: Dict[str, Any]):
    return importlib.import_module(
        f"chip_bench.reference.{conf['model_type']}")


@dataclasses.dataclass
class Run:
    """One invocation: the cell's files, the arguments, and the clock."""

    name: str
    cell: Dict[str, Any]
    conf: Dict[str, Any]
    mix: Dict[str, Any]
    limits: Dict[str, float]
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: List[Any]
    peaks: Dict[str, Any]
    out_dir: Path
    clock: Callable[[], float] = time.perf_counter
    rate_scale: float = 1.0
    calibrate: int = 0
    setup: Dict[str, float] = dataclasses.field(default_factory=dict)

    def mark(self, phase: str, since: float) -> float:
        """Record a set-up phase's seconds; returns the clock's now."""
        now = self.clock()
        self.setup[phase] = self.setup.get(phase, 0.0) + now - since
        return now


class CompileCounter:
    """XLA backend compilations, counted from JAX's monitoring events (a
    persistent-cache hit compiles nothing and emits none)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def peak_memory_bytes(devices) -> int:
    """Peak bytes in use on the fullest device since the process began."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def percentile(values, q: float) -> float:
    """The q-th percentile (linear interpolation between order
    statistics) of all the values."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def per_layer_readers(bench: Dict[str, Any], workload: str):
    """{metric name: read function} for the cell's per-layer metrics;
    each reader is ``chip_bench/metrics/<name>.py``."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        path = HERE / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"chip_bench_metric_{len(out)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[m["name"]] = (mod.read, m["unit"])
    return out


def compared(values: Dict[str, float], limits: Dict[str, float]
             ) -> Dict[str, Dict[str, float]]:
    """{name: {value, limit}} for every number the check compares."""
    return {k: {"value": float(values[k]), "limit": float(limits[k])}
            for k in limits}


def result_line(run: Run, *, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Any], device_extra: Dict[str, Any],
                checks: Dict[str, Dict[str, float]],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices)}
    device.update(device_extra)
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = checks
    return json.dumps(out)


def print_checks(checks: Dict[str, Dict[str, float]]) -> None:
    """The numbers compared, beside their limits: the last lines on
    standard error."""
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
