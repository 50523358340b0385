"""The benchmark's only contact with the program under test: its public
entry points (``repro.launch.serve.build_engine`` and
``repro.launch.train.build_trainer``), its model configuration type,
the request type the engine drains, and its telemetry (the metrics
registry the trainer counts into, the span tracer it names its work
with). Everything else the benchmark needs it computes itself."""

from __future__ import annotations

import importlib
from typing import Any, Dict

from repro.launch.runtime import use_compile_cache  # noqa: F401
from repro.launch.serve import build_engine as _build_engine
from repro.launch.train import build_trainer as _build_trainer
from repro.models.config import ModelConfig
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import SpanTracer
from repro.serve.scheduler import Request


def model_config(conf: Dict[str, Any]) -> ModelConfig:
    """The program's configuration of a ``config.json``: each model type
    maps its keys in ``chip_bench/adapters/<model_type>.py``."""
    adapter = importlib.import_module(
        f"chip_bench.adapters.{conf['model_type']}")
    return adapter.model_config(conf)


class StampTracer(SpanTracer):
    """A disabled tracer whose clock is the benchmark's, and which keeps
    one thing: the time each request's lifecycle span last ended (its
    completion, after any preemption). The engine stamps ready, admit and
    first-token times with ``tracer.clock`` too."""

    def __init__(self, clock):
        super().__init__(clock=clock, enabled=False)
        self._slot_rid: Dict[int, int] = {}
        self.ended: Dict[int, float] = {}

    def begin(self, name, *, pid=0, tid=0, **_):
        if name.startswith("req:"):
            self._slot_rid[tid] = int(name[4:])

    def end(self, *, pid=0, tid=0, ts=None):
        rid = self._slot_rid.pop(tid, None)
        if rid is not None:
            self.ended[rid] = self.clock()


def build_engine(conf: Dict[str, Any], eng: Dict[str, Any], clock):
    """The CLI's engine for a serving mix's ``engine`` settings, with
    kernels picked from the platform, and its stamp tracer."""
    tracer = StampTracer(clock)
    engine = _build_engine(
        model_config(conf), window=eng["window"],
        max_batch=eng["max_batch"], chunk=eng["chunk"],
        page_size=eng["page_size"], prefill_chunk=eng["prefill_chunk"],
        prefix_cache=eng["prefix_cache"], temperature=eng["temperature"],
        tracer=tracer)
    return engine, tracer


def request_stamps(engine, tracer, rid: int) -> Dict[str, float]:
    """A finished request's ready / admission / first-token / completion times on the
    benchmark's clock. The engine keeps the first two per request of its
    latest ``run()`` in ``_req_obs``; there is no public record yet."""
    o = engine._req_obs[rid]
    return {"ready": o["ready"], "admit": o["admit"], "first": o["first"],
            "last": tracer.ended[rid]}


def make_request(r: Dict[str, Any]) -> Request:
    return Request(rid=r["rid"], prompt=r["prompt"], max_new=r["max_new"],
                   arrival=r["arrival"])


def build_trainer(conf: Dict[str, Any], mix: Dict[str, Any],
                  ckpt_dir: str, seed: int, tracer=None):
    """The CLI's trainer for a training mix, never saving inside a run
    (``checkpoint_every`` past any run's last step), with the registry
    that the trainer, its checkpoint writer and its model count into.
    Returns (trainer, state, registry)."""
    registry = MetricsRegistry()
    trainer, state = _build_trainer(
        model_config(conf), batch=mix["batch"], seq=mix["seq"],
        microbatches=mix["microbatches"], ckpt_dir=ckpt_dir,
        checkpoint_every=10 ** 9, seed=seed % (2 ** 31),
        optimizer=mix["optimizer"]["name"], metrics=registry,
        tracer=tracer)
    return trainer, state, registry


def counters(registry) -> Dict[str, float]:
    """The registry's counters and gauges by name (histograms left
    out)."""
    return {k: float(v) for k, v in registry.snapshot().items()
            if not isinstance(v, dict)}
