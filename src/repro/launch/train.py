"""Training driver: config -> mesh -> sharded state -> resilient loop.

CPU-runnable end to end with reduced (smoke) configs; the same code lowers
the full configs on the production meshes (that path is exercised by
launch/dryrun.py, which only compiles).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2_0_5b --smoke \
      --steps 50 --batch 8 --seq 64 --ckpt-dir ckpt/qwen_smoke
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import get_arch, get_smoke
from repro.core.cci import CCI_BY_NAME, CarbonLedger
from repro.core.ocs import OCSPodScheduler
from repro.data.pipeline import DataConfig, DataPipeline
from repro.launch.cells import make_optimizer
from repro.launch.runtime import CHECKOUT_ROOT, use_compile_cache
from repro.models.blocks import ModelContext
from repro.models.config import ModelConfig
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import SpanTracer
from repro.resilience.driver import FailurePlan, ResilientTrainer
from repro.train.step import TrainSettings, init_train_state, \
    make_train_step


def build_trainer(cfg: ModelConfig, *, batch: int, seq: int,
                  ckpt_dir: str, microbatches: int = 1,
                  checkpoint_every: int = 20, seed: int = 0,
                  optimizer: str = "adamw",
                  failures: Optional[Dict[int, int]] = None,
                  compute_dtype=jnp.float32,
                  metrics=None, tracer=None):
    # the attention kernels follow the platform, as build_engine's do:
    # the differentiable flash kernels on a TPU, jnp attention elsewhere
    attn_impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    # the checkpoint writer and the model share the trainer's registry
    metrics = MetricsRegistry() if metrics is None else metrics
    ctx = ModelContext(compute_dtype=compute_dtype, q_chunk=2048,
                       mamba_chunk=64, rwkv_chunk=16, attn_impl=attn_impl,
                       metrics=metrics)
    opt = make_optimizer(optimizer, total_steps=10_000)
    step_fn = jax.jit(make_train_step(
        cfg, ctx, opt, TrainSettings(microbatches=microbatches)),
        donate_argnums=(0,))
    pipeline = DataPipeline(
        DataConfig(global_batch=batch, seq_len=seq,
                   vocab_size=cfg.vocab_size, seed=seed), cfg)
    ckpt = CheckpointManager(ckpt_dir, tracer=tracer, metrics=metrics)
    sched = OCSPodScheduler(total_cubes=144)  # Ironwood-scale cube count
    sched.allocate("train", 128 * 64)
    trainer = ResilientTrainer(
        train_step=step_fn, pipeline=pipeline, ckpt=ckpt, scheduler=sched,
        job="train", checkpoint_every=checkpoint_every,
        failure_plan=FailurePlan(failures=dict(failures or {})),
        metrics=metrics, tracer=tracer)
    state = init_train_state(jax.random.key(seed), cfg, opt)
    # restore-if-present (restart semantics)
    latest = ckpt.latest_step()
    if latest is not None:
        state = ckpt.restore(latest, state)
    return trainer, state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=str(CHECKOUT_ROOT / "ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a cube failure at this step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="append a timestamped JSONL metrics snapshot")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the step/ckpt/replay Chrome trace")
    ap.add_argument("--steptrace-out", default=None, metavar="PATH",
                    help="write the measured step-time trace (replayable "
                         "via fleet.perf.StepTimeModel.from_trace)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    failures = {args.fail_at: 0} if args.fail_at is not None else None
    tracer = SpanTracer() if args.trace_out else None
    trainer, state = build_trainer(
        cfg, batch=args.batch, seq=args.seq, ckpt_dir=args.ckpt_dir,
        microbatches=args.microbatches, checkpoint_every=args.ckpt_every,
        seed=args.seed, failures=failures, tracer=tracer)

    carbon = CarbonLedger(CCI_BY_NAME["ironwood"])
    t0 = time.time()
    state, ledger, losses = trainer.run(state, args.steps)
    wall = time.time() - t0
    flops_per_step = 6.0 * cfg.active_params() * args.batch * args.seq
    carbon.record_step(flops_per_step * len(losses))
    print(f"\ntrained {len(losses)} effective steps in {wall:.1f}s; "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    # one-line goodput/step-time summary from the telemetry registry
    # (rescales is constitutionally 0 here: the trainer restores at full
    # scale — the shrink arm lives in repro.fleet)
    rs = trainer.replay_summary()
    hist = trainer.metrics.histogram("train_step_s")
    print(f"telemetry: goodput={ledger.goodput:.4f} "
          f"steps={rs['effective_steps']} "
          f"replayed={rs['replayed_steps']} rescales={rs['rescales']} "
          f"ckpts={trainer.metrics.counter('train_ckpt_saves').value:.0f} "
          f"| step p50={hist.quantile(0.5) * 1e3:.0f}ms "
          f"p95={hist.quantile(0.95) * 1e3:.0f}ms")
    print("carbon:", {k: f"{v:.3e}" for k, v in carbon.summary().items()})
    if args.metrics_out:
        trainer.metrics.to_jsonl(args.metrics_out)
        print(f"metrics snapshot appended to {args.metrics_out}")
    if args.trace_out:
        trainer.tracer.write(args.trace_out)
        print(f"chrome trace written to {args.trace_out} "
              f"({len(trainer.tracer.events)} events)")
    if args.steptrace_out:
        trainer.steptrace().write(args.steptrace_out)
        print(f"steptrace written to {args.steptrace_out}")


if __name__ == "__main__":
    main()
