"""Serving driver: continuous-batching engine over a request trace.

Two modes:

  * default — one batch of identical prompts through ``generate`` (the
    legacy smoke path, now served by the chunked engine);
  * ``--trace N`` — N requests with seeded arrivals/lengths drained by
    the continuous-batching scheduler, reporting tokens/s, occupancy and
    preemptions.

``--mesh D,M`` serves on a (data, model) mesh of D*M devices (on a CPU
host, give the process fake devices yourself:
``XLA_FLAGS=--xla_force_host_platform_device_count=N``). Add
``--disaggregate`` for prefill/decode disaggregation with ``N``
``--prefill-workers`` handing pages over a modeled ``--link`` (ici|dcn).

The kernels follow the platform: on a TPU the paged attention and the
grouped MoE GEMM run as compiled Pallas kernels, elsewhere as jnp.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2_0_5b --smoke \
      --batch 4 --prompt-len 32 --max-new 16
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2_0_5b --smoke \
      --trace 16 --max-batch 4 --chunk 8 --mesh 4,2 --disaggregate
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_arch, get_smoke
from repro.launch.mesh import make_mesh
from repro.launch.runtime import use_compile_cache
from repro.models import api
from repro.models.blocks import ModelContext
from repro.models.config import ModelConfig
from repro.models.params import init_params
from repro.obs.trace import SpanTracer
from repro.serve.admission import AdmissionController, AdmissionPolicy
from repro.serve.engine import ServeEngine, quantize_weights
from repro.serve.faults import FaultInjector, FaultPlan, startup_bist
from repro.serve.scheduler import Request


def make_trace(n: int, vocab: int, seed: int, *, prompt_lo=8, prompt_hi=32,
               new_lo=8, new_hi=24, mean_gap=3):
    """Deterministic multi-user arrival trace (geometric inter-arrivals)."""
    prompt_lo = min(prompt_lo, prompt_hi)
    new_lo = min(new_lo, new_hi)
    rng = np.random.default_rng(seed)
    reqs, t = [], 0
    for rid in range(n):
        t += int(rng.geometric(1.0 / max(mean_gap, 1)) - 1)
        reqs.append(Request(
            rid=rid,
            prompt=rng.integers(0, vocab, int(rng.integers(prompt_lo,
                                                           prompt_hi + 1))),
            max_new=int(rng.integers(new_lo, new_hi + 1)),
            arrival=t))
    return reqs


def slo_line(engine) -> str:
    """One-line TTFT/TPOT/role-split summary from the metrics registry."""
    s = engine.slo_summary()
    return (f"slo: requests={s['requests']:.0f} "
            f"ttft p50={s['ttft_p50_s'] * 1e3:.1f}ms "
            f"p95={s['ttft_p95_s'] * 1e3:.1f}ms | "
            f"tpot p50={s['tpot_p50_s'] * 1e3:.2f}ms "
            f"p95={s['tpot_p95_s'] * 1e3:.2f}ms | "
            f"queue p50={s['queue_wait_p50_steps']:.0f} steps | "
            f"prefill {s['prefill_time_s']:.2f}s "
            f"({s['prefill_tok_s']:.0f} tok/s) / "
            f"decode {s['decode_time_s']:.2f}s "
            f"({s['decode_tok_s']:.0f} tok/s)")


def dump_telemetry(engine, args) -> None:
    """--metrics-out / --trace-out / --steptrace-out epilogue."""
    if args.metrics_out:
        engine.metrics.to_jsonl(args.metrics_out)
        print(f"metrics snapshot appended to {args.metrics_out}")
    if args.trace_out:
        engine.tracer.write(args.trace_out)
        print(f"chrome trace written to {args.trace_out} "
              f"({len(engine.tracer.events)} events)")
    if args.steptrace_out:
        engine.steptrace.write(args.steptrace_out)
        print(f"steptrace written to {args.steptrace_out} "
              f"({len(engine.steptrace)} events)")


def build_engine(cfg: ModelConfig, *, window: int, max_batch: int = 4,
                 chunk: int = 8, page_size: int = 16, draft_k: int = 0,
                 prefix_cache: bool = True, prefill_chunk: int = 128,
                 kv_int8: bool = False, temperature: float = 0.0, mesh=None,
                 rules: str = "baseline_dp_tp", disaggregate: bool = False,
                 prefill_workers: int = 1, link: str = "ici",
                 tracer=None, faults=None, admission=None,
                 attn_impl: str | None = None) -> ServeEngine:
    """The serving engine the CLI runs, for ``cfg`` with a ``window``-token
    budget per request (prompt + generated).

    ``attn_impl`` None picks the kernels from the platform: the compiled
    Pallas kernels on a TPU, jnp elsewhere; a caller names it only to
    build a reference engine. Speculation and the prefix cache apply to
    the paged families and are switched off for the others."""
    if cfg.mla:
        raise ValueError(
            f"{cfg.name}: latent attention (MLA) cannot be served yet: the "
            f"engine has no latent KV cache (pages of kv_lora_rank + "
            f"qk_rope_dim = {cfg.kv_lora_rank + cfg.qk_rope_dim} values a "
            f"token) and no latent paged kernel")
    if attn_impl is None:
        attn_impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    ctx = ModelContext(
        compute_dtype=jnp.float32, q_chunk=1024, mamba_chunk=16,
        rwkv_chunk=8, attn_impl=attn_impl,
        decode_cache_dtype=jnp.int8 if kv_int8 else None)
    paged = api.supports_paged_decode(cfg)
    return ServeEngine(cfg, ctx, window=window, max_batch=max_batch,
                       chunk=chunk, page_size=page_size,
                       temperature=temperature,
                       draft_k=draft_k if paged else 0,
                       prefix_cache=paged and prefix_cache,
                       prefill_chunk=prefill_chunk,
                       mesh=mesh, rules=rules,
                       disaggregate=disaggregate,
                       prefill_workers=prefill_workers,
                       transfer_link=link, tracer=tracer,
                       faults=faults, admission=admission)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--trace", type=int, default=0,
                    help="serve N trace requests via continuous batching")
    ap.add_argument("--draft-k", type=int, default=0,
                    help="self-speculative draft length (paged archs)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable prompt-prefix page sharing")
    ap.add_argument("--prefill-chunk", type=int, default=128,
                    help="span size for chunked prefill (clamped to the "
                         "window; final partial chunk buckets to pow2)")
    ap.add_argument("--quantize", choices=["none", "int8", "fp8"],
                    default="none")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 paged KV pages (attention archs)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="D,M",
                    help="serve on a (data, model) mesh of D*M devices")
    ap.add_argument("--rules", default="baseline_dp_tp",
                    help="AxisRules set for the serving mesh")
    ap.add_argument("--disaggregate", action="store_true",
                    help="prefill/decode disaggregation (paged archs)")
    ap.add_argument("--prefill-workers", type=int, default=1)
    ap.add_argument("--link", choices=["ici", "dcn"], default="ici",
                    help="modeled prefill->decode page-transfer link")
    ap.add_argument("--bist", action="store_true",
                    help="run the functional built-in self-test (golden "
                         "patterns through the real matmul and paged-decode "
                         "kernels) before admitting traffic; refuse to "
                         "start on mismatch")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="enable the deterministic fault injector with "
                         "this schedule seed (worker kills, KV page "
                         "flips, transfer drops, stragglers)")
    ap.add_argument("--ttft-deadline", type=int, default=None,
                    metavar="STEPS",
                    help="shed requests whose best-case TTFT exceeds this "
                         "many engine steps")
    ap.add_argument("--spec-off-depth", type=int, default=None,
                    metavar="DEPTH",
                    help="drop speculative decoding while more than DEPTH "
                         "requests queue")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="append a timestamped JSONL metrics snapshot")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the request-lifecycle Chrome trace")
    ap.add_argument("--steptrace-out", default=None, metavar="PATH",
                    help="write the measured step-time trace (replayable "
                         "via fleet.perf.StepTimeModel.from_trace)")
    args = ap.parse_args()

    use_compile_cache()
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split(","))
        if jax.device_count() < d * m:
            raise SystemExit(f"--mesh {d},{m} needs {d * m} devices, "
                             f"have {jax.device_count()}")
        mesh = make_mesh((d, m), ("data", "model"))

    if args.bist:
        # on a TPU the goldens go through the compiled kernels, so the
        # self-test screens the hardware and not the interpreter
        res = startup_bist(interpret=jax.default_backend() != "tpu")
        print(f"bist: matmul max_err={res.matmul_report.max_abs_err:.3e} "
              f"paged_decode max_err={res.paged_decode_max_err:.3e} "
              f"-> {'PASS' if res.passed else 'FAIL'}")
        if not res.passed:
            raise SystemExit(
                "bist: kernel self-test failed; refusing to serve")

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    params = init_params(jax.random.key(args.seed), api.model_specs(cfg))
    if args.quantize == "fp8":
        params = quantize_weights(params, jnp.float8_e4m3fn)
    elif args.quantize == "int8":
        params = quantize_weights(params, jnp.int8)  # storage demo only

    tracer = SpanTracer() if args.trace_out else None
    faults = None
    if args.chaos is not None:
        faults = FaultInjector(FaultPlan(
            seed=args.chaos, worker_fail_rate=0.05, page_flip_rate=0.05,
            transfer_drop_rate=0.05, straggler_rate=0.05))
    admission = None
    if args.ttft_deadline is not None or args.spec_off_depth is not None:
        admission = AdmissionController(AdmissionPolicy(
            ttft_deadline_steps=args.ttft_deadline,
            spec_off_queue_depth=args.spec_off_depth))
    engine = build_engine(
        cfg, window=args.prompt_len + args.max_new,
        max_batch=args.max_batch, chunk=args.chunk,
        page_size=args.page_size, draft_k=args.draft_k,
        prefix_cache=not args.no_prefix_cache,
        prefill_chunk=args.prefill_chunk, kv_int8=args.kv_int8,
        temperature=args.temperature, mesh=mesh, rules=args.rules,
        disaggregate=args.disaggregate,
        prefill_workers=args.prefill_workers, link=args.link,
        tracer=tracer, faults=faults, admission=admission)
    mode = "paged" if engine.paged else "dense"
    if mesh is not None:
        mode += "/sharded"
        rep = engine.sharding_report
        print(f"mesh={rep['mesh']} rules={rep['rules']}")
        for line in rep["dropped_rules"]:
            print(f"  fallback: {line}")
    if args.disaggregate:
        mode += "/disagg"
    rng = np.random.default_rng(args.seed)

    if args.trace:
        reqs = make_trace(args.trace, cfg.vocab_size, args.seed,
                          prompt_hi=args.prompt_len, new_hi=args.max_new)
        if cfg.is_encoder_decoder:
            for req in reqs:  # enc-dec requests carry their audio features
                req.extras["enc_feats"] = rng.standard_normal(
                    (1, cfg.encoder_seq, cfg.d_model),
                    dtype=np.float32) * 0.1
        t0 = time.time()
        out = engine.run(params, reqs, key=jax.random.key(args.seed))
        wall = time.time() - t0
        toks = sum(len(v) for v in out.values())
        s = engine.scheduler
        print(f"[{mode}] {args.trace} requests, {toks} tokens in "
              f"{wall:.2f}s ({toks / wall:.1f} tok/s)")
        print(f"occupancy={s.mean_occupancy:.2f} stats={s.stats}")
        print(slo_line(engine))
        if engine.paged:
            print(f"prefix_hit_rate={engine.prefix_hit_rate:.2f} "
                  f"acceptance_length={engine.acceptance_length:.2f} "
                  f"kv={engine.kv.counters}")
        if faults is not None or admission is not None:
            print(f"[faults] {dict(engine.fault_stats.items())}")
        if args.disaggregate:
            ts = engine.transfer_stats()
            print(f"[disagg] link={ts['link']} "
                  f"transfers={ts['transfers']} "
                  f"pages={ts['transfer_pages']} "
                  f"bytes={ts['transfer_bytes']} "
                  f"stall_boundaries={ts['transfer_stall_boundaries']} "
                  f"idle_boundaries={ts['decode_idle_boundaries']}")
            print(f"[disagg] prefill queue depth mean="
                  f"{ts['prefill_depth_mean']:.2f} "
                  f"peak={ts['prefill_depth_peak']} | decode queue depth "
                  f"mean={ts['decode_depth_mean']:.2f} "
                  f"peak={ts['decode_depth_peak']} | "
                  f"pool={engine.prefill_pool.stats}")
        if mesh is not None and engine.sharding_report["dropped_rules"]:
            print("sharding fallbacks:",
                  "; ".join(engine.sharding_report["dropped_rules"]))
        dump_telemetry(engine, args)
        return

    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)}
    if cfg.is_encoder_decoder:
        batch["enc_feats"] = jnp.asarray(
            rng.standard_normal((args.batch, cfg.encoder_seq, cfg.d_model),
                                dtype=np.float32) * 0.1)

    t0 = time.time()
    key = jax.random.key(args.seed) if args.temperature > 0 else None
    out = engine.generate(params, batch, max_new=args.max_new,
                          temperature=args.temperature, key=key)
    wall = time.time() - t0
    toks = args.batch * args.max_new
    print(f"[{mode}] generated {toks} tokens in {wall:.2f}s "
          f"({toks / wall:.1f} tok/s batch={args.batch}) "
          f"host_syncs={engine.counters['host_syncs']}")
    print(slo_line(engine))
    print("sample:", np.asarray(out[0])[:16])
    dump_telemetry(engine, args)


if __name__ == "__main__":
    main()
