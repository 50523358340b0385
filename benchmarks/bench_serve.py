"""Serve-engine benchmark: device-resident chunked decode vs the legacy
per-token loop, prefix caching + self-speculative decoding, and the
int8-vs-bf16 paged-decode capacity lever.

Five sections:

  1. static batch — chunked loop vs per-token loop (PR 1's win: one
     compiled program per chunk, one host sync per chunk);
  2. arrival trace — continuous batching under a synthetic multi-user
     trace (occupancy / preemptions, TTFT/TPOT percentiles and the
     prefill-vs-decode time split from the telemetry registry);
  3. shared-prefix batch — requests sharing a long prompt prefix served
     cold (PR 1 engine) vs with prefix caching + draft-k speculation.
     Reports prefix-cache hit rate, speculative acceptance length,
     cross-request dedup stats (pages shared / unique, bytes saved) and
     the per-token speedup (gate: >= 1.3x at batch 4);
  4. int8 vs bf16 paged decode — tokens/s for both pool dtypes,
     estimated HBM bytes/token streamed by paged attention, and the
     resident-batch capacity ratio (gate: int8 fits >= 1.5x the tokens);
  5. sharded serving — the same trace on a (data, model) mesh (forced
     fake host devices in a subprocess): tok/s vs single-host, plus
     disaggregated prefill/decode page-transfer traffic. On CPU the
     fake-device mesh pays real overhead, so tok/s is a wiring check,
     not a speedup claim (see docs/serving.md);
  6. MoE dispatch — sort-based grouped (dropless) vs the dense capacity
     buffer on the mixtral/kimi smoke MoE layers at a decode-shaped
     batch: tok/s plus the estimated HBM bytes/token each dispatch
     streams (gate: grouped beats capacity on mixtral).

  PYTHONPATH=src python benchmarks/bench_serve.py [--arch qwen2_0_5b]
      [--json]        # also write BENCH_serve.json
      [--smoke]       # fast interpret-mode kernel-routing check + the
                      # fatal sharded-parity gate (tier-1)

``benchmarks/run.py --only serve --json`` runs the same sections at
smoke scale through the CSV/JSON harness. See docs/benchmarks.md.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_smoke
from repro.models import api
from repro.models.blocks import ModelContext
from repro.models.params import init_params
from repro.launch.mesh import make_mesh
from repro.launch.serve import make_trace
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import Request


def bench_static_batch(engine, params, cfg, batch, max_new, reps=3):
    """Same fixed batch through both decode loops (compile excluded)."""
    engine.generate_pertoken(params, batch, max_new=2)  # warm
    t0 = time.time()
    for _ in range(reps):
        engine.generate_pertoken(params, batch, max_new=max_new)
    pertoken = reps * batch["tokens"].shape[0] * max_new / (time.time() - t0)

    engine.generate(params, batch, max_new=2)  # warm (compiles the chunk)
    t0 = time.time()
    for _ in range(reps):
        engine.generate(params, batch, max_new=max_new)
    chunked = reps * batch["tokens"].shape[0] * max_new / (time.time() - t0)
    return pertoken, chunked


def shared_prefix_requests(cfg, n, prefix_len, tail_len, max_new, seed):
    """n requests sharing a prompt prefix (system prompt / few-shot
    template shape) with small unique tails."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, prefix_len)
    return [Request(
        rid=i,
        prompt=np.concatenate([prefix,
                               rng.integers(0, cfg.vocab_size, tail_len)]),
        max_new=max_new) for i in range(n)]


def timed_run(engine, params, make_reqs, seed=0, reps=3):
    """Best-of-reps tokens/s: a shared CPU stalls individual reps by
    multiples, so the max is the stable estimate of sustained rate."""
    best = 0.0
    for _ in range(reps):
        reqs = make_reqs()
        t0 = time.time()
        out = engine.run(params, reqs, key=jax.random.key(seed))
        wall = time.time() - t0
        best = max(best, sum(len(v) for v in out.values()) / wall)
    return best


def bench_shared_prefix(cfg, ctx, params, *, batch, prefix_len, tail_len,
                        max_new, chunk, draft_k, seed):
    """Cold (PR 1) engine vs prefix-cache + speculative engine on the
    same shared-prefix batch. Both are warmed (compile excluded); the
    cached engine's warm run also populates the prefix index, so the
    timed run measures the steady serving state."""
    window = prefix_len + tail_len + max_new

    def reqs():
        return shared_prefix_requests(cfg, batch, prefix_len, tail_len,
                                      max_new, seed)

    base = ServeEngine(cfg, ctx, window=window, max_batch=batch,
                       chunk=chunk, prefix_cache=False)
    base.run(params, reqs())  # warm: compiles prefill + chunk
    base_tps = timed_run(base, params, reqs)

    eng = ServeEngine(cfg, ctx, window=window, max_batch=batch,
                      chunk=chunk, draft_k=draft_k)
    eng.run(params, reqs())  # warm 1: compiles + populates the index
    eng.run(params, reqs())  # warm 2: steady cached state
    for k in ("prompt_tokens", "cached_prompt_tokens", "spec_steps",
              "spec_tokens"):
        eng.counters[k] = 0
    for k in ("pages_shared", "pages_allocated"):
        eng.kv.counters[k] = 0  # dedup stats cover the timed window only
    cached_tps = timed_run(eng, params, reqs)
    return base_tps, cached_tps, eng


def bench_int8_vs_bf16(cfg, params, *, batch, prompt_len, max_new, chunk,
                       seed, reps=3):
    """int8 page pool vs bf16 on the same paged decode: tokens/s,
    estimated HBM bytes/token (full residency: each decode step streams
    the whole resident cache), and the capacity ratio — how many more
    tokens the int8 pool holds in the same HBM (scale pages included)."""
    window = prompt_len + max_new
    rng = np.random.default_rng(seed)
    toks = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)}
    out = {}
    for tag, cdt in (("bf16", jnp.bfloat16), ("int8", jnp.int8)):
        ctx = ModelContext(compute_dtype=jnp.float32, q_chunk=512,
                           decode_cache_dtype=cdt)
        eng = ServeEngine(cfg, ctx, window=window, max_batch=batch,
                          chunk=chunk, prefix_cache=False)
        eng.generate(params, toks, max_new=2)  # warm
        t0 = time.time()
        for _ in range(reps):
            eng.generate(params, toks, max_new=max_new)
        tps = reps * batch * max_new / (time.time() - t0)
        ptb = eng.kv.per_token_bytes()
        out[tag] = {"tok_s": tps, "per_token_bytes": ptb,
                    "est_hbm_bytes_per_token": window * ptb}
    out["capacity_ratio"] = (out["bf16"]["per_token_bytes"]
                             / out["int8"]["per_token_bytes"])
    return out


# -- MoE dispatch (section 6 + the tier-1 grouped-kernel gate) --------------


def _moe_hbm_bytes_per_token(cfg, t, mode, plan=None):
    """Estimated HBM bytes streamed per token by one MoE layer's
    dispatch at fp32. Capacity reads every expert's weights and writes/
    reads the dense (E, C, D) buffer; grouped reads one (D, F) weight
    tile per *used* m-tile plus the sorted M_pad row buffer."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    nw = 3 if cfg.mlp_act == "swiglu" else 2
    itemsize = 4
    if mode == "capacity":
        cap = t  # dropless: capacity == chunk token count
        weights = e * nw * d * f
        buffers = e * cap * (2 * d + f)  # scatter in, h, gather out
    else:
        used = int(np.sum(np.asarray(plan.block_experts) >= 0))
        weights = used * nw * d * f  # one weight tile DMA per used tile
        buffers = plan.padded_rows * (2 * d + f)
    return (weights + buffers) * itemsize / t


def bench_moe(emit, log, *, t=256, block_m=64, reps=20, seed=0):
    """Section 6: grouped vs capacity dispatch on the MoE smoke layers,
    decode-shaped (t single-token rows through one expert layer).
    ``block_m=64`` is the serving-scale m-tile: the dropless buffer is
    round_up(t*k + E*(block_m-1), block_m) rows vs capacity's E*t."""
    from repro.models.moe import (grouped_dispatch_plan, moe_ffn,
                                  moe_param_specs, _route)

    log(f"== [moe] grouped vs capacity dropless dispatch "
        f"(t={t} decode rows, block_m={block_m})")
    for arch in ("mixtral_8x22b", "kimi_k2_1t_a32b"):
        cfg = get_smoke(arch)
        p = init_params(jax.random.fold_in(jax.random.key(1), seed),
                        moe_param_specs(cfg))
        x = jax.random.normal(jax.random.key(2), (t, 1, cfg.d_model),
                              jnp.float32)
        tok_s = {}
        for tag, kw in (("grouped", {"dispatch": "grouped", "impl": "ref",
                                     "block_m": block_m}),
                        ("capacity", {"dropless": True})):
            fn = jax.jit(lambda pp, xx, kw=kw: moe_ffn(
                pp, xx, cfg, jnp.float32, **kw)[0])
            fn(p, x).block_until_ready()  # warm
            best = 0.0
            for _ in range(3):  # best-of-3: shared-CPU stall robustness
                t0 = time.time()
                for _ in range(reps):
                    out = fn(p, x)
                out.block_until_ready()
                best = max(best, reps * t / (time.time() - t0))
            tok_s[tag] = best
        _, _, _, gate_idx = _route(p, x.reshape(t, cfg.d_model),
                                   jnp.float32, cfg.experts_per_token)
        plan = grouped_dispatch_plan(gate_idx, n_experts=cfg.n_experts,
                                     block_m=block_m)
        hbm = {tag: _moe_hbm_bytes_per_token(cfg, t, tag, plan)
               for tag in ("grouped", "capacity")}
        speedup = tok_s["grouped"] / tok_s["capacity"]
        gated = arch == "mixtral_8x22b" and speedup <= 1.0
        for tag in ("grouped", "capacity"):
            emit(f"serve/moe_{arch}_{tag}_tok_s", tok_s[tag], "")
            emit(f"serve/moe_{arch}_{tag}_hbm_bytes_per_token", hbm[tag],
                 "")
        emit(f"serve/moe_{arch}_grouped_speedup", speedup,
             "FAILED: grouped <= capacity on mixtral" if gated
             else ("gate > 1.0x" if arch == "mixtral_8x22b" else ""))
        log(f"{arch}: grouped {tok_s['grouped']:8.1f} tok/s "
            f"({hbm['grouped']:.0f} B/token) | capacity "
            f"{tok_s['capacity']:8.1f} tok/s ({hbm['capacity']:.0f} "
            f"B/token) | {speedup:.2f}x")


def _moe_smoke() -> int:
    """Grouped-kernel==oracle gate (fatal, tier-1): the m-grouped GEMM
    kernel in interpret mode must match kernels/ref.grouped_matmul_ref
    for bf16 and int8(+scale) weights, and the grouped serving dispatch
    end-to-end (interpret kernel) must match capacity-dropless on the
    mixtral smoke MoE layer."""
    from repro.kernels import moe_gemm, ref as kref
    from repro.models.moe import (moe_ffn, moe_param_specs,
                                  quantize_moe_params)

    failures = 0
    key = jax.random.key(3)
    m, d, f, e = 64, 32, 48, 4
    gids = jnp.array([0, 0, 1, -1, 2, 3, 3, -1], jnp.int32)
    x32 = jax.random.normal(jax.random.fold_in(key, 0), (m, d))
    wf = jax.random.normal(jax.random.fold_in(key, 1), (e, d, f))
    w8 = jnp.clip(jnp.round(wf * 40), -127, 127).astype(jnp.int8)
    s8 = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (e,))) + 0.1
    # f32 sums of d products in different orders may differ by
    # d * max|x| * max|w| * scale * eps_f32 (int8 weights reach 127)
    tol8 = (d * float(jnp.max(jnp.abs(x32))) * 127.0 * float(jnp.max(s8))
            * float(jnp.finfo(jnp.float32).eps))
    cases = {
        "bf16": (x32.astype(jnp.bfloat16), wf.astype(jnp.bfloat16), None,
                 2e-2),
        "int8": (x32, w8, s8, tol8),
    }
    for tag, (x, w, scale, tol) in cases.items():
        got = moe_gemm.grouped_matmul(x, w, gids, w_scale=scale,
                                      interpret=True, block_f=16)
        want = kref.grouped_matmul_ref(x, w, gids, w_scale=scale)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        if err > tol:
            print(f"FAILED [moe {tag}]: grouped kernel != oracle "
                  f"(max|diff|={err:.2e} > {tol})")
            failures += 1
    cfg = get_smoke("mixtral_8x22b")
    p = quantize_moe_params(init_params(jax.random.fold_in(key, 4),
                                        moe_param_specs(cfg)))
    xm = jax.random.normal(jax.random.fold_in(key, 5),
                           (2, 5, cfg.d_model))
    got, _ = moe_ffn(p, xm, cfg, jnp.float32, dispatch="grouped",
                     impl="interpret")
    want, _ = moe_ffn(p, xm, cfg, jnp.float32, dropless=True)
    err = float(jnp.max(jnp.abs(got - want)))
    if err > 1e-4:
        print(f"FAILED [moe]: grouped dispatch != capacity dropless "
              f"(max|diff|={err:.2e})")
        failures += 1
    print(f"smoke [moe]: grouped kernel==oracle (bf16, int8) and "
          f"grouped==capacity-dropless on mixtral (max|diff|={err:.1e})")
    return failures


# -- sharded serving (section 5 + the tier-1 parity gate) -------------------
#
# The mesh needs multiple XLA devices, and jax locks the device count at
# first init — so everything sharded runs in a SUBPROCESS with
# --xla_force_host_platform_device_count in XLA_FLAGS. The parent invokes
# this same file with an inner flag; the child prints one JSON line.


def _run_sharded_child(flag: str, devices: int = 8,
                       timeout: int = 900) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    # a CPU run by design: the child never reaches for an accelerator
    # the parent may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, os.path.abspath(__file__), flag],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


def sharded_smoke_inner() -> int:
    """Child process (forced multi-device): sharded-vs-single-host parity.

    Single-host jnp-oracle engine vs a (2, 2)-mesh engine routing the
    Pallas kernels (interpret mode) through shard_map — bf16-class and
    int8 pools, speculation on, run twice so the second pass decodes off
    prefix-cache hits. Then disaggregated vs co-located on the oracle
    path with nonzero modeled transfer traffic. Exact token match."""
    cfg = get_smoke("qwen2_0_5b")
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (9, 13)]
    reqs = lambda: [Request(rid=i, prompt=p, max_new=4)
                    for i, p in enumerate(prompts)]
    mesh = make_mesh((2, 2), ("data", "model"))
    failures = 0
    for cdt, tag in ((None, "fp32"), (jnp.int8, "int8")):
        octx = ModelContext(compute_dtype=jnp.float32, q_chunk=64,
                            decode_cache_dtype=cdt)
        kctx = ModelContext(compute_dtype=jnp.float32, q_chunk=64,
                            decode_cache_dtype=cdt,
                            attn_impl="pallas_interpret")
        solo = ServeEngine(cfg, octx, window=32, max_batch=2, chunk=2,
                           page_size=8, draft_k=2)
        shard = ServeEngine(cfg, kctx, window=32, max_batch=2, chunk=2,
                            page_size=8, draft_k=2, mesh=mesh)
        for r in range(2):  # run 2 decodes off prefix-cache hits
            so, sh = solo.run(params, reqs()), shard.run(params, reqs())
            for i in range(len(prompts)):
                if not np.array_equal(so[i], sh[i]):
                    print(f"FAILED [{tag} run {r}]: sharded {sh[i]} != "
                          f"single-host {so[i]} (rid {i})")
                    failures += 1
        if shard.prefix_hit_rate <= 0:
            print(f"FAILED [{tag}]: sharded run 2 took no prefix hits")
            failures += 1
    co = ServeEngine(cfg, ModelContext(compute_dtype=jnp.float32,
                                       q_chunk=64),
                     window=32, max_batch=2, chunk=2, page_size=8)
    dis = ServeEngine(cfg, ModelContext(compute_dtype=jnp.float32,
                                        q_chunk=64),
                      window=32, max_batch=2, chunk=2, page_size=8,
                      disaggregate=True)
    coo, dio = co.run(params, reqs()), dis.run(params, reqs())
    for i in range(len(prompts)):
        if not np.array_equal(coo[i], dio[i]):
            print(f"FAILED [disagg]: {dio[i]} != co-located {coo[i]}")
            failures += 1
    if dis.transfer_stats()["transfer_bytes"] <= 0:
        print("FAILED [disagg]: no modeled transfer traffic")
        failures += 1
    print("SHARDED-PARITY", "FAILED" if failures else "OK",
          json.dumps(dis.transfer_stats()))
    return 1 if failures else 0


def sharded_bench_inner() -> int:
    """Child process (forced multi-device): section-5 measurements.
    Prints one JSON object: single-host vs (2, 2)-mesh trace tok/s and
    the disaggregated run's transfer traffic / stalls."""
    cfg = get_smoke("qwen2_0_5b")
    ctx = ModelContext(compute_dtype=jnp.float32, q_chunk=512)
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    window, chunk, trace = 28, 4, 8
    mesh = make_mesh((2, 2), ("data", "model"))
    out = {}

    def tps(eng):
        reqs = make_trace(trace, cfg.vocab_size, 0, prompt_hi=16, new_hi=12)
        eng.run(params, make_trace(2, cfg.vocab_size, 1, prompt_hi=16,
                                   new_hi=4))  # warm
        t0 = time.time()
        o = eng.run(params, reqs, key=jax.random.key(0))
        return sum(len(v) for v in o.values()) / (time.time() - t0)

    solo = ServeEngine(cfg, ctx, window=window, max_batch=4, chunk=chunk,
                       page_size=8)
    out["single_tok_s"] = tps(solo)
    shard = ServeEngine(cfg, ctx, window=window, max_batch=4, chunk=chunk,
                        page_size=8, mesh=mesh)
    out["sharded_tok_s"] = tps(shard)
    out["mesh"] = shard.sharding_report["mesh"]
    out["dropped_rules"] = shard.sharding_report["dropped_rules"]
    dis = ServeEngine(cfg, ctx, window=window, max_batch=4, chunk=chunk,
                      page_size=8, mesh=mesh, disaggregate=True,
                      prefill_workers=2)
    out["disagg_tok_s"] = tps(dis)
    ts = dis.transfer_stats()
    out["transfer_bytes"] = ts["transfer_bytes"]
    out["transfer_pages"] = ts["transfer_pages"]
    out["transfer_stall_boundaries"] = ts["transfer_stall_boundaries"]
    print(json.dumps(out))
    return 0


def bench_sharded(emit, log) -> None:
    """Section 5 driver: parse the child's JSON and emit metrics."""
    proc = _run_sharded_child("--sharded-bench-inner")
    if proc.returncode != 0:
        emit("serve/sharded_tok_s", 0.0,
             f"FAILED: child rc={proc.returncode}: {proc.stderr[-400:]}")
        return
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    ratio = r["sharded_tok_s"] / r["single_tok_s"]
    emit("serve/single_host_tok_s", r["single_tok_s"], "")
    emit("serve/sharded_tok_s", r["sharded_tok_s"],
         f"mesh={r['mesh']} (fake CPU devices: wiring check)")
    emit("serve/sharded_vs_single", ratio, "")
    emit("serve/disagg_tok_s", r["disagg_tok_s"], "")
    emit("serve/disagg_transfer_bytes", r["transfer_bytes"],
         f"pages={r['transfer_pages']}" if r["transfer_bytes"] > 0
         else "FAILED: no transfer traffic")
    emit("serve/disagg_stall_boundaries", r["transfer_stall_boundaries"],
         "")
    log(f"sharded serving (mesh={r['mesh']}, fake CPU devices):")
    log(f"single host    : {r['single_tok_s']:8.1f} tok/s")
    log(f"sharded        : {r['sharded_tok_s']:8.1f} tok/s   "
        f"({ratio:.2f}x — CPU mesh overhead expected)")
    log(f"disaggregated  : {r['disagg_tok_s']:8.1f} tok/s   "
        f"{r['transfer_bytes']} transfer bytes, "
        f"{r['transfer_stall_boundaries']} stall boundaries")
    for line in r["dropped_rules"]:
        log(f"  fallback: {line}")


# Section-2 arrivals rows: (emitted row name, engine.slo_summary() key).
# A module constant so the golden-snapshot test
# (tests/test_serve_edge.py) pins the exact schema bench_serve exports
# for the arrivals workload — adding/renaming a row is a deliberate,
# test-visible change.
ARRIVALS_SLO_ROWS = (
    ("serve/ttft_p50_s", "ttft_p50_s"),
    ("serve/ttft_p95_s", "ttft_p95_s"),
    ("serve/tpot_p50_s", "tpot_p50_s"),
    ("serve/tpot_p95_s", "tpot_p95_s"),
    ("serve/queue_wait_p50_steps", "queue_wait_p50_steps"),
    ("serve/prefill_time_s", "prefill_time_s"),
    ("serve/decode_time_s", "decode_time_s"),
)


def run_sections(emit, *, arch="qwen2_0_5b", batch=4, prompt_len=16,
                 max_new=32, chunk=8, trace=12, prefix_len=448, tail_len=4,
                 prefix_max_new=12, draft_k=2, seed=0,
                 log=lambda *a: None):
    """All four sections through an ``emit(name, value, note)`` sink —
    shared by the CLI (pretty print + JSON) and benchmarks/run.py."""
    cfg = get_smoke(arch)
    ctx = ModelContext(compute_dtype=jnp.float32, q_chunk=512,
                       mamba_chunk=16, rwkv_chunk=8)
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    window = prompt_len + max_new
    engine = ServeEngine(cfg, ctx, window=window, max_batch=batch,
                         chunk=chunk, prefix_cache=False)
    mode = "paged" if engine.paged else "dense"
    rng = np.random.default_rng(seed)
    batch_toks = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)}
    log(f"== bench_serve {arch} [{mode}] batch={batch} "
        f"chunk={chunk} max_new={max_new}")

    # 1. static batch ------------------------------------------------------
    pertoken, chunked = bench_static_batch(engine, params, cfg, batch_toks,
                                           max_new)
    speedup = chunked / pertoken
    emit("serve/pertoken_tok_s", pertoken, "")
    emit("serve/chunked_tok_s", chunked,
         "" if speedup > 1.0 else "FAILED: chunked <= per-token")
    emit("serve/chunked_speedup", speedup, f"host_syncs="
         f"{engine.counters['host_syncs']}")
    log(f"per-token loop : {pertoken:8.1f} tok/s")
    log(f"chunked loop   : {chunked:8.1f} tok/s   ({speedup:.2f}x)")

    # 2. arrival trace -----------------------------------------------------
    reqs = make_trace(trace, cfg.vocab_size, seed, prompt_hi=prompt_len,
                      new_hi=max_new)
    t0 = time.time()
    out = engine.run(params, reqs, key=jax.random.key(seed))
    wall = time.time() - t0
    toks = sum(len(v) for v in out.values())
    s = engine.scheduler
    emit("serve/trace_tok_s", toks / wall, f"{trace} reqs")
    emit("serve/trace_occupancy", s.mean_occupancy,
         " ".join(f"{k}={v}" for k, v in s.stats.items()))
    log(f"trace ({trace} reqs): {toks} tokens in {wall:.2f}s "
        f"({toks / wall:.1f} tok/s)  occupancy={s.mean_occupancy:.2f}")
    # SLO percentiles on the arrivals workload, straight off the
    # engine's telemetry registry (TTFT is measured at chunk drain, so
    # its floor is one chunk of decode on this host)
    slo = engine.slo_summary()
    notes = {"serve/ttft_p50_s": "measured at chunk drain",
             "serve/prefill_time_s": f"{slo['prefill_tok_s']:.0f} tok/s",
             "serve/decode_time_s": f"{slo['decode_tok_s']:.0f} tok/s"}
    for row, key in ARRIVALS_SLO_ROWS:
        emit(row, slo[key], notes.get(row, ""))
    log(f"slo: ttft p50={slo['ttft_p50_s'] * 1e3:.1f}ms "
        f"p95={slo['ttft_p95_s'] * 1e3:.1f}ms | "
        f"tpot p50={slo['tpot_p50_s'] * 1e3:.2f}ms "
        f"p95={slo['tpot_p95_s'] * 1e3:.2f}ms | "
        f"queue p50={slo['queue_wait_p50_steps']:.0f} steps | "
        f"prefill {slo['prefill_time_s']:.2f}s / "
        f"decode {slo['decode_time_s']:.2f}s")
    global _LAST_SNAPSHOT
    _LAST_SNAPSHOT = engine.metrics.snapshot()

    if not engine.paged:
        return

    # 3. shared-prefix batch ----------------------------------------------
    base_tps, cached_tps, eng = bench_shared_prefix(
        cfg, ctx, params, batch=batch, prefix_len=prefix_len,
        tail_len=tail_len, max_new=prefix_max_new, chunk=chunk,
        draft_k=draft_k, seed=seed)
    prefix_speedup = cached_tps / base_tps
    dedup = eng.kv.dedup_stats()
    emit("serve/prefix_cold_tok_s", base_tps, "")
    emit("serve/prefix_cached_tok_s", cached_tps,
         "" if prefix_speedup >= 1.3 else "FAILED: below 1.3x gate")
    emit("serve/prefix_speedup", prefix_speedup, "gate >= 1.3x")
    emit("serve/prefix_hit_rate", eng.prefix_hit_rate, "")
    emit("serve/acceptance_length", eng.acceptance_length, "")
    emit("serve/dedup_pages_shared", dedup["pages_shared"], "")
    emit("serve/dedup_pages_unique", dedup["pages_unique"], "")
    emit("serve/dedup_bytes_saved", dedup["bytes_saved"], "")
    log(f"shared-prefix batch (prefix={prefix_len} tail={tail_len} "
        f"draft_k={draft_k}):")
    log(f"cold engine    : {base_tps:8.1f} tok/s")
    log(f"cached+spec    : {cached_tps:8.1f} tok/s   "
        f"({prefix_speedup:.2f}x)")
    log(f"prefix hit rate: {eng.prefix_hit_rate:.2f}   "
        f"acceptance length: {eng.acceptance_length:.2f}")
    log(f"dedup: {dedup['pages_shared']} pages shared / "
        f"{dedup['pages_unique']} unique, "
        f"{dedup['bytes_saved']} bytes saved")

    # 4. int8 vs bf16 paged decode ----------------------------------------
    q = bench_int8_vs_bf16(cfg, params, batch=batch, prompt_len=prompt_len,
                           max_new=max_new, chunk=chunk, seed=seed)
    cap = q["capacity_ratio"]
    for tag in ("bf16", "int8"):
        emit(f"serve/{tag}_tok_s", q[tag]["tok_s"], "")
        emit(f"serve/{tag}_hbm_bytes_per_token",
             q[tag]["est_hbm_bytes_per_token"],
             f"per_token_bytes={q[tag]['per_token_bytes']}")
    emit("serve/int8_capacity_ratio", cap,
         "gate >= 1.5x" if cap >= 1.5 else "FAILED: below 1.5x capacity")
    log(f"int8 vs bf16 paged decode:")
    log(f"bf16 pool      : {q['bf16']['tok_s']:8.1f} tok/s   "
        f"{q['bf16']['est_hbm_bytes_per_token']} B/token")
    log(f"int8 pool      : {q['int8']['tok_s']:8.1f} tok/s   "
        f"{q['int8']['est_hbm_bytes_per_token']} B/token")
    log(f"capacity ratio : {cap:.2f}x resident tokens per HBM byte")

    # 5. sharded serving (subprocess: needs a multi-device mesh) ----------
    bench_sharded(emit, log)

    # 6. MoE dispatch: grouped (dropless sort) vs capacity buffer ---------
    bench_moe(emit, log, seed=seed)


# last arrivals-workload registry snapshot, exported to run.py --json
# under the BENCH_serve.json "metrics" key (see metrics_snapshot())
_LAST_SNAPSHOT: dict = {}


def metrics_snapshot() -> dict:
    """run.py --json hook: the arrivals-workload engine's final
    telemetry-registry snapshot (counters + SLO histograms)."""
    return _LAST_SNAPSHOT


def run(emit):
    """benchmarks/run.py suite entry (smoke scale, CSV/JSON harness).

    The shared prefix stays long relative to the decode budget — the
    system-prompt traffic shape the 1.3x gate is defined over (chunked
    cold prefill made short-prompt admission cheap enough that a short
    prefix no longer shows the cache win)."""
    run_sections(emit, batch=2, prompt_len=12, max_new=12, chunk=4,
                 trace=6, prefix_len=384, tail_len=4, prefix_max_new=8,
                 draft_k=2)


def _fault_smoke() -> int:
    """Fault-injection gate (fatal, tier-1): a disaggregated engine under
    an injected schedule (prefill-worker kill + KV page flips + transfer
    drops + stragglers) must (a) detect nonzero faults, (b) finish most
    requests, and (c) emit byte-identical tokens for every survivor vs
    the fault-free run on the same seed — the replay-recovery parity
    contract (see docs/serving.md, Faults and degradation)."""
    from repro.serve.faults import FaultInjector, FaultPlan

    cfg = get_smoke("qwen2_0_5b")
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    ctx = ModelContext(compute_dtype=jnp.float32, q_chunk=64)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (9, 13, 11)]
    mk = lambda: [Request(rid=i, prompt=p, max_new=4)
                  for i, p in enumerate(prompts)]

    def build(faults=None):
        return ServeEngine(cfg, ctx, window=32, max_batch=2, chunk=2,
                           page_size=8, disaggregate=True,
                           prefill_workers=2, faults=faults)

    failures = 0
    base = build().run(params, mk())
    inj = FaultInjector(FaultPlan(
        seed=7, worker_fail_rate=0.25, page_flip_rate=0.25,
        transfer_drop_rate=0.2, straggler_rate=0.2))
    eng = build(inj)
    out = eng.run(params, mk())
    fs = eng.fault_stats
    injected = (fs["fault_worker_failures"] + fs["fault_page_corruptions"]
                + fs["fault_transfer_drops"] + fs["fault_stragglers"])
    if injected == 0:
        print("FAILED [faults]: schedule injected nothing")
        failures += 1
    if fs["fault_page_corruptions"] > 0 and fs["fault_detections"] == 0:
        print("FAILED [faults]: page corruptions went undetected")
        failures += 1
    if len(out) < 2:
        print(f"FAILED [faults]: only {len(out)}/{len(prompts)} requests "
              "survived the schedule")
        failures += 1
    for rid, toks in out.items():
        if not np.array_equal(toks, base[rid]):
            print(f"FAILED [faults]: survivor rid {rid} diverged: "
                  f"{toks} != fault-free {base[rid]}")
            failures += 1
    print(f"smoke [faults]: {len(out)}/{len(prompts)} survivors "
          f"token-identical under "
          + " ".join(f"{k.split('fault_')[-1]}={fs[k]}" for k in (
              "fault_worker_failures", "fault_page_corruptions",
              "fault_transfer_drops", "fault_stragglers",
              "fault_detections", "retry_requeues")))
    return failures


def run_smoke() -> int:
    """Fast interpret-mode kernel-routing gate for tier-1: every paged
    serving path (chunked cold prefill, suffix prefill, spec verify,
    decode) through the Pallas kernels — fp AND int8 — must reproduce
    the jnp gather-dequant oracle engine exactly, with one span-prefill
    compile."""
    cfg = get_smoke("qwen2_0_5b")
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (9, 13)]
    failures = 0
    for cdt, tag in ((None, "fp32"), (jnp.int8, "int8")):
        kctx = ModelContext(compute_dtype=jnp.float32, q_chunk=64,
                            decode_cache_dtype=cdt,
                            attn_impl="pallas_interpret")
        octx = ModelContext(compute_dtype=jnp.float32, q_chunk=64,
                            decode_cache_dtype=cdt)
        kern = ServeEngine(cfg, kctx, window=32, max_batch=2, chunk=2,
                           page_size=8, draft_k=2)
        orac = ServeEngine(cfg, octx, window=32, max_batch=2, chunk=2,
                           page_size=8, draft_k=2)
        compiles = None
        for r in range(3):  # run 2 exercises the cached-suffix span;
            # run 3 must hit only already-compiled span programs
            reqs = lambda: [Request(rid=i, prompt=p, max_new=4)
                            for i, p in enumerate(prompts)]
            ko = kern.run(params, reqs())
            oo = orac.run(params, reqs())
            for i in range(len(prompts)):
                if not np.array_equal(ko[i], oo[i]):
                    print(f"FAILED [{tag} run {r}]: kernel {ko[i]} != "
                          f"oracle {oo[i]} (rid {i})")
                    failures += 1
            if r == 1:
                compiles = kern.counters["span_prefill_compiles"]
        if kern.counters["span_prefill_compiles"] != compiles:
            print(f"FAILED [{tag}]: span-prefill program family grew "
                  f"({compiles} -> "
                  f"{kern.counters['span_prefill_compiles']})")
            failures += 1
        print(f"smoke [{tag}]: kernel==oracle over "
              f"{sum(len(p) for p in prompts)} prompt + 8 decode tokens, "
              f"{compiles} span-prefill programs (stable)")
    # grouped-MoE gate (fatal): m-grouped GEMM kernel == jnp oracle
    # (bf16 + int8) and grouped dispatch == capacity-dropless
    failures += _moe_smoke()
    # fault-injection parity gate (fatal): survivors of an injected
    # fault schedule must match the fault-free run byte for byte
    failures += _fault_smoke()
    # sharded-parity gate (fatal): mesh decode == single-host decode,
    # disaggregated == co-located — in a forced-multi-device subprocess
    proc = _run_sharded_child("--sharded-smoke-inner")
    tail = proc.stdout.strip().splitlines()
    print(tail[-1] if tail else "(sharded child produced no output)")
    if proc.returncode != 0:
        print(f"FAILED [sharded]: child rc={proc.returncode}\n"
              f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
        failures += 1
    print("bench_serve --smoke:", "FAILED" if failures else "PASSED")
    return 1 if failures else 0


def main() -> None:
    if "--sharded-smoke-inner" in sys.argv:
        sys.exit(sharded_smoke_inner())
    if "--sharded-bench-inner" in sys.argv:
        sys.exit(sharded_bench_inner())
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_0_5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--trace", type=int, default=12)
    ap.add_argument("--prefix-len", type=int, default=448,
                    help="shared prompt prefix for section 3")
    ap.add_argument("--tail-len", type=int, default=4)
    ap.add_argument("--prefix-max-new", type=int, default=12,
                    help="decode budget for section 3 (prefill-heavy by "
                         "design: the system-prompt traffic shape)")
    ap.add_argument("--draft-k", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_serve.json")
    ap.add_argument("--smoke", action="store_true",
                    help="fast interpret-mode kernel-routing gate "
                         "(tier-1); skips the timing sections")
    args = ap.parse_args()

    if args.smoke:
        sys.exit(run_smoke())

    rows = []
    failed = []

    def emit(name, value, note=""):
        rows.append({"name": name, "value": value, "note": note})
        if "FAILED" in note:
            failed.append(name)

    run_sections(emit, arch=args.arch, batch=args.batch,
                 prompt_len=args.prompt_len, max_new=args.max_new,
                 chunk=args.chunk, trace=args.trace,
                 prefix_len=args.prefix_len, tail_len=args.tail_len,
                 prefix_max_new=args.prefix_max_new, draft_k=args.draft_k,
                 seed=args.seed, log=print)
    if args.json:
        with open("BENCH_serve.json", "w") as f:
            json.dump({"suite": "serve", "rows": rows,
                       "metrics": metrics_snapshot()}, f, indent=1,
                      default=str)
        print("wrote BENCH_serve.json")
    if failed:
        print(f"WARNING: gates failed: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
