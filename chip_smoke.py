#!/usr/bin/env python3
"""Bring-up check: the serve and train entry points on a TPU at the full
published width of Qwen2-0.5B (24 layers, d_model 896, 14/2 heads, vocab
151936), with random weights made from ``--seed``. Nothing is downloaded.

  python3 chip_smoke.py               # one chip: BIST, serve parity, train
  python3 chip_smoke.py --four-chips  # (2, 2)-mesh serving vs one chip

One process drives every phase and stops at the first failure with a
non-zero exit. Without a TPU it exits non-zero before any phase runs. The
last line of a passing run is one JSON object naming the device.

Phases (one chip):
  a. device: the first device must be a TPU;
  b. BIST: the FBIST golden patterns and a paged-decode golden through the
     compiled Pallas kernels;
  c. serve: 8 requests (prompts of 64-512 tokens, 32 new each) through the
     CLI's engine with the Pallas kernels, paged KV and the prefix cache,
     token for token against the same engine on jnp attention;
  d. train: 4 steps of the CLI's trainer with a failure injected at step
     3; the replayed steps must reproduce the first pass's losses exactly.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2_0_5b"
# A flipped greedy token is accepted only where the reference's logits
# put the two candidates within this many logit units. Both runs compute
# in f32 under "highest" matmul precision, so they differ only in
# accumulation order (~1e-5 relative, carried through 24 layers); the
# random head spreads logits with a std near 0.6 (0.02 init over d=896),
# which leaves the top two ~0.1 apart. A kernel fault moves logits by far
# more than 5e-3, rounding by far less.
NEAR_TIE = 5e-3
SERVE = dict(n_requests=8, prompt_lo=64, prompt_hi=512, max_new=32,
             max_batch=8, chunk=8, page_size=16, prefill_chunk=128)
# 8 x 512 tokens per step: the cross-compiled step takes 5.5 GiB of
# donated state plus 4.2 GiB of temporaries on a 16 GB v5e
TRAIN = dict(batch=8, seq=512, steps=4, fail_at=3)


class SmokeFailure(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"chip_smoke FAILED: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds of XLA backend compilation, summed from JAX's monitoring
    events (these do not nest, unlike its tracing events); the rest of a
    phase's wall time is tracing, host work and the device."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.total += duration

    def phase(self, name: str, t0: float, c0: float) -> None:
        wall = time.perf_counter() - t0
        comp = self.total - c0
        print(f"[{name}] wall {wall:.1f}s = XLA compile {comp:.1f}s "
              f"+ rest {wall - comp:.1f}s", flush=True)


def reference_logits(params, cfg, tokens):
    """Last-position logits of a plain prefill on jnp attention (f32)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import api
    from repro.models.blocks import ModelContext

    ctx = ModelContext(compute_dtype=jnp.float32, q_chunk=1024)
    logits, _ = jax.jit(lambda p, t: api.prefill_fn(
        p, {"tokens": t}, cfg, ctx, window=t.shape[1]))(
            params, jnp.asarray(tokens, jnp.int32)[None])
    return np.asarray(logits[0, -1], np.float64)


def compare_streams(got, want, prompts, logits_fn, tol=NEAR_TIE):
    """Token-for-token comparison. Where a stream first differs, the
    reference logits at that position must show a near-tie between the two
    tokens. Returns [(rid, index, logit gap)] for the flips accepted."""
    import numpy as np

    ties = []
    for rid, ref in want.items():
        out = got.get(rid)
        check(out is not None and len(out) == len(ref),
              f"request {rid}: {None if out is None else len(out)} tokens, "
              f"reference {len(ref)}")
        diff = np.flatnonzero(np.asarray(out) != np.asarray(ref))
        if diff.size == 0:
            continue
        i = int(diff[0])
        logits = logits_fn(np.concatenate([prompts[rid], ref[:i]]))
        check(bool(np.isfinite(logits).all()),
              f"request {rid}: reference logits not finite")
        gap = float(logits.max() - logits[out[i]])
        check(gap <= tol,
              f"request {rid} token {i}: {out[i]} vs reference {ref[i]}, "
              f"logit gap {gap:.3e} > {tol:.0e} is no near-tie")
        ties.append((rid, i, gap))
    return ties


def serve_setup(cfg, seed, s):
    """The trace (a fresh Request list per call: the engine mutates them),
    its prompts by rid, and the engine keywords shared by both runs."""
    from repro.launch.serve import make_trace

    def reqs_fn():
        return make_trace(s["n_requests"], cfg.vocab_size, seed,
                          prompt_lo=s["prompt_lo"], prompt_hi=s["prompt_hi"],
                          new_lo=s["max_new"], new_hi=s["max_new"])

    prompts = {r.rid: r.prompt for r in reqs_fn()}
    kw = dict(window=s["prompt_hi"] + s["max_new"],
              max_batch=s["max_batch"], chunk=s["chunk"],
              page_size=s["page_size"], prefill_chunk=s["prefill_chunk"])
    return reqs_fn, prompts, kw


def check_served(out, n_requests, max_new, vocab):
    import numpy as np
    check(len(out) == n_requests,
          f"{len(out)} of {n_requests} requests completed")
    for rid, toks in out.items():
        check(len(toks) == max_new,
              f"request {rid}: {len(toks)} of {max_new} tokens")
        check(bool(np.all((toks >= 0) & (toks < vocab))),
              f"request {rid}: token outside the vocabulary")


def lowered_has_kernel(engine, params) -> bool:
    """Whether the decode chunk and the span prefill lower to Pallas
    custom calls (``tpu_custom_call``) rather than jnp attention."""
    import jax
    import jax.numpy as jnp
    kv = engine.kv
    chunk = engine._run_chunk.lower(
        params, kv.table_device(), engine._tok, engine._pos, engine._done,
        engine._n_out, engine._max_new, jax.random.key(0), jnp.float32(0),
        jnp.int32(0), kv.pages).as_text()
    span = jnp.zeros((1, engine.span_len), jnp.int32)
    prefill = engine._prefill_span.lower(
        params, kv.pages, span, kv.table_row(0), jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), jnp.int32), jax.random.key(0), jnp.float32(0),
        None).as_text()
    return "tpu_custom_call" in chunk and "tpu_custom_call" in prefill


def phase_bist(clock) -> None:
    from repro.serve.faults import startup_bist
    t0, c0 = time.perf_counter(), clock.total
    res = startup_bist(interpret=False)
    print(f"[bist] matmul max_err={res.matmul_report.max_abs_err:.3e} "
          f"paged_decode max_err={res.paged_decode_max_err:.3e} "
          f"-> {'PASS' if res.passed else 'FAIL'}", flush=True)
    clock.phase("bist", t0, c0)
    check(res.passed, "BIST: kernel self-test mismatch")


def phase_serve(clock, cfg, params, seed, *, impl=None, serve=SERVE):
    """The CLI's engine (kernels picked from the platform unless ``impl``
    names them) against the same engine on jnp attention, both under
    "highest" matmul precision. Returns the candidate's tokens and the
    prompts, by request id."""
    import jax
    from repro.launch.serve import build_engine

    s = serve
    reqs_fn, prompts, kw = serve_setup(cfg, seed, s)
    key = jax.random.key(seed)
    with jax.default_matmul_precision("highest"):
        t0, c0 = time.perf_counter(), clock.total
        engine = build_engine(cfg, attn_impl=impl, **kw)
        got = engine.run(params, reqs_fn(), key=key)
        clock.phase(f"serve {engine.ctx.attn_impl}", t0, c0)
        check_served(got, s["n_requests"], s["max_new"], cfg.vocab_size)
        if jax.default_backend() == "tpu":
            check(engine.ctx.attn_impl == "pallas",
                  f"engine picked {engine.ctx.attn_impl!r} on a TPU")
            check(lowered_has_kernel(engine, params),
                  "served programs hold no tpu_custom_call")
        print(f"[serve] {len(got)} requests, "
              f"{sum(len(v) for v in got.values())} tokens, prompts "
              f"{sum(len(p) for p in prompts.values())} tokens, "
              f"prefix_hit_rate={engine.prefix_hit_rate:.2f} "
              f"host_syncs={engine.counters['host_syncs']}", flush=True)
        del engine
        t0, c0 = time.perf_counter(), clock.total
        ref_engine = build_engine(cfg, attn_impl="xla", **kw)
        want = ref_engine.run(params, reqs_fn(), key=key)
        clock.phase("serve xla reference", t0, c0)
        del ref_engine
        ties = compare_streams(
            got, want, prompts,
            lambda toks: reference_logits(params, cfg, toks))
    same = sum(1 for rid in want if (got[rid] == want[rid]).all())
    print(f"[serve] parity: {same}/{len(want)} streams identical, "
          f"near-tie flips {ties} (tolerance {NEAR_TIE:.0e} logits)",
          flush=True)
    return got, prompts


def phase_train(clock, cfg, seed, out_dir: Path, *, train=TRAIN) -> None:
    """The CLI's trainer at full width: a failure at ``fail_at`` restores
    the bootstrap checkpoint and replays steps 0..fail_at-1, which must
    reproduce the first pass's losses bit for bit."""
    import numpy as np
    from repro.launch.train import build_trainer

    ckpt = out_dir / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)  # a restart would resume it
    t0, c0 = time.perf_counter(), clock.total
    trainer, state = build_trainer(
        cfg, batch=train["batch"], seq=train["seq"], ckpt_dir=str(ckpt),
        checkpoint_every=10 * train["steps"], seed=seed,
        failures={train["fail_at"]: 0})
    state, _, losses = trainer.run(state, train["steps"])
    del state
    clock.phase("train", t0, c0)
    shutil.rmtree(ckpt, ignore_errors=True)
    first = {r.step: r.loss for r in trainer.records if not r.replayed}
    replay = {r.step: r.loss for r in trainer.records if r.replayed}
    print(f"[train] batch {train['batch']} x seq {train['seq']}, losses "
          f"{losses}, replayed {sorted(replay)}", flush=True)
    check(len(losses) == train["steps"],
          f"{len(losses)} of {train['steps']} steps")
    check(bool(np.isfinite(losses).all()), "non-finite loss")
    check(sorted(replay) == list(range(train["fail_at"])),
          f"replayed steps {sorted(replay)}")
    check(all(replay[s] == first[s] for s in replay),
          f"replay diverged: {replay} vs first pass {first}")


def phase_four_chips(clock, cfg, params, seed, devices, *, serve=SERVE):
    """Sharded serving on a (2, 2) mesh: kv=2 heads split over "model"
    (true KV tensor parallelism), token for token against one chip."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import build_engine

    s = serve
    reqs_fn, prompts, kw = serve_setup(cfg, seed, s)
    key = jax.random.key(seed)
    with jax.default_matmul_precision("highest"):
        t0, c0 = time.perf_counter(), clock.total
        solo = build_engine(cfg, **kw)
        want = solo.run(params, reqs_fn(), key=key)
        clock.phase("serve one chip", t0, c0)
        check_served(want, s["n_requests"], s["max_new"], cfg.vocab_size)
        del solo
        gc.collect()
        t0, c0 = time.perf_counter(), clock.total
        mesh = make_mesh((2, 2), ("data", "model"))
        shard = build_engine(cfg, mesh=mesh, **kw)
        sparams = shard.shard_params(params)
        got = shard.run(sparams, reqs_fn(), key=key)
        clock.phase("serve (2, 2) mesh", t0, c0)
        check_served(got, s["n_requests"], s["max_new"], cfg.vocab_size)
        rep = shard.sharding_report
        print(f"[four-chips] mesh={rep['mesh']} "
              f"dropped_rules={rep['dropped_rules']} "
              f"pool={jax.tree.leaves(shard.kv.pages)[0].sharding.spec}",
              flush=True)
        if jax.default_backend() == "tpu":
            check(shard.ctx.attn_impl == "pallas"
                  and lowered_has_kernel(shard, sparams),
                  "sharded programs hold no tpu_custom_call")
            # the one-chip params stay on device 0; devices 1-3 hold only
            # their shards of the sharded copy and of the page pool
            param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
            in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
            print(f"[four-chips] bytes_in_use per device {in_use} "
                  f"(unsharded params {param_bytes})", flush=True)
            check(min(in_use[1:]) >= param_bytes // 4,
                  "sharded state does not spread over the four devices")
        ties = compare_streams(
            got, want, prompts,
            lambda toks: reference_logits(params, cfg, toks))
    same = sum(1 for rid in want if (got[rid] == want[rid]).all())
    print(f"[four-chips] parity: {same}/{len(want)} streams identical, "
          f"near-tie flips {ties}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only (2, 2)-mesh sharded serving and the "
                         "one-chip engine it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "smoke_out"),
                    help="output directory (the trainer's checkpoints)")
    args = ap.parse_args()

    check((ROOT / "src" / "repro").is_dir(),
          f"no repository next to {Path(__file__).name}: "
          f"{ROOT / 'src' / 'repro'} is missing")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"no TPU found: JAX's first device is {dev.platform!r}")
    need = 4 if args.four_chips else 1
    check(len(devices) >= need, f"{need} chips needed, {len(devices)} found")
    print(f"device: {dev.device_kind} x {len(devices)} "
          f"(platform {dev.platform}, jax {jax.__version__})", flush=True)

    from repro.configs.registry import get_arch
    from repro.launch.runtime import use_compile_cache
    from repro.models import api
    from repro.models.params import init_params

    print(f"compile cache: {use_compile_cache()}", flush=True)
    clock = CompileClock()
    cfg = get_arch(ARCH)
    params = init_params(jax.random.key(args.seed), api.model_specs(cfg))
    if args.four_chips:
        phase_four_chips(clock, cfg, params, args.seed, devices[:4])
    else:
        phase_bist(clock)
        phase_serve(clock, cfg, params, args.seed)
        del params
        gc.collect()
        phase_train(clock, cfg, args.seed, Path(args.out))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
