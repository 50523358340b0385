"""Serving cells: the window drives ``ServeEngine.run`` round after round.

Set-up makes the weights on the device from the seed, builds the CLI's
engine, and drains one warm-up round that compiles every program the
rounds use. The window then drains whole rounds (``serve_round``) until
``--seconds`` have passed; rates and tails are taken over all of them.
A ``--trace 1`` run traces the first round alone and reports the
per-layer metrics from it.

Once the window has closed and the engine is freed, the check runs the
plain reference over a sample of the finished requests (drawn from the
seed, the longest always in it): for every served token, how far its
logit lies below the reference's best at that position.
"""

from __future__ import annotations

import gc
from typing import Any, Dict, List

import numpy as np

from chip_bench import common, flops, program, traffic
from chip_bench import trace as tr


def _round_stats(engine, tracer, reqs, made, out, t0, t1, chunks0):
    """Per-request records of one drained round (``made``: the Request
    objects the engine drained)."""
    recs = []
    for r, m in zip(reqs, made):
        toks = out.get(r["rid"])
        rec = {"rid": r["rid"], "prompt": r["prompt"],
               "max_new": r["max_new"], "tokens": toks, "ok": False}
        if toks is not None:
            st = program.request_stamps(engine, tracer, r["rid"])
            rec.update(st)
            rec["cached"] = m.cached_prefix_len
            rec["ok"] = len(toks) == r["max_new"]
        recs.append(rec)
    stats = {"wall": t1 - t0,
             "chunks": engine.counters["chunks"] - chunks0,
             "occupancy": list(engine.scheduler._occupancy)}
    return recs, stats


def ramp_share(occ: List[float]) -> float:
    """Share of a round's chunks before the batch first reaches 90% of
    its median occupancy or after it last does: ramp-up plus drain."""
    if not occ:
        return 0.0
    med = float(np.median(occ))
    hi = [i for i, o in enumerate(occ) if o >= 0.9 * med]
    return 1.0 - (hi[-1] - hi[0] + 1) / len(occ)


def queue_growth(recs) -> float:
    """Mean admit wait (s) of a round's last third of requests minus its
    first third's: positive when the queue grows through the round."""
    waits = [r["admit"] - r["ready"] for r in recs if "admit" in r]
    if len(waits) < 3:
        return 0.0
    k = len(waits) // 3
    return float(np.mean(waits[-k:]) - np.mean(waits[:k]))


def check_served(run, ref, recs, controls=()) -> Dict[str, Any]:
    """The check's readings over a sample of the finished requests:
    ``max_logit_gap``, the widest gap by which a served token's logit
    lies below the reference's best at its position; ``mean_logit_gap``,
    the mean of those gaps over every served token of the sample (0 where
    the served token is the reference's best); and ``flips``, the number
    of served tokens with a gap. With ``controls`` (calibration), the
    same readings for the tokens each reference precision puts first,
    and for the served tokens with each request's first decoded token
    altered (``fault_altered``)."""
    mix = run.mix
    done = [r for r in recs if r["ok"]]
    rng = traffic.rng_for(run.seed, 4)
    k = min(mix["check"]["sample_requests"], len(done))
    longest = max(range(len(done)),
                  key=lambda i: len(done[i]["prompt"]) + len(done[i]["tokens"]))
    others = [i for i in rng.permutation(len(done)) if i != longest]
    pick = [longest] + others[:k - 1]
    pad_to = -(-mix["engine"]["window"] // ref.Q_BLOCK) * ref.Q_BLOCK
    rows_to = mix["output"]["max"]
    key = common.seed_key(run.seed)
    w = ref.make_weights(run.conf, key)
    seqs, served = [], []
    for i in pick:
        r = done[i]
        p, t = np.asarray(r["prompt"]), np.asarray(r["tokens"])
        seq = np.concatenate([p, t])
        rows = np.arange(len(p) - 1, len(p) - 1 + len(t))
        seqs.append((seq, rows,
                     ref.served_logits(w, run.conf, seq, rows, pad_to,
                                       rows_to)))
        served.append(t)
    del w

    def reading(tokens):
        g = np.concatenate([lg.max(-1) - lg[np.arange(len(t)), t]
                            for (_, _, lg), t in zip(seqs, tokens)])
        return {"max_logit_gap": float(g.max()),
                "mean_logit_gap": float(g.mean()),
                "flips": int((g > 0).sum())}

    out = {"served_tokens_checked": sum(len(t) for t in served)}
    out.update(reading(served))
    if controls:
        vocab = run.conf["vocab_size"]
        altered = [np.concatenate([t[:1], (t[1:2] + 1) % vocab, t[2:]])
                   for t in served]
        out["fault_altered"] = reading(altered)
    for dtype in controls:
        wl = ref.make_weights(run.conf, key, ref.storage(dtype))
        tops = [ref.served_logits(wl, run.conf, seq, rows, pad_to, rows_to,
                                  dtype).argmax(-1)
                for seq, rows, _ in seqs]
        del wl
        name = np.dtype(dtype).name if dtype != ref.FP8 else ref.FP8
        out[f"control_{name}"] = reading(tops)
    return out


def run_cell(run: common.Run, bench: Dict[str, Any], counter) -> str:
    import jax
    ref = common.reference_module(run.conf)
    mix = run.mix
    vocab = run.conf["vocab_size"]
    t = run.clock()
    key = common.seed_key(run.seed)
    params = ref.make_weights(run.conf, key)
    jax.block_until_ready(params)
    t = run.mark("weights", t)
    engine, tracer = program.build_engine(run.conf, mix["engine"], run.clock)
    t = run.mark("engine", t)
    c0 = counter.seconds
    warm = [program.make_request(r)
            for r in traffic.warmup_requests(mix, vocab)]
    engine.run(params, warm, key=key)
    t = run.mark("warmup", t)
    run.setup["warmup_compile"] = counter.seconds - c0
    rate = traffic.offered_rate(mix, run.rate_scale)
    setup_s = run.clock() - run.t_start

    if run.calibrate:
        return calibrate(run, ref, engine, params, rate, key)

    if run.trace:
        engine._admit_into_slot = tr.annotate(engine._admit_into_slot,
                                              "bench.admit_and_prefill")
        engine._run_chunk = tr.annotate(engine._run_chunk,
                                        "bench.chunk_dispatch")
        engine.kv.table_device = tr.annotate(engine.kv.table_device,
                                             "bench.page_table_upload")
    compiles0 = counter.count
    recs_all, rounds = [], []
    w0 = run.clock()
    idx = 0
    trace_dir = run.out_dir / "trace"
    while True:
        reqs = traffic.serve_round(mix, vocab, run.seed, idx, rate)
        c0 = engine.counters["chunks"]
        made = [program.make_request(r) for r in reqs]
        with tr.capture(trace_dir, run.trace, f"round {idx}"):
            t0 = run.clock()
            out = engine.run(params, made, key=key)
            t1 = run.clock()
        recs, stats = _round_stats(engine, tracer, reqs, made, out, t0, t1,
                                   c0)
        stats["queue_growth"] = queue_growth(recs)
        recs_all += recs
        rounds.append(stats)
        idx += 1
        if run.trace or run.clock() - w0 >= run.seconds:
            break
    window_s = run.clock() - w0
    in_window = counter.count - compiles0
    peak = common.peak_memory_bytes(run.devices)
    for i, s in enumerate(rounds):
        common.log(f"round {i}: {s['wall']:.3f} s, {s['chunks']} chunks, "
                   f"ramp+drain share {ramp_share(s['occupancy']):.3f}, "
                   f"queue growth {s['queue_growth']:.4f} s")
    common.log(f"rate {rate:.4f} req/step ({run.rate_scale} x "
               f"{mix['arrivals']['knee_fraction']} of the knee "
               f"{traffic.knee_rate(mix):.4f}); compiles in window "
               f"{in_window}; set-up {setup_s:.3f} s {run.setup}")

    done = [r for r in recs_all if r["ok"]]
    attempted, failed = len(recs_all), len(recs_all) - len(done)
    metrics: Dict[str, Any] = {}
    device_extra: Dict[str, Any] = {"memory_peak_bytes": peak}
    breakdown = None
    if run.trace:
        summary = tr.reduce(trace_dir, len(run.devices))
        reading = serve_reading(run, summary, recs_all, rounds)
        for name, (read, unit) in common.per_layer_readers(
                bench, run.name).items():
            v = read(reading)
            if v is not None:
                metrics[name] = {"value": v, "unit": unit}
        device_extra.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = summary.breakdown()
    else:
        gen = sum(len(r["tokens"]) for r in done)
        ttft = [r["first"] - r["ready"] for r in done]
        tpot = [(r["last"] - r["first"]) / (len(r["tokens"]) - 1)
                for r in done if len(r["tokens"]) > 1]
        metrics = {
            "gen_tok_s": {"value": gen / window_s, "unit": "tokens/s"},
            "ttft_p95_s": {"value": common.percentile(ttft, 95),
                           "unit": "s"},
            "tpot_p95_s": {"value": common.percentile(tpot, 95),
                           "unit": "s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        common.log(f"window {window_s:.3f} s, {len(rounds)} rounds, "
                   f"{len(done)} requests, {gen} tokens")

    del engine, params, out
    gc.collect()
    t = run.clock()
    got = check_served(run, ref, recs_all)
    values = {"max_logit_gap": got["max_logit_gap"],
              "mean_logit_gap": got["mean_logit_gap"],
              "unfinished": failed}
    checks = common.compared(values, run.limits)
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    common.log(f"checked {got['served_tokens_checked']} served tokens in "
               f"{run.clock() - t:.3f} s")
    common.print_checks(checks)
    return common.result_line(run, correct=correct, attempted=attempted,
                              failed=failed, metrics=metrics,
                              device_extra=device_extra, checks=checks,
                              breakdown=breakdown)


def serve_reading(run, summary, recs, rounds) -> Dict[str, Any]:
    """What a per-layer reader of a serving cell may read."""
    ref = common.reference_module(run.conf)
    n = ref.dims(run.conf)
    chunk = run.mix["engine"]["chunk"]
    span = run.mix["engine"]["prefill_chunk"]
    pre = dec = 0
    d_ctx, spans = [], []
    prompt_tokens = 0
    for r in recs:
        if r["tokens"] is None:
            continue
        p, c, g = len(r["prompt"]), r["cached"], len(r["tokens"])
        a, b = flops.serve_request_flops(n, p, c, g)
        pre, dec = pre + a, dec + b
        d_ctx.extend(flops.decode_contexts(p, g))
        spans.extend(flops.prefill_spans(p, c, span))
        prompt_tokens += p - c
    occ = [o for s in rounds for o in s["occupancy"]]
    return {"kind": "serve", "trace": summary, "peaks": run.peaks,
            "chips": len(run.devices), "dims": n,
            "model_flops": pre + dec, "prefill_flops": pre,
            # per call: the least time of a set of calls is the sum of
            # each call's own bound
            "decode_work": [flops.paged_decode_work(n, [c], 4)
                            for c in d_ctx],
            "span_work": [flops.paged_span_work(n, [sp], 4) for sp in spans],
            "decode_steps": sum(s["chunks"] for s in rounds) * chunk,
            "prompt_tokens": prompt_tokens,
            "occupancy": float(np.mean(occ)) if occ else None}


def calibrate(run, ref, engine, params, rate, key) -> str:
    """Readings for setting the check's limit: for ``run.calibrate``
    seeds from ``run.seed``, one round at the cell's load through the
    same engine, then the check's readings for the program, for the
    bfloat16 and FP8 controls and for an altered token, on the same
    sample. One JSON line per seed."""
    import json

    import jax.numpy as jnp
    mix = run.mix
    vocab = run.conf["vocab_size"]
    lines = []
    for s in range(run.seed, run.seed + run.calibrate):
        k = common.seed_key(s)
        del params
        gc.collect()
        params = ref.make_weights(run.conf, k)
        reqs = traffic.serve_round(mix, vocab, s, 0, rate)
        out = engine.run(params, [program.make_request(r) for r in reqs],
                         key=k)
        recs = [{"rid": r["rid"], "prompt": r["prompt"],
                 "tokens": out.get(r["rid"]),
                 "ok": out.get(r["rid"]) is not None
                 and len(out[r["rid"]]) == r["max_new"]} for r in reqs]
        sub = common.Run(**{**run.__dict__, "seed": s})
        got = check_served(sub, ref, recs,
                           controls=(jnp.bfloat16, ref.FP8))
        got.update(seed=s, unfinished=sum(not r["ok"] for r in recs))
        lines.append(json.dumps(got))
        common.log(lines[-1])
    return "\n".join(lines)
