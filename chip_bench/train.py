"""Training cells: the window drives ``ResilientTrainer.run`` step by step.

Set-up builds the CLI's trainer (its compiled step and its state), puts
the weights the benchmark made from the seed into that state, gives the
trainer the benchmark's feed, and drives the first three steps through
``trainer.run`` (the first writes the trainer's bootstrap checkpoint).
In a ``--trace 1`` run the trainer is given an enabled span tracer, so
its spans name its work in set-up and in the traced window.
It reads, from the program's own state, the norms of the first clipped
gradient (AdamW's first moment after one step, over 1 - b1) and of the
weights' change over the three steps. The same trainer and state then
run the window: one ``run`` call per step, each ending in the loss's
host sync, until ``--seconds`` have passed. A ``--trace 1`` run traces
``trace_steps`` steps, and its per-layer readers read ``reading()``.

Once the window has closed and the program's state is freed, the plain
reference runs the same three steps from the same weights and batches.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
from typing import Any, Dict

import numpy as np

from chip_bench import common, program, traffic
from chip_bench import trace as tr

CHECK_STEPS = 3


class Feed:
    """The benchmark's batches, in the shape ``ResilientTrainer`` asks
    its pipeline for. ``fault="half"`` repeats each batch's first half in
    its second half (the mean is then over half the rows)."""

    def __init__(self, mix, vocab, seed, fault=None):
        self.mix, self.vocab, self.seed, self.fault = mix, vocab, seed, fault

    def batch_for_step(self, step: int) -> Dict[str, np.ndarray]:
        tokens, labels = traffic.train_batch(self.mix, self.vocab,
                                             self.seed, step)
        if self.fault == "half":
            h = tokens.shape[0] // 2
            tokens = np.concatenate([tokens[:h], tokens[:h]])
            labels = np.concatenate([labels[:h], labels[:h]])
        return {"tokens": tokens, "labels": labels}


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             counted) -> float:
    """Worst leaf's |program norm - reference norm|, over the larger of
    the reference's norm of that leaf and of the median leaf."""
    med = float(np.median([ref[k] for k in counted]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in counted)


def gaps(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The three numbers compared. Leaves whose reference gradient is
    under a thousandth of the median leaf's (nought to rounding, as the
    key bias is under softmax) are left out of both leaf numbers."""
    g = ref["first_grad"]
    med = float(np.median(list(g.values())))
    counted = [k for k in g if g[k] >= 1e-3 * med]
    return {"loss_gap": max(abs(a - b) / abs(b) for a, b in
                            zip(prog["losses"], ref["losses"])),
            "grad_gap": leaf_gap(prog["first_grad"], g, counted),
            "change_gap": leaf_gap(prog["change"], ref["change"], counted)}


def program_steps(run, ref, trainer, state, key, feed):
    """Drive the program's first CHECK_STEPS steps and read its numbers.
    Returns (state, readings)."""
    b1 = run.mix["optimizer"]["b1"]
    trainer.pipeline = feed
    state, _, losses = trainer.run(state, 1)
    m = ref.flat_floats(ref.leaf_norms(state["opt"]["m"]))
    first_grad = {k: v / (1 - b1) for k, v in m.items()}
    state, _, more = trainer.run(state, CHECK_STEPS)
    w0 = ref.make_weights(run.conf, key)
    change = ref.flat_floats(ref.leaf_diff_norms(state["params"], w0))
    del w0
    return state, {"losses": losses + more, "first_grad": first_grad,
                   "change": change}


def reference_steps(run, ref, key, dtype=None, fault=None):
    """The reference's CHECK_STEPS steps from the seeded weights, on the
    run's batches (``fault`` as in ``Feed``)."""
    import jax.numpy as jnp
    feed = Feed(run.mix, run.conf["vocab_size"], run.seed, fault)
    batches = [(b["tokens"], b["labels"]) for b in
               map(feed.batch_for_step, range(CHECK_STEPS))]
    losses, first_grad, change = ref.train_steps(
        run.conf, run.mix["optimizer"], batches, key,
        jnp.float32 if dtype is None else dtype)
    return {"losses": losses, "first_grad": first_grad, "change": change}


def fresh_state(ref, run, state, key):
    """The state with the benchmark's weights, zero moments, step 0."""
    import jax
    import jax.numpy as jnp
    state.clear()  # free the old weights and moments before the new
    gc.collect()
    params = ref.make_weights(run.conf, key)
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
    return {"params": params,
            "opt": {"m": jax.tree.map(zeros, params),
                    "v": jax.tree.map(zeros, params)},
            "step": jnp.zeros((), jnp.int32)}


def window(run, trainer, state, registry):
    """Drive the window: one ``trainer.run`` call per step, each ending
    in the loss's host sync, until ``--seconds`` have passed (a
    ``--trace 1`` run: ``trace_steps`` steps, under the profiler).
    Returns (state, the window's numbers)."""
    step = CHECK_STEPS
    step_s = []
    # traced, the call is a span of its own: the host time that
    # ``ResilientTrainer.run`` spends outside its step spans (the step
    # counter's transfer, the checkpoint listing) is then named
    run_to = (tr.annotate(trainer.run, "bench.run") if run.trace
              else trainer.run)
    counters0 = program.counters(registry)
    w0 = run.clock()
    with tr.capture(run.out_dir / "trace", run.trace, "steps"):
        while True:
            state, _, _ = run_to(state, step + 1)
            step_s.append(trainer.records[-1].duration_s)
            step += 1
            if run.trace:
                if step - CHECK_STEPS >= run.mix["trace_steps"]:
                    break
            elif run.clock() - w0 >= run.seconds:
                break
    return state, {"seconds": run.clock() - w0, "steps": step - CHECK_STEPS,
                   "step_s": step_s, "counters0": counters0,
                   "counters": program.counters(registry)}


def reading(run, summary, win, setup_spans) -> Dict[str, Any]:
    """What the per-layer readers read of a traced window: the trace
    (``Summary``, with the program's spans in the window), the model
    FLOPs of its tokens from the configuration's reference module, the
    program's counters at its end and their change over it, and the
    program's spans in set-up (``trace.chrome_spans``)."""
    ref = common.reference_module(run.conf)
    mix = run.mix
    c0, c1 = win["counters0"], win["counters"]
    delta = {k: v - c0.get(k, 0.0) for k, v in c1.items()}
    sequences = win["steps"] * mix["batch"]
    return {"kind": "train", "trace": summary, "peaks": run.peaks,
            "chips": len(run.devices), "conf": run.conf,
            "dims": ref.dims(run.conf), "seq": mix["seq"],
            "steps": win["steps"], "sequences": sequences,
            "model_flops": ref.train_flops(run.conf, mix["seq"],
                                           sequences * mix["seq"], delta),
            "counters": c1, "counters_window": delta,
            "spans": summary.spans, "setup_spans": setup_spans}


def run_cell(run: common.Run, bench: Dict[str, Any], counter) -> str:
    ref = common.reference_module(run.conf)
    mix = run.mix
    vocab = run.conf["vocab_size"]
    ckpt = run.out_dir / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)  # the trainer would resume it
    t = run.clock()
    key = common.seed_key(run.seed)
    # the program names its work (in the profiler's trace and in the
    # tracer's events) only in the traced run, so that the end-to-end
    # metrics are measured with its tracer off
    tracer = program.SpanTracer(clock=run.clock) if run.trace else None
    trainer, state, registry = program.build_trainer(
        run.conf, mix, str(ckpt), run.seed, tracer=tracer)
    t = run.mark("trainer", t)
    state = fresh_state(ref, run, state, key)
    t = run.mark("weights", t)
    if run.calibrate:
        return calibrate(run, ref, trainer, state)
    c0 = counter.seconds
    state, prog = program_steps(run, ref, trainer, state, key,
                                Feed(mix, vocab, run.seed))
    t = run.mark("first_steps_and_bootstrap_ckpt", t)
    run.setup["first_steps_compile"] = counter.seconds - c0
    # the bootstrap checkpoint's pages reach the disk now, in set-up, and
    # not while the window runs
    os.sync()
    t = run.mark("ckpt_sync", t)
    setup_s = run.clock() - run.t_start

    setup_events = list(tracer.events) if tracer else []
    compiles0 = counter.count
    state, win = window(run, trainer, state, registry)
    steps = win["steps"]
    in_window = counter.count - compiles0
    peak = common.peak_memory_bytes(run.devices)
    tokens = steps * mix["batch"] * mix["seq"]
    common.log(f"window {win['seconds']:.3f} s, {steps} steps, {tokens} "
               f"tokens; compiles in window {in_window}; set-up "
               f"{setup_s:.3f} s {run.setup}")
    step_s = win["step_s"]
    common.log(f"steps: median {np.median(step_s):.4f} s, slowest "
               f"{max(step_s):.4f} s (window step {int(np.argmax(step_s))})")
    metrics: Dict[str, Any] = {}
    device_extra: Dict[str, Any] = {"memory_peak_bytes": peak}
    breakdown = None
    if run.trace:
        summary = tr.reduce(run.out_dir / "trace", len(run.devices),
                            tr.chrome_processes(tracer.events).values())
        r = reading(run, summary, win, tr.chrome_spans(setup_events))
        for name, (read, unit) in common.per_layer_readers(
                bench, run.name).items():
            v = read(r)
            if v is not None:
                metrics[name] = {"value": v, "unit": unit}
        device_extra.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = summary.breakdown()
    else:
        metrics = {"train_tok_s": {"value": tokens / win["seconds"],
                                   "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}

    del state, trainer
    gc.collect()
    shutil.rmtree(ckpt, ignore_errors=True)
    t = run.clock()
    want = reference_steps(run, ref, key)
    common.log(f"reference steps took {run.clock() - t:.3f} s")
    values = gaps(prog, want)
    checks = common.compared(values, run.limits)
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    common.log(f"losses program {prog['losses']} reference {want['losses']}")
    common.print_checks(checks)
    return common.result_line(run, correct=correct, attempted=steps,
                              failed=0, metrics=metrics,
                              device_extra=device_extra, checks=checks,
                              breakdown=breakdown)


def calibrate(run, ref, trainer, state) -> str:
    """Readings for setting the check's limits, for ``run.calibrate``
    seeds from ``run.seed``: the program's three numbers, then, once the
    program's state is freed, those of the bfloat16 and FP8 controls and
    of half of each batch left out, each planted in the reference put in
    the program's place, all against the float32 reference. One JSON
    line per seed."""
    import jax.numpy as jnp
    mix, vocab = run.mix, run.conf["vocab_size"]
    seeds = range(run.seed, run.seed + run.calibrate)
    progs = {}
    for s in seeds:
        key = common.seed_key(s)
        state = fresh_state(ref, run, state, key)
        sub = common.Run(**{**run.__dict__, "seed": s})
        state, progs[s] = program_steps(sub, ref, trainer, state, key,
                                        Feed(mix, vocab, s))
    state.clear()
    del trainer
    gc.collect()
    lines = []
    for s in seeds:
        sub = common.Run(**{**run.__dict__, "seed": s})
        key = common.seed_key(s)
        want = reference_steps(sub, ref, key)
        line = {"seed": s, "program": gaps(progs[s], want),
                "half": gaps(reference_steps(sub, ref, key, fault="half"),
                             want),
                "losses": progs[s]["losses"], "ref_losses": want["losses"]}
        for dtype in (jnp.bfloat16, ref.FP8):
            name = "bfloat16" if dtype == jnp.bfloat16 else ref.FP8
            line[f"control_{name}"] = gaps(
                reference_steps(sub, ref, key, dtype), want)
        lines.append(json.dumps(line))
        common.log(lines[-1])
    return "\n".join(lines)
