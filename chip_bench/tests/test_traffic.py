"""The traffic generator: determinism, the fixed multiset of sizes, the
knee arithmetic, and one tiny round through the CLI's engine on the
CPU (no timing is read)."""

from __future__ import annotations

import numpy as np

from chip_bench import traffic
from chip_bench.tests.helpers import load

BIG_SEED = 2 ** 31 + 12345


def _key(reqs):
    return [(r["rid"], r["prompt"].tobytes(), r["max_new"], r["arrival"])
            for r in reqs]


def test_rounds_are_deterministic_and_differ_by_seed_and_round():
    mix = load("serve-smoke.json")
    a = traffic.serve_round(mix, 8192, BIG_SEED, 0, 0.5)
    assert _key(a) == _key(traffic.serve_round(mix, 8192, BIG_SEED, 0, 0.5))
    b = traffic.serve_round(mix, 8192, BIG_SEED + 1, 0, 0.5)
    c = traffic.serve_round(mix, 8192, BIG_SEED, 1, 0.5)
    assert _key(a) != _key(b) and _key(a) != _key(c)
    # every seed and round carries the same multiset of sizes, and
    # every seed the same schedule: sizes and arrivals in the same order
    for other in (b, c):
        assert sorted(len(r["prompt"]) for r in a) == \
            sorted(len(r["prompt"]) for r in other)
        assert sorted(r["max_new"] for r in a) == \
            sorted(r["max_new"] for r in other)
    sched = [(len(r["prompt"]), r["max_new"], r["arrival"]) for r in a]
    assert sched == [(len(r["prompt"]), r["max_new"], r["arrival"])
                     for r in b]
    assert sched != [(len(r["prompt"]), r["max_new"], r["arrival"])
                     for r in c]
    arr = [r["arrival"] for r in a]
    assert arr == sorted(arr) and arr[0] >= 0
    assert c[0]["rid"] == mix["round_requests"]


def test_lengths_are_clipped_lognormal_quantiles():
    d = {"dist": "lognormal", "median": 192, "sigma": 0.6, "min": 64,
         "max": 512}
    x = traffic.lengths(d, 1001)
    assert x[500] == 192 and x.min() >= 64 and x.max() <= 512
    assert np.all(np.diff(x) >= 0)


def test_exponential_gaps_have_the_mean():
    g = traffic.exp_gaps(3.0, 20000)
    assert abs(g.mean() - 3.0) < 0.01


def test_knee_by_hand():
    mix = load("serve-smoke.json")
    mix = dict(mix, output={"dist": "lognormal", "median": 10, "sigma": 0.0,
                            "min": 4, "max": 24})
    # every answer is 10 tokens: a slot is held ceil(10 / 4) x 4 = 12
    # steps, so 4 slots take 4 / 12 requests per step
    assert abs(traffic.knee_rate(mix) - 4 / 12) < 1e-12
    assert abs(traffic.offered_rate(mix) - 0.8 * 4 / 12) < 1e-12


def test_warmup_covers_every_remainder_class():
    mix = load("serve-smoke.json")
    warm = traffic.warmup_requests(mix, 8192)
    span = mix["engine"]["prefill_chunk"]
    want = {int(p) % span for p in traffic.lengths(mix["prompt"],
                                                   mix["round_requests"])}
    assert {len(r["prompt"]) % span for r in warm} == want
    assert min(r["rid"] for r in warm) >= traffic.WARMUP_RID


def test_train_batches():
    mix = load("train-smoke.json")
    t0, l0 = traffic.train_batch(mix, 8192, BIG_SEED, 0)
    t0b, _ = traffic.train_batch(mix, 8192, BIG_SEED, 0)
    t1, _ = traffic.train_batch(mix, 8192, BIG_SEED, 1)
    assert t0.shape == (2, 32) and np.array_equal(t0, t0b)
    assert np.array_equal(t0[:, 1:], l0[:, :-1])
    rows = {r.tobytes() for r in np.concatenate([t0, t1])}
    assert len(rows) == 4


def test_one_round_through_the_engine():
    import jax
    from chip_bench import common, program
    from chip_bench.reference import qwen2
    conf, mix = load("qwen2-smoke.json"), load("serve-smoke.json")
    params = qwen2.make_weights(conf, common.seed_key(BIG_SEED))
    engine, tracer = program.build_engine(conf, mix["engine"],
                                          lambda: 0.0)
    reqs = traffic.serve_round(mix, conf["vocab_size"], BIG_SEED, 0,
                               traffic.offered_rate(mix))
    out = engine.run(params, [program.make_request(r) for r in reqs],
                     key=jax.random.key(0))
    assert {r["rid"]: r["max_new"] for r in reqs} == \
        {rid: len(t) for rid, t in out.items()}
    for r in reqs:
        st = program.request_stamps(engine, tracer, r["rid"])
        assert set(st) == {"ready", "admit", "first", "last"}
