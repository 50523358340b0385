"""The serving harness's stamps from the engine, on a clock that counts
its own calls: the stamp tracer is disabled, so the engine's spans take
no clock sample and the stamps stay where they were before the engine
had spans; the engine's public ``request_times`` agrees with them."""

from __future__ import annotations

from chip_bench import common, program, traffic
from chip_bench.tests.helpers import load

BIG_SEED = 2 ** 31 + 12345

# {rid: (ready, admit, first, last)}: the smoke round on the counting
# clock, read from the engine before it named its host work in spans
STAMPS = {0: (3.0, 4.0, 11.0, 12.0), 1: (3.0, 6.0, 11.0, 33.0),
          2: (3.0, 8.0, 11.0, 16.0), 3: (20.0, 21.0, 24.0, 41.0),
          4: (28.0, 29.0, 32.0, 55.0), 5: (34.0, 35.0, 40.0, 51.0),
          6: (34.0, 37.0, 40.0, 47.0), 7: (42.0, 43.0, 46.0, 56.0)}


class Counting:
    """A clock that reads one more second at every call."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def test_stamps_unchanged_and_public_times_agree():
    import jax
    from chip_bench.reference import qwen2
    conf, mix = load("qwen2-smoke.json"), load("serve-smoke.json")
    params = qwen2.make_weights(conf, common.seed_key(BIG_SEED))
    engine, tracer = program.build_engine(conf, mix["engine"], Counting())
    reqs = traffic.serve_round(mix, conf["vocab_size"], BIG_SEED, 0,
                               traffic.offered_rate(mix))
    engine.run(params, [program.make_request(r) for r in reqs],
               key=jax.random.key(0))
    got = {}
    for r in reqs:
        st = program.request_stamps(engine, tracer, r["rid"])
        got[r["rid"]] = (st["ready"], st["admit"], st["first"], st["last"])
    assert got == STAMPS
    times = engine.request_times()
    assert sorted(times) == sorted(STAMPS)
    for rid, (ready, admit, first, last) in STAMPS.items():
        t = times[rid]
        assert (t["ready"], t["admit"], t["first"]) == (ready, admit, first)
        # the drain that finished it, before the stamp tracer's own read
        assert first <= t["last"] < last
