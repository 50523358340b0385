"""The one traffic generator: every mix is a JSON file of parameters under
``chip_bench/traffic/`` that this module reads.

Serving mixes offer open-loop Poisson arrivals on the engine's step
clock (the clock ``ServeEngine.run`` compares ``Request.arrival``
with, which only decode chunks advance), in rounds: one ``run()`` call
per round. The homogeneous-rate case of ``fleet/serve_jobs.py``'s
``ArrivalProcess`` is copied here: exponential gaps at the rate.

Each round draws a fixed multiset of sizes: its prompt lengths, output
lengths and arrival gaps are the distributions' quantiles at the
midpoints (i + 0.5) / n, shuffled by the round index alone. The seed
draws the token ids. So every round carries the same work in another
order, and every seed the same rounds with other tokens: the engine's
schedule (admissions, chunks, completions on the step clock) is the
same for every seed, and runs differ by the device's and the host's
timing, not by how the seed lets the arrivals fall together.
"""

from __future__ import annotations

import json
from pathlib import Path
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
WARMUP_RID = 10 ** 9  # above every window rid


def load_mix(name: str) -> Dict[str, Any]:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator keyed on the whole seed (any size) and a stream."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), *stream])


def lengths(dist: Dict[str, Any], count: int) -> np.ndarray:
    """The ``count`` midpoint quantiles of a clipped lognormal, sorted."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / count) for i in range(count)])
    raw = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(raw, dist["min"], dist["max"]).astype(np.int64)


def exp_gaps(mean: float, count: int) -> np.ndarray:
    """The ``count`` midpoint quantiles of an exponential of ``mean``."""
    u = (np.arange(count) + 0.5) / count
    return -mean * np.log1p(-u)


def residency_steps(n_out: np.ndarray, chunk: int) -> np.ndarray:
    """Decode steps a request holds its slot: its tokens, rounded up to
    whole chunks (slots are freed only at a chunk boundary)."""
    return np.ceil(n_out / chunk) * chunk


def knee_rate(mix: Dict[str, Any]) -> float:
    """The step-clock knee, max_batch / E[slot residency in steps]: the
    arrival rate (requests per engine step) at which the batch is full
    on average. Prefill does not advance the step clock, so the knee
    does not depend on the device's speed."""
    eng = mix["engine"]
    outs = lengths(mix["output"], mix["round_requests"])
    return eng["max_batch"] / float(residency_steps(outs, eng["chunk"])
                                    .mean())


def offered_rate(mix: Dict[str, Any], scale: float = 1.0) -> float:
    return mix["arrivals"]["knee_fraction"] * scale * knee_rate(mix)


def serve_round(mix: Dict[str, Any], vocab: int, seed: int, index: int,
                rate: float) -> List[Dict[str, Any]]:
    """Round ``index``: [{rid, prompt, max_new, arrival}] sorted by
    arrival (engine steps from the round's start)."""
    n = mix["round_requests"]
    order = rng_for(0, 1, index)  # the schedule: the same for every seed
    prompts = order.permutation(lengths(mix["prompt"], n))
    outs = order.permutation(lengths(mix["output"], n))
    gaps = order.permutation(exp_gaps(1.0 / rate, n))
    arrivals = np.floor(np.cumsum(gaps)).astype(np.int64)
    ids = rng_for(seed, 1, index)
    base = index * n
    return [{"rid": base + i,
             "prompt": ids.integers(0, vocab, int(prompts[i]),
                                    dtype=np.int32),
             "max_new": int(outs[i]), "arrival": int(arrivals[i])}
            for i in range(n)]


def warmup_requests(mix: Dict[str, Any], vocab: int) -> List[Dict[str, Any]]:
    """Set-up traffic: for every remainder class of the mix's prompt
    lengths modulo the prefill chunk, one prompt of a full chunk plus that
    remainder, so every prefill program the rounds can use is compiled
    before the window; one chunk of output each. The same for every
    seed."""
    eng = mix["engine"]
    span = eng["prefill_chunk"]
    sizes = sorted({span + int(p) % span for p in
                    lengths(mix["prompt"], mix["round_requests"])})
    rng = rng_for(0, 2)
    return [{"rid": WARMUP_RID + i,
             "prompt": rng.integers(0, vocab, p, dtype=np.int32),
             "max_new": eng["chunk"], "arrival": 0}
            for i, p in enumerate(sizes)]


def train_batch(mix: Dict[str, Any], vocab: int, seed: int, step: int):
    """Step ``step``'s (tokens, labels), each (batch, seq) int32: rows of
    uniform random token ids, labels shifted by one. Every row of every
    step differs."""
    rng = rng_for(seed, 3, step)
    toks = rng.integers(0, vocab, (mix["batch"], mix["seq"] + 1),
                        dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]
