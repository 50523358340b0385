"""Prefix caching + self-speculative decoding on the paged KV pool:
cached-vs-cold parity, copy-on-write isolation under eviction, LRU
index behavior, speculative accept/reject parity against greedy decode,
the k-token span kernel, and the prompt-lookup drafter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke
from repro.kernels import ops
from repro.models import api
from repro.models.blocks import ModelContext
from repro.models.params import init_params
from repro.serve.engine import ServeEngine
from repro.serve.kv_cache import PagedKVCache
from repro.serve.scheduler import Request

jax.config.update("jax_default_matmul_precision", "highest")

CTX = ModelContext(compute_dtype=jnp.float32, q_chunk=64)


@pytest.fixture(scope="module")
def qwen():
    cfg = get_smoke("qwen2_0_5b")
    params = init_params(jax.random.key(0), api.model_specs(cfg))
    return cfg, params


def cold_ref(cfg, params, prompt, max_new, window=64):
    """Solo greedy run with no prefix cache and no speculation."""
    eng = ServeEngine(cfg, CTX, window=window, max_batch=1, chunk=4,
                      page_size=8, prefix_cache=False)
    return eng.run(params, [Request(rid=0, prompt=prompt,
                                    max_new=max_new)])[0]


# -------------------------------------------------------- prefix caching


def test_prefix_cache_warm_rerun_matches_cold(qwen):
    """A re-submitted prompt must skip the cached full pages (suffix-only
    prefill) and still reproduce the cold greedy tokens exactly — the
    cached KV pages hold the same values a fresh prefill would compute."""
    cfg, params = qwen
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 21)
    ref = cold_ref(cfg, params, prompt, 10)
    eng = ServeEngine(cfg, CTX, window=64, max_batch=2, chunk=4,
                      page_size=8)
    o1 = eng.run(params, [Request(rid=0, prompt=prompt, max_new=10)])[0]
    o2 = eng.run(params, [Request(rid=0, prompt=prompt, max_new=10)])[0]
    np.testing.assert_array_equal(o1, ref)
    np.testing.assert_array_equal(o2, ref)
    # 21 tokens = 2 full pages of 8 cached + 5-token suffix prefilled
    assert eng.counters["cached_prompt_tokens"] == 16
    assert eng.counters["suffix_prefills"] == 1
    assert eng.prefix_hit_rate == pytest.approx(16 / 42)


def test_prefix_cache_same_boundary_sharing(qwen):
    """Identical prompts admitted in one scheduling boundary: the first
    registers its pages, the rest share them immediately."""
    cfg, params = qwen
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, 21)
    ref = cold_ref(cfg, params, prompt, 10)
    eng = ServeEngine(cfg, CTX, window=64, max_batch=3, chunk=4,
                      page_size=8)
    out = eng.run(params, [Request(rid=i, prompt=prompt, max_new=10)
                           for i in range(3)])
    for i in range(3):
        np.testing.assert_array_equal(out[i], ref)
    assert eng.counters["suffix_prefills"] == 2  # rid 1 and 2
    assert eng.kv.counters["pages_shared"] == 4  # 2 pages x 2 sharers


def test_prefix_cache_partial_prefix_hit(qwen):
    """Prompts sharing only a prefix hit exactly the page-aligned shared
    region; the divergent tails stay private (CoW-safe by construction)."""
    cfg, params = qwen
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab_size, 16)  # 2 full pages
    pa = np.concatenate([shared, rng.integers(0, cfg.vocab_size, 5)])
    pb = np.concatenate([shared, rng.integers(0, cfg.vocab_size, 7)])
    eng = ServeEngine(cfg, CTX, window=64, max_batch=2, chunk=4,
                      page_size=8)
    oa = eng.run(params, [Request(rid=0, prompt=pa, max_new=8)])[0]
    ob = eng.run(params, [Request(rid=0, prompt=pb, max_new=8)])[0]
    np.testing.assert_array_equal(oa, cold_ref(cfg, params, pa, 8))
    np.testing.assert_array_equal(ob, cold_ref(cfg, params, pb, 8))
    # the second admission hit exactly the 16 shared-prefix tokens
    assert eng.counters["cached_prompt_tokens"] == 16


def test_multiturn_followup_hits_generated_pages(qwen):
    """Completion publishes generated pages too: a follow-up turn whose
    prompt extends (prompt + response) reuses them."""
    cfg, params = qwen
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab_size, 11)
    eng = ServeEngine(cfg, CTX, window=64, max_batch=1, chunk=4,
                      page_size=8)
    first = eng.run(params, [Request(rid=0, prompt=prompt, max_new=13)])[0]
    follow = np.concatenate([prompt, first,
                             rng.integers(0, cfg.vocab_size, 6)])
    out = eng.run(params, [Request(rid=0, prompt=follow, max_new=8)])[0]
    np.testing.assert_array_equal(out, cold_ref(cfg, params, follow, 8))
    # 11 + 13 = 24 tokens of turn one -> 3 full pages cached
    assert eng.counters["cached_prompt_tokens"] == 24


def test_prefix_cache_with_eviction_and_preemption_parity(qwen):
    """Shared-prefix traffic through a pool too small to keep everything:
    LRU eviction of cached pages and (possibly) preemption must never
    corrupt a sharer — every output equals its solo cold run."""
    cfg, params = qwen
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_size, 16)
    ps = [np.concatenate([shared, rng.integers(0, cfg.vocab_size, n)])
          for n in (3, 5, 2, 7, 4)]
    eng = ServeEngine(cfg, CTX, window=64, max_batch=3, chunk=4,
                      page_size=8, num_pages=14)
    out = eng.run(params, [Request(rid=i, prompt=p, max_new=14)
                           for i, p in enumerate(ps)])
    assert eng.kv.counters["pages_evicted"] >= 1
    for i, p in enumerate(ps):
        np.testing.assert_array_equal(out[i], cold_ref(cfg, params, p, 14))


def test_prefix_cache_int8_pages(qwen):
    """int8 page quantization composes with sharing: a warm rerun equals
    the int8 cold run bit-for-bit (same quantized pages are reused)."""
    cfg, params = qwen
    ctx8 = ModelContext(compute_dtype=jnp.float32, q_chunk=64,
                        decode_cache_dtype=jnp.int8)
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, 19)
    cold = ServeEngine(cfg, ctx8, window=48, max_batch=1, chunk=4,
                       page_size=8, prefix_cache=False)
    ref = cold.run(params, [Request(rid=0, prompt=prompt, max_new=10)])[0]
    eng = ServeEngine(cfg, ctx8, window=48, max_batch=1, chunk=4,
                      page_size=8)
    o1 = eng.run(params, [Request(rid=0, prompt=prompt, max_new=10)])[0]
    o2 = eng.run(params, [Request(rid=0, prompt=prompt, max_new=10)])[0]
    np.testing.assert_array_equal(o1, ref)
    np.testing.assert_array_equal(o2, ref)
    assert eng.counters["cached_prompt_tokens"] == 16


# ------------------------------------------------ copy-on-write / index


def _unit_kv(cfg, num_pages=8, page_size=4, max_batch=2):
    return PagedKVCache(cfg, CTX, num_pages=num_pages, page_size=page_size,
                        max_batch=max_batch, max_pages_per_seq=4)


def _copy_fn(pages, src, dst):
    return {sl: {n: a.at[:, dst].set(a[:, src]) for n, a in sub.items()}
            for sl, sub in pages.items()}


def test_cow_fork_isolates_writers(qwen):
    """fork() must give the writer a private copy: mutating the forked
    page leaves the shared original (and its other sharer) untouched."""
    cfg, _ = qwen
    kv = _unit_kv(cfg)
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, cfg.vocab_size, 4)  # one full page
    assert kv.grow(0, 5)
    src = int(kv._table[0, 0])
    # stamp recognizable content into the page
    kv.pages = jax.tree.map(lambda a: a.at[:, src].set(1.0), kv.pages)
    assert kv.register_prefix(0, np.append(tokens, 0)) == 1
    cached, pids = kv.lookup_prefix(np.append(tokens, 0))
    assert cached == 4 and pids == [src]
    kv.adopt_prefix(1, pids)
    assert kv._ref[src] == 2
    assert kv.ensure_private(1, 0, _copy_fn)  # forces the fork
    new = int(kv._table[1, 0])
    assert new != src and kv._ref[src] == 1 and kv._ref[new] == 1
    assert kv.counters["pages_forked"] == 1
    # write through the fork; the original must keep its content
    kv.pages = jax.tree.map(lambda a: a.at[:, new].set(-2.0), kv.pages)
    leaf = kv.pages[next(iter(kv.pages))]["k"]
    assert float(jnp.min(leaf[:, src])) == 1.0
    assert float(jnp.max(leaf[:, new])) == -2.0


def test_lru_eviction_spares_referenced_pages(qwen):
    """Allocation under pressure evicts only cached pages with refcount
    zero, least-recently-used first; referenced pages are never stolen."""
    cfg, _ = qwen
    kv = _unit_kv(cfg, num_pages=5, page_size=4)  # 4 usable pages
    rng = np.random.default_rng(8)
    ta = rng.integers(0, cfg.vocab_size, 4)
    tb = rng.integers(0, cfg.vocab_size, 4)
    assert kv.grow(0, 4)
    kv.register_prefix(0, np.append(ta, 0))
    pa = int(kv._table[0, 0])
    kv.release(0)  # ref 0, stays cached
    assert kv.grow(0, 4)
    kv.register_prefix(0, np.append(tb, 0))
    pb = int(kv._table[0, 0])
    assert kv.lookup_prefix(np.append(ta, 0))[0] == 4  # refresh A's LRU
    kv.release(0)
    # two cached pages (A newer tick), two free; demand all four: the
    # cached ones are evicted (B first: least recently used)
    assert kv.grow(1, 16)
    assert kv.counters["pages_evicted"] == 2
    assert kv.lookup_prefix(np.append(ta, 0))[0] == 0  # both gone
    assert kv.lookup_prefix(np.append(tb, 0))[0] == 0
    # everything referenced now: a fifth page does not exist
    assert not kv.grow(0, 4)
    assert kv._ref[pa] >= 0 and kv._ref[pb] >= 0


def test_abort_adoption_rolls_back_hit_counters(qwen):
    """An admission that adopts cached pages but then fails grow() must
    not leave its lookup/share counter bumps behind — retries would
    inflate the reported hit metrics arbitrarily."""
    cfg, _ = qwen
    kv = _unit_kv(cfg)
    rng = np.random.default_rng(18)
    tokens = rng.integers(0, cfg.vocab_size, 9)  # 2 full pages + 1
    assert kv.grow(0, 9)
    kv.register_prefix(0, tokens)
    kv.release(0)
    before = dict(kv.counters)
    cached, pids = kv.lookup_prefix(tokens)
    assert cached == 8
    kv.adopt_prefix(1, pids)
    kv.abort_adoption(1, cached, pids)
    assert kv.counters == before
    assert kv.slot_pages(1) == [] and int(kv._frontier[1]) == 0
    # the pages are still cached: a later retry hits again
    assert kv.lookup_prefix(tokens)[0] == 8


def test_lookup_verifies_block_tokens_on_hash_collision(qwen):
    """The chain hash is a 64-bit filter, not a proof: a colliding index
    entry with different block tokens must not serve its pages."""
    cfg, _ = qwen
    kv = _unit_kv(cfg)
    rng = np.random.default_rng(17)
    tokens = rng.integers(0, cfg.vocab_size, 5)
    h = kv.prefix_hashes(tokens)[0]
    # forge a colliding entry: same chain hash, different content
    assert kv.grow(0, 4)
    pid = int(kv._table[0, 0])
    kv._published[pid] = h
    kv._index[h] = (pid, ("not", "these", "tokens", "!"))
    cached, pids = kv.lookup_prefix(tokens)
    assert cached == 0 and pids == []


def test_chain_hash_certifies_whole_prefix(qwen):
    """The block hash chains through ancestors: an identical second page
    behind a *different* first page must not hit."""
    cfg, _ = qwen
    kv = _unit_kv(cfg, num_pages=8, page_size=4)
    rng = np.random.default_rng(9)
    common = rng.integers(0, cfg.vocab_size, 4)
    a = np.concatenate([rng.integers(0, cfg.vocab_size, 4), common, [1]])
    b = np.concatenate([rng.integers(0, cfg.vocab_size, 4), common, [1]])
    assert kv.grow(0, 8)
    kv.register_prefix(0, a)
    assert kv.lookup_prefix(a)[0] == 8
    assert kv.lookup_prefix(b)[0] == 0  # page 2 content equal, chain not


# --------------------------------------------------- speculative decode


def test_spec_greedy_parity_mixed_batch(qwen):
    """draft_k > 0 must reproduce the plain engine's greedy tokens for a
    mixed-length batch, while actually accepting drafts (random-init
    greedy falls into repetitive attractors the n-gram drafter nails)."""
    cfg, params = qwen
    rng = np.random.default_rng(1)
    ps = [rng.integers(0, cfg.vocab_size, n) for n in (9, 14, 21)]
    eng = ServeEngine(cfg, CTX, window=64, max_batch=3, chunk=4,
                      page_size=8, draft_k=4)
    out = eng.run(params, [Request(rid=i, prompt=p, max_new=20)
                           for i, p in enumerate(ps)])
    for i, p in enumerate(ps):
        np.testing.assert_array_equal(out[i], cold_ref(cfg, params, p, 20))
    assert eng.acceptance_length > 1.5  # drafts really were accepted
    assert (eng.counters["spec_tokens"]
            == sum(len(out[i]) for i in range(3)))


def test_spec_eos_parity(qwen):
    """EOS inside an accepted span: emission must stop at the EOS token
    exactly as the non-speculative engine does."""
    cfg, params = qwen
    rng = np.random.default_rng(10)
    p = rng.integers(0, cfg.vocab_size, 10)
    full = cold_ref(cfg, params, p, 12, window=48)
    eos = int(full[4])
    plain = ServeEngine(cfg, CTX, window=48, max_batch=1, chunk=4,
                        page_size=8, eos_id=eos, prefix_cache=False)
    want = plain.run(params, [Request(rid=0, prompt=p, max_new=12)])[0]
    spec = ServeEngine(cfg, CTX, window=48, max_batch=1, chunk=4,
                       page_size=8, eos_id=eos, draft_k=3,
                       prefix_cache=False)
    got = spec.run(params, [Request(rid=0, prompt=p, max_new=12)])[0]
    np.testing.assert_array_equal(got, want)
    assert got[-1] == eos and len(got) < 12


def test_spec_sampling_routes_to_plain_chunk(qwen):
    """With temperature > 0 greedy-match acceptance would skew the output
    distribution, so run() takes the plain 1-token chunk: no span work is
    paid and the sampled stream is identical to a draft_k=0 engine."""
    cfg, params = qwen
    rng = np.random.default_rng(11)
    p = rng.integers(0, cfg.vocab_size, 9)
    eng = ServeEngine(cfg, CTX, window=48, max_batch=1, chunk=4,
                      page_size=8, draft_k=3, prefix_cache=False,
                      temperature=0.8)
    out = eng.run(params, [Request(rid=0, prompt=p, max_new=10)],
                  key=jax.random.key(7))[0]
    plain = ServeEngine(cfg, CTX, window=48, max_batch=1, chunk=4,
                        page_size=8, prefix_cache=False, temperature=0.8)
    want = plain.run(params, [Request(rid=0, prompt=p, max_new=10)],
                     key=jax.random.key(7))[0]
    np.testing.assert_array_equal(out, want)
    assert len(out) == 10
    assert eng.counters["spec_steps"] == 0  # span path never ran
    assert eng.acceptance_length == 1.0


def test_spec_with_prefix_cache_and_pallas_kernel(qwen):
    """The span decode routes through the k-token Pallas kernel under
    attn_impl='pallas_interpret' and matches the gather-oracle engine."""
    cfg, params = qwen
    ctxp = ModelContext(compute_dtype=jnp.float32, q_chunk=64,
                        attn_impl="pallas_interpret")
    rng = np.random.default_rng(12)
    p = rng.integers(0, cfg.vocab_size, 19)
    kern = ServeEngine(cfg, ctxp, window=48, max_batch=1, chunk=4,
                       page_size=8, draft_k=2)
    orac = ServeEngine(cfg, CTX, window=48, max_batch=1, chunk=4,
                       page_size=8, draft_k=2)
    for _ in range(2):  # second run exercises the cached-prefix span
        ok_ = kern.run(params, [Request(rid=0, prompt=p, max_new=8)])[0]
        oo = orac.run(params, [Request(rid=0, prompt=p, max_new=8)])[0]
        np.testing.assert_array_equal(ok_, oo)
    assert kern.counters["suffix_prefills"] == 1


def test_spec_requires_paged_backend(qwen):
    cfg, _ = qwen
    with pytest.raises(ValueError):
        ServeEngine(cfg, CTX, window=32, max_batch=1, chunk=4,
                    paged=False, draft_k=2)


def test_drafter_prefers_full_continuation(qwen):
    """Unit: the prompt-lookup drafter must pick the latest bigram match
    whose continuation is fully known, not the tip match whose
    continuation is unwritten history."""
    cfg, _ = qwen
    eng = ServeEngine(cfg, CTX, window=64, max_batch=2, chunk=4,
                      page_size=8, draft_k=3)
    hist = jnp.zeros((2, 64), jnp.int32)
    # row 0: strict repetition 5,7,5,7,... tip bigram (7,5) recurs
    hist = hist.at[0, :10].set(jnp.asarray([5, 7] * 5))
    # row 1: no earlier occurrence of the tip bigram
    hist = hist.at[1, :6].set(jnp.asarray([1, 2, 3, 4, 5, 6]))
    pos = jnp.asarray([10, 6], jnp.int32)
    tok = jnp.asarray([[5], [9]], jnp.int32)
    drafts = np.asarray(eng._draft_tokens(hist, pos, tok))
    np.testing.assert_array_equal(drafts[0], [7, 5, 7])  # full continuation
    np.testing.assert_array_equal(drafts[1], [-1, -1, -1])  # miss


# ------------------------------------------------------ span kernel


def test_paged_span_kernel_matches_ref():
    key = jax.random.key(0)
    b, t, h, kv, d, p, m, n = 3, 4, 8, 2, 32, 8, 4, 16
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, t, h, d))
    kp = jax.random.normal(jax.random.fold_in(key, 2), (n, p, kv, d))
    vp = jax.random.normal(jax.random.fold_in(key, 3), (n, p, kv, d))
    table = jnp.array([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]],
                      jnp.int32)
    pos = jnp.array([17, 6, 27], jnp.int32)
    for window in (None, 7):
        out = ops.paged_decode_span_attention(
            q, kp, vp, table, pos, impl="interpret", window=window)
        want = ops.paged_decode_span_attention(
            q, kp, vp, table, pos, impl="ref", window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_span_kernel_t1_matches_single_token_kernel():
    """A span of one token must agree with the original scalar-prefetch
    decode kernel (pos conventions: span pos counts tokens BEFORE it)."""
    key = jax.random.key(4)
    b, h, kv, d, p, n = 2, 4, 2, 32, 8, 8
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, h, d))
    kp = jax.random.normal(jax.random.fold_in(key, 2), (n, p, kv, d))
    vp = jax.random.normal(jax.random.fold_in(key, 3), (n, p, kv, d))
    table = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
    pos = jnp.array([11, 19], jnp.int32)
    single = ops.paged_decode_attention(q, kp, vp, table, pos + 1,
                                        impl="interpret")
    span = ops.paged_decode_span_attention(q[:, None], kp, vp, table, pos,
                                           impl="interpret")
    np.testing.assert_allclose(np.asarray(span[:, 0]), np.asarray(single),
                               rtol=1e-5, atol=1e-5)


def test_span_decode_matches_sequential_paged_decode(qwen):
    """Model-level: one span call over T tokens reproduces T sequential
    paged decode steps — spans of one token, ``pos`` advanced by the
    caller as the decode chunk does (logits and written pages)."""
    cfg, params = qwen
    b, p_, m, n = 2, 8, 4, 16
    spec = api.paged_state_spec(cfg, n, p_, b, m, CTX)
    pages = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         spec)["pages"]
    table = jnp.array([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
    rng = np.random.default_rng(13)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 5)), jnp.int32)
    st = {"pages": pages, "page_table": table,
          "pos": jnp.zeros((b,), jnp.int32)}
    seq_logits = []
    for t in range(5):
        lg, st = api.decode_span_paged_fn(params, toks[:, t:t + 1], st,
                                          cfg, CTX)
        st = {**st, "pos": st["pos"] + 1}
        seq_logits.append(lg[:, 0])
    seq_logits = jnp.stack(seq_logits, 1)
    st2 = {"pages": pages, "page_table": table,
           "pos": jnp.zeros((b,), jnp.int32)}
    span_logits, st2 = api.decode_span_paged_fn(params, toks, st2, cfg, CTX)
    np.testing.assert_allclose(np.asarray(seq_logits),
                               np.asarray(span_logits),
                               rtol=1e-5, atol=1e-5)
    for a, bb in zip(jax.tree.leaves(st["pages"]),
                     jax.tree.leaves(st2["pages"])):
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(bb, dtype=np.float32),
                                   rtol=1e-5, atol=1e-5)


def _pallas_kernels(jaxpr) -> list:
    """Names of the kernel functions of every ``pallas_call`` in a jaxpr,
    nested jaxprs (scan bodies, shard_maps) included."""
    from jax.extend import core as jcore
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["jaxpr"].debug_info.func_name)
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                if isinstance(sub, jcore.ClosedJaxpr):
                    names += _pallas_kernels(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    names += _pallas_kernels(sub)
    return names


@pytest.mark.parametrize("page_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_span_of_one_scores_through_the_decode_kernel(qwen, page_dtype):
    """A paged span of one token is plain decode: it lowers through the
    one-query paged decode kernel, not the span kernel, and its logits and
    written pages match the gather oracle's."""
    cfg, params = qwen
    b, p_, m, n = 2, 8, 4, 16
    ctxs = {impl: ModelContext(compute_dtype=jnp.float32, q_chunk=64,
                               attn_impl=impl, decode_cache_dtype=page_dtype)
            for impl in ("pallas_interpret", "xla")}
    spec = api.paged_state_spec(cfg, n, p_, b, m, ctxs["xla"])
    rng = np.random.default_rng(17)
    pages = jax.tree.map(
        lambda s: jnp.asarray(rng.normal(size=s.shape), s.dtype),
        spec["pages"])
    st = {"pages": pages,
          "page_table": jnp.array([[1, 2, 3, 0], [4, 5, 6, 7]], jnp.int32),
          "pos": jnp.array([13, 26], jnp.int32)}
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 1)), jnp.int32)

    def run(impl):
        return api.decode_span_paged_fn(params, tok, st, cfg, ctxs[impl])

    kernels = _pallas_kernels(
        jax.make_jaxpr(lambda: run("pallas_interpret"))().jaxpr)
    assert kernels and set(kernels) == {"_paged_decode_kernel"}, kernels
    (lk, sk), (lo, so) = run("pallas_interpret"), run("xla")
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lo),
                               rtol=1e-5, atol=1e-5)
    for a, bb in zip(jax.tree.leaves(sk["pages"]),
                     jax.tree.leaves(so["pages"])):
        np.testing.assert_array_equal(np.asarray(a, dtype=np.float32),
                                      np.asarray(bb, dtype=np.float32))
