"""Continuous-batching serving engine with a device-resident decode loop.

The Ironwood-era premise: serving is a first-class supercomputer workload,
so the engine is built like one — a stable deterministic datapath (the
paged pool + chunked decode scan) that rapidly-changing workload tricks
plug into without changing the architecture:

  * **Continuous batching** (scheduler.py): requests are admitted into
    free batch slots and drained *mid-decode*; finished or preempted
    slots refill without flushing the batch.
  * **Block/paged KV cache** (kv_cache.py): pure-attention stacks store
    KV in a shared page pool addressed through a device page table, with
    int8 page quantization as the HBM lever — quantized pages stream
    natively through the Pallas kernels (in-VMEM dequant via page-
    aligned scale pages); other families (Mamba/RWKV/enc-dec) use
    per-slot dense ring/state caches behind the same interface.
  * **Chunked prefill**: cold prompts prefill in fixed-size spans
    (``prefill_chunk``) through the same span-decode datapath as
    cached-suffix prefill — one compiled program family for every
    prompt length, prefill compute scaling with the prompt instead of
    the window. Hybrid (attention + state) stacks get the same through
    the *dense* span path (``api.decode_span_fn``): right-aligned
    chunks at absolute positions, recurrent state threading through.
  * **Prefix caching** (kv_cache.py): full prompt pages are content-
    addressed in a global LRU index; admissions that hit share the cached
    pages by reference (copy-on-write protected) and prefill only the
    prompt *suffix* via the span-decode path — the system-prompt /
    few-shot-template traffic shape served at near-zero prefill cost.
  * **Self-speculative decoding** (``draft_k > 0``): an n-gram prompt-
    lookup drafter (no second model) proposes ``draft_k`` tokens from a
    device-resident token history; one batched span decode scores the
    whole draft, the longest prefix matching the model's own greedy
    targets is accepted, and rollback of rejected tokens is a pure
    position rewind — pages are append-only, so un-accepted k/v simply
    stay beyond the validity frontier until overwritten (the paper's
    checkpoint-replay framing applied to decode).
  * **Device-resident decode**: the hot loop is a ``lax.scan`` of
    ``chunk`` decode steps compiled once — draft, verify, sample,
    EOS/budget masking, cache write and position bookkeeping all stay on
    device. The host syncs once per *chunk* (not per token) to drain
    emitted tokens and make scheduling decisions.

The legacy single-batch ``generate()`` survives as a thin wrapper that
submits one request per batch row; ``generate_pertoken()`` keeps the old
one-jit-call-per-token loop as the benchmark baseline. See
docs/serving.md for lifecycle diagrams of all three subsystems.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hwspec
from repro.core.topology import Torus
from repro.models import api
from repro.models.blocks import ModelContext
from repro.models.config import ModelConfig
from repro.models.params import axes_tree
from repro.obs.metrics import (CounterDict, MetricsRegistry,
                               QUEUE_WAIT_BUCKETS_STEPS)
from repro.obs.steptrace import StepTrace
from repro.obs.trace import SpanTracer
from repro.serve.kv_cache import DenseKVCache, PagedKVCache
from repro.serve.scheduler import (ContinuousBatchingScheduler,
                                   PrefillWorkerPool, Request)
from repro.sharding.axes import (AxisRules, RULE_SETS, logical_constraint,
                                 summarize_dropped, tree_shardings)

Array = jax.Array
PyTree = Any

PAD_TOKEN = -1  # emitted by finished slots inside a chunk

log = logging.getLogger(__name__)

# modeled one-way software+wire latency of the prefill->decode handoff
_LINK_LATENCY_S = {"ici": 1.0e-6, "dcn": 50.0e-6}
# DCN-class bandwidth as a fraction of one ICI link direction (the paper's
# cross-pod federation rides data-center network, not ICI)
_DCN_LINK_FRACTION = 0.25


class PageTransferModel:
    """Modeled prefill->decode KV-page handoff for disaggregated serving.

    The two roles are modeled as slices of the same generation joined by a
    2-ring (one hop): an "ici" link for intra-pod disaggregation, or a
    DCN-class path (lower bandwidth, higher latency) for the paper's
    cross-pod federation. Transfer time = link latency + bytes / the
    ring's bisection bandwidth (core/topology.py), quantized to decode
    chunk boundaries against an HBM-roofline estimate of boundary time —
    so short prompts hide in one boundary while long cold prompts stall
    their slot for several."""

    def __init__(self, *, page_bytes: int, chunk: int, resident_bytes: int,
                 hw: str = "tpu_v5e", link: str = "ici"):
        if link not in _LINK_LATENCY_S:
            raise ValueError(
                f"transfer link must be one of {sorted(_LINK_LATENCY_S)}, "
                f"got {link!r}")
        spec = hwspec.get(hw)
        gbps = spec.ici_link_gbps * (1.0 if link == "ici"
                                     else _DCN_LINK_FRACTION)
        self.link = link
        self.torus = Torus(dims=(2,), link_gbps=gbps)
        self.latency_s = _LINK_LATENCY_S[link]
        self.page_bytes = page_bytes
        # decode boundary walltime: ``chunk`` steps, each streaming the
        # resident KV working set once (memory-bound decode roofline)
        self.boundary_s = chunk * resident_bytes / (spec.hbm_gbps * 1e9)

    def transfer_s(self, n_pages: int) -> float:
        bw = self.torus.bisection_gbps() * 1e9  # bytes/s across the hop
        return self.latency_s + n_pages * self.page_bytes / bw

    def delay_boundaries(self, n_pages: int) -> int:
        """Whole decode boundaries the pages are in flight (>= 1: a
        handoff is never visible inside the boundary that issued it)."""
        return max(1, math.ceil(self.transfer_s(max(1, n_pages))
                                / self.boundary_s))


@dataclasses.dataclass
class ServeEngine:
    """``window``: max total tokens per request (prompt + generated).

    ``draft_k``: speculative draft length per decode step (0 disables;
    requires the paged backend). ``prefix_cache``: share prompt-prefix
    pages across requests (None -> on whenever paged).
    ``prefill_chunk``: span size for chunked prefill (clamped to the
    window; the final partial chunk buckets to pow2).

    ``mesh``: a (data, model) ``jax.sharding.Mesh`` — when set, every
    prefill/decode/span program compiles under NamedSharding: KV-head
    pools shard over "model" (GQA replicating via the AxisRules
    divisibility fallback, reported once in ``dropped_rules``), batch
    slots over "data", host bookkeeping replicated. ``rules`` is an
    AxisRules or a RULE_SETS name.

    ``disaggregate``: prefill/decode disaggregation (paged only) —
    ``prefill_workers`` dedicated workers chunk-prefill cold prompts
    (placed by queue depth) and hand finished pages to the decode side
    over a modeled ``transfer_link`` ("ici" intra-pod | "dcn" cross-pod)
    of hardware generation ``transfer_hw``; arriving slots stay *parked*
    (frozen, token-identical on activation) until the modeled transfer
    lands, and the traffic/stall accounting shows up in
    ``transfer_stats()``."""

    cfg: ModelConfig
    ctx: ModelContext
    window: int
    max_batch: int = 4
    chunk: int = 8
    page_size: int = 8
    num_pages: Optional[int] = None
    paged: Optional[bool] = None  # None -> auto by family
    eos_id: Optional[int] = None
    temperature: float = 0.0
    draft_k: int = 0
    prefix_cache: Optional[bool] = None
    prefill_chunk: int = 128  # span size for chunked prefill
    mesh: Any = None  # serving mesh (None -> single host)
    rules: Any = "baseline_dp_tp"  # AxisRules or RULE_SETS name
    disaggregate: bool = False
    prefill_workers: int = 1
    transfer_link: str = "ici"  # "ici" | "dcn"
    transfer_hw: str = "tpu_v5e"  # hwspec generation for the transfer
    # resilience: a serve.faults.FaultInjector turns on the chaos
    # harness (page CRC stamping, per-boundary injection + detection +
    # replay); None leaves the fault-free path bit-identical to an
    # engine without the harness. ``admission`` is a
    # serve.admission.AdmissionController (None admits everything).
    # ``retry_budget`` bounds fault replays per request before the
    # deterministic terminal failure (state="failed").
    faults: Any = None
    admission: Any = None
    retry_budget: int = 3
    metrics: Any = None  # obs.MetricsRegistry (None -> fresh enabled one)
    tracer: Any = None  # obs.SpanTracer (None -> disabled)

    def __post_init__(self) -> None:
        cfg, ctx = self.cfg, self.ctx
        if self.paged is None:
            self.paged = api.supports_paged_decode(cfg)
        if self.paged and not api.supports_paged_decode(cfg):
            raise ValueError(f"{cfg.name}: paged serving unsupported")
        if self.draft_k and not self.paged:
            raise ValueError("speculative decoding (draft_k > 0) requires "
                             "the paged KV backend")
        if self.draft_k < 0:
            raise ValueError("draft_k must be >= 0")
        if self.prefix_cache is None:
            self.prefix_cache = self.paged
        if self.prefix_cache and not self.paged:
            raise ValueError("prefix caching requires the paged KV backend")
        if self.disaggregate and not self.paged:
            raise ValueError("prefill/decode disaggregation requires the "
                             "paged KV backend (pages are the handoff unit)")
        if self.prefill_workers < 1:
            raise ValueError("prefill_workers must be >= 1")
        if isinstance(self.rules, str):
            self.rules = RULE_SETS[self.rules]
        if not isinstance(self.rules, AxisRules):
            raise ValueError(f"rules must be AxisRules or one of "
                             f"{sorted(RULE_SETS)}")
        self.dropped_rules: List[str] = []
        self._dropped_raw: List[Tuple[str, int]] = []
        if self.mesh is not None:
            self.ctx = ctx = self._mesh_context(ctx)
        # Telemetry is host-side only (never touches a device program),
        # so an instrumented engine is token-identical to a bare one.
        # ``counters``/``disagg_stats`` keep their historical dict-style
        # call sites via CounterDict facades; the registry owns the
        # numbers under "serve_"-prefixed names (obs.CATALOG).
        if self.metrics is None:
            self.metrics = MetricsRegistry()
        if self.tracer is None:
            self.tracer = SpanTracer(enabled=False)
        self.steptrace = StepTrace(
            source="serve", meta={"arch": self.cfg.name})
        self.counters = CounterDict(
            self.metrics,
            ("prefills", "chunks", "decode_steps", "host_syncs",
             "pertoken_steps", "pages_trimmed", "suffix_prefills",
             "prompt_tokens", "cached_prompt_tokens", "spec_steps",
             "spec_tokens", "prefill_span_calls", "span_prefill_compiles",
             "span_prefill_dense_compiles"),
            prefix="serve_")
        m = self.metrics
        self._m = {
            "ttft": m.histogram("serve_ttft_s"),
            "tpot": m.histogram("serve_tpot_s"),
            "e2e": m.histogram("serve_e2e_s"),
            "queue_wait": m.histogram("serve_queue_wait_steps",
                                      edges=QUEUE_WAIT_BUCKETS_STEPS),
            "prefill_hist": m.histogram("serve_prefill_s"),
            "chunk_hist": m.histogram("serve_chunk_s"),
            "prefill_time": m.counter("serve_prefill_time_s"),
            "decode_time": m.counter("serve_decode_time_s"),
            "prefill_tokens": m.counter("serve_prefill_tokens"),
            "decode_tokens": m.counter("serve_decode_tokens"),
            "generated_tokens": m.counter("serve_generated_tokens"),
            "admitted": m.counter("serve_requests_admitted"),
            "finished": m.counter("serve_requests_finished"),
            "preempted": m.counter("serve_requests_preempted"),
        }
        self._trace_pid = self.tracer.process("serve")
        # decode chunks run all slots at once: one "device" lane past
        # the per-slot request lanes (tids 0..max_batch-1)
        self._device_tid = self.tracer.thread(
            self._trace_pid, self.max_batch, "device")
        self._req_obs: Dict[int, Dict[str, float]] = {}
        self._park_spans: set = set()
        if self.paged:
            # +1 page of table headroom: a finished slot's frozen pos can
            # sit exactly at `window`, whose page index must still resolve
            # (to the trash page) instead of clamping into a live page.
            # Speculative spans write up to draft_k positions past the
            # frontier; those slots must resolve (to trash) too.
            self.pages_per_seq = (
                -(-(self.window + self.draft_k) // self.page_size) + 1)
            if self.num_pages is None:
                self.num_pages = 1 + self.max_batch * self.pages_per_seq
            self.kv: Any = PagedKVCache(
                cfg, ctx, self.num_pages, self.page_size, self.max_batch,
                self.pages_per_seq, mesh=self.mesh, rules=self.rules,
                dropped=self._dropped_raw)
        else:
            self.kv = DenseKVCache(cfg, ctx, self.window, self.max_batch,
                                   mesh=self.mesh, rules=self.rules,
                                   dropped=self._dropped_raw)
        self._note_dropped()
        # Pure state-family stacks (mamba/rwkv) carry O(1) state, so the
        # dense prefill would otherwise compile once per prompt length.
        # Front-padding to power-of-two buckets (masked embeddings; the
        # recurrent state stays zero through the pad prefix) bounds the
        # compile count to log2(window).
        self.bucket_prefill = (not self.paged
                               and not cfg.is_encoder_decoder
                               and set(cfg.sublayer_kinds()) <=
                               {"mamba", "rwkv"})
        # Chunked prefill through the dense span path for any remaining
        # decoder-only stack with attention sublayers (hybrid jamba, or a
        # pure-attention stack forced onto the dense backend): prompts
        # are right-aligned into fixed-size spans at absolute positions,
        # so attention needs no front padding and every prompt length
        # reuses ONE compiled program. Requires append-only (non-ring)
        # caches, so SWA archs whose window exceeds the serve window are
        # excluded. mrope positions thread through the span paths (sliced
        # per chunk from the request's extras).
        self.chunk_prefill = (not self.paged
                              and not self.bucket_prefill
                              and not cfg.is_encoder_decoder
                              and (cfg.sliding_window is None
                                   or self.window <= cfg.sliding_window))
        # span size for chunked prefill (paged cold + suffix, dense)
        self.span_len = max(1, min(self.prefill_chunk, self.window))
        self.prefill_bucket_sizes: set = set()
        self._use_spec = False  # per-run: draft_k > 0 and greedy temp
        # disaggregation state: parked slots (admitted but frozen while
        # their modeled page transfer is in flight) and traffic counters
        self._parked: Dict[int, int] = {}
        self.transfer_model: Optional[PageTransferModel] = None
        if self.disaggregate:
            self.page_bytes = self.kv.per_token_bytes() * self.page_size
            self.transfer_model = PageTransferModel(
                page_bytes=self.page_bytes, chunk=self.chunk,
                resident_bytes=self.max_batch * self.window
                * self.kv.per_token_bytes(),
                hw=self.transfer_hw, link=self.transfer_link)
        self.disagg_stats = CounterDict(
            self.metrics,
            ("transfers", "transfer_pages", "transfer_bytes",
             "transfer_stall_boundaries", "decode_idle_boundaries",
             "boundaries", "prefill_depth_sum", "prefill_depth_peak",
             "decode_depth_sum", "decode_depth_peak"),
            prefix="serve_")
        if self.retry_budget < 0:
            raise ValueError("retry_budget must be >= 0")
        self.fault_stats = CounterDict(
            self.metrics,
            ("fault_worker_failures", "fault_page_corruptions",
             "fault_pages_quarantined", "fault_transfer_drops",
             "fault_stragglers", "fault_detections", "retry_requeues",
             "retry_failures", "shed_requests", "shed_spec_chunks"),
            prefix="serve_")
        if self.faults is not None and self.paged:
            # CRC-stamp published pages so injected corruption is caught
            # at the next boundary — before any chunk could read it
            self.kv.integrity_checks = True
        self._build_jitted()
        self._reset_carry()

    # ----------------------------------------------------------- mesh wiring

    def _mesh_context(self, ctx: ModelContext) -> ModelContext:
        """Rebuild the model context with the serving mesh threaded in:
        ``shard`` becomes a logical_constraint against (mesh, rules) so
        every activation/page annotation resolves under GSPMD, and the
        mesh/axis names ride along for the shard_map'd paged kernels."""
        mesh, rules = self.mesh, self.rules

        def shard(x: Array, logical: Tuple[Optional[str], ...]) -> Array:
            return logical_constraint(x, logical, mesh, rules)

        return ModelContext(
            compute_dtype=ctx.compute_dtype, q_chunk=ctx.q_chunk,
            shard=shard, mamba_chunk=ctx.mamba_chunk,
            rwkv_chunk=ctx.rwkv_chunk, attn_impl=ctx.attn_impl,
            decode_cache_dtype=ctx.decode_cache_dtype,
            full_cache_window=ctx.full_cache_window, mesh=mesh,
            data_axis="data", model_axis="model",
            moe_dispatch=ctx.moe_dispatch, moe_impl=ctx.moe_impl,
            metrics=ctx.metrics)

    def _note_dropped(self, raw=None) -> None:
        """Fold freshly-recorded divisibility fallbacks into the one-time
        report: visible in logs at WARNING and in ``sharding_report``."""
        if self.mesh is None:
            return
        if raw is not None:
            self._dropped_raw.extend(raw)
        lines = summarize_dropped(self._dropped_raw, self.mesh, self.rules)
        new = [ln for ln in lines if ln not in self.dropped_rules]
        if new:
            self.dropped_rules.extend(new)
            log.warning("serve sharding fallbacks (%s on %s): %s",
                        self.rules.name, self.cfg.name, "; ".join(new))

    @property
    def sharding_report(self) -> Dict[str, Any]:
        """Mesh layout + every dropped-rule fallback seen so far."""
        if self.mesh is None:
            return {"mesh": None, "rules": self.rules.name,
                    "dropped_rules": []}
        return {"mesh": dict(zip(self.mesh.axis_names,
                                 self.mesh.devices.shape)),
                "rules": self.rules.name,
                "dropped_rules": list(self.dropped_rules)}

    def shard_params(self, params: PyTree) -> PyTree:
        """device_put the parameter tree onto the serving mesh per the
        logical rules (identity without a mesh). ``run()`` applies this
        automatically; calling it once up front skips the first-boundary
        transfer. Already-placed trees are a no-op device_put."""
        if self.mesh is None:
            return params
        logical = axes_tree(api.model_specs(self.cfg))
        shapes = jax.tree.map(lambda p: p.shape, params)
        raw: List[Tuple[str, int]] = []
        shardings = tree_shardings(logical, shapes, self.mesh, self.rules,
                                   raw)
        out = jax.device_put(params, shardings)
        self._note_dropped(raw)
        return out

    def transfer_stats(self) -> Dict[str, float]:
        """Disaggregation traffic/stall/queue-depth accounting (empty
        dict when ``disaggregate`` is off)."""
        if not self.disaggregate:
            return {}
        st = dict(self.disagg_stats)
        n = max(1, st.pop("boundaries"))
        st["prefill_depth_mean"] = st.pop("prefill_depth_sum") / n
        st["decode_depth_mean"] = st.pop("decode_depth_sum") / n
        st["transfer_s_per_page"] = self.transfer_model.transfer_s(1)
        st["link"] = self.transfer_link
        return st

    # ------------------------------------------------------------ jit build

    @staticmethod
    def _pick(logits: Array, key: Array, temp: Array) -> Array:
        """logits (B,1,V) -> (B,1) int32 next tokens.

        ``temp`` is a traced scalar: greedy (temp <= 0) and sampled paths
        share one compilation, so changing the temperature neither
        recompiles nor requires rebuilding the engine."""
        last = logits[:, -1, :].astype(jnp.float32)
        greedy = jnp.argmax(last, axis=-1)
        sampled = jax.random.categorical(
            key, last / jnp.maximum(temp, 1e-6), axis=-1)
        return jnp.where(temp > 0.0, sampled,
                         greedy)[:, None].astype(jnp.int32)

    @staticmethod
    def _prefill_key(key: Array, rid: int) -> Array:
        """Per-request sampling key for the first token. Double fold (a
        dedicated stream id, then the rid) keeps it disjoint from the
        single-fold per-step chunk keys and from other admissions in the
        same boundary."""
        return jax.random.fold_in(jax.random.fold_in(key, 0x9e3779), rid)

    def _draft_tokens(self, hist: Array, pos: Array, tok: Array) -> Array:
        """n-gram prompt-lookup drafter, fully on device.

        hist: (B, window) token at each absolute position (< pos valid);
        tok: (B, 1) the current input token (position ``pos``, not yet in
        hist). Finds the latest earlier occurrence of the tip bigram
        (hist[pos-1], tok) and proposes the ``draft_k`` tokens that
        followed it. Misses return -1 (never matches a greedy target, so
        verification rejects the whole draft). Drafts are *advisory
        only*: acceptance compares against the model's own greedy
        targets, so a bad draft can cost speed, never correctness."""
        b, w = hist.shape
        dk = self.draft_k
        bidx = jnp.arange(b)
        idx = jnp.arange(w)[None, :]
        prev = jnp.pad(hist, ((0, 0), (1, 0)))[:, :w]  # hist shifted right
        last = hist[bidx, jnp.clip(pos - 1, 0, w - 1)]  # (B,)
        m = (hist == tok) & (prev == last[:, None])
        m &= (idx >= 1) & (idx < pos[:, None])
        # prefer the latest match whose dk-token continuation is fully
        # inside known history (j + dk <= pos - 1); matches closer to the
        # tip would propose positions that are not written yet
        j_full = jnp.where(m & (idx + dk <= pos[:, None] - 1),
                           idx, -1).max(axis=1)
        j_part = jnp.where(m & (idx <= pos[:, None] - 2),
                           idx, -1).max(axis=1)
        j = jnp.where(j_full >= 0, j_full, j_part)
        gidx = jnp.clip(j[:, None] + 1 + jnp.arange(dk)[None, :], 0, w - 1)
        drafts = hist[bidx[:, None], gidx]
        # tokens proposed past the known tip are unknown: void them
        known = j[:, None] + 1 + jnp.arange(dk)[None, :] < pos[:, None]
        drafts = jnp.where(known & (j[:, None] >= 0), drafts, -1)
        return drafts.astype(jnp.int32)

    def _build_jitted(self) -> None:
        cfg, ctx = self.cfg, self.ctx
        eos = self.eos_id

        # ---- prefill ----------------------------------------------------
        def prefill_dense(params, batch, key, temp):
            logits, cache = api.prefill_fn(params, batch, cfg, ctx,
                                           window=self.window)
            first = self._pick(logits, key, temp)
            return first, cache

        def prefill_bucketed(params, tokens, pad_left, key, temp):
            logits, cache = api.prefill_fn(
                params, {"tokens": tokens}, cfg, ctx, window=self.window,
                pad_left=pad_left)
            first = self._pick(logits, key, temp)
            return first, cache

        self._prefill_dense = jax.jit(prefill_dense)
        self._prefill_bucketed = jax.jit(prefill_bucketed)

        # ---- span prefill (paged): cold chunks AND cached suffixes ------
        # Every paged prefill rides the span-decode datapath in fixed-size
        # chunks: queries attend to everything already in the pages (a
        # cold chunk's predecessors, or an adopted cached prefix) through
        # the page table, and the chunk's k/v scatter straight into the
        # slot's pages — quantized on write for int8 pools, streamed back
        # by the same kernels decode uses. One compiled program serves
        # every prompt length (the trace-time counter below is the
        # compile-count regression probe).
        def prefill_span(params, pages, span, table, pos0, valid, key,
                         temp, mrope=None):
            # trace-time: jax runs this Python once per compiled program
            # variant, so compile_event counts compilations (a cache hit
            # never re-enters the tracer; a retrace legitimately counts)
            self.metrics.compile_event("serve_span_prefill")
            state = {"pages": pages, "page_table": table, "pos": pos0}
            # only the chunk's last real token needs logits: the gather
            # happens before the lm head, so the vocab projection is
            # (1, 1, V) per chunk, not (1, span, V)
            idx = jnp.clip(valid - 1, 0, span.shape[1] - 1)
            logits, new_state = api.decode_span_paged_fn(
                params, span, state, cfg, ctx, valid=valid, logits_at=idx,
                mrope_positions=mrope)
            first = self._pick(logits, key, temp)
            return first, new_state["pages"]

        self._prefill_span = jax.jit(prefill_span, donate_argnums=(1,))

        # ---- span prefill (dense): chunked prefill for hybrid stacks ----
        # Right-aligned chunks at absolute positions: only the FIRST chunk
        # carries (dead) front padding, flagged by pos < 0 inside
        # lm_decode_span — attention writes drop, recurrent state threads
        # through chunks untouched by the pad.
        def prefill_span_dense(params, cache, span, pos0, key, temp,
                               mrope=None):
            # trace-time compile counter; see prefill_span above
            self.metrics.compile_event("serve_span_prefill_dense")
            state = dict(cache)
            state["pos"] = pos0
            # right-aligned chunks end on a live token: its logits alone
            # are gathered before the lm head (see prefill_span)
            last = jnp.full((span.shape[0],), span.shape[1] - 1, jnp.int32)
            logits, new_state = api.decode_span_fn(
                params, span, state, cfg, ctx, logits_at=last,
                mrope_positions=mrope)
            first = self._pick(logits, key, temp)
            return first, {"blocks": new_state["blocks"]}

        self._prefill_span_dense = jax.jit(prefill_span_dense,
                                           donate_argnums=(1,))

        # ---- copy-on-write page copy (prefix cache fork) ----------------
        def copy_page(pages, src, dst):
            new = {}
            for sl, sub in pages.items():
                new[sl] = {name: arr.at[:, dst].set(arr[:, src])
                           for name, arr in sub.items()}
            return new

        self._copy_page = jax.jit(copy_page, donate_argnums=(0,))

        # ---- dense slot write -------------------------------------------
        def write_dense(cache, row_cache, slot):
            blocks = jax.tree.map(lambda c, r: c.at[:, slot].set(r[:, 0]),
                                  cache["blocks"], row_cache["blocks"])
            out = dict(cache)
            out["blocks"] = blocks
            return out

        self._write_dense = jax.jit(write_dense, donate_argnums=(0,))

        # ---- device-resident decode chunk -------------------------------
        def chunk_body(params, table, temp, carry, i):
            tok, pos, done, n_out, max_new, key, cache = carry
            if self.paged:
                state = {"pages": cache, "page_table": table, "pos": pos}
                logits, new_state = api.decode_paged_fn(
                    params, tok, state, cfg, ctx)
                new_cache = new_state["pages"]
            else:
                state = dict(cache)
                state["pos"] = pos
                logits, new_state = api.decode_fn(
                    params, tok, state, cfg, ctx)
                new_cache = {k: v for k, v in new_state.items()
                             if k != "pos"}
            emitted = jnp.where(done, PAD_TOKEN, tok[:, 0])
            n_out = n_out + jnp.where(done, 0, 1)
            newly_done = ~done & (n_out >= max_new)
            if eos is not None:
                newly_done |= ~done & (tok[:, 0] == eos)
            done = done | newly_done
            # finished slots freeze: their (garbage) writes keep landing on
            # the same slot/trash page and their position stops advancing
            pos = jnp.where(done, pos, pos + 1)
            nxt = self._pick(logits, jax.random.fold_in(key, i), temp)
            tok = jnp.where(done[:, None], tok, nxt)
            return (tok, pos, done, n_out, max_new, key, new_cache), emitted

        def run_chunk(params, table, tok, pos, done, n_out, max_new, key,
                      temp, t0, cache):
            def step(carry, i):
                return chunk_body(params, table, temp, carry, i)

            carry0 = (tok, pos, done, n_out, max_new, key, cache)
            carry, toks = jax.lax.scan(
                step, carry0, t0 + jnp.arange(self.chunk))
            tok, pos, done, n_out, max_new, _, cache = carry
            return tok, pos, done, n_out, cache, toks.T  # toks (B, C)

        self._run_chunk = jax.jit(run_chunk, donate_argnums=(10,))

        # ---- speculative decode chunk (draft_k > 0) ---------------------
        # One scan step = draft -> one span decode scoring (1 + draft_k)
        # tokens -> accept the longest prefix matching the model's own
        # greedy targets -> emit 1..1+draft_k tokens. Rollback of the
        # rejected tail is the position bookkeeping alone: its k/v stay
        # in append-only pages beyond the validity frontier and are
        # rewritten before the frontier reaches them.
        dk = self.draft_k

        def spec_chunk_body(params, table, temp, carry, i):
            tok, pos, done, n_out, max_new, key, cache, hist = carry
            b = tok.shape[0]
            bidx = jnp.arange(b)
            w = hist.shape[1]
            drafts = self._draft_tokens(hist, pos, tok)  # (B, dk)
            span = jnp.concatenate([tok, drafts], axis=1)  # (B, 1+dk)
            state = {"pages": cache, "page_table": table, "pos": pos}
            logits, new_state = api.decode_span_paged_fn(
                params, span, state, cfg, ctx)
            new_cache = new_state["pages"]
            greedy = jnp.argmax(
                logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
            # greedy[:, t] is the model's target for position pos + t + 1;
            # under sampling (temp > 0) greedy-match acceptance would
            # change the output distribution, so drafts are voided (the
            # guard is belt-and-braces: run() routes temp > 0 to the
            # plain chunk and never pays for the span at all).
            match = (drafts == greedy[:, :dk]) & (temp <= 0.0)
            accepted = jnp.cumprod(match.astype(jnp.int32), axis=1)
            a = accepted.sum(axis=1)  # (B,) accepted draft count
            t_idx = jnp.arange(1 + dk)
            emit_ok = (t_idx[None, :] <= a[:, None]) & ~done[:, None]
            emit_ok &= (n_out[:, None] + t_idx[None, :]) < max_new[:, None]
            if eos is not None:
                is_eos = span == eos
                prior = jnp.cumsum(is_eos.astype(jnp.int32),
                                   axis=1) - is_eos
                emit_ok &= prior == 0  # nothing emits past an EOS
            emitted = jnp.where(emit_ok, span, PAD_TOKEN)
            n_emit = emit_ok.sum(axis=1).astype(jnp.int32)
            n_out = n_out + n_emit
            newly_done = ~done & (n_out >= max_new)
            if eos is not None:
                newly_done |= ~done & jnp.any(emit_ok & is_eos, axis=1)
            done = done | newly_done
            # token history: scatter the emitted span at positions pos+t
            wpos = jnp.clip(pos[:, None] + t_idx[None, :], 0, w - 1)
            cur = hist[bidx[:, None], wpos]
            hist = hist.at[bidx[:, None], wpos].set(
                jnp.where(emit_ok, span, cur))
            pos = pos + n_emit  # rollback == not advancing past acceptance
            pick0 = self._pick(logits[:, :1], jax.random.fold_in(key, i),
                               temp)
            bonus = greedy[bidx, jnp.clip(a, 0, dk)][:, None]
            nxt = jnp.where(temp > 0.0, pick0, bonus)
            tok = jnp.where(done[:, None], tok, nxt)
            return ((tok, pos, done, n_out, max_new, key, new_cache, hist),
                    emitted)

        def run_chunk_spec(params, table, tok, pos, done, n_out, max_new,
                           key, temp, t0, cache, hist):
            def step(carry, i):
                return spec_chunk_body(params, table, temp, carry, i)

            carry0 = (tok, pos, done, n_out, max_new, key, cache, hist)
            carry, toks = jax.lax.scan(
                step, carry0, t0 + jnp.arange(self.chunk))
            tok, pos, done, n_out, max_new, _, cache, hist = carry
            # toks (C, B, 1+dk) -> (B, C, 1+dk), chronological per slot
            return (tok, pos, done, n_out, cache, hist,
                    toks.transpose(1, 0, 2))

        if dk:
            self._run_chunk_spec = jax.jit(run_chunk_spec,
                                           donate_argnums=(10, 11))

    # --------------------------------------------------------- carry state

    def _reset_carry(self) -> None:
        b = self.max_batch
        self._tok = jnp.zeros((b, 1), jnp.int32)
        self._pos = jnp.zeros((b,), jnp.int32)
        self._done = jnp.ones((b,), bool)  # empty slots are "done"
        self._n_out = jnp.zeros((b,), jnp.int32)
        self._max_new = jnp.ones((b,), jnp.int32)
        self._t = 0  # global decode-step clock (also the sampling stream)
        if self.draft_k:
            # token-at-position history for the prompt-lookup drafter.
            # draft_k + 1 columns of headroom keep every span scatter
            # index in range and distinct (a clipped duplicate write
            # would resolve nondeterministically).
            self._hist = jnp.zeros(
                (b, self.window + self.draft_k + 1), jnp.int32)

    @staticmethod
    def _pow2_bucket(t: int, cap: int) -> int:
        """Compile length for a partial span: pow2 >= t (floor 4), capped
        at the full span size — the program *family* is O(log span_len),
        constant in prompt length, and a short suffix never pays a
        full-span query block."""
        return min(cap, max(4, 1 << (t - 1).bit_length()))

    def _span_prefill_paged(self, params, slot: int, tokens: np.ndarray,
                            start: int, key: Array, temp: Array,
                            mrope: Optional[np.ndarray] = None) -> Array:
        """Prefill ``tokens`` at absolute positions ``start..`` through
        the span-decode datapath in fixed-size chunks — cold prompts
        (start=0) and cached-prefix suffixes (start=cached) share the
        same compiled program family (full-span program + pow2 buckets
        for the final partial chunk). Back padding inside a partial
        chunk writes to the trash page; logits index the final real
        token. ``mrope`` (3, S_total) carries the request's explicit
        multimodal rope rows indexed by *absolute* token position; each
        chunk slices its window (pad slots are dead: zero rows)."""
        s_len = self.span_len
        if not self.kv.ensure_private(slot, start, self._copy_page):
            raise RuntimeError("page pool exhausted during CoW fork")
        first = None
        i = 0
        while i < len(tokens):
            t = min(s_len, len(tokens) - i)
            b_len = self._pow2_bucket(t, s_len)
            span = np.zeros((1, b_len), np.int32)
            span[0, :t] = tokens[i:i + t]
            chunk_m = None
            if mrope is not None:
                cm = np.zeros((3, 1, b_len), np.int32)
                cm[:, 0, :t] = mrope[:, start + i:start + i + t]
                chunk_m = jnp.asarray(cm)
            first, self.kv.pages = self._prefill_span(
                params, self.kv.pages, jnp.asarray(span),
                self.kv.table_row(slot),
                jnp.full((1,), start + i, jnp.int32),
                jnp.full((1,), t, jnp.int32), key, temp, chunk_m)
            self.counters["prefill_span_calls"] += 1
            i += t
        return first

    def _span_prefill_dense(self, params, slot: int, tokens: np.ndarray,
                            key: Array, temp: Array,
                            mrope: Optional[np.ndarray] = None) -> Array:
        """Chunked prefill on the dense backend (hybrid stacks): the
        prompt is RIGHT-aligned into fixed-size spans so only the first
        chunk is (front-)padded — dead positions sit at negative absolute
        positions, attention stays absolute-positioned, and recurrent
        state threads through the chunks. The first (partial) chunk
        buckets to pow2; every other chunk reuses the full-span program.
        ``mrope`` (3, S) explicit rope rows; the dead front pad gets zero
        rows (its writes are dropped anyway)."""
        s_len = self.span_len
        s = len(tokens)
        r = s % s_len or min(s, s_len)  # first (partial) chunk tokens
        b0 = self._pow2_bucket(r, s_len)
        pad = b0 - r
        padded = np.zeros((1, pad + s), np.int32)
        padded[0, pad:] = tokens
        m_full = None
        if mrope is not None:
            m_full = np.zeros((3, pad + s), np.int32)
            m_full[:, pad:] = mrope[:, :s]
        cache = {"blocks": jax.tree.map(
            lambda sd: jnp.zeros(sd.shape, sd.dtype),
            api.cache_spec(self.cfg, 1, self.window, self.ctx)["blocks"])}
        first = None
        i = 0
        while i < padded.shape[1]:
            b_len = b0 if i == 0 else s_len
            chunk_m = (None if m_full is None else
                       jnp.asarray(m_full[:, None, i:i + b_len]))
            first, cache = self._prefill_span_dense(
                params, cache, jnp.asarray(padded[:, i:i + b_len]),
                jnp.full((1,), i - pad, jnp.int32), key, temp, chunk_m)
            self.counters["prefill_span_calls"] += 1
            i += b_len
        self.kv.write_prefill(self._write_dense, slot, cache)
        return first

    def _req_mrope(self, req: Request, s: int) -> Optional[np.ndarray]:
        """(3, S) absolute-indexed mrope rows for a resume prompt of
        length ``s``: the request's explicit positions, extended past the
        original prompt (generated tokens folded in on resume) by the
        standard max(pos)+1 text continuation."""
        if self.cfg.pos_emb != "mrope":
            return None
        v = req.extras.get("positions")
        if v is None:
            return None  # text default: span paths broadcast positions
        m = np.asarray(v, np.int32).reshape(3, -1)
        if m.shape[1] < s:
            tail = int(m.max()) + 1 + np.arange(s - m.shape[1],
                                                dtype=np.int32)
            m = np.concatenate(
                [m, np.broadcast_to(tail, (3, tail.size))], axis=1)
        return m[:, :s]

    def _admit_into_slot(self, params, req: Request, slot: int,
                         key: Array, temp: Array) -> None:
        rp = req.resume_prompt()
        s = len(rp)
        self.counters["prefills"] += 1
        pkey = self._prefill_key(key, req.rid)
        cached = req.cached_prefix_len if self.paged else 0
        mrope = self._req_mrope(req, s)
        if self.paged:
            # every paged prefill is a chunked span prefill; a prefix hit
            # just starts past the adopted pages (suffix-only compute)
            first = self._span_prefill_paged(params, slot, rp[cached:],
                                             cached, pkey, temp, mrope)
            if cached > 0:
                self.counters["suffix_prefills"] += 1
        elif self.chunk_prefill and not (req.extras.keys() - {"positions"}):
            first = self._span_prefill_dense(params, slot, rp, pkey, temp,
                                             mrope)
        elif self.bucket_prefill and not req.extras:
            sb = 1 << max(3, (s - 1).bit_length())  # pow2 >= s, floor 8
            self.prefill_bucket_sizes.add(sb)
            padded = np.zeros((1, sb), np.int32)
            padded[0, sb - s:] = rp
            first, cache = self._prefill_bucketed(
                params, jnp.asarray(padded),
                jnp.full((1,), sb - s, jnp.int32), pkey, temp)
            self.kv.write_prefill(self._write_dense, slot, cache)
        else:
            batch = {"tokens": jnp.asarray(rp[None, :])}
            for k, v in req.extras.items():
                batch[k] = jnp.asarray(v)
            first, cache = self._prefill_dense(params, batch, pkey, temp)
            self.kv.write_prefill(self._write_dense, slot, cache)
        if self.paged and self.prefix_cache and mrope is None:
            # publish the full prompt pages so later admissions (and this
            # request's own resume after a preemption) can share them.
            # Explicit-mrope requests never publish (or adopt): the index
            # is content-addressed on tokens alone, and the same tokens
            # under different position rows hold different KV.
            self.kv.register_prefix(slot, rp)
        if self.draft_k and self._use_spec:
            row = np.zeros(self.window + self.draft_k + 1, np.int32)
            row[:s] = rp
            self._hist = self._hist.at[slot].set(jnp.asarray(row))
        self._tok = self._tok.at[slot].set(first[0])
        self._pos = self._pos.at[slot].set(s)
        self._done = self._done.at[slot].set(False)
        self._n_out = self._n_out.at[slot].set(len(req.generated))
        self._max_new = self._max_new.at[slot].set(req.max_new)

    # ------------------------------------------------------------- faults

    def _fail_slot(self, slot: int, sched: ContinuousBatchingScheduler,
                   clock: int, reason: str) -> None:
        """Fault recovery for one running slot: release its pages,
        freeze the slot, and replay the request (re-admission re-prefills
        ``resume_prompt()`` past surviving cached pages — token-identical
        under greedy, same as preemption resume) with exponential backoff
        until the retry budget forces the deterministic terminal
        failure."""
        req = sched.running.get(slot)
        if req is None:
            return
        if self.paged:
            self.kv.release(slot)
        self._done = self._done.at[slot].set(True)
        self._parked.pop(slot, None)
        pid = self._trace_pid
        if slot in self._park_spans:
            self.tracer.end(pid=pid, tid=slot)
            self._park_spans.discard(slot)
        self.tracer.end(pid=pid, tid=slot)  # req span
        self.tracer.instant("fault_replay", pid=pid, tid=slot,
                            cat="serve",
                            args={"rid": req.rid, "reason": reason,
                                  "retries": req.retries})
        if req.retries >= self.retry_budget:
            sched.fail(req)
            self.fault_stats["retry_failures"] += 1
        else:
            backoff = self.chunk * (1 << min(req.retries, 6))
            sched.requeue(req, not_before=clock + backoff)
            self.fault_stats["retry_requeues"] += 1

    def _apply_faults(self, boundary: int, clock: int,
                      sched: ContinuousBatchingScheduler,
                      pool: Optional[PrefillWorkerPool]) -> None:
        """Inject this boundary's scheduled faults, then run detection —
        in that order, before the chunk dispatch, so corrupted KV is
        quarantined before any decode step could read it (which is what
        makes survivor token-parity exact rather than probabilistic)."""
        inj = self.faults
        fs = self.fault_stats
        pid = self._trace_pid
        if pool is not None:
            w = inj.worker_failure(boundary)
            if w is not None:
                lost = pool.fail_worker(w % pool.n_workers, clock)
                fs["fault_worker_failures"] += 1
                fs["fault_detections"] += 1
                self.tracer.instant(
                    "worker_fail", pid=pid, tid=self._device_tid,
                    cat="serve", args={"worker": w % pool.n_workers,
                                       "replaced": len(lost)})
        if pool is not None and self._parked:
            r = inj.transfer_drop(boundary)
            if r is not None:
                slot = sorted(self._parked)[r % len(self._parked)]
                retry = inj.plan.transfer_retry_boundaries
                self._parked[slot] = clock + retry * self.chunk
                fs["fault_transfer_drops"] += 1
                fs["fault_detections"] += 1
                self.tracer.instant(
                    "transfer_drop", pid=pid, tid=slot, cat="serve",
                    args={"retry_boundaries": retry})
        if self.paged:
            r = inj.page_flip(boundary)
            if r is not None:
                pids = self.kv.corruptible_pages()
                if pids:
                    self.kv.corrupt_page(pids[r % len(pids)])
                    fs["fault_page_corruptions"] += 1
            # detection: CRC-verify every stamped page; quarantine
            # mismatches and replay every request still mapping them
            for bad_pid, _h in self.kv.verify_integrity():
                fs["fault_detections"] += 1
                fs["fault_pages_quarantined"] += 1
                for slot in self.kv.slots_referencing(bad_pid):
                    self._fail_slot(slot, sched, clock,
                                    reason="kv_corruption")

    # ---------------------------------------------------------------- run

    def submit_check(self, req: Request) -> None:
        total = len(req.prompt) + req.max_new
        if total > self.window:
            raise ValueError(
                f"request {req.rid}: prompt+max_new={total} exceeds "
                f"window={self.window}")

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admitted prompt tokens served from cached pages."""
        total = self.counters["prompt_tokens"]
        return (self.counters["cached_prompt_tokens"] / total
                if total else 0.0)

    @property
    def acceptance_length(self) -> float:
        """Mean tokens emitted per speculative verify step (>= 1)."""
        steps = self.counters["spec_steps"]
        return (self.counters["spec_tokens"] / steps if steps else 1.0)

    def request_times(self) -> Dict[int, Dict[str, float]]:
        """Each request the latest ``run()`` finished, by rid: the
        seconds on ``tracer.clock`` at which it was first ready
        (``ready``), last admitted (``admit``: after any preemption),
        had its first token drained (``first``) and its last
        (``last``). Queue wait is ``admit - ready``."""
        return {rid: dict(o) for rid, o in self._req_obs.items()
                if "last" in o}

    def slo_summary(self) -> Dict[str, float]:
        """Serving SLO summary straight from the registry: TTFT/TPOT
        percentiles, queue wait, and the prefill/decode role split
        (time and tokens/s). All zeros on a disabled registry."""
        m = self._m
        pf_t = float(m["prefill_time"].value)
        dc_t = float(m["decode_time"].value)
        return {
            "requests": float(m["finished"].value),
            "ttft_p50_s": m["ttft"].quantile(0.5),
            "ttft_p95_s": m["ttft"].quantile(0.95),
            "tpot_p50_s": m["tpot"].quantile(0.5),
            "tpot_p95_s": m["tpot"].quantile(0.95),
            "queue_wait_p50_steps": m["queue_wait"].quantile(0.5),
            "prefill_time_s": pf_t,
            "decode_time_s": dc_t,
            "prefill_tok_s": (float(m["prefill_tokens"].value) / pf_t
                              if pf_t > 0 else 0.0),
            "decode_tok_s": (float(m["decode_tokens"].value) / dc_t
                             if dc_t > 0 else 0.0),
        }

    def run(self, params, requests: Sequence[Request], *,
            key: Optional[Array] = None,
            temperature: Optional[float] = None) -> Dict[int, np.ndarray]:
        """Drain all requests; returns {rid: generated tokens}."""
        params = self.shard_params(params)  # no-op without a mesh
        sched = ContinuousBatchingScheduler(self.max_batch)
        self.scheduler = sched
        key = key if key is not None else jax.random.key(0)
        temp = jnp.float32(self.temperature if temperature is None
                           else temperature)
        for req in requests:
            self.submit_check(req)
            sched.add(req)
        # greedy-match acceptance is only sound (and only profitable) for
        # greedy decoding: sampled runs take the plain 1-token chunk, so
        # they never pay for the (1 + draft_k)-query span
        self._use_spec = bool(self.draft_k) and float(temp) <= 0.0
        self._reset_carry()
        # request-lifecycle observation: wall stamps (ready/admit/first
        # token) per rid, feeding TTFT/TPOT/e2e histograms and lifecycle
        # spans. Host-side only; the device programs never see any of it.
        now = self.tracer.clock
        mtr = self._m
        pid = self._trace_pid
        # host work between chunks is named by spans on the device lane,
        # never on a slot lane (a slot lane's spans are its requests')
        dev = self._device_tid
        self._req_obs = {}
        self._park_spans = set()
        run_t0 = now()
        pool: Optional[PrefillWorkerPool] = None
        if self.disaggregate:
            pool = PrefillWorkerPool(self.prefill_workers, self.span_len,
                                     self.chunk)
            self.prefill_pool = pool
            self._parked = {}
        clock = 0
        boundary = -1  # chunk-boundary index: the fault-schedule clock
        # max tokens one decode step can emit
        per_step = 1 + self.draft_k if self._use_spec else 1
        while sched.has_work() or (pool is not None and pool.pending()):
            boundary += 1
            with self.tracer.span("schedule", pid=pid, tid=dev,
                                  cat="serve"):
                wall = now()
                for r in sched.waiting:
                    # "ready": first boundary at which the request is live
                    # (arrived); queue-wait and e2e anchor here
                    if r.arrival <= clock:
                        self._req_obs.setdefault(r.rid, {}) \
                            .setdefault("ready", wall)
                if self.admission is not None:
                    # enqueue-time load shedding: a request whose best-case
                    # first token already misses its TTFT deadline is dropped
                    # before it consumes a prefill worker or decode slot
                    for r in list(sched.waiting):
                        if r.arrival <= clock and self.admission.should_shed(
                                r, clock, chunk=self.chunk,
                                span_len=self.span_len,
                                disaggregated=pool is not None):
                            sched.shed_request(r)
                            self.fault_stats["shed_requests"] += 1
                            self.tracer.instant(
                                "shed", pid=pid, tid=self._device_tid,
                                cat="serve", args={"rid": r.rid})
                if pool is not None:
                    # 0) disaggregation bookkeeping: activate parked slots
                    #    whose modeled page transfer has landed (rewriting the
                    #    frozen position's k/v is idempotent — see chunk_body's
                    #    freeze contract — so activation is token-identical to
                    #    co-located admission); route cold arrivals to the
                    #    shallowest prefill worker queue; surface finished
                    #    prefills back into the decode-side admission queue.
                    for slot, ready in list(self._parked.items()):
                        if clock >= ready:
                            del self._parked[slot]
                            self._done = self._done.at[slot].set(False)
                            if slot in self._park_spans:
                                self.tracer.end(pid=pid, tid=slot)
                                self._park_spans.discard(slot)
                    for r in [r for r in sched.waiting
                              if r.arrival <= clock and not r.prefill_done
                              and r.not_before <= clock]:
                        sched.waiting.remove(r)
                        pool.place(r, clock)
                    for r in pool.pop_ready(clock):
                        sched.add(r)
                    st = self.disagg_stats
                    st["boundaries"] += 1
                    depth = sum(pool.depths())
                    st["prefill_depth_sum"] += depth
                    st["prefill_depth_peak"] = max(st["prefill_depth_peak"],
                                                   depth)
                    st["decode_depth_sum"] += len(sched.waiting)
                    st["decode_depth_peak"] = max(st["decode_depth_peak"],
                                                  len(sched.waiting))
                if self.faults is not None:
                    # inject this boundary's scheduled faults, then detect:
                    # quarantined pages and failed slots are settled before
                    # admission or the chunk can observe them
                    self._apply_faults(boundary, clock, sched, pool)
                # 1) page headroom for running slots; preempt youngest on
                #    pressure (its pages free up for the older requests)
                if self.paged:
                    # grow oldest-first so preemption (youngest-first) never
                    # starves the requests with the most progress
                    order = sorted(
                        sched.running,
                        key=lambda s: (sched.running[s].arrival,
                                       sched.running[s].rid))
                    for slot in order:
                        if slot not in sched.running:
                            continue  # already preempted this boundary
                        req = sched.running[slot]
                        # tokens cached after the next chunk: prompt +
                        # emitted so far + chunk new writes (+1 boundary)
                        target = int(len(req.prompt) + len(req.generated)
                                     + self.chunk * per_step + 1)
                        while not self.kv.grow(slot, min(target, self.window)):
                            victim = sched.preempt_victim()
                            if victim is None:
                                raise RuntimeError(
                                    "page pool too small for a single request")
                            vslot = victim.slot
                            sched.preempt(victim)
                            self.kv.release(vslot)
                            self._done = self._done.at[vslot].set(True)
                            # a parked victim's in-flight transfer is moot:
                            # its pages are gone; it re-prefills on resume
                            self._parked.pop(vslot, None)
                            if vslot in self._park_spans:
                                self.tracer.end(pid=pid, tid=vslot)
                                self._park_spans.discard(vslot)
                            self.tracer.end(pid=pid, tid=vslot)  # req span
                            self.tracer.instant(
                                "preempt", pid=pid, tid=vslot, cat="serve",
                                args={"rid": victim.rid})
                            mtr["preempted"].inc()
                            if vslot == slot:
                                break  # we were the youngest: self-preempted
                # 2) admissions into free slots (never preempt to admit)
                while True:
                    req = sched.next_admittable(clock)
                    slots = sched.free_slots()
                    if req is None or not slots:
                        break
                    slot = slots[0]
                    if self.paged:
                        rp = req.resume_prompt()
                        # explicit-mrope requests bypass the content-addressed
                        # prefix index (same tokens, different position rows
                        # => different KV)
                        use_pc = (self.prefix_cache
                                  and "positions" not in req.extras)
                        cached, pids = ((0, []) if not use_pc
                                        else self.kv.lookup_prefix(rp))
                        if cached:
                            self.kv.adopt_prefix(slot, pids)
                        need = len(rp) + self.chunk * per_step + 1
                        if not self.kv.grow(slot, min(need, self.window)):
                            if use_pc:
                                # undo adoption AND its counter bumps: the
                                # retry next boundary repeats the lookup
                                self.kv.abort_adoption(slot, cached, pids)
                            break  # no pages: wait for completions
                        req.cached_prefix_len = cached
                        self.counters["prompt_tokens"] += len(rp)
                        self.counters["cached_prompt_tokens"] += cached
                    wall = now()
                    o = self._req_obs.setdefault(req.rid, {})
                    o.setdefault("ready", wall)
                    resumed = "admit" in o  # re-admission after a preemption
                    o["admit"] = wall
                    mtr["admitted"].inc()
                    mtr["queue_wait"].observe(float(clock - req.arrival))
                    self.tracer.begin(
                        f"req:{req.rid}", pid=pid, tid=slot, cat="serve",
                        args={"rid": req.rid, "prompt": len(req.prompt),
                              "resumed": resumed})
                    sched.admit(req, slot)
                    n_prefill = (len(req.prompt) + len(req.generated)
                                 - req.cached_prefix_len)
                    with self.tracer.span(
                            "prefill", pid=pid, tid=dev, cat="serve",
                            args={"rid": req.rid, "slot": slot,
                                  "tokens": n_prefill,
                                  "cached": req.cached_prefix_len}):
                        self._admit_into_slot(params, req, slot, key, temp)
                    dt = now() - wall
                    mtr["prefill_hist"].observe(dt)
                    mtr["prefill_time"].add(dt)
                    mtr["prefill_tokens"].add(n_prefill)
                    self.steptrace.record(
                        "prefill", dt, tokens=n_prefill,
                        cached=req.cached_prefix_len, batch=1)
                    if pool is not None:
                        # the prefill ran on the prefill role; its finished
                        # pages now cross the modeled link. Park the slot
                        # (frozen exactly like a finished one) until the
                        # transfer's boundary count elapses.
                        moved = (self.kv.pages_for(len(rp))
                                 - cached // self.page_size)
                        delay = self.transfer_model.delay_boundaries(moved)
                        self._parked[slot] = clock + delay * self.chunk
                        self._done = self._done.at[slot].set(True)
                        st = self.disagg_stats
                        st["transfers"] += 1
                        st["transfer_pages"] += moved
                        st["transfer_bytes"] += moved * self.page_bytes
                        self.tracer.begin(
                            "park", pid=pid, tid=slot, cat="serve",
                            args={"pages": moved, "delay_boundaries": delay})
                        self._park_spans.add(slot)
                if not sched.running:
                    if sched.next_admittable(clock) is not None:
                        raise RuntimeError(
                            "admission stalled with an empty batch: the page "
                            "pool cannot hold one request (shrink window or "
                            "grow num_pages)")
                    if pool is not None and pool.pending():
                        clock += self.chunk  # prefill workers still cooking
                        continue
                    if not sched.waiting:
                        # shedding emptied the queue this boundary; the
                        # loop condition settles whether work remains
                        continue
                    # idle: jump the trace clock to the next arrival (or the
                    # earliest replay-backoff expiry, for requeued requests)
                    nxt = min(max(r.arrival, r.not_before)
                              for r in sched.waiting)
                    clock = max(clock + self.chunk, nxt)
                    continue
                if (pool is not None and sched.running
                        and all(s in self._parked for s in sched.running)):
                    # every running slot is frozen in transfer: the decode
                    # role is idle, so skip the device chunk entirely (frozen
                    # slots emit nothing and their state is untouched — the
                    # skip is token-identical) and just advance the clock.
                    clock += self.chunk
                    st = self.disagg_stats
                    st["transfer_stall_boundaries"] += 1
                    st["decode_idle_boundaries"] += 1
                    continue
            # 3) one device-resident chunk
            sched.record_occupancy(len(sched.running))
            chunk_t0 = now()
            live = sum(1 for s in sched.running if s not in self._parked)
            cache = self.kv.pages if self.paged else \
                {k: v for k, v in self.kv.cache.items() if k != "pos"}
            if self.paged:
                with self.tracer.span("table_upload", pid=pid, tid=dev,
                                      cat="serve"):
                    table = self.kv.table_device()
            else:
                table = jnp.zeros((self.max_batch, 1), jnp.int32)
            # graceful degradation under queue pressure: spend this
            # boundary's FLOPs on a plain chunk instead of the
            # (1 + draft_k)-query speculative span. Token-identical by
            # construction (acceptance only ever matches the model's own
            # greedy targets), so the policy is a pure latency trade.
            use_spec = self._use_spec
            if use_spec and self.admission is not None \
                    and self.admission.drop_speculation(
                        len(sched.waiting)):
                use_spec = False
                self.fault_stats["shed_spec_chunks"] += 1
            with self.tracer.span("chunk", pid=pid, tid=dev, cat="serve",
                                  args={"live": live, "spec": use_spec}):
                if use_spec:
                    (self._tok, self._pos, self._done, self._n_out,
                     new_cache, self._hist, toks) = self._run_chunk_spec(
                        params, table, self._tok, self._pos, self._done,
                        self._n_out, self._max_new, key, temp,
                        jnp.int32(self._t), cache, self._hist)
                else:
                    (self._tok, self._pos, self._done, self._n_out,
                     new_cache, toks) = self._run_chunk(
                        params, table, self._tok, self._pos, self._done,
                        self._n_out, self._max_new, key, temp,
                        jnp.int32(self._t), cache)
            if self.paged:
                self.kv.pages = new_cache
            else:
                new_cache = dict(new_cache)
                new_cache["pos"] = self._pos
                self.kv.update(new_cache)
            self._t += self.chunk
            clock += self.chunk
            if self.faults is not None:
                # straggler: the chunk did one chunk of work but took
                # extra boundaries of wall clock. Purely a clock event —
                # per-request tokens are batch-composition independent,
                # so stragglers shift TTFT/queue waits, never tokens.
                extra = self.faults.straggler(boundary)
                if extra:
                    clock += extra * self.chunk
                    self.fault_stats["fault_stragglers"] += 1
                    self.tracer.instant(
                        "straggler", pid=pid, tid=self._device_tid,
                        cat="serve", args={"extra_boundaries": extra})
            self.counters["chunks"] += 1
            self.counters["decode_steps"] += self.chunk
            if pool is not None and self._parked:
                st = self.disagg_stats
                st["transfer_stall_boundaries"] += 1
                if all(s in self._parked for s in sched.running):
                    st["decode_idle_boundaries"] += 1
            # 4) drain: the single host sync per chunk, then token emission
            with self.tracer.span("drain", pid=pid, tid=dev, cat="serve"):
                toks_h, done_h, pos_h = jax.device_get(
                    (toks, self._done, self._pos))
                self.counters["host_syncs"] += 1
                wall_drain = now()
                chunk_dt = wall_drain - chunk_t0
                emitted = 0
                for slot in list(sched.running):
                    if slot in self._parked:
                        continue  # frozen in transfer: emitted PADs only
                    req = sched.running[slot]
                    if use_spec:
                        # toks_h[slot]: (chunk, 1+draft_k); emitted tokens
                        # form a prefix of each step row
                        for step_row in toks_h[slot]:
                            cnt = 0
                            for t in step_row:
                                if t != PAD_TOKEN:
                                    req.generated.append(int(t))
                                    cnt += 1
                            if cnt:
                                self.counters["spec_steps"] += 1
                                self.counters["spec_tokens"] += cnt
                                emitted += cnt
                    else:
                        for t in toks_h[slot]:
                            if t != PAD_TOKEN:
                                req.generated.append(int(t))
                                emitted += 1
                    o = self._req_obs.get(req.rid, {})
                    if req.generated and "first" not in o:
                        o["first"] = wall_drain
                        mtr["ttft"].observe(
                            wall_drain - o.get("ready", run_t0))
                    finished = bool(done_h[slot])
                    if finished:
                        o["last"] = wall_drain
                        n = len(req.generated)
                        if "first" in o and n > 1:
                            mtr["tpot"].observe(
                                (wall_drain - o["first"]) / (n - 1))
                        mtr["e2e"].observe(wall_drain - o.get("ready", run_t0))
                        mtr["finished"].inc()
                        mtr["generated_tokens"].add(n)
                        self.tracer.end(pid=pid, tid=slot)  # req span
                        sched.complete(slot)
                        if self.paged:
                            if (self.prefix_cache
                                    and "positions" not in req.extras):
                                # publish generated pages too: multi-turn
                                # prompts extending this output will hit
                                self.kv.register_prefix(
                                    slot, np.concatenate(
                                        [req.prompt,
                                         np.asarray(req.generated, np.int32)]))
                            self.kv.release(slot)
                    elif self.paged and self.cfg.sliding_window is not None:
                        # SWA: positions behind pos - window are masked out of
                        # attention; release their pages back to the pool
                        self.counters["pages_trimmed"] += self.kv.trim(
                            slot, int(pos_h[slot]) - self.cfg.sliding_window)
            # chunk-level telemetry: role time split and the measured
            # steptrace event
            mtr["decode_time"].add(chunk_dt)
            mtr["chunk_hist"].observe(chunk_dt)
            mtr["decode_tokens"].add(emitted)
            self.steptrace.record(
                "spec_decode" if use_spec else "decode", chunk_dt,
                batch=live, steps=self.chunk, tokens=emitted,
                queue_depth=len(sched.waiting))
        return {r.rid: np.asarray(r.generated, np.int32)
                for r in sched.finished}

    # ------------------------------------------------------- legacy API

    def generate(self, params, batch: Dict[str, Array], *, max_new: int,
                 temperature: float = 0.0,
                 key: Optional[Array] = None) -> Array:
        """Single-batch generation (old API), served by the new engine.

        Returns (B, max_new) tokens; rows that hit EOS early are padded
        with the EOS id."""
        tokens = np.asarray(batch["tokens"])
        b = tokens.shape[0]
        reqs = []
        for i in range(b):
            req = Request(rid=i, prompt=tokens[i], max_new=max_new)
            # mrope "positions" are (3, B, S): the batch axis is axis 1
            req.extras = {k: (np.asarray(v)[:, i:i + 1]
                              if k == "positions"
                              else np.asarray(v[i:i + 1]))
                          for k, v in batch.items() if k != "tokens"}
            reqs.append(req)
        out = self.run(params, reqs, key=key, temperature=temperature)
        pad = self.eos_id if self.eos_id is not None else 0
        rows = []
        for i in range(b):
            row = out[i]
            if len(row) < max_new:
                row = np.concatenate(
                    [row, np.full(max_new - len(row), pad, np.int32)])
            rows.append(row)
        return jnp.asarray(np.stack(rows))

    def generate_pertoken(self, params, batch: Dict[str, Array], *,
                          max_new: int, temperature: float = 0.0,
                          key: Optional[Array] = None) -> Array:
        """The pre-rebuild per-token loop: one jit dispatch per token.

        Kept as the benchmark baseline and as a cross-check oracle."""
        if not hasattr(self, "_legacy_prefill"):
            cfg, ctx = self.cfg, self.ctx

            def prefill(params, batch):
                return api.prefill_fn(params, batch, cfg, ctx, self.window)

            def decode(params, token, cache):
                return api.decode_fn(params, token, cache, cfg, ctx)

            self._legacy_prefill = jax.jit(prefill)
            self._legacy_decode = jax.jit(decode, donate_argnums=(2,))

        def pick(logits, k):
            last = logits[:, -1, :].astype(jnp.float32)
            if temperature <= 0.0 or k is None:
                return jnp.argmax(last, axis=-1)[:, None].astype(jnp.int32)
            return jax.random.categorical(
                k, last / temperature, axis=-1)[:, None].astype(jnp.int32)

        logits, cache = self._legacy_prefill(params, batch)
        tokens = []
        tok = pick(logits, key)
        for i in range(max_new):
            tokens.append(tok)
            logits, cache = self._legacy_decode(params, tok, cache)
            key_i = (jax.random.fold_in(key, i + 1)
                     if key is not None else None)
            tok = pick(logits, key_i)
            self.counters["pertoken_steps"] += 1
        return jnp.concatenate(tokens, axis=1)


def quantize_weights(params: Any, dtype=jnp.float8_e4m3fn) -> Any:
    """Weight-only storage quantization (embeddings/norms stay bf16)."""

    def leaf(p: Array) -> Array:
        if p.ndim >= 2 and jnp.issubdtype(p.dtype, jnp.floating):
            return p.astype(dtype)
        return p

    return jax.tree.map(leaf, params)
