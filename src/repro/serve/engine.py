"""Continuous-batching serving engine over the sparse decode stack.

The engine turns the straight-line ``serve()`` loop into a serving
system:

* a request queue with per-request prompt / generation-budget state
  (``repro.serve.request``);
* a slot scheduler that admits new requests into freed batch slots
  mid-flight — no drain barrier, decode keeps running at full batch
  width under a stream of arrivals (``repro.serve.scheduler``);
* a slotted KV-cache manager that reuses one donated ``init_cache``
  allocation across request lifetimes (``repro.serve.cache``) — or, with
  ``paged=True``, a paged KV cache (``repro.serve.paging``): fixed-size
  pages allocated lazily off a free list and gathered through per-slot
  page tables, so reserved cache bytes scale with live tokens instead of
  ``num_slots × max_len`` and out-of-pages admission queues instead of
  crashing;
* a chunked prefill subsystem (``repro.serve.prefill``): admitted
  prompts are ingested ``prefill_chunk`` tokens at a time through one
  batched ``build_prefill_step`` call per engine step — chunks from
  every mid-prefill request ride one padded ``(B, C)`` call, every
  projection dispatches at M = C through the packed weight stream, and
  decode keeps running between calls — instead of teacher-forcing each
  prompt through the decode step one position per step
  (``prefill_chunk=0`` keeps that legacy walk as the equivalence
  oracle);
* weights pruned once (``global_l1_prune``) and the *whole serve-time
  stack* packed once into the paper's ``BitmapWeight`` format
  (``repro.serve.packed.pack_model``): attention q/k/v/o, MLP
  gate/up/down, the MoE router + expert stacks, the mamba/rwkv mixer
  and channel-mix projections, and the LM head all dispatch through
  ``kernels/ops.bitmap_spmm`` (per-expert: ``bitmap_spmm_grouped``)
  every decode step — the bitmap-compressed HBM path runs end-to-end at
  serve time, and the per-tensor manifest records what packed vs fell
  back (and why).  DESIGN_PACKED.md documents the subsystem.

Positions are per-slot: the decode step takes a (B,) position vector so
each slot advances through its own sequence independently (the models
layer grew vector-position support for exactly this).

**Request lifecycle + failure semantics** (DESIGN_SERVING.md §Failure
semantics): every request ends in exactly one terminal state — DONE,
CANCELLED (``engine.cancel(rid)``, valid queued / mid-prefill /
mid-decode / mid-preempt-replay), EXPIRED (``deadline_ms`` elapsed), or
SHED (admission control under overload raises/records the typed
``ServeOverloaded``).  A request preempted ``max_preempts`` times is
*pinned*: it re-admits with a worst-case (reserved-page) commitment and
is excluded from victim selection, so recompute-on-preempt can never
livelock one request.  ``audit=True`` runs the step-level invariant
auditor (``repro.serve.faults.InvariantAuditor``) and turns on packed-
tensor integrity scanning: a corrupted tensor (seeded ``FaultPlan``
bitflips, NaN-poisoned heads, or real bit-rot) is quarantined to its
dense fallback with a recorded manifest reason and the engine replays
the affected step deterministically instead of serving garbage.
"""
from __future__ import annotations

import math
import contextlib
import time
import warnings
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch import sharding as shd
from repro.launch.mesh import make_elastic_mesh
from repro.launch.steps import (build_prefill_step, build_prefill_step_spmd,
                                build_serve_step, build_serve_step_spmd)
from repro.models.config import ModelConfig
from repro.models.model import init_params, lm_head_weight
from repro.serve.cache import SlotKVCache
from repro.serve.errors import (DeadlineExceeded, RequestRejected,
                                ServeOverloaded)
from repro.serve.faults import FaultPlan, InvariantAuditor
from repro.serve.packed import (ROUTED_EXPERT, PackedModel, block_reason,
                                choose_block, pack_model)
from repro.serve.paging import OutOfPages, PagedKVCache
from repro.serve.prefill import PrefillPlanner
from repro.serve.request import Request, RequestState
from repro.serve.scheduler import SlotScheduler
from repro.serve.telemetry import (SPAN_PREFIX, STEP_SPAN, Clock,
                                   MetricsRegistry, Telemetry)
from repro.sparse.format import BitmapWeight, pack_bitmap, shard_bitmap
from repro.sparse.pruning import global_l1_prune, per_tensor_prune, \
    sparsity_of


def _head_block(d_model: int, vocab: int, chip: bool = False,
                cap: int = 128) -> Optional[Tuple[int, int]]:
    """Largest (BK, BN) bitmap tile that divides the head (BN % 8 == 0)
    and, for the compiled TPU kernel (``chip``), that the chip takes."""
    block = choose_block(d_model, vocab, cap)
    return None if block_reason(d_model, vocab, block, chip) else block


def pack_lm_head(params, cfg: ModelConfig, sparsity: float = 0.0,
                 shards: int = 1, chip: bool = False
                 ) -> Optional[BitmapWeight]:
    """Prune (per-tensor) + pack the (D, V) LM head once for serving.

    ``shards > 1`` asks for the vocab-split (column-parallel) sharded
    layout: the head packs against a tile of the per-shard ``(D, V/S)``
    slice and ``shard_bitmap`` splits the tile axes, so each model-axis
    device stores 1/S of the packed head.  Falls back to the replicated
    pack (``shard=None`` — the caller records the typed reason) when the
    vocab doesn't divide or no per-shard tile fits."""
    block = _head_block(cfg.d_model, cfg.vocab_size, chip)
    if block is None:
        return None
    w = lm_head_weight(params, cfg)
    if sparsity > 0:
        w = per_tensor_prune(w, sparsity)
    wf = np.asarray(w.astype(jnp.float32))
    if shards > 1 and cfg.vocab_size % shards == 0:
        sblock = _head_block(cfg.d_model, cfg.vocab_size // shards, chip)
        if sblock is not None:
            bw = pack_bitmap(wf, block=sblock)
            return shard_bitmap(bw, shards, "col")
    return pack_bitmap(wf, block=block)


class ServeEngine:
    """Continuous-batching decode over ``num_slots`` batch slots.

    Its 28 keyword options, each documented in ``__init__``: shape and
    placement (``num_slots``, ``max_len``, ``seed``, ``model_parallel``,
    ``devices``, ``impl``), the packed weights (``sparsity``,
    ``stream_weights``, ``bitmap_head``, ``head_sparsity``), sampling
    (``top_k``), the KV cache (``paged``, ``page_len``,
    ``page_pool_tokens``, ``kv_shards``, ``prefix_reuse``), prefill
    (``prefill_chunk``), admission and lifecycle (``preempt``,
    ``max_preempts``, ``history``, ``deadline_ms``, ``max_queue``,
    ``ttft_budget_ms``), fault handling (``audit``, ``faults``) and
    telemetry (``trace_out``, ``events_out``, ``metrics_out``).
    """

    def __init__(self, cfg: ModelConfig, *, num_slots: int = 4,
                 max_len: int = 128, sparsity: float = 0.0, seed: int = 0,
                 model_parallel: int = 1, impl: Optional[str] = None,
                 devices: Optional[Sequence] = None,
                 bitmap_head: bool = True,
                 head_sparsity: Optional[float] = None,
                 stream_weights: bool = True, top_k: int = 0,
                 paged: bool = False, page_len: int = 16,
                 page_pool_tokens: Optional[int] = None,
                 kv_shards: Optional[int] = None,
                 prefill_chunk: int = 0, prefix_reuse: bool = False,
                 preempt: bool = False, history: int = 512,
                 deadline_ms: Optional[float] = None,
                 max_queue: Optional[int] = None,
                 ttft_budget_ms: Optional[float] = None,
                 max_preempts: int = 8, audit: bool = False,
                 faults: Optional[FaultPlan] = None,
                 trace_out: Optional[str] = None,
                 events_out: Optional[str] = None,
                 metrics_out: Optional[str] = None):
        """``devices``: the devices the engine's (data, model) mesh spans
        (default: every visible device — more than ``model_parallel``
        of them adds a data axis and routes the steps through
        ``shard_map``); pass ``jax.devices()[:1]`` to hold a one-chip
        engine to one chip on a multi-chip host.

        ``head_sparsity``: ``global_l1_prune`` deliberately keeps
        (tied) embeddings dense, so the LM head is additionally pruned
        per-tensor to this level before packing — that is what gives the
        bitmap head its compression at serve time.  Defaults to
        ``sparsity``; pass 0.0 to stream the exact dense head through the
        bitmap path instead (compression < 1, numerics identical to the
        dense head).

        ``stream_weights``: pack the whole decode stack (attention
        q/k/v/o, MLP gate/up/down, MoE router + expert stacks, SSM
        mixer / channel-mix projections) once via ``pack_model`` and
        stream it bitmap-compressed every step.  Packing is lossless, so
        tokens are identical to dense dispatch at any sparsity; pass
        False for a dense-dispatch baseline.

        ``top_k``: engine-default top-k truncation for sampled requests
        (0 = no truncation); each request may override it via
        ``submit(top_k=...)`` — the jitted sampler then applies a
        per-slot masked top-k (all-default serving keeps the static
        ``lax.top_k`` path; the first override costs one extra jit
        signature, mirroring how sampling itself engages).

        ``paged``: page the attention KV cache (``repro.serve.paging``)
        into ``page_len``-token pages gathered through per-slot page
        tables — reserved cache bytes scale with live tokens instead of
        ``num_slots × max_len``.  ``page_pool_tokens`` bounds each page
        pool (default: worst case, still lazily allocated); when pages
        run out, admission queues until retirements free pages.
        ``paged=False`` (or ``page_len=0``) keeps the contiguous layout.

        ``prefill_chunk``: ingest admitted prompts in batched
        ``prefill_chunk``-token chunks (one ``build_prefill_step`` call
        per engine step, chunks from every mid-prefill request batched
        together) instead of teacher-forcing them through decode steps
        one position at a time.  0 keeps the legacy teacher-forcing walk
        — the equivalence oracle: chunked prefill is token-identical to
        it.  Archs with recurrent mixer state (mamba/rwkv/rwkv_cm) or
        the frames frontend fall back to teacher-forcing with a recorded
        reason.

        ``prefix_reuse``: hash ``page_len``-token prompt blocks and map
        a new request's matching prefix onto already-resident physical
        pages copy-on-write (``repro.serve.paging`` prefix cache) — the
        matched region skips prefill entirely, so TTFT on a hit
        collapses to queue + first-decode.  Requires paging; archs with
        recurrent mixer state or the frames frontend fall back with a
        recorded reason (pages don't capture that state, so skipping
        ingestion would drop it).

        ``preempt``: recompute-on-preempt eviction.  Admission commits
        only the *live* ingest pages instead of the worst case
        (occupancy rises at equal pool size); when the free list runs
        dry mid-flight the engine evicts cached prefixes and then
        preempts the youngest slot — its pages return to the pool and
        the request re-queues at the head of the FIFO with prompt +
        already-generated tokens re-ingested on re-admission.  Sampling
        keys fold the absolute position, so recomputed requests emit
        token-identical streams.  Requires paging; the frames frontend
        falls back (its embeds derive from the global step counter, so
        a recompute would diverge).

        ``history``: retired requests kept for inspection (a bounded
        deque); latency aggregates are folded in at retire time
        (``RollingStat``), so a long-lived engine's memory and
        ``report()`` cost stay O(history), not O(total traffic).

        ``deadline_ms``: default per-request latency budget, measured
        from the moment a request's arrival comes due; requests that
        blow it — queued or mid-flight — expire with a recorded
        ``DeadlineExceeded`` (``submit(deadline_ms=...)`` overrides
        per request; None = no deadline).

        ``max_queue`` / ``ttft_budget_ms``: admission-control load
        shedding.  A request that comes due while more than
        ``max_queue`` requests are already due-and-waiting, or while
        the estimated TTFT (queue drain at the observed step rate)
        exceeds ``ttft_budget_ms``, is shed with a typed
        ``ServeOverloaded`` — raised from ``submit`` for requests due
        immediately, recorded on the request for future arrivals.
        None disables shedding (the pre-hardening behavior: queue
        forever).

        ``max_preempts``: bounded-preemption policy.  A request
        preempted this many times re-admits *pinned* — worst-case page
        commitment (the reserved-page fast path) and excluded from
        victim selection — so it finishes instead of livelocking.

        ``audit``: run the step-level invariant auditor every step
        (scheduler slots, page refcount conservation, free xor
        referenced, table aliasing, request-state legality, finite
        logits) plus packed-tensor integrity scans; corruption is
        quarantined to the dense fallback and the step replayed.

        ``faults``: a seeded ``repro.serve.faults.FaultPlan`` whose
        scheduled faults the engine fires at each step start — the
        chaos harness.  Injected faults are deterministic and (under
        ``audit=True``) recoverable: served tokens stay bit-identical
        to a fault-free run.

        ``trace_out`` / ``events_out`` / ``metrics_out``: telemetry
        artifacts (``repro.serve.telemetry``), finished by ``close()``:
        a profiler trace directory (the engine runs a
        ``jax.profiler`` session from its first step to ``close()``:
        its ``serve.*`` step and phase spans beside the device's
        operations, on one clock; Perfetto-viewable), a structured JSONL
        event log, and a metrics-registry snapshot (JSON, or Prometheus
        text for ``.prom`` paths).  Setting any of them also feeds the
        per-phase histograms.  All three default off — telemetry-off
        serving is bit-identical (spans and events are plain ``is not
        None`` checks; the metrics registry itself is always on, since
        ``report()`` is rendered from it).  The ``serve.*`` profiler
        spans are opened either way and record whenever any profiler
        session is on.
        """
        self.cfg = cfg
        self.metrics = MetricsRegistry()
        self._clock = Clock()
        self._steps = 0
        # telemetry first: init-time fallback warnings below emit into
        # the event log, so spans/events must exist before any
        # _warn_fallback can fire
        self.telemetry: Optional[Telemetry] = None
        if trace_out or events_out or metrics_out:
            self.telemetry = Telemetry(self.metrics, trace_out=trace_out,
                                       events_out=events_out,
                                       metrics_out=metrics_out)
        self.spans = (self.telemetry.spans
                      if self.telemetry is not None else None)
        self.events = (self.telemetry.events
                       if self.telemetry is not None else None)
        self.num_slots = num_slots
        self.max_len = max_len
        self.sparsity = sparsity
        self.mesh = make_elastic_mesh(model_parallel, devices)
        # fallback bookkeeping: every recorded reason lands in
        # ``self.fallbacks`` (mirrored by report()["fallbacks"]) and is
        # warned at most once per (key, reason) per engine instance
        self.fallbacks: Dict[str, str] = {}
        self._warned: set = set()

        params = init_params(jax.random.PRNGKey(seed), cfg)
        if sparsity > 0:
            params = global_l1_prune(params, sparsity)
        self.weight_sparsity = sparsity_of(params) if sparsity > 0 else 0.0
        pspecs = shd.named(self.mesh, shd.param_specs(cfg, self.mesh))
        self.params = jax.device_put(params, pspecs)

        # pack once, cache on the engine: every decode step streams the
        # stack + head through the bitmap-compressed kernels/ops path.
        # On the compiled TPU kernel a tile the chip refuses serves dense
        # with a typed reason.
        from repro.kernels.ops import default_impl
        impl = impl or default_impl()
        chip = impl == "pallas"
        self.stream_fallback: Optional[str] = None
        mp_actual = int(self.mesh.shape.get("model", 1))
        self.model_parallel = mp_actual
        # SPMD serving: any multi-device elastic mesh routes the decode /
        # prefill steps through shard_map (steps.build_serve_step_spmd) —
        # packed BitmapWeight leaves shard their explicit shard axis over
        # the "model" axis (format.shard_bitmap layout), paged KV pools
        # shard their pages axis over "data".  Single device keeps the
        # plain jitted steps, bit-identical to before.
        self._spmd = int(self.mesh.devices.size) > 1
        if not stream_weights:
            self.stream_fallback = "stream_weights=False"
            self.fallbacks["stream"] = self.stream_fallback
        self.packed: Optional[PackedModel] = (
            pack_model(self.params, shards=(mp_actual if self._spmd else 1),
                       chip=chip)
            if stream_weights else None)
        if self._spmd and self.packed is not None:
            # place each sharded leaf's shard axis on its own model-axis
            # device (replicated-fallback leaves broadcast) — the
            # per-device packed-HBM cut the stream report models
            self.packed.blocks = jax.device_put(
                self.packed.blocks,
                shd.named(self.mesh,
                          shd.packed_specs(self.packed.blocks, self.mesh)))
        self.head_sparsity = (sparsity if head_sparsity is None
                              else head_sparsity)
        self.head_fallback: Optional[str] = None
        self.head_shard_fallback: Optional[str] = None
        if bitmap_head:
            self.lm_weight = pack_lm_head(
                self.params, cfg, self.head_sparsity,
                shards=(mp_actual if self._spmd else 1), chip=chip)
            if (self._spmd and mp_actual > 1
                    and self.lm_weight is not None
                    and self.lm_weight.shard is None):
                self.head_shard_fallback = (
                    f"shard: vocab={cfg.vocab_size} not divisible by "
                    f"{mp_actual} shards (or no per-shard tile); head "
                    f"stored replicated")
                self._warn_fallback(
                    "head_shard", self.head_shard_fallback,
                    f"bitmap LM head stored replicated: "
                    f"{self.head_shard_fallback}")
            if self._spmd and self.lm_weight is not None:
                self.lm_weight = jax.device_put(
                    self.lm_weight,
                    shd.named(self.mesh,
                              shd.bitmap_specs(self.lm_weight, self.mesh)))
            if self.lm_weight is None:
                why = block_reason(
                    cfg.d_model, cfg.vocab_size,
                    choose_block(cfg.d_model, cfg.vocab_size), chip)
                self.head_fallback = (
                    f"LM head (d_model={cfg.d_model}, "
                    f"vocab={cfg.vocab_size}): {why}; head served dense")
                self._warn_fallback(
                    "head", self.head_fallback,
                    f"bitmap LM head fell back to dense: "
                    f"{self.head_fallback}")
        else:
            self.lm_weight = None
            self.head_fallback = "disabled (bitmap_head=False)"
            self.fallbacks["head"] = self.head_fallback
        self.head_compression = (self.lm_weight.compression
                                 if self.lm_weight is not None else 1.0)

        self.scheduler = SlotScheduler(num_slots, history=history)
        # paged KV cache: pages only help when some block caches per-token
        # KV lines — otherwise fall back to contiguous with a reason
        self.paging_fallback: Optional[str] = None
        if not paged:
            page_len = 0
        elif not any(b.mixer == "attn" for b in cfg.pattern):
            page_len = 0
            self.paging_fallback = (
                f"{cfg.name}: no attention blocks — recurrent state is "
                f"O(1)/slot, nothing to page")
        if self.paging_fallback:
            self._warn_fallback(
                "paging", self.paging_fallback,
                f"paged KV cache fell back to contiguous: "
                f"{self.paging_fallback}")
        self.page_len = page_len

        # shared-prefix reuse + preemption both live on the paged cache;
        # each falls back (recorded reason, same idiom as above) when its
        # preconditions don't hold rather than failing the engine
        recurrent = any(b.mixer != "attn" or b.ffn == "rwkv_cm"
                        for b in cfg.pattern)
        self.prefix_fallback: Optional[str] = None
        if prefix_reuse:
            if not page_len:
                self.prefix_fallback = (
                    "paged KV cache disabled (or fell back to "
                    "contiguous); no pages to share")
            elif cfg.frontend == "frames":
                self.prefix_fallback = (
                    f"{cfg.name}: frames frontend derives embeds from "
                    f"the step counter; prompt-token hashing is "
                    f"meaningless")
            elif recurrent:
                self.prefix_fallback = (
                    f"{cfg.name}: recurrent mixer state (mamba/rwkv) is "
                    f"not captured by KV pages; skipping ingestion "
                    f"would drop it")
            if self.prefix_fallback:
                prefix_reuse = False
                self._warn_fallback(
                    "prefix_reuse", self.prefix_fallback,
                    f"shared-prefix reuse fell back: "
                    f"{self.prefix_fallback}")
        self.prefix_reuse = prefix_reuse
        self.preempt_fallback: Optional[str] = None
        if preempt:
            if not page_len:
                self.preempt_fallback = (
                    "paged KV cache disabled (or fell back to "
                    "contiguous); no pages to reclaim")
            elif cfg.frontend == "frames":
                self.preempt_fallback = (
                    f"{cfg.name}: frames embeds fold the global step "
                    f"counter, so a preempted request's recompute would "
                    f"diverge from its first run")
            if self.preempt_fallback:
                preempt = False
                self._warn_fallback(
                    "preempt", self.preempt_fallback,
                    f"recompute-on-preempt fell back: "
                    f"{self.preempt_fallback}")
        self.preempt = preempt

        # data-axis KV sharding: partition the paged pools' page-id
        # ranges (and the slots) across the mesh "data" axis so every
        # slot's pages are device-local — allocation stays host-side,
        # the shard_map step gathers/slices the pools per call.  Auto
        # (kv_shards=None): the data extent whenever it divides the
        # slot count; indivisible shapes record a typed reason and keep
        # the replicated pool instead of crashing.
        self.kv_shard_fallback: Optional[str] = None
        ndata = int(self.mesh.shape.get("data", 1))
        kv_actual = 1
        if page_len and self._spmd and ndata > 1:
            want = ndata if kv_shards is None else int(kv_shards)
            if want > 1 and (num_slots % want == 0 and want <= num_slots
                             and want == ndata):
                kv_actual = want
            elif want > 1:
                self.kv_shard_fallback = (
                    f"shard: kv_shards={want} must equal the mesh data "
                    f"axis ({ndata}) and divide num_slots={num_slots}; "
                    f"page pools stored replicated")
                self._warn_fallback(
                    "kv_shard", self.kv_shard_fallback,
                    f"paged KV pools stored replicated: "
                    f"{self.kv_shard_fallback}")
        self.kv = (PagedKVCache(cfg, num_slots, max_len, page_len,
                                pool_tokens=page_pool_tokens,
                                strict=not preempt, shards=kv_actual)
                   if page_len else SlotKVCache(cfg, num_slots, max_len))
        self._kv_data_pools: Tuple[str, ...] = (
            tuple(self.kv.pools) if page_len and kv_actual > 1 else ())
        self.top_k_default = top_k
        if self._spmd:
            step_fn = build_serve_step_spmd(
                cfg, self.mesh, impl=impl, top_k=top_k,
                data_pools=self._kv_data_pools)
        else:
            step_fn = build_serve_step(cfg, impl=impl, top_k=top_k)
        self._jit_step = jax.jit(step_fn, donate_argnums=(1,))

        # chunked prefill: admitted prompts are ingested prefill_chunk
        # tokens at a time through one batched prefill call per engine
        # step; 0 keeps the legacy teacher-forced prompt walk (the
        # equivalence oracle).  Recurrent mixer state advances one token
        # per step by construction, and the frames frontend derives its
        # embeds from the step counter — both keep teacher-forcing with
        # a recorded reason, like the paging fallbacks above.
        self.prefill_fallback: Optional[str] = None
        if prefill_chunk > 0:
            if cfg.frontend == "frames":
                self.prefill_fallback = (
                    f"{cfg.name}: frames frontend derives per-step embeds "
                    f"from the step counter; nothing to prefill")
            elif any(b.mixer != "attn" or b.ffn == "rwkv_cm"
                     for b in cfg.pattern):
                self.prefill_fallback = (
                    f"{cfg.name}: recurrent mixer state (mamba/rwkv) has "
                    f"no chunked prefill path yet; teacher-forcing kept")
            if self.prefill_fallback:
                prefill_chunk = 0
                self._warn_fallback(
                    "prefill", self.prefill_fallback,
                    f"chunked prefill fell back to teacher-forcing: "
                    f"{self.prefill_fallback}")
        self.prefill_chunk = prefill_chunk
        self.planner: Optional[PrefillPlanner] = (
            PrefillPlanner(num_slots, prefill_chunk)
            if prefill_chunk else None)
        if self._spmd:
            prefill_fn = build_prefill_step_spmd(
                cfg, self.mesh, impl=impl,
                data_pools=self._kv_data_pools)
        else:
            prefill_fn = build_prefill_step(cfg, impl=impl)
        self._jit_prefill = (
            jax.jit(prefill_fn, donate_argnums=(1,))
            if prefill_chunk else None)
        # engine-owned accounting lives in the metrics registry — the
        # report sections below are rendered views over these metrics
        m = self.metrics
        self._c_prefill_steps = m.counter(
            "steps.prefill", help="engine steps that ran a prefill call")
        self._c_decode_steps = m.counter(
            "steps.decode", help="engine steps that ran a decode call")

        self._tok = np.zeros(num_slots, np.int32)
        self._pos = np.zeros(num_slots, np.int32)
        # frames frontend: per-step embeddings come from a jax PRNG key
        # folded with the step counter *inside* the jitted step — the old
        # host-side standard_normal forced a host sync every decode step
        self._embed_key = jax.random.PRNGKey(seed + 0x5eed)
        # per-slot sampling state (greedy slots keep temperature 0).
        # _use_sampling stays False until some request asks for T > 0, so
        # all-greedy serving never pays the categorical/top-k machinery
        # (flipping it later costs one extra jit signature compile).
        self._use_sampling = False
        # the per-slot top-k vector (a full-vocab sort in the sampler)
        # only engages once some request *overrides* the engine default —
        # all-default serving keeps the cheaper static lax.top_k path
        self._use_topk_vec = False
        self._temp = np.zeros(num_slots, np.float32)
        self._topk = np.zeros(num_slots, np.int32)
        self._keys = np.zeros((num_slots, 2), np.uint32)
        self._seed = seed
        self._warm = False
        self._c_slot_steps = m.counter(
            "steps.active_slots",
            help="decoding slot-steps (occupancy numerator)")
        self._next_rid = 0
        # per-slot ingest = prompt + tokens generated before a preemption
        # — the teacher-forcing/prefill source, so a recomputed request
        # replays its own history instead of resampling it
        self._ingest: Dict[int, List[int]] = {}
        self._admit_seq = np.zeros(num_slots, np.int64)  # preempt order
        self._admit_counter = 0
        self._c_recomputed = m.counter(
            "tokens.recomputed",
            help="positions re-ingested after preemption")
        # bounded retained history + streaming aggregates: report() reads
        # these instead of rescanning every request ever submitted (the
        # registry histograms keep the seeded RollingStat reservoirs)
        self.history = history
        self.requests: deque = deque(maxlen=max(1, history))
        self._c_done = m.counter("requests.done",
                                 help="requests retired DONE")
        self._c_gen_tokens = m.counter("tokens.generated",
                                       help="tokens delivered by DONE "
                                            "requests")
        self._h_lat = m.histogram("request.latency_s", seed=1,
                                  help="arrival-due -> last token")
        self._h_ftl = m.histogram("request.first_token_s", seed=2,
                                  help="arrival-due -> first token")
        self._h_queue = m.histogram("request.queue_s", seed=3,
                                    help="arrival-due -> slot granted")
        self._h_prefill = m.histogram("request.prefill_s", seed=4,
                                      help="slot granted -> prompt "
                                           "cache resident")
        self._h_fdec = m.histogram("request.first_decode_s", seed=5,
                                   help="prompt resident -> first token")
        self._h_ftl_hit = m.histogram("request.ttft_hit_s", seed=6,
                                      help="TTFT, prefix-cache hits")
        self._h_ftl_miss = m.histogram("request.ttft_miss_s", seed=7,
                                       help="TTFT, prefix-cache misses")

        # ---- lifecycle hardening: deadlines, shedding, bounded
        # preemption, fault injection + invariant auditing ----
        self.deadline_ms = deadline_ms
        self.max_queue = max_queue
        self.ttft_budget_ms = ttft_budget_ms
        self.max_preempts = max_preempts
        self._has_deadlines = deadline_ms is not None
        self._c_cancelled = m.counter("requests.cancelled")
        self._c_expired = m.counter("requests.expired")
        self._c_shed = m.counter("requests.shed")
        self._c_forced_preempts = m.counter(
            "preempts.forced", help="fault-injected forced preemptions")
        self._c_wasted = m.counter(
            "tokens.wasted", help="tokens generated by aborted requests")
        self._step_wall_ema: Optional[float] = None  # TTFT estimator
        self.quarantined: Dict[str, str] = {}
        self.faults = faults
        self.audit = audit
        # checksums are taken here, before any fault can fire — the
        # auditor's integrity scans compare against this pristine state
        self.auditor: Optional[InvariantAuditor] = (
            InvariantAuditor(self) if audit else None)

        # ---- telemetry: every subsystem registers into the one
        # registry; phase histograms and events only exist when an
        # output is asked for ----
        self.scheduler.register_metrics(m)
        self.kv.register_metrics(m)
        if self.planner is not None:
            self.planner.register_metrics(m)
        if self.packed is not None:
            self.packed.register_metrics(m)
        if self.faults is not None:
            self.faults.register_metrics(m)
        if self.auditor is not None:
            self.auditor.register_metrics(m)
        m.gauge("steps.total", lambda: self._steps,
                help="engine steps taken (includes idle fast-forward)")
        m.gauge("queue.due_depth", self._due_depth,
                help="waiting requests whose arrival has come due")
        self._register_report_views()

    @classmethod
    def from_arch(cls, arch: str, smoke: bool = True, **kw) -> "ServeEngine":
        cfg = get_smoke_config(arch) if smoke else get_config(arch)
        return cls(cfg, **kw)

    def _warn_fallback(self, key: str, reason: str,
                       message: Optional[str] = None) -> None:
        """Record a fallback reason (mirrored into
        ``report()["fallbacks"]``) and warn it — once per (key, reason)
        per engine instance, never once per request or step."""
        self.fallbacks[key] = reason
        msg = message or f"{key} fell back: {reason}"
        if (key, reason) not in self._warned:
            self._warned.add((key, reason))
            self._emit("fallback", key=key, reason=reason)
            warnings.warn(msg, stacklevel=3)

    # --------------------------------------------------------- telemetry ----

    @property
    def _forced_preempts(self) -> int:
        """Fault-injected forced-preemption count (registry counter)."""
        return self._c_forced_preempts.value

    def _emit(self, kind: str, rid: Optional[int] = None,
              **fields) -> None:
        """Append to the structured event log (no-op when telemetry is
        off — a single ``is None`` check, nothing allocated)."""
        if self.events is not None:
            self.events.emit(kind, t=self._clock.now_or_zero(),
                             step=self._steps, rid=rid, **fields)

    def close(self) -> List[str]:
        """Write the configured telemetry artifacts (``--trace-out`` /
        ``--events-out`` / ``--metrics-out``); idempotent, returns the
        paths written.  An artifacts-off engine returns []."""
        if self.telemetry is None:
            return []
        return self.telemetry.close()

    @contextlib.contextmanager
    def _phase(self, name: str):
        """One host phase of ``step`` (``telemetry.PHASES``): the
        profiler span ``serve.<name>`` and, with telemetry on, one
        observation of the ``step.phase.<name>_s`` histogram."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield
        if self.spans is not None:
            self.spans.phase_done(name, time.perf_counter() - t0)

    def _register_report_views(self) -> None:
        """Register ``report()``'s top-level fields and sections as
        registry views, in the legacy key order — ``report()`` is then
        a rendered snapshot of the registry and nothing else.  Field
        names and types are pinned by the schema snapshot test."""
        m = self.metrics
        m.view("requests", lambda: self._c_done.value)
        m.view("retained_requests", lambda: len(self.requests))
        m.view("generated_tokens", lambda: self._c_gen_tokens.value)
        m.view("steps", lambda: self._steps)
        m.view("wall_s", lambda: (self._clock.now()
                                  if self._clock.started else 0.0))

        def tok_per_s():
            dt = self._clock.now() if self._clock.started else 0.0
            gen = self._c_gen_tokens.value
            return gen / dt if dt > 0 else float("nan")

        m.view("tok_per_s", tok_per_s)
        m.view("latency_s", self._h_lat.percentiles)
        m.view("first_token_s", self._h_ftl.percentiles)
        # TTFT decomposition: queueing (no slot), prompt ingestion
        # (chunked prefill calls or the legacy teacher-forced walk),
        # and the first real decode step — first_token_s is their sum
        m.view("ttft", lambda: {
            "queue_s": self._h_queue.percentiles(),
            "prefill_s": self._h_prefill.percentiles(),
            "first_decode_s": self._h_fdec.percentiles(),
        })
        m.view("prefill", self.prefill_report)
        m.view("prefix_reuse", self.prefix_reuse_report)
        m.view("slot_occupancy",
               lambda: (self._c_slot_steps.value
                        / (self._steps * self.num_slots)
                        if self._steps else 0.0))
        m.view("weight_sparsity", lambda: self.weight_sparsity)
        m.view("head_compression", lambda: self.head_compression)
        m.view("head_fallback", lambda: self.head_fallback)
        m.view("weight_stream", self.weight_stream_report)
        m.view("paging", self.paging_report)
        m.view("cache_resets", lambda: self.kv.resets)
        m.view("lifecycle", self.lifecycle_report)
        m.view("fallbacks", lambda: dict(self.fallbacks))

    # ------------------------------------------------------------ intake ----

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               arrival: float = 0.0, temperature: float = 0.0,
               seed: Optional[int] = None,
               top_k: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> Request:
        """``temperature`` > 0 samples this request's tokens with its own
        PRNG stream, seeded by ``seed`` (default: engine seed + rid); 0
        stays greedy.  ``top_k`` truncates *this request's* sampling
        (None: the engine default; 0: no truncation).  ``deadline_ms``
        overrides the engine-default latency budget for this request
        (measured from the moment its arrival comes due).

        Raises ``RequestRejected`` (typed, process keeps serving) when
        the request can never run: empty prompt, a generation budget
        below one token, budget beyond ``max_len``, or — under paging —
        a worst-case page need larger than the whole pool.  Raises
        ``ServeOverloaded`` when the request is due *now* and admission
        control is shedding (``max_queue`` / ``ttft_budget_ms``);
        future arrivals are accepted and re-checked when they come due.
        A merely *busy* engine without shedding configured never
        rejects; the request queues until slots (and pages) free up."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise RequestRejected("empty prompt")
        if max_new_tokens < 1:
            # the engine's done-check runs only after appending a token,
            # so a zero budget would quietly generate one anyway — reject
            # it typed instead of silently over-delivering
            raise RequestRejected(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        need = len(prompt) + max_new_tokens - 1
        if need > self.max_len:
            raise RequestRejected(
                f"prompt {len(prompt)} + {max_new_tokens} new tokens "
                f"exceeds max_len {self.max_len}")
        if self.page_len and not self.kv.possible(need):
            raise RequestRejected(
                f"prompt {len(prompt)} + {max_new_tokens} new tokens needs "
                f"more pages than the whole pool holds "
                f"(page_len={self.page_len}); raise page_pool_tokens")
        if arrival <= self._steps:
            reason = self._overload_reason()
            if reason is not None:
                self._c_shed.inc()
                self._emit("shed", reason=reason, at="submit")
                raise ServeOverloaded(
                    reason, queue_depth=self._due_depth(),
                    est_ttft_s=self.estimated_ttft_s())
        req = Request(rid=self._next_rid, prompt=prompt,
                      max_new_tokens=max_new_tokens, arrival=arrival,
                      temperature=temperature, seed=seed, top_k=top_k,
                      deadline_ms=(deadline_ms if deadline_ms is not None
                                   else self.deadline_ms))
        if req.deadline_ms is not None:
            self._has_deadlines = True
        if temperature > 0:
            self._use_sampling = True
        if top_k is not None and top_k != self.top_k_default:
            self._use_topk_vec = True
        self._next_rid += 1
        # the scheduler owns the request until retirement; the engine's
        # bounded ``requests`` history only receives it when done (the
        # old append-on-submit list grew with total traffic forever)
        self.scheduler.submit(req)
        self._emit("submit", rid=req.rid, prompt_tokens=len(prompt),
                   max_new_tokens=max_new_tokens, arrival=arrival)
        return req

    # -------------------------------------------------------- lifecycle ----

    def cancel(self, rid: int) -> bool:
        """Cancel a request by rid, valid at every lifecycle stage:
        queued (including mid-preempt-replay requeue), mid-prefill, or
        mid-decode.  Pages and prefix-cache references are released
        exactly; partial tokens are kept on the request (state
        CANCELLED, no error — the client asked).  Returns False for an
        unknown or already-terminal rid."""
        for r in self.scheduler.waiting:
            if r.rid == rid:
                self.scheduler.cancel_waiting(r)
                r.transition(RequestState.CANCELLED)
                self._abort(r, RequestState.CANCELLED)
                return True
        for slot, r in list(self.scheduler.active.items()):
            if r.rid == rid:
                req = self._release_slot(slot, RequestState.CANCELLED)
                self._abort(req, RequestState.CANCELLED)
                return True
        return False

    def _release_slot(self, slot: int, state: RequestState) -> Request:
        """Tear a slot down into any terminal state through one path —
        planner job, pages, ingest history, and sampling lanes are all
        released, so no abort route can leak."""
        if self.planner is not None:
            self.planner.cancel(slot)
        req = self.scheduler.release(slot, state=state)
        if self.page_len:
            self.kv.retire(slot)
        self._ingest.pop(slot, None)
        self._pos[slot] = 0
        self._temp[slot] = 0.0
        self._topk[slot] = 0
        return req

    def _abort(self, req: Request, state: RequestState,
               error: Optional[Exception] = None) -> None:
        """Terminal bookkeeping for the non-DONE outcomes."""
        req.error = error
        req.done_step = self._steps
        if self._clock.started:
            req.t_done = self._wall()
        if state is RequestState.CANCELLED:
            self._c_cancelled.inc()
        elif state is RequestState.EXPIRED:
            self._c_expired.inc()
        elif state is RequestState.SHED:
            self._c_shed.inc()
        self._c_wasted.inc(len(req.tokens))
        self.requests.append(req)
        self._emit(state.name.lower(), rid=req.rid,
                   tokens=len(req.tokens),
                   reason=str(error) if error is not None else None)

    def _due_depth(self) -> int:
        """Waiting requests whose arrival has come due."""
        return sum(1 for r in self.scheduler.waiting
                   if r.arrival <= self._steps)

    def estimated_ttft_s(self) -> Optional[float]:
        """Deterministic queue-drain TTFT estimate for a request
        arriving now: outstanding work tokens (due queue + live
        remainder) spread over the slots, at the observed per-step wall
        EMA.  None until the first step has been timed."""
        if self._step_wall_ema is None:
            return None
        work = 0
        for r in self.scheduler.waiting:
            if r.arrival <= self._steps:
                work += len(r.prompt) + r.max_new_tokens - 1
        for slot, r in self.scheduler.active.items():
            total = len(r.prompt) + r.max_new_tokens - 1
            work += max(0, total - int(self._pos[slot]))
        return (work / self.num_slots) * self._step_wall_ema

    def _overload_reason(self, exclude_self: bool = False) -> Optional[str]:
        """Shed reason if admission control refuses a request due now.

        ``exclude_self``: the step-start sweep evaluates a request that
        already sits in the waiting queue, so it must not count toward
        its own queue depth (a lone request on an idle engine is never
        "overload")."""
        depth = self._due_depth() - (1 if exclude_self else 0)
        if self.max_queue is not None and depth >= self.max_queue:
            return f"queue depth {depth} >= max_queue {self.max_queue}"
        if self.ttft_budget_ms is not None:
            est = self.estimated_ttft_s()
            if est is not None and est * 1e3 > self.ttft_budget_ms:
                return (f"estimated TTFT {est * 1e3:.1f}ms > budget "
                        f"{self.ttft_budget_ms:.1f}ms")
        return None

    def _deadline_passed(self, req: Request, wall: float) -> bool:
        return (req.deadline_ms is not None and req.t_due is not None
                and (wall - req.t_due) * 1e3 > req.deadline_ms)

    def _pinned(self, slot: int) -> bool:
        """A slot whose request exhausted its preemption budget: it
        holds a worst-case (reserved) page commitment and is excluded
        from victim selection — the reserved-page fast path that lets
        an over-preempted request finish instead of livelocking."""
        req = self.scheduler.active.get(slot)
        return (req is not None
                and len(req.t_preempt) >= self.max_preempts)

    # ------------------------------------------------------------- loop ----

    def _wall(self) -> float:
        return self._clock.now()

    def _commit_tokens(self, req: Request) -> int:
        """Pages to commit at admission, in tokens.  Strict mode commits
        the worst case (prompt + full budget) so allocation can never
        fail mid-flight; preemptible mode commits only the *live* ingest
        (prompt + tokens already generated before a preemption) — more
        requests fit the same pool, and growth past the commitment is
        covered by recompute-on-preempt.  A request that exhausted its
        ``max_preempts`` budget re-admits with the worst case even in
        preemptible mode: its pages are genuinely reserved, so it can
        run to completion untouched (the pinned fast path)."""
        if self.preempt and len(req.t_preempt) < self.max_preempts:
            return len(req.prompt) + len(req.tokens)
        return len(req.prompt) + req.max_new_tokens - 1

    def _with_pages(self, fn, requester: int):
        """Run a page-allocating call, resolving ``OutOfPages`` (raised
        only in preemptible mode, after the prefix cache has been
        drained) by preempting the youngest slot until it succeeds."""
        while True:
            try:
                return fn()
            except OutOfPages:
                self._reclaim(requester)

    def _reclaim(self, requester: int) -> None:
        # sharded pools: only a same-shard victim's pages can serve the
        # requester (page-id ranges are disjoint across shards)
        d = self.kv.slot_shard(requester)
        victims = [s for s in self.scheduler.active
                   if s != requester and not self._pinned(s)
                   and self.kv.slot_shard(s) == d]
        if not victims and self.kv.restore_held():
            # a fault-injected page squeeze confiscated the headroom and
            # there is no one left to preempt: hand the pages back early
            # rather than deadlocking the pinned/last request
            return
        # unreachable by construction: submit() checks possible(), a
        # lone slot's own pages never exceed its capped worst case, and
        # pinned slots hold worst-case commitments (they never need to
        # steal) — a dry pool always implicates an evictable cache entry
        # (already drained) or a preemptable slot
        assert victims, "page pool exhausted with no preemptable slot"
        victim = max(victims, key=lambda s: int(self._admit_seq[s]))
        self._preempt_slot(victim)

    def _preempt_slot(self, slot: int) -> None:
        """Preempt: reclaim the slot's pages and re-queue its request at
        the head of the FIFO.  Everything computed so far is discarded;
        on re-admission the prompt + already-generated tokens re-ingest
        through the normal prefill path (vLLM-style recompute).  Decode
        sampling keys fold the absolute position, so the recomputed
        stream is token-identical to the undisturbed one."""
        req = self.scheduler.active[slot]
        req.t_preempt.append(self._wall())
        self._emit("preempt", rid=req.rid, slot=slot,
                   tokens=len(req.tokens))
        if self.planner is not None:
            self.planner.cancel(slot)
        self.scheduler.requeue(slot)
        if self.page_len:
            self.kv.retire(slot)
        self._ingest.pop(slot, None)
        self._pos[slot] = 0
        self._temp[slot] = 0.0
        self._topk[slot] = 0

    def _retire(self, req: Request) -> None:
        """Fold the finished request into the streaming aggregates and
        the bounded retained history — report() never rescans."""
        self._c_done.inc()
        self._c_gen_tokens.inc(len(req.tokens))
        self._h_lat.observe(req.latency_s)
        self._h_ftl.observe(req.first_token_s)
        self._h_queue.observe(req.queue_s)
        self._h_prefill.observe(req.prefill_s)
        self._h_fdec.observe(req.first_decode_s)
        (self._h_ftl_hit if req.prefix_hit_tokens > 0
         else self._h_ftl_miss).observe(req.first_token_s)
        self.requests.append(req)
        self._emit("done", rid=req.rid, tokens=len(req.tokens),
                   latency_s=req.latency_s)

    def _recover_corruption(self, logits, decoding: List[int]) -> bool:
        """Integrity scan + quarantine + deterministic replay (the
        ``audit=True`` corruption path).  Returns True when corruption
        was found — the caller then discards the step's results.

        Detection: every packed tensor (stack leaves + LM head) is
        checksummed against its pack-time CRC and scanned for
        non-finite values.  Recovery: each corrupted tensor is
        *quarantined* — its packed leaf becomes None so
        ``matmul_or_bitmap`` dispatches the pristine dense params
        tensor, with the reason recorded in the manifest — then the
        prefix cache is flushed (published pages may hold KV lines
        written through the corrupt path) and every active slot is
        preempted, so all in-flight requests replay through the clean
        path.  Packing is lossless and replay deterministic, so the
        recovered stream is bit-identical to a never-faulted run.
        Non-finite logits with *no* attributable tensor raise
        ``AuditViolation`` instead — that is a bug, not a recoverable
        fault."""
        bad = self.auditor.integrity_scan()
        if not bad:
            if logits is not None:
                self.auditor.check_logits(np.asarray(logits), decoding)
            return False
        for path in bad:
            reason = ("quarantined: integrity checksum mismatch "
                      "(served dense from pristine params)")
            if path == "lm_head":
                self.lm_weight = None
                self.head_fallback = reason
                self.head_compression = 1.0
                self._warn_fallback(
                    "head", reason,
                    f"bitmap LM head quarantined to dense: corrupted "
                    f"value/bitmap payload detected")
            else:
                self.packed.quarantine(path, reason)
                self._warn_fallback(
                    f"quarantine:{path}", reason,
                    f"packed tensor {path} quarantined to dense: "
                    f"corrupted value/bitmap payload detected")
            self.quarantined[path] = reason
            self.auditor.drop(path)
            self._emit("quarantine", tensor=path, reason=reason)
        if self.page_len:
            self.kv.flush_prefix()
        for slot in list(self.scheduler.active):
            self._preempt_slot(slot)
        return True

    def _decode_args(self, tok: jnp.ndarray, pos: jnp.ndarray):
        """The ``(args, kwargs)`` of one call of the jitted decode step."""
        packed = self.packed.blocks if self.packed is not None else None
        kw = dict(lm_weight=self.lm_weight, packed=packed)
        if self.page_len:
            kw["page_tables"] = self.kv.tables()
        if self._use_sampling:
            kw.update(sample_keys=jnp.asarray(self._keys),
                      temperature=jnp.asarray(self._temp))
            if self._use_topk_vec:
                kw["top_ks"] = jnp.asarray(self._topk)
        if self.cfg.frontend == "frames":
            # device-side frame embeddings: fold the step counter into a
            # carried key — no host RNG (and no host sync) in the hot loop
            kw["embed_rng"] = jax.random.fold_in(self._embed_key,
                                                 self._steps)
            tok = None
        return (self.params, self.kv.cache, tok, pos), kw

    def _decode(self, tok: jnp.ndarray, pos: jnp.ndarray):
        args, kw = self._decode_args(tok, pos)
        return self._jit_step(*args, **kw)

    def _prefill_args(self, tokens: np.ndarray, pos: np.ndarray,
                      lens: np.ndarray):
        """The ``(args, kwargs)`` of one call of the jitted prefill step."""
        packed = self.packed.blocks if self.packed is not None else None
        kw = dict(packed=packed)
        if self.page_len:
            kw["page_tables"] = self.kv.tables()
        return (self.params, self.kv.cache, jnp.asarray(tokens),
                jnp.asarray(pos), jnp.asarray(lens)), kw

    def _prefill(self, tokens: np.ndarray, pos: np.ndarray,
                 lens: np.ndarray):
        """One jitted chunked-prefill call over the fixed (B, C) batch."""
        args, kw = self._prefill_args(tokens, pos, lens)
        return self._jit_prefill(*args, **kw)

    def lower(self, phase: str) -> jax.stages.Lowered:
        """Lower the engine's jitted ``"decode"`` or ``"prefill"`` step
        with the arguments the next call would take (the current slot
        tokens and positions; an all-masked chunk for prefill).
        Lowering runs nothing, so the live cache is untouched."""
        if phase == "decode":
            args, kw = self._decode_args(jnp.asarray(self._tok[:, None]),
                                         jnp.asarray(self._pos))
            return self._jit_step.lower(*args, **kw)
        if phase == "prefill" and self._jit_prefill is not None:
            z = np.zeros(self.num_slots, np.int32)
            args, kw = self._prefill_args(
                np.zeros((self.num_slots, self.prefill_chunk), np.int32),
                z, z)
            return self._jit_prefill.lower(*args, **kw)
        raise ValueError(f"no {phase!r} step to lower")

    def _prefill_call(self) -> None:
        """Run the planner's next batched chunk call and route results.

        Under paging, every participating slot's chunk pages are
        bulk-mapped in one admission (``ensure_range``) before the call.
        Slots that finish their last chunk here flip to decode phase at
        position ``len(prompt) - 1`` — the next decode step consumes the
        final prompt token and samples the first generated token, just
        like the teacher-forcing path's last prompt step did.

        Two phases: ``prefill`` holds the host work (planning, page
        mapping, dispatch, routing the slots, which reads nothing the
        call computes) and ``prefill_sync`` the wait for the call's
        result, so device idle inside ``serve.prefill`` is host time.
        """
        with self._phase("prefill"):
            tokens, pos, lens, finished = self.planner.next_call()
            if self.page_len:
                # oldest slots first: if mapping runs the pool dry in
                # preemptible mode, the youngest victims haven't mapped
                # yet — their reclaimed pages go to the older requesters
                # (a preempted slot's lane still scatters, into the trash
                # page)
                order = sorted(np.nonzero(lens)[0],
                               key=lambda s: int(self._admit_seq[int(s)]))
                for slot in order:
                    if int(slot) not in self.scheduler.active:
                        continue
                    self._with_pages(
                        lambda s=int(slot): self.kv.ensure_range(
                            s, int(pos[s]), int(pos[s]) + int(lens[s])),
                        int(slot))
            hidden, cache = self._prefill(tokens, pos, lens)
            self.kv.cache = cache
            if self.prefix_reuse:
                # publish each advanced slot's fully-written blocks *now*
                # — before any later chunk can ring-wrap over them
                for slot in np.nonzero(lens)[0]:
                    if int(slot) in self.scheduler.active:
                        self.kv.register_prefix(
                            int(slot), self._ingest[int(slot)],
                            int(pos[slot]) + int(lens[slot]))
            finished = [s for s in finished if s in self.scheduler.active]
            for slot in finished:
                ing = self._ingest[slot]
                self._pos[slot] = len(ing) - 1
                self._tok[slot] = ing[-1]
            for slot in np.nonzero(lens)[0]:
                if self.planner.in_prefill(int(slot)):
                    # park the passenger's decode write on the next
                    # unwritten prompt position: the next chunk rewrites
                    # that line before anything reads it
                    self._pos[slot] = self.planner.next_pos(int(slot))
            self._c_prefill_steps.inc()
        with self._phase("prefill_sync"):
            jax.block_until_ready(hidden)
            wall = self._wall()
            for slot in finished:
                req = self.scheduler.active[slot]
                if req.t_prefill_done is None:
                    req.t_prefill_done = wall
                    self._emit("prefill_done", rid=req.rid, slot=slot)

    def probe_logits(self, tokens: Sequence[int]) -> np.ndarray:
        """(num_slots, V) logits of one decode step feeding ``tokens``
        (one per slot) at position 0, through the engine's own jitted
        step — packed or dense as configured.  A correctness probe: like
        ``warmup``'s throwaway steps, it writes only position 0 of idle
        slots, which admission zeroes again."""
        tok = jnp.asarray(np.asarray(tokens, np.int32).reshape(-1, 1))
        if tok.shape[0] != self.num_slots:
            raise ValueError(f"probe_logits takes one token per slot "
                             f"({self.num_slots}), got {tok.shape[0]}")
        _, logits, self.kv.cache = self._decode(
            tok, jnp.zeros(self.num_slots, jnp.int32))
        return np.asarray(logits)

    def warmup(self) -> None:
        """Compile the decode step + slot reset before the latency clock
        starts — otherwise the first request's percentiles measure XLA
        compile time, not serving.  Slots are all idle here; whatever the
        throwaway steps write at position 0 is zeroed again on admission.

        Two throwaway decodes, not one: the first consumes the freshly
        allocated (uncommitted) cache, but its *output* cache carries the
        mesh's NamedSharding, which is a different jit signature — a
        single-step warmup left the steady-state executable to compile
        inside the first timed step (≈0.8 s mid-run for the packed
        stack).  The second call compiles the steady-state signature.
        """
        if self._warm:
            return
        for _ in range(2):
            nxt, _, cache = self._decode(jnp.asarray(self._tok[:, None]),
                                         jnp.asarray(self._pos))
            self.kv.cache = cache
        jax.block_until_ready(nxt)
        if self.prefill_chunk:
            # compile the prefill signature too: a throwaway call with
            # every lane masked (lens = 0) writes nothing — contiguous
            # lanes drop out of the scatter, paged lanes hit the trash
            # page — so the cache the first real step sees is untouched.
            # It runs after the decode warmup, so it consumes (and
            # yields) the steady-state committed-sharding cache.
            hidden, cache = self._prefill(
                np.zeros((self.num_slots, self.prefill_chunk), np.int32),
                np.zeros(self.num_slots, np.int32),
                np.zeros(self.num_slots, np.int32))
            self.kv.cache = cache
            jax.block_until_ready(hidden)
        self.kv.warmup()
        self._warm = True

    def step(self) -> None:
        """One engine step: admit, at most one batched prefill call, then
        the full-batch decode step (skipped only when every active slot
        is mid-prefill).

        The step is the profiler span ``serve.step`` (with its
        ``step_num``), and every host-side stretch of it sits inside
        exactly one phase span ``serve.<phase>`` (``telemetry.PHASES``):
        schedule → [prefill → prefill_sync] → [page_ensure → decode →
        host_sync → sample] → [deadline_sweep] → [audit].  Spans bracket
        host code only — ``decode`` ends at dispatch, and device time
        surfaces in ``host_sync`` / ``prefill_sync`` (the existing
        block-until-ready points) — so they add no transfers or syncs."""
        self._start()
        t_begin = time.perf_counter()
        with jax.profiler.StepTraceAnnotation(STEP_SPAN,
                                              step_num=self._steps):
            self._step()
        dt = time.perf_counter() - t_begin
        if self.spans is not None:
            self.spans.step_done(dt)
        self._step_wall_ema = (dt if self._step_wall_ema is None
                               else 0.8 * self._step_wall_ema + 0.2 * dt)
        self._steps += 1

    def _start(self) -> None:
        """Warm up, then start the serving clock and the telemetry's
        profiler session — both *after* warmup, through idempotent
        helpers, so no call path can leak compile time into the first
        timed step."""
        self.warmup()
        self._clock.start()
        if self.telemetry is not None:
            self.telemetry.start()

    def _step(self) -> None:
        with self._phase("schedule"):
            self._schedule()

        # at most one prefill call per engine step: a stream of long
        # prompts interleaves chunk calls with decode steps instead of
        # starving the decoding slots
        prefilled = False
        if self.planner is not None and self.planner.has_work:
            self._prefill_call()
            prefilled = True

        in_prefill = (self.planner.in_prefill if self.planner is not None
                      else lambda s: False)
        decoding = [s for s in self.scheduler.active if not in_prefill(s)]
        if decoding or not prefilled:
            if self.page_len:
                # map each decoding slot's current write page; mid-prefill
                # passengers stay unmapped and scribble into the trash
                # page (or an unwritten line their next chunk rewrites).
                # Oldest first: in preemptible mode a dry pool preempts
                # the youngest slots, which haven't mapped yet
                with self._phase("page_ensure"):
                    for slot in sorted(
                            decoding, key=lambda s: int(self._admit_seq[s])):
                        if slot not in self.scheduler.active:
                            continue
                        self._with_pages(
                            lambda s=slot: self.kv.ensure(
                                s, int(self._pos[s])), slot)
                    decoding = [s for s in self.scheduler.active
                                if not in_prefill(s)]
            with self._phase("decode"):
                nxt, logits, cache = self._decode(
                    jnp.asarray(self._tok[:, None]), jnp.asarray(self._pos))
                self.kv.cache = cache
            with self._phase("host_sync"):
                nxt_host = np.asarray(nxt)
            wall = self._wall()
            with self._phase("sample"):
                self._commit(nxt_host, logits, decoding, in_prefill, wall)
            self._c_decode_steps.inc()
        elif self.audit:
            # prefill-only step: no logits to check, but a fault may
            # have corrupted tensors the prefill call just consumed
            with self._phase("audit"):
                self._recover_corruption(None, [])
        if self._has_deadlines:
            with self._phase("deadline_sweep"):
                wall = self._wall()
                for slot in list(self.scheduler.active):
                    req = self.scheduler.active[slot]
                    if self._deadline_passed(req, wall):
                        self._release_slot(slot, RequestState.EXPIRED)
                        self._abort(req, RequestState.EXPIRED,
                                    error=DeadlineExceeded(
                                        f"rid {req.rid}: exceeded its "
                                        f"{req.deadline_ms:.0f}ms deadline "
                                        f"mid-flight"))
        if self.auditor is not None:
            with self._phase("audit"):
                try:
                    self.auditor.check_step()
                except Exception as e:
                    self._emit("audit_violation", reason=str(e))
                    raise

    def _schedule(self) -> None:
        """Fire scheduled faults, shed or expire due requests, and admit
        waiting requests into free slots."""
        now = float(self._steps)
        if self.faults is not None:
            n_log = len(self.faults.log)
            self.faults.fire(self, self._steps)
            if self.events is not None:
                for entry in list(self.faults.log)[n_log:]:
                    self._emit("fault", kind_detail=entry.get("kind"),
                               fired=bool(entry.get("fired")),
                               tensor=entry.get("tensor"))
        shedding = (self.max_queue is not None
                    or self.ttft_budget_ms is not None)
        for r in list(self.scheduler.waiting):
            if r.arrival <= now and r.t_due is None:
                r.t_due = self._wall()
                if shedding:
                    reason = self._overload_reason(exclude_self=True)
                    if reason is not None:
                        # came due while overloaded: shed silently with
                        # the typed error recorded (submit already
                        # raised for requests due at submission time)
                        self.scheduler.cancel_waiting(r)
                        r.transition(RequestState.SHED)
                        self._abort(r, RequestState.SHED,
                                    error=ServeOverloaded(
                                        reason,
                                        queue_depth=self._due_depth()))
                        continue
            if self._has_deadlines and self._deadline_passed(
                    r, self._wall()):
                self.scheduler.cancel_waiting(r)
                r.transition(RequestState.EXPIRED)
                self._abort(r, RequestState.EXPIRED,
                            error=DeadlineExceeded(
                                f"rid {r.rid}: queued past its "
                                f"{r.deadline_ms:.0f}ms deadline"))
        fits = None
        if self.page_len:
            # out-of-pages: the head-of-line request queues (strict FIFO)
            # until retirements free enough pages — never a crash.  The
            # gate *reserves* (check-and-commit), so multiple admissions
            # in one pass can't over-commit the pool.
            # the reservation lands in the candidate slot's shard — admit
            # evaluates fits *before* popping the slot, so free[0] is the
            # slot this request will get (sharded pools commit per shard;
            # unsharded pools ignore the slot)
            fits = lambda r: self.kv.reserve(
                self._commit_tokens(r),
                slot=(self.scheduler.free[0] if self.scheduler.free else 0))
        for slot, req in self.scheduler.admit(now, fits=fits):
            # ingest = prompt plus tokens generated before a preemption:
            # a recomputed request teacher-forces/prefills its own
            # history instead of resampling it
            ing = list(req.prompt) + list(req.tokens)
            self._admit_seq[slot] = self._admit_counter
            self._admit_counter += 1
            shared = 0
            if self.page_len:
                blocks = None
                if self.prefix_reuse:
                    _, blocks = self.kv.match_prefix(ing, slot=slot)
                shared = self.kv.admit(slot, self._commit_tokens(req),
                                       prefix=blocks)
            else:
                self.kv.reset_slot(slot)
            self._ingest[slot] = ing
            if not req.t_preempt:
                req.prefix_hit_tokens = shared
            else:
                # recompute cost actually paid on this re-admission
                # (adopted blocks — often this request's own earlier
                # registrations — shrink it)
                req.recomputed_tokens += max(0, len(ing) - 1 - shared)
                self._c_recomputed.inc(max(0, len(ing) - 1 - shared))
            self._pos[slot] = shared
            self._tok[slot] = ing[shared]
            self._temp[slot] = req.temperature
            self._topk[slot] = (req.top_k if req.top_k is not None
                                else self.top_k_default)
            rseed = req.seed if req.seed is not None \
                else self._seed + 0x9e37 * (req.rid + 1)
            self._keys[slot] = np.asarray(jax.random.PRNGKey(rseed))
            req.admit_step = self._steps
            if req.t_due is None:
                req.t_due = self._wall()
            if req.t_admit is None:   # re-admissions keep the first mark
                req.t_admit = self._wall()
            if self.planner is not None:
                self.planner.start(slot, ing, start=shared)
            if shared >= len(ing) - 1 and req.t_prefill_done is None:
                # nothing left to ingest — single-token prompt, or a full
                # prefix hit: TTFT collapses to queue + first-decode
                req.t_prefill_done = req.t_admit
            self._emit("admit", rid=req.rid, slot=slot,
                       prefix_hit_tokens=shared)

    def _commit(self, nxt_host: np.ndarray, logits, decoding: List[int],
                in_prefill, wall: float) -> None:
        """Commit one decode step's tokens: advance every decoding slot,
        teacher-force prompt/recompute history, append sampled tokens and
        retire finished requests."""
        if self.audit and self._recover_corruption(logits, decoding):
            # a corrupted tensor was quarantined and every active slot
            # preempted: nothing from this step is committed — the
            # requests replay deterministically through the now-clean
            # (dense-fallback) path, emitting the exact tokens the
            # uncorrupted step would have
            return
        self._c_slot_steps.inc(len(decoding))
        for slot, req in list(self.scheduler.active.items()):
            if in_prefill(slot):
                continue
            ing = self._ingest[slot]
            p = int(self._pos[slot])
            self._pos[slot] = p + 1
            if self.prefix_reuse and (p + 1) % self.page_len == 0:
                # a block boundary just filled: publish it (prompt *and*
                # generated blocks — identical greedy requests reuse
                # each other's generations too)
                self.kv.register_prefix(slot, ing, p + 1)
            if p + 1 < len(ing):
                # still consuming prompt/recompute history: teacher-force
                # the next token (legacy walk, or a preempted request
                # replaying its generated prefix)
                self._tok[slot] = ing[p + 1]
                if p + 1 == len(ing) - 1 and req.t_prefill_done is None:
                    req.t_prefill_done = wall  # cache resident
                    self._emit("prefill_done", rid=req.rid, slot=slot)
                continue
            t = int(nxt_host[slot])
            req.tokens.append(t)
            ing.append(t)
            if req.t_first is None:
                req.t_first = wall
                if self.events is not None:
                    self._emit("first_token", rid=req.rid, slot=slot)
            self._tok[slot] = t
            if (len(req.tokens) >= req.max_new_tokens
                    or p + 1 >= self.max_len):
                req.t_done = wall
                req.done_step = self._steps
                self._release_slot(slot, RequestState.DONE)
                self._retire(req)

    def run(self) -> dict:
        """Drive until every submitted request has drained; report stats."""
        self._start()
        while self.scheduler.has_work:
            if not self.scheduler.active:
                # idle: fast-forward the step clock to the next arrival
                nxt = self.scheduler.next_arrival()
                if nxt > self._steps:
                    self._steps = int(math.ceil(nxt))
            self.step()
        return self.report()

    # ---------------------------------------------------------- reports ----

    def weight_stream_report(self) -> dict:
        """Modeled per-step weight-HBM bytes, sparse vs dense, aggregated
        across the whole decode stack (blocks + LM head).

        Embeddings are excluded: the token lookup gathers B rows, it does
        not stream the table.  The head term is the packed head's bitmap
        bytes, or its dense bytes when the head fell back.

        MoE expert stacks count once per *activated* expert per step —
        with ``num_slots`` slots each routing to ``top_k`` experts, a
        decode step touches at most ``min(E, num_slots × top_k)`` experts
        — not once per stored expert (accounting rule in
        DESIGN_PACKED.md §6).
        """
        head_dense = (self.cfg.d_model * self.cfg.vocab_size
                      * np.dtype(np.float32).itemsize)
        head_sparse = (self.lm_weight.hbm_bytes
                       if self.lm_weight is not None else head_dense)
        head_sh = (self.lm_weight.shard[1]
                   if self.lm_weight is not None
                   and self.lm_weight.shard is not None else 1)
        activated = (self.num_slots * self.cfg.top_k
                     if self.cfg.num_experts else None)
        if self.packed is not None:
            rep = self.packed.stream_report(activated_experts=activated)
        else:
            # dense-dispatch baseline: same accounting rule, same code —
            # router-gated expert stacks stream once per activated expert
            from repro.serve.packed import ROUTED_EXPERT, activated_scale
            dense = 0
            for bdict in self.params["blocks"].values():
                for comp, tensors in bdict.items():
                    for name, leaf in tensors.items():
                        b = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                        routed = (leaf.shape[1]
                                  if (comp, name) in ROUTED_EXPERT
                                  and leaf.ndim == 4 else 0)
                        dense += int(round(
                            b * activated_scale(routed, activated)))
            rep = {"sparse_bytes_per_step": dense,
                   "dense_bytes_per_step": dense, "reduction": 1.0,
                   "packed_tensors": 0, "fallback_tensors": 0,
                   "activated_experts": activated,
                   "fallbacks": {"*": self.stream_fallback
                                 or "stream_weights=False"},
                   "shards": 1,
                   "device_sparse_bytes_per_step": dense,
                   "device_dense_bytes_per_step": dense,
                   "shard_fallbacks": {}}
        sparse = rep["sparse_bytes_per_step"] + head_sparse
        dense = rep["dense_bytes_per_step"] + head_dense
        # per-device terms: a sharded head streams 1/S of its packed
        # bytes per model-axis device; the dense head (and a replicated
        # packed head) is resident — and streamed — whole on every device
        dev_sparse = (rep["device_sparse_bytes_per_step"]
                      + head_sparse // head_sh)
        dev_dense = rep["device_dense_bytes_per_step"] + head_dense
        shard_fb = dict(rep.get("shard_fallbacks", {}))
        if self.head_shard_fallback:
            shard_fb["lm_head"] = self.head_shard_fallback
        out = {**rep,
               "spmm_grid_steps_per_step": self._spmm_grid_steps(),
               "spmm_prefill_expansions_per_tile":
                   self._spmm_prefill_expansions_per_tile(),
               "sparse_bytes_per_step": sparse,
               "dense_bytes_per_step": dense,
               "reduction": dense / sparse if sparse else 1.0,
               "device_sparse_bytes_per_step": dev_sparse,
               "device_dense_bytes_per_step": dev_dense,
               "shard_fallbacks": shard_fb}
        rows = self._expert_row_use_pct()
        if rows is not None:
            out["expert_row_use_pct"] = rows
        return out

    def _expert_row_use_pct(self) -> Optional[float]:
        """Share of the rows the grouped expert products compute in one
        decode step that carry a routed (token, expert) pair, in percent:
        ``layers.moe_rows`` of the decode's dispatch, one token per slot
        (the share is the same for every slot); None without experts."""
        from repro.models.layers import moe_rows
        cfg = self.cfg
        if not (cfg.num_experts and any(b.ffn == "moe"
                                         for b in cfg.pattern)):
            return None
        routed, computed = moe_rows(1, cfg)
        return 100.0 * routed / computed

    def _spmm_calls(self, rows: int, head: bool) -> list:
        """(packed weight, rows per matrix) of each ``bitmap_spmm`` a
        step over ``rows`` tokens streams on the Pallas path: each packed
        matrix at ``rows``, a routed expert stack at each expert's buffer
        (``expert_capacity`` of one token per batch row, as ``moe_rows``
        sizes it for the decode and the folded prefill dispatch), and the
        packed head where ``head``."""
        from repro.models.layers import expert_capacity
        leaves = self.packed.leaves() if self.packed is not None else []
        calls = []
        for path, bw in leaves:
            routed = tuple(path.split("/")[-2:]) in ROUTED_EXPERT
            calls.append((bw, rows * expert_capacity(1, self.cfg)
                          if routed else rows))
        if head and self.lm_weight is not None:
            calls.append((self.lm_weight, rows))
        return calls

    def _spmm_grid_steps(self) -> int:
        """``bitmap_spmm`` grid steps of one decode step on the Pallas
        path: each packed matrix once, at the decode rows (``num_slots``),
        and the packed head."""
        from repro.kernels.bitmap_spmm import grid_steps
        dt = jnp.dtype(self.cfg.compute_dtype)
        return sum(grid_steps(bw, m, dt)
                   for bw, m in self._spmm_calls(self.num_slots, head=True))

    def _spmm_prefill_expansions_per_tile(self) -> Optional[float]:
        """Tile expansions of one chunked-prefill call on the Pallas path
        ÷ the packed tiles it streams (each packed matrix at ``num_slots
        × prefill_chunk`` rows; no head): the kernel expands every tile
        once per row block (``bitmap_spmm.row_blocks``).  None without
        packed weights or chunked prefill."""
        from repro.kernels.bitmap_spmm import row_blocks
        calls = self._spmm_calls(self.num_slots * self.prefill_chunk,
                                 head=False)
        if not (calls and self.prefill_chunk):
            return None
        dt = jnp.dtype(self.cfg.compute_dtype)
        tiles = [math.prod(bw.values.shape[:-2]) for bw, _ in calls]
        expanded = sum(t * row_blocks(bw, m, dt)[1]
                       for t, (bw, m) in zip(tiles, calls))
        return expanded / sum(tiles)

    def prefill_report(self) -> dict:
        """The prefill section: chunk-call accounting + the step split."""
        rep = {"enabled": self.prefill_chunk > 0,
               "fallback": self.prefill_fallback,
               "prefill_steps": self._c_prefill_steps.value,
               "decode_steps": self._c_decode_steps.value}
        if self.planner is not None:
            rep.update(self.planner.report())
        else:
            rep.update({"chunk": 0, "calls": 0, "tokens_prefilled": 0,
                        "in_flight": 0, "lane_utilization": None})
        return rep

    def prefix_reuse_report(self) -> dict:
        """Shared-prefix + preemption stats: cache hit/evict/fork
        counters (from the paged cache), the hit-vs-miss TTFT split, and
        the preemption/recompute accounting."""
        rep = {
            "enabled": self.prefix_reuse,
            "fallback": self.prefix_fallback,
            "ttft_hit_s": self._h_ftl_hit.percentiles(),
            "ttft_miss_s": self._h_ftl_miss.percentiles(),
            "hit_requests": self._h_ftl_hit.count,
            "miss_requests": self._h_ftl_miss.count,
            "preempt": {
                "enabled": self.preempt,
                "fallback": self.preempt_fallback,
                "count": self.scheduler.preemptions,
                "recomputed_tokens": self._c_recomputed.value,
            },
        }
        if self.page_len:
            rep.update(self.kv.prefix_report())
        return rep

    def lifecycle_report(self) -> dict:
        """Terminal-state taxonomy + overload/fault accounting.

        Every request the engine has ever retired lands in exactly one
        terminal state (DONE / CANCELLED / EXPIRED / SHED); the counts
        here partition ``requests + retained`` minus what is still
        queued or active.  ``shed`` additionally counts submit-time
        rejections (no Request object is retained for those)."""
        by_state: Dict[str, int] = {}
        for req in self.requests:
            by_state[req.state.name] = by_state.get(req.state.name, 0) + 1
        rep = {
            "deadline_ms": self.deadline_ms,
            "max_queue": self.max_queue,
            "ttft_budget_ms": self.ttft_budget_ms,
            "max_preempts": self.max_preempts,
            "cancelled": self._c_cancelled.value,
            "expired": self._c_expired.value,
            "shed": self._c_shed.value,
            "forced_preempts": self._forced_preempts,
            "wasted_tokens": self._c_wasted.value,
            "estimated_ttft_s": self.estimated_ttft_s(),
            "terminal_states": by_state,
            "quarantined": dict(self.quarantined),
        }
        if self.faults is not None:
            rep["faults"] = self.faults.summary()
        if self.auditor is not None:
            rep["audit"] = self.auditor.report()
        return rep

    def paging_report(self) -> dict:
        """The paging section: pool accounting under paged KV, or the
        contiguous-reservation equivalent when paging fell back."""
        if self.page_len:
            positions = [int(self._pos[s]) for s in self.scheduler.active]
            return {"paged": True, "fallback": None,
                    **self.kv.report(positions)}
        reserved = self.kv.reserved_kv_bytes()
        return {"paged": False, "fallback": self.paging_fallback,
                "reserved_kv_bytes": reserved,
                "contiguous_kv_bytes": reserved,
                "reserved_reduction": 1.0}

    def report(self) -> dict:
        """A rendered snapshot of the metrics registry — every section
        is a registered view, every scalar a registered metric, so the
        same registry also exports Prometheus text and the JSON
        snapshot (``--metrics-out``) without a second bookkeeping
        path.  Key order and field types match the pre-registry
        report() exactly (pinned by the schema snapshot test)."""
        return self.metrics.render()
