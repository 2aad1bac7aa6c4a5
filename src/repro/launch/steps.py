"""Step-function builders: train, prefill, serve (decode).

These close over the static ModelConfig so the jitted callables take only
array pytrees — the exact functions the dry-run lowers and the drivers run.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.sharding import bitmap_sharded, bitmap_specs, packed_specs
from repro.models.config import ModelConfig
from repro.models.model import (LM_HEAD_SCOPE, decode_hidden, forward,
                                head_logits, lm_head_weight, lm_loss,
                                loss_fn, prefill_hidden)
from repro.sparse.format import BitmapWeight, gather_bitmap
from repro.train import optimizer as opt_lib


def build_train_step(cfg: ModelConfig, opt_cfg: opt_lib.OptConfig,
                     prune_masks: Optional[Dict] = None,
                     accum_steps: int = 1) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``prune_masks`` (same tree as params, 0/1) keeps pruned weights at zero
    through training — the paper's sparse-model regime as a first-class
    training feature (masked-gradient sparse training).

    ``accum_steps`` > 1 splits the batch into microbatches scanned
    sequentially with gradient accumulation — the activation working set
    (the dominant train-cell memory term, §Perf) shrinks ~linearly while
    the DP gradient all-reduce still happens once per step.  Token-mean
    loss with equal microbatch sizes makes this *numerically identical* to
    the single-pass step (tests/test_accum.py).
    """

    def grads_of(params, batch):
        return jax.value_and_grad(loss_fn, has_aux=True)(params, batch, cfg)

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            (loss, metrics), grads = grads_of(params, batch)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps)
                                    + tuple(x.shape[1:])), batch)

            def mb(carry, mbatch):
                gsum, lsum, csum = carry
                (_, m), g = grads_of(params, mbatch)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (gsum, lsum + m["loss"] * m["tokens"],
                        csum + m["tokens"]), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum, csum), _ = jax.lax.scan(
                mb, (zeros, jnp.float32(0), jnp.float32(0)), micro)
            # microbatches carry equal token counts -> mean of means is
            # exact; grads averaged the same way
            grads = jax.tree.map(lambda g: g / accum_steps, gsum)
            loss = lsum / jnp.maximum(csum, 1)
            metrics = {"loss": loss, "tokens": csum}
        if prune_masks is not None:
            grads = jax.tree.map(lambda g, m: g * m, grads, prune_masks)
        new_params, new_opt, opt_metrics = opt_lib.update(
            params, grads, opt_state, opt_cfg)
        if prune_masks is not None:
            new_params = jax.tree.map(lambda p, m: p * m, new_params,
                                      prune_masks)
        return new_params, new_opt, {**metrics, **opt_metrics}

    return train_step


def build_eval_step(cfg: ModelConfig) -> Callable:
    def eval_step(params, batch):
        loss, metrics = loss_fn(params, batch, cfg)
        return metrics
    return eval_step


def build_prefill_logits_step(cfg: ModelConfig) -> Callable:
    """Forward over the full prompt; returns last-position logits.

    The *dry-run* prefill cell: it measures the forward compute and
    intentionally omits KV export (see DESIGN.md).  The serving engine's
    cache-writing chunked prefill is ``build_prefill_step`` below.
    """

    def prefill_logits_step(params, batch):
        hidden = forward(params, cfg, tokens=batch.get("tokens"),
                         embeds=batch.get("embeds"))
        w = lm_head_weight(params, cfg).astype(hidden.dtype)
        logits = (hidden[:, -1] @ w).astype(jnp.float32)
        return logits

    return prefill_logits_step


def build_prefill_step(cfg: ModelConfig, impl: Optional[str] = None
                       ) -> Callable:
    """One chunked-prefill call for the serving engine:
    (params, cache, tokens, pos, lens) -> (hidden, new_cache).

    ``tokens`` is a (B, C) chunk batch — one C-token slice of prompt per
    batch slot, assembled by ``repro.serve.prefill.PrefillPlanner`` from
    however many admitted requests are mid-prefill.  ``pos`` ((B,) int32)
    is each slot's chunk start position and ``lens`` ((B,) int32) its
    valid token count this call (0 = padding lane: the slot writes
    nothing).  The call writes C KV lines per participating slot —
    causal within the chunk, attending to the slot's existing cache — so
    a prompt is ingested in ``ceil((len(prompt) - 1) / C)`` calls
    instead of ``len(prompt) - 1`` full-batch decode steps, with every
    projection dispatched at M = C through the packed
    ``matmul_or_bitmap`` path (``packed`` / ``lm_weight`` mirror
    ``build_serve_step``; there is no LM head here — the first sampled
    token comes from the first real decode step after prefill).

    ``page_tables`` routes the KV writes through the paged layout; the
    engine bulk-maps the chunk's pages (``PagedKVCache.ensure_range``)
    before the call.
    """

    def prefill_step(params, cache, tokens, pos, lens, embeds=None,
                     packed=None, page_tables=None):
        return prefill_hidden(params, cache, cfg, tokens, pos, lens,
                              embeds=embeds, packed=packed, impl=impl,
                              page_tables=page_tables)

    return prefill_step


def build_serve_step(cfg: ModelConfig, impl: Optional[str] = None,
                     top_k: int = 0) -> Callable:
    """One decode step + head: (params, cache, tokens/embeds, pos)
    -> (next_token, logits, new_cache).

    ``pos`` may be a scalar (classic lock-step decode) or a (B,) vector of
    per-slot positions (continuous batching).  ``lm_weight`` (a
    ``BitmapWeight``) routes the LM head through the bitmap-compressed
    ``kernels/ops.bitmap_spmm`` path and ``packed`` (the block tree from
    ``repro.serve.packed.pack_model``) does the same for every attention
    and MLP projection; ``impl`` pins the kernel dispatch
    ("xla" | "pallas" | "pallas_interpret", default backend-chosen).

    ``page_tables`` (``{bname: (B, page_slots) int32}``, see
    ``repro.serve.paging``) routes the KV cache through the paged layout;
    omitted, the contiguous per-slot cache is unchanged.

    ``embed_rng`` (frames frontend): a PRNG key the step derives the
    per-step frame embeddings from on device — no host round-trip in the
    decode loop.

    Sampling: with ``sample_keys`` ((B, 2) uint32, one key per slot) and
    ``temperature`` ((B,) f32) the head samples from
    ``softmax(logits / T)``; slots with T == 0 stay exactly greedy, so
    the default is unchanged.  ``top_ks`` ((B,) int32) truncates each
    slot's sample to its own top-k via a masked threshold (0 = no
    truncation) — per-request top_k with one jit signature; the builder's
    static ``top_k`` is only a fallback default when no vector is passed.
    Keys are folded with the slot position, so a request's sample at
    position p depends only on (its seed, p) — deterministic under
    continuous batching regardless of scheduling.
    """

    def serve_step(params, cache, tokens, pos, embeds=None, lm_weight=None,
                   packed=None, embed_rng=None, sample_keys=None,
                   temperature=None, top_ks=None, page_tables=None):
        if embed_rng is not None and embeds is None:
            b = pos.shape[0] if jnp.ndim(pos) else 1
            embeds = jax.random.normal(embed_rng, (b, 1, cfg.d_model),
                                       jnp.float32)
        hidden, new_cache = decode_hidden(params, cache, cfg, tokens, pos,
                                          embeds=embeds, packed=packed,
                                          impl=impl, page_tables=page_tables)
        with jax.named_scope(LM_HEAD_SCOPE):
            next_tok, logits = _head_and_sample(
                params, hidden[:, 0], pos, lm_weight, sample_keys,
                temperature, top_ks)
        return next_tok, logits, new_cache

    def _head_and_sample(params, hidden, pos, lm_weight, sample_keys,
                         temperature, top_ks):
        logits = head_logits(params, cfg, hidden, lm_weight, impl)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if sample_keys is not None and temperature is not None:
            posv = jnp.broadcast_to(pos, next_tok.shape)
            keys = jax.vmap(jax.random.fold_in)(sample_keys, posv)
            scaled = logits.astype(jnp.float32) / jnp.maximum(
                temperature, 1e-6)[:, None]
            if top_ks is not None:
                # per-slot masked top-k: each row keeps values >= its own
                # k-th largest (same tie behaviour as lax.top_k's static
                # truncation); k <= 0 rows keep the full distribution
                v = scaled.shape[-1]
                desc = -jnp.sort(-scaled, axis=-1)
                idx = jnp.clip(top_ks - 1, 0, v - 1)[:, None]
                kth = jnp.take_along_axis(desc, idx, axis=1)
                scaled = jnp.where((top_ks[:, None] > 0) & (scaled < kth),
                                   -jnp.inf, scaled)
            elif top_k > 0:
                # every slot at the engine default: the static lax.top_k
                # threshold (O(V·k)) beats the per-slot full-vocab sort
                kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
                scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
            sampled = jax.vmap(jax.random.categorical)(keys, scaled)
            next_tok = jnp.where(temperature > 0,
                                 sampled.astype(jnp.int32), next_tok)
        return next_tok, logits

    return serve_step


# ---------------------------------------------------------------- SPMD ----
# Sharded serving: the decode/prefill steps above, run under shard_map on
# the engine's elastic (data, model) mesh.  Packed ``BitmapWeight`` leaves
# arrive model-sharded along their explicit shard axis (format.shard_bitmap
# layout — the per-device HBM cut), paged KV pools arrive data-sharded
# along the pages axis (paging.PagedKVCache(shards=...) keeps every slot's
# pages shard-local).  The body is gather-then-compute: sharded operands
# are all-gathered device-side, the *unchanged* base step runs, and each
# device keeps its own slice of the new cache — so the numerics (and the
# sampled tokens) are bit-identical to the single-device step by
# construction, while the weights and pool pages each device *stores*
# are 1/shard of the stack.


def _replicated(tree) -> object:
    """A matching tree of fully-replicated PartitionSpecs (None stays
    None, so optional step kwargs spec out naturally)."""
    return jax.tree.map(lambda _: P(), tree)


def _cache_specs(cache, data_pools: frozenset, ndata: int) -> Dict:
    """Specs for the serve cache dict: paged k/v pools shard their pages
    axis (axis 1, after the period stack) over "data"; everything else —
    contiguous caches, recurrent state, non-pool blocks — replicates."""

    def spec(bname, key, leaf):
        if ndata > 1 and bname in data_pools and key in ("k", "v"):
            axes: list = [None] * leaf.ndim
            axes[1] = "data"
            return P(*axes)
        return P()

    return {b: {k: spec(b, k, v) for k, v in leafd.items()}
            for b, leafd in cache.items()}


def _gather_cache(cache, data_pools: frozenset, ndata: int) -> Dict:
    """Inside the shard_map body: reassemble the full page pools from
    the per-device chunks (page ids in the tables are global)."""
    if ndata <= 1 or not data_pools:
        return cache
    return {b: ({k: (jax.lax.all_gather(v, "data", axis=1, tiled=True)
                     if k in ("k", "v") else v)
                 for k, v in leafd.items()}
                if b in data_pools else leafd)
            for b, leafd in cache.items()}


def _slice_cache(cache, data_pools: frozenset, ndata: int) -> Dict:
    """Inverse of ``_gather_cache``: each device keeps its own shard's
    contiguous page chunk of the written pool.  The paged allocator maps
    every slot's pages (and its trash writes) inside its own shard's id
    range, so the kept chunk holds exactly this device's slots' lines."""
    if ndata <= 1 or not data_pools:
        return cache
    idx = jax.lax.axis_index("data")

    def keep(leaf):
        local = leaf.shape[1] // ndata
        return jax.lax.dynamic_slice_in_dim(leaf, idx * local, local,
                                            axis=1)

    return {b: ({k: (keep(v) if k in ("k", "v") else v)
                 for k, v in leafd.items()}
                if b in data_pools else leafd)
            for b, leafd in cache.items()}


def _gather_packed(tree, mesh):
    """All-gather every model-sharded ``BitmapWeight`` in a packed block
    tree back to its unsharded layout (replicated leaves pass through)."""
    if tree is None:
        return None
    return jax.tree.map(
        lambda bw: (gather_bitmap(bw, "model")
                    if bitmap_sharded(bw, mesh) else bw),
        tree, is_leaf=lambda x: isinstance(x, BitmapWeight))


def build_serve_step_spmd(cfg: ModelConfig, mesh,
                          impl: Optional[str] = None, top_k: int = 0,
                          data_pools: Sequence[str] = ()) -> Callable:
    """``build_serve_step`` under shard_map on ``mesh`` — same signature,
    same numerics, sharded storage.

    ``data_pools``: names of the paged-cache pools whose pages axis is
    sharded over the mesh "data" axis (the engine passes its
    ``PagedKVCache`` pool names when ``kv.shards`` matches the data
    extent; empty = cache fully replicated).  PartitionSpecs are derived
    from the actual arguments at trace time: ``BitmapWeight`` leaves via
    ``sharding.bitmap_specs`` (their explicit shard axis over "model"),
    pool k/v leaves over "data", everything else replicated.
    """
    base = build_serve_step(cfg, impl=impl, top_k=top_k)
    pools = frozenset(data_pools)
    ndata = int(mesh.shape.get("data", 1))

    def serve_step(params, cache, tokens, pos, embeds=None, lm_weight=None,
                   packed=None, embed_rng=None, sample_keys=None,
                   temperature=None, top_ks=None, page_tables=None):
        args = (params, cache, tokens, pos, embeds, lm_weight, packed,
                embed_rng, sample_keys, temperature, top_ks, page_tables)
        cspecs = _cache_specs(cache, pools, ndata)
        in_specs = (_replicated(params), cspecs, _replicated(tokens),
                    _replicated(pos), _replicated(embeds),
                    bitmap_specs(lm_weight, mesh),
                    packed_specs(packed, mesh), _replicated(embed_rng),
                    _replicated(sample_keys), _replicated(temperature),
                    _replicated(top_ks), _replicated(page_tables))

        def body(params, cache, tokens, pos, embeds, lm_weight, packed,
                 embed_rng, sample_keys, temperature, top_ks, page_tables):
            lm = (gather_bitmap(lm_weight, "model")
                  if bitmap_sharded(lm_weight, mesh) else lm_weight)
            nxt, logits, new_cache = base(
                params, _gather_cache(cache, pools, ndata), tokens, pos,
                embeds=embeds, lm_weight=lm,
                packed=_gather_packed(packed, mesh), embed_rng=embed_rng,
                sample_keys=sample_keys, temperature=temperature,
                top_ks=top_ks, page_tables=page_tables)
            return nxt, logits, _slice_cache(new_cache, pools, ndata)

        return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=(P(), P(), cspecs),
                             check_vma=False)(*args)

    return serve_step


def build_prefill_step_spmd(cfg: ModelConfig, mesh,
                            impl: Optional[str] = None,
                            data_pools: Sequence[str] = ()) -> Callable:
    """``build_prefill_step`` under shard_map on ``mesh`` — the chunked
    prefill analogue of ``build_serve_step_spmd`` (same gather-then-
    compute body, same spec derivation, no head)."""
    base = build_prefill_step(cfg, impl=impl)
    pools = frozenset(data_pools)
    ndata = int(mesh.shape.get("data", 1))

    def prefill_step(params, cache, tokens, pos, lens, embeds=None,
                     packed=None, page_tables=None):
        args = (params, cache, tokens, pos, lens, embeds, packed,
                page_tables)
        cspecs = _cache_specs(cache, pools, ndata)
        in_specs = (_replicated(params), cspecs, _replicated(tokens),
                    _replicated(pos), _replicated(lens),
                    _replicated(embeds), packed_specs(packed, mesh),
                    _replicated(page_tables))

        def body(params, cache, tokens, pos, lens, embeds, packed,
                 page_tables):
            hidden, new_cache = base(
                params, _gather_cache(cache, pools, ndata), tokens, pos,
                lens, embeds=embeds, packed=_gather_packed(packed, mesh),
                page_tables=page_tables)
            return hidden, _slice_cache(new_cache, pools, ndata)

        return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=(P(), cspecs),
                             check_vma=False)(*args)

    return prefill_step
