"""Unified decoder LM: shapes, init, forward, decode, loss.

One model covers all ten assigned architectures through ``ModelConfig``:
the layer stack is ``lax.scan`` over ``num_periods`` repetitions of the
(possibly heterogeneous) block pattern, with ``jax.checkpoint`` on the
period body — O(1) HLO in depth and one residual per layer of activation
memory.  Parameters are stored stacked over periods: leading dim P on every
block leaf.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import shard_utils
from repro.models import ssm
from repro.models.config import BlockCfg, ModelConfig

RWKV_MIX_RANK = 32
RWKV_DECAY_RANK = 64

#: ``jax.named_scope`` names of the serving programs' layers, carried in
#: each HLO op's ``op_name`` metadata (and so in a profiler trace): the
#: attention core (RoPE, K/V write, gather, scores, softmax, PV — not the
#: q/k/v/o projections), the MLP/MoE block, and the head with sampling;
#: inside ``ffn`` an MoE block's four stages (``layers.moe_ffn``).
ATTENTION_SCOPE, FFN_SCOPE, LM_HEAD_SCOPE = "attention", "ffn", "lm_head"
MOE_SCOPES = (L.MOE_ROUTE_SCOPE, L.MOE_DISPATCH_SCOPE, L.MOE_EXPERTS_SCOPE,
              L.MOE_COMBINE_SCOPE)

# ------------------------------------------------------------- shapes ------


def _block_shapes(cfg: ModelConfig, blk: BlockCfg) -> Dict[str, tuple]:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    f = cfg.d_ff
    shp: Dict[str, tuple] = {}
    if blk.mixer == "attn":
        shp["attn"] = {
            "norm": (d,), "wq": (d, h * hd), "wk": (d, kv * hd),
            "wv": (d, kv * hd), "wo": (h * hd, d),
        }
        if cfg.qk_norm:
            shp["attn"]["q_norm"] = (hd,)
            shp["attn"]["k_norm"] = (hd,)
    elif blk.mixer == "mamba":
        di, n, dtr = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
        shp["mamba"] = {
            "norm": (d,), "in_proj": (d, 2 * di),
            "conv_w": (di, cfg.mamba_conv), "conv_b": (di,),
            "x_proj": (di, dtr + 2 * n), "dt_proj": (dtr, di),
            "dt_bias": (di,), "A_log": (di, n), "D": (di,),
            "out_proj": (di, d),
        }
    elif blk.mixer == "rwkv":
        hh = cfg.rwkv_heads
        shp["rwkv"] = {
            "norm": (d,), "mix_mu": (5, d),
            "mix_A": (d, 5 * RWKV_MIX_RANK),
            "mix_B": (5, RWKV_MIX_RANK, d),
            "w0": (d,), "decay_A": (d, RWKV_DECAY_RANK),
            "decay_B": (RWKV_DECAY_RANK, d),
            "w_r": (d, d), "w_k": (d, d), "w_v": (d, d), "w_g": (d, d),
            "w_o": (d, d), "u": (hh, cfg.rwkv_head_dim), "gn_scale": (d,),
        }
    else:
        raise ValueError(blk.mixer)

    if blk.ffn == "mlp":
        shp["mlp"] = {"norm": (d,), "w_up": (d, f), "w_down": (f, d)}
        if cfg.act == "silu":
            shp["mlp"]["w_gate"] = (d, f)
    elif blk.ffn == "moe":
        e = cfg.num_experts
        shp["moe"] = {
            "norm": (d,), "router": (d, e), "w_gate": (e, d, f),
            "w_up": (e, d, f), "w_down": (e, f, d),
        }
    elif blk.ffn == "rwkv_cm":
        shp["rwkv_cm"] = {"norm": (d,), "cm_mu": (2, d), "cm_k": (d, f),
                          "cm_v": (f, d), "cm_r": (d, d)}
    elif blk.ffn == "none":
        pass
    else:
        raise ValueError(blk.ffn)
    return shp


def param_shapes(cfg: ModelConfig) -> Dict:
    """Nested dict of shape tuples (block leaves stacked over periods)."""
    p = cfg.num_periods
    blocks = {}
    for i, blk in enumerate(cfg.pattern):
        blocks[f"b{i}"] = jax.tree.map(
            lambda s: (p,) + s, _block_shapes(cfg, blk),
            is_leaf=lambda x: isinstance(x, tuple))
    shapes = {
        "embed": (cfg.vocab_size, cfg.d_model),
        "final_norm": (cfg.d_model,),
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        shapes["lm_head"] = (cfg.d_model, cfg.vocab_size)
    return shapes


def param_structs(cfg: ModelConfig) -> Dict:
    """ShapeDtypeStruct pytree for allocation-free lowering (dry-run)."""
    dt = jnp.dtype(cfg.param_dtype)
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, dt),
                        param_shapes(cfg),
                        is_leaf=lambda x: isinstance(x, tuple))


def init_params(rng: jax.Array, cfg: ModelConfig) -> Dict:
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(rng, len(flat))
    dt = jnp.dtype(cfg.param_dtype)
    depth_scale = 1.0 / math.sqrt(2 * cfg.num_layers)

    leaves = []
    for (path, shape), key in zip(flat, keys):
        name = jax.tree_util.keystr(path).lower()
        if "a_log" in name:
            n = shape[-1]
            leaf = jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), shape)
        elif "dt_bias" in name:
            leaf = jnp.full(shape, math.log(math.expm1(0.01)))
        elif "mix_mu" in name or name.endswith("['u']"):
            leaf = jnp.full(shape, 0.5)
        elif "w0" in name:
            leaf = jnp.full(shape, -1.0) + 0.5 * jax.random.normal(key, shape)
        elif "gn_scale" in name or "cm_mu" in name:
            leaf = jnp.full(shape, 1.0 if "gn" in name else 0.5)
        elif "norm" in name:
            leaf = jnp.zeros(shape)
        elif "conv_b" in name or name.endswith("['d']"):
            leaf = (jnp.zeros(shape) if "conv" in name
                    else jnp.ones(shape))
        elif "embed" in name:
            leaf = 0.02 * jax.random.normal(key, shape)
        elif any(k in name for k in ("wo", "out_proj", "w_down", "w_o")):
            leaf = (0.02 * depth_scale) * jax.random.normal(key, shape)
        else:
            leaf = 0.02 * jax.random.normal(key, shape)
        leaves.append(leaf.astype(dt))
    return jax.tree.unflatten(treedef, leaves)

# ------------------------------------------------------------ forward ------


def _apply_attn(p: Dict, x: jax.Array, cfg: ModelConfig, blk: BlockCfg,
                positions: jax.Array) -> jax.Array:
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    dt_ = x.dtype
    xn = L.norm(x, p.get("norm"), cfg.norm)
    q = (xn @ p["wq"].astype(dt_)).reshape(b, s, h, hd)
    k = (xn @ p["wk"].astype(dt_)).reshape(b, s, kv, hd)
    v = (xn @ p["wv"].astype(dt_)).reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = L.norm(q, p["q_norm"], "rmsnorm")
        k = L.norm(k, p["k_norm"], "rmsnorm")
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    o = L.scan_attention(q, k, v, positions, window=blk.window)
    return (o.reshape(b, s, h * hd) @ p["wo"].astype(dt_))


def _apply_block(bp: Dict, x: jax.Array, blk: BlockCfg, cfg: ModelConfig,
                 positions: jax.Array) -> jax.Array:
    if blk.mixer == "attn":
        x = x + _apply_attn(bp["attn"], x, cfg, blk, positions)
    elif blk.mixer == "mamba":
        xn = L.norm(x, bp["mamba"].get("norm"), cfg.norm)
        x = x + ssm.mamba_mix(bp["mamba"], xn, cfg)
    elif blk.mixer == "rwkv":
        xn = L.norm(x, bp["rwkv"].get("norm"), cfg.norm)
        x = x + ssm.rwkv_mix(bp["rwkv"], xn, cfg)

    if blk.ffn == "mlp":
        xn = L.norm(x, bp["mlp"].get("norm"), cfg.norm)
        x = x + L.mlp(bp["mlp"], xn, cfg)
    elif blk.ffn == "moe":
        xn = L.norm(x, bp["moe"].get("norm"), cfg.norm)
        x = x + L.moe_ffn(bp["moe"], xn, cfg)
    elif blk.ffn == "rwkv_cm":
        xn = L.norm(x, bp["rwkv_cm"].get("norm"), cfg.norm)
        x = x + ssm.rwkv_channel_mix(bp["rwkv_cm"], xn, cfg)
    return x


def embed_inputs(params: Dict, cfg: ModelConfig,
                 tokens: Optional[jax.Array],
                 embeds: Optional[jax.Array]) -> jax.Array:
    dt_ = jnp.dtype(cfg.compute_dtype)
    parts = []
    if embeds is not None:
        parts.append(embeds.astype(dt_))
    if tokens is not None:
        e = jnp.take(params["embed"], tokens, axis=0).astype(dt_)
        if getattr(cfg, "embed_scale", False):
            e = e * math.sqrt(cfg.d_model)
        parts.append(e)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def forward(params: Dict, cfg: ModelConfig,
            tokens: Optional[jax.Array] = None,
            embeds: Optional[jax.Array] = None,
            positions: Optional[jax.Array] = None) -> jax.Array:
    """Returns final hidden states (B, S, D) after the final norm."""
    x = embed_inputs(params, cfg, tokens, embeds)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def period_fn(x, period_params):
        for i, blk in enumerate(cfg.pattern):
            x = _apply_block(period_params[f"b{i}"], x, blk, cfg, positions)
        return x, None

    body = period_fn
    if cfg.remat:
        body = jax.checkpoint(period_fn,
                              policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, params["blocks"])
    else:
        for per in range(cfg.num_periods):
            sliced = jax.tree.map(lambda a: a[per], params["blocks"])
            x, _ = body(x, sliced)
    return L.norm(x, params.get("final_norm"), cfg.norm)

# --------------------------------------------------------------- loss ------


def lm_head_weight(params: Dict, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def lm_loss(params: Dict, hidden: jax.Array, targets: jax.Array,
            cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    """Chunked vocab-parallel cross entropy.

    hidden: (B, S, D); targets: (B, S) int32, -1 = masked.  The LM head +
    softmax run per sequence chunk so the (B, chunk, V) logits — not the
    (B, S, V) tensor — bound memory; with V sharded over "model" the
    normaliser and the target logit are computed with one-hot reductions
    (Megatron-style vocab-parallel CE).
    """
    b, s, d = hidden.shape
    w = lm_head_weight(params, cfg).astype(hidden.dtype)
    chunk = min(cfg.loss_chunk, s)
    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    hid = hidden.reshape(b, n_chunks, chunk, d).swapaxes(0, 1)
    tgt = targets.reshape(b, n_chunks, chunk).swapaxes(0, 1)

    from repro.models.perf_flags import baseline_mode

    def chunk_loss_core(h, t):
        # §Perf H1: keep the chunk logits vocab-sharded over "model" —
        # without the hint GSPMD replicates full-vocab logits per device.
        logits = (h @ w).astype(jnp.float32)                # (B, c, V)
        if not baseline_mode():
            logits = shard_utils.hint(logits, "batch", None, "model")
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        vio = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        sel = vio == jnp.maximum(t, 0)[:, :, None]
        tl = jnp.sum(jnp.where(sel, logits, 0.0), axis=-1)
        mask = (t >= 0).astype(jnp.float32)
        return jnp.sum((lse - tl) * mask), jnp.sum(mask)

    # §Perf H2: remat the chunk — otherwise the scan saves every chunk's
    # (B, c, V) logits as backward residuals (e.g. 13 GB/device for olmo).
    if not baseline_mode():
        chunk_loss_core = jax.checkpoint(chunk_loss_core)

    def chunk_loss(carry, xs):
        h, t = xs
        ls, m = chunk_loss_core(h, t)
        loss_sum, cnt = carry
        return (loss_sum + ls, cnt + m), None

    (loss_sum, cnt), _ = jax.lax.scan(
        chunk_loss, (jnp.float32(0), jnp.float32(0)), (hid, tgt))
    loss = loss_sum / jnp.maximum(cnt, 1)
    return loss, {"loss": loss, "tokens": cnt}


def loss_fn(params: Dict, batch: Dict, cfg: ModelConfig
            ) -> Tuple[jax.Array, Dict]:
    hidden = forward(params, cfg, tokens=batch.get("tokens"),
                     embeds=batch.get("embeds"))
    return lm_loss(params, hidden, batch["targets"], cfg)

# ------------------------------------------------------------- decode ------


def attn_capacity(blk: BlockCfg, max_len: int) -> int:
    """Per-slot KV line count for one attention block: the sliding window
    bounds the live set, so windowed blocks cache a ring of that size."""
    return min(blk.window, max_len) if blk.window else max_len


def paged_layout(cfg: ModelConfig, max_len: int,
                 page_len: int) -> Dict[str, int]:
    """Page-table width per attention block: ``{bname: page_slots}``.

    ``page_slots = ceil(capacity / page_len)`` — the number of page-table
    entries one slot needs to cover its whole capacity (window-bounded for
    sliding-window blocks).  Blocks with no attention mixer carry O(1)
    recurrent state per slot and are not paged.
    """
    assert page_len > 0
    out = {}
    for i, blk in enumerate(cfg.pattern):
        if blk.mixer == "attn":
            out[f"b{i}"] = -(-attn_capacity(blk, max_len) // page_len)
    return out


def paged_addressing(page_slots: int, page_len: int,
                     window: Optional[int]) -> Tuple[int, bool]:
    """(capacity_tokens, ring) for one paged pool — the write addressing
    that the host-side allocator (``PagedKVCache.ensure``) and the
    device-side cache write (``_decode_attn``) must agree on exactly:
    ring pools write at ``pos % capacity``, others clip to the last
    slot.  One definition for both sides, so they cannot drift."""
    cap = page_slots * page_len
    return cap, window is not None and cap >= window


def _cache_shapes(cfg: ModelConfig, blk: BlockCfg, batch: int,
                  max_len: int, page_len: int = 0,
                  pool_pages: Optional[int] = None) -> Dict[str, tuple]:
    p = cfg.num_periods
    hd = cfg.resolved_head_dim
    if blk.mixer == "attn":
        if page_len > 0:
            # paged layout: a pool of fixed-size pages shared across slots
            # (axis 1 = physical page id, axis 2 = line within the page);
            # a per-slot page table maps logical token slots onto pages.
            # Physical page 0 is the reserved trash page (never allocated)
            # that unmapped table entries point at.
            slots = -(-attn_capacity(blk, max_len) // page_len)
            n = (batch * slots + 1) if pool_pages is None else pool_pages
            return {"k": (p, n, page_len, cfg.num_kv_heads, hd),
                    "v": (p, n, page_len, cfg.num_kv_heads, hd)}
        c = attn_capacity(blk, max_len)
        return {"k": (p, batch, c, cfg.num_kv_heads, hd),
                "v": (p, batch, c, cfg.num_kv_heads, hd)}
    if blk.mixer == "mamba":
        return {"h": (p, batch, cfg.mamba_d_inner, cfg.mamba_d_state),
                "conv": (p, batch, cfg.mamba_conv - 1, cfg.mamba_d_inner)}
    if blk.mixer == "rwkv":
        shp = {"s": (p, batch, cfg.rwkv_heads, cfg.rwkv_head_dim,
                     cfg.rwkv_head_dim),
               "x_prev": (p, batch, cfg.d_model)}
        return shp
    raise ValueError(blk.mixer)


def cache_structs(cfg: ModelConfig, batch: int, max_len: int,
                  page_len: int = 0,
                  pool_pages: Optional[Dict[str, int]] = None) -> Dict:
    """ShapeDtypeStructs of the decode cache (bf16 KV, f32 SSM states).

    ``page_len > 0`` switches attention leaves to the paged layout:
    ``(P, pool, page_len, Hkv, hd)`` pools indexed through per-slot page
    tables (see ``paged_layout`` / ``repro.serve.paging``), with
    ``pool_pages[bname]`` physical pages per block (default: worst case
    ``batch * page_slots`` + the trash page).  Recurrent (SSM/RWKV) state
    stays slotted — it is O(1) per slot and needs no paging.
    """
    out = {}
    for i, blk in enumerate(cfg.pattern):
        shp = _cache_shapes(cfg, blk, batch, max_len, page_len,
                            (pool_pages or {}).get(f"b{i}"))
        entry = {}
        for k, s in shp.items():
            dt = jnp.float32 if k in ("h", "s") else jnp.dtype(
                cfg.compute_dtype)
            entry[k] = jax.ShapeDtypeStruct(s, dt)
        if blk.ffn == "rwkv_cm":
            entry["cm_x_prev"] = jax.ShapeDtypeStruct(
                (cfg.num_periods, batch, cfg.d_model),
                jnp.dtype(cfg.compute_dtype))
        out[f"b{i}"] = entry
    return out


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               page_len: int = 0,
               pool_pages: Optional[Dict[str, int]] = None) -> Dict:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_structs(cfg, batch, max_len, page_len,
                                      pool_pages))


def _decode_attn(p: Dict, x: jax.Array, cache: Dict, cfg: ModelConfig,
                 blk: BlockCfg, pos: jax.Array, packed: Optional[Dict] = None,
                 impl: Optional[str] = None,
                 page_table: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Dict]:
    """``packed`` maps projection names (wq/wk/wv/wo) to ``BitmapWeight``s;
    present entries stream compressed through kernels/ops (serve time).

    ``page_table`` ((B, page_slots) int32, physical page ids, 0 = the
    reserved trash page) switches the cache onto the paged layout: the
    K/V write scatters through the table into the page pool and attention
    gathers the slot's pages back into one contiguous view (see
    ``repro.serve.paging``).
    """
    b, _, d = x.shape
    hd = cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    pk = packed or {}
    xn = L.norm(x, p.get("norm"), cfg.norm)
    q = L.matmul_or_bitmap(xn, p["wq"], pk.get("wq"), impl).reshape(
        b, 1, h, hd)
    k = L.matmul_or_bitmap(xn, p["wk"], pk.get("wk"), impl).reshape(
        b, 1, kv, hd)
    v = L.matmul_or_bitmap(xn, p["wv"], pk.get("wv"), impl).reshape(
        b, 1, kv, hd)
    if cfg.qk_norm:
        q = L.norm(q, p["q_norm"], "rmsnorm")
        k = L.norm(k, p["k_norm"], "rmsnorm")
    with jax.named_scope(ATTENTION_SCOPE):
        o, new_cache = _decode_attn_core(q, k, v, cache, cfg, blk, pos,
                                         page_table)
    out = L.matmul_or_bitmap(o.reshape(b, 1, h * hd), p["wo"],
                             pk.get("wo"), impl)
    return out, new_cache


def _decode_attn_core(q, k, v, cache: Dict, cfg: ModelConfig,
                      blk: BlockCfg, pos: jax.Array,
                      page_table: Optional[jax.Array]
                      ) -> Tuple[jax.Array, Dict]:
    """RoPE, the K/V cache write and attention of one decode step."""
    b = q.shape[0]
    pos = jnp.asarray(pos)
    posv = jnp.broadcast_to(pos, (b,)) if pos.ndim == 0 else pos
    posb = posv[:, None]
    q = L.rope(q, posb, cfg.rope_theta)
    k = L.rope(k, posb, cfg.rope_theta)
    if page_table is not None:
        plen = cache["k"].shape[1]
        cap, ring = paged_addressing(page_table.shape[1], plen, blk.window)
        slot = (posv % cap) if ring else jnp.clip(posv, 0, cap - 1)
        k_cache, v_cache = L.paged_kv_update(
            cache["k"], cache["v"], k, v, page_table, slot)
        k_att = L.paged_gather(k_cache, page_table)
        v_att = L.paged_gather(v_cache, page_table)
    else:
        c = cache["k"].shape[1]
        ring = blk.window is not None and c == blk.window
        if pos.ndim == 0:
            slot = (pos % c) if ring else pos
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        else:
            # per-slot positions (continuous batching): each batch row
            # writes its own cache line, so the update is a batched scatter
            slot = (posv % c) if ring else jnp.clip(posv, 0, c - 1)
            k_cache, v_cache = L.slot_kv_update(cache["k"], cache["v"],
                                                k, v, slot)
        k_att, v_att = k_cache, v_cache
    o = L.decode_attention(q, k_att, v_att, pos, window=blk.window,
                           ring=ring)
    return o, {"k": k_cache, "v": v_cache}


def _prefill_attn(p: Dict, x: jax.Array, cache: Dict, cfg: ModelConfig,
                  blk: BlockCfg, pos: jax.Array, lens: jax.Array,
                  packed: Optional[Dict] = None,
                  impl: Optional[str] = None,
                  page_table: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Dict]:
    """Chunked-prefill attention: C tokens per slot through one call.

    x: (B, C, D) chunk hidden states; pos: (B,) start position of each
    slot's chunk; lens: (B,) valid tokens this call (rows past their
    length are padding lanes whose cache writes are masked off).

    The q/k/v/o projections run batched over the whole chunk (M = B·C
    rows through ``matmul_or_bitmap`` — where the compressed weight
    stream amortizes), and so does the attention core: one blocked
    causal call over the chunk (``_prefill_attn_core``).  The contract
    against teacher-forcing the prompt through decode steps (ring wraps,
    windows and paging included): the cache writes are bit-identical,
    each token's attention output attends the same (position, K/V)
    pairs and differs only by the order of the softmax and value sums,
    and the served tokens match.
    """
    b, c_chunk, d = x.shape
    hd = cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    pk = packed or {}
    xn = L.norm(x, p.get("norm"), cfg.norm)
    q = L.matmul_or_bitmap(xn, p["wq"], pk.get("wq"), impl).reshape(
        b, c_chunk, h, hd)
    k = L.matmul_or_bitmap(xn, p["wk"], pk.get("wk"), impl).reshape(
        b, c_chunk, kv, hd)
    v = L.matmul_or_bitmap(xn, p["wv"], pk.get("wv"), impl).reshape(
        b, c_chunk, kv, hd)
    if cfg.qk_norm:
        q = L.norm(q, p["q_norm"], "rmsnorm")
        k = L.norm(k, p["k_norm"], "rmsnorm")
    with jax.named_scope(ATTENTION_SCOPE):
        o, new_cache = _prefill_attn_core(q, k, v, cache, cfg, blk, pos,
                                          lens, page_table)
    out = L.matmul_or_bitmap(o.reshape(b, c_chunk, h * hd), p["wo"],
                             pk.get("wo"), impl)
    return out, new_cache


def latest_writes(slot: jax.Array, valid: jax.Array) -> jax.Array:
    """(B, C) bool: the valid chunk tokens that are the latest valid
    token to write their cache line ``slot`` ((B, C)), so one scatter
    writes each line once, whatever order it applies duplicates in."""
    t = jnp.arange(slot.shape[1])
    later = ((slot[:, None, :] == slot[:, :, None])
             & (t[None, :] > t[:, None])[None] & valid[:, None, :])
    return valid & ~later.any(axis=-1)


def _prefill_attn_core(q, k, v, cache: Dict, cfg: ModelConfig,
                       blk: BlockCfg, pos: jax.Array, lens: jax.Array,
                       page_table: Optional[jax.Array]
                       ) -> Tuple[jax.Array, Dict]:
    """RoPE, then the whole chunk's attention in one blocked causal call
    against the cache as it stood before the chunk (one gather of each
    paged pool), then the chunk's K/V lines written in one scatter (see
    ``_prefill_attn``).

    Padding lanes (t >= lens) write to the trash page (paged) or are
    dropped (contiguous).  Where several chunk tokens map to one cache
    line (a chunk wider than a ring), only the latest is written and the
    others are masked like padding, so the cache never depends on the
    order of a scatter's duplicate targets.
    """
    c_chunk = q.shape[1]
    posv = jnp.asarray(pos)
    t = jnp.arange(c_chunk)
    posb = posv[:, None] + t[None, :]                        # (B, C)
    q = L.rope(q, posb, cfg.rope_theta)
    k = L.rope(k, posb, cfg.rope_theta)
    if page_table is not None:
        plen = cache["k"].shape[1]
        cap, ring = paged_addressing(page_table.shape[1], plen, blk.window)
        k_old = L.paged_gather(cache["k"], page_table)
        v_old = L.paged_gather(cache["v"], page_table)
    else:
        cap = cache["k"].shape[1]
        ring = blk.window is not None and cap == blk.window
        k_old, v_old = cache["k"], cache["v"]
    o = L.chunk_attention(q, k_old, v_old, k, v, posv, window=blk.window,
                          ring=ring)
    slot = (posb % cap) if ring else jnp.clip(posb, 0, cap - 1)
    live = latest_writes(slot, t[None, :] < lens[:, None])
    if page_table is not None:
        k_cache, v_cache = L.paged_kv_update(
            cache["k"], cache["v"], k, v, page_table, slot, valid=live)
    else:
        k_cache, v_cache = L.slot_kv_update(
            cache["k"], cache["v"], k, v, slot, valid=live)
    return o, {"k": k_cache, "v": v_cache}


def prefill_hidden(params: Dict, cache: Dict, cfg: ModelConfig,
                   tokens: jax.Array, pos: jax.Array, lens: jax.Array,
                   embeds: Optional[jax.Array] = None,
                   packed: Optional[Dict] = None,
                   impl: Optional[str] = None,
                   page_tables: Optional[Dict] = None
                   ) -> Tuple[jax.Array, Dict]:
    """One chunked-prefill call: C prompt tokens per slot in one pass.

    tokens: (B, C) (or embeds (B, C, D)); pos: (B,) chunk start
    positions; lens: (B,) valid tokens per slot (0 = the slot sits this
    call out; its lane is padding and writes nothing).  Returns (hidden
    (B, C, D) after the final norm, new cache) — the C KV lines per slot
    are written into the cache, which is the whole point: after the last
    chunk the slot joins the decode batch at position ``len(prompt) - 1``
    with its prompt cache fully resident.

    Projections and MLPs dispatch batched over the chunk (M = C through
    the packed ``matmul_or_bitmap`` path); MoE FFNs dispatch per token
    (chunk rows folded into the batch dim) so expert capacity — which
    scales with sequence length — matches the decode path token for
    token.  Recurrent mixers (mamba/rwkv) have no chunked path yet; the
    engine keeps teacher-forcing for those archs with a recorded reason.
    """
    x = embed_inputs(params, cfg, tokens, embeds)
    b, c_chunk, d = x.shape

    def period_fn(x, xs):
        period_params, period_cache, period_packed = xs
        new_cache = {}
        for i, blk in enumerate(cfg.pattern):
            bp = period_params[f"b{i}"]
            pc = period_cache[f"b{i}"]
            pw = (period_packed or {}).get(f"b{i}") or {}
            nc = {}
            if blk.mixer == "attn":
                o, nc = _prefill_attn(bp["attn"], x, pc, cfg, blk, pos,
                                      lens, packed=pw.get("attn"),
                                      impl=impl,
                                      page_table=(page_tables or {}).get(
                                          f"b{i}"))
                x = x + o
            else:
                raise NotImplementedError(
                    f"chunked prefill has no {blk.mixer} path; the engine "
                    f"falls back to teacher-forcing for this arch")
            with jax.named_scope(FFN_SCOPE):
                if blk.ffn == "mlp":
                    xn = L.norm(x, bp["mlp"].get("norm"), cfg.norm)
                    x = x + L.mlp(bp["mlp"], xn, cfg, packed=pw.get("mlp"),
                                  impl=impl)
                elif blk.ffn == "moe":
                    xn = L.norm(x, bp["moe"].get("norm"), cfg.norm)
                    # per-token dispatch: capacity = ceil(S·k·cf/E)
                    # depends on the sequence length, so a (B, C) chunk
                    # through one MoE call would drop tokens differently
                    # than C decode steps — folding the chunk into the
                    # batch dim keeps the dispatch (and the tokens)
                    # bit-identical to decode
                    mo = L.moe_ffn(bp["moe"],
                                   xn.reshape(b * c_chunk, 1, d), cfg,
                                   packed=pw.get("moe"), impl=impl)
                    x = x + mo.reshape(b, c_chunk, d)
            if blk.ffn == "rwkv_cm":
                raise NotImplementedError(
                    "chunked prefill has no rwkv_cm path; the engine "
                    "falls back to teacher-forcing for this arch")
            new_cache[f"b{i}"] = nc
        return x, new_cache

    x, new_cache = jax.lax.scan(period_fn, x,
                                (params["blocks"], cache, packed))
    return L.norm(x, params.get("final_norm"), cfg.norm), new_cache


def decode_hidden(params: Dict, cache: Dict, cfg: ModelConfig,
                  tokens: Optional[jax.Array], pos: jax.Array,
                  embeds: Optional[jax.Array] = None,
                  packed: Optional[Dict] = None,
                  impl: Optional[str] = None,
                  page_tables: Optional[Dict] = None
                  ) -> Tuple[jax.Array, Dict]:
    """One decode step up to (and including) the final norm — no LM head.

    tokens: (B, 1) (or embeds (B, 1, D)); pos: scalar shared position or a
    (B,) vector of per-slot positions (continuous batching).  Returns
    (hidden (B, 1, D), new cache).  Scans over periods, carrying the
    hidden state and threading each period's cache slice through as
    scan xs/ys.

    ``packed`` mirrors ``params["blocks"]`` with period-stacked
    ``BitmapWeight`` leaves — 2-D projections (attention, MLP, MoE
    router, mamba/rwkv mixer and channel-mix GEMMs) plus group-stacked
    MoE expert tensors and rwkv's mix_B (or None where a tensor fell
    back to dense — see repro.serve.packed); the scan slices off the
    period axis so each iteration's projections stream bitmap-compressed
    through kernels/ops.

    ``page_tables`` (``{bname: (B, page_slots) int32}``) switches attention
    blocks onto the paged-cache layout.  Tables are shared by all periods
    of a block (the physical-page axis of each pool already carries the
    period dim), so they ride into the scan body by closure, not as xs.
    """
    x = embed_inputs(params, cfg, tokens, embeds)
    b = x.shape[0]

    def period_fn(x, xs):
        period_params, period_cache, period_packed = xs
        new_cache = {}
        for i, blk in enumerate(cfg.pattern):
            bp = period_params[f"b{i}"]
            pc = period_cache[f"b{i}"]
            pw = (period_packed or {}).get(f"b{i}") or {}
            nc = {}
            if blk.mixer == "attn":
                o, nc = _decode_attn(bp["attn"], x, pc, cfg, blk, pos,
                                     packed=pw.get("attn"), impl=impl,
                                     page_table=(page_tables or {}).get(
                                         f"b{i}"))
                x = x + o
            elif blk.mixer == "mamba":
                xn = L.norm(x, bp["mamba"].get("norm"), cfg.norm)
                o, st = ssm.mamba_decode(bp["mamba"], xn,
                                         {"h": pc["h"], "conv": pc["conv"]},
                                         cfg, packed=pw.get("mamba"),
                                         impl=impl)
                x = x + o
                nc = st
            elif blk.mixer == "rwkv":
                xn = L.norm(x, bp["rwkv"].get("norm"), cfg.norm)
                o, st = ssm.rwkv_decode(bp["rwkv"], xn,
                                        {"s": pc["s"],
                                         "x_prev": pc["x_prev"]}, cfg,
                                        packed=pw.get("rwkv"), impl=impl)
                x = x + o
                nc = st
            with jax.named_scope(FFN_SCOPE):
                if blk.ffn == "mlp":
                    xn = L.norm(x, bp["mlp"].get("norm"), cfg.norm)
                    x = x + L.mlp(bp["mlp"], xn, cfg, packed=pw.get("mlp"),
                                  impl=impl)
                elif blk.ffn == "moe":
                    xn = L.norm(x, bp["moe"].get("norm"), cfg.norm)
                    x = x + L.moe_ffn(bp["moe"], xn, cfg,
                                      packed=pw.get("moe"), impl=impl)
                elif blk.ffn == "rwkv_cm":
                    xn = L.norm(x, bp["rwkv_cm"].get("norm"), cfg.norm)
                    x = x + ssm.rwkv_channel_mix(
                        bp["rwkv_cm"], xn, cfg,
                        x_prev=pc["cm_x_prev"][:, None],
                        packed=pw.get("rwkv_cm"), impl=impl)
                    nc["cm_x_prev"] = xn[:, 0]
            new_cache[f"b{i}"] = nc
        return x, new_cache

    x, new_cache = jax.lax.scan(period_fn, x,
                                (params["blocks"], cache, packed))
    return L.norm(x, params.get("final_norm"), cfg.norm), new_cache


def head_logits(params: Dict, cfg: ModelConfig, hidden: jax.Array,
                lm_weight=None, lm_impl: Optional[str] = None) -> jax.Array:
    """LM head over (B, D) hidden states -> (B, V) f32 logits.

    ``lm_weight`` (a ``BitmapWeight``) switches the head matmul onto the
    bitmap-compressed path through ``kernels/ops.bitmap_spmm`` — the
    serving engine packs the head once and streams it compressed, so the
    dominant decode weight-traffic term runs the paper's format end-to-end.
    """
    logits = L.matmul_or_bitmap(hidden, lm_head_weight(params, cfg),
                                lm_weight, lm_impl).astype(jnp.float32)
    from repro.models.perf_flags import baseline_mode
    if not baseline_mode():
        # §Perf: keep decode logits vocab-sharded — otherwise GSPMD
        # gathers the whole embedding table per device (~12 GB/step for
        # gemma3-12b's 262k vocab).
        logits = shard_utils.hint(logits, "batch", "model")
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


def decode_step(params: Dict, cache: Dict, cfg: ModelConfig,
                tokens: Optional[jax.Array], pos: jax.Array,
                embeds: Optional[jax.Array] = None, lm_weight=None,
                packed: Optional[Dict] = None,
                lm_impl: Optional[str] = None,
                page_tables: Optional[Dict] = None
                ) -> Tuple[jax.Array, Dict]:
    """One decode step + LM head: (logits (B, V), new cache).

    ``packed`` (block-tree of period-stacked ``BitmapWeight``s) and
    ``lm_weight`` together put the whole per-step weight stream —
    attention q/k/v/o, MLP gate/up/down, LM head — on the
    bitmap-compressed kernels/ops path; ``page_tables`` routes the KV
    cache through the paged layout (see ``decode_hidden``).
    """
    x, new_cache = decode_hidden(params, cache, cfg, tokens, pos,
                                 embeds=embeds, packed=packed, impl=lm_impl,
                                 page_tables=page_tables)
    return head_logits(params, cfg, x[:, 0], lm_weight, lm_impl), new_cache
