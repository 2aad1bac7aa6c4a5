"""Neural building blocks (pure functions over param dicts).

Everything is written against the shapes in ``model.param_shapes`` and kept
jit/pjit-friendly: no data-dependent shapes, scan-based attention for long
sequences, sort-based MoE dispatch with static capacity.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

# ---------------------------------------------------------------- norms ----


def norm(x: jax.Array, scale: Optional[jax.Array], kind: str) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
        if scale is not None:
            y = y * (1.0 + scale.astype(jnp.float32))
    elif kind == "ln_nonparam":          # olmo: non-parametric LayerNorm
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
    elif kind == "ln":
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
        if scale is not None:
            y = y * (1.0 + scale.astype(jnp.float32))
    else:
        raise ValueError(kind)
    return y.astype(x.dtype)


def group_norm_heads(x: jax.Array, scale: jax.Array, heads: int) -> jax.Array:
    """Per-head group norm (RWKV output norm). x: (..., H*Dh)."""
    shp = x.shape
    xf = x.reshape(*shp[:-1], heads, shp[-1] // heads).astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
    return (y.reshape(shp) * scale.astype(jnp.float32)).astype(x.dtype)

# ----------------------------------------------------------------- rope ----


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D) with D even; positions: (B, S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def activation(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(kind)

# ------------------------------------------------------------ attention ----


def _online_block(carry, kc, vc, q, q_pos, k_pos, window, scale):
    """One online-softmax step over a KV chunk. q:(B,H,Sq,D) kc:(B,H,C,D)."""
    m_prev, l_prev, acc = carry
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kc,
                   preferred_element_type=jnp.float32) * scale
    mask = q_pos[:, None, :, None] >= k_pos[:, None, None, :]
    if window is not None:
        mask &= (q_pos[:, None, :, None] - k_pos[:, None, None, :]) < window
    s = jnp.where(mask, s, -1e30)
    m_cur = jnp.max(s, -1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + p.sum(-1, keepdims=True)
    acc_new = alpha * acc + jnp.einsum(
        "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32))
    return (m_new, l_new, acc_new)


def scan_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   positions: jax.Array, *, window: Optional[int] = None,
                   q_chunk: int = 2048, kv_chunk: int = 512) -> jax.Array:
    """Causal flash-style attention in pure XLA (O(S) memory).

    q: (B, S, Hq, D); k/v: (B, S, Hkv, D); positions: (B, S).
    Python loop over query chunks; each chunk scans only the causally
    reachable KV prefix (FLOP-optimal), giving O(n_q) scan bodies in HLO.
    """
    from repro.models.perf_flags import baseline_mode
    if baseline_mode():  # §Perf H4 "before": materialise repeated KV
        g0 = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, g0, axis=2)
        v = jnp.repeat(v, g0, axis=2)
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = d ** -0.5
    q_chunk = min(q_chunk, s)
    kv_chunk = min(kv_chunk, s)

    # GQA without materialising repeated KV (§Perf H4): fold the group dim
    # into the query-sequence dim (s-major) so each KV head serves its G
    # query heads through the same (B, Hkv, ·) tiles — an 8× KV traffic cut
    # at kv=8 / 64 heads.
    qh = (q.reshape(b, s, hkv, g, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, hkv, s * g, d).astype(jnp.float32))
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)      # (B, Hkv, S, D)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)

    outs = []
    n_q = -(-s // q_chunk)
    for iq in range(n_q):
        q0 = iq * q_chunk
        q1 = min(q0 + q_chunk, s)
        qc = qh[:, :, q0 * g:q1 * g]
        qp = jnp.repeat(positions[:, q0:q1], g, axis=1)  # (B, (q1-q0)*g)
        kv_hi = q1  # causal reach
        if window is not None:
            kv_lo = max(0, (q0 - window + 1) // kv_chunk * kv_chunk)
        else:
            kv_lo = 0
        n_kv = -(-(kv_hi - kv_lo) // kv_chunk)
        kv_len = n_kv * kv_chunk
        kc = jax.lax.dynamic_slice_in_dim(
            kh, kv_lo, min(kv_len, s - kv_lo), axis=2)
        vc = jax.lax.dynamic_slice_in_dim(
            vh, kv_lo, min(kv_len, s - kv_lo), axis=2)
        if kc.shape[2] < kv_len:  # pad tail chunk
            pad = kv_len - kc.shape[2]
            kc = jnp.pad(kc, ((0, 0), (0, 0), (0, pad), (0, 0)))
            vc = jnp.pad(vc, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kcs = kc.reshape(b, hkv, n_kv, kv_chunk, d).transpose(2, 0, 1, 3, 4)
        vcs = vc.reshape(b, hkv, n_kv, kv_chunk, d).transpose(2, 0, 1, 3, 4)
        kp = (kv_lo + jnp.arange(kv_len)).reshape(n_kv, kv_chunk)
        kp = jnp.broadcast_to(kp[:, None, :], (n_kv, b, kv_chunk))
        qn = (q1 - q0) * g
        init = (jnp.full((b, hkv, qn, 1), -1e30, jnp.float32),
                jnp.zeros((b, hkv, qn, 1), jnp.float32),
                jnp.zeros((b, hkv, qn, d), jnp.float32))

        def step(carry, xs):
            kcb, vcb, kpb = xs
            return _online_block(carry, kcb, vcb, qc, qp, kpb, window,
                                 scale), None

        (m, l, acc), _ = jax.lax.scan(step, init, (kcs, vcs, kp))
        outs.append(acc / jnp.maximum(l, 1e-30))
    out = jnp.concatenate(outs, axis=2)                  # (B, Hkv, S*g, D)
    out = (out.reshape(b, hkv, s, g, d).transpose(0, 2, 1, 3, 4)
           .reshape(b, s, hq, d))
    return out.astype(q.dtype)


def cache_line_positions(pc: jax.Array, c: int, ring: bool) -> jax.Array:
    """(B, C) position held by each of a cache's C lines once position
    ``pc`` ((B, 1)) is written: line i itself, or in a ring the latest
    position p <= pc with p % C == i (negative for a line still cold)."""
    slots = jnp.arange(c)[None, :]
    if not ring:
        return jnp.broadcast_to(slots, (pc.shape[0], c))
    base = pc - (pc % c)
    return jnp.where(slots <= (pc % c), base + slots, base - c + slots)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, *, window: Optional[int] = None,
                     ring: bool = False) -> jax.Array:
    """Single-token attention against a cache.

    q: (B, 1, Hq, D); caches: (B, C, Hkv, D); pos: scalar current position,
    or a (B,) vector of per-sequence positions (continuous batching: each
    batch slot decodes at its own offset).
    ``ring`` marks a sliding-window ring buffer of size C == window.
    """
    from repro.models.perf_flags import baseline_mode
    if baseline_mode():  # §Perf H4 "before"
        g0 = q.shape[2] // k_cache.shape[2]
        k_cache = jnp.repeat(k_cache, g0, axis=2)
        v_cache = jnp.repeat(v_cache, g0, axis=2)
    b, c, hkv, d = k_cache.shape
    hq = q.shape[2]
    g = hq // hkv
    scale = d ** -0.5
    # grouped-query einsum — never materialise repeated KV (§Perf H4);
    # KV stays in cache dtype with f32 MXU accumulation (§Perf iter 3):
    # upcasting the KV shard to f32 per layer doubles decode HBM traffic.
    qh = q[:, 0].reshape(b, hkv, g, d).astype(k_cache.dtype)
    s = jnp.einsum("bkgd,bckd->bkgc", qh, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = s.reshape(b, hq, c)
    pos = jnp.asarray(pos)
    pc = (jnp.broadcast_to(pos, (b,)) if pos.ndim == 0 else pos)[:, None]
    slot_pos = cache_line_positions(pc, c, ring)
    valid = (slot_pos <= pc) & (slot_pos >= 0)
    if window is not None:
        valid &= (pc - slot_pos) < window
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).reshape(b, hkv, g, c)
    out = jnp.einsum("bkgc,bckd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def chunk_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                    k: jax.Array, v: jax.Array, pos: jax.Array, *,
                    window: Optional[int] = None, ring: bool = False
                    ) -> jax.Array:
    """Causal attention of a C-token chunk in one blocked call.

    q: (B, C, Hq, D); k_cache/v_cache: (B, S, Hkv, D), the cache as it
    stood before the chunk; k/v: (B, C, Hkv, D), the chunk's own lines
    (read in the cache dtype, as once written); pos: (B,) position of
    each slot's first chunk token.  Query t (at ``pos + t``) attends

    * cache lines holding positions in ``[0, pos - 1]`` (ring lines read
      at ``pos - 1``, as ``cache_line_positions`` maps them) and inside
      its window, and
    * chunk lines j <= t with t - j < window,

    the (position, K/V) pairs ``decode_attention`` sees at ``pos + t``
    once the chunk's lines up to t are written: only the order of the
    softmax and value sums differs.  A padding lane's output (a token
    past its slot's chunk length) is unspecified.  Dtypes follow
    ``decode_attention``: K/V in the cache dtype, f32 scores, softmax and
    accumulation, and repeated KV is never materialised for GQA.
    """
    b, s, hkv, d = k_cache.shape
    c, hq = q.shape[1], q.shape[2]
    g = hq // hkv
    scale = d ** -0.5
    qh = q.reshape(b, c, hkv, g, d).astype(k_cache.dtype)
    s_old = jnp.einsum("btkgd,bskd->bkgts", qh, k_cache,
                       preferred_element_type=jnp.float32) * scale
    s_new = jnp.einsum("btkgd,bjkd->bkgtj", qh, k.astype(k_cache.dtype),
                       preferred_element_type=jnp.float32) * scale
    pos = jnp.asarray(pos)
    t = jnp.arange(c)
    last = (pos - 1)[:, None]                                   # (B, 1)
    line_pos = cache_line_positions(last, s, ring)              # (B, S)
    old_ok = ((line_pos <= last) & (line_pos >= 0))[:, None, :]
    new_ok = (t[None, :] <= t[:, None])[None]                   # (1, C, C)
    if window is not None:
        q_pos = (pos[:, None] + t[None, :])[:, :, None]         # (B, C, 1)
        old_ok = old_ok & ((q_pos - line_pos[:, None, :]) < window)
        new_ok = new_ok & ((t[:, None] - t[None, :]) < window)[None]
    scores = jnp.concatenate(
        [jnp.where(old_ok[:, None, None], s_old, -1e30),
         jnp.where(new_ok[:, None, None], s_new, -1e30)], axis=-1)
    p = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = (jnp.einsum("bkgts,bskd->btkgd", p[..., :s], v_cache,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bkgtj,bjkd->btkgd", p[..., s:],
                        v.astype(v_cache.dtype),
                        preferred_element_type=jnp.float32))
    return out.reshape(b, c, hq, d).astype(q.dtype)


def paged_kv_update(k_pool: jax.Array, v_pool: jax.Array, k: jax.Array,
                    v: jax.Array, page_table: jax.Array,
                    write_slot: jax.Array,
                    valid: Optional[jax.Array] = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Write T K/V lines per batch row through a page table.

    k_pool/v_pool: (NP, L, Hkv, D) page pools (NP physical pages of L
    tokens; page 0 is the reserved trash page).  k/v: (B, T, Hkv, D).
    page_table: (B, S) int32 physical page ids, 0 = unmapped.
    write_slot: (B, T) logical token slots in [0, S*L) ((B,) when T = 1).

    Rows whose logical page is unmapped (idle batch slots decoding at
    position 0) resolve to page 0 and scribble into the trash line —
    live pages are only ever written by the slot that owns them, so
    distinct rows never collide outside the trash page.

    ``valid`` (write_slot's shape, bool) additionally routes masked-off
    lines to the trash page — chunked prefill runs a fixed-width batch
    where lanes past their chunk length must not touch live pages.
    """
    page_len = k_pool.shape[1]
    write_slot = write_slot.reshape(k.shape[:2])
    pi = write_slot // page_len
    off = write_slot % page_len
    phys = jnp.take_along_axis(page_table, pi, axis=1)
    if valid is not None:
        phys = jnp.where(valid.reshape(phys.shape), phys, 0)
    k_pool = k_pool.at[phys, off].set(k.astype(k_pool.dtype))
    v_pool = v_pool.at[phys, off].set(v.astype(v_pool.dtype))
    return k_pool, v_pool


def slot_kv_update(k_cache: jax.Array, v_cache: jax.Array, k: jax.Array,
                   v: jax.Array, write_slot: jax.Array,
                   valid: Optional[jax.Array] = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Write T K/V lines per batch row into the contiguous slotted cache.

    k_cache/v_cache: (B, C, Hkv, D); k/v: (B, T, Hkv, D); write_slot:
    (B, T) cache lines ((B,) when T = 1).  ``valid`` (write_slot's shape,
    bool) drops masked-off lines from the scatter entirely (the
    contiguous layout has no trash line, so chunked prefill's padding
    lanes redirect out of bounds and are dropped) — decode's
    unconditional write passes no mask and keeps its exact scatter.
    """
    bidx = jnp.arange(k_cache.shape[0])[:, None]
    write_slot = write_slot.reshape(k.shape[:2])
    if valid is None:
        k_cache = k_cache.at[bidx, write_slot].set(k.astype(k_cache.dtype))
        v_cache = v_cache.at[bidx, write_slot].set(v.astype(v_cache.dtype))
        return k_cache, v_cache
    slot = jnp.where(valid.reshape(write_slot.shape), write_slot,
                     k_cache.shape[1])                      # OOB when masked
    k_cache = k_cache.at[bidx, slot].set(k.astype(k_cache.dtype),
                                         mode="drop")
    v_cache = v_cache.at[bidx, slot].set(v.astype(v_cache.dtype),
                                         mode="drop")
    return k_cache, v_cache


def paged_gather(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """Gather each slot's pages into a contiguous (B, S*L, H, D) view.

    Unmapped entries (0) gather the trash page — garbage lines that the
    attention validity mask (slot_pos <= pos, window) always excludes, so
    pages never need zeroing when they move between requests.
    """
    b, s = page_table.shape
    lines = pool[page_table.reshape(-1)]            # (B*S, L, H, D)
    return lines.reshape(b, s * pool.shape[1], *pool.shape[2:])


# ----------------------------------------------------------------- MoE -----

#: ``jax.named_scope`` names of ``moe_ffn``'s four stages (inside the
#: block's ``ffn`` scope): the router with its softmax and top-k, the
#: sort, rank and scatter into the capacity buffers, the three grouped
#: expert products, and the gather and gate-weighted sum back per token
MOE_ROUTE_SCOPE, MOE_DISPATCH_SCOPE, MOE_EXPERTS_SCOPE, MOE_COMBINE_SCOPE = (
    "moe_route", "moe_dispatch", "moe_experts", "moe_combine")


def expert_capacity(tokens: int, cfg: ModelConfig) -> int:
    """Rows each expert takes from a dispatch over ``tokens`` tokens."""
    return int(tokens * cfg.top_k * cfg.capacity_factor
               / cfg.num_experts) + 1


def moe_rows(tokens: int, cfg: ModelConfig) -> Tuple[int, int]:
    """Rows of ``moe_ffn``'s dispatch of one batch row of ``tokens``
    tokens: the routed (token, expert) pairs, and the rows the grouped
    expert products compute (every expert's capacity buffer)."""
    return (tokens * cfg.top_k,
            cfg.num_experts * expert_capacity(tokens, cfg))


def expert_matmul_or_bitmap(h: jax.Array, w: jax.Array, bw, impl
                            ) -> jax.Array:
    """Per-expert GEMM ``h[..., e, :, :] @ w[e]`` for expert stacks.

    h: (..., E, C, K); w: (E, K, N).  A group-stacked ``BitmapWeight``
    (``bw`` — see ``sparse.format.pack_bitmap_experts``) streams each
    expert's compressed tiles through ``kernels/ops.bitmap_spmm_grouped``
    instead; ``bw is None`` keeps the dense einsum both MoE dispatch
    variants always ran."""
    if bw is None:
        return jnp.einsum("...eck,ekn->...ecn", h, w.astype(h.dtype))
    from repro.kernels import ops  # lazy: layers must not import kernels
    lead = h.shape[:-3]
    e, c, k = h.shape[-3:]
    hx = jnp.moveaxis(h.reshape((-1, e, c, k)), 1, 0).reshape(e, -1, k)
    out = ops.bitmap_spmm_grouped(hx, bw, impl=impl)
    n = out.shape[-1]
    return jnp.moveaxis(out.reshape(e, -1, c, n), 0, 1).reshape(
        lead + (e, c, n))


def _moe_ffn_global(params: dict, x: jax.Array, cfg: ModelConfig,
                    packed: Optional[dict] = None,
                    impl: Optional[str] = None) -> jax.Array:
    """§Perf H3 "before": global flat-token dispatch (argsort across the
    whole batch) — forces GSPMD to all-gather the token buffer."""
    pk = packed or {}
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    t = b * s
    cap = expert_capacity(t, cfg)
    xt = x.reshape(t, d)
    logits = matmul_or_bitmap(xt, params["router"], pk.get("router"), impl)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    gate, expert_idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    flat_expert = expert_idx.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    same = jnp.cumsum(jax.nn.one_hot(sorted_expert, e, dtype=jnp.int32), 0)
    rank = same[jnp.arange(t * k), sorted_expert] - 1
    keep = rank < cap
    slot = sorted_expert * cap + jnp.where(keep, rank, 0)
    src_token = order // k
    buf = jnp.zeros((e * cap, d), x.dtype)
    buf = buf.at[slot].add(jnp.where(keep[:, None], xt[src_token], 0))
    buf = buf.reshape(e, cap, d)
    h = activation(expert_matmul_or_bitmap(buf, params["w_gate"],
                                           pk.get("w_gate"), impl), cfg.act)
    h = h * expert_matmul_or_bitmap(buf, params["w_up"], pk.get("w_up"),
                                    impl)
    y = expert_matmul_or_bitmap(h, params["w_down"], pk.get("w_down"),
                                impl).reshape(e * cap, d)
    gath = jnp.where(keep[:, None], y[slot], 0)
    gval = gate.reshape(-1)[order]
    out = jnp.zeros((t, d), jnp.float32)
    out = out.at[src_token].add(gath.astype(jnp.float32) * gval[:, None])
    return out.reshape(b, s, d).astype(x.dtype)


def moe_ffn(params: dict, x: jax.Array, cfg: ModelConfig,
            packed: Optional[dict] = None,
            impl: Optional[str] = None) -> jax.Array:
    """Sort-based top-k MoE with static capacity. x: (B, S, D) -> (B, S, D).

    Dispatch is *per batch row* (§Perf H3): the sort, ranking and bucket
    scatter are all vectorised over B with no cross-row dataflow, so with B
    sharded over the data axes GSPMD keeps dispatch entirely local — a
    global flat-token argsort forces all-gathers of the whole token buffer.
    Capacity is per-row: C = ceil(S·k·cf / E); overflow tokens are dropped
    (standard capacity dispatch).  Expert weights shard on the FFN dim
    ("model"), so the expert einsums are local too.

    ``packed`` maps ``router`` to a period-stacked ``BitmapWeight`` and
    ``w_gate``/``w_up``/``w_down`` to expert-stacked ones (serve-time
    compressed streaming — see repro.serve.packed / DESIGN_PACKED.md);
    present entries dispatch per-expert bitmap SpMM through kernels/ops.
    """
    from repro.models import shard_utils
    from repro.models.perf_flags import baseline_mode
    if baseline_mode():
        return _moe_ffn_global(params, x, cfg, packed=packed, impl=impl)
    pk = packed or {}
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    _, buf_rows = moe_rows(s, cfg)
    cap = buf_rows // e

    with jax.named_scope(MOE_ROUTE_SCOPE):
        logits = matmul_or_bitmap(x, params["router"], pk.get("router"),
                                  impl)
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
        gate, expert_idx = jax.lax.top_k(probs, k)       # (B, S, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    with jax.named_scope(MOE_DISPATCH_SCOPE):
        flat_e = expert_idx.reshape(b, s * k)
        order = jnp.argsort(flat_e, axis=-1, stable=True)    # (B, S*k)
        sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
        # rank within expert group = position − first occurrence (sorted
        # rows); §Perf iter 4: the one-hot/cumsum rank cost (B, S·k, E)
        # int traffic (~900 GB/step for moonshot); searchsorted is
        # O(S·k·log)
        first = jax.vmap(lambda row: jnp.searchsorted(row, row,
                                                      side="left"))(sorted_e)
        rank = jnp.arange(s * k)[None, :] - first
        keep = rank < cap
        slot = sorted_e * cap + jnp.where(keep, rank, 0)     # (B, S*k)
        src = order // k                                      # token id

        rows = jnp.arange(b)[:, None]
        gathered = jnp.take_along_axis(x, src[..., None], axis=1)
        buf = jnp.zeros((b, buf_rows, d), x.dtype)
        buf = buf.at[rows, slot].add(
            jnp.where(keep[..., None], gathered, 0))
        buf = shard_utils.hint(buf.reshape(b, e, cap, d), "batch")

    with jax.named_scope(MOE_EXPERTS_SCOPE):
        h = activation(expert_matmul_or_bitmap(
            buf, params["w_gate"], pk.get("w_gate"), impl), cfg.act)
        h = h * expert_matmul_or_bitmap(buf, params["w_up"], pk.get("w_up"),
                                        impl)
        # §Perf iter 4: gather h across the F shards so the w_down
        # contraction and the whole combine run locally on D shards (no
        # capacity-buffer AR)
        h = shard_utils.hint(h, "batch", None, None, None)
        y = expert_matmul_or_bitmap(h, params["w_down"], pk.get("w_down"),
                                    impl).reshape(b, buf_rows, d)
        y = shard_utils.hint(y, "batch", None, "model")

    with jax.named_scope(MOE_COMBINE_SCOPE):
        out_tok = jnp.take_along_axis(y, slot[..., None], axis=1)
        gval = jnp.take_along_axis(gate.reshape(b, s * k), order, axis=-1)
        contrib = jnp.where(keep[..., None], out_tok, 0).astype(jnp.float32)
        out = jnp.zeros((b, s, d), jnp.float32)
        out = out.at[rows, src].add(contrib * gval[..., None])
        return out.astype(x.dtype)


def matmul_or_bitmap(h: jax.Array, w: jax.Array, bw, impl) -> jax.Array:
    """One projection: dense ``h @ w`` unless a packed ``BitmapWeight`` is
    provided, in which case the matmul streams the compressed form through
    ``kernels/ops.bitmap_spmm`` (xla ref on CPU, Pallas on TPU) — packing
    is lossless, and the dense branch spells the matmul exactly as the
    kernel's reference does (f32 accumulation, one rounding to the
    activation dtype), so the two paths are numerically identical."""
    if bw is None:
        return jnp.dot(h, w.astype(h.dtype),
                       preferred_element_type=jnp.float32).astype(h.dtype)
    from repro.kernels import ops  # lazy: layers must not import kernels
    return ops.bitmap_spmm(h, bw, impl=impl)


def mlp(params: dict, x: jax.Array, cfg: ModelConfig,
        packed: Optional[dict] = None, impl: Optional[str] = None
        ) -> jax.Array:
    """Gated/plain MLP; ``packed`` maps weight names to ``BitmapWeight``s
    (serve-time compressed streaming — see repro.serve.packed)."""
    pk = packed or {}
    if "w_gate" in params:
        h = activation(matmul_or_bitmap(x, params["w_gate"],
                                        pk.get("w_gate"), impl), cfg.act)
        h = h * matmul_or_bitmap(x, params["w_up"], pk.get("w_up"), impl)
    else:
        h = activation(matmul_or_bitmap(x, params["w_up"],
                                        pk.get("w_up"), impl), cfg.act)
    return matmul_or_bitmap(h, params["w_down"], pk.get("w_down"), impl)
