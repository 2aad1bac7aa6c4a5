"""Pallas TPU kernel: flash attention (online softmax, KV streaming).

Not a paper contribution, but the perf-critical attention kernel for the
long-sequence shapes; it follows the same fetch-once discipline: each KV tile
is streamed HBM→VMEM once per query block and reused across the whole query
tile, with O(BQ·D) accumulator state instead of the O(S²) score matrix.

Supports GQA (query head h reads KV head h // group) via the KV BlockSpec
index map, causal masking, and sliding-window masking — the gemma3-style
local attention layers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int | None,
            bq: int, bkv: int, n_kv: int):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                    # (BQ, D)
    k = k_ref[0]                                    # (BKV, D)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bkv), 0)
    k_pos = kv_i * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = jnp.ones((bq, bkv), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                             # (BQ, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv_i == n_kv - 1)
    def _store():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bkv", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    bq: int = 128, bkv: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, D), k/v: (B, Hkv, Skv, D) -> (B, Hq, Sq, D)."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    scale = d ** -0.5
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)
    n_kv = skv // bkv
    grid = (b * hq, sq // bq, n_kv)

    def kv_map(h, iq, ik):
        bb = h // hq
        hh = (h % hq) // group
        return (bb * hkv + hh, ik, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          bq=bq, bkv=bkv, n_kv=n_kv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bkv, d), kv_map),
            pl.BlockSpec((1, bkv, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)
