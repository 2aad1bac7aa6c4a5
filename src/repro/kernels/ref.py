"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sparse.format import (BitmapWeight, BlockSparseWeight,
                                 unpack_bitmap, unpack_bitmap_stacked,
                                 unpack_block_sparse)


def bitmap_spmm_ref(x: jax.Array, w: BitmapWeight) -> jax.Array:
    """Oracle for ``bitmap_spmm``; also the serve-time xla dispatch.

    Decodes the bitmap (the EIM re-sort, ``unpack_bitmap``) inside the
    call, so a served step reads the same packed arrays the kernel does.
    """
    dense = unpack_bitmap(w).astype(x.dtype)
    return jnp.dot(x, dense, preferred_element_type=jnp.float32).astype(
        x.dtype)


def bitmap_spmm_grouped_ref(x: jax.Array, w: BitmapWeight) -> jax.Array:
    """Oracle for ``bitmap_spmm_grouped``; also the serve-time xla
    dispatch for group-stacked weights (MoE expert stacks, RWKV lerp
    stacks).  x: (G, M, K); W leaves lead with G.  Returns (G, M, N).
    Decodes through ``unpack_bitmap_stacked``, as ``bitmap_spmm_ref``
    does through ``unpack_bitmap``."""
    dense = unpack_bitmap_stacked(w).astype(x.dtype)
    return jnp.einsum("gmk,gkn->gmn", x, dense,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def block_sparse_matmul_ref(x: jax.Array, w: BlockSparseWeight) -> jax.Array:
    dense = unpack_block_sparse(w).astype(x.dtype)
    return jnp.dot(x, dense, preferred_element_type=jnp.float32).astype(
        x.dtype)


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int | None = None
                  ) -> jax.Array:
    """Dense masked attention with GQA. q: (B,Hq,S,D), k/v: (B,Hkv,S,D)."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((sq, k.shape[2]), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(
        q.dtype)
