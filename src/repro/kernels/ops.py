"""Jitted public entry points for the kernel layer.

Dispatch policy: on TPU the Pallas kernels run compiled; everywhere else
(this CPU container, and the 512-device dry-run which lowers through XLA)
the mathematically-identical reference path is used, keeping ``.lower()``
valid on any backend.  ``impl="pallas_interpret"`` forces the Pallas kernel
body through the interpreter — that is how the kernels are validated here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.bitmap_spmm import bitmap_spmm as _bitmap_spmm_pallas
from repro.kernels.bitmap_spmm import (
    bitmap_spmm_grouped as _bitmap_spmm_grouped_pallas)
from repro.kernels.bitmap_spmm import shard_slice
from repro.kernels.block_sparse import (
    block_sparse_matmul as _block_sparse_pallas)
from repro.kernels.flash_attention import (
    flash_attention as _flash_attention_pallas)
from repro.sparse.format import (BitmapWeight, BlockSparseWeight,
                                 unshard_bitmap)


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def bitmap_spmm(x: jax.Array, w: BitmapWeight, impl: str | None = None,
                **kw) -> jax.Array:
    """``x @ W`` with W bitmap-compressed; x may be (..., K) — leading
    dims are flattened into the kernel's row dimension (the Pallas path's
    small-M variant handles decode batches without padding to 128)."""
    impl = impl or default_impl()
    lead = x.shape[:-1]
    if x.ndim != 2:
        x = x.reshape(-1, x.shape[-1])
    if impl == "xla":
        # the reference path is tiling-independent, so the unsharded
        # fold-back is value-identical to per-shard composition
        out = _ref.bitmap_spmm_ref(x, unshard_bitmap(w))
    elif w.shard is not None:
        out = _sharded_spmm(x, w, _bitmap_spmm_pallas,
                            interpret=(impl == "pallas_interpret"), **kw)
    else:
        out = _bitmap_spmm_pallas(x, w,
                                  interpret=(impl == "pallas_interpret"),
                                  **kw)
    return out.reshape(lead + (w.shape[1],)) if len(lead) != 1 else out


def _sharded_spmm(x: jax.Array, w: BitmapWeight, kernel, **kw) -> jax.Array:
    """Per-shard Pallas dispatch over a sharded ``BitmapWeight``.

    Column shards each produce a contiguous N slice (concat); row shards
    each consume a contiguous K slice and their partial products sum —
    the same composition a psum performs across model-axis devices.
    x's contraction axis is last (2D ``(M, K)`` or grouped ``(G, M, K)``).
    """
    mode, shards = w.shard
    if mode == "col":
        return jnp.concatenate(
            [kernel(x, shard_slice(w, s), **kw) for s in range(shards)],
            axis=-1)
    ks = w.shape[0] // shards
    parts = [kernel(x[..., s * ks:(s + 1) * ks], shard_slice(w, s), **kw)
             for s in range(shards)]
    return functools.reduce(jnp.add, parts)


def bitmap_spmm_grouped(x: jax.Array, w: BitmapWeight,
                        impl: str | None = None, **kw) -> jax.Array:
    """Per-group ``x[g] @ W_g`` over a group-stacked ``BitmapWeight``
    (MoE expert stacks, RWKV lerp stacks — layout in
    ``sparse.format.pack_bitmap_experts``).  x: (G, M, K) -> (G, M, N);
    the Pallas path is one kernel launch with the group as its outermost
    grid axis, each group streaming only its own compressed tiles."""
    impl = impl or default_impl()
    if impl == "xla":
        return _ref.bitmap_spmm_grouped_ref(x, unshard_bitmap(w))
    if w.shard is not None:
        return _sharded_spmm(x, w, _bitmap_spmm_grouped_pallas,
                             interpret=(impl == "pallas_interpret"), **kw)
    return _bitmap_spmm_grouped_pallas(
        x, w, interpret=(impl == "pallas_interpret"), **kw)


def block_sparse_matmul(x: jax.Array, w: BlockSparseWeight,
                        impl: str | None = None, **kw) -> jax.Array:
    impl = impl or default_impl()
    if impl == "xla":
        return _ref.block_sparse_matmul_ref(x, w)
    return _block_sparse_pallas(x, w, interpret=(impl == "pallas_interpret"),
                                **kw)


def flash_attention(q, k, v, impl: str | None = None, *, causal=True,
                    window=None, **kw) -> jax.Array:
    impl = impl or default_impl()
    if impl == "xla":
        return _ref.attention_ref(q, k, v, causal=causal, window=window)
    return _flash_attention_pallas(
        q, k, v, causal=causal, window=window,
        interpret=(impl == "pallas_interpret"), **kw)
