"""Pallas TPU kernel: dense × N:M-structured-sparse matmul (beyond paper).

Same fetch-once/broadcast structure as ``bitmap_spmm`` (activation tiles
reused across the output-column grid dim, compressed weights across the
output-row dim, output-stationary f32 accumulator over K), but the
decompression is M·N masked selects instead of a cumsum re-sort — fully
regular, no data-dependent indexing, which is exactly what the MXU wants.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sparse.nm import NmWeight


def _decompress(vals, idx, *, n: int, m: int, bk: int, bn: int, dtype):
    """(BKc, BN) packed -> (BK, BN) dense via M·N selects."""
    g = bk // m
    v = vals.reshape(g, n, bn)
    ix = idx.reshape(g, n, bn).astype(jnp.int32)
    pos = jax.lax.broadcasted_iota(jnp.int32, (g, n, m, bn), 2)
    sel = ix[:, :, None, :] == pos
    dense = jnp.sum(jnp.where(sel, v[:, :, None, :], 0), axis=1)
    return dense.reshape(bk, bn).astype(dtype)


def _kernel(x_ref, v_ref, i_ref, o_ref, acc_ref, *, n, m, bk, bn, n_k):
    kq = pl.program_id(2)

    @pl.when(kq == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _decompress(v_ref[0, 0], i_ref[0, 0], n=n, m=m, bk=bk, bn=bn,
                    dtype=x_ref.dtype)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(kq == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret", "out_dtype"))
def nm_spmm(x: jax.Array, w: NmWeight, *, bm: int = 128,
            interpret: bool = False, out_dtype=None) -> jax.Array:
    """Compute ``x @ W`` with W N:M-compressed. x: (M, K) -> (M, N)."""
    mm, k = x.shape
    kk, n_cols = w.shape
    assert k == kk
    bk, bn = w.block
    kt, nt = k // bk, n_cols // bn
    bkc = w.values.shape[2]
    assert mm % bm == 0
    out_dtype = out_dtype or x.dtype

    return pl.pallas_call(
        functools.partial(_kernel, n=w.n_keep, m=w.m_group, bk=bk, bn=bn,
                          n_k=kt),
        grid=(mm // bm, nt, kt),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kq: (i, kq)),
            pl.BlockSpec((1, 1, bkc, bn), lambda i, j, kq: (kq, j, 0, 0)),
            pl.BlockSpec((1, 1, bkc, bn), lambda i, j, kq: (kq, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kq: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, n_cols), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="nm_spmm",
    )(x, w.values, w.idx)
