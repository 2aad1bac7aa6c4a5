"""Pallas TPU kernel: block-sparse matmul with scalar-prefetched indices.

Coarse-grain counterpart of ``bitmap_spmm``: all-zero (BK×BN) weight blocks
are *never fetched and never multiplied*.  The compressed per-column-block
K-index list (``kidx``) is the EIM idea at block granularity — matching is
done once at pack time and the grid iterates only over surviving blocks, so
no "PE" (grid step) is wasted on a failed match; ``nnzb`` masks the padded
tail steps (the only idling, bounded by load imbalance across column blocks —
the same tail the paper's Fig. 6 utilisation measures).

Uses ``PrefetchScalarGridSpec`` so the index list is resident before the
pipeline starts — the activation BlockSpec *computes its HBM address from the
prefetched index*, i.e. data-dependent fetch, exactly how SIDR's shared index
drives the SRAM address.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sparse.format import BlockSparseWeight


def _kernel(kidx_ref, nnzb_ref, x_ref, w_ref, o_ref, acc_ref, *, smax: int):
    j = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < nnzb_ref[j])
    def _mac():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[0, 0],
                                preferred_element_type=jnp.float32)

    @pl.when(s == smax - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret", "out_dtype"))
def block_sparse_matmul(x: jax.Array, w: BlockSparseWeight, *, bm: int = 128,
                        interpret: bool = False, out_dtype=None) -> jax.Array:
    """Compute ``x @ W`` with W block-sparse.  x: (M, K) -> (M, N)."""
    m, k = x.shape
    kk, n = w.shape
    assert k == kk
    bk, bn = w.block
    nt = n // bn
    smax = w.smax
    assert m % bm == 0
    out_dtype = out_dtype or x.dtype

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // bm, nt, smax),
        in_specs=[
            # activation block chosen by the prefetched K-block index
            pl.BlockSpec((bm, bk),
                         lambda i, j, s, kidx, nnzb: (i, kidx[j, s])),
            pl.BlockSpec((1, 1, bk, bn),
                         lambda i, j, s, kidx, nnzb: (j, s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn),
                               lambda i, j, s, kidx, nnzb: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, smax=smax),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="block_sparse_matmul",
    )(w.kidx, w.nnzb, x, w.values)
