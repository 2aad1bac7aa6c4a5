"""Pallas TPU kernel: dense × bitmap-compressed-sparse matmul (EIM on TPU).

TPU adaptation of the paper's EIM + SIDR (DESIGN.md §2):

* weights travel HBM→VMEM in the paper's bitmap format (packed bits +
  packed non-zero values, ``sparse.format.BitmapWeight``) — HBM traffic
  falls by ≈ the density ratio, the analogue of the 86 % SRAM-access cut;
* inside VMEM each tile is decompressed with the EIM re-sort (a value's
  slot is the number of set bits before it — the IMId/masked-bitmap logic
  of §II-C) and fed dense to the MXU.  Every step of the re-sort is an
  operation the TPU compiler lowers: the prefix counts are matmuls
  against triangular ones tiles, a tile row's (at most BN) values are
  fetched from the two stored value rows they can span by one-hot
  matmuls, and the expansion into bitmap positions is a 2-D lane gather;
* each expanded tile is applied to every row of the call: the row block
  is all of M whenever its blocks fit VMEM (``row_blocks``), so a tile is
  fetched and expanded once per call — the SIDR column-broadcast;
* output-stationary f32 accumulator in VMEM across the K grid axis.

Grid: (G, M/RB, N/BN, KT/TK), the K strips innermost (sequential
accumulation); G is the group axis of a group-stacked weight (1 for a
plain matrix) and RB the row block.  One grid step takes a *strip* of
TK consecutive K-tiles of one output column block: its blocks are
(RB, TK·BK) of x and (TK, ·, BN) of bitmap words and value rows, and its
body expands the TK tiles in K order, ``STRIP_UNROLL`` to a loop
iteration so that independent tiles' expansion chains overlap, and adds
each ``x[:, t·BK:(t+1)·BK] @ W_t`` into the f32 accumulator: a block of
fewer than 128 rows carries it in registers tile by tile, a larger one
expands the iteration's tiles first and carries each 128-row sub-block's
share through their products.  Both sizes are worked out from the
shapes against ``STRIP_VMEM_BUDGET``: RB is M rounded up to the sublane
multiple, or for an M whose one-tile blocks do not fit, the fewest equal
row blocks that do; TK is the largest divisor of KT whose blocks fit
beside RB's accumulator and output.  At olmo-1b's and
granite-3.0-3b-a800m's widths the row block is the whole M, decode (16
rows) and chunked prefill (512) alike, and the strip is the whole K but
for olmo's ``w_down`` at prefill (KT = 64 in 4 strips); where it is,
x's block index is constant across the column blocks and x is fetched
once per call.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sparse.format import TILE_ND, WORD_ROWS, BitmapWeight

#: lane width of a TPU vector register: the tile's column width on chip
LANES = 128
#: VMEM bytes one grid step's double-buffered x, bitmap, value and
#: output blocks and its f32 accumulator may take: half the 16 MiB
#: default scoped limit, the rest left to the tiles being expanded.  A
#: larger scoped limit asked of the compiler slowed olmo-1b's decode
#: step by 2.4 % on a v5e: it takes VMEM from XLA's own copies around
#: the kernel
STRIP_VMEM_BUDGET = 8 * 2 ** 20
#: K-tiles of a strip expanded per loop iteration, statically unrolled
#: so that the scheduler overlaps independent tiles' expansion chains.
#: Timed alone on a v5e at olmo-1b's widths and M = 16, a 128x128 tile
#: took 0.86 us at 1 tile per iteration, 0.69 at 2, 0.62 at 4, 0.49 at 8
#: and 0.42 at 16; 32 gained only at K = 8192 (0.39) for twice the
#: compile time (benchmarks/spmm_tile_time.py)
STRIP_UNROLL = 16


def tile_fits_chip(k: int, n: int, block: Tuple[int, int]) -> Optional[str]:
    """Why the compiled kernel cannot take a (K, N) weight tiled
    ``block``, or None when it can.  A block's last two dims must be
    divisible by (8, 128) or span the whole array; the kernel's lane
    gather wants exactly one vreg of lanes per tile row, and its bitmap
    words cover 32 rows each."""
    bk, bn = block
    if bn != LANES:
        return f"BN={bn} is not the {LANES}-lane width the TPU kernel needs"
    if bk % WORD_ROWS or (bk % LANES and bk != k):
        return (f"BK={bk} is neither a multiple of {LANES} nor the whole "
                f"K={k} in {WORD_ROWS}-row bitmap words")
    return None


def _decompress_tile(words, vals, bk: int, dtype):
    """EIM re-sort inside VMEM: packed tile -> dense (BK, BN).

    words: (ceil(BK/32), BN) int32 bitmap words; vals: (Q, BN) value rows.
    """
    nw, bn = words.shape
    f32, b16 = jnp.float32, jnp.bfloat16
    shifts = jax.lax.broadcasted_iota(jnp.int32, (WORD_ROWS, bn), 0)
    bits = jnp.concatenate([(words[i:i + 1] >> shifts) & 1
                            for i in range(nw)], axis=0)[:bk]
    # prefix counts as matmuls on 0/1 tiles: exact (f32 accumulation of
    # integers <= BK*BN), and the MXU takes them where cumsum has no
    # TPU lowering
    row = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
    upper = (row <= col).astype(b16)
    incl = jnp.dot(bits.astype(b16), upper, preferred_element_type=f32)
    row = jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 1)
    lower = (col < row).astype(b16)
    row_nnz = jnp.broadcast_to(incl[:, bn - 1:], (bk, bn)).astype(b16)
    start = jnp.dot(lower, row_nnz, preferred_element_type=f32)
    slot = (start + incl).astype(jnp.int32) - 1       # tile slot per element
    # a row's slots lie in stored rows h0 and h0 + 1: fetch both by
    # one-hot matmuls (exact: one non-zero term per output)
    h0 = start[:, :1].astype(jnp.int32) // bn                   # (BK, 1)
    q = vals.shape[0]
    qs = jax.lax.broadcasted_iota(jnp.int32, (bk, q), 1)
    prec = (jax.lax.Precision.HIGHEST if vals.dtype == f32
            else jax.lax.Precision.DEFAULT)

    def fetch(h):
        return jnp.dot((qs == h).astype(vals.dtype), vals, precision=prec,
                       preferred_element_type=f32)

    off = slot - h0 * bn                              # in [0, 2*BN) on bits
    lane = off % bn
    first = jnp.take_along_axis(fetch(h0), lane, axis=1)
    second = jnp.take_along_axis(fetch(h0 + 1), lane, axis=1)
    w = jnp.where(off < bn, first, second)
    return jnp.where(bits != 0, w, 0.0).astype(dtype)


def _kernel(x_ref, bits_ref, vals_ref, o_ref, acc_ref, *, bk: int,
            n_s: int):
    s = pl.program_id(3)
    tk = bits_ref.shape[0]
    rows = x_ref.shape[0]

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def expand(t):
        return _decompress_tile(bits_ref[t], vals_ref[t], bk, x_ref.dtype)

    def product(t, w_tile, r: slice):
        x = (x_ref[r, t * bk:(t + 1) * bk] if isinstance(t, int)
             else x_ref[r, pl.ds(pl.multiple_of(t * bk, bk), bk)])
        return jnp.dot(x, w_tile, preferred_element_type=jnp.float32)

    # u tiles per iteration, statically unrolled (Mosaic lowers a loop
    # only whole or not unrolled at all)
    u = max(d for d in range(1, min(STRIP_UNROLL, tk) + 1) if tk % d == 0)

    if rows < LANES:
        # a decode-sized block: each tile expanded, then multiplied into
        # an accumulator carried in registers (0.42 us per tile at 16
        # rows on a v5e, 0.43 in the form below)
        def tiles(i, acc):
            for j in range(u):
                t = i * u + j
                acc = acc + product(t, expand(t), slice(None))
            return acc
    else:
        # the iteration's u tiles expanded once, then each 128-row
        # sub-block's accumulator carried through their products (all
        # 512 rows' would be 64 vregs): 0.36 us per tile at 512 rows on a
        # v5e, 0.61 with the accumulator added to in VMEM per tile
        def tiles(i, acc):
            ts = [i * u + j for j in range(u)]
            ws = [expand(t) for t in ts]
            for r0 in range(0, rows, LANES):
                r = slice(r0, min(r0 + LANES, rows))
                sub = acc_ref[r]
                for t, w_tile in zip(ts, ws):
                    sub = sub + product(t, w_tile, r)
                acc_ref[r] = sub
            return acc

    acc = acc_ref[...] if rows < LANES else None
    acc = (tiles(0, acc) if tk == u
           else jax.lax.fori_loop(0, tk // u, tiles, acc))
    if rows < LANES:
        acc_ref[...] = acc

    @pl.when(s == n_s - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _sublane(itemsize: int) -> int:
    """Minimum second-to-minor tile size for an item size (f32: 8,
    bf16: 16)."""
    return {2: 16, 1: 32}.get(itemsize, 8)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _weight_bytes(nw: int, q: int, bn: int, v_itemsize: int) -> int:
    """VMEM of one K-tile's double-buffered bitmap words (nw, bn) int32
    and value rows (q, bn), each padded to its VMEM tiling."""
    return 2 * (_round_up(nw, 8) * bn * 4
                + _round_up(q, _sublane(v_itemsize)) * bn * v_itemsize)


def _out_bytes(rows: int, bn: int) -> int:
    """VMEM of a row block's f32 accumulator and its double-buffered
    output block (counted at f32, the widest output)."""
    return 3 * rows * bn * 4


def strip_tiles(kt: int, bk: int, nw: int, q: int, bn: int, rows: int,
                x_itemsize: int, v_itemsize: int) -> int:
    """K-tiles per grid step (TK): the largest divisor of ``kt`` whose
    blocks — x (rows, TK·bk), bitmap words (TK, nw, bn) and value rows
    (TK, q, bn), double-buffered — fit ``STRIP_VMEM_BUDGET`` beside the row
    block's accumulator and output; 1 when no larger divisor fits."""
    room = STRIP_VMEM_BUDGET - _out_bytes(rows, bn)
    per_tile = 2 * rows * bk * x_itemsize + _weight_bytes(nw, q, bn,
                                                           v_itemsize)
    return max(d for d in range(1, kt + 1)
               if kt % d == 0 and (d == 1 or d * per_tile <= room))


def _row_block(m: int, bk: int, nw: int, q: int, bn: int,
               x_itemsize: int, v_itemsize: int) -> Tuple[int, int]:
    """(RB, row blocks) of a call with M = ``m``: all of M, rounded up to
    the sublane multiple, when a one-tile strip's blocks at that many
    rows fit ``STRIP_VMEM_BUDGET``; else the fewest equal row blocks that
    fit.  At M <= 128 the block is M rounded up, as it always was."""
    sub = _sublane(x_itemsize)
    room = STRIP_VMEM_BUDGET - _weight_bytes(nw, q, bn, v_itemsize)
    per_row = 2 * bk * x_itemsize + _out_bytes(1, bn)
    most = max(sub, room // per_row // sub * sub)
    blocks = -(-_round_up(m, sub) // most)
    return _round_up(-(-m // blocks), sub), blocks


def _tiles(w: BitmapWeight) -> Tuple[int, ...]:
    """(kt, nt, bk, nw, q, bn, value itemsize) of ``w``'s per-matrix
    tiles."""
    kt, nt, q, bn = w.values.shape[-4:]
    return (kt, nt, w.block[0], w.packed_bits.shape[-2], q, bn,
            jnp.dtype(w.values.dtype).itemsize)


def row_blocks(w: BitmapWeight, m: int, x_dtype) -> Tuple[int, int]:
    """(RB, row blocks) the kernel runs ``w`` at with ``m`` rows: each
    row block fetches and expands every tile of ``w`` once."""
    _, _, bk, nw, q, bn, vs = _tiles(w)
    return _row_block(m, bk, nw, q, bn, jnp.dtype(x_dtype).itemsize, vs)


def grid_steps(w: BitmapWeight, m: int, x_dtype) -> int:
    """Grid steps of the ``bitmap_spmm_grouped`` calls that stream every
    matrix held in ``w`` once, at ``m`` rows per matrix.  Leading axes of
    ``w``'s arrays (periods, groups, shards) count one matrix each; the
    tile counts are the per-shard ones its arrays hold."""
    kt, nt, bk, nw, q, bn, vs = _tiles(w)
    rows, blocks = row_blocks(w, m, x_dtype)
    tk = strip_tiles(kt, bk, nw, q, bn, rows, jnp.dtype(x_dtype).itemsize,
                     vs)
    mats = math.prod(w.values.shape[:-4])
    return mats * blocks * nt * (kt // tk)


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def bitmap_spmm_grouped(x: jax.Array, w: BitmapWeight, *,
                        interpret: bool = False,
                        out_dtype=None) -> jax.Array:
    """Per-group ``x[g] @ W_g`` over a group-stacked ``BitmapWeight``.

    x: (G, M, K); W logical shape (K, N) per group, array leaves leading
    with G.  Returns (G, M, N).  One kernel launch covers every group
    (the group is the outermost grid axis), each group streaming only its
    own compressed tiles.  Note the capacity-dispatch MoE caller runs this
    over *all* stored experts; the manifest's per-activated-expert HBM
    accounting models a gather dispatch that skips unselected groups
    (DESIGN_PACKED.md §6, modeled vs executed).

    Any M is accepted: the row block (``row_blocks``) is M rounded up to
    the dtype's sublane multiple, split into equal blocks only where VMEM
    requires; the handful of zero pad rows accumulate zeros and are
    sliced away.
    """
    g, m, k = x.shape
    kk, n = w.shape
    assert k == kk, (x.shape, w.shape)
    assert g == w.values.shape[0], (x.shape, w.values.shape)
    kt, nt, bk, nw, q, bn, vs = _tiles(w)
    out_dtype = out_dtype or x.dtype
    rows, blocks = row_blocks(w, m, x.dtype)
    m_pad = rows * blocks
    if m_pad != m:
        x = jnp.pad(x, ((0, 0), (0, m_pad - m), (0, 0)))
    tk = strip_tiles(kt, bk, nw, q, bn, rows, x.dtype.itemsize, vs)
    n_s = kt // tk

    sq = pl.Squeezed()
    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, n_s=n_s),
        grid=(g, blocks, nt, n_s),
        in_specs=[
            pl.BlockSpec((sq, rows, tk * bk), lambda gi, i, j, s: (gi, i, s)),
            pl.BlockSpec((sq, tk, sq, nw, bn),
                         lambda gi, i, j, s: (gi, s, j, 0, 0)),
            pl.BlockSpec((sq, tk, sq, q, bn),
                         lambda gi, i, j, s: (gi, s, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((sq, rows, bn),
                               lambda gi, i, j, s: (gi, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m_pad, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((rows, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="bitmap_spmm",
    )(x, w.packed_bits, w.values)
    return out[:, :m] if m_pad != m else out


def bitmap_spmm(x: jax.Array, w: BitmapWeight, *, interpret: bool = False,
                out_dtype=None) -> jax.Array:
    """Compute ``x @ W`` with W stored bitmap-compressed.

    x: (M, K); W logical shape (K, N).  Returns (M, N).  The one-group
    case of ``bitmap_spmm_grouped``.
    """
    w1 = BitmapWeight(packed_bits=w.packed_bits[None],
                      values=w.values[None], shape=w.shape, block=w.block)
    return bitmap_spmm_grouped(x[None], w1, interpret=interpret,
                               out_dtype=out_dtype)[0]


def shard_slice(w: BitmapWeight, s: int) -> BitmapWeight:
    """The s-th shard of a sharded ``BitmapWeight`` as a plain per-shard
    ``BitmapWeight`` (shard axis indexed away, per-shard logical shape).

    Column shards hold ``(K, N/S)``, row shards ``(K/S, N)`` — exact
    contiguous slices of the unsharded matrix, so the Pallas kernel
    consumes them as-is and the caller composes outputs (concat over N
    for col, sum over partial products for row).
    """
    assert w.shard is not None
    mode, shards = w.shard
    k, n = w.shape
    shape = (k, n // shards) if mode == "col" else (k // shards, n)

    def take(leaf):
        return jnp.take(leaf, s, axis=leaf.ndim - TILE_ND - 1)

    return BitmapWeight(packed_bits=take(w.packed_bits),
                        values=take(w.values), shape=shape, block=w.block)


def hbm_traffic_model(x_shape: Tuple[int, int], w: BitmapWeight,
                      itemsize: int = 2) -> dict:
    """Analytic HBM bytes of one bitmap_spmm call vs its dense equivalent.

    Weights stream once per row block (``row_blocks``: one at every M
    whose blocks fit VMEM, decode and chunked prefill alike); x once per
    call when a strip holds the whole K (its block index is then constant
    across the column blocks), else once per output-column block;
    outputs are written once.  Used by benchmarks/kernel_bench.py.
    """
    m, k = x_shape
    _, n = w.shape
    kt, nt, bk, nw, q, bn, vs = _tiles(w)
    rows, mt = _row_block(m, bk, nw, q, bn, itemsize, vs)
    x_reads = 1 if strip_tiles(kt, bk, nw, q, bn, rows, itemsize,
                               vs) == kt else nt
    x_bytes = m * k * itemsize * x_reads
    out_bytes = m * n * itemsize
    w_sparse = w.hbm_bytes * mt
    w_dense = w.dense_bytes * mt
    return {
        "sparse_bytes": x_bytes + out_bytes + w_sparse,
        "dense_bytes": x_bytes + out_bytes + w_dense,
        "weight_compression": w.compression,
    }
