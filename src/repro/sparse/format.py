"""Framework-level sparse weight containers (TPU adaptation of Fig. 1).

Two storage formats, both jax pytrees so they flow through jit/pjit:

* ``BitmapWeight`` — the paper's bitmap format at VMEM-tile granularity:
  per (BK×BN) tile a bitmap (1 bit/element, packed 32 rows to an int32
  word) and the tile's non-zero values, row-major, in BN-wide rows.  The
  EIM re-sort (§II-C: a value's slot is the number of set bits before it)
  runs inside the kernel as prefix-count matmuls on the bitmap, so no
  per-row offsets are stored.
  HBM bytes ≈ density·data + 1/8·bitmap ⇒ ~3.2× traffic cut at 75 % sparsity.

* ``BlockSparseWeight`` — coarse-grain: all-zero (BK×BN) blocks are dropped
  entirely; per output-column-block a compressed list of surviving K-block
  indices (CSR-of-blocks = EIM at block granularity, consumed by the kernel
  through scalar prefetch).

Both formats enforce their structure at pack time (top-magnitude within the
budget), mirroring how the paper prunes to a target sparsity.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


#: bitmap rows packed into one int32 word (bit ``r % 32`` of word
#: ``r // 32`` in each column)
WORD_ROWS = 32


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BitmapWeight:
    """Bitmap-compressed (K, N) weight, tiled (BK, BN).

    ``packed_bits`` and ``values`` are the weight's only form: the Pallas
    kernel streams them, and the xla dispatch (``ref.bitmap_spmm_ref``)
    decodes them through ``unpack_bitmap`` inside the step.
    """

    packed_bits: jax.Array   # (KT, NT, ceil(BK/32), BN) int32 bit words
    values: jax.Array        # (KT, NT, Q, BN) dtype, row-major packed
    shape: Tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    block: Tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    #: sharded layout marker: ``("col"|"row", S)`` when every array leaf
    #: carries an explicit shard axis (extent S) immediately before its
    #: tile dims — ``shard_bitmap`` below.  ``shape``/``block`` stay the
    #: full logical geometry; per-shard tiles are ``block``-sized slices
    #: of an N- (col) or K- (row) contiguous range.
    shard: Optional[Tuple[str, int]] = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def budget(self) -> int:
        """Value slots per tile (the max tile non-zero count, rounded up
        to whole BN-wide rows: a tile row's at most BN values then span
        at most two stored rows, which is what the kernel gathers from)."""
        return self.values.shape[-2] * self.values.shape[-1]

    @property
    def hbm_bytes(self) -> int:
        return (self.packed_bits.size * self.packed_bits.dtype.itemsize
                + self.values.size * self.values.dtype.itemsize)

    @property
    def dense_bytes(self) -> int:
        # stacked weights (pack_bitmap_stacked: leading P axis;
        # pack_bitmap_experts: leading (P, E) axes) carry extra leading
        # dims on the arrays while `shape` stays per-matrix — count them
        stacks = math.prod(self.values.shape[:-4])
        if self.shard is not None:
            # the explicit shard axis inflates the leading dims but the
            # S shards together hold exactly one logical matrix
            stacks //= self.shard[1]
        return (stacks * self.shape[0] * self.shape[1]
                * self.values.dtype.itemsize)

    @property
    def compression(self) -> float:
        return self.dense_bytes / self.hbm_bytes


def _pack_words(bits: np.ndarray) -> np.ndarray:
    """(..., BK, BN) bool -> (..., ceil(BK/32), BN) int32 bit words."""
    bk = bits.shape[-2]
    nw = -(-bk // WORD_ROWS)
    pad = [(0, 0)] * (bits.ndim - 2) + [(0, nw * WORD_ROWS - bk), (0, 0)]
    b = np.pad(bits, pad).reshape(bits.shape[:-2] + (nw, WORD_ROWS,
                                                     bits.shape[-1]))
    shifts = np.arange(WORD_ROWS, dtype=np.uint32)[:, None]
    words = (b.astype(np.uint32) << shifts).sum(axis=-2, dtype=np.uint32)
    return words.view(np.int32)


def pack_bitmap(w, block: Tuple[int, int] = (128, 128),
                density_budget: float | None = None,
                budget: int | None = None) -> BitmapWeight:
    """Pack a dense (K, N) array (zeros = pruned) into BitmapWeight.

    If a tile holds more non-zeros than ``budget = ceil(BK·BN·density_budget)``
    the smallest-magnitude surplus is re-pruned (top-k per tile), as recorded
    in DESIGN.md.  Default budget = measured max tile density.  An explicit
    ``budget`` (≥ the max tile non-zero count — packing is then lossless)
    lets callers share one value-slot budget across several packs, e.g. the
    period-stacked pack below.  The stored slot count rounds the budget up
    to whole BN-wide rows.
    """
    w = np.asarray(w)
    k, n = w.shape
    bk, bn = block
    assert k % bk == 0 and n % bn == 0, (w.shape, block)
    assert bn % 8 == 0
    kt, nt = k // bk, n // bn
    tiles = w.reshape(kt, bk, nt, bn).transpose(0, 2, 1, 3)  # (KT,NT,BK,BN)

    bits = tiles != 0
    per_tile = bits.reshape(kt, nt, -1).sum(-1)
    if budget is not None:
        assert density_budget is None
        assert budget >= int(per_tile.max()), (budget, int(per_tile.max()))
    elif density_budget is None:
        budget = int(per_tile.max())
    else:
        budget = math.ceil(bk * bn * density_budget)
        over = per_tile > budget
        if over.any():
            flat = np.abs(tiles.reshape(kt, nt, -1))
            # keep the `budget` largest magnitudes per overflowing tile
            kth = np.partition(flat, flat.shape[-1] - budget, axis=-1)[
                ..., flat.shape[-1] - budget]
            keep = flat >= kth[..., None]
            keep &= flat > 0
            tiles = tiles * keep.reshape(tiles.shape)
            bits = tiles != 0
            per_tile = bits.reshape(kt, nt, -1).sum(-1)
    q = max(1, -(-budget // bn))

    # a tile's non-zeros in row-major order fill its slots 0, 1, ...: the
    # slot of element (r, c) is the count of set bits before it in the tile
    nz = tiles[bits]                           # every tile's, tile-major
    counts = per_tile.reshape(-1)
    starts = np.cumsum(counts) - counts
    tile_of = np.repeat(np.arange(kt * nt), counts)
    slot = np.arange(nz.size) - np.repeat(starts, counts)
    values = np.zeros((kt * nt, q * bn), w.dtype)
    values[tile_of, slot] = nz

    return BitmapWeight(
        packed_bits=jnp.asarray(_pack_words(bits)),
        values=jnp.asarray(values.reshape(kt, nt, q, bn)),
        shape=(k, n), block=(bk, bn))


def unpack_bitmap(bw: BitmapWeight) -> jax.Array:
    """Pure-jnp decompression oracle (the EIM re-sort the kernel runs:
    a value's slot is the count of set bits before it in its tile)."""
    kt, nt, nw, bn = bw.packed_bits.shape
    bk = bw.block[0]
    shifts = jnp.arange(WORD_ROWS, dtype=jnp.int32)[:, None]
    bits = (bw.packed_bits[:, :, :, None, :] >> shifts) & 1  # (..,NW,32,BN)
    bits = bits.reshape(kt, nt, nw * WORD_ROWS, bn)[:, :, :bk]
    flat = bits.reshape(kt, nt, bk * bn)
    slot = jnp.clip(jnp.cumsum(flat, -1) - 1, 0, bw.budget - 1)
    vals = jnp.take_along_axis(bw.values.reshape(kt, nt, -1), slot, axis=-1)
    dense_tiles = jnp.where(flat != 0, vals, 0).reshape(kt, nt, bk, bn)
    return dense_tiles.transpose(0, 2, 1, 3).reshape(bw.shape)


def pack_bitmap_stacked(w, block: Tuple[int, int]) -> BitmapWeight:
    """Pack a period-stacked (P, K, N) tensor into one ``BitmapWeight``
    whose array leaves carry a leading P axis.

    All periods share the tile ``block`` and one value-slot ``budget``
    (the max tile non-zero count across periods), so ``lax.scan`` over the
    stacked container yields a plain per-period ``BitmapWeight`` each
    iteration — exactly how the serving decode step consumes it.  Packing
    is lossless: no re-pruning happens at pack time.
    """
    w = np.asarray(w)
    assert w.ndim == 3, w.shape
    p, k, n = w.shape
    bk, bn = block
    assert k % bk == 0 and n % bn == 0, (w.shape, block)
    kt, nt = k // bk, n // bn
    tile_nnz = (w.reshape(p, kt, bk, nt, bn) != 0).transpose(
        0, 1, 3, 2, 4).reshape(p, kt, nt, -1).sum(-1)
    budget = max(1, int(tile_nnz.max()))
    per = [pack_bitmap(w[i], block=block, budget=budget) for i in range(p)]
    return BitmapWeight(
        packed_bits=jnp.stack([q.packed_bits for q in per]),
        values=jnp.stack([q.values for q in per]),
        shape=(k, n), block=block)


def unpack_bitmap_stacked(bw: BitmapWeight) -> jax.Array:
    """Dense oracle for a stacked ``BitmapWeight``: recurses over every
    leading stack axis (one for period-stacked tensors, two for the
    (P, E) expert layout), returning ``(*stack_axes, K, N)``."""
    if bw.values.ndim == 4:
        return unpack_bitmap(bw)
    return jnp.stack([
        unpack_bitmap_stacked(BitmapWeight(
            packed_bits=bw.packed_bits[i], values=bw.values[i],
            shape=bw.shape, block=bw.block))
        for i in range(bw.packed_bits.shape[0])])


def pack_bitmap_experts(w, block: Tuple[int, int]) -> BitmapWeight:
    """Pack a period-stacked expert stack (P, E, K, N) into one
    ``BitmapWeight`` whose array leaves carry leading (P, E) axes.

    The expert analogue of ``pack_bitmap_stacked``: every (period,
    expert) matrix shares the tile ``block`` and one value-slot
    ``budget`` (the max tile non-zero count across the whole stack), so
    the serve-time ``lax.scan`` over periods yields an (E, ...)-leading
    ``BitmapWeight`` each iteration whose per-expert slices the grouped
    kernel dispatch (``kernels/ops.bitmap_spmm_grouped``) consumes.
    Packing is lossless — no re-pruning happens at pack time.

    Also used for non-router group stacks with the same dataflow (e.g.
    RWKV6's 5-way low-rank lerp stack ``mix_B``): any (P, G, K, N)
    tensor consumed as G independent (K, N) GEMMs packs this way.
    """
    w = np.asarray(w)
    assert w.ndim == 4, w.shape
    p, e, k, n = w.shape
    flat = pack_bitmap_stacked(w.reshape(p * e, k, n), block=block)
    return BitmapWeight(
        packed_bits=flat.packed_bits.reshape(
            (p, e) + flat.packed_bits.shape[1:]),
        values=flat.values.reshape((p, e) + flat.values.shape[1:]),
        shape=(k, n), block=block)


def unpack_bitmap_experts(bw: BitmapWeight) -> jax.Array:
    """Dense (P, E, K, N) oracle for an expert-stacked ``BitmapWeight``."""
    return unpack_bitmap_stacked(bw)


# --------------------------------------------------------------------------
# Sharded layout: EIE-style partitioning of the compressed stream.  The N
# (column-parallel) or K (row-parallel) tile axis is split into S
# contiguous shard ranges and re-exposed as an explicit shard axis placed
# immediately before each leaf's tile dims, so a single PartitionSpec axis
# ('model' at that position) makes every shard's bitmap+values
# device-local.  ``shape``/``block`` keep the full logical geometry.

#: trailing per-tile dims of each array leaf, (KT, NT, ·, BN): the shard
#: axis sits just before these; leading stack axes (period P, expert E)
#: come first
TILE_ND = 4

#: offset from ndim of the tile axis being split: col splits NT, row KT
_SHARD_OFF = {"col": TILE_ND - 1, "row": TILE_ND}


def _split_leaf(leaf, mode: str, shards: int):
    """Split the sharded tile axis into ``shards`` contiguous ranges and
    move the new shard axis to just before the tile dims."""
    nd = leaf.ndim
    ax = nd - _SHARD_OFF[mode]
    size = leaf.shape[ax]
    assert size % shards == 0, (leaf.shape, ax, shards)
    r = leaf.reshape(leaf.shape[:ax] + (shards, size // shards)
                     + leaf.shape[ax + 1:])
    return jnp.moveaxis(r, ax, nd - TILE_ND)


def _merge_leaf(leaf, mode: str):
    """Inverse of ``_split_leaf``: fold the shard axis back into the tile
    axis it was split from (shard ranges are contiguous, so this is a
    pure reshape after the moveaxis)."""
    n = leaf.ndim
    j = n - _SHARD_OFF[mode] - 1
    m = jnp.moveaxis(leaf, n - TILE_ND - 1, j)
    return m.reshape(m.shape[:j] + (m.shape[j] * m.shape[j + 1],)
                     + m.shape[j + 2:])


def shard_bitmap(bw: BitmapWeight, shards: int, mode: str) -> BitmapWeight:
    """Re-layout a packed ``BitmapWeight`` with an explicit shard axis.

    ``mode="col"`` splits the output-column tile axis (NT) — each shard
    owns a contiguous N range (wq/wk/wv/w_gate/w_up, vocab-split head);
    ``mode="row"`` splits the contraction tile axis (KT) — each shard
    owns a K range and partial products sum (wo/w_down).  Lossless: the
    per-shard leaves are exact slices of the unsharded pack.
    """
    assert mode in ("col", "row"), mode
    assert bw.shard is None, bw.shard
    if shards == 1:
        return bw
    return dataclasses.replace(
        bw,
        packed_bits=_split_leaf(bw.packed_bits, mode, shards),
        values=_split_leaf(bw.values, mode, shards),
        shard=(mode, shards))


def unshard_bitmap(bw: BitmapWeight) -> BitmapWeight:
    """Fold the explicit shard axis back in — the exact unsharded pack."""
    if bw.shard is None:
        return bw
    mode, _ = bw.shard
    return dataclasses.replace(
        bw,
        packed_bits=_merge_leaf(bw.packed_bits, mode),
        values=_merge_leaf(bw.values, mode),
        shard=None)


def gather_bitmap(bw: BitmapWeight, axis_name: str) -> BitmapWeight:
    """Inside ``shard_map``: all-gather each device's shard slice over
    ``axis_name`` and fold the shard axis away, yielding the full
    unsharded ``BitmapWeight`` (value-identical to the single-device
    pack, so downstream compute needs no per-shard composition)."""
    if bw.shard is None:
        return bw
    mode, _ = bw.shard

    def g(leaf):
        ax = leaf.ndim - TILE_ND - 1
        return _merge_leaf(
            jax.lax.all_gather(leaf, axis_name, axis=ax, tiled=True), mode)

    return dataclasses.replace(
        bw,
        packed_bits=g(bw.packed_bits),
        values=g(bw.values),
        shard=None)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BlockSparseWeight:
    """Block-sparse (K, N) weight: zero (BK×BN) blocks dropped."""

    values: jax.Array     # (NT, SMAX, BK, BN) surviving blocks per col-block
    kidx: jax.Array       # (NT, SMAX) int32 — source K-block index (pad: 0)
    nnzb: jax.Array       # (NT,) int32 — number of valid blocks per col-block
    shape: Tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    block: Tuple[int, int] = dataclasses.field(metadata=dict(static=True))

    @property
    def smax(self) -> int:
        return self.values.shape[1]

    @property
    def hbm_bytes(self) -> int:
        return (self.values.size * self.values.dtype.itemsize
                + self.kidx.size * 4 + self.nnzb.size * 4)

    @property
    def density(self) -> float:
        kt = self.shape[0] // self.block[0]
        return float(np.asarray(self.nnzb).sum()) / (kt * self.kidx.shape[0])


def pack_block_sparse(w, block: Tuple[int, int] = (128, 128)
                      ) -> BlockSparseWeight:
    w = np.asarray(w)
    k, n = w.shape
    bk, bn = block
    assert k % bk == 0 and n % bn == 0
    kt, nt = k // bk, n // bn
    tiles = w.reshape(kt, bk, nt, bn).transpose(2, 0, 1, 3)  # (NT,KT,BK,BN)
    alive = (tiles != 0).any((-1, -2))                        # (NT, KT)
    nnzb = alive.sum(-1).astype(np.int32)
    smax = max(int(nnzb.max()), 1)
    values = np.zeros((nt, smax, bk, bn), w.dtype)
    kidx = np.zeros((nt, smax), np.int32)
    for j in range(nt):
        ks = np.nonzero(alive[j])[0]
        values[j, :len(ks)] = tiles[j, ks]
        kidx[j, :len(ks)] = ks
    return BlockSparseWeight(
        values=jnp.asarray(values), kidx=jnp.asarray(kidx),
        nnzb=jnp.asarray(nnzb), shape=(k, n), block=(bk, bn))


def unpack_block_sparse(bw: BlockSparseWeight) -> jax.Array:
    nt, smax, bk, bn = bw.values.shape
    kt = bw.shape[0] // bk
    dense = jnp.zeros((nt, kt, bk, bn), bw.values.dtype)
    valid = jnp.arange(smax)[None, :] < bw.nnzb[:, None]
    vals = jnp.where(valid[..., None, None], bw.values, 0)
    j = jnp.repeat(jnp.arange(nt), smax)
    dense = dense.at[j, bw.kidx.reshape(-1)].add(
        vals.reshape(nt * smax, bk, bn))
    return dense.transpose(1, 2, 0, 3).reshape(bw.shape)
