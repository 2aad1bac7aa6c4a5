"""Whole-stack packed-model subsystem: prune once, pack once, stream
bitmap-compressed on every decode step.

``pack_model`` walks the params tree (the ``param_shapes`` inventory,
stacked over periods) and packs every serve-time GEMM operand into one
``BitmapWeight`` per tensor, choosing the largest valid ``(BK, BN)``
tile per shape:

* **period-stacked 2-D projections** (``pack_bitmap_stacked``):
  attention ``wq/wk/wv/wo``, MLP ``w_gate/w_up/w_down``, the MoE
  ``router``, mamba ``in/x/dt/out`` projections, rwkv
  ``w_r/w_k/w_v/w_g/w_o``, ``decay_A/decay_B``, ``mix_A`` and the
  rwkv channel-mix ``cm_k/cm_v/cm_r``;
* **group-stacked expert tensors** (``pack_bitmap_experts``): MoE
  ``w_gate/w_up/w_down`` — a ``(P, E, D, F)`` stack whose per-expert
  slices dispatch through ``kernels/ops.bitmap_spmm_grouped`` — and
  rwkv's 5-way lerp stack ``mix_B``, which shares the layout.

The result is a pytree mirroring ``params["blocks"]`` (``BitmapWeight``
leaves where packed, ``None`` where dense) that threads through
``build_serve_step`` → ``decode_step`` → ``decode_hidden`` (and the
chunked-prefill path ``build_prefill_step`` → ``prefill_hidden``) into
``layers.matmul_or_bitmap`` / ``layers.expert_matmul_or_bitmap`` and the
ssm decode cells, so the per-step matmuls dispatch via
``kernels/ops.bitmap_spmm`` / ``bitmap_spmm_grouped`` instead of dense
``@``.

Invariants (DESIGN_PACKED.md has the full subsystem doc):

* **Packing is lossless** — the per-tensor value-slot budget equals the
  max tile non-zero count, so the packed stream is numerically identical
  to dense dispatch of the same (pruned) weights; compression comes only
  from upstream pruning.
* **Every fallback carries a reason** — a tensor that cannot pack is
  served dense with the reason recorded in the manifest (no valid tile,
  unexpected rank, not a GEMM operand, …); nothing silently degrades.
* **A packed tensor has one form** — ``packed_bits`` and ``values``,
  which every dispatch reads and ``hbm_bytes`` counts.
* **Router-gated expert stacks account per *activated* expert** — a
  gather-dispatch serving engine streams only the experts the router
  selected, so ``stream_report(activated_experts=...)`` scales those
  entries by ``min(E, activated) / E`` (the engine passes
  ``num_slots × top_k``, the per-step worst case) whether the stack
  packed or fell back; always-active group stacks (rwkv ``mix_B``) and
  everything else count in full.

This is the paper's regime end-to-end: EIE runs *every* FC layer from
compressed storage; here the entire decode stack — MoE expert stacks
and SSM mixers included — streams the bitmap format.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.sparse.format import (BitmapWeight, pack_bitmap_experts,
                                 pack_bitmap_stacked, shard_bitmap)

# (component, tensor) pairs with a compressed dispatch path in the
# decode step.  2-D entries are period-stacked projections; GROUPED
# entries are (P, G, K, N) stacks dispatched per group.  Everything else
# records a fallback reason in the manifest.
DISPATCHABLE_2D = {
    ("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
    ("mlp", "w_gate"), ("mlp", "w_up"), ("mlp", "w_down"),
    ("moe", "router"),
    ("mamba", "in_proj"), ("mamba", "x_proj"), ("mamba", "dt_proj"),
    ("mamba", "out_proj"),
    ("rwkv", "w_r"), ("rwkv", "w_k"), ("rwkv", "w_v"), ("rwkv", "w_g"),
    ("rwkv", "w_o"), ("rwkv", "decay_A"), ("rwkv", "decay_B"),
    ("rwkv", "mix_A"),
    ("rwkv_cm", "cm_k"), ("rwkv_cm", "cm_v"), ("rwkv_cm", "cm_r"),
}
DISPATCHABLE_GROUPED = {
    ("moe", "w_gate"), ("moe", "w_up"), ("moe", "w_down"),
    ("rwkv", "mix_B"),
}
# router-gated expert stacks: per-step traffic scales with *activated*
# experts (rwkv's mix_B is group-stacked but always fully active)
ROUTED_EXPERT = {("moe", "w_gate"), ("moe", "w_up"), ("moe", "w_down")}


def activated_scale(experts: int, activated: Optional[int]) -> float:
    """The accounting rule, single-sourced: router-gated expert stacks
    stream ``min(E, activated)`` of their ``E`` stored experts per step
    (``experts == 0`` or ``activated is None`` ⇒ no scaling)."""
    if not experts or activated is None:
        return 1.0
    return min(experts, activated) / experts


def choose_block(k: int, n: int, cap: int = 128
                 ) -> Optional[Tuple[int, int]]:
    """Largest (BK, BN) bitmap tile dividing (k, n); BN % 8 == 0."""
    bk = next((d for d in range(min(k, cap), 0, -1) if k % d == 0), None)
    bn = next((d for d in range(min(n, cap), 0, -1)
               if n % d == 0 and d % 8 == 0), None)
    if bk is None or bn is None:
        return None
    return bk, bn


def block_reason(k: int, n: int, block: Optional[Tuple[int, int]],
                 chip: bool) -> str:
    """Why a (k, n) operand tiled ``block`` cannot stream packed ("" when
    it can): no tile at all, or — for the compiled TPU kernel (``chip``)
    — a tile the chip's tiling rules refuse."""
    if block is None:
        return f"no (BK, BN) tile divides ({k}, {n}) with BN % 8"
    if chip:
        from repro.kernels.bitmap_spmm import tile_fits_chip
        why = tile_fits_chip(k, n, block)
        if why:
            return f"tile {block} of ({k}, {n}) does not fit the TPU: {why}"
    return ""


@dataclasses.dataclass
class PackEntry:
    """Manifest row: one tensor's pack decision + modeled per-step bytes.

    ``sparse_bytes``/``dense_bytes`` are *stored-stack* totals (all
    periods, all experts); the per-activated-expert scaling happens in
    ``PackedModel.stream_report``.  ``layout`` is ``"stacked"``
    (period-stacked 2-D), ``"grouped"`` (expert/group stack) or
    ``"dense"`` (fallback).  ``experts`` is the stored expert count for
    router-gated stacks (0 otherwise).
    """

    path: str
    shape: Tuple[int, ...]
    packed: bool
    reason: str                      # "" when packed, else why dense
    block: Optional[Tuple[int, int]]
    sparsity: float                  # measured zero fraction
    sparse_bytes: int                # streamed per step on the chosen path
    dense_bytes: int
    layout: str = "dense"
    experts: int = 0
    #: sharded layout: ("col"|"row", S) when the packed leaf carries an
    #: explicit shard axis; None for replicated/unsharded tensors
    shard: Optional[Tuple[str, int]] = None
    #: why a TP-ruled tensor could not shard (stored replicated); ""
    #: when sharded or when no rule applies
    shard_reason: str = ""


@dataclasses.dataclass
class PackedModel:
    """The packed pytree + its manifest and aggregate traffic model."""

    blocks: Dict                     # mirrors params["blocks"]
    manifest: List[PackEntry]
    shards: int = 1                  # model-axis shard count at pack time

    @property
    def packed_entries(self) -> List[PackEntry]:
        return [e for e in self.manifest if e.packed]

    @property
    def fallback_entries(self) -> List[PackEntry]:
        return [e for e in self.manifest if not e.packed]

    def leaves(self) -> List[Tuple[str, BitmapWeight]]:
        """Every currently-packed ``(path, BitmapWeight)`` leaf, manifest
        order — the fault injector's target list and the integrity
        auditor's checksum domain."""
        out = []
        for bname, bdict in self.blocks.items():
            for comp, tensors in bdict.items():
                for name, bw in tensors.items():
                    if bw is not None:
                        out.append((f"blocks/{bname}/{comp}/{name}", bw))
        return out

    def replace_leaf(self, path: str, bw: Optional[BitmapWeight]) -> None:
        """Swap the leaf at ``path`` (fault injection writes a corrupted
        copy; quarantine writes ``None``)."""
        _, bname, comp, name = path.split("/")
        assert name in self.blocks[bname][comp], path
        self.blocks[bname][comp][name] = bw

    def quarantine(self, path: str, reason: str) -> bool:
        """Serve ``path`` dense from now on: the leaf becomes ``None``
        (``matmul_or_bitmap`` dispatches the dense params tensor) and
        the manifest entry flips to a recorded fallback carrying
        ``reason``, so ``stream_report()`` and the fallback snapshot
        reflect the quarantine.  Returns False if already dense."""
        _, bname, comp, name = path.split("/")
        if self.blocks.get(bname, {}).get(comp, {}).get(name) is None:
            return False
        self.blocks[bname][comp][name] = None
        for e in self.manifest:
            if e.path == path:
                e.packed = False
                e.reason = reason
                e.layout = "dense"
                e.block = None
                e.sparse_bytes = e.dense_bytes
                e.shard = None
        return True

    def register_metrics(self, reg) -> None:
        reg.gauge("stream.packed_tensors",
                  lambda: len(self.packed_entries))
        reg.gauge("stream.fallback_tensors",
                  lambda: len(self.fallback_entries))

    def stream_report(self, activated_experts: Optional[int] = None) -> Dict:
        """Modeled per-step weight-HBM bytes across the stack (no head —
        the engine adds its head term on top).

        ``activated_experts`` (the engine passes ``num_slots × top_k``):
        router-gated expert stacks stream only the experts the router
        selected, so their stored-stack bytes scale by
        ``min(E, activated) / E`` — on the sparse *and* the dense side,
        since a gather-dispatch dense baseline also fetches only
        activated experts; the reduction therefore isolates the format,
        not the gating (accounting rule in DESIGN_PACKED.md).
        """
        def step_bytes(e: PackEntry, attr: str) -> int:
            return int(round(getattr(e, attr)
                             * activated_scale(e.experts,
                                               activated_experts)))

        sparse = sum(step_bytes(e, "sparse_bytes") for e in self.manifest)
        dense = sum(step_bytes(e, "dense_bytes") for e in self.manifest)
        dev_sparse = sum(
            entry_device_bytes(e, "sparse_bytes", activated_experts)
            for e in self.manifest)
        dev_dense = sum(
            entry_device_bytes(e, "dense_bytes", activated_experts)
            for e in self.manifest)
        return {
            "sparse_bytes_per_step": sparse,
            "dense_bytes_per_step": dense,
            "reduction": dense / sparse if sparse else 1.0,
            "packed_tensors": len(self.packed_entries),
            "fallback_tensors": len(self.fallback_entries),
            "activated_experts": activated_experts,
            "fallbacks": {e.path: e.reason for e in self.fallback_entries},
            "shards": self.shards,
            "device_sparse_bytes_per_step": dev_sparse,
            "device_dense_bytes_per_step": dev_dense,
            "shard_fallbacks": {e.path: e.shard_reason
                                for e in self.manifest if e.shard_reason},
        }


def entry_device_bytes(e: PackEntry, attr: str,
                       activated: Optional[int]) -> int:
    """Per-device per-step bytes for one manifest row: the exact
    aggregate accounting (``int(round(bytes × activated_scale))``)
    divided by the tensor's shard count."""
    b = int(round(getattr(e, attr) * activated_scale(e.experts, activated)))
    return b // e.shard[1] if e.shard is not None else b


def _shard_block(comp: str, name: str, k: int, n: int, cap: int,
                 shards: int) -> Tuple[Optional[Tuple[int, int]],
                                       Optional[Tuple[str, int]], str]:
    """Choose the (block, shard, shard_reason) for one tensor.

    With ``shards == 1`` or no TP rule for (comp, name), this is plain
    ``choose_block`` with no shard.  Otherwise the tile is chosen against
    the *per-shard* slice — ``(k, n/S)`` column-parallel, ``(k/S, n)``
    row-parallel — so every shard's range is whole tiles; a dim the
    shard count doesn't divide (or with no valid per-shard tile) stays
    replicated with a typed reason instead of failing the pack.
    """
    from repro.launch.sharding import packed_mode
    mode = shards > 1 and packed_mode(comp, name) or None
    if not mode:
        return choose_block(k, n, cap), None, ""
    dim, dim_name = (n, "N") if mode == "col" else (k, "K")
    if dim % shards != 0:
        return choose_block(k, n, cap), None, (
            f"shard: {dim_name}={dim} not divisible by {shards} shards; "
            f"stored replicated")
    block = (choose_block(k, n // shards, cap) if mode == "col"
             else choose_block(k // shards, n, cap))
    if block is None:
        return choose_block(k, n, cap), None, (
            f"shard: no (BK, BN) tile fits the per-shard "
            f"{'column' if mode == 'col' else 'row'} slice; "
            f"stored replicated")
    return block, (mode, shards), ""


def _local_dims(k: int, n: int, shard: Optional[Tuple[str, int]]
                ) -> Tuple[int, int]:
    """The (K, N) extent one shard's kernel call sees."""
    if shard is None:
        return k, n
    mode, s = shard
    return (k, n // s) if mode == "col" else (k // s, n)


def _pack_leaf(path: str, comp: str, name: str, w, cap: int,
               shards: int = 1, chip: bool = False
               ) -> Tuple[PackEntry, Optional[BitmapWeight]]:
    arr = np.asarray(w)
    dense_bytes = arr.size * arr.dtype.itemsize
    sparsity = 1.0 - np.count_nonzero(arr) / max(arr.size, 1)
    key = (comp, name)
    # the activated-expert accounting applies to router-gated stacks
    # whether they pack or fall back — a gather-dispatch dense baseline
    # also fetches only the selected experts
    routed = (arr.shape[1] if key in ROUTED_EXPERT and arr.ndim == 4
              else 0)

    def fallback(reason: str) -> Tuple[PackEntry, None]:
        return PackEntry(path=path, shape=arr.shape, packed=False,
                         reason=reason, block=None, sparsity=sparsity,
                         sparse_bytes=dense_bytes,
                         dense_bytes=dense_bytes, experts=routed), None
    if key in DISPATCHABLE_GROUPED:
        if arr.ndim != 4:            # (P, G, K, N) = period × group stack
            return fallback(f"group stack with unexpected rank "
                            f"(ndim={arr.ndim}, want 4)")
        _, g, k, n = arr.shape
        block, shard, shard_reason = _shard_block(comp, name, k, n, cap,
                                                  shards)
        reason = block_reason(*_local_dims(k, n, shard), block, chip)
        if reason:
            return fallback(reason)
        bw = pack_bitmap_experts(arr, block=block)
        if shard is not None:
            bw = shard_bitmap(bw, shard[1], shard[0])
        entry = PackEntry(path=path, shape=arr.shape, packed=True, reason="",
                          block=block, sparsity=sparsity,
                          sparse_bytes=bw.hbm_bytes, dense_bytes=dense_bytes,
                          layout="grouped", experts=routed, shard=shard,
                          shard_reason=shard_reason)
        return entry, bw
    if key not in DISPATCHABLE_2D:
        # every GEMM operand of the decode step is listed above; the rest
        # are elementwise/state/conv tensors with no matmul to compress
        return fallback("not a GEMM operand (elementwise/state/conv tensor)")
    if arr.ndim != 3:                # (P, K, N) = period-stacked projection
        return fallback(f"not a 2-D projection (ndim={arr.ndim - 1})")
    _, k, n = arr.shape
    block, shard, shard_reason = _shard_block(comp, name, k, n, cap, shards)
    reason = block_reason(*_local_dims(k, n, shard), block, chip)
    if reason:
        return fallback(reason)
    bw = pack_bitmap_stacked(arr, block=block)
    if shard is not None:
        bw = shard_bitmap(bw, shard[1], shard[0])
    entry = PackEntry(path=path, shape=arr.shape, packed=True, reason="",
                      block=block, sparsity=sparsity,
                      sparse_bytes=bw.hbm_bytes, dense_bytes=dense_bytes,
                      layout="stacked", shard=shard,
                      shard_reason=shard_reason)
    return entry, bw


def pack_model(params: Dict, cap: int = 128, shards: int = 1,
               chip: bool = False) -> PackedModel:
    """Pack every dispatchable serve-time GEMM operand of ``params``.

    Packing is lossless (per-tensor budget = max tile non-zero count), so
    the packed stream is numerically identical to dense dispatch — the
    compression comes from whatever pruning already happened upstream
    (``global_l1_prune`` in the engine).

    ``chip`` (the engine sets it when the compiled TPU kernel serves)
    sends every tensor whose tile the chip's tiling rules refuse
    (``kernels.bitmap_spmm.tile_fits_chip``) to a typed dense fallback.

    ``shards > 1`` packs every TP-ruled tensor (``launch.sharding``'s
    PACKED_COL/PACKED_ROW) with an explicit shard axis so each
    model-axis device owns a local bitmap+values slice; tensors whose
    sharded dim the count doesn't divide stay replicated with a typed
    ``shard_reason`` in the manifest.
    """
    manifest: List[PackEntry] = []
    packed_blocks: Dict = {}
    for bname, bdict in params["blocks"].items():
        packed_b: Dict = {}
        for comp, tensors in bdict.items():
            packed_c: Dict = {}
            for name, w in tensors.items():
                path = f"blocks/{bname}/{comp}/{name}"
                entry, bw = _pack_leaf(path, comp, name, w, cap, shards,
                                       chip)
                manifest.append(entry)
                packed_c[name] = bw
            packed_b[comp] = packed_c
        packed_blocks[bname] = packed_b
    return PackedModel(blocks=packed_blocks, manifest=manifest,
                       shards=shards)
