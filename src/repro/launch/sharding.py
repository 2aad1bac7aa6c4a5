"""Sharding rules: parameter/optimizer/batch/cache PartitionSpecs.

Policy (DESIGN.md §3):
  * params — Megatron-style TP over the "model" axis: column-parallel
    in-projections, row-parallel out-projections, vocab-sharded embedding
    and LM head, expert-FFN dim sharded (EP-compatible for divisible expert
    counts); small/vector params replicated;
  * batch — sharded over ("pod", "data");
  * decode caches — batch-sharded; the 500k single-sequence cells shard the
    KV cache over sequence instead (SP) since batch=1 cannot shard;
  * any dim not divisible by its axis extent falls back to replication
    (granite's 40 experts on a 16-way axis, rwkv's 40 heads, ...).

Rules are name-based over the param tree paths; block leaves carry the
leading period-stack dim, handled by spec prepending.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.model import cache_structs, param_structs
from repro.sparse.format import TILE_ND, BitmapWeight

# (regex over path, spec over the *unstacked* leaf dims)
_RULES = [
    (r"embed$", ("model", None)),
    (r"lm_head$", (None, "model")),
    (r"\['w[qkv]'\]$", (None, "model")),
    (r"\['wo'\]$", ("model", None)),
    (r"(w_gate|w_up|cm_k)'\]$", (None, "model")),
    (r"(w_down|cm_v)'\]$", ("model", None)),
    (r"router'\]$", (None, None)),
    (r"in_proj'\]$", (None, "model")),
    (r"(conv_w|x_proj|A_log|out_proj)'\]$", ("model", None)),
    (r"(conv_b|dt_bias)'\]$", ("model",)),
    (r"\['D'\]$", ("model",)),
    (r"dt_proj'\]$", (None, "model")),
    (r"w_[rkvg]'\]$", (None, "model")),
    (r"w_o'\]$", ("model", None)),
]

_MOE_RULES = [
    (r"moe'\]\['w_(gate|up)'\]$", (None, None, "model")),
    # w_down shards the OUTPUT dim (§Perf iter 4): contracting over the
    # sharded F dim makes GSPMD all-reduce the (B, E·C, D) capacity buffer
    # (4 GB/layer for moonshot); with D sharded the combine stays local and
    # only the (B, S, D) output is gathered at the residual.
    (r"moe'\]\['w_down'\]$", (None, None, "model")),
]

# expert parallelism: shard the expert dim over "model" instead. Measured
# 1.5× fewer HBM bytes and 1.3× less wire than TP-inside-expert on
# moonshot train_4k (§Perf iter 5), so EP is the default whenever the
# expert count divides the model axis (moonshot 64/16 ✓; granite 40/16 ✗
# falls back to the iter-4 TP scheme). REPRO_MOE_EP=0/1 forces either.
_MOE_RULES_EP = [
    (r"moe'\]\['w_(gate|up)'\]$", ("model", None, None)),
    (r"moe'\]\['w_down'\]$", ("model", None, None)),
]


def _moe_rules(cfg: "ModelConfig", mesh, serve: bool):
    import os
    force = os.environ.get("REPRO_MOE_EP", "")
    if force == "1":
        return _MOE_RULES_EP
    if force == "0":
        return _MOE_RULES
    from repro.models.perf_flags import baseline_mode
    # EP regresses decode (measured 1.3× more bytes, 6× more wire on
    # moonshot decode_32k): per-token buckets are tiny, so the cross-shard
    # combine dominates — serve keeps the TP-inside-expert scheme.
    if (not baseline_mode() and not serve and cfg.num_experts
            and cfg.num_experts % mesh.shape["model"] == 0):
        return _MOE_RULES_EP
    return _MOE_RULES


def _fit(spec: tuple, shape: tuple, mesh) -> P:
    """Drop axes that don't divide the corresponding dim."""
    out = []
    for dim, ax in zip(shape, spec):
        if ax is None:
            out.append(None)
        else:
            size = mesh.shape[ax]
            out.append(ax if dim % size == 0 else None)
    return P(*out)


def param_specs(cfg: ModelConfig, mesh, serve: bool = False) -> Any:
    structs = param_structs(cfg)

    def rule_for(path, leaf):
        name = jax.tree_util.keystr(path)
        stacked = name.startswith("['blocks']")
        for pat, spec in _moe_rules(cfg, mesh, serve) + _RULES:
            if re.search(pat, name):
                full = ((None,) + spec) if stacked else spec
                if len(full) != len(leaf.shape):
                    return P()
                return _fit(full, leaf.shape, mesh)
        return P(*([None] * len(leaf.shape)))

    return jax.tree_util.tree_map_with_path(rule_for, structs)


def opt_specs(cfg: ModelConfig, mesh) -> Any:
    """Optimizer-state specs: params' specs + ZeRO-1-style sharding of the
    Adam moments over the data axis (§Perf iter 7) — m/v are pure
    per-element state, so each DP shard can own a slice and the weight
    update all-gathers, cutting the fp32 state footprint by the DP degree.
    First spare (None) dim that the data axis divides gets "data";
    baseline mode keeps moments param-aligned."""
    ps = param_specs(cfg, mesh)
    from repro.models.perf_flags import baseline_mode
    if baseline_mode() or "data" not in mesh.axis_names:
        return {"m": ps, "v": ps, "step": P()}
    structs = param_structs(cfg)
    dsize = mesh.shape["data"]

    def zero1(spec, leaf):
        spec = tuple(spec)
        for i, (ax, dim) in enumerate(zip(spec, leaf.shape)):
            if ax is None and dim % dsize == 0 and dim >= dsize:
                return P(*spec[:i], "data", *spec[i + 1:])
        return P(*spec)

    ms = jax.tree.map(zero1, ps, structs,
                      is_leaf=lambda x: isinstance(x, P))
    return {"m": ms, "v": ms, "step": P()}


def batch_specs(cfg: ModelConfig, mesh, batch: int) -> Any:
    """Specs for a data batch dict (tokens/targets/embeds)."""
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]
    bspec = baxes if batch % bsize == 0 and batch > 1 else None

    def spec(leaf_name):
        if leaf_name == "embeds":
            return P(bspec, None, None)
        return P(bspec, None)

    return spec


def cache_specs(cfg: ModelConfig, mesh, batch: int, max_len: int,
                shard_seq: bool = False) -> Any:
    """PartitionSpecs matching ``cache_structs``.

    shard_seq: the long-context (batch=1) policy — KV sequence dim over
    "data" (SP), SSM inner dim over "model"; otherwise caches shard over
    batch, KV heads over "model" where divisible.
    """
    structs = cache_structs(cfg, batch, max_len)
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]
    bspec = baxes if batch % bsize == 0 and batch > 1 else None

    from repro.models.perf_flags import baseline_mode

    def rule_for(path, leaf):
        name = jax.tree_util.keystr(path)
        shape = leaf.shape
        if name.endswith("['k']") or name.endswith("['v']"):
            # (P, B, C, KV, hd)
            seq_ax = ("data" if shard_seq and
                      shape[2] % mesh.shape["data"] == 0 else None)
            kv_ax = ("model" if shape[3] % mesh.shape["model"] == 0
                     else None)
            # §Perf iteration 2: when KV heads don't divide the model
            # axis, shard the cache *sequence* over "model" instead of
            # replicating 16× (partial-softmax reduce is tiny vs the read)
            if (not baseline_mode() and kv_ax is None and seq_ax is None
                    and shape[2] % mesh.shape["model"] == 0):
                seq_ax = "model"
            return P(None, bspec, seq_ax, kv_ax, None)
        if name.endswith("['h']"):          # (P, B, dI, N)
            di_ax = "model" if shape[2] % mesh.shape["model"] == 0 else None
            return P(None, bspec, di_ax, None)
        if name.endswith("['conv']"):       # (P, B, K-1, dI)
            di_ax = "model" if shape[3] % mesh.shape["model"] == 0 else None
            return P(None, bspec, None, di_ax)
        if name.endswith("['s']"):          # (P, B, H, hd, hd)
            return P(None, bspec, None, None, None)
        # x_prev / cm_x_prev: (P, B, D)
        return P(None, bspec, None)

    return jax.tree_util.tree_map_with_path(rule_for, structs)


# --------------------------------------------------------------------------
# Packed-layout sharding: the bitmap-compressed serving analogue of the
# dense _RULES above.  Column-parallel tensors split the N tile axis
# (each shard owns its output columns — no cross-shard composition);
# row-parallel tensors split K (per-shard partial products sum, the psum
# composition `kernels/ops._sharded_spmm` performs).  The LM head is
# vocab-split (col).  Tensors with no rule (router, SSM decay/mix
# vectors, norms) stay replicated.

PACKED_COL = {
    ("attn", "wq"), ("attn", "wk"), ("attn", "wv"),
    ("mlp", "w_gate"), ("mlp", "w_up"),
    ("moe", "w_gate"), ("moe", "w_up"),
    ("mamba", "in_proj"), ("mamba", "dt_proj"),
    ("rwkv", "w_r"), ("rwkv", "w_k"), ("rwkv", "w_v"), ("rwkv", "w_g"),
    ("rwkv_cm", "cm_k"),
}
PACKED_ROW = {
    ("attn", "wo"),
    ("mlp", "w_down"),
    ("moe", "w_down"),
    ("mamba", "out_proj"), ("mamba", "x_proj"),
    ("rwkv", "w_o"),
    ("rwkv_cm", "cm_v"),
}


def packed_mode(comp: str, name: str) -> Optional[str]:
    """Shard mode for a packed tensor: "col", "row", or None (replicate)."""
    if (comp, name) in PACKED_COL:
        return "col"
    if (comp, name) in PACKED_ROW:
        return "row"
    return None


def bitmap_sharded(bw: Optional[BitmapWeight], mesh) -> bool:
    """Whether this ``BitmapWeight``'s explicit shard axis lines up with
    the mesh's live model axis (the single predicate the spec builder
    and the shard_map gather share)."""
    return (bw is not None and bw.shard is not None
            and "model" in mesh.shape
            and mesh.shape["model"] == bw.shard[1] > 1)


def bitmap_specs(bw: Optional[BitmapWeight], mesh) -> Any:
    """A ``BitmapWeight`` of ``PartitionSpec`` leaves mirroring ``bw``:
    'model' on the explicit shard axis when it matches the mesh, else
    fully replicated.  ``dataclasses.replace`` keeps the static fields
    (shape/block/shard), so the spec tree has the same treedef as the
    array tree — valid for ``device_put`` and ``shard_map`` in_specs."""
    if bw is None:
        return None
    live = bitmap_sharded(bw, mesh)

    def spec(leaf):
        if not live:
            return P()
        axes: list = [None] * leaf.ndim
        axes[leaf.ndim - TILE_ND - 1] = "model"
        return P(*axes)

    return dataclasses.replace(bw, packed_bits=spec(bw.packed_bits),
                               values=spec(bw.values))


def packed_specs(tree: Any, mesh) -> Any:
    """Specs for a packed block tree (``PackedModel.blocks``): per-leaf
    ``bitmap_specs``, Nones preserved."""
    return jax.tree.map(lambda bw: bitmap_specs(bw, mesh), tree,
                        is_leaf=lambda x: isinstance(x, BitmapWeight))


def named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
