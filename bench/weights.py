"""The served weights, made on the device from the seed.

A pruned checkpoint is the benchmark's input: ``make(config, seed)``
draws every matrix from N(0, std^2) in the served dtype, in one jitted
call, and prunes it to the configuration's global L1 sparsity in a
second (in place).  Both the engine (as its initial parameters) and the
plain reference (after the window) get the weights from here, so neither
takes anything from the other.

Every leaf's values depend only on the seed and the leaf's path, and
period-stacked leaves are drawn one period at a time, so no float32 copy
of a whole stack is ever held.

The tree follows the program's parameter layout (period-stacked block
leaves under ``blocks/b<i>/<component>/<tensor>``); the harness checks
the shapes against the program's own inventory before it hands them over.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench.generator import rng_for

#: extra pruned weights beyond ceil(sparsity * n): the program's own
#: global prune then finds the same zeros at its threshold (0) and
#: changes nothing, whatever its float32 rounding of the quantile's rank
PRUNE_MARGIN = 4096


def block_shapes(model: dict, blk: dict) -> Dict[str, Dict[str, tuple]]:
    d, h, kv = model["d_model"], model["num_heads"], model["num_kv_heads"]
    hd = model["head_dim"] or d // h
    f = model["d_ff"]
    out: Dict[str, Dict[str, tuple]] = {}
    if blk["mixer"] != "attn":
        raise ValueError(f"no weights for mixer {blk['mixer']!r}")
    out["attn"] = {"norm": (d,), "wq": (d, h * hd), "wk": (d, kv * hd),
                   "wv": (d, kv * hd), "wo": (h * hd, d)}
    if blk["ffn"] == "mlp":
        out["mlp"] = {"norm": (d,), "w_up": (d, f), "w_down": (f, d),
                      "w_gate": (d, f)}
    elif blk["ffn"] == "moe":
        e = model["num_experts"]
        out["moe"] = {"norm": (d,), "router": (d, e), "w_gate": (e, d, f),
                      "w_up": (e, d, f), "w_down": (e, f, d)}
    else:
        raise ValueError(f"no weights for ffn {blk['ffn']!r}")
    return out


def shapes(model: dict) -> Dict:
    """Nested dict of shapes; block leaves carry the period axis first."""
    periods = model["num_layers"] // len(model["pattern"])
    blocks = {}
    for i, blk in enumerate(model["pattern"]):
        blocks[f"b{i}"] = {c: {n: (periods,) + s for n, s in t.items()}
                           for c, t in block_shapes(model, blk).items()}
    out = {"embed": (model["vocab_size"], model["d_model"]),
           "final_norm": (model["d_model"],), "blocks": blocks}
    if not model["tie_embeddings"]:
        out["lm_head"] = (model["d_model"], model["vocab_size"])
    return out


def _is_leaf(x) -> bool:
    return isinstance(x, tuple)


def prunable(path: str, shape: tuple) -> bool:
    """Projection matrices; the embedding and norms stay dense."""
    return "embed" not in path and "norm" not in path and len(shape) >= 2


def _draw(key, path: str, shape: tuple, std: float, dtype, stacked: bool):
    key = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if "norm" in path:
        return jnp.zeros(shape, dtype)
    if not stacked:
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    return jax.lax.map(
        lambda p: (std * jax.random.normal(jax.random.fold_in(key, p),
                                           shape[1:], jnp.float32)
                   ).astype(dtype), jnp.arange(shape[0]))


def _nest(flat):
    out: Dict = {}
    for path, leaf in flat:
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return out


def layout(model: dict) -> Tuple:
    """The leaves as a hashable ((key, ...), shape) tuple."""
    flat = jax.tree_util.tree_flatten_with_path(shapes(model),
                                                is_leaf=_is_leaf)[0]
    return tuple((tuple(k.key for k in p), s) for p, s in flat)


@functools.partial(jax.jit, static_argnames=("lay", "std", "dtype"))
def _init(key, lay, std, dtype):
    return _nest([(path, _draw(key, "/".join(path), shape, std,
                               jnp.dtype(dtype),
                               stacked=path[0] == "blocks"))
                  for path, shape in lay])


def _magnitude_bits(w):
    """bf16/f16/f32 |w| as an integer that orders like the value."""
    bits = {2: jnp.uint16, 4: jnp.uint32}[w.dtype.itemsize]
    mask = {2: 0x7FFF, 4: 0x7FFFFFFF}[w.dtype.itemsize]
    return (jax.lax.bitcast_convert_type(w, bits) & mask).astype(jnp.int32)


@jax.jit
def threshold_bits(leaves, k):
    """Smallest magnitude pattern m with count(|w| <= m) >= k, counted
    over every leaf (bisection, 31 reads, no sort or concatenation)."""
    def count_le(m):
        return sum(jnp.sum(_magnitude_bits(l) <= m, dtype=jnp.uint32)
                   for l in leaves)

    def step(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        ok = count_le(mid) >= k
        return jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)

    lo, _ = jax.lax.fori_loop(0, 31, step,
                              (jnp.int32(0), jnp.int32(0x7FFFFFFF)))
    return lo


@functools.partial(jax.jit, donate_argnums=0)
def _prune(leaves, m):
    return tuple(jnp.where(_magnitude_bits(l) <= m, jnp.zeros_like(l), l)
                 for l in leaves)


def prune(params, sparsity: float):
    """Zero every prunable weight whose magnitude is at or below the
    ``sparsity`` quantile of all of them (ties pruned), in place."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    idx = [i for i, (p, l) in enumerate(flat)
           if prunable(jax.tree_util.keystr(p), l.shape)]
    leaves = tuple(flat[i][1] for i in idx)
    n = sum(int(l.size) for l in leaves)
    if n >= 2 ** 32:
        raise ValueError(f"{n} prunable weights overflow the uint32 count")
    k = min(n, math.ceil(sparsity * n) + PRUNE_MARGIN)
    m = threshold_bits(leaves, jnp.uint32(k))
    pruned = _prune(leaves, m)
    out = [l for _, l in flat]
    for i, l in zip(idx, pruned):
        out[i] = l
    return jax.tree.unflatten(treedef, out)


def init(config: dict, seed: int):
    """The dense weights of ``config`` for ``seed``, on the device."""
    model = config["model"]
    key = jax.random.PRNGKey(int(rng_for(seed, "weights").integers(2 ** 31)))
    return _init(key, lay=layout(model), std=float(config["weights"]["std"]),
                 dtype=model["param_dtype"])


def make(config: dict, seed: int):
    """The pruned, served weights of ``config`` for ``seed``."""
    return prune(init(config, seed), float(config["engine"]["sparsity"]))

