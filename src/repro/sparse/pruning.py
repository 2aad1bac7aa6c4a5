"""Global L1 fine-grained pruning (Han et al. [1]) on jax pytrees.

The paper prunes MobileNetV2 to 75 % weight sparsity with a single global
magnitude threshold; this module does the same for framework models, plus a
per-tensor variant and sparsity accounting helpers.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _is_prunable(path: tuple, leaf: jax.Array,
                 predicate: Callable | None) -> bool:
    if leaf.ndim < 2:      # biases, norms, scalars stay dense
        return False
    if predicate is not None:
        return predicate(path, leaf)
    name = "/".join(str(p) for p in path).lower()
    return "embed" not in name  # embeddings stay dense by default


@jax.jit
def _kth_magnitude(leaves: Tuple[jax.Array, ...], k: jax.Array) -> jax.Array:
    """The k-th smallest (0-based) float32 |w| over every leaf.

    Bisection on the float32 bit pattern (ordered like the value for
    non-negative floats), counting ``<=`` per leaf: 31 reads of the
    leaves and no concatenated copy or sort, so a model whose prunable
    weights fill most of the device memory still fits.
    """
    def count_le(p):
        return sum(jnp.sum(jax.lax.bitcast_convert_type(
            jnp.abs(l).astype(jnp.float32), jnp.int32) <= p,
            dtype=jnp.uint32) for l in leaves)

    def step(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        ok = count_le(mid) > k
        return jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)

    lo, _ = jax.lax.fori_loop(0, 31, step,
                              (jnp.int32(0), jnp.int32(0x7F800000)))
    return jax.lax.bitcast_convert_type(lo, jnp.float32)


def _position(q: float, n: int) -> Tuple[int, int]:
    """The two ranks ``jnp.quantile`` (linear) interpolates between over
    ``n`` values, as host ints: its float32 sample position
    ``q * (n - 1)``, floored and ceiled, clamped to ``[0, n - 1]`` in
    float32 as it does, so a count past 2**31 - 1 overflows nothing.
    Where float32 rounds ``n - 1`` up to ``n``, the rank is held to
    ``n - 1``, as ``jnp.quantile``'s gather clamps it."""
    last = np.float32(n) - np.float32(1)
    pos = np.float32(q) * last
    return tuple(min(int(np.clip(r, 0, last)), n - 1)
                 for r in (np.floor(pos), np.ceil(pos)))


@functools.partial(jax.jit, static_argnames="n")
def _interpolate(lo_v, hi_v, q, n: int):
    """``jnp.quantile``'s linear interpolation, in its float32
    arithmetic."""
    pos = q * (jnp.float32(n) - 1)
    high_w = pos - jnp.floor(pos)
    return lo_v * (1 - high_w) + hi_v * high_w


def global_threshold(leaves: Sequence[jax.Array],
                     sparsity: float) -> jax.Array:
    """``jnp.quantile`` (linear method) of every leaf's |w| taken
    together, without concatenating them: the two order statistics it
    interpolates come from ``_kth_magnitude``, and the interpolation is
    one jitted expression like ``jnp.quantile``'s own, so the threshold
    is the same float32 number.  The bisection counts in uint32, so the
    leaves may hold up to 2**32 - 1 weights."""
    n = sum(int(l.size) for l in leaves)
    if n >= 2 ** 32:
        raise ValueError(f"{n} prunable weights overflow the uint32 count")
    q = jnp.float32(sparsity)
    low, high = _position(sparsity, n)
    leaves = tuple(leaves)
    lo_v = _kth_magnitude(leaves, jnp.uint32(low))
    hi_v = _kth_magnitude(leaves, jnp.uint32(high))
    return _interpolate(lo_v, hi_v, q, n)


@functools.partial(jax.jit, donate_argnums=0)
def _prune_leaf(leaf, thresh):
    return jnp.where(jnp.abs(leaf) <= thresh, 0, leaf)


def global_l1_prune(params: Any, sparsity: float,
                    predicate: Callable | None = None) -> Any:
    """Zero the globally-smallest |w| fraction across all prunable leaves.

    Each prunable leaf is donated to its pruned copy, so the device never
    holds two copies of the weights: the caller's prunable leaves are
    consumed, and only the returned tree may be used."""
    if sparsity <= 0:
        return params
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    which = [_is_prunable(p, l, predicate) for p, l in flat]
    if not any(which):
        return params
    thresh = global_threshold([l for (_, l), w in zip(flat, which) if w],
                              sparsity)
    return jax.tree_util.tree_unflatten(treedef, [
        _prune_leaf(l, thresh) if w else l for (_, l), w in zip(flat, which)])


def per_tensor_prune(w: jax.Array, sparsity: float) -> jax.Array:
    """Magnitude-prune a single tensor to exactly ``sparsity``."""
    if sparsity <= 0:
        return w
    k = int(round(sparsity * w.size))
    if k <= 0:
        return w
    thresh = jnp.sort(jnp.abs(w).reshape(-1))[k - 1]
    return jnp.where(jnp.abs(w) <= thresh, 0, w)


def sparsity_of(params: Any) -> float:
    leaves = [l for l in jax.tree_util.tree_leaves(params)
              if hasattr(l, "size") and l.ndim >= 2]
    total = sum(l.size for l in leaves)
    zeros = sum(int((l == 0).sum()) for l in leaves)
    return zeros / max(total, 1)
