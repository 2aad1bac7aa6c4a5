"""Activation-sharding hints that degrade to no-ops off-mesh.

``hint(x, *axes)`` applies ``with_sharding_constraint`` when tracing under
a mesh set with ``jax.set_mesh``, dropping axes the mesh doesn't have as
``Auto`` axes (inside ``shard_map`` they are ``Manual``: the per-device
layout is already explicit) or that don't divide the dim — so model code
can carry production sharding annotations while remaining runnable on a
single CPU device (smoke tests, examples).

Axis conventions (launch/sharding.py): "batch" expands to ("pod","data");
"model" is tensor parallel; None replicates.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P


def hint(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Constrain dim i of x to axis names axes[i] ("batch"/"model"/None)."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = set(mesh.auto_axes) if not mesh.empty else set()
    if not auto:
        return x
    spec = []
    for dim, ax in zip(x.shape, axes):
        if ax == "batch":
            names: Tuple[str, ...] = tuple(
                a for a in ("pod", "data") if a in auto)
            size = 1
            for a in names:
                size *= mesh.shape[a]
            spec.append(names if names and dim % size == 0 and dim > 1
                        else None)
        elif ax is not None and ax in auto:
            spec.append(ax if dim % mesh.shape[ax] == 0 else None)
        else:
            spec.append(None)
    spec += [None] * (len(x.shape) - len(spec))
    return jax.lax.with_sharding_constraint(x, P(*spec))
