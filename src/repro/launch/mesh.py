"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before jax init,
while smoke tests and benches must see one device.

Every mesh here is built with ``Auto`` axis types: the model code places
activations through ``with_sharding_constraint`` hints
(``models/shard_utils.hint``) and lets the partitioner propagate the
rest, which is the contract ``Auto`` axes give (``jax.make_mesh``
defaults to ``Explicit`` axes, whose sharding-in-types rules the model
code was not written for).  Enter a mesh with ``jax.set_mesh(mesh)``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis ``Auto`` (see module doc)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_elastic_mesh(model_parallel: int = 16,
                      devices: Optional[Sequence] = None):
    """Build the largest (data, model) mesh the given device set allows
    (default: every live device).

    Elastic-restart path: after losing a host, the job re-derives the DP
    extent from the surviving device count and restores the checkpoint onto
    the smaller mesh (checkpoint.restore reshards).
    """
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    mp = min(model_parallel, n)
    while n % mp:
        mp -= 1
    return make_mesh((n // mp, mp), ("data", "model"), devices=devices)


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def mesh_tag(mesh) -> str:
    return "x".join(str(s) for s in mesh.devices.shape)
