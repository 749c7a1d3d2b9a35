"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single-pod: (data=16, model=16) = 256 chips
(TPU v5e pod).  Multi-pod: (pod=2, data=16, model=16) = 512 chips.

Every mesh in the repo is built by :func:`make_mesh`, which gives each
axis the ``Auto`` type: ``jax.make_mesh`` defaults to ``Explicit`` axes,
and ``with_sharding_constraint`` (``ParallelCtx.constrain``) may only
refer to ``Auto`` ones.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType

from ..parallel.sharding import ParallelCtx


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def ctx_for_mesh(mesh, **kw) -> ParallelCtx:
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return ParallelCtx(mesh=mesh, data_axes=data_axes, **kw)


def small_host_mesh(n: Optional[int] = None, model: int = 2):
    """Host-device mesh for tests (requires XLA_FLAGS host device count)."""
    n = n or len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))
