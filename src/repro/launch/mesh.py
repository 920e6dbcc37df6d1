"""Mesh construction.

Every mesh in the repository is made by :func:`make_mesh`, with ``Auto``
axes: the code places arrays with ``NamedSharding`` and
``with_sharding_constraint`` and lets the partitioner propagate the rest,
which ``Explicit`` axes (``jax.make_mesh``'s default) refuse.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips for the multi-pod run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small host-device mesh for tests (requires enough host devices)."""
    return make_mesh((n_data, n_model), ("data", "model"))
