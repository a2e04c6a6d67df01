"""Mesh construction: every mesh in the repo is built by :func:`make_mesh`.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count locks on first backend init).

Every axis is ``AxisType.Auto``.  With Explicit axes (the default of a bare
``jax.make_mesh``) a sharding becomes part of each array's type, and plain
slicing of a ``shard_map`` result raises ``ShardingTypeError``; the engine
and the step builders rely on the SPMD partitioner placing such results.
Enter a mesh for tracing with ``jax.set_mesh(mesh)``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto; ``devices`` defaults to all."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)
