"""The mesh and shard_map spellings every call site shares.

``shard_map`` is ``jax.shard_map`` (call sites pass ``check_vma``).
``make_mesh`` pins every axis to ``AxisType.Auto``: the sharded closures
are written for sharding resolved by the compiler, and ``jax.make_mesh``
would otherwise make the axes Explicit.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax

shard_map = jax.shard_map


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    shape = tuple(axis_shapes)
    return jax.make_mesh(shape, tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
                         devices=devices)


def default_mesh(data_axes: Tuple[str, ...] = ("data",),
                 model_axis: Optional[str] = None) -> jax.sharding.Mesh:
    """All local devices laid out on the first data axis (trivial otherwise)."""
    names = tuple(data_axes) + ((model_axis,) if model_axis else ())
    shape = (len(jax.devices()),) + (1,) * (len(names) - 1)
    return make_mesh(shape, names)
