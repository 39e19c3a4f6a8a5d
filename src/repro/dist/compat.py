"""Mesh and shard_map entry points of the distribution layer.

Thin wrappers that fix the repository's conventions in one place:
``shard_map`` is ``jax.shard_map`` with an optional ``check_vma``, and
every mesh is built with ``AxisType.Auto`` axes (GSPMD picks layouts
unless a program pins them).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None):
    """``jax.shard_map``; ``check_vma=None`` keeps jax's default."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with auto axis types."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(tuple(axis_names)),
                         **kwargs)


def make_client_mesh(num_clients: int, axis_name: str = "data"):
    """The 1-axis client mesh every DFL shard_map program runs on."""
    return make_mesh((num_clients,), (axis_name,))
