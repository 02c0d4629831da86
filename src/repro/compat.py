"""One spelling for the jax mesh and sharding APIs the repo uses (DESIGN.md §6).

The repo supports one jax: 0.9.0.  This module keeps the repo's own
spelling of the few mesh/sharding entry points it calls, so a future
jax move lands in one file:

    ===============================  ================================
    exported here                    jax 0.9
    ===============================  ================================
    shard_map(..., check=)           jax.shard_map(..., check_vma=)
    make_mesh (Auto axes default)    jax.make_mesh(..., axis_types=)
    AxisType                         jax.sharding.AxisType
    set_mesh                         jax.set_mesh
    get_abstract_mesh                jax.sharding.get_abstract_mesh
    ===============================  ================================

The ``compat-boundary`` lint rule (DESIGN.md §11) keeps these names from
being spelled anywhere else.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax

AxisType = jax.sharding.AxisType
set_mesh = jax.set_mesh
get_abstract_mesh = jax.sharding.get_abstract_mesh


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the varying-manual-axes check off by
    default (``check`` maps to ``check_vma``)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None, axis_types: Optional[Sequence] = None
              ) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto-typed axes unless ``axis_types`` says
    otherwise (explicit-sharding mode is opt-in)."""
    axis_names = tuple(axis_names)
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(tuple(axis_shapes), axis_names, devices=devices,
                         axis_types=tuple(axis_types))
