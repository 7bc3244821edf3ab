"""Mesh and shard_map construction in one place.

Every mesh is built with Auto axis types and every ``shard_map`` runs with
the replication checker off, so call sites stay one-liners.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map``; ``check_vma`` defaults off — the exchange/MoE
    bodies use collectives whose replication the checker cannot prove."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
