"""The mesh helpers every mesh user in repro goes through, so the
replication-check default and the mesh axis types live in one place."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """SPMD-map ``f`` over ``mesh`` with replication checking disabled by
    default (the AFM step mixes replicated and sharded state on purpose)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def make_mesh(axis_sizes: tuple[int, ...], axis_names: tuple[str, ...]):
    """Device mesh over the available devices, with ``Auto`` axes: arrays
    placed on it stay usable by plain jnp code outside ``shard_map``
    (``jax.make_mesh`` defaults to ``Explicit`` axes, under which e.g. a
    BMU gather on mesh-sharded weights is a sharding type error)."""
    return jax.make_mesh(axis_sizes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_sizes))
