"""Placement seam for the discrete-event engine (DESIGN.md §10).

A ``Placement`` is a pool capacity and a runner: where the event engine's
message pool lives and the compiled loop that simulates over it (the pool's
own work is ``repro.core.pool``, below every placement). ``SinglePool`` is
the historical (golden-suite-pinned) layout; ``MeshPlacement`` partitions
units and the free-list ring pool across a ``shard_map`` device mesh with
batched per-round halo exchange.
"""
from repro.core.placement.base import Placement, resolve_placement
from repro.core.placement.mesh import MeshPlacement
from repro.core.placement.single import SinglePool

__all__ = ["Placement", "SinglePool", "MeshPlacement", "resolve_placement"]
