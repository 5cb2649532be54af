"""The placement seam of the discrete-event engine.

A ``Placement`` decides where the engine's message pool — and the unit
state it serves — lives, and runs the simulation there. It answers two
questions:

- **pool capacity** — ``pool_capacity(cfg, ecfg)``: how many message slots
  one pool holds (for a partitioned placement: per shard);
- **execution** — ``build_runner(...)``: the compiled simulation loop,
  ``go(state, samples, step_keys, lat_key) -> (state, aux, report)``.

What a pool does — round selection, the send side, the delivery round's
receiver side — is one module below every placement (``repro.core.pool``);
a placement's runner calls it and keeps what is its own. Placements are
frozen dataclasses: hashable, so they key the engine's ``lru_cache`` of
jitted runners exactly like ``EventConfig`` does.

Two placements exist: ``SinglePool`` (one dense pool on one device — the
historical engine, golden-suite-pinned bitwise) and ``MeshPlacement``
(units and the free-list ring pool partitioned across a ``shard_map``
device mesh, cross-shard traffic as batched per-round halos). See their
modules and DESIGN.md §10.
"""
from __future__ import annotations

from typing import Protocol, runtime_checkable


@runtime_checkable
class Placement(Protocol):
    """What the event engine needs from a placement (see module docstring)."""

    name: str

    @property
    def shards(self) -> int: ...

    def pool_capacity(self, cfg, ecfg) -> int: ...

    def build_runner(self, cfg, ecfg, num_events: int,
                     search, p_fn, l_c_fn): ...


def resolve_placement(spec=None, *, shards: int | None = None) -> Placement:
    """Normalize a placement spec: ``None`` / ``'single'`` -> ``SinglePool``,
    ``'mesh'`` -> ``MeshPlacement(shards)``, a ``Placement`` instance passes
    through (its shard count must agree with ``shards`` when both are given).
    """
    from repro.core.placement.mesh import MeshPlacement
    from repro.core.placement.single import SinglePool

    if spec is None or spec == "single":
        if shards not in (None, 1):
            raise ValueError(
                f"placement 'single' is one pool on one device; shards="
                f"{shards} needs placement='mesh'")
        return SinglePool()
    if spec == "mesh":
        return MeshPlacement(shards=1 if shards is None else int(shards))
    if isinstance(spec, Placement):
        if shards is not None and spec.shards != shards:
            raise ValueError(
                f"placement {spec!r} has shards={spec.shards}, but shards="
                f"{shards} was also requested")
        return spec
    raise ValueError(
        f"placement must be None, 'single', 'mesh', or a Placement, "
        f"got {spec!r}")
