"""Names of the program's host spans and device scopes, and ``span``.

A host span is a ``jax.profiler.TraceAnnotation``: it records an interval
on the host's line of a profiler trace, on the clock of the device's op
line, and costs one enter and one exit (well under a microsecond) when no
trace is running. A device scope is a ``jax.named_scope``: it only adds its
name to the ``op_name`` metadata of the HLO ops traced under it, so a
profile can put each device op down to the stage that issued it.

Host spans (nesting shown by indentation):

- ``topomap.fit`` — ``TopoMap.fit``
    - ``backend.run`` — the compiled step loop, up to its result being ready
- ``topomap.label`` — ``TopoMap.label``
- ``gateway.dispatch`` — one coalesced (or inline) gateway dispatch, with
  the merged ``requests`` and ``rows`` as metadata
    - ``gateway.merge`` — concatenating the merged requests
    - ``engine.bmu`` — ``MapService``'s engine call: pad, transfer, kernel,
      slice, up to the answer being ready
    - ``gateway.resolve`` — materialising, post-processing, the futures

Device scopes: ``afm.search``, ``afm.adapt`` and ``afm.cascade`` (the
staged step's three stages), ``fused.wave_keys`` (the fused step's wave-key
chain and Bernoulli draws), ``events.pool`` (the event engine's message
pool: selection, enqueue, and the delivery round's take and clear) and
``events.deliver`` (the delivery round's receiver side: drive, per-receiver
segment sums, weight rows), in both placements; and, in the mesh placement
only, ``mesh.search`` (a sample round's local distance pass and its
min-reduce over the shards) and ``mesh.exchange`` (the halo ``ppermute``
pair, the due-flag ``psum`` of each drain iteration, and the enqueue of the
halo arrivals).
"""
from __future__ import annotations

import jax

TOPOMAP_FIT = "topomap.fit"
BACKEND_RUN = "backend.run"
TOPOMAP_LABEL = "topomap.label"
GATEWAY_DISPATCH = "gateway.dispatch"
GATEWAY_MERGE = "gateway.merge"
ENGINE_BMU = "engine.bmu"
GATEWAY_RESOLVE = "gateway.resolve"

AFM_SEARCH = "afm.search"
AFM_ADAPT = "afm.adapt"
AFM_CASCADE = "afm.cascade"
FUSED_WAVE_KEYS = "fused.wave_keys"
EVENTS_POOL = "events.pool"
EVENTS_DELIVER = "events.deliver"
MESH_SEARCH = "mesh.search"
MESH_EXCHANGE = "mesh.exchange"


def span(name: str, **metadata):
    """A host span named ``name`` (a context manager); ``metadata`` is
    recorded beside it as the event's stats, not in its name."""
    return jax.profiler.TraceAnnotation(name, **metadata)
