"""The program's own spans and scopes in a profiler trace.

``trace.load`` keeps each device op's name and interval; this module adds
what the program marks inside itself (``repro.obs``): each op's scope path
and the host spans the program opens. A device op's scope path is the
``op_name`` metadata of its HLO instruction, which the trace keeps in the
op's ``tf_op`` stat (``jit(f)/while/body/afm.cascade/...``). A fusion is put
down to the scope in its own metadata, which XLA takes from the fusion's
root: the ops fused into it from other scopes count under the root's.

Every share here is of leaf ops only: a control-flow op (``while``,
``conditional``, ``call``) spans its body's ops and the gaps between them,
so it counts neither as busy time nor under a scope. ``device_idle.*``
counts control flow as busy; ``train.loop_idle`` does not, which is what
lets it see the bubbles inside a compiled loop.

    summary = scopes.reduce(scopes.load(path), span_names)
    scopes.readings(summary, {"queued_s": ..., "dispatch_requests": ...})

The summary is ``trace.reduce``'s, with idle gaps named by the program's
spans as well as the benchmark's, plus ``host`` (those spans, inside the
window) and ``scopes`` (each device's scope paths, parallel to ``ops``).
"""
from __future__ import annotations

from harness import trace

#: The program's host spans (``repro.obs``).
PROGRAM_SPANS = frozenset({
    "topomap.fit", "backend.run", "topomap.label", "gateway.dispatch",
    "gateway.merge", "engine.bmu", "gateway.resolve"})
#: The stat of a device op's metadata that holds its ``op_name``.
OP_NAME_STAT = "tf_op"


def _xspace_class():
    """The protobuf class of a profiler ``XSpace``, built from the fields
    read here (the profiler's own ``xplane.proto`` numbering)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    fd = descriptor_pb2.FieldDescriptorProto
    fp = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                            package="bench_xplane")

    def message(parent, name, fields):
        m = parent.add(name=name)
        for number, field, kind, repeated in fields:
            f = m.field.add(name=field, number=number,
                            label=fd.LABEL_REPEATED if repeated
                            else fd.LABEL_OPTIONAL)
            if isinstance(kind, str):
                f.type, f.type_name = fd.TYPE_MESSAGE, f".bench_xplane.{kind}"
            else:
                f.type = kind
        return m

    i64, u64, text = fd.TYPE_INT64, fd.TYPE_UINT64, fd.TYPE_STRING
    message(fp.message_type, "XStat", [(1, "metadata_id", i64, False),
                                       (5, "str_value", text, False),
                                       (7, "ref_value", u64, False)])
    message(fp.message_type, "XEvent", [(1, "metadata_id", i64, False)])
    message(fp.message_type, "XLine", [(2, "name", text, False),
                                       (4, "events", "XEvent", True)])
    message(fp.message_type, "XEventMetadata", [(2, "name", text, False),
                                                (5, "stats", "XStat", True)])
    message(fp.message_type, "XStatMetadata", [(2, "name", text, False)])
    plane = message(fp.message_type, "XPlane", [
        (2, "name", text, False), (3, "lines", "XLine", True),
        (4, "event_metadata", "XPlane.EventMeta", True),
        (5, "stat_metadata", "XPlane.StatMeta", True)])
    for entry, value in (("EventMeta", "XEventMetadata"),
                         ("StatMeta", "XStatMetadata")):
        m = message(plane.nested_type, entry, [(1, "key", i64, False),
                                               (2, "value", value, False)])
        m.options.map_entry = True
    message(fp.message_type, "XSpace", [(1, "planes", "XPlane", True)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def op_scopes(path: str, device_plane: str = trace.DEVICE_PLANE,
              op_line: str = trace.OP_LINE) -> dict:
    """{plane: [op_name]}: the scope path of each event of each device
    plane's op lines, in ``trace.load``'s order ("" where the op has none)."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith(device_plane):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        stat = [k for k, v in names.items() if v == OP_NAME_STAT]
        scope = {}
        for k, meta in plane.event_metadata.items():
            for st in meta.stats:
                if st.metadata_id in stat:
                    scope[k] = st.str_value or names.get(st.ref_value, "")
        out[plane.name] = [scope.get(e.metadata_id, "")
                           for line in plane.lines
                           if line.name.startswith(op_line)
                           for e in line.events]
    return out


def load(path: str, **kw) -> dict:
    """``trace.load`` plus ``scopes``: each device op's scope path."""
    tr = trace.load(path, **kw)
    tr["scopes"] = op_scopes(path, **{k: v for k, v in kw.items()
                                      if k in ("device_plane", "op_line")})
    for plane, ops in tr["devices"].items():
        if len(tr["scopes"].get(plane, ())) != len(ops):
            raise ValueError(f"scope paths of {plane!r} do not line up with "
                             f"its ops")
    return tr


def reduce(tr: dict, span_names: set) -> dict:
    """``trace.reduce`` with the program's spans naming the idle gaps, plus
    the window's spans (the benchmark's ``span_names`` and the program's) and
    the devices' scope paths."""
    names = set(span_names) | PROGRAM_SPANS
    summary = trace.reduce(tr, names)
    lo, hi = summary["lo"], summary["hi"]
    summary["host"] = [h for h in tr["host"]
                       if h[0] in names and h[2] > lo and h[1] < hi]
    summary["scopes"] = {d: tr["scopes"][d] for d in summary["ops"]}
    return summary


def in_scope(op_name: str, scope: str) -> bool:
    """Whether ``scope`` is a component of the op's scope path."""
    return scope in op_name.rstrip(":").split("/")


def spans(summary: dict, name: str) -> list:
    """[(start_ns, end_ns)] of the host spans ``name`` inside the window."""
    lo, hi = summary["lo"], summary["hi"]
    return sorted((max(s, lo), min(e, hi)) for n, s, e in summary["host"]
                  if n == name and min(e, hi) > max(s, lo))


def _leaves(summary: dict):
    """[(name, start, end, scope)] of each device's leaf ops, in the order
    of ``summary["ops"]``."""
    for d, ops in summary["ops"].items():
        yield [(n, s, e, sc) for (n, s, e), sc
               in zip(ops, summary["scopes"][d])
               if not n.startswith(trace.CONTAINERS)]


def _intervals(summary: dict, within) -> list:
    return ([(summary["lo"], summary["hi"])] if within is None
            else spans(summary, within))


def leaf_busy_s(summary: dict, within: str | None = None) -> float:
    """Device seconds in which a leaf op runs, inside the spans ``within``
    (the whole window when None), averaged over the devices."""
    total = []
    for ops in _leaves(summary):
        events = [(n, s, e) for n, s, e, _ in ops]
        total.append(sum(trace.busy_ns(events, lo, hi)
                         for lo, hi in _intervals(summary, within)))
    return sum(total) / max(len(total), 1) * 1e-9


def scope_s(summary: dict, scope: str, within: str | None = None) -> float:
    """Device seconds of the leaf ops under ``scope`` that start inside the
    spans ``within`` (the whole window when None), averaged over the
    devices."""
    ivs = _intervals(summary, within)
    total = []
    for ops in _leaves(summary):
        total.append(sum(e - s for _, s, e, sc in ops
                         if in_scope(sc, scope)
                         and any(lo <= s < hi for lo, hi in ivs)))
    return sum(total) / max(len(total), 1) * 1e-9


def span_s(summary: dict, name: str) -> float:
    """Seconds covered by the spans ``name`` inside the window."""
    return sum(e - s for s, e in spans(summary, name)) * 1e-9


def idle_by_span(summary: dict) -> dict:
    """{span: seconds} of every idle gap of the first device in the window
    (``device_idle``'s idle time, control flow counted busy), each gap put
    down as ``trace.idle_gaps`` names it."""
    first = summary["ops"][sorted(summary["ops"])[0]]
    gaps = trace.idle_gaps(first, summary["host"], summary["lo"],
                           summary["hi"], k=len(first) + 1)
    out = {}
    for label, seconds in gaps:
        out[label] = out.get(label, 0.0) + seconds
    return out


def top_ops(summary: dict, k: int = 10) -> list:
    """[[name, seconds, op_name]] of the ``k`` leaf ops of the first device
    with the most time in the window, each with its scope path."""
    lo, hi = summary["lo"], summary["hi"]
    total, path = {}, {}
    for n, s, e, sc in next(_leaves(summary)):
        if lo <= s < hi:
            name = trace.op_name(n)
            total[name] = total.get(name, 0) + (e - s)
            path.setdefault(name, sc)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9, path[n]] for n, t in ranked]


def readings(summary: dict, counters: dict | None = None) -> dict:
    """The per-layer numbers the program's spans, scopes and counters give,
    each left out where the trace (or ``counters``) holds nothing for it:

    - ``serve.queue_ms``: ``GatewayStats.queued_s / dispatch_requests``;
    - ``serve.dispatch_ms``: mean ``gateway.dispatch`` span;
    - ``train.loop_idle``: % of the ``backend.run`` spans with no leaf op;
    - ``fused.key_chain_share``, ``train.cascade_share``: % of the leaf-op
      time inside ``backend.run`` under ``fused.wave_keys``, ``afm.cascade``;
    - ``async.pool_share``: % of the window's leaf-op time under
      ``events.pool``.
    """
    out = {}
    c = counters or {}
    if c.get("queued_s") is not None and c.get("dispatch_requests"):
        out["serve.queue_ms"] = 1e3 * c["queued_s"] / c["dispatch_requests"]
    dispatches = spans(summary, "gateway.dispatch")
    if dispatches:
        out["serve.dispatch_ms"] = (1e3 * span_s(summary, "gateway.dispatch")
                                    / len(dispatches))
    run_s = span_s(summary, "backend.run")
    if run_s > 0:
        busy = leaf_busy_s(summary, "backend.run")
        out["train.loop_idle"] = 100.0 * (1.0 - busy / run_s)
        for name, scope in (("fused.key_chain_share", "fused.wave_keys"),
                            ("train.cascade_share", "afm.cascade")):
            part = scope_s(summary, scope, "backend.run")
            if part > 0:
                out[name] = 100.0 * part / busy
    pool = scope_s(summary, "events.pool")
    if pool > 0:
        out["async.pool_share"] = 100.0 * pool / leaf_busy_s(summary)
    return out
