"""Profiler capture and the reduction from a trace to numbers.

The device's operations are the events of its op line (``XLA Ops`` on a
TPU). Busy time is the union of their intervals inside the traced window;
a kernel's time is the summed duration of its op's instances, named as
the trace names them (a Pallas call by the function that wraps it:
``fused_step_pallas``, ``bmu_pallas``).
Host spans are the ``TraceAnnotation``s the benchmark opens around its calls
into the program; each idle gap of the device is labelled by the innermost
span open at its midpoint.
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile

#: The span that brackets the traced window.
WINDOW_SPAN = "bench.window"
#: Longest traced window, in seconds: a profile of a few seconds holds every
#: cell's repeating work many times over and reads back within a run's time.
WINDOW_SECONDS = 3.0
#: Where the device's operations are, by plane-name prefix and line name.
DEVICE_PLANE, OP_LINE = "/device:TPU:", "XLA Ops"
HOST_PLANE = "/host:CPU"


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (a no-op when none is running)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class Capture:
    """Records a profiler trace into a temporary directory while open; the
    directory is removed by ``close``."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.path = None

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        self.path = found[-1] if found else None
        return False

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def load(path: str, device_plane: str = DEVICE_PLANE,
         op_line: str = OP_LINE, host_plane: str = HOST_PLANE) -> dict:
    """{"devices": {plane: [(name, start_ns, end_ns)]}, "host": [...]}:
    the events of the op lines (names starting with ``op_line``) of each
    device plane, and every event of the host plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(device_plane):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name.startswith(op_line):
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
        if plane.name == host_plane:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
    return {"devices": devices, "host": host}


def window(host: list) -> tuple[float, float]:
    """(start_ns, end_ns) of the window span."""
    spans = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return spans[0]


def merged(events: list, lo: float, hi: float) -> list:
    """The union of the events' intervals clipped to [lo, hi], as sorted
    disjoint (start, end) pairs."""
    out = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events: list, lo: float, hi: float) -> float:
    return float(sum(e - s for s, e in merged(events, lo, hi)))


#: Control-flow operations whose events enclose the operations they run.
CONTAINERS = ("%while", "%conditional", "%call")


def op_name(event_name: str) -> str:
    """The short name of an op event: the HLO instruction's name, before
    its text (``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``)."""
    return event_name.split(" = ", 1)[0]


def is_op(event_name: str, name: str) -> bool:
    """Whether an op event is an instance of ``name``: its instruction name
    (``%bmu_pallas.9``) starts with it, ``%`` aside."""
    return op_name(event_name).lstrip("%").startswith(name)


def kernel_ns(events: list, name: str, lo: float, hi: float) -> float:
    """Summed duration of the instances of op ``name`` that start inside the
    window."""
    return float(sum(e - s for n, s, e in events
                     if lo <= s < hi and is_op(n, name)))


def top_ops(events: list, lo: float, hi: float, k: int = 10) -> list:
    """[[name, seconds]] of the ``k`` operations with the most device time,
    leaving out control flow, whose time is that of the ops inside it."""
    total = {}
    for n, s, e in events:
        if lo <= s < hi and not n.startswith(CONTAINERS):
            total[op_name(n)] = total.get(op_name(n), 0) + (e - s)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in ranked]


def idle_gaps(events: list, host_spans: list, lo: float, hi: float,
              k: int = 10) -> list:
    """[[span, seconds]] of the ``k`` longest idle gaps of the device in the
    window, each named by the innermost host span open at its midpoint."""
    gaps, t = [], lo
    for s, e in merged(events, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        mid = (s + e) / 2
        open_ = [(hs, he, n) for n, hs, he in host_spans
                 if hs <= mid < he and n != WINDOW_SPAN]
        label = max(open_)[2] if open_ else "none"
        out.append([label, (e - s) * 1e-9])
    return out


def reduce(tr: dict, span_names: set) -> dict:
    """The numbers every reader may use: the window, busy time averaged over
    the devices, the op events of each device and the labelled gaps."""
    lo, hi = window(tr["host"])
    spans = [h for h in tr["host"] if h[0] in span_names]
    devs = sorted(tr["devices"])
    if not devs:
        raise ValueError("no device plane in the trace")
    busy = [busy_ns(tr["devices"][d], lo, hi) for d in devs]
    first = tr["devices"][devs[0]]
    return {
        "lo": lo, "hi": hi, "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "ops": {d: tr["devices"][d] for d in devs},
        "breakdown": {"device_ops": top_ops(first, lo, hi),
                      "idle_gaps": idle_gaps(first, spans, lo, hi)},
    }


def kernel_seconds(summary: dict, name: str) -> float:
    """A kernel's device seconds, summed over the devices in the window."""
    return sum(kernel_ns(ev, name, summary["lo"], summary["hi"])
               for ev in summary["ops"].values()) * 1e-9


def kernel_calls(summary: dict, name: str) -> int:
    """Events of a kernel in the window, summed over the devices."""
    lo, hi = summary["lo"], summary["hi"]
    return sum(1 for ev in summary["ops"].values()
               for n, s, _ in ev if lo <= s < hi and is_op(n, name))
