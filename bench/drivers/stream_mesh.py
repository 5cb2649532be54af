"""Online training of a map whose units are split over chips:
``TopoMap.partial_fit`` on consecutive chunks of the train set under the
event engine's mesh placement (``backend_options`` ``placement: mesh``,
``shards: K``), as ``stream_train --shards K`` feeds it.

Traffic keys: those of ``stream.py``, whose set-up, calls and window this
driver runs. Each call's report is also summed into the mesh's own counters
(collective steps, halo messages, narrow delivery rounds, messages sent).

The check replays the first ``check_calls`` calls with the plain mesh
reference (``harness.mesh_ref``), band by band, teacher-forced by the units
the program chose, and compares what ``stream.py`` compares (every call's
counts, each cascade's size and waves, the counters and weights after the
last call, the message accounting of every call of the window, the
distances) and, for each call, the mesh's counts: collective steps, halo
messages and narrow rounds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness import mesh_ref, reference as ref, spec

_stream = spec.driver("stream")
SPANS = _stream.SPANS
#: Report fields of the mesh's collective steps and halo messages; a program
#: without them cannot be checked, so set-up stops before any call.
MESH_FIELDS = ("exchanges", "halo_sent")
_COUNTS = ("rounds", "samples", "deliveries", "sent", "dropped",
           "exchanges", "halo_sent", "narrow_rounds")


@jax.jit
def _tally(acc, rep):
    """Running sums of a call's mesh counts: collective steps, halo
    messages, narrow rounds, messages sent."""
    return acc + jnp.stack([rep.exchanges, rep.halo_sent, rep.narrow_rounds,
                            rep.sent])


def _call(run):
    _stream_call(run)
    st = run.state
    rep = st["tm"].backend.last_report
    st["mesh_acc"] = _tally(st["mesh_acc"], rep)
    if st["calls"] <= int(run.traffic["check_calls"]):
        st["records"][-1].update(exchanges=rep.exchanges,
                                 halo_sent=rep.halo_sent,
                                 narrow_rounds=rep.narrow_rounds)


# this module's own copy of stream.py (spec.driver loads a fresh one) calls
# _call above, in set-up and in the window alike
_stream_call, _stream._call = _stream._call, _call


def setup(run):
    from repro.core import events
    missing = [f for f in MESH_FIELDS if f not in events.EventReport._fields]
    if missing:
        raise RuntimeError(
            f"the program's EventReport has no {', '.join(missing)}: the "
            f"mesh cell's check cannot be made, so no call is run")
    run.state["mesh_acc"] = jnp.zeros((4,), jnp.int32)
    _stream.setup(run)


def window(run, seconds: float) -> dict:
    st = run.state
    acc0 = np.asarray(st["mesh_acc"])
    raw = _stream.window(run, seconds)
    d = np.asarray(st["mesh_acc"]) - acc0
    raw.update(exchanges=int(d[0]), halo_sent=int(d[1]),
               narrow_rounds=int(d[2]), sent=int(d[3]),
               diagnostics={"w_devices": len(
                   st["tm"].state_.w.sharding.device_set)})
    return raw


end_to_end = _stream.end_to_end
release = _stream.release


def counters(run, raw: dict) -> dict:
    return {**_stream.counters(run, raw),
            **{k: raw[k] for k in ("exchanges", "halo_sent", "narrow_rounds",
                                   "sent")},
            "chips": raw["diagnostics"]["w_devices"]}


def _replay(run, calls: int, gmus=None, precision="highest", fault="none"):
    st = run.state
    opts = st["opts"]
    rp = mesh_ref.MeshReplay(st["p"], shards=int(opts["shards"]),
                             delay=opts["delay"], latency=opts["latency"],
                             spacing=opts.get("sample_spacing", 1.0),
                             precision=precision, fault=fault)
    outs = []
    for k, lat in enumerate(_stream._lat_keys(run, calls)):
        init_key, key = _stream._keys(run, k)
        x = st["chunks"][k % st["chunks"].shape[0]]
        g = None if gmus is None else np.asarray(gmus[k])
        outs.append(rp.call(x, key, lat, gmu=g, init_key=init_key))
    return outs, rp


def _compare(run, prog: list, w_last, c_last) -> dict:
    refs, rp = _replay(run, len(prog), gmus=[p["gmu"] for p in prog])
    mismatch, best, at_g, q2 = 0, [], [], []
    for p, r in zip(prog, refs):
        mismatch += sum(abs(int(p[k]) - int(r[k])) for k in _COUNTS)
        n = min(len(p["q2"]), len(r["q2"]))
        for k in ("sizes", "waves"):
            a, b = np.asarray(p[k]), np.asarray(r[k])
            mismatch += int(np.sum(a[:n] != b[:n])) + abs(len(a) - len(b))
        q2.append(np.asarray(p["q2"])[:n])
        best.append(r["best"][:n])
        at_g.append(r["at_g"][:n])
    mismatch += int(np.sum(np.asarray(c_last) != rp.c))
    out = ref.readings(np.concatenate(best), np.concatenate(at_g),
                       np.concatenate(q2))
    # the program's weights span the chips; compare them on the reference's
    out.update(w_gap=ref.w_gap(jnp.asarray(np.asarray(w_last)), rp.w),
               count_mismatch=float(mismatch))
    return out


def check(run) -> dict:
    st = run.state
    out = _compare(run, st["records"], st["w_last"], st["c_last"])
    out["count_mismatch"] += float(np.asarray(st["acc"])[3])
    return out


def stand_in(run, precision: str = "high", fault: str = "none") -> dict:
    """The reference in the program's place, at ``precision`` and with
    ``fault`` planted, over the checked calls; compared as the program's
    outputs would be (the control, and the fault readings)."""
    outs, rp = _replay(run, len(run.state["records"]), precision=precision,
                       fault=fault)
    return _compare(run, outs, rp.w, rp.c)
