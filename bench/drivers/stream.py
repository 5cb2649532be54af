"""Online training: ``TopoMap.partial_fit`` on consecutive chunks of the
train set (shuffled from the seed, cycled), one call per chunk, as
``stream_train`` feeds the event engine.

Traffic keys: ``chunk`` (samples per call), ``warm_calls`` (calls made in
set-up), ``check_calls`` (calls the reference replays from the seed: the
set-up's and the first of the window), ``backend_options``.

The check replays the first ``check_calls`` calls teacher-forced by the
units the program chose, compares every count of each call and the weights
and counters after the last, and checks the message accounting of every
call of the window.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import data, events_ref, reference as ref
from harness.trace import span

SPANS = {"partial_fit"}


def _lat_seed(seed: int) -> int:
    return int(seed) % (2 ** 31 - 1)


@jax.jit
def _tally(acc, rep, e):
    """Running sums of a call's report: rounds, samples, deliveries, and
    the calls whose accounting is off (samples != e, drops, stranded
    messages, or sent != delivered + dropped)."""
    off = ((rep.samples != e).astype(jnp.int32) + rep.dropped + rep.stranded
           + rep.dropped_fault + jnp.abs(
               rep.sent - (rep.deliveries + rep.dropped + rep.dropped_fault)))
    return acc + jnp.stack([rep.rounds, rep.samples, rep.deliveries, off])


def setup(run):
    from repro.api import TopoMap

    st = run.state
    afm = run.afm_config()
    name, opts = run.backend()
    opts["lat_seed"] = _lat_seed(run.seed)
    st["opts"] = opts
    st["tm"] = TopoMap(afm, backend=name, backend_options=opts)
    xtr, _, _, _ = data.make_data(run.key, run.cfg["data"])
    e = int(run.traffic["chunk"])
    nchunks = xtr.shape[0] // e
    perm = jax.random.permutation(jax.random.fold_in(run.key, 0x57E),
                                  xtr.shape[0])[:nchunks * e]
    st["chunks"] = jax.block_until_ready(
        xtr[perm].reshape(nchunks, e, xtr.shape[1]))
    st["e"], st["calls"], st["records"] = e, 0, []
    st["p"] = ref.map_params(run.cfg["afm"])
    st["key_stream"] = jax.random.fold_in(run.key, 0x5EED)
    st["acc"] = jnp.zeros((4,), jnp.int32)
    for _ in range(int(run.traffic["warm_calls"])):
        _call(run)


def _call(run):
    st = run.state
    k = st["calls"]
    x = st["chunks"][k % st["chunks"].shape[0]]
    with span("partial_fit"):
        st["tm"].partial_fit(x, key=jax.random.fold_in(st["key_stream"], k))
    rep = st["tm"].backend.last_report
    st["acc"] = _tally(st["acc"], rep, st["e"])
    if k < int(run.traffic["check_calls"]):
        aux = st["tm"].fit_aux_
        st["records"].append({
            "gmu": aux.gmu[:, 0], "q2": aux.q2[:, 0],
            "sizes": aux.cascade_size, "waves": aux.waves,
            "rounds": rep.rounds, "samples": rep.samples,
            "deliveries": rep.deliveries, "sent": rep.sent,
            "dropped": rep.dropped})
        st["w_last"], st["c_last"] = st["tm"].state_.w, st["tm"].state_.c
    st["calls"] = k + 1


def window(run, seconds: float) -> dict:
    st = run.state
    acc0, calls0 = st["acc"], st["calls"]
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        _call(run)
    acc = np.asarray(jax.block_until_ready(st["acc"]))
    t1 = time.perf_counter()
    d = acc - np.asarray(acc0)
    calls = st["calls"] - calls0
    return {"attempted": calls, "failed": 0, "calls": calls,
            "samples": calls * st["e"], "window_s": t1 - t0,
            "rounds": int(d[0]), "samples_consumed": int(d[1]),
            "deliveries": int(d[2])}


def end_to_end(run, raw: dict) -> dict:
    return {"stream_samples_per_s": raw["samples"] / raw["window_s"]}


def counters(run, raw: dict) -> dict:
    return {"samples": raw["samples_consumed"], "rounds": raw["rounds"],
            "receipts": raw["deliveries"], "window_s": raw["window_s"]}


def release(run):
    run.state.pop("tm", None)


def _keys(run, k: int):
    """(init key or None, step key) of call ``k`` as ``partial_fit`` splits
    them."""
    key = jax.random.fold_in(run.state["key_stream"], k)
    if k == 0:
        k_init, key = jax.random.split(key)
        return k_init, key
    return None, key


def _lat_keys(run, calls: int):
    lat = jax.random.PRNGKey(_lat_seed(run.seed))
    out = []
    for _ in range(calls):
        lat, sub = jax.random.split(lat)
        out.append(sub)
    return out


def _replay(run, calls: int, gmus=None, precision="highest", fault="none"):
    st = run.state
    rp = events_ref.EventReplay(st["p"], delay=st["opts"]["delay"],
                                spacing=st["opts"].get("sample_spacing", 1.0),
                                precision=precision, fault=fault)
    outs = []
    for k, lat in enumerate(_lat_keys(run, calls)):
        init_key, key = _keys(run, k)
        x = st["chunks"][k % st["chunks"].shape[0]]
        g = None if gmus is None else np.asarray(gmus[k])
        outs.append(rp.call(x, key, lat, gmu=g, init_key=init_key))
    return outs, rp


_COUNTS = ("rounds", "samples", "deliveries", "sent", "dropped")


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
    out.update(w_gap=ref.w_gap(w_last, rp.w), count_mismatch=float(mismatch))
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
