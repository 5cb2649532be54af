"""Whole fits back to back: ``TopoMap.fit`` from a fresh key each time, each
followed by unit labelling, the way a user trains a map to its budget.

Traffic keys: ``backend_options`` (over the configuration's).

The window closes at the end of the last fit started before the deadline.
The check replays two fits of the window from the seed (the first, and one
drawn from the seed), each teacher-forced by the units the program chose.
"""
from __future__ import annotations

import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import data, reference as ref
from harness.trace import span

SPANS = {"fit", "label"}


def setup(run):
    from repro.api import TopoMap

    afm = run.afm_config()
    name, opts = run.backend()
    run.state["tm"] = TopoMap(afm, backend=name, backend_options=opts)
    xtr, ytr, _, _ = data.make_data(run.key, run.cfg["data"])
    run.state["x"], run.state["y"] = jax.block_until_ready((xtr, ytr))
    run.state["p"] = ref.map_params(run.cfg["afm"])
    run.state["steps"] = afm.num_steps
    run.state["fit_key"] = jax.random.fold_in(run.key, 0xF17)
    run.state["pick"] = random.Random(run.seed)
    _fit(run, 0)                         # compiles the fit and the labelling


def _fit(run, k: int) -> dict:
    st = run.state
    tm = st["tm"]
    key = jax.random.fold_in(st["fit_key"], k)
    with span("fit"):
        tm.fit(st["x"], key=key)
    with span("label"):
        tm.label(st["x"], st["y"])
        labels = jax.block_until_ready(tm.unit_labels_)
    aux = tm.fit_aux_
    return {"key": key, "gmu": aux.gmu, "q2": aux.q2,
            "firings": jnp.sum(aux.cascade_size), "wave_sum": jnp.sum(aux.waves),
            "size": aux.cascade_size, "waves": aux.waves,
            "w": tm.state_.w, "c": tm.state_.c, "labels": labels}


def window(run, seconds: float) -> dict:
    st = run.state
    fits, first, drawn = 0, None, None
    sizes, waves = [], []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        rec = _fit(run, fits + 1)
        fits += 1
        sizes.append(rec["firings"])
        waves.append(rec["wave_sum"])
        if first is None:
            first = rec
        elif st["pick"].random() < 1.0 / (fits - 1):
            drawn = rec                  # uniform over the later fits
    t1 = time.perf_counter()
    st["checked"] = [r for r in (first, drawn) if r is not None]
    b = run.cfg["afm"]["batch"]
    return {"attempted": fits, "failed": 0, "fits": fits,
            "samples": fits * st["steps"] * b, "window_s": t1 - t0,
            "firings": int(sum(int(s) for s in sizes)),
            "waves": int(sum(int(w) for w in waves))}


def end_to_end(run, raw: dict) -> dict:
    return {"train_samples_per_s": raw["samples"] / raw["window_s"]}


def counters(run, raw: dict) -> dict:
    steps = raw["fits"] * run.state["steps"]
    return {"samples": raw["samples"], "steps": steps,
            "receipts": 4 * raw["firings"], "waves": raw["waves"],
            "window_s": raw["window_s"]}


def release(run):
    run.state.pop("tm", None)


def _compare(run, key, prog: dict) -> dict:
    """Compared numbers of one fit: the program's (or a stand-in's) outputs
    ``prog`` against the reference teacher-forced by ``prog['gmu']``."""
    st = run.state
    r = ref.fit_replay(st["x"], key, prog["gmu"], p_items=st["p"],
                       steps=st["steps"], free=False)
    out = ref.readings(r["best"], r["at_g"], prog["q2"])
    out.update({
        "w_gap": ref.w_gap(prog["w"], r["w"]),
        "count_mismatch": float(
            np.sum(np.asarray(prog["size"]) != np.asarray(r["size"]))
            + np.sum(np.asarray(prog["waves"]) != np.asarray(r["waves"]))
            + np.sum(np.asarray(prog["c"]) != np.asarray(r["c"]))),
    })
    classes = int(run.cfg["data"]["classes"])
    cmin = ref.class_min_dists(r["w"], st["x"], st["y"], classes=classes)
    out["label_gap"] = ref.label_gap(cmin, prog["labels"])
    return out


def _worst(results: list) -> dict:
    return {k: max(r[k] for r in results) for k in results[0]}


def check(run) -> dict:
    return _worst([_compare(run, rec["key"], rec)
                   for rec in run.state["checked"]])


def stand_in(run, precision: str = "high", fault: str = "none") -> dict:
    """The reference in the program's place, at ``precision`` and with
    ``fault`` planted, over the first checked fit's key; compared as the
    program's outputs would be (the control, and the fault readings)."""
    st = run.state
    key = st["checked"][0]["key"]
    zeros = jnp.zeros((st["steps"], run.cfg["afm"]["batch"]), jnp.int32)
    out = ref.fit_replay(st["x"], key, zeros, p_items=st["p"],
                         steps=st["steps"], free=True, precision=precision,
                         fault=fault)
    classes = int(run.cfg["data"]["classes"])
    cmin = ref.class_min_dists(out["w"], st["x"], st["y"], classes=classes,
                               precision=precision)
    return _compare(run, key, {**out, "labels": jnp.argmin(cmin, axis=1)})
