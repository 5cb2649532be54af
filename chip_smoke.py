#!/usr/bin/env python3
"""Chip smoke test: train and serve a paper-scale map on a TPU.

Drives the map's main path once, in this one process, through the entry
points a user calls, at the paper's largest MNIST map (side 40, so N = 1600
units, at D = 784; ``benchmarks/fig6_scalability.py``) on the seeded MNIST
stand-in, with random initial weights made from ``--seed``.

One chip (the default) runs four phases:

  a. ``TopoMap(backend="pallas")``: staged BMU + cascade kernels;
  b. the same with ``kernel="fused"`` (the training megakernel);
  c. ``backend="async"`` at zero latency with ``kernel="fused"``;
  d. save phase a's map to a ``MapStore`` under ``results/`` and serve it
     through ``MapService`` across the whole bucket ladder.

``--chips 4`` runs only the two multi-device paths, each against the same
config on one device in this process:

  e. ``backend="sharded"`` on a 1x4 mesh;
  f. ``backend="async"`` with ``placement="mesh", shards=4``.

Every phase checks that the compiled kernels ran (not the Pallas
interpreter, not the jnp oracle) and compares its results with a jnp
reference at ``Precision.HIGHEST`` on the same chip. The lines before the
last are smoke output (phase results, compile and warm times), not
benchmark results. The last line is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The script exits non-zero, with no such line, when JAX finds no TPU or when
any phase fails. Run it from the repository root::

    python chip_smoke.py             # one chip: phases a-d
    python chip_smoke.py --chips 4   # four chips: phases e-f
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

#: The paper's largest MNIST map (``benchmarks/fig6_scalability.py``).
SIDE, DIM, BATCH = 40, 784, 16
#: Samples each training phase takes ("a few hundred").
TRAIN_SAMPLES = 320
#: Steps whose kernel output is compared with the reference step.
PARITY_STEPS = 8
#: Request sizes sent to the service: below, at and above each bucket of
#: ``serving.maps.DEFAULT_BUCKETS`` (5000 is chunked by the top bucket).
REQUEST_SIZES = (5, 8, 40, 64, 300, 512, 3000, 4096, 5000)
#: Bounds of every comparison with the HIGHEST-precision reference.
MIN_INDEX_AGREEMENT = 0.99
MAX_QE_REL = 1e-4
MAX_W_REL = 1e-4
#: Multi-device QE bound, as in ``tests/test_placement.py``.
MESH_QE_RATIO = 1.3


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def require_tpu(chips: int):
    """The devices, or exit: this script never runs anywhere but a TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees platform "
                 f"{devices[0].platform!r}); it never falls back to the CPU")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX sees {len(devices)}")
    return devices


# ------------------------------------------------------------- references


def reference_bmu(w, x):
    """Exact BMU in plain jnp at HIGHEST precision, chunked like a server."""
    import jax
    import jax.numpy as jnp

    from repro.core import search

    fn = jax.jit(search.exact_bmu)
    parts = [fn(w, x[i:i + 4096]) for i in range(0, x.shape[0], 4096)]
    return (jnp.concatenate([p[0] for p in parts]),
            jnp.concatenate([p[1] for p in parts]))


def qe_rel(q2_a, q2_b) -> float:
    import numpy as np
    qa = float(np.mean(np.sqrt(np.asarray(q2_a, np.float64))))
    qb = float(np.mean(np.sqrt(np.asarray(q2_b, np.float64))))
    return abs(qa - qb) / qb


def compare_map(tm, xte) -> dict:
    """The fitted map's BMUs through its inference engine vs the reference."""
    import numpy as np
    w = tm.state_.w
    idx = np.asarray(tm.transform(xte))
    ridx, rq2 = reference_bmu(w, xte)
    agree = float(np.mean(idx == np.asarray(ridx)))
    _, q2 = tm.engine.bmu(w, xte)
    rel = qe_rel(q2, rq2)
    check(agree >= MIN_INDEX_AGREEMENT, f"map BMU agreement {agree}")
    check(rel <= MAX_QE_REL, f"map QE relative error {rel}")
    return {"map_bmu_agreement": agree, "map_qe_rel": rel,
            "map_qe": float(np.mean(np.sqrt(np.asarray(rq2))))}


def compare_steps(step, ref_step, state, xtr, key) -> dict:
    """``PARITY_STEPS`` steps, each from the same state through the phase's
    kernels and through the reference; the run follows the kernel path."""
    import jax
    import numpy as np
    gmus, rgmus, q2s, rq2s, w_rels = [], [], [], [], []
    for k in range(PARITY_STEPS):
        ks, kd = jax.random.split(jax.random.fold_in(key, k))
        x = xtr[jax.random.randint(kd, (BATCH,), 0, xtr.shape[0])]
        new, aux = step(state, x, ks)
        ref, raux = ref_step(state, x, ks)
        gmu, rgmu = np.ravel(aux.gmu), np.ravel(raux.gmu)
        gmus.append(gmu), rgmus.append(rgmu)
        q2s.append(np.ravel(aux.q2)), rq2s.append(np.ravel(raux.q2))
        if np.array_equal(gmu, rgmu):
            # same winners: the counters must match exactly and the weights
            # to within rounding
            check(np.array_equal(np.asarray(new.c), np.asarray(ref.c)),
                  f"step {k}: counters differ with identical winners")
            w, rw = np.asarray(new.w), np.asarray(ref.w)
            w_rels.append(float(np.max(np.abs(w - rw)) / np.max(np.abs(rw))))
        state = new
    agree = float(np.mean(np.concatenate(gmus) == np.concatenate(rgmus)))
    rel = qe_rel(np.concatenate(q2s), np.concatenate(rq2s))
    w_rel = max(w_rels) if w_rels else float("nan")
    check(agree >= MIN_INDEX_AGREEMENT, f"step BMU agreement {agree}")
    check(rel <= MAX_QE_REL, f"step QE relative error {rel}")
    check(bool(w_rels) and w_rel <= MAX_W_REL,
          f"step weight relative error {w_rel} over {len(w_rels)} steps")
    return {"step_bmu_agreement": agree, "step_qe_rel": rel,
            "step_w_rel": w_rel, "steps_compared": len(w_rels)}


def assert_compiled(name: str, obj=None) -> None:
    """Kernel flags (``obj``'s, or the auto policy's) resolved to the
    compiled kernel, never the interpreter or the jnp oracle."""
    from repro.kernels.bmu import ops as bmu_ops
    flags = (bmu_ops.resolve_flags(None, None) if obj is None
             else (obj.use_pallas, obj.interpret))
    check(flags == (True, False), f"{name}: kernel flags {flags}")


def assert_lowered_kernel(jitted, *args) -> None:
    text = jitted.lower(*args).as_text()
    check("tpu_custom_call" in text, "no Pallas TPU kernel in the program")


def timed_fit(tm, xtr, ytr, key, num_steps) -> dict:
    """Fit twice from the same key: the first call compiles."""
    import jax
    out = {}
    for label in ("cold_s", "warm_s"):
        t = time.perf_counter()
        tm.fit(xtr, ytr, key=key, num_steps=num_steps)
        jax.block_until_ready(tm.state_.w)
        out[label] = round(time.perf_counter() - t, 3)
    return out


# ---------------------------------------------------------- one-chip phases


def phase_pallas(cfg, data, key, kernel: str):
    import jax

    from repro.api import TopoMap
    from repro.core import afm

    xtr, ytr, xte = data
    tm = TopoMap(cfg, backend="pallas", backend_options={"kernel": kernel})
    assert_compiled(f"pallas/{kernel}", tm.backend)
    res = timed_fit(tm, xtr, ytr, key, TRAIN_SAMPLES // BATCH)
    ref_step = jax.jit(lambda s, x, k: afm.train_step_batch(
        s, x, k, cfg, stages=afm.EXACT_STAGES))
    res.update(compare_steps(tm.backend.step, ref_step, tm.state_, xtr,
                             jax.random.fold_in(key, 1)))
    assert_lowered_kernel(tm.backend._jit_step, tm.state_, xtr[:BATCH], key)
    res.update(compare_map(tm, xte))
    return tm, res


def phase_async_fused(cfg, data, key):
    import jax

    from repro.api import TopoMap
    from repro.api.backends import get_backend

    xtr, ytr, xte = data
    tm = TopoMap(cfg, backend="async",
                 backend_options={"kernel": "fused", "search": "exact"})
    check(tm.backend.ecfg.kernel == "fused", "async kernel option lost")
    check(tm.backend.ecfg.latency == "zero", "async latency is not zero")
    assert_compiled("async/fused")
    res = timed_fit(tm, xtr, ytr, key, TRAIN_SAMPLES)
    ref = get_backend("reference", cfg, search="exact")
    res.update(compare_steps(tm.backend.step, ref.step, tm.state_, xtr,
                             jax.random.fold_in(key, 2)))
    res.update(compare_map(tm, xte))
    return tm, res


def phase_serve(tm, xte):
    import numpy as np

    from repro.api import MapStore
    from repro.serving import MapService

    root = os.path.join(HERE, "results", "chip_smoke_store")
    shutil.rmtree(root, ignore_errors=True)
    spec = MapStore(root).save(tm, f"mnist-{SIDE}x{SIDE}")
    svc = MapService.from_store(root, spec)
    assert_compiled("MapService engine", svc.engine)
    w = svc.snapshot()[0].w
    labels = np.asarray(svc.snapshot()[1])
    assert_lowered_kernel(svc.engine._call, w, xte[:svc.engine.buckets[0]])
    res = {"spec": spec, "requests": 0}
    worst_agree, worst_rel = 1.0, 0.0
    t = time.perf_counter()
    for n in REQUEST_SIZES:
        x = xte[:n]
        ridx, rq2 = reference_bmu(w, x)
        ridx = np.asarray(ridx)
        idx = np.asarray(svc.transform(x))
        pred = np.asarray(svc.predict(x))
        qe = np.asarray(svc.quantization_errors(x))
        check(idx.shape == (n,) and pred.shape == (n,) and qe.shape == (n,),
              f"request of {n}: shapes {idx.shape} {pred.shape} {qe.shape}")
        check(bool(np.all(np.isfinite(qe))), f"request of {n}: non-finite QE")
        agree = min(float(np.mean(idx == ridx)),
                    float(np.mean(pred == labels[ridx])))
        rel = qe_rel(qe ** 2, rq2)
        check(agree >= MIN_INDEX_AGREEMENT, f"request of {n}: agree {agree}")
        check(rel <= MAX_QE_REL, f"request of {n}: QE rel {rel}")
        worst_agree, worst_rel = min(worst_agree, agree), max(worst_rel, rel)
        res["requests"] += 3
    res["cold_s"] = round(time.perf_counter() - t, 3)
    t = time.perf_counter()
    for n in REQUEST_SIZES:
        np.asarray(svc.quantization_errors(xte[:n]))
    res["warm_s"] = round(time.perf_counter() - t, 3)
    res.update(worst_agreement=worst_agree, worst_qe_rel=worst_rel,
               engine_compiles=svc.engine.trace_count,
               buckets=list(svc.engine.buckets))
    return None, res


# -------------------------------------------------------- four-chip phases


def phase_sharded(cfg, data, key):
    from repro.api import TopoMap
    from repro.sharding import compat

    xtr, ytr, xte = data
    out = {}
    qe = {}
    for name, mesh in (("mesh_1x4", compat.make_mesh((1, 4),
                                                     ("data", "model"))),
                       ("one_device", compat.make_mesh((1, 1),
                                                       ("data", "model")))):
        tm = TopoMap(cfg, backend="sharded", backend_options={"mesh": mesh})
        out[name] = timed_fit(tm, xtr, ytr, key, TRAIN_SAMPLES // BATCH)
        state = tm.backend.init(key, xtr)
        out[name]["w_devices"] = len(state.w.sharding.device_set)
        qe[name] = tm.quantization_error(xte)
        out[name]["qe"] = qe[name]
    check(out["mesh_1x4"]["w_devices"] == 4,
          f"sharded state spans {out['mesh_1x4']['w_devices']} devices")
    check(qe["mesh_1x4"] < MESH_QE_RATIO * qe["one_device"],
          f"sharded QE {qe['mesh_1x4']} vs one device {qe['one_device']}")
    return None, out


def _accounting(rep) -> dict:
    """Message conservation, per shard and globally."""
    import numpy as np
    rows = np.asarray(rep.shard_counts, np.int64)
    # columns: [sent, delivered, dropped_overflow + stranded, fault, stranded]
    unaccounted = [int(r[0] - (r[1] + r[2] + r[3])) for r in rows]
    sums = (int(rows[:, 0].sum()) == int(rep.sent)
            and int(rows[:, 1].sum()) == int(rep.deliveries)
            and int(rows[:, 3].sum()) == int(rep.dropped_fault))
    glob = int(rep.sent) - (int(rep.deliveries) + int(rep.dropped)
                            + int(rep.dropped_fault))
    check(unaccounted == [0] * len(rows), f"per-shard {unaccounted}")
    check(sums, "shard rows do not sum to the global counters")
    check(glob == 0, f"{glob} messages unaccounted globally")
    return {"shards": len(rows), "sent": int(rep.sent),
            "deliveries": int(rep.deliveries), "dropped": int(rep.dropped),
            "samples": int(rep.samples)}


def phase_async_mesh(cfg, data, key):
    from repro.api import TopoMap

    xtr, ytr, xte = data
    out = {}
    qe = {}
    for name, opts in (("mesh_4", {"placement": "mesh", "shards": 4}),
                       ("one_device", {})):
        tm = TopoMap(cfg, backend="async",
                     backend_options={"search": "exact", **opts})
        out[name] = timed_fit(tm, xtr, ytr, key, TRAIN_SAMPLES)
        rep = tm.backend.last_report
        out[name].update(_accounting(rep))
        check(out[name]["samples"] == TRAIN_SAMPLES,
              f"{name}: {out[name]['samples']} samples consumed")
        check(out[name]["dropped"] == 0, f"{name}: messages dropped")
        qe[name] = tm.quantization_error(xte)
        out[name]["qe"] = qe[name]
        out[name]["w_devices"] = len(tm.state_.w.sharding.device_set)
    check(out["mesh_4"]["w_devices"] == 4,
          f"mesh state spans {out['mesh_4']['w_devices']} devices")
    check(qe["mesh_4"] < MESH_QE_RATIO * qe["one_device"],
          f"mesh QE {qe['mesh_4']} vs one device {qe['one_device']}")
    return None, out


# ------------------------------------------------------------------- main


def run_phase(name: str, phase, *args):
    """Run one phase, log its result line, return its estimator (or None).
    A failed check raises, and the script exits non-zero."""
    t = time.perf_counter()
    tm, res = phase(*args)
    log(f"phase {name}: ok in {time.perf_counter() - t:.1f} s "
        f"{json.dumps(res, sort_keys=True)}")
    return tm


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-d on one chip; 4: the mesh phases")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devices = require_tpu(args.chips)

    import jax

    from repro import compile_cache
    from repro.api import AFMConfig
    from repro.data import make_dataset

    log(f"compile cache: {compile_cache.enable()}")
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    cfg = AFMConfig(side=SIDE, dim=DIM, batch=BATCH, e_factor=1.0,
                    i_max=40 * SIDE * SIDE)
    xtr, ytr, xte, _ = make_dataset("mnist", seed=args.seed, train_size=4000,
                                    test_size=max(REQUEST_SIZES),
                                    real_data_ok=False)
    data = (xtr, ytr, xte)
    key = jax.random.PRNGKey(args.seed)

    if args.chips == 1:
        tm = run_phase("a pallas/staged", phase_pallas, cfg, data, key,
                       "staged")
        run_phase("b pallas/fused", phase_pallas, cfg, data, key, "fused")
        run_phase("c async/fused", phase_async_fused, cfg, data, key)
        run_phase("d serve", phase_serve, tm, xte)
    else:
        run_phase("e sharded 1x4", phase_sharded, cfg, data, key)
        run_phase("f async mesh x4", phase_async_mesh, cfg, data, key)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
