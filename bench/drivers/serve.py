"""Open-loop serving through the gateway: requests of mixed sizes to the
``transform``, ``predict`` and ``quantization_errors`` endpoints of one map,
arriving at a fixed rate.

Set-up trains the map from the seed (one whole fit of the configuration's
budget, made by the plain reference, so that the weights served are a
trained map's and none of them comes from the program), labels its units
through ``TopoMap.label``, serves it from a ``MapService`` behind a
``MapGateway`` and compiles every bucket and every merged row count a
dispatch can reach.

Traffic keys: ``rate_hz``, ``sizes`` and ``shares``, ``kinds``,
``max_delay_s`` and ``coalesce_max`` (the gateway's coalescing window and
largest merged dispatch), ``wait_s`` (how long past the window's close
answers are awaited).

The check compares every answer of the window with the reference's
distances from each row to every unit and the reference's Eq. (7) labels.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import data, openloop, reference as ref, stats
from harness.trace import span

SPANS = {"gateway.submit", "generator.wait"}
MAP = "map"


def setup(run):
    from repro.api import TopoMap
    from repro.core import links
    from repro.core.afm import AFMState
    from repro.serving import MapGateway, MapService

    st = run.state
    afm = run.afm_config()
    xtr, ytr, xte, _ = data.make_data(run.key, run.cfg["data"])
    zeros = jnp.zeros((afm.num_steps, afm.batch), jnp.int32)
    w = ref.fit_replay(xtr, jax.random.fold_in(run.key, 0x3A9), zeros,
                       p_items=ref.map_params(run.cfg["afm"]),
                       steps=afm.num_steps, free=True)["w"]
    n = afm.n_units
    state = AFMState(w=w, c=jnp.zeros((n,), jnp.int32),
                     far=jnp.zeros((n, afm.phi), jnp.int32),
                     near=links.near_neighbor_table(afm.side),
                     i=jnp.int32(0))
    name, opts = run.backend()
    tm = TopoMap.from_state(state, afm, backend=name, backend_options=opts)
    tm.label(xtr, ytr)
    svc = MapService.from_estimator(tm)
    gw = MapGateway(max_delay=float(run.traffic["max_delay_s"]),
                    coalesce_max=int(run.traffic["coalesce_max"]))
    gw.attach(MAP, svc)
    xte_np = np.asarray(xte)
    for bucket in svc.engine.buckets:
        jax.block_until_ready(svc.engine.bmu(w, xte[:bucket]))
    # the engine pads and slices each dispatch eagerly, one small program per
    # merged row count: compile those of every count a merge can reach
    for rows in range(1, gw.coalesce_max + 1):
        jax.block_until_ready(svc.engine.bmu(w, xte_np[:rows]))
    for kind in run.traffic["kinds"]:
        gw.submit(MAP, xte_np[:1], kind=kind).result()
    st.update(tm=tm, svc=svc, gw=gw, w=w, xtr=xtr, ytr=ytr, xte=xte_np,
              labels=jax.block_until_ready(tm.unit_labels_),
              rng=np.random.default_rng(run.seed))


def window(run, seconds: float) -> dict:
    st, tr = run.state, run.traffic
    rng = st["rng"]
    sched = openloop.schedule(rng, float(tr["rate_hz"]), seconds,
                              tr["sizes"], tr["shares"], tr["kinds"])
    n = len(sched["t"])
    offs = rng.integers(0, st["xte"].shape[0] - max(tr["sizes"]), size=n)
    gw, xte = st["gw"], st["xte"]
    eng, gstats = st["svc"].engine, gw.stats
    pad0, disp0, dreq0 = eng.padded, gstats.dispatches, \
        gstats.dispatch_requests

    def submit(i):
        with span("gateway.submit"):
            return gw.submit(MAP, xte[offs[i]:offs[i] + sched["size"][i]],
                             kind=sched["kind"][i])

    tracker = openloop.Tracker(n)
    t0 = tracker.run(sched, submit, seconds)
    close = t0 + seconds
    with span("generator.wait"):
        tracker.wait(max(0.0, close - time.perf_counter())
                     + float(tr["wait_s"]))
    out = tracker.outcome(give_up=close + float(tr["wait_s"]))
    sent = [i for i in range(n) if tracker.futures[i] is not None]
    done = tracker.done[sent]
    rows = int(sum(sched["size"][i] for i in sent
                   if not np.isnan(tracker.done[i])))
    end = np.nanmax(done) if np.any(~np.isnan(done)) else close
    st["answers"] = [(sched["kind"][i], offs[i], sched["size"][i],
                      tracker.futures[i]) for i in sent]
    late = out["late_s"]
    return {"attempted": len(sent), "failed": out["failed"],
            "latency_s": out["latency_s"], "rows": rows,
            "requested": int(sum(sched["size"][i] for i in sent)),
            "window_s": max(end, close) - t0,
            "padded": eng.padded - pad0,
            "dispatches": gstats.dispatches - disp0,
            "dispatch_requests": gstats.dispatch_requests - dreq0,
            "diagnostics": {
                "generator_late_p95_ms": stats.percentile(late, 95) * 1e3,
                "generator_late_max_ms": max(late) * 1e3,
                "requests": len(sent)}}


def end_to_end(run, raw: dict) -> dict:
    return {"serve_p95_ms": stats.percentile(raw["latency_s"], 95) * 1e3,
            "serve_rows_per_s": raw["rows"] / raw["window_s"]}


def counters(run, raw: dict) -> dict:
    return {"requested": raw["requested"], "padded": raw["padded"],
            "dispatches": raw["dispatches"],
            "dispatch_requests": raw["dispatch_requests"],
            "window_s": raw["window_s"]}


def release(run):
    gw = run.state.pop("gw", None)
    if gw is not None:
        gw.close()
    run.state.pop("svc", None)
    run.state.pop("tm", None)


def _answers(run):
    """Rows and answers of each endpoint, from the window's requests."""
    st = run.state
    by = {k: ([], []) for k in run.traffic["kinds"]}
    missing = 0
    for kind, off, size, fut in st["answers"]:
        try:
            ans = np.asarray(fut.result(0))
        except Exception:  # noqa: BLE001 — an answer that never came
            missing += 1
            continue
        by[kind][0].append(st["xte"][off:off + size])
        by[kind][1].append(ans)
    return by, missing


@jax.jit
def _per_label_min(d, labels, classes_onehot):
    """(R, C) least distance of each row to a unit of each label."""
    big = jnp.where(classes_onehot.T[None, :, :] > 0, d[:, None, :], jnp.inf)
    return jnp.min(big, axis=2)


def _gaps(run, by: dict, labels_ref) -> dict:
    """Gaps of the answers to the reference's best, per endpoint, over the
    larger of each row's best distance and the median one."""
    st = run.state
    classes = int(run.cfg["data"]["classes"])
    onehot = jax.nn.one_hot(labels_ref, classes)
    best, got = {}, {}
    for kind, (xs, answers) in by.items():
        if not xs:
            continue
        x = np.concatenate(xs)
        a = np.concatenate(answers)
        b_parts, g_parts = [], []
        for lo in range(0, x.shape[0], 4096):
            d = ref.row_dists(jnp.asarray(x[lo:lo + 4096]), st["w"])
            ans = a[lo:lo + 4096]
            b_parts.append(np.asarray(jnp.min(d, axis=1), np.float64))
            if kind == "transform":
                g_parts.append(np.asarray(jnp.take_along_axis(
                    d, jnp.asarray(ans, jnp.int32)[:, None], 1)[:, 0]))
            elif kind == "predict":
                per = np.asarray(_per_label_min(d, labels_ref, onehot))
                g_parts.append(np.take_along_axis(
                    per, ans.astype(int)[:, None], 1)[:, 0])
            else:
                g_parts.append(np.asarray(ans, np.float64) ** 2)
        best[kind] = np.concatenate(b_parts)
        got[kind] = np.asarray(np.concatenate(g_parts), np.float64)
    med = max(float(np.median(np.concatenate(list(best.values())))), ref.EPS)

    def worst(kind, absolute=False):
        if kind not in best:
            return 0.0
        gap = got[kind] - best[kind]
        return float(np.max((np.abs(gap) if absolute else gap)
                            / np.maximum(best[kind], med)))

    return {"bmu_gap": worst("transform"), "pred_gap": worst("predict"),
            "qe_gap": worst("quantization_errors", absolute=True)}


def _reference_labels(run, precision="highest"):
    st = run.state
    cmin = ref.class_min_dists(st["w"], st["xtr"], st["ytr"],
                               classes=int(run.cfg["data"]["classes"]),
                               precision=precision)
    return cmin, jnp.argmin(cmin, axis=1).astype(jnp.int32)


def check(run) -> dict:
    by, missing = _answers(run)
    cmin, labels_ref = _reference_labels(run)
    out = _gaps(run, by, labels_ref)
    out["label_gap"] = ref.label_gap(cmin, run.state["labels"])
    out["missing"] = float(missing)
    return out


def stand_in(run, precision: str = "high", fault: str = "none") -> dict:
    """The reference in the program's place: the window's requests answered
    by distances at ``precision``, with ``fault`` planted ('altered': the
    next unit; 'half': the second half of each request answered as the
    first), then compared as the program's answers are."""
    st = run.state
    by, _ = _answers(run)
    cmin_c, labels_c = _reference_labels(run, precision)
    stood = {}
    for kind, (xs, _) in by.items():
        answers = []
        for x in xs:
            d = ref.row_dists(jnp.asarray(x), st["w"], precision=precision)
            idx = np.asarray(jnp.argmin(d, axis=1))
            q2 = np.asarray(jnp.min(d, axis=1))
            if fault == "altered":
                idx = (idx + 1) % d.shape[1]
            elif fault == "half":
                h = (len(idx) + 1) // 2
                idx = np.concatenate([idx[:h], idx[:len(idx) - h]])
                q2 = np.concatenate([q2[:h], q2[:len(q2) - h]])
            answers.append(
                idx if kind == "transform" else
                np.asarray(labels_c)[idx] if kind == "predict" else
                np.sqrt(np.maximum(q2, 0.0)))
        stood[kind] = (xs, answers)
    _, labels_ref = _reference_labels(run)
    out = _gaps(run, stood, labels_ref)
    out["label_gap"] = ref.label_gap(_reference_labels(run)[0], labels_c)
    out["missing"] = 0.0
    return out
