"""§3.5 complexity: per sample the AFM does O(N) work (e = e_factor · N
exploration probes dominate; greedy steps and cascade sizes stay O(1)-ish),
so total training work under i_max ~ N scales ~ N².

This benchmark measures the discrete-event engine itself (``engine='event'``
so the fused zero-latency shortcut never kicks in) across a sweep of map
sizes N and across *placements*: the single-pool engine at every N, plus
mesh-partitioned points (``placement='mesh'``) run through
``common.mesh_point``: over the real devices on an accelerator, in a
subprocess with XLA host virtual devices on the CPU. Two claims come out:

- **algorithmic**: ops/sample (e + greedy steps + cascade size) grows at
  most linearly in N;
- **measured**: wall time/sample grows at most linearly in N within a
  fit budget (``time_growth_budget`` — generous, because small-N points
  are dispatch-overhead-dominated which *flatters* the ratio, and CI boxes
  are noisy).

CI runs the quick sweep and asserts the claims via ``--assert-linear``:

    PYTHONPATH=src python -m benchmarks.complexity --assert-linear \
        --json-out results

The committed ``BENCH_complexity.json`` snapshot comes from the same
entry point.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from benchmarks import common

#: wall-time growth allowance over perfect linearity (see module docstring)
TIME_GROWTH_BUDGET = 2.0
OPS_GROWTH_BUDGET = 1.5

def measure(side: int, events: int, shards: int = 1, seed: int = 7) -> dict:
    """Time ``events`` event-engine samples on a ``side``² map.

    Compiles on a throwaway call, then times ``repeat`` runs and keeps the
    best (dispatch noise only inflates, never deflates). Returns one
    benchmark row; runs under whatever devices are visible — mesh points
    call this through ``common.mesh_point``.
    """
    from repro.core import afm as afm_lib
    from repro.core import events as events_lib

    n = side * side
    cfg = afm_lib.AFMConfig(side=side, dim=3, e_factor=1.0, i_max=events)
    ecfg = events_lib.EventConfig(latency="zero", engine="event")
    placement = "mesh" if shards > 1 else "single"
    key = jax.random.PRNGKey(seed)
    k_init, k_data, k_steps = jax.random.split(key, 3)
    state = afm_lib.init(k_init, cfg)
    samples = jax.random.uniform(k_data, (events, cfg.dim))
    step_keys = jax.random.split(k_steps, events)

    def once():
        out, aux, rep = events_lib.run_events(
            state, samples, step_keys, cfg, ecfg,
            placement=placement, shards=shards)
        jax.block_until_ready(out.w)
        return out, aux, rep

    once()                                   # compile
    best, aux, rep = None, None, None
    for _ in range(2):
        t0 = time.perf_counter()
        _, aux, rep = once()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    greedy = float(np.asarray(aux.greedy_steps, np.float64).mean())
    casc = float(np.asarray(aux.cascade_size, np.float64).mean())
    return {"N": n, "side": side, "placement": placement, "shards": shards,
            "events": events, "seconds": best,
            "us_per_sample": 1e6 * best / events,
            "samples_per_sec": events / best,
            "e": cfg.e, "greedy_steps": greedy, "mean_cascade": casc,
            "ops_per_sample": cfg.e + greedy + casc,
            "rounds": int(rep.rounds), "deliveries": int(rep.deliveries),
            "dropped": int(rep.dropped)}


def run(quick: bool = True):
    sides = (6, 8, 10, 12) if quick else (8, 12, 16, 20)
    per_n = 4 if quick else 16                # events = per_n · N per point
    rows = []
    for side in sides:
        row = measure(side, events=per_n * side * side)
        rows.append(row)
        print(f"  N={row['N']:4d} single    "
              f"{row['us_per_sample']:9.1f} us/sample  "
              f"ops/sample={row['ops_per_sample']:8.1f}", flush=True)
    # mesh points at the largest sizes (even sides; 2 host devices)
    for side in sides[-2:]:
        if side % 2:
            continue
        row = common.mesh_point("complexity", side=side,
                                events=per_n * side * side, shards=2)
        rows.append(row)
        print(f"  N={row['N']:4d} mesh/s=2  "
              f"{row['us_per_sample']:9.1f} us/sample", flush=True)

    single = [r for r in rows if r["placement"] == "single"]
    lo, hi = single[0], single[-1]
    n_ratio = hi["N"] / lo["N"]
    time_growth = (hi["us_per_sample"] / lo["us_per_sample"]) / n_ratio
    ops_growth = (hi["ops_per_sample"] / lo["ops_per_sample"]) / n_ratio
    mesh_rows = [r for r in rows if r["placement"] == "mesh"]
    derived = {
        "time_growth_factor": time_growth,
        "time_growth_budget": TIME_GROWTH_BUDGET,
        "claim_time_at_most_linear": time_growth <= TIME_GROWTH_BUDGET,
        "ops_growth_factor": ops_growth,
        "claim_ops_at_most_linear": ops_growth <= OPS_GROWTH_BUDGET,
        "mesh_points": len(mesh_rows),
        "mesh_ok": all(r["dropped"] == 0 for r in mesh_rows),
    }
    common.save("complexity", {"rows": rows, "derived": derived})
    return rows, derived


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json-out", default=None, metavar="DIR",
                    help="write BENCH_complexity.json here")
    ap.add_argument("--assert-linear", action="store_true",
                    help="exit nonzero unless both linearity claims hold "
                         "and every mesh point ran drop-free (CI gate)")
    args = ap.parse_args()
    rows, derived = run(quick=not args.full)
    if args.json_out:
        os.makedirs(args.json_out, exist_ok=True)
        path = os.path.join(args.json_out, "BENCH_complexity.json")
        with open(path, "w") as f:
            json.dump({"results": rows, "derived": derived}, f, indent=1)
        print(f"wrote {path}")
    print(";".join(f"{k}={v}" for k, v in derived.items()))
    if args.assert_linear:
        bad = [k for k in ("claim_time_at_most_linear",
                           "claim_ops_at_most_linear", "mesh_ok")
               if not derived[k]]
        if not derived["mesh_points"]:
            bad.append("mesh_points=0")
        if bad:
            raise SystemExit(f"complexity claims failed: {bad}")


if __name__ == "__main__":
    main()
