"""Fault-injection degradation sweep (ISSUE 10 acceptance).

The paper's robustness claim, measured: train the same map under an
escalating ``FaultPlan`` — broadcast loss ``p_loss`` and unit-dropout
fraction — and record quantization error plus the engine's full message
accounting. Two structural gates make this CI-assertable:

- **graceful degradation**: QE at ``p_loss = 0.1`` stays within
  ``DEGRADATION_BUDGET``× the fault-free QE (the map absorbs 10% broadcast
  loss without collapsing);
- **conservation**: every row satisfies
  ``sent == deliveries + dropped_overflow + dropped_fault + stranded``
  exactly — zero unaccounted messages, per shard and globally.

Single-pool rows run in-process; 2-shard mesh rows (same sweep points, plus
a straggler multiplier) run through ``common.mesh_point``, like
``benchmarks.complexity``: over the real devices on an accelerator, in a
subprocess with XLA host devices forced on the CPU. Every row uses
``engine='event'`` so the fault-free baseline and the faulty runs time the
same discrete-event runtime.

    PYTHONPATH=src python -m benchmarks.fault_bench [--full]
    # CI smoke:
    PYTHONPATH=src python -m benchmarks.fault_bench --quick \\
        --assert-degradation --json-out BENCH_faults.json
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common

#: QE at p_loss = 0.1 must stay within this factor of the fault-free QE.
DEGRADATION_BUDGET = 1.5

P_LOSS_SWEEP = (0.0, 0.05, 0.1, 0.2)
DROPOUT_SWEEP = (0.1, 0.25)

def measure(side: int, events: int, plan: dict | None,
            shards: int = 1, seed: int = 7) -> dict:
    """Train ``events`` samples on a ``side``² map under ``plan`` and
    return QE + the full message-accounting row. ``plan=None`` is the
    fault-free baseline on the identical engine path."""
    from repro.core import afm as afm_lib
    from repro.core import events as events_lib
    from repro.core import search as search_lib
    from repro.faults import resolve_plan

    cfg = afm_lib.AFMConfig(side=side, dim=3, e_factor=1.0, i_max=events)
    ecfg = events_lib.EventConfig(latency="zero", engine="event",
                                  faults=resolve_plan(plan))
    placement = "mesh" if shards > 1 else "single"
    key = jax.random.PRNGKey(seed)
    k_init, k_data, k_steps, k_eval = jax.random.split(key, 4)
    state = afm_lib.init(k_init, cfg)
    samples = jax.random.uniform(k_data, (events, cfg.dim))
    step_keys = jax.random.split(k_steps, events)
    eval_data = jax.random.uniform(k_eval, (512, cfg.dim))

    t0 = time.perf_counter()
    out, _, rep = events_lib.run_events(state, samples, step_keys, cfg, ecfg,
                                        placement=placement, shards=shards)
    jax.block_until_ready(out.w)
    seconds = time.perf_counter() - t0
    _, q2 = search_lib.exact_bmu(out.w, eval_data)
    qe = float(jnp.mean(jnp.sqrt(q2)))

    sent = int(rep.sent)
    deliveries = int(rep.deliveries)
    overflow = int(rep.dropped_overflow)
    fault = int(rep.dropped_fault)
    stranded = int(rep.stranded)
    shard_rows = np.asarray(rep.shard_counts).tolist()
    # per-shard conservation: each (K, 5) row is
    # [sent, delivered, dropped_overflow(+stranded), dropped_fault, stranded]
    shard_unaccounted = [
        row[0] - (row[1] + (row[2] - row[4]) + row[3] + row[4])
        for row in shard_rows
    ]
    return {
        "side": side, "events": events, "shards": shards,
        "plan": dict(plan or {}), "seconds": seconds, "qe": qe,
        "sent": sent, "deliveries": deliveries,
        "dropped_overflow": overflow, "dropped_fault": fault,
        "stranded": stranded, "samples_dead": int(rep.samples_dead),
        "shard_counts": shard_rows,
        "unaccounted": sent - (deliveries + overflow + fault + stranded),
        "shard_unaccounted": shard_unaccounted,
    }


def run(quick: bool = True, with_mesh: bool = True):
    side = 6 if quick else 10
    events = 16 * side * side
    window = {"dropout_start": events * 0.25, "dropout_len": events * 0.5}

    rows = []
    for p in P_LOSS_SWEEP:
        plan = {"seed": 11, "p_loss": p} if p else None
        row = measure(side, events, plan)
        row["axis"] = "p_loss"
        rows.append(row)
        print(f"  single p_loss={p:<5} qe={row['qe']:.4f} "
              f"fault={row['dropped_fault']:6d} "
              f"unaccounted={row['unaccounted']}")
    for frac in DROPOUT_SWEEP:
        plan = {"seed": 11, "dropout_frac": frac, **window}
        row = measure(side, events, plan)
        row["axis"] = "dropout"
        rows.append(row)
        print(f"  single dropout={frac:<4} qe={row['qe']:.4f} "
              f"fault={row['dropped_fault']:6d} "
              f"dead_samples={row['samples_dead']:5d} "
              f"unaccounted={row['unaccounted']}")

    mesh_rows = []
    if with_mesh:
        mesh_plans = [None,
                      {"seed": 11, "p_loss": 0.1},
                      {"seed": 11, "p_loss": 0.1, "dropout_frac": 0.1,
                       **window, "shard_latency_mult": [1.0, 1.0]}]
        for plan in mesh_plans:
            row = common.mesh_point("fault_bench", side=side, events=events,
                                    plan=plan, shards=2)
            row["axis"] = "mesh"
            mesh_rows.append(row)
            print(f"  mesh2  plan={plan or 'none'} qe={row['qe']:.4f} "
                  f"unaccounted={row['unaccounted']} "
                  f"per-shard={row['shard_unaccounted']}")

    base = rows[0]["qe"]
    at_01 = next(r["qe"] for r in rows
                 if r["axis"] == "p_loss" and r["plan"].get("p_loss") == 0.1)
    all_rows = rows + mesh_rows
    derived = {
        "qe_fault_free": round(base, 4),
        "qe_ploss_0.1": round(at_01, 4),
        "qe_ratio_ploss_0.1": round(at_01 / base, 4),
        "degradation_budget": DEGRADATION_BUDGET,
        "unaccounted_messages": max(
            [abs(r["unaccounted"]) for r in all_rows]
            + [abs(u) for r in all_rows for u in r["shard_unaccounted"]]),
        "mesh_rows": len(mesh_rows),
    }
    results = {"single": rows, "mesh": mesh_rows}
    common.save("fault_bench", results)
    return results, derived


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="small sweep (the CI smoke variant; also the "
                         "default)")
    ap.add_argument("--no-mesh", action="store_true",
                    help="skip the 2-shard mesh points")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="also write results+derived as JSON "
                         "(BENCH_faults.json, the committed artifact)")
    ap.add_argument("--assert-degradation", action="store_true",
                    help="fail unless QE at p_loss=0.1 stays within the "
                         "degradation budget of fault-free AND every row "
                         "accounts for every message")
    args = ap.parse_args()
    results, derived = run(quick=not args.full, with_mesh=not args.no_mesh)
    for k, v in derived.items():
        print(f"{k}: {v}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"results": results, "derived": derived}, f, indent=1)
        print(f"wrote {args.json_out}")
    if args.assert_degradation:
        ratio = derived["qe_ratio_ploss_0.1"]
        if ratio > DEGRADATION_BUDGET:
            raise SystemExit(
                f"degradation gate FAILED: QE ratio at p_loss=0.1 is "
                f"{ratio} > budget {DEGRADATION_BUDGET}")
        if derived["unaccounted_messages"] != 0:
            raise SystemExit(
                f"accounting gate FAILED: "
                f"{derived['unaccounted_messages']} unaccounted message(s)")
        print(f"degradation gate OK: ratio {ratio} <= {DEGRADATION_BUDGET}, "
              f"0 unaccounted messages")
