"""Runs one cell's traced window and prints what the program's own spans,
scopes and counters show in it, as one JSON line.

    python bench/trace_report.py --workload <name> --seed <n> [--keep DIR]

Set-up and the window are ``bench/run.py --trace 1``'s; the reduction is
``harness.scopes``: the per-layer readings of ``scopes.readings``, the
window's idle time put down to the innermost span open in each gap (the
program's spans and the benchmark's), the device seconds under each of the
program's scopes, the ops with the most time and their scope paths, the
seconds and count of each program span, and the traced window's own rate.
``existing`` holds every per-layer metric of the cell as its reader gives
it from ``trace.reduce``'s summary and from the larger one of
``scopes.reduce``: the two must agree. ``--keep DIR`` copies the trace
file there. No comparison with the reference is made.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

#: The program's device scopes (``repro.obs``).
SCOPES = ("afm.search", "afm.adapt", "afm.cascade", "fused.wave_keys",
          "events.pool")


def _gateway(run) -> dict:
    """The gateway's queue counters, where the cell serves through one."""
    gw = run.state.get("gw")
    if gw is None:
        return {}
    return {"queued_s": gw.stats.queued_s,
            "dispatch_requests": gw.stats.dispatch_requests}


def _rate(raw: dict) -> dict:
    from harness import stats
    if "samples" in raw:
        return {"samples_per_s": raw["samples"] / raw["window_s"]}
    if "latency_s" in raw:
        return {"p95_ms": stats.percentile(raw["latency_s"], 95) * 1e3,
                "rows_per_s": raw["rows"] / raw["window_s"]}
    return {}


def main(argv=None) -> int:
    from harness import cell as cell_lib
    from harness import device, peaks, scopes, spec, trace, work

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    cell = spec.cell(spec.benchmark(), args.workload)
    try:
        devices = device.require_chips(cell["workload"]["chips"])
    except device.NoChip as e:
        print(f"trace_report: {e}", file=sys.stderr)
        return 3
    device.enable_cache(spec.ROOT)
    drv = spec.driver(cell["traffic"]["kind"])
    run = cell_lib.Run(cell, args.seed, devices)
    drv.setup(run)
    before = _gateway(run)
    cap = trace.Capture()
    try:
        with cap:
            with trace.span(trace.WINDOW_SPAN):
                raw = drv.window(run, trace.WINDOW_SECONDS)
        tr = scopes.load(cap.path)
        plain = trace.reduce(tr, drv.SPANS)
        summary = scopes.reduce(tr, drv.SPANS)
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            shutil.copy(cap.path, os.path.join(
                args.keep, f"{args.workload}-{args.seed}.xplane.pb"))
    finally:
        cap.close()
    after = _gateway(run)
    counters = drv.counters(run, raw)
    counters.update({k: after[k] - before[k] for k in after})
    existing = {}
    for m in cell["per_layer"]:
        values = []
        for reduced in (plain, summary):
            ctx = {"counters": counters, "trace": reduced,
                   "peaks": peaks.peaks_for(device.describe(devices)["kind"]),
                   "work": work, "afm": cell["config"]["afm"], "raw": raw}
            values.append(spec.metric_reader(m["name"])(ctx))
        existing[m["name"]] = values
    drv.release(run)
    host = {name: {"s": scopes.span_s(summary, name),
                   "count": len(scopes.spans(summary, name))}
            for name in sorted(scopes.PROGRAM_SPANS)}
    out = {"workload": args.workload, "seed": args.seed,
           "device": device.describe(devices),
           "window_s": summary["window_s"], "busy_s": summary["busy_s"],
           "leaf_busy_s": scopes.leaf_busy_s(summary),
           "readings": scopes.readings(summary, counters),
           "traced_rate": _rate(raw),
           "diagnostics": raw.get("diagnostics", {}),
           "idle_by_span": scopes.idle_by_span(summary),
           "scopes_s": {s: scopes.scope_s(summary, s) for s in SCOPES},
           "spans": host,
           "breakdown": summary["breakdown"],
           "top_ops": scopes.top_ops(summary),
           "existing": existing,
           "existing_same": all(a == b for a, b in existing.values())}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
