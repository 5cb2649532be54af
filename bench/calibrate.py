"""Readings that the limits of ``correct`` are set from; not run by the
benchmark's own runs.

For one cell, in one process over many seeds: the program's compared numbers
after a short window (the lower readings), and the same numbers for the
reference put in the program's place at a lower precision (the control) or
with a fault planted (the upper readings).

    python bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 3 \\
        --stand-ins high:none,highest:frozen,highest:half,highest:altered

Each reading is one JSON line on standard output, with the verdict that the
cell's committed limits give it (``correct``, as ``bench/run.py`` would
print it) and, for the program, the window's end-to-end metrics; the last
line sums them up: per number, the largest program reading and the least
reading of each stand-in, and per source the seeds it came out correct on.
``--sweep-rates`` instead offers a serving cell's traffic at each rate in
turn, to find the highest rate it sustains.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def readings(cell: dict, seeds: list, seconds: float, stand_ins: list,
             devices, emit=print, stand_in_seeds: int | None = None) -> dict:
    """Run the cell's program on each seed, and each stand-in after it on the
    first ``stand_in_seeds`` seeds (all by default); returns {source:
    {number: [readings]}}, with the seeds judged correct under
    ``"correct_on"``."""
    from harness import cell as cell_lib
    from harness import spec

    drv = spec.driver(cell["traffic"]["kind"])
    out = {}
    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        run = cell_lib.Run(cell, seed, devices)
        drv.setup(run)
        raw = drv.window(run, seconds)
        drv.release(run)
        rows = [("program", drv.check(run))]
        if stand_in_seeds is None or n < stand_in_seeds:
            rows += [(f"{p}:{f}", drv.stand_in(run, p, f))
                     for p, f in stand_ins]
        for source, checks in rows:
            ok, _ = cell_lib.judge(checks, cell["limits"])
            got = out.setdefault(source, {"correct_on": []})
            if ok:
                got["correct_on"].append(seed)
            for k, v in checks.items():
                got.setdefault(k, []).append(v)
            line = {"seed": seed, "source": source, "correct": ok,
                    "attempted": raw["attempted"], "checks": checks}
            if source == "program":
                line["end_to_end"] = drv.end_to_end(run, raw)
            emit(json.dumps(line))
        emit(json.dumps({"seed": seed, "seconds": time.perf_counter() - t}))
    return out


def sweep(cell: dict, seed: int, rates: list, seconds: float, devices,
          emit=print) -> None:
    """Serving only: one set-up, then a window at each offered rate; prints
    the tail, the rows served and whether the backlog grew (the last
    third's median latency against the first third's)."""
    import numpy as np

    from harness import cell as cell_lib
    from harness import spec, stats

    from harness import device

    drv = spec.driver(cell["traffic"]["kind"])
    run = cell_lib.Run(cell, seed, devices)
    compiles = device.CompileCounter()
    drv.setup(run)
    for rate in rates:
        run.traffic["rate_hz"] = rate
        before = compiles.programs
        raw = drv.window(run, seconds)
        lat = np.asarray(raw["latency_s"])
        third = max(1, len(lat) // 3)
        emit(json.dumps({
            "rate_hz": rate, "requests": len(lat), "failed": raw["failed"],
            "p50_ms": stats.percentile(lat, 50) * 1e3,
            "p95_ms": stats.percentile(lat, 95) * 1e3,
            "first_third_p50_ms": float(np.median(lat[:third])) * 1e3,
            "last_third_p50_ms": float(np.median(lat[-third:])) * 1e3,
            "rows_per_s": raw["rows"] / raw["window_s"],
            "requests_per_dispatch": (raw["dispatch_requests"]
                                      / max(1, raw["dispatches"])),
            "programs_in_window": compiles.programs - before,
            **raw["diagnostics"]}))
    drv.release(run)


def main(argv=None) -> int:
    from harness import device, spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--stand-ins", default="high:none")
    ap.add_argument("--stand-in-seeds", type=int, default=None,
                    help="run the stand-ins on this many seeds only")
    ap.add_argument("--sweep-rates", default="",
                    help="serving: offered rates (Hz) to sweep instead")
    args = ap.parse_args(argv)
    cell = spec.cell(spec.benchmark(), args.workload)
    devices = device.require_chips(cell["workload"]["chips"])
    device.enable_cache(spec.ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.sweep_rates:
        sweep(cell, seeds[0], [float(r) for r in args.sweep_rates.split(",")],
              args.seconds, devices)
        return 0
    stand_ins = [tuple(s.split(":")) for s in args.stand_ins.split(",") if s]
    out = readings(cell, seeds, args.seconds, stand_ins, devices,
                   stand_in_seeds=args.stand_in_seeds)
    summary = {src: {k: (v if k == "correct_on" else
                         max(v) if src == "program" else min(v))
                     for k, v in nums.items()} for src, nums in out.items()}
    print(json.dumps({"summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
