"""Runs one cell of the on-chip benchmark once and prints one JSON line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; everything else is found by those names under ``bench/``. The
command exits non-zero, printing no result, where JAX finds no accelerator
or fewer chips than the cell asks for. With ``--trace 0`` it prints the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics from a
profiler trace of the window. Every run then compares what the window
produced with the plain reference; each compared number and its limit are
the last lines on standard error and the last key of the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main(argv=None) -> int:
    from harness import cell as cell_lib
    from harness import device, spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(spec.benchmark(), args.workload)
    try:
        devices = device.require_chips(cell["workload"]["chips"])
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    device.enable_cache(spec.ROOT)
    result = cell_lib.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), devices, T_START)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
