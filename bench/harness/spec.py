"""Finds everything by name: the cell in ``BENCHMARK.json``, its
configuration file, its traffic mix under ``bench/traffic/``, its limits
under ``bench/limits/``, the driver of the mix's kind under
``bench/drivers/`` and each per-layer metric's reader under
``bench/metrics/``. Adding a cell, a mix or a metric adds files; no file here
changes."""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def _named(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{[e['name'] for e in entries]}")


def cell(bench: dict, workload: str, root: pathlib.Path = ROOT) -> dict:
    """Everything one cell needs, resolved by name: the workload entry, its
    configuration, its traffic mix, its limits and the metrics it
    reports in each mode."""
    wl = _named(bench["workloads"], workload, "workload")
    cfg_entry = _named(bench["configs"], wl["config"], "configuration")
    return {
        "workload": wl,
        "config": _json(root / cfg_entry["file"]),
        "traffic": _json(BENCH / "traffic" / f"{wl['traffic']}.json"),
        "limits": _json(BENCH / "limits" / f"{workload}.json"),
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in bench["per_layer"]
                      if workload in m.get("workloads", [workload])],
    }


def _module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise KeyError(f"no file {path.relative_to(ROOT)} for {name!r}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str):
    """The driver module of a traffic kind: ``bench/drivers/<kind>.py``."""
    return _module(BENCH / "drivers" / f"{kind}.py", kind)


def metric_reader(name: str):
    """The reader of a per-layer metric: ``bench/metrics/<name>.py``, whose
    ``read(ctx)`` returns the value, or None where it finds nothing."""
    return _module(BENCH / "metrics" / f"{name}.py", name).read
