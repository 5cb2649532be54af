"""Shared benchmark utilities.

The paper's experiments (N=900, i_max=600N, e=3N) are CPU-hours at full
fidelity; every benchmark here runs a structurally identical, budget-reduced
configuration (documented per benchmark and in EXPERIMENTS.md) and the knobs
to scale back up on real hardware (--full).
"""
from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
import time

import jax

from repro.api import AFMConfig, TopoMap
from repro.data import make_dataset

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "bench")


def save(name: str, payload: dict) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


_MESH_WORKER = r"""
import importlib, json, os, sys
job = json.loads(sys.argv[1])
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + str(job["kwargs"]["shards"]))
sys.path[:0] = [job["repo"], os.path.join(job["repo"], "src")]
mod = importlib.import_module("benchmarks." + job["module"])
print(json.dumps(mod.measure(**job["kwargs"])))
"""


def mesh_point(module: str, **kwargs) -> dict:
    """One mesh point: ``benchmarks.<module>.measure(**kwargs)`` over
    ``kwargs["shards"]`` devices.

    On an accelerator it runs in this process over the real devices: the
    process already holds them, so a child could not reach them. On the
    CPU it runs in a child that forces ``shards`` host devices (XLA takes
    that flag only before jax starts). A failed point raises."""
    if jax.default_backend() != "cpu":
        return importlib.import_module(f"benchmarks.{module}").measure(
            **kwargs)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    job = json.dumps({"module": module, "kwargs": kwargs, "repo": repo})
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run([sys.executable, "-c", _MESH_WORKER, job],
                          capture_output=True, text=True, timeout=1800,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"mesh point {module} {kwargs} failed:\n"
                           f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def train_afm(key, cfg: AFMConfig, data, backend: str = "batched",
              backend_options: dict | None = None):
    """Fit a TopoMap on ``data``; returns (estimator, stacked aux, seconds)."""
    tm = TopoMap(cfg, backend=backend, backend_options=backend_options)
    t0 = time.time()
    tm.fit(data, key=key)
    return tm, tm.fit_aux_, time.time() - t0


def map_quality(tm: TopoMap, samples, side=None):
    del side  # the estimator knows its own lattice
    return tm.quantization_error(samples), tm.topographic_error(samples)


def dataset(name: str, train_size: int, test_size: int):
    return make_dataset(name, train_size=train_size, test_size=test_size)
