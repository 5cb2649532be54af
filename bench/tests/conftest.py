"""Shared helpers of the benchmark's own tests. They run on the CPU at tiny
sizes: ``python -m pytest bench/tests``."""
from __future__ import annotations

import copy
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

#: The committed cells, by traffic kind, that the tiny cells stand for.
CELLS = {"fit": "mnist40-fit-fused", "stream": "mnist40-async-exp-stream",
         "serve": "mnist40-serve-open"}


def tiny_cell(workload: str, side: int = 4, dim: int = 8,
              train: int = 512) -> dict:
    """The committed cell ``workload`` with its limits, cut to a small map
    (4 x 4 over 8-wide inputs and a few hundred rows by default), so that a
    CPU runs it in seconds."""
    from harness import spec

    cell = copy.deepcopy(spec.cell(spec.benchmark(), workload))
    cell["config"]["afm"].update(side=side, dim=dim, i_max=16 * side * side)
    cell["config"]["data"].update(dim=dim, train=train, test=128)
    t = cell["traffic"]
    if t["kind"] == "stream":
        t.update(chunk=8, check_calls=4)
    if t["kind"] == "serve":
        t.update(rate_hz=100, wait_s=5, coalesce_max=16)
    return cell


@pytest.fixture
def cpu_devices():
    import jax
    return jax.devices("cpu")
