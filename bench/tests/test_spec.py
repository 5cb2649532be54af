"""The harness finds each cell's configuration, traffic mix, limits, driver
and metric readers by the names in ``BENCHMARK.json``, and the file keeps to
the benchmark's contract."""
from __future__ import annotations

import re
import subprocess
import sys

import pytest

from harness import spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
DRIVER_API = ("setup", "window", "end_to_end", "counters", "release",
              "check", "stand_in", "SPANS")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_is_found_by_name(workload):
    cell = spec.cell(BENCH, workload)
    assert cell["config"]["afm"]["side"] >= 1
    assert cell["limits"]
    drv = spec.driver(cell["traffic"]["kind"])
    assert all(hasattr(drv, a) for a in DRIVER_API)
    names = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert cell["per_layer"]


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_is_found_by_name(metric):
    assert callable(spec.metric_reader(metric))


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        spec.cell(BENCH, "no-such-cell")
    with pytest.raises(KeyError):
        spec.metric_reader("no.such_metric")


def test_benchmark_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    configs = {c["name"] for c in BENCH["configs"]}
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == configs
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
    rooflines = [m for m in BENCH["per_layer"] if "roofline" in m["name"]]
    assert all(m["unit"] == "%" for m in rooflines)


def test_command_refuses_the_cpu():
    out = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "3000000123",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "no accelerator" in out.stderr
