"""The four-chip mesh cell, at a size a CPU holds on four host devices: its
check comes out not correct for each fault planted in the program (a run
that returns its state unchanged, half of each chunk left out, an answer
altered, the halo exchange between bands left out), for the reference with
the halo left out in the program's place, and for the one-pass bf16
control, and correct for the sound program. The readers of its per-layer
metrics find nothing, and say so, where the program lacks the counter they
read.

The runs need four devices, which the CPU gives only to a process started
with ``--xla_force_host_platform_device_count``: one child process runs
them all.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH
from harness import spec

WORKLOAD = "mnist400-async-mesh4-stream"

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import sys
import time
sys.path[:0] = [os.environ["BENCH_TESTS"], os.environ["BENCH_DIR"]]
import jax
from conftest import tiny_cell
from harness import cell as cell_lib, spec
from repro.core import events
from repro.core.placement import mesh

cell = tiny_cell(os.environ["WORKLOAD"], side=8, dim=16, train=1024)
cell["traffic"].update(chunk=16, check_calls=4)
SEED = 3_000_000_123
orig = events.run_events


def planted(kind):
    def run_events(state, samples, step_keys, cfg, ecfg, **kw):
        if kind == "half":
            h = samples.shape[0] // 2
            return orig(state, samples[:h], step_keys[:h], cfg, ecfg, **kw)
        new, aux, rep = orig(state, samples, step_keys, cfg, ecfg, **kw)
        if kind == "frozen":
            return state, aux, rep
        return new, aux._replace(gmu=(aux.gmu + 1) % cfg.n_units), rep
    return run_events


outbox = mesh._Outbox


def no_halo(*args, **kwargs):
    # every band's outbox sent empty: the exchange between bands left out
    box = outbox(*args, **kwargs)
    return box._replace(up_mask=0 * box.up_mask, dn_mask=0 * box.dn_mask)


out = {}
for kind in ("none", "frozen", "half", "altered", "nohalo"):
    events.run_events = planted(kind) if kind in ("frozen", "half",
                                                  "altered") else orig
    mesh._Outbox = no_halo if kind == "nohalo" else outbox
    events._compiled_runner.cache_clear()
    res = cell_lib.run_cell(cell, SEED, 1.0, False, jax.devices(),
                            time.perf_counter())
    out[kind] = {"correct": res["correct"], "attempted": res["attempted"],
                 "checks": res["checks"], "window": res["window"]}
events.run_events, mesh._Outbox = orig, outbox
events._compiled_runner.cache_clear()
drv = spec.driver(cell["traffic"]["kind"])
run = cell_lib.Run(cell, SEED, jax.devices())
drv.setup(run)
drv.window(run, 1.0)
drv.release(run)
ok, checks = cell_lib.judge(drv.check(run), cell["limits"])
out["program_again"] = {"correct": ok, "checks": checks}
ok, checks = cell_lib.judge(drv.stand_in(run, "bf16", "none"),
                            cell["limits"])
out["control_bf16"] = {"correct": ok, "checks": checks}
ok, checks = cell_lib.judge(drv.stand_in(run, "highest", "nohalo"),
                            cell["limits"])
out["reference_nohalo"] = {"correct": ok, "checks": checks}
out["halo_sent"] = [int(r["halo_sent"]) for r in run.state["records"]]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_runs():
    env = dict(os.environ, BENCH_TESTS=str(BENCH / "tests"),
               BENCH_DIR=str(BENCH), WORKLOAD=WORKLOAD, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(BENCH.parent / "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_mesh_program_is_correct(mesh_runs):
    for source in ("none", "program_again"):
        assert mesh_runs[source]["correct"] is True, mesh_runs[source]
    assert mesh_runs["none"]["window"]["w_devices"] == 4
    assert mesh_runs["none"]["checks"]["count_mismatch"]["value"] == 0


@pytest.mark.parametrize("fault", ["frozen", "half", "altered", "nohalo"])
def test_broken_mesh_program_is_not_correct(mesh_runs, fault):
    assert mesh_runs[fault]["attempted"] > 0
    assert mesh_runs[fault]["correct"] is False, mesh_runs[fault]["checks"]


def test_reference_without_the_halo_is_not_correct(mesh_runs):
    """Bands of two rows put every unit on a band edge, so the checked
    calls send halo messages, and a replay that drops them is seen."""
    assert sum(mesh_runs["halo_sent"]) > 0, mesh_runs["halo_sent"]
    checks = mesh_runs["reference_nohalo"]["checks"]
    assert mesh_runs["reference_nohalo"]["correct"] is False, checks
    assert checks["count_mismatch"]["value"] > 0, checks


def test_one_pass_control_is_not_correct(mesh_runs):
    checks = mesh_runs["control_bf16"]["checks"]
    assert mesh_runs["control_bf16"]["correct"] is False, checks
    assert checks["q2_mae"]["value"] > checks["q2_mae"]["limit"], checks


_MISSING = {"samples": 64, "rounds": 80, "receipts": 16, "window_s": 1.0}


@pytest.mark.parametrize("metric", [
    "mesh.step_mfu", "mesh.exchanges_per_sample", "mesh.halo_share",
    "mesh.narrow_share", "mesh.collective_share"])
def test_reader_finds_nothing_where_the_counter_is_missing(metric):
    from harness import peaks, work
    ctx = {"counters": dict(_MISSING), "afm": {"side": 8, "dim": 16},
           "work": work, "peaks": peaks.peaks_for("TPU v5 lite"),
           "trace": {"lo": 0, "hi": 10, "busy_s": 1e-8, "window_s": 1e-8,
                     "ops": {"/device:TPU:0": [("%fusion.1 = f32[]", 1, 3)]}}}
    assert spec.metric_reader(metric)(ctx) is None
