"""One run of one cell: set-up, the measured (or traced) window, the metrics,
then the comparison with the reference that decides ``correct``."""
from __future__ import annotations

import math
import time

import jax

from harness import data, device, peaks, spec, trace, work


class Run:
    """What a driver builds and reads during one run."""

    def __init__(self, cell: dict, seed: int, devices):
        self.cell = cell
        self.cfg = cell["config"]
        self.traffic = cell["traffic"]
        self.seed = int(seed)
        self.key = data.root_key(seed)
        self.devices = devices
        self.state = {}

    def backend(self) -> tuple[str, dict]:
        """The configuration's backend and its options, with the traffic
        mix's options on top."""
        name = self.cfg["backend"]
        opts = {**self.cfg.get("backend_options", {}),
                **self.traffic.get("backend_options", {})}
        return name, opts

    def afm_config(self):
        from repro.api import AFMConfig
        return AFMConfig(**self.cfg["afm"])


def judge(checks: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct when every number is
    present, finite and at or under its limit."""
    out, ok = {}, True
    for name, limit in limits.items():
        value = checks.get(name)
        good = (value is not None and math.isfinite(value)
                and value <= limit)
        ok = ok and good
        out[name] = {"value": value, "limit": limit}
    return ok, out


def run_cell(cell: dict, seed: int, seconds: float, traced: bool, devices,
             t_start: float) -> dict:
    """One run. ``t_start`` is the host clock at process start, where
    set-up begins. Returns the result object the command prints."""
    drv = spec.driver(cell["traffic"]["kind"])
    run = Run(cell, seed, devices)
    compiles = device.CompileCounter()
    drv.setup(run)
    jax.effects_barrier()
    setup_s = time.perf_counter() - t_start
    before = compiles.snapshot()
    if traced:
        seconds = min(seconds, trace.WINDOW_SECONDS)
        cap = trace.Capture()
        try:
            with cap:
                with trace.span(trace.WINDOW_SPAN):
                    raw = drv.window(run, seconds)
            summary = trace.reduce(trace.load(cap.path), drv.SPANS)
        finally:
            cap.close()
    else:
        raw = drv.window(run, seconds)
    in_window = [a - b for a, b in zip(compiles.snapshot(), before)]
    mem = device.memory_peak(devices)
    dev = device.describe(devices)
    dev["memory_peak_bytes"] = mem
    result = {"correct": False, "attempted": raw["attempted"],
              "failed": raw["failed"], "metrics": {}, "device": dev}
    if traced:
        dev["busy_s"], dev["window_s"] = summary["busy_s"], summary["window_s"]
        ctx = {"counters": drv.counters(run, raw), "trace": summary,
               "peaks": peaks.peaks_for(dev["kind"]), "work": work,
               "afm": cell["config"]["afm"], "raw": raw}
        for m in cell["per_layer"]:
            value = spec.metric_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = summary["breakdown"]
    else:
        e2e = drv.end_to_end(run, raw)
        e2e["setup_s"] = setup_s
        for m in cell["end_to_end"]:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    result["window"] = {"programs_compiled_or_loaded": in_window[0],
                        "cache_misses": in_window[1],
                        "setup_programs": before[0],
                        "setup_cache_misses": before[1],
                        **raw.get("diagnostics", {})}
    drv.release(run)
    result["correct"], result["checks"] = judge(drv.check(run),
                                                cell["limits"])
    return result
