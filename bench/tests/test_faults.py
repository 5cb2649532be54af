"""A run whose timed path is broken underneath, at a size a CPU holds, comes
out not correct: once for each fault its cell can have. The faults are
planted in the program: a step that returns its state unchanged, half of
each batch left out with the mean taken over the rest, and an answer altered
where it is produced. (No cell spans chips, so none can lose an exchange.)
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import pytest

from conftest import CELLS, tiny_cell
from harness import cell as cell_lib


def _fit_fault(monkeypatch, kind):
    from repro.core import afm
    orig = afm._step

    def step(state, samples, key, cfg, stages=afm.DEFAULT_STAGES):
        b = samples.shape[0]
        if kind == "half":
            new, aux = orig(state, samples[: b // 2], key, cfg, stages)
            twice = lambda a: jnp.concatenate([a, a])
            aux = aux._replace(gmu=twice(aux.gmu), q2=twice(aux.q2),
                               greedy_steps=twice(aux.greedy_steps))
            return new._replace(i=state.i + b), aux
        new, aux = orig(state, samples, key, cfg, stages)
        if kind == "frozen":
            return state._replace(i=new.i), aux
        return new, aux._replace(gmu=(aux.gmu + 1) % cfg.n_units)

    monkeypatch.setattr(afm, "_step", step)


def _stream_fault(monkeypatch, kind):
    from repro.core import events
    orig = events.run_events

    def run_events(state, samples, step_keys, cfg, ecfg, **kw):
        if kind == "half":
            h = samples.shape[0] // 2
            return orig(state, samples[:h], step_keys[:h], cfg, ecfg, **kw)
        new, aux, rep = orig(state, samples, step_keys, cfg, ecfg, **kw)
        if kind == "frozen":
            return state, aux, rep
        return new, aux._replace(gmu=(aux.gmu + 1) % cfg.n_units), rep

    monkeypatch.setattr(events, "run_events", run_events)


def _serve_fault(monkeypatch, kind):
    from repro.serving import maps
    orig = maps.BmuEngine.bmu

    def bmu(self, w, data, *, cap=None):
        idx, q2 = orig(self, w, data, cap=cap)
        if kind == "half":
            n = idx.shape[0]
            h = (n + 1) // 2
            return (jnp.concatenate([idx[:h], idx[:n - h]]),
                    jnp.concatenate([q2[:h], q2[:n - h]]))
        return (idx + 1) % w.shape[0], q2

    monkeypatch.setattr(maps.BmuEngine, "bmu", bmu)


FAULTS = [("fit", "frozen", _fit_fault), ("fit", "half", _fit_fault),
          ("fit", "altered", _fit_fault),
          ("stream", "frozen", _stream_fault),
          ("stream", "half", _stream_fault),
          ("stream", "altered", _stream_fault),
          ("serve", "half", _serve_fault),
          ("serve", "altered", _serve_fault)]


@pytest.mark.parametrize("kind,fault,plant", FAULTS,
                         ids=[f"{k}-{f}" for k, f, _ in FAULTS])
def test_broken_timed_path_is_not_correct(kind, fault, plant, monkeypatch,
                                          cpu_devices):
    cell = tiny_cell(CELLS[kind])
    plant(monkeypatch, fault)
    result = cell_lib.run_cell(cell, 3_000_000_123, 1.0, False, cpu_devices,
                               time.perf_counter())
    assert result["attempted"] > 0
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_sound_timed_path_is_correct(kind, cpu_devices):
    cell = tiny_cell(CELLS[kind])
    result = cell_lib.run_cell(cell, 3_000_000_123, 1.0, False, cpu_devices,
                               time.perf_counter())
    assert result["correct"] is True, result["checks"]
