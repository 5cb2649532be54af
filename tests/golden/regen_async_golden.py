"""Regenerate the event-engine golden fingerprints (``async_engine.npz``).

The goldens pin the engine's *round semantics* bitwise: they were generated
from the PR-4 dense engine (pre sparse-round optimization, PR 5) and every
subsequent engine rewrite must reproduce them exactly — weights, counters,
per-sample aux, and the full ``EventReport`` — across all three latency
models. They were recorded with the non-partitionable threefry stream
(``jax_threefry_partitionable=False``), so ``main`` and the golden test both
run the cases under ``jax.threefry_partitionable(False)``. Regenerate ONLY
when the round semantics change on purpose:

    PYTHONPATH=src python tests/golden/regen_async_golden.py

and say so loudly in the PR description.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import afm, events
from repro.core.afm import AFMConfig

HERE = os.path.dirname(os.path.abspath(__file__))
PATH = os.path.join(HERE, "async_engine.npz")

def _p_hot(i, cfg):
    """Schedule override that keeps cascade traffic heavy for the whole run
    (the default schedule barely fires at golden-sized budgets)."""
    del i, cfg
    return jnp.float32(0.8)


#: (name, cfg, num_events, EventConfig kwargs, hot) — small enough to run in
#: CI, big enough that cascades actually overlap at nonzero latency. The
#: ``hot`` cases force p = 0.8 and a low theta so every latency model
#: processes real message traffic (overlapping cascades, in-flight fronts).
CASES = [
    ("small_zero", AFMConfig(side=6, dim=12, i_max=48, e_factor=0.5),
     48, dict(), False),
    ("ten_zero", AFMConfig(side=10, dim=8, i_max=100, e_factor=0.3),
     100, dict(), False),
    ("ten_const", AFMConfig(side=10, dim=8, i_max=100, e_factor=0.3),
     100, dict(latency="constant", delay=1.5), False),
    ("ten_exp", AFMConfig(side=10, dim=8, i_max=100, e_factor=0.3),
     100, dict(latency="exponential", delay=1.5), False),
    ("hot_zero", AFMConfig(side=6, dim=4, theta=3, i_max=96, e_factor=0.5),
     96, dict(), True),
    ("hot_const", AFMConfig(side=6, dim=4, theta=3, i_max=96, e_factor=0.5),
     96, dict(latency="constant", delay=2.5), True),
    ("hot_exp", AFMConfig(side=6, dim=4, theta=3, i_max=96, e_factor=0.5),
     96, dict(latency="exponential", delay=2.5), True),
    # undersized pool: pins which messages overflow and how drops are counted
    ("tiny_pool", AFMConfig(side=6, dim=4, theta=3, i_max=96, e_factor=0.5),
     96, dict(latency="constant", delay=2.5, capacity=12), True),
]

#: Zero-latency cases the fused-megakernel runner must replay bitwise
#: (``EventConfig(kernel='fused-interpret')`` — the real Pallas kernel body
#: in the interpreter). ``tiny_pool`` is excluded by construction: its
#: capacity (12 < 4N) disqualifies the fast path the kernel rides on, and
#: its latency model is nonzero anyway. The goldens themselves are
#: unchanged — the megakernel is pinned against the same fingerprints as
#: every other runner.
FUSED_CASES = ["small_zero", "ten_zero", "hot_zero"]


#: ``q2`` (the reported distance) is the one field allowed to move: XLA:CPU's
#: distance reduction rounds it differently across builds (1-2 ULP
#: measured), so it is held to this bound. Every other field is bitwise.
Q2_ULP_BOUND = 4


def assert_matches_golden(out: dict, gold, case: str, label: str = ""):
    """Compare one run's fields with the recorded fingerprints."""
    for k, v in out.items():
        want = gold[f"{case}/{k}"]
        if k == "q2":
            ulp = np.abs(np.asarray(v, np.float32).view(np.int32)
                         .astype(np.int64)
                         - want.view(np.int32).astype(np.int64))
            assert ulp.max() <= Q2_ULP_BOUND, (
                f"{case}/q2 {label}: {ulp.max()} ULP")
            continue
        np.testing.assert_array_equal(np.asarray(v), want,
                                      err_msg=f"{case}/{k} {label}")


def run_case(cfg: AFMConfig, num_events: int, ekw: dict, hot: bool):
    """One seeded engine run; seeds are derived from the config so cases
    stay independent."""
    key = jax.random.PRNGKey(cfg.side * 1000 + cfg.dim)
    k_init, k_data, k_steps, k_lat = jax.random.split(key, 4)
    data = jax.random.normal(k_data, (256, cfg.dim))
    state = afm.init(k_init, cfg, data)
    samples = data[:num_events]
    step_keys = jax.random.split(k_steps, num_events)
    kw = dict(p_fn=_p_hot) if hot else {}
    st, aux, rep = events.run_events(
        state, samples, step_keys, cfg, events.EventConfig(**ekw),
        lat_key=k_lat, **kw)
    return {
        "w": np.asarray(st.w), "c": np.asarray(st.c),
        "i": np.asarray(st.i),
        "gmu": np.asarray(aux.gmu), "q2": np.asarray(aux.q2),
        "cascade_size": np.asarray(aux.cascade_size),
        "waves": np.asarray(aux.waves),
        "greedy_steps": np.asarray(aux.greedy_steps),
        "rounds": np.asarray(rep.rounds), "samples": np.asarray(rep.samples),
        "deliveries": np.asarray(rep.deliveries),
        "dropped": np.asarray(rep.dropped), "t_end": np.asarray(rep.t_end),
        "clock": np.asarray(rep.clock), "nevents": np.asarray(rep.nevents),
    }


def main():
    payload = {}
    for name, cfg, num_events, ekw, hot in CASES:
        with jax.threefry_partitionable(False):
            out = run_case(cfg, num_events, ekw, hot)
        for k, v in out.items():
            payload[f"{name}/{k}"] = v
        print(f"{name}: rounds={out['rounds']}, deliveries="
              f"{out['deliveries']}, dropped={out['dropped']}")
    np.savez(PATH, **payload)
    print(f"wrote {PATH} ({len(payload)} arrays)")


if __name__ == "__main__":
    main()
