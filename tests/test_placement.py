"""The placement seam (ISSUE 8): SinglePool golden parity, mesh partitioning.

Contracts under test:
- ``run_events(placement='single')`` reproduces the golden engine
  fingerprints (``tests/golden/async_engine.npz``) **bitwise** across all
  three latency models — the seam refactor changed no op;
- ``MeshPlacement(shards=1)`` equals ``SinglePool`` bitwise (it runs the
  identical single-pool runner — no partition boundary exists);
- placement resolution and validation fail fast with actionable errors
  (bad spec, indivisible side, budgeted runner under mesh, too few
  devices) — at ``run_events``, at the ``async`` backend, and at the CLIs;
- multi-shard runs (subprocess, forced XLA host devices): same
  ``(seed, shards)`` replays **bitwise** (the per-shard ``fold_in``
  seeding contract documented on ``run_events``), zero-latency training
  quality stays within tolerance of the ``reference`` backend, and the
  accounting conserves (``samples == E``, ``dropped == 0``);
- on four forced devices under exponential latency, the mesh matches the
  plain reference of its semantics (``tests/mesh_ref.py``), teacher-forced,
  count for count, including its collective steps, halo messages and
  narrow rounds; its narrow and worst-case delivery widths agree bitwise,
  and so do its narrow and wide enqueues of fires and halo arrivals.
"""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.api import AFMConfig, get_backend
from repro.core import afm, events
from repro.core.placement import MeshPlacement, SinglePool, resolve_placement

_HERE = os.path.dirname(os.path.abspath(__file__))
_GOLDEN_NPZ = os.path.join(_HERE, "golden", "async_engine.npz")


def _load_regen():
    spec = importlib.util.spec_from_file_location(
        "regen_async_golden",
        os.path.join(_HERE, "golden", "regen_async_golden.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_REGEN = _load_regen()
_CASE_BY_NAME = {name: (cfg, ne, ekw, hot)
                 for name, cfg, ne, ekw, hot in _REGEN.CASES}


def _run_case(case: str, **run_kw):
    """One seeded golden-case engine run (the regen script's seeding),
    with extra ``run_events`` kwargs — placements, engine forcing."""
    cfg, num_events, ekw, hot = _CASE_BY_NAME[case]
    ekw = dict(ekw, **run_kw.pop("ekw", {}))
    key = jax.random.PRNGKey(cfg.side * 1000 + cfg.dim)
    k_init, k_data, k_steps, k_lat = jax.random.split(key, 4)
    data = jax.random.normal(k_data, (256, cfg.dim))
    state = afm.init(k_init, cfg, data)
    kw = dict(p_fn=_REGEN._p_hot) if hot else {}
    return events.run_events(
        state, data[:num_events], jax.random.split(k_steps, num_events),
        cfg, events.EventConfig(**ekw), lat_key=k_lat, **kw, **run_kw)


def _flatten(st, aux, rep) -> dict:
    return {"w": st.w, "c": st.c, "i": st.i,
            "gmu": aux.gmu, "q2": aux.q2, "cascade_size": aux.cascade_size,
            "waves": aux.waves, "greedy_steps": aux.greedy_steps,
            "rounds": rep.rounds, "samples": rep.samples,
            "deliveries": rep.deliveries, "dropped": rep.dropped,
            "t_end": rep.t_end, "clock": rep.clock, "nevents": rep.nevents}


# ------------------------------------------ SinglePool == golden, bitwise

#: one case per latency model, plus the forced event engine at zero latency
_GOLDEN_CASES = [("small_zero", {}), ("ten_const", {}), ("ten_exp", {}),
                 ("small_zero", {"engine": "event"})]


@pytest.mark.parametrize("case,ekw", _GOLDEN_CASES,
                         ids=[f"{c}{'-event' if e else ''}"
                              for c, e in _GOLDEN_CASES])
def test_single_placement_matches_golden_bitwise(case, ekw):
    """The explicit ``placement='single'`` spelling must land on the exact
    golden fingerprints (``q2`` within the regen script's ULP bound): the
    seam is a refactor, not a new engine."""
    gold = np.load(_GOLDEN_NPZ)
    with jax.threefry_partitionable(False):   # the goldens' stream
        out = _flatten(*_run_case(case, ekw=ekw, placement="single"))
    _REGEN.assert_matches_golden(out, gold, case)


@pytest.mark.parametrize("case", ["small_zero", "ten_exp"])
def test_mesh_one_shard_equals_single_bitwise(case):
    """A 1-shard mesh has no partition boundary: it must run the identical
    single-pool runner, bit for bit (runner identity, not just tolerance)."""
    a = _flatten(*_run_case(case, placement="single"))
    b = _flatten(*_run_case(case, placement="mesh", shards=1))
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


# --------------------------------------------------- resolution/validation


def test_resolve_placement():
    assert isinstance(resolve_placement(None), SinglePool)
    assert isinstance(resolve_placement("single"), SinglePool)
    mesh = resolve_placement("mesh", shards=2)
    assert isinstance(mesh, MeshPlacement) and mesh.shards == 2
    assert resolve_placement("mesh").shards == 1
    p = MeshPlacement(shards=2)
    assert resolve_placement(p, shards=2) is p
    with pytest.raises(ValueError, match="placement"):
        resolve_placement("warp")
    with pytest.raises(ValueError, match="mesh"):
        resolve_placement("single", shards=2)
    with pytest.raises(ValueError, match="shards=3"):
        resolve_placement(p, shards=3)
    with pytest.raises(ValueError, match="shards"):
        MeshPlacement(shards=0)


def test_pool_sits_below_the_engine_and_the_placements():
    """``core.pool`` is the layer both placements call: importing it in a
    fresh interpreter loads neither ``core.events`` nor ``core.placement``,
    so no import runs back up from it."""
    code = ("import sys, repro.core.pool; print(sorted(m for m in sys.modules"
            " if m.startswith(('repro.core.events',"
            " 'repro.core.placement'))))")
    env = dict(os.environ, PYTHONPATH=os.path.join(_HERE, "..", "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]"


def test_mesh_build_validation():
    cfg = AFMConfig(side=6, dim=4, i_max=16, e_factor=0.5)
    with pytest.raises(ValueError, match="divide"):
        MeshPlacement(shards=4).build_runner(
            cfg, events.EventConfig(), 16, afm.search_heuristic, None, None)
    with pytest.raises(ValueError, match="max_rounds"):
        MeshPlacement(shards=2).build_runner(
            cfg, events.EventConfig(max_rounds=100), 16,
            afm.search_heuristic, None, None)
    if len(jax.devices()) < 2:
        with pytest.raises(ValueError, match="devices"):
            MeshPlacement(shards=2).build_runner(
                cfg, events.EventConfig(), 16,
                afm.search_heuristic, None, None)


def test_backend_placement_options_fail_fast():
    cfg = AFMConfig(side=6, dim=4, i_max=16, e_factor=0.5)
    with pytest.raises(ValueError, match="mesh"):
        get_backend("async", cfg, shards=2)          # placement left single
    with pytest.raises(ValueError, match="divide"):
        get_backend("async", cfg, placement="mesh", shards=4)
    with pytest.raises(ValueError, match="max_rounds"):
        get_backend("async", cfg, placement="mesh", shards=2,
                    max_rounds=100)
    with pytest.raises(ValueError, match="placement"):
        get_backend("async", cfg, placement="warp")
    # the valid spellings construct (runner building is deferred to run)
    assert get_backend("async", cfg, placement="mesh",
                       shards=2).placement.shards == 2
    assert get_backend("async", cfg).placement.shards == 1


# ------------------------------------- multi-shard runs (forced devices)

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax
import numpy as np
from repro.api import AFMConfig, TopoMap
from repro.core import afm, events

cfg = AFMConfig(side=6, dim=3, i_max=1024, e_factor=1.0)
key = jax.random.PRNGKey(11)
k_init, k_data, k_steps, k_fit = jax.random.split(key, 4)
E = 192
data = jax.random.uniform(k_data, (2048, cfg.dim))
samples = data[:E]
step_keys = jax.random.split(k_steps, E)

def mesh_run():
    st = afm.init(k_init, cfg, data)
    return events.run_events(st, samples, step_keys, cfg,
                             events.EventConfig(latency="zero"),
                             lat_seed=3, placement="mesh", shards=2)

st_a, aux_a, rep_a = mesh_run()
st_b, aux_b, rep_b = mesh_run()

tm_ref = TopoMap(cfg, backend="reference").fit(np.asarray(data), key=k_fit)
tm_mesh = TopoMap(cfg, backend="async",
                  backend_options={"placement": "mesh", "shards": 2}
                  ).fit(np.asarray(data), key=k_fit)
xte = np.asarray(jax.random.uniform(jax.random.fold_in(k_data, 1),
                                    (256, cfg.dim)))
q_init = float(TopoMap.from_state(afm.init(k_init, cfg, data), cfg)
               .quantization_error(xte))
print(json.dumps({
    "bitwise_repeat": bool(
        np.array_equal(np.asarray(st_a.w), np.asarray(st_b.w))
        and np.array_equal(np.asarray(st_a.c), np.asarray(st_b.c))
        and np.array_equal(np.asarray(aux_a.gmu), np.asarray(aux_b.gmu))
        and int(rep_a.rounds) == int(rep_b.rounds)),
    "samples": int(rep_a.samples), "E": E,
    "dropped": int(rep_a.dropped),
    "deliveries": int(rep_a.deliveries),
    "nan": bool(np.any(np.isnan(np.asarray(st_a.w)))),
    "q_init": q_init,
    "q_ref": float(tm_ref.quantization_error(xte)),
    "q_mesh": float(tm_mesh.quantization_error(xte)),
}))
"""


def test_mesh_determinism_quality_accounting():
    """One 2-device subprocess covering the multi-shard contracts: same
    ``(seed, shards)`` replays bitwise; zero-latency mesh training matches
    ``reference`` quality within tolerance; accounting conserves."""
    env = dict(os.environ, PYTHONPATH=os.path.join(_HERE, "..", "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["bitwise_repeat"], res       # the run_events seeding contract
    assert res["samples"] == res["E"]
    assert res["dropped"] == 0
    assert not res["nan"]
    # weights start data-sampled (afm.init), so QE begins near its floor:
    # the contract is staying in that band, not a large reduction
    assert res["q_ref"] < 1.5 * res["q_init"], res
    assert np.isfinite(res["q_mesh"]), res
    # the partitioned engine must land in the reference's quality band
    # (different PRNG partition => different trajectory, same physics)
    assert res["q_mesh"] < 1.3 * res["q_ref"], res


_MESH_FAULT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro.api import AFMConfig
from repro.core import afm, events
from repro.faults import FaultPlan

cfg = AFMConfig(side=6, dim=3, i_max=256, e_factor=1.0)
key = jax.random.PRNGKey(11)
k_init, k_data, k_steps = jax.random.split(key, 3)
E = 128
st0 = afm.init(k_init, cfg)
samples = jax.random.uniform(k_data, (E, cfg.dim))
step_keys = jax.random.split(k_steps, E)
p_one = lambda i, c: jnp.float32(1.0)

plan = FaultPlan(seed=21, p_loss=0.15, dropout_frac=0.2,
                 dropout_start=E * 0.25, dropout_len=E * 0.5,
                 shard_latency_mult=(1.0, 3.0))
ecfg = events.EventConfig(latency="constant", delay=0.5, engine="event",
                          faults=plan)

def go():
    return events.run_events(st0, samples, step_keys, cfg, ecfg,
                             p_fn=p_one, lat_key=jax.random.PRNGKey(5),
                             placement="mesh", shards=2)

st_a, _, rep_a = go()
st_b, _, rep_b = go()

rows = np.asarray(rep_a.shard_counts, np.int64)
# per-shard columns: [sent, delivered, dropped_overflow+stranded,
#                     dropped_fault, stranded]
per_shard_unaccounted = [
    int(r[0] - (r[1] + (r[2] - r[4]) + r[3] + r[4])) for r in rows
]
print(json.dumps({
    "bitwise_repeat": bool(
        np.array_equal(np.asarray(st_a.w), np.asarray(st_b.w))
        and int(rep_a.dropped_fault) == int(rep_b.dropped_fault)),
    "shard_rows": rows.tolist(),
    "per_shard_unaccounted": per_shard_unaccounted,
    "sent": int(rep_a.sent), "deliveries": int(rep_a.deliveries),
    "dropped_overflow": int(rep_a.dropped_overflow),
    "dropped_fault": int(rep_a.dropped_fault),
    "stranded": int(rep_a.stranded),
    "row_sums_match_globals": bool(
        int(rows[:, 0].sum()) == int(rep_a.sent)
        and int(rows[:, 1].sum()) == int(rep_a.deliveries)
        and int(rows[:, 3].sum()) == int(rep_a.dropped_fault)),
    "nan": bool(np.any(np.isnan(np.asarray(st_a.w)))),
}))
"""


def test_mesh_fault_accounting_per_shard_and_global():
    """ISSUE 10: under a composite fault plan (loss + dropout window +
    straggler shard) every shard satisfies
    ``sent == delivered + dropped_overflow + dropped_fault + stranded``
    exactly, the shard rows sum to the global counters, and the faulty
    run replays bitwise."""
    env = dict(os.environ, PYTHONPATH=os.path.join(_HERE, "..", "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MESH_FAULT_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["bitwise_repeat"], res
    assert not res["nan"]
    assert res["per_shard_unaccounted"] == [0, 0], res
    assert res["row_sums_match_globals"], res
    assert res["sent"] == (res["deliveries"] + res["dropped_overflow"]
                           + res["dropped_fault"] + res["stranded"]), res
    assert res["dropped_fault"] > 0, res     # the plan genuinely dropped


# ------------------ the mesh against its plain reference (forced devices)

_MESH_REF_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import sys
import jax
import jax.numpy as jnp
import numpy as np
from repro import obs
from repro.api import AFMConfig
from repro.core import afm, events, pool
from repro.core.placement import MeshPlacement
sys.path.insert(0, os.environ["MESH_REF_DIR"])
import mesh_ref

cfg = AFMConfig(side=8, dim=16, i_max=4096, e_factor=1.0)
ecfg = events.EventConfig(latency="exponential", delay=1.0)
E = 96
key = jax.random.PRNGKey(5)
k_init, k_data, k_steps, k_lat = jax.random.split(key, 4)
data = jax.random.uniform(k_data, (2 * E, cfg.dim))
COUNTS = ("rounds", "samples", "deliveries", "sent", "dropped", "exchanges",
          "halo_sent", "narrow_rounds")

def runs(ecfg=ecfg):
    # two consecutive runs, so the second starts at i0 = E with counters set
    st = afm.init(k_init, cfg, data)
    out = []
    for r in range(2):
        lat = jax.random.fold_in(k_lat, r)
        steps = jax.random.split(jax.random.fold_in(k_steps, r), E)
        x = data[r * E:(r + 1) * E]
        st, aux, rep = events.run_events(
            st, x, steps, cfg, ecfg, search=afm.search_exact, lat_key=lat,
            placement="mesh", shards=4)
        out.append((x, steps, lat, st, aux, rep))
    return out

def flat(out):
    # every output but the counts of the widths run
    return [np.asarray(a).tolist() for *_, st, aux, rep in out
            for a in (st.w, st.c, aux.gmu, aux.q2, aux.cascade_size,
                      aux.waves) + tuple(rep._replace(
                          narrow_rounds=0, narrow_fires=0, wide_fires=0))]

def set_width(width):
    pool.NARROW_WIDTH = width
    events._compiled_runner.cache_clear()

def fires(out):
    # (narrow enqueues, wide enqueues, firing incidents) of each call
    return [[int(rep.narrow_fires), int(rep.wide_fires),
             int(np.sum(aux.cascade_size))] for *_, aux, rep in out]

# a sandpile cascade over two bands of a side-16 map: every unit one short
# of theta, p = 1, constant latency; its fronts fire more units of a band
# at once than the narrow width takes, and cross the band boundary
big = AFMConfig(side=16, dim=2, theta=4, i_max=256)
p_one = lambda i, c: jnp.float32(1.0)

def cascade():
    st = afm.init(k_init, big)
    st = st._replace(c=jnp.full((big.n_units,), big.theta - 1, jnp.int32))
    x = st.w[jnp.asarray([8 * 16 + 8, 3 * 16 + 5])]
    steps = jax.random.split(k_steps, 2)
    st, aux, rep = events.run_events(
        st, x, steps, big, events.EventConfig(latency="constant", delay=1.0),
        search=afm.search_exact, p_fn=p_one, lat_key=k_lat,
        placement="mesh", shards=2)
    return [(x, steps, k_lat, st, aux, rep)]

default_width = pool.NARROW_WIDTH
narrow = runs()
again = runs()
fn = events._compiled_runner(cfg, ecfg, 4, afm.search_exact,
                             events._default_p, events._default_l_c, False,
                             MeshPlacement(4))
hlo = fn.lower(afm.init(k_init, cfg, data), data[:4],
               jax.random.split(k_steps, 4), k_lat).compile().as_text()
names = [line for line in hlo.splitlines() if "op_name=" in line]
scopes = {s: any(f"/{s}/" in line for line in names)
          for s in (obs.MESH_SEARCH, obs.MESH_EXCHANGE, obs.EVENTS_POOL,
                    obs.EVENTS_DELIVER)}
# under constant latency a fire's messages share a round; at a narrow width
# of 2 the larger rounds take the cond's wide branch
const = events.EventConfig(latency="constant", delay=1.0)
set_width(2)
mixed = runs(const)
set_width(MeshPlacement(4).pool_capacity(cfg, ecfg))
wide = runs()
const_wide = runs(const)
set_width(4 * big.n_units)
cascade_wide = cascade()
set_width(default_width)
cascade_default = cascade()
# at a width of 8, fires of up to 2 units and halos of up to 8 arrivals
# take the narrow enqueue, larger ones the wide
set_width(8)
small = runs()
small_const = runs(const)
cascade_small = cascade()

p = dict(n=cfg.n_units, side=cfg.side, dim=cfg.dim, theta=cfg.theta,
         l_s=cfg.l_s, c_o=cfg.c_o, c_s=cfg.c_s, c_m=cfg.c_m, c_d=cfg.c_d,
         i_max=cfg.total_samples, max_waves=8 * cfg.side ** 2)
rp = mesh_ref.MeshReplay(tuple(sorted(p.items())), shards=4, delay=1.0)
rp.w = afm.init(k_init, cfg, data).w
rp.c = np.zeros((cfg.n_units,), np.int64)
mismatch, prog_counts, ref_counts, best_gap = {}, [], [], 0.0
for x, steps, lat, st, aux, rep in narrow:
    got = rp.run(x, steps, lat, gmu=np.asarray(aux.gmu[:, 0]))
    prog_counts.append({k: int(getattr(rep, k)) for k in COUNTS})
    ref_counts.append({k: int(got[k]) for k in COUNTS})
    for k in ("sizes", "waves"):
        prog = np.asarray(aux.cascade_size if k == "sizes" else aux.waves)
        mismatch[k] = mismatch.get(k, 0) + int(np.sum(prog != got[k]))
    best_gap = max(best_gap, float(np.max(got["at_g"] - got["best"])))
st = narrow[-1][3]
mismatch["c"] = int(np.sum(np.asarray(st.c) != rp.c))
w_gap = float(np.max(np.abs(np.asarray(st.w) - np.asarray(rp.w))))

print(json.dumps({
    "prog_counts": prog_counts, "ref_counts": ref_counts,
    "mismatch": mismatch, "w_gap": w_gap, "best_gap": best_gap,
    "w_devices": len(st.w.sharding.device_set),
    "narrow_equals_wide": flat(narrow) == flat(wide),
    "wide_narrow_rounds": [int(o[5].narrow_rounds) for o in wide],
    "mixed_equals_wide": flat(mixed) == flat(const_wide),
    "mixed_narrow_rounds": [int(o[5].narrow_rounds) for o in mixed],
    "mixed_delivery_rounds": [int(o[5].rounds - o[5].samples) for o in mixed],
    "const_wide_narrow_rounds": [int(o[5].narrow_rounds) for o in const_wide],
    "repeat_bitwise": flat(narrow) == flat(again),
    "narrow_fires": fires(narrow),
    "small_equals_narrow": flat(small) == flat(narrow),
    "small_fires": fires(small),
    "small_const_equals_wide": flat(small_const) == flat(const_wide),
    "cascade_default_equals_wide": flat(cascade_default) == flat(cascade_wide),
    "cascade_small_equals_wide": flat(cascade_small) == flat(cascade_wide),
    "cascade_fires": [fires(o)[0] for o in (cascade_default, cascade_small,
                                             cascade_wide)],
    "cascade_halo_sent": int(cascade_wide[0][5].halo_sent),
    "scopes": scopes,
}))
"""


@pytest.fixture(scope="module")
def mesh_vs_reference():
    """One 4-device subprocess: the mesh at side 8, D = 16, exponential
    latency, two consecutive runs of 96 samples, replayed by the plain
    reference (``tests/mesh_ref.py``) teacher-forced by the program's units;
    the same runs again, and with every delivery round and enqueue at the
    worst-case width or at a width of 8; a side-16 sandpile cascade over
    two bands at three widths; and the runner's compiled HLO."""
    env = dict(os.environ, PYTHONPATH=os.path.join(_HERE, "..", "src"),
               MESH_REF_DIR=_HERE)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MESH_REF_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_mesh_matches_plain_reference_teacher_forced(mesh_vs_reference):
    """Every call's rounds, samples, deliveries, sent and dropped messages,
    each cascade's size and waves, and the final counters equal the plain
    reference's exactly. The weights agree to 1e-6: both apply the same
    float32 operations in the same order, but in separately compiled
    programs, where XLA may fuse a multiply and an add differently (they
    read 0 apart here)."""
    res = mesh_vs_reference
    assert res["prog_counts"] == res["ref_counts"], res
    assert res["mismatch"] == {"sizes": 0, "waves": 0, "c": 0}, res
    assert res["w_gap"] < 1e-6, res
    # the reference's own distances agree with the units the program chose
    assert res["best_gap"] <= 1e-5, res
    assert all(c["deliveries"] > 0 and c["halo_sent"] > 0
               for c in res["prog_counts"]), res
    assert res["w_devices"] == 4


def test_mesh_counters_agree_with_reference(mesh_vs_reference):
    """``exchanges`` (drain iterations plus sample rounds), ``halo_sent``
    and ``narrow_rounds`` count what the reference counts; under
    exponential latency every delivery round runs narrow."""
    for prog, ref in zip(mesh_vs_reference["prog_counts"],
                         mesh_vs_reference["ref_counts"]):
        for k in ("exchanges", "halo_sent", "narrow_rounds"):
            assert prog[k] == ref[k], (k, prog, ref)
        assert prog["exchanges"] >= prog["samples"]
        assert prog["narrow_rounds"] == prog["rounds"] - prog["samples"]


def test_mesh_narrow_and_wide_rounds_agree_bitwise(mesh_vs_reference):
    """The narrow and the worst-case delivery width give bit for bit the
    same weights, counters, aux and report (``narrow_rounds`` aside, which
    counts the width): under exponential latency, where every round is
    narrow, and under constant latency at a narrow width of 2, where the
    round's ``lax.cond`` takes both branches."""
    res = mesh_vs_reference
    assert res["narrow_equals_wide"]
    assert res["wide_narrow_rounds"] == [0, 0]
    assert res["mixed_equals_wide"]
    assert res["const_wide_narrow_rounds"] == [0, 0]
    for narrow, rounds in zip(res["mixed_narrow_rounds"],
                              res["mixed_delivery_rounds"]):
        assert 0 < narrow < rounds, res


def test_mesh_fire_and_halo_widths_agree_bitwise(mesh_vs_reference):
    """A band's enqueue gives bit for bit the same run at every width: at a
    width of 8 (fires of up to 2 units and halos of up to 8 arrivals
    narrow) under exponential latency, where the run also equals the plain
    reference's, and under constant latency; and in a sandpile cascade over
    two bands of a side-16 map, whose fronts fire more units of a band than
    the narrow width takes and send halo bursts across the boundary, at
    the default width and at 8, against every enqueue wide."""
    res = mesh_vs_reference
    assert res["small_equals_narrow"]
    assert res["small_const_equals_wide"]
    assert res["cascade_default_equals_wide"]
    assert res["cascade_small_equals_wide"]
    assert res["cascade_halo_sent"] > 0


def test_mesh_fire_counters_count_the_enqueues(mesh_vs_reference):
    """``narrow_fires`` and ``wide_fires`` count the enqueues of the bands'
    ``fire()`` calls that fired a unit. Under exponential latency a band's
    round fires at most one unit, so they add up to the firing incidents:
    all narrow at a width of 8; all wide at the default width, which is no
    narrower than a side-8 band's 4L = 64 candidates. The cascade's large
    fronts run wide at the default width and its small ones narrow."""
    res = mesh_vs_reference
    for (narrow, wide, fired), (small_n, small_w, small_fired) in zip(
            res["narrow_fires"], res["small_fires"]):
        assert narrow == 0 and wide == fired > 0
        assert small_w == 0 and small_n == small_fired == fired
    default, small, every = res["cascade_fires"]
    assert default[0] > 0 and default[1] > 0
    assert every[0] == 0
    assert every[1] == default[0] + default[1] == small[0] + small[1]


def test_mesh_exponential_replays_bitwise(mesh_vs_reference):
    """Same seeds and shard count reproduce bitwise under exponential
    latency, with the narrow round in the loop."""
    assert mesh_vs_reference["repeat_bitwise"]


def test_mesh_scopes_reach_the_hlo_op_name_metadata(mesh_vs_reference):
    assert all(mesh_vs_reference["scopes"].values()), \
        mesh_vs_reference["scopes"]
