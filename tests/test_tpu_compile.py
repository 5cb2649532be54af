"""Compile rehearsal: the main path's Pallas kernels, compiled for TPU v5e.

Each test compiles (never runs) one kernel at the paper's widths — the
largest MNIST map, side 40 (N = 1600) at D = 784, training batch 16 — for a
described ``v5e:2x2`` topology, and asserts that the compiled program holds
the Mosaic kernel (``tpu_custom_call``). What the TPU compiler refuses
(tiling, unsupported primitives, VMEM) fails here without a chip.

The topology is described only inside the module fixture, after a test of
this file has started: the TPU compiler library admits one process at a
time, so no import, ``skipif`` or ``parametrize`` may touch it. The
persistent compilation cache is off around the compiles (entries written
for a described chip cannot be read back without one).
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.afm import AFMConfig
from repro.kernels.bmu import ops as bmu_ops
from repro.kernels.cascade.cascade import cascade_wave_pallas
from repro.kernels.fused import fused as fused_lib
from repro.kernels.fused import ops as fused_ops
from repro.serving.maps import BmuEngine, CompileCache

SIDE, DIM, BATCH = 40, 784, 16
#: Largest side whose fused step fits the kernel's VMEM budget at DIM.
FUSED_MAX_SIDE = 56


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, name, *args):
    """The compiled program holds the Mosaic kernel as op ``name`` (the
    name a device trace shows it by)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert calls
    assert any(line.startswith(f"%{name}") for line in calls), calls


@pytest.mark.parametrize("precision", bmu_ops.PRECISIONS)
def test_bmu_kernel_compiles_for_v5e(one_chip, precision):
    _assert_kernel(
        lambda w, s: bmu_ops.bmu(w, s, use_pallas=True, interpret=False,
                                 precision=precision), "bmu_pallas",
        _shape(one_chip, (SIDE * SIDE, DIM)), _shape(one_chip, (BATCH, DIM)))


def test_cascade_wave_kernel_compiles_for_v5e(one_chip):
    lattice = _shape(one_chip, (SIDE, SIDE), jnp.int32)
    _assert_kernel(lambda c, f, b: cascade_wave_pallas(c, f, b, 4),
                   "cascade_wave_pallas", lattice, lattice,
                   _shape(one_chip, (4, SIDE, SIDE), jnp.int32))


def test_fused_step_compiles_for_v5e_at_its_largest_side(one_chip):
    side = FUSED_MAX_SIDE
    assert (fused_lib.vmem_bytes(side, DIM, fused_ops.DEFAULT_WAVE_CAP)
            <= fused_lib.VMEM_LIMIT_BYTES)
    cfg = AFMConfig(side=side, dim=DIM, batch=BATCH)
    _assert_kernel(
        lambda w, c, s, k: fused_ops.fused_step_parts(
            w, c, s, k, cfg, l_c=0.5, p_i=0.3, use_pallas=True,
            interpret=False), "fused_step_pallas",
        _shape(one_chip, (side * side, DIM)),
        _shape(one_chip, (side * side,), jnp.int32),
        _shape(one_chip, (BATCH, DIM)),
        _shape(one_chip, (2,), jnp.uint32))


def test_bmu_engine_bucket_compiles_for_v5e(one_chip):
    engine = BmuEngine(use_pallas=True, interpret=False, cache=CompileCache())
    _assert_kernel(engine._call, "bmu_pallas",
                   _shape(one_chip, (SIDE * SIDE, DIM)),
                   _shape(one_chip, (engine.buckets[2], DIM)))



def test_mesh_event_runner_compiles_for_v5e_2x2(topo, monkeypatch):
    """The event engine's mesh placement over the host's four chips (row
    bands of a side-40 map at D = 784, exponential latency): the runner
    compiles, with the halo exchange as collective-permutes and the
    due-flag and min-reduce collectives beside it."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    from repro.core import afm, events
    from repro.core.placement import MeshPlacement
    from repro.core.placement import mesh as mesh_mod

    mesh = Mesh(np.array(topo.devices), (mesh_mod.AXIS,),
                axis_types=(AxisType.Auto,))
    # the runner takes its mesh from the placement's cache, which would
    # otherwise build it over the visible (CPU) devices
    monkeypatch.setitem(mesh_mod._MESHES._meshes, 4, mesh)
    cfg = AFMConfig(side=SIDE, dim=DIM, batch=1, i_max=40 * SIDE * SIDE)
    ecfg = events.EventConfig(latency="exponential", delay=1.0)
    e = 8
    placement = MeshPlacement(4)
    runner = placement.build_runner(cfg, ecfg, e, afm.search_exact,
                                    events._default_p, events._default_l_c)
    repl = NamedSharding(mesh, PartitionSpec())
    state = jax.tree.map(
        lambda x: _shape(repl, x.shape, x.dtype),
        jax.eval_shape(lambda k: afm.init(k, cfg), jax.random.PRNGKey(0)))
    text = jax.jit(runner).lower(
        state, _shape(repl, (e, DIM)), _shape(repl, (e, 2), jnp.uint32),
        _shape(repl, (2,), jnp.uint32)).compile().as_text()
    assert "collective-permute" in text
    assert "all-reduce" in text


def _hlo_computations(text):
    """Compiled HLO text -> ({computation: instruction lines},
    {instruction: result type})."""
    comps, types, cur = {}, {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line)
            inst = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (\S+)", line)
            if inst:
                types[inst.group(1)] = inst.group(2)
    return comps, types


def _called(comps, name):
    """Every computation ``name`` runs, itself included."""
    seen, todo = set(), [name]
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps.get(comp, ()):
            for group in re.findall(
                    r"(?:calls|to_apply|body|condition|branch_computations|"
                    r"called_computations)=(\{[^}]*\}|%[\w.\-]+)", line):
                todo += re.findall(r"%([\w.\-]+)", group)
    return seen


def test_event_runner_narrow_enqueue_writes_the_pool_in_place(one_chip):
    """The single-pool event runner at the async cell's shapes (side 40,
    D = 784, exponential latency, exact search): each of ``fire()``'s
    three-way enqueue branches (skip, narrow, wide) runs its narrow branch
    as a payload scatter of at most 64 rows into the f32[12800, 784] pool,
    in place: the branch holds no copy of the pool. The wide branch writes
    all 4N = 6,400 candidates."""
    from repro.core import afm, events
    from repro.core.placement import SinglePool

    cfg = AFMConfig(side=SIDE, dim=DIM, batch=1, i_max=40 * SIDE * SIDE)
    ecfg = events.EventConfig(latency="exponential", delay=1.0)
    e = 64
    runner = SinglePool().build_runner(cfg, ecfg, e, afm.search_exact,
                                       events._default_p,
                                       events._default_l_c)
    state = jax.tree.map(
        lambda x: _shape(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda k: afm.init(k, cfg), jax.random.PRNGKey(0)))
    text = jax.jit(runner).lower(
        state, _shape(one_chip, (e, DIM)),
        _shape(one_chip, (e, 2), jnp.uint32),
        _shape(one_chip, (2,), jnp.uint32)).compile().as_text()
    comps, types = _hlo_computations(text)
    pool = f"f32[{8 * cfg.n_units},{DIM}]"

    def payload_rows(branch):
        """Rows of each scatter into the payload pool the branch runs."""
        rows = []
        for comp in _called(comps, branch):
            for line in comps[comp]:
                m = re.search(r"= (\S+) scatter\(%[\w.\-]+, %[\w.\-]+, "
                              r"%([\w.\-]+)\)", line)
                if m and m.group(1).startswith(pool):
                    rows.append(int(re.match(r"f32\[(\d+),",
                                             types[m.group(2)]).group(1)))
        return rows

    def pool_copies(branch):
        return [line for comp in _called(comps, branch)
                for line in comps[comp]
                if re.search(r"= \(?" + re.escape(pool)
                             + r"\S* .*\bcopy(-start)?\(", line)]

    switches = [re.search(r"branch_computations=\{([^}]*)\}", line)
                for lines in comps.values() for line in lines
                if " conditional(" in line and pool in line]
    branches = [re.findall(r"%([\w.\-]+)", m.group(1))
                for m in switches if m]
    enqueues = [b for b in branches if len(b) == 3]
    assert enqueues, branches
    for skip, narrow, wide in enqueues:
        assert payload_rows(skip) == []
        assert payload_rows(narrow) and max(payload_rows(narrow)) <= 64
        assert pool_copies(narrow) == []
        assert 4 * cfg.n_units in payload_rows(wide)
