"""Fault-injection seam + crash-resume tests (ISSUE 10).

Pins the four contracts of ``repro.faults``:

- an inactive plan (``None`` / ``FaultPlan.none()`` / seed-only) builds the
  exact fault-free compute graph — bitwise, on top of the golden suite;
- a seeded faulty run replays **bitwise** for the same ``(plan, keys)``;
- every fault is counted: ``sent == deliveries + dropped_overflow +
  dropped_fault + stranded`` always, with the overflow/fault split exact;
- the quiescence watchdog raises on a silently-exhausted round budget,
  while explicit ``max_rounds`` truncation stays reported-not-raised
  (the PR-4 visibility contract).

Plus the crash-resume unit: pytree checksums, ``TrainCheckpoint``
round-trips, corruption rejection, the ``Overloaded`` retry helper, and a
kill-and-resume ``run_stream`` that reproduces the uninterrupted run
bitwise.
"""
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import afm as afm_lib
from repro.core import events as events_lib
from repro.faults import FaultPlan, resolve_plan
from repro.training import checkpoint as ckpt


def _setup(side=4, n_events=48, seed=2):
    cfg = afm_lib.AFMConfig(side=side, dim=3, e_factor=1.0, i_max=n_events)
    key = jax.random.PRNGKey(seed)
    k_init, k_data, k_steps = jax.random.split(key, 3)
    state = afm_lib.init(k_init, cfg)
    samples = jax.random.uniform(k_data, (n_events, cfg.dim))
    step_keys = jax.random.split(k_steps, n_events)
    return cfg, state, samples, step_keys


def _p_one(i, cfg):
    del i, cfg
    return jnp.float32(1.0)


def _run(faults=None, latency="constant", delay=0.5, p_fn=None,
         max_rounds=None, **setup):
    cfg, state, samples, step_keys = _setup(**setup)
    ecfg = events_lib.EventConfig(latency=latency, delay=delay,
                                  engine="event", max_rounds=max_rounds,
                                  faults=faults)
    kwargs = {"p_fn": p_fn} if p_fn is not None else {}
    out, _, rep = events_lib.run_events(state, samples, step_keys, cfg,
                                        ecfg, lat_key=jax.random.PRNGKey(5),
                                        **kwargs)
    return out, rep


def _identity(rep) -> int:
    return int(rep.sent) - (int(rep.deliveries) + int(rep.dropped_overflow)
                            + int(rep.dropped_fault) + int(rep.stranded))


# ------------------------------------------------------------ plan semantics


def test_plan_validation():
    with pytest.raises(ValueError, match="p_loss"):
        FaultPlan(p_loss=1.5)
    with pytest.raises(ValueError, match="dropout_frac"):
        FaultPlan(dropout_frac=-0.1)
    with pytest.raises(ValueError, match="shard_latency_mult"):
        FaultPlan(shard_latency_mult=(1.0, 0.0))
    with pytest.raises(ValueError, match="pool_reserve"):
        FaultPlan(pool_reserve=-1)
    with pytest.raises(ValueError, match="faults must be"):
        resolve_plan("p_loss=0.1")


def test_plan_hashable_and_resolvable():
    a = resolve_plan({"seed": 3, "p_loss": 0.1})
    assert a == FaultPlan(seed=3, p_loss=0.1)
    assert hash(a) == hash(FaultPlan(seed=3, p_loss=0.1))
    assert resolve_plan(None) is None
    assert resolve_plan(a) is a


def test_seed_only_plan_is_inactive():
    assert FaultPlan.none().is_none()
    assert FaultPlan(seed=99).is_none()
    assert not FaultPlan(p_loss=0.01).is_none()
    assert not events_lib.EventConfig(faults=FaultPlan(seed=99)).fault_active


def test_eventconfig_rejects_dict_spec():
    with pytest.raises(ValueError, match="resolved by the backend"):
        events_lib.EventConfig(faults={"p_loss": 0.1})


def test_backend_resolves_dict_spec():
    from repro.training.async_trainer import AsyncBackend
    cfg = afm_lib.AFMConfig(side=4, dim=3, i_max=16)
    be = AsyncBackend(cfg, faults={"seed": 3, "p_loss": 0.25})
    assert be.ecfg.plan == FaultPlan(seed=3, p_loss=0.25)
    assert be.ecfg.fault_active


def test_dead_units_selection_is_seeded_and_sized():
    plan = FaultPlan(seed=13, dropout_frac=0.25, dropout_len=10.0)
    m1 = np.asarray(plan.dead_units(16))
    m2 = np.asarray(plan.dead_units(16))
    np.testing.assert_array_equal(m1, m2)
    assert m1.sum() == 4
    other = np.asarray(FaultPlan(seed=14, dropout_frac=0.25,
                                 dropout_len=10.0).dead_units(16))
    assert other.sum() == 4          # same count, (almost surely) new draw


# ----------------------------------------------- fault-free bitwise contract


def test_none_plan_builds_identical_graph():
    """faults=None, FaultPlan.none(), and a seed-only plan are bitwise
    interchangeable — the golden contract, on a nonzero-latency engine."""
    base, rep0 = _run(faults=None, p_fn=_p_one)
    for plan in (FaultPlan.none(), FaultPlan(seed=77)):
        out, rep = _run(faults=plan, p_fn=_p_one)
        np.testing.assert_array_equal(np.asarray(base.w), np.asarray(out.w))
        np.testing.assert_array_equal(np.asarray(base.c), np.asarray(out.c))
        assert int(rep.deliveries) == int(rep0.deliveries)
        assert int(rep.sent) == int(rep0.sent)
        assert int(rep.dropped_fault) == 0
    # the sent counter is live even fault-free: conservation always holds
    assert int(rep0.sent) > 0 and _identity(rep0) == 0


# -------------------------------------------------------- injected-fault law


def test_loss_counted_and_replayed_bitwise():
    plan = FaultPlan(seed=21, p_loss=0.3)
    a_out, a_rep = _run(faults=plan, p_fn=_p_one)
    b_out, b_rep = _run(faults=plan, p_fn=_p_one)
    np.testing.assert_array_equal(np.asarray(a_out.w), np.asarray(b_out.w))
    assert int(a_rep.dropped_fault) == int(b_rep.dropped_fault) > 0
    assert _identity(a_rep) == 0
    # the faulty trajectory genuinely differs from fault-free
    free, _ = _run(faults=None, p_fn=_p_one)
    assert not np.array_equal(np.asarray(a_out.w), np.asarray(free.w))


def test_dropout_freezes_dead_units():
    """Dead units neither adapt nor fire for the whole window; messages to
    them are consumed as dropped_fault; they hold their initial weights."""
    n_events = 48
    plan = FaultPlan(seed=5, dropout_frac=0.5, dropout_start=0.0,
                     dropout_len=1e9)           # dead for the entire run
    # the scenario was seeded on the non-partitionable threefry stream;
    # under the partitionable one every winning unit is drawn dead, so no
    # live unit would ever adapt
    with jax.threefry_partitionable(False):
        cfg, state, samples, step_keys = _setup(n_events=n_events)
        ecfg = events_lib.EventConfig(latency="constant", delay=0.5,
                                      engine="event", faults=plan)
        out, _, rep = events_lib.run_events(state, samples, step_keys, cfg,
                                            ecfg, p_fn=_p_one,
                                            lat_key=jax.random.PRNGKey(5))
        dead = np.asarray(plan.dead_units(cfg.n_units))
    w0 = np.asarray(state.w)
    w1 = np.asarray(out.w)
    np.testing.assert_array_equal(w1[dead], w0[dead])
    assert not np.array_equal(w1[~dead], w0[~dead])
    assert int(rep.samples_dead) > 0
    assert _identity(rep) == 0


def test_pool_reserve_forces_overflow_not_fault_drops():
    plan = FaultPlan(seed=5, pool_reserve=8 * 16 - 6)   # 6 slots on a 4x4
    _, rep = _run(faults=plan, p_fn=_p_one)
    assert int(rep.dropped_overflow) > 0
    assert int(rep.dropped_fault) == 0
    assert _identity(rep) == 0


def test_straggler_mult_requires_mesh():
    with pytest.raises(ValueError, match="mesh"):
        _run(faults=FaultPlan(shard_latency_mult=(1.0, 4.0)))


def test_zero_latency_faults_leave_fast_path():
    """An active plan disqualifies the fused zero-latency scan (engine
    simulation only) but still satisfies conservation."""
    _, rep = _run(faults=FaultPlan(seed=3, p_loss=0.5), latency="zero",
                  delay=0.0, p_fn=_p_one)
    assert int(rep.rounds) > 0               # fused path reports rounds == 0
    assert int(rep.dropped_fault) > 0
    assert _identity(rep) == 0


# --------------------------------------------------- quiescence watchdog (c)


def _watchdog_setup(max_rounds=None):
    import dataclasses
    cfg, state, samples, step_keys = _setup(side=4, n_events=32)
    cfg = dataclasses.replace(cfg, max_waves=1, theta=1)
    ecfg = events_lib.EventConfig(latency="exponential", delay=4.0,
                                  engine="event", max_rounds=max_rounds)
    return cfg, state, samples, step_keys, ecfg


def test_round_budget_exhaustion_raises():
    """The engine's internal round cap tripping at quiescence drain is an
    error, not a silent truncation (the pre-fix bug: stranded messages
    vanished into ``dropped`` with no signal)."""
    cfg, state, samples, step_keys, ecfg = _watchdog_setup()
    with pytest.raises(RuntimeError, match="round budget exhausted"):
        events_lib.run_events(state, samples, step_keys, cfg, ecfg,
                              p_fn=_p_one, lat_key=jax.random.PRNGKey(5))


def test_explicit_max_rounds_truncation_still_reported_not_raised():
    """PR-4 contract preserved: budgeted truncation is visible accounting
    (``dropped``/``stranded``), never an exception."""
    cfg, state, samples, step_keys, ecfg = _watchdog_setup(max_rounds=64)
    out, _, rep = events_lib.run_events(state, samples, step_keys, cfg,
                                        ecfg, p_fn=_p_one,
                                        lat_key=jax.random.PRNGKey(5))
    assert np.isfinite(np.asarray(out.w)).all()
    assert int(rep.dropped) > 0              # truncation is accounted
    assert _identity(rep) == 0


# -------------------------------------------------- checkpoint integrity (a)


def test_pytree_checksum_roundtrip_and_corruption(tmp_path):
    tree = {"w": jnp.arange(12.0).reshape(3, 4), "i": jnp.int32(7)}
    path = str(tmp_path / "t.msgpack")
    ckpt.save(path, tree)
    back = ckpt.restore(path, {"w": jnp.zeros((3, 4)), "i": jnp.int32(0)})
    np.testing.assert_array_equal(np.asarray(back["w"]),
                                  np.asarray(tree["w"]))
    with open(path, "rb") as f:
        raw = bytearray(f.read())
    raw[-5] ^= 0xFF                          # flip a byte inside leaf data
    with open(path, "wb") as f:
        f.write(bytes(raw))
    with pytest.raises(ValueError, match="corrupt or truncated"):
        ckpt.restore(path, {"w": jnp.zeros((3, 4)), "i": jnp.int32(0)})


def test_truncated_pytree_payload_rejected(tmp_path):
    path = str(tmp_path / "t.msgpack")
    ckpt.save(path, {"x": jnp.ones((8,))})
    with open(path, "rb") as f:
        raw = f.read()
    with open(path, "wb") as f:
        f.write(raw[: len(raw) // 3])
    with pytest.raises(ValueError, match="corrupt or truncated"):
        ckpt.restore(path, {"x": jnp.zeros((8,))})


def test_train_checkpoint_roundtrip(tmp_path):
    cfg = afm_lib.AFMConfig(side=4, dim=3, i_max=32)
    state = afm_lib.init(jax.random.PRNGKey(0), cfg)
    lat_key = jax.random.PRNGKey(9)
    cursor = {"consumed": 64, "pos": 10, "step": 3, "since_swap": 0,
              "swaps": 1}
    path = str(tmp_path / "ck")
    sums = ckpt.save_train_checkpoint(
        path, config={"side": 4}, state=state, cursor=cursor,
        lat_key=lat_key, meta={"name": "m"})
    assert set(sums) == {"state.msgpack", "engine.msgpack"}
    tc = ckpt.load_train_checkpoint(path, state_like=state)
    assert tc.cursor == cursor and tc.config == {"side": 4}
    assert tc.meta["name"] == "m" and tc.checksums == sums
    np.testing.assert_array_equal(np.asarray(tc.lat_key),
                                  np.asarray(lat_key))
    np.testing.assert_array_equal(np.asarray(tc.state.w),
                                  np.asarray(state.w))
    # overwrite in place (the --checkpoint-every cadence) stays atomic
    cursor2 = dict(cursor, consumed=96)
    ckpt.save_train_checkpoint(path, config={"side": 4}, state=state,
                               cursor=cursor2, lat_key=lat_key)
    assert ckpt.load_train_checkpoint(
        path, state_like=state).cursor["consumed"] == 96


def test_train_checkpoint_corruption_rejected(tmp_path):
    cfg = afm_lib.AFMConfig(side=4, dim=3, i_max=32)
    state = afm_lib.init(jax.random.PRNGKey(0), cfg)
    path = str(tmp_path / "ck")
    ckpt.save_train_checkpoint(path, config={}, state=state,
                               cursor={"consumed": 1})
    p = os.path.join(path, "state.msgpack")
    with open(p, "rb") as f:
        raw = bytearray(f.read())
    raw[len(raw) // 2] ^= 0x01
    with open(p, "wb") as f:
        f.write(bytes(raw))
    with pytest.raises(ValueError, match="corrupt or truncated"):
        ckpt.load_train_checkpoint(path, state_like=state)
    with pytest.raises(FileNotFoundError):
        ckpt.load_train_checkpoint(str(tmp_path / "nope"), state_like=state)


# --------------------------------------------------------- retry helper (b)


def test_retry_helper_honors_retry_after_and_backoff():
    from repro.serving.fleet import Overloaded
    from repro.serving.retry import call_with_retries

    sheds = [Overloaded("busy", retry_after=0.2),
             Overloaded("busy", retry_after=0.01)]
    calls, delays = [], []

    def flaky(x):
        calls.append(x)
        if sheds:
            raise sheds.pop(0)
        return x * 2

    out = call_with_retries(flaky, 21, max_retries=3, base_delay=0.05,
                            max_delay=2.0, sleep=delays.append)
    assert out == 42 and len(calls) == 3
    # first wait takes the fleet hint (0.2 > 0.05), second the backoff
    # floor (0.01 < 0.05 * 2)
    assert delays == [0.2, 0.1]


def test_retry_helper_gives_up_and_passes_other_errors():
    from repro.serving.fleet import Overloaded
    from repro.serving.retry import call_with_retries

    def always_shed():
        raise Overloaded("busy", retry_after=0.0)

    delays = []
    with pytest.raises(Overloaded):
        call_with_retries(always_shed, max_retries=2, sleep=delays.append)
    assert len(delays) == 2                  # retried exactly max_retries

    def boom():
        raise KeyError("not transient")

    with pytest.raises(KeyError):
        call_with_retries(boom, sleep=delays.append)
    assert len(delays) == 2                  # no retry on non-Overloaded


# ------------------------------------------------- kill-and-resume (bitwise)


def test_stream_resume_reproduces_uninterrupted_run_bitwise(tmp_path):
    """Acceptance: SIGTERM mid-run + --resume lands on the exact state the
    uninterrupted run reaches (zero-latency; the exponential-latency chain
    restore is covered by the lat_key round-trip above)."""
    from repro.api import AFMConfig, MapStore
    from repro.launch.stream_train import run_stream

    cfg = AFMConfig(side=4, dim=3, i_max=96)
    rng = np.random.default_rng(0)
    xtr = rng.normal(size=(120, 3)).astype(np.float32)
    xte = rng.normal(size=(32, 3)).astype(np.float32)
    common = dict(backend="async", events=96, chunk=24, swap_every=48,
                  clients=0, min_client_reads=0, name="m", seed=7)

    def final_state(root):
        art = MapStore(root).load_artifact("m")
        return np.asarray(art.state.w), int(art.state.i)

    r1 = run_stream(cfg, xtr, xte, store_root=str(tmp_path / "a"), **common)
    assert not r1.interrupted and r1.qe_finite

    ckdir = str(tmp_path / "ck")
    r2 = run_stream(cfg, xtr, xte, store_root=str(tmp_path / "b"),
                    checkpoint_dir=ckdir, checkpoint_every=24,
                    die_after=48, **common)
    assert r2.interrupted and r2.events == 48
    assert r2.checkpoint_path == ckdir

    logs = []
    r3 = run_stream(cfg, xtr, xte, store_root=str(tmp_path / "b"),
                    checkpoint_dir=ckdir, resume=True,
                    log=lambda *a: logs.append(" ".join(map(str, a))),
                    **common)
    assert not r3.interrupted and r3.qe_finite
    assert r3.resumed_from["consumed"] == 48
    assert any("checksum verified" in line for line in logs)

    wa, ia = final_state(str(tmp_path / "a"))
    wb, ib = final_state(str(tmp_path / "b"))
    assert ia == ib == 96
    np.testing.assert_array_equal(wa, wb)


def test_stream_resume_rejects_config_mismatch(tmp_path):
    from repro.api import AFMConfig
    from repro.launch.stream_train import run_stream

    rng = np.random.default_rng(0)
    xtr = rng.normal(size=(60, 3)).astype(np.float32)
    xte = rng.normal(size=(16, 3)).astype(np.float32)
    ckdir = str(tmp_path / "ck")
    common = dict(backend="async", events=48, chunk=24, swap_every=48,
                  clients=0, min_client_reads=0, name="m", seed=7)
    run_stream(AFMConfig(side=4, dim=3, i_max=48), xtr, xte,
               checkpoint_dir=ckdir, checkpoint_every=24, die_after=24,
               **common)
    with pytest.raises(ValueError, match="does not match"):
        run_stream(AFMConfig(side=6, dim=3, i_max=48), xtr, xte,
                   checkpoint_dir=ckdir, resume=True, **common)
