"""Event-driven ``async`` backend (ISSUE 4 acceptance; sparse rounds ISSUE 5).

Contracts under test:
- zero-latency ``async`` == ``reference`` **bitwise** (fit and step; the
  acceptance 10x10 seeded map included);
- the broadcast-after-theta rule fires exactly at the threshold;
- the engine's avalanche sizes equal ``core.sandpile``'s chain exactly at
  p = 1 (the BTW-abelian regime);
- nonzero latency changes the dynamics (stale broadcasts) but stays finite
  and conserves message accounting;
- the sparse-round engine (ISSUE 5) reproduces the pre-optimization round
  semantics **bitwise** across all three latency models — golden
  fingerprints in ``tests/golden/async_engine.npz`` pin weights, counters,
  per-sample aux, and every ``EventReport`` field for all three runners
  (fused zero-latency scan, sample-scan engine, budgeted loop), including
  pool-overflow drop accounting;
- the packed round key and its lexicographic fallback agree, and the
  fallback survives generation counts near the int32 cap (the old
  ``2**30`` sentinel regression);
- the ``reference`` backend's jitted run scan is cached across ``fit``
  calls (no per-call retrace);
- ``stream_train``'s publish-while-serving loop is torn-read safe against
  concurrent gateway clients, in-memory and store-backed.
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.runtime import TraceGuard
from repro.api import AFMConfig, TopoMap, available_backends, get_backend
from repro.core import afm, events, pool, sandpile
from repro.core import search as search_lib
from repro.data import make_dataset
from repro.faults import FaultPlan
from repro.launch.stream_train import run_stream

CFG = AFMConfig(side=6, dim=12, i_max=48, batch=1, e_factor=0.5)


def _tiny_data(dim=12, n=256, seed=3):
    key = jax.random.PRNGKey(seed)
    return jax.random.normal(key, (n, dim))


# ------------------------------------------------------- backend contract


def test_async_backend_registered():
    assert "async" in available_backends()
    b = get_backend("async", CFG)
    assert b.cfg.batch == 1          # per-sample semantics, like reference


def test_async_rejects_bad_options():
    with pytest.raises(ValueError, match="latency"):
        get_backend("async", CFG, latency="warp")
    with pytest.raises(ValueError, match="search"):
        get_backend("async", CFG, search="oracle")
    with pytest.raises(ValueError, match="delay"):
        events.EventConfig(latency="constant", delay=-1.0)
    with pytest.raises(ValueError, match="no delay"):
        events.EventConfig(latency="zero", delay=0.5)
    with pytest.raises(ValueError, match="engine"):
        events.EventConfig(engine="warp")
    with pytest.raises(ValueError, match="engine"):
        get_backend("async", CFG, engine="fused")


# ------------------------------------------- zero-latency == reference


def test_zero_latency_fit_matches_reference_bitwise():
    x = _tiny_data()
    key = jax.random.PRNGKey(7)
    ref = TopoMap(CFG, backend="reference").fit(x, key=key)
    asy = TopoMap(CFG, backend="async").fit(x, key=key)
    np.testing.assert_array_equal(np.asarray(ref.state_.w),
                                  np.asarray(asy.state_.w))
    np.testing.assert_array_equal(np.asarray(ref.state_.c),
                                  np.asarray(asy.state_.c))
    assert int(asy.state_.i) == int(ref.state_.i) == CFG.i_max
    # the whole per-step trajectory matches, not just the endpoint
    for field in ("gmu", "q2", "cascade_size", "waves", "greedy_steps"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ref.fit_aux_, field)),
            np.asarray(getattr(asy.fit_aux_, field)), err_msg=field)
    rep = asy.backend.last_report
    assert int(rep.dropped) == 0
    assert int(rep.samples) == CFG.i_max
    # at zero latency: one round per sample + one per cascade wave
    assert int(rep.rounds) == CFG.i_max + int(np.sum(
        np.asarray(asy.fit_aux_.waves)))


def test_zero_latency_10x10_seeded_map_bitwise():
    """Acceptance: bitwise weight parity on a seeded 10x10 map."""
    cfg = AFMConfig(side=10, dim=8, i_max=100, batch=1, e_factor=0.3)
    x = _tiny_data(dim=8, n=512, seed=11)
    key = jax.random.PRNGKey(42)
    w_ref = TopoMap(cfg, backend="reference").fit(x, key=key).state_.w
    w_asy = TopoMap(cfg, backend="async").fit(x, key=key).state_.w
    np.testing.assert_array_equal(np.asarray(w_ref), np.asarray(w_asy))


def test_zero_latency_step_matches_reference_bitwise():
    """partial_fit parity: same per-sample key split as ReferenceBackend."""
    x = _tiny_data()
    ref = get_backend("reference", CFG)
    asy = get_backend("async", CFG)
    state = ref.init(jax.random.PRNGKey(1), x)
    k = jax.random.PRNGKey(9)
    s_ref, aux_ref = ref.step(state, x[:16], k)
    s_asy, aux_asy = asy.step(state, x[:16], k)
    np.testing.assert_array_equal(np.asarray(s_ref.w), np.asarray(s_asy.w))
    np.testing.assert_array_equal(np.asarray(s_ref.c), np.asarray(s_asy.c))
    np.testing.assert_array_equal(np.asarray(aux_ref.gmu),
                                  np.asarray(aux_asy.gmu))


def test_zero_latency_exact_search_matches_reference_bitwise():
    x = _tiny_data()
    key = jax.random.PRNGKey(5)
    w_ref = TopoMap(CFG, backend="reference",
                    backend_options={"search": "exact"}).fit(x, key=key) \
        .state_.w
    w_asy = TopoMap(CFG, backend="async",
                    backend_options={"search": "exact"}).fit(x, key=key) \
        .state_.w
    np.testing.assert_array_equal(np.asarray(w_ref), np.asarray(w_asy))


# -------------------------------------------------- event-handler rules


def _site_search(state, samples, key, cfg):
    """Deterministic routing stage: the sample's value *is* the target unit."""
    del key, cfg
    gmu = samples[:, 0].astype(jnp.int32)
    zeros = jnp.zeros_like(gmu)
    return search_lib.SearchResult(gmu, jnp.zeros(gmu.shape, jnp.float32),
                                   zeros, zeros)


def _p_one(i, cfg):
    del i, cfg
    return jnp.float32(1.0)


def _l_c_const(i, cfg):
    del i, cfg
    return jnp.float32(0.25)


def _unit_state(cfg, seed=0):
    return afm.init(jax.random.PRNGKey(seed), cfg)


def test_broadcast_fires_exactly_at_theta():
    """Rule ii): a unit broadcasts after theta adaptations, not before."""
    cfg = AFMConfig(side=5, dim=1, theta=4, l_s=0.1, i_max=16)
    center = 12                      # (2, 2): all 4 neighbours on-lattice
    state = _unit_state(cfg)
    w0 = np.asarray(state.w).copy()
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    target = jnp.full((4, 1), float(center), jnp.float32)

    # theta - 1 sample deliveries: adaptations but no broadcast
    st3, aux3, rep3 = events.run_events(
        state, target[:3], keys[:3], cfg, events.EventConfig(),
        search=_site_search, p_fn=_p_one, l_c_fn=_l_c_const)
    assert int(rep3.deliveries) == 0
    assert int(st3.c[center]) == 3
    assert np.asarray(aux3.cascade_size).sum() == 0
    neigh = [center - 5, center + 5, center - 1, center + 1]
    np.testing.assert_array_equal(np.asarray(st3.w)[neigh], w0[neigh])

    # the theta-th adaptation fires: counter resets, 4 neighbours receive
    st4, aux4, rep4 = events.run_events(
        state, target, keys, cfg, events.EventConfig(),
        search=_site_search, p_fn=_p_one, l_c_fn=_l_c_const)
    assert int(rep4.deliveries) == 4
    assert int(st4.c[center]) == 0
    assert list(np.asarray(aux4.cascade_size)) == [0, 0, 0, 1]
    w_center = float(st4.w[center, 0])
    for j in neigh:
        # receiver rule: w_j += l_c (w_k - w_j), with the sender's weights
        # as broadcast (post its theta adaptations)
        expect = w0[j, 0] + 0.25 * (w_center - w0[j, 0])
        assert float(st4.w[j, 0]) == pytest.approx(expect, rel=1e-6)
        assert int(st4.c[j]) == 1    # driven once per received broadcast
    # per-unit logical clocks: only touched units advanced
    touched = np.asarray(rep4.nevents)
    assert touched[center] == 4 and all(touched[j] == 1 for j in neigh)
    assert touched.sum() == 8


def test_max_rounds_truncation_is_reported():
    """A max_rounds exit must be visible: stranded messages count as
    dropped and the report's sample count reflects what actually ran."""
    cfg = AFMConfig(side=5, dim=1, theta=4, l_s=0.1, i_max=8)
    state = _unit_state(cfg)
    target = jnp.full((8, 1), 12.0, jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    # 4 sample rounds reach theta and enqueue 4 broadcasts; the bound
    # stops the loop before the delivery round
    _, _, rep = events.run_events(
        state, target, keys, cfg, events.EventConfig(max_rounds=4),
        search=_site_search, p_fn=_p_one, l_c_fn=_l_c_const)
    assert int(rep.rounds) == 4
    assert int(rep.samples) == 4         # not the requested 8
    assert int(rep.dropped) == 4         # the stranded broadcasts


def test_avalanche_sizes_match_sandpile_at_p1():
    """At p = 1 (BTW-abelian regime) the event engine's per-sample cascade
    sizes equal the pure sandpile chain's exactly — same sites, same
    toppling multiset, message passing notwithstanding."""
    side, steps = 12, 300
    # replicate sandpile.run_chain's site sequence key-for-key
    keys = jax.random.split(jax.random.PRNGKey(0), steps)
    sites = jax.vmap(
        lambda k: jax.random.randint(jax.random.split(k)[0], (2,), 0, side)
    )(keys)
    flat = (sites[:, 0] * side + sites[:, 1]).astype(jnp.float32)

    cfg = AFMConfig(side=side, dim=1, l_s=0.0, theta=4, i_max=steps)
    state = _unit_state(cfg)._replace(c=jnp.zeros((side * side,), jnp.int32))
    _, aux, rep = events.run_events(
        state, flat[:, None], jax.random.split(jax.random.PRNGKey(1), steps),
        cfg, events.EventConfig(), search=_site_search, p_fn=_p_one,
        l_c_fn=_l_c_const)
    ref_sizes = sandpile.run_chain(jax.random.PRNGKey(0), side=side,
                                   steps=steps, p=1.0)
    np.testing.assert_array_equal(np.asarray(aux.cascade_size),
                                  np.asarray(ref_sizes))
    assert int(rep.dropped) == 0
    assert np.asarray(aux.cascade_size).max() >= 5   # real avalanches ran


# ------------------------------------------------------- latency models


def test_latency_changes_dynamics_but_stays_sound():
    """Stale broadcasts and overlapping cascades: nonzero delay must change
    the trajectory (it is the asynchrony) without breaking accounting."""
    cfg = dataclasses.replace(CFG, i_max=64)
    x = _tiny_data()
    key = jax.random.PRNGKey(3)
    state = afm.init(jax.random.PRNGKey(1), cfg, x)
    samples = x[:64]
    step_keys = jax.random.split(key, 64)

    def run(ecfg):
        return events.run_events(state, samples, step_keys, cfg, ecfg,
                                 p_fn=_p_one, l_c_fn=_l_c_const)

    st0, aux0, rep0 = run(events.EventConfig())
    st_c, aux_c, rep_c = run(events.EventConfig(latency="constant",
                                                delay=2.0))
    st_e, _, rep_e = run(events.EventConfig(latency="exponential",
                                            delay=2.0, capacity=2048))
    assert not np.array_equal(np.asarray(st0.w), np.asarray(st_c.w))
    assert not np.array_equal(np.asarray(st0.w), np.asarray(st_e.w))
    for st, rep in ((st0, rep0), (st_c, rep_c), (st_e, rep_e)):
        assert np.isfinite(np.asarray(st.w)).all()
        assert int(rep.dropped) == 0
        assert int(st.i) == 64
        # each firing broadcasts to 2..4 on-lattice neighbours
        fired = int(np.sum(np.asarray(
            aux0.cascade_size if rep is rep0 else aux_c.cascade_size)))
        if rep is not rep_e:
            assert 2 * fired <= int(rep.deliveries) <= 4 * fired
    # exponential mode delivers messages one at a time: at least as many
    # rounds as the wave-synchronous modes
    assert int(rep_e.rounds) >= int(rep_c.rounds) - 1


def test_lat_seed_default_matches_explicit_key_bitwise():
    """The latency stream is seedable (lat_seed / lat_key); the default
    seed 0 reproduces the historical hardcoded-PRNGKey(0) stream bitwise,
    so the golden fingerprints pinned by this suite are unchanged."""
    cfg = dataclasses.replace(CFG, i_max=32)
    x = _tiny_data()
    state = afm.init(jax.random.PRNGKey(1), cfg, x)
    samples = x[:32]
    step_keys = jax.random.split(jax.random.PRNGKey(3), 32)
    ecfg = events.EventConfig(latency="exponential", delay=1.0,
                              capacity=2048)

    def run(ecfg_, **kw):
        return events.run_events(state, samples, step_keys, cfg, ecfg_,
                                 p_fn=_p_one, l_c_fn=_l_c_const, **kw)

    st_default, _, _ = run(ecfg)
    st_key0, _, _ = run(ecfg, lat_key=jax.random.PRNGKey(0))
    st_seed7, _, _ = run(ecfg, lat_seed=7)
    assert np.array_equal(np.asarray(st_default.w), np.asarray(st_key0.w))
    # a different latency seed is a different asynchrony realisation
    assert not np.array_equal(np.asarray(st_default.w),
                              np.asarray(st_seed7.w))
    # zero latency consumes no latency bits: lat_seed is inert there
    z0, _, _ = run(events.EventConfig())
    z7, _, _ = run(events.EventConfig(), lat_seed=7)
    assert np.array_equal(np.asarray(z0.w), np.asarray(z7.w))


def test_zero_latency_report_clocks_monotone():
    x = _tiny_data()
    tm = TopoMap(CFG, backend="async").fit(x, key=jax.random.PRNGKey(7))
    rep = tm.backend.last_report
    clock = np.asarray(rep.clock)
    assert clock.max() <= float(rep.t_end)
    assert int(rep.events) == int(rep.samples) + int(rep.deliveries)


# ------------------------------------------------ stream train-and-serve


STREAM_CFG = AFMConfig(side=4, dim=12, i_max=96, e_factor=0.5)


def test_stream_train_swap_is_torn_read_safe():
    """Concurrent gateway clients read per-sample QE for the whole run
    while the trainer hot-swaps state in; every read must be finite and
    error-free (clients assert in-thread)."""
    x = _tiny_data(n=200)
    rep = run_stream(STREAM_CFG, x, x[:64], backend="async", events=96,
                     chunk=16, swap_every=32, clients=2, client_batch=4)
    assert rep.client_errors == []
    assert rep.events == 96
    assert rep.swaps >= 3
    assert rep.client_requests >= 1
    assert rep.qe_finite and rep.qe.shape == (64,)


def test_stream_train_store_backed_reload(tmp_path):
    """Store-backed publication: artifact versions append and the gateway
    serves the reloaded map."""
    from repro.api import MapStore
    x = _tiny_data(n=200)
    root = str(tmp_path / "maps")
    rep = run_stream(STREAM_CFG, x, x[:32], backend="batched", events=96,
                     chunk=16, swap_every=48, clients=1, client_batch=4,
                     store_root=root, name="stream-test")
    assert rep.client_errors == []
    assert rep.qe_finite
    assert len(MapStore(root).versions("stream-test")) >= 3
    assert rep.swaps >= 2


def test_stream_train_works_without_clients():
    x = _tiny_data(n=128)
    rep = run_stream(STREAM_CFG, x, x[:16], backend="batched", events=64,
                     chunk=32, swap_every=32, clients=0)
    assert rep.qe_finite and rep.client_requests == 0


# ----------------------------------- sparse-round engine (ISSUE 5 golden)

_HERE = os.path.dirname(os.path.abspath(__file__))
_GOLDEN_NPZ = os.path.join(_HERE, "golden", "async_engine.npz")


def _load_regen():
    """Import the golden generator (shares the seeded case definitions)."""
    spec = importlib.util.spec_from_file_location(
        "regen_async_golden",
        os.path.join(_HERE, "golden", "regen_async_golden.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_REGEN = _load_regen()
_CASE_BY_NAME = {name: (cfg, ne, ekw, hot)
                 for name, cfg, ne, ekw, hot in _REGEN.CASES}

#: (case, runner variant): 'auto' is the production dispatch (fused scan at
#: zero latency, sample-scan engine otherwise); 'event' forces the
#: discrete-event engine (covers its zero-latency path); 'budget' runs the
#: budgeted loop with a non-binding round budget; 'fused' runs the training
#: megakernel (real Pallas body, interpreted) inside the zero-latency scan.
#: Every variant must equal the PR-4 dense engine's output bit-for-bit.
_GOLDEN_RUNS = [(name, "auto") for name in _CASE_BY_NAME] + [
    ("small_zero", "event"), ("ten_zero", "event"), ("hot_zero", "event"),
    ("ten_zero", "budget"), ("hot_const", "budget"), ("tiny_pool", "budget"),
] + [(name, "fused") for name in _REGEN.FUSED_CASES]


@pytest.mark.parametrize("case,variant", _GOLDEN_RUNS,
                         ids=[f"{c}-{v}" for c, v in _GOLDEN_RUNS])
def test_round_semantics_match_pre_optimization_golden(case, variant):
    """Bitwise parity with the pre-sparse-rounds engine: weights, counters,
    the full per-sample aux trajectory, and every EventReport field —
    including the seeded 10x10 report (``ten_*``) and overflow drop
    accounting (``tiny_pool``). The goldens were recorded with the
    non-partitionable threefry stream, so the run draws from that stream;
    ``q2`` is held to the regen script's ``Q2_ULP_BOUND``."""
    gold = np.load(_GOLDEN_NPZ)
    cfg, num_events, ekw, hot = _CASE_BY_NAME[case]
    ekw = dict(ekw)
    if variant == "event":
        ekw["engine"] = "event"
    elif variant == "budget":
        ekw["max_rounds"] = 10 ** 7          # non-binding budget
    elif variant == "fused":
        ekw["kernel"] = "fused-interpret"    # the megakernel, interpreted
    with jax.threefry_partitionable(False):
        out = _REGEN.run_case(cfg, num_events, ekw, hot)
    _REGEN.assert_matches_golden(out, gold, case, f"({variant})")


#: Exponential latency with a live dropout window: messages addressed to
#: dead units are consumed by the delivery round but not delivered.
_DROPOUT = FaultPlan(seed=5, dropout_frac=0.25, dropout_start=10.0,
                     dropout_len=40.0)
_WIDTH_CASES = {name: _CASE_BY_NAME[name]
                for name in ("hot_const", "hot_exp", "tiny_pool")}
_WIDTH_CASES["hot_exp_dropout"] = _CASE_BY_NAME["hot_exp"][:2] + (
    dict(_CASE_BY_NAME["hot_exp"][2], faults=_DROPOUT), True)


@pytest.fixture
def narrow_width(monkeypatch):
    """Sets the event engine's narrow delivery width; compiled runners are
    dropped on each change and after the test, so none built at a patched
    width outlives it."""
    def set_width(width):
        monkeypatch.setattr(pool, "NARROW_WIDTH", width)
        events._compiled_runner.cache_clear()
    yield set_width
    events._compiled_runner.cache_clear()


@pytest.mark.parametrize("case", sorted(_WIDTH_CASES))
def test_narrow_and_wide_delivery_rounds_agree_bitwise(case, narrow_width,
                                                       monkeypatch):
    """A delivery round run at the narrow width gives bit for bit what the
    worst-case width gives. Width 4 (one fired unit's broadcast) sends every
    exponential round and a constant-latency round of one fired unit down
    the narrow branch, the rest down the wide one; the pool's size sends
    every round down the wide one."""
    cfg, num_events, ekw, hot = _WIDTH_CASES[case]
    m = events._resolve(cfg, events.EventConfig(**ekw), num_events)[0]
    reports = []
    run_events = events.run_events

    def recording(*args, **kwargs):
        out = run_events(*args, **kwargs)
        reports.append(out[2])
        return out

    monkeypatch.setattr(events, "run_events", recording)
    outs = []
    for width in (4, m):
        narrow_width(width)
        with jax.threefry_partitionable(False):
            outs.append(_REGEN.run_case(cfg, num_events, ekw, hot))
    narrow, wide = outs
    for k in wide:
        np.testing.assert_array_equal(narrow[k], wide[k], err_msg=k)
    rep_narrow, rep_wide = reports
    assert int(rep_wide.narrow_rounds) == 0
    assert int(rep_narrow.narrow_rounds) > 0
    for rep in reports:
        assert int(rep.sent) == (int(rep.deliveries) + int(rep.dropped_overflow)
                                 + int(rep.dropped_fault) + int(rep.stranded))
    if "faults" in ekw:
        assert int(rep_narrow.dropped_fault) == int(rep_wide.dropped_fault) > 0


@pytest.mark.parametrize("width", [1, 4, pool.NARROW_WIDTH])
def test_compress_matches_nonzero(width):
    """At the narrow widths ``compress`` counts ranks in place of calling
    ``jnp.nonzero``, and equals it (the mask's size as fill) with fewer, as
    many and more True entries than ``width``."""
    rng = np.random.default_rng(width)
    for size, ntrue in ((300, 0), (300, width), (300, 3 * width), (300, 300)):
        mask = np.zeros(size, bool)
        mask[rng.choice(size, min(ntrue, size), replace=False)] = True
        want = jnp.nonzero(mask, size=width, fill_value=size)[0]
        np.testing.assert_array_equal(pool.compress(jnp.asarray(mask), width),
                                      want)


def test_narrow_rounds_count_the_delivery_rounds():
    """Every exponential-latency delivery round delivers one message and
    runs narrow; constant-latency rounds run narrow when one fire's output
    fits the narrow width."""
    def report(case):
        cfg, num_events, ekw, hot = _CASE_BY_NAME[case]
        with jax.threefry_partitionable(False):
            state = afm.init(jax.random.PRNGKey(0), cfg)
            x = jax.random.normal(jax.random.PRNGKey(1),
                                  (num_events, cfg.dim))
            return events.run_events(
                state, x, jax.random.split(jax.random.PRNGKey(2), num_events),
                cfg, events.EventConfig(**ekw),
                p_fn=_REGEN._p_hot if hot else events._default_p)[2]

    exp = report("hot_exp")
    delivery_rounds = int(exp.rounds) - int(exp.samples)
    assert delivery_rounds > 0
    assert int(exp.narrow_rounds) == delivery_rounds
    assert int(exp.deliveries) == delivery_rounds
    const = report("hot_const")
    assert 0 < int(const.narrow_rounds) <= int(const.rounds) - int(
        const.samples)
    _, _, empty = events.run_events(
        afm.init(jax.random.PRNGKey(0), CFG), jnp.zeros((0, CFG.dim)),
        jnp.zeros((0, 2), jnp.uint32), CFG)
    assert int(empty.narrow_rounds) == 0


#: A side-8 map (4N = 256 fire candidates, four times the narrow width)
#: under each pool mode an enqueue can meet.
_FIRE_CFG = AFMConfig(side=8, dim=4, theta=3, i_max=96, e_factor=0.5)
_FIRE_EXP = dict(latency="exponential", delay=2.5)
_FIRE_CASES = {
    "packed": (_FIRE_CFG, _FIRE_EXP),
    "lex": (dataclasses.replace(_FIRE_CFG, max_waves=2 ** 30), _FIRE_EXP),
    "constant": (_FIRE_CFG, dict(latency="constant", delay=1.5)),
    "overflow": (_FIRE_CFG, dict(_FIRE_EXP, capacity=48)),
    "loss": (_FIRE_CFG, dict(_FIRE_EXP,
                             faults=FaultPlan(seed=3, p_loss=0.3))),
    "dropout": (_FIRE_CFG, dict(_FIRE_EXP, faults=_DROPOUT)),
}


def _used_pool(es, free: int, seed: int = 0):
    """``es`` with a pool in use: a shuffled free ring whose ``free`` free
    slots wrap past its end, and the other slots holding messages."""
    m = es.msg_t.shape[0]
    rng = np.random.default_rng(seed)
    ring = rng.permutation(m).astype(np.int32)
    head = m - 3
    busy = ring[(head + np.arange(free, m)) % m]
    msg_t = np.full(m, np.inf, np.float32)
    msg_t[busy] = rng.uniform(0.0, 20.0, busy.size)
    msg_w = np.zeros(es.msg_w.shape, np.float32)
    msg_w[busy] = rng.normal(size=(busy.size, msg_w.shape[1]))
    return es._replace(free_ring=jnp.asarray(ring), free_head=jnp.int32(head),
                       free_n=jnp.int32(free), msg_t=jnp.asarray(msg_t),
                       msg_w=jnp.asarray(msg_w))


@pytest.mark.parametrize("case", sorted(_FIRE_CASES))
def test_narrow_and_wide_fires_agree_bitwise(case, narrow_width):
    """One ``fire()`` on the same used pool gives bit for bit the same pool
    fields, free ring, counters, ``sent``, ``dropped`` and
    ``dropped_fault`` whatever width its enqueue runs at: the default (16
    units narrow), a width of 40 units, and 4N (every fire wide), for fires
    of 0, 1, 16, 17 and 40 units; ``narrow_fires`` and ``wide_fires``
    count the width each took."""
    cfg, ekw = _FIRE_CASES[case]
    ecfg = events.EventConfig(**ekw)
    n, e = cfg.n_units, 8
    assert (pool.key_scale(e, events.wave_cap(cfg)) is None) == (case == "lex")
    state = afm.init(jax.random.PRNGKey(0), cfg)
    es0 = events.init_events(state, cfg, ecfg, e, jax.random.PRNGKey(1))
    m = es0.msg_t.shape[0]
    es0 = _used_pool(es0, free=9 if "capacity" in ekw else m // 2)
    rng = np.random.default_rng(1)
    masks = []
    for k in (0, 1, 16, 17, 40):
        mask = np.zeros(n, bool)
        mask[rng.choice(n, k, replace=False)] = True
        masks.append(jnp.asarray(mask))
    outs = {}
    for units in (pool.NARROW_WIDTH // 4, 40, n):
        narrow_width(4 * units)
        fire = jax.jit(events._make_round_fns(
            cfg, ecfg, e, afm.search_exact, events._default_p,
            events._default_l_c, i0=es0.i, far=state.far,
            near=state.near)[3])
        outs[units] = [fire(es0, mask, jnp.int32(3), jnp.float32(12.5),
                            jnp.int32(2)) for mask in masks]
    widths = ("narrow_fires", "wide_fires")
    for units, runs in outs.items():
        for out, ref in zip(runs, outs[n]):
            for field in events.EventState._fields:
                if field not in widths:
                    np.testing.assert_array_equal(
                        np.asarray(getattr(out, field)),
                        np.asarray(getattr(ref, field)), err_msg=field)
            k = int(out.sizes[3])              # units that fired
            narrow = 0 < k <= units < n
            assert int(out.narrow_fires) == narrow, (units, k)
            assert int(out.wide_fires) == (k > 0 and not narrow), (units, k)
    enqueued = [int(out.sent) for out in outs[n]]
    assert enqueued[0] == 0 < enqueued[1] < enqueued[2]
    if "capacity" in ekw:
        assert int(outs[n][-1].dropped) > 0
    if case == "loss":
        assert int(outs[n][-1].dropped_fault) > 0
    if case == "dropout":
        assert int(outs[n][-1].sizes[3]) < 40     # dead units do not fire


def test_fire_of_more_than_the_narrow_units_agrees_bitwise(narrow_width):
    """A constant-latency cascade under the sandpile's p = 1 from a lattice
    one short of theta everywhere: its fronts fire more units than the
    narrow width takes, so the run meets both enqueue widths, and gives bit
    for bit what a run with every enqueue wide gives."""
    cfg = AFMConfig(side=12, dim=2, theta=4, l_s=0.1, i_max=16)
    state = _unit_state(cfg)._replace(
        c=jnp.full((cfg.n_units,), cfg.theta - 1, jnp.int32))
    target = jnp.asarray([[66.0, 0.0], [30.0, 0.0]], jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    ecfg = events.EventConfig(latency="constant", delay=1.0)
    runs = []
    for width in (pool.NARROW_WIDTH, 4 * cfg.n_units):
        narrow_width(width)
        runs.append(events.run_events(
            state, target, keys, cfg, ecfg, search=_site_search,
            p_fn=_p_one, l_c_fn=_l_c_const))
    (st_d, aux_d, rep_d), (st_w, aux_w, rep_w) = runs
    np.testing.assert_array_equal(np.asarray(st_d.w), np.asarray(st_w.w))
    np.testing.assert_array_equal(np.asarray(st_d.c), np.asarray(st_w.c))
    np.testing.assert_array_equal(np.asarray(aux_d.cascade_size),
                                  np.asarray(aux_w.cascade_size))
    widths = ("narrow_rounds", "narrow_fires", "wide_fires")
    for field in events.EventReport._fields:
        if field not in widths:
            np.testing.assert_array_equal(
                np.asarray(getattr(rep_d, field)),
                np.asarray(getattr(rep_w, field)), err_msg=field)
    assert int(rep_d.narrow_fires) > 0 and int(rep_d.wide_fires) > 0
    assert int(rep_w.narrow_fires) == 0
    assert int(rep_w.wide_fires) == int(rep_d.narrow_fires) + int(
        rep_d.wide_fires)
    assert int(rep_d.dropped) == 0


def test_fire_counters_count_the_enqueues():
    """Under exponential latency every enqueue runs narrow: ``narrow_fires``
    equals the number of ``fire()`` calls that fired a unit, counted here
    round by round (a round's one ``fire()`` fired iff the cascade sizes
    grew), and ``wide_fires`` is 0, in the round loop and in the report."""
    cfg, num_events, ekw, _ = _CASE_BY_NAME["hot_exp"]
    ecfg = events.EventConfig(**ekw)
    state = afm.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (num_events, cfg.dim))
    keys = jax.random.split(jax.random.PRNGKey(2), num_events)
    lat = jax.random.PRNGKey(3)
    es = events.init_events(state, cfg, ecfg, num_events, lat)
    sample_round, delivery_round, pool_min, _ = map(
        jax.jit, events._make_round_fns(
            cfg, ecfg, num_events, afm.search_heuristic, _REGEN._p_hot,
            events._default_l_c, i0=es.i, far=state.far, near=state.near))
    firing_calls = 0
    for ev in range(num_events + 1):
        t_limit = ev * ecfg.sample_spacing if ev < num_events else np.inf
        while True:
            tmin, gmin, cmin, sel, have = pool_min(es)
            if not (bool(have) and float(tmin) <= t_limit):
                break
            after = delivery_round(es, tmin, gmin, cmin, sel)
            firing_calls += int(after.sizes.sum()) > int(es.sizes.sum())
            es = after
        if ev < num_events:
            after = sample_round(es, x[ev], keys[ev])
            firing_calls += int(after.sizes.sum()) > int(es.sizes.sum())
            es = after
    assert int(es.free_n) == es.msg_t.shape[0]        # drained
    assert firing_calls > 0
    assert int(es.narrow_fires) == firing_calls
    assert int(es.wide_fires) == 0
    _, _, rep = events.run_events(state, x, keys, cfg, ecfg, lat_key=lat,
                                  p_fn=_REGEN._p_hot)
    assert int(rep.rounds) == int(es.rounds)
    assert int(rep.narrow_fires) == firing_calls
    assert int(rep.wide_fires) == 0


def test_zero_fast_path_dispatch_conditions():
    """The fused scan only takes over when it is provably equivalent."""
    ok = events._zero_fast_ok
    assert ok(CFG, events.EventConfig(), 16)
    assert not ok(CFG, events.EventConfig(engine="event"), 16)
    assert not ok(CFG, events.EventConfig(max_rounds=100), 16)
    assert not ok(CFG, events.EventConfig(latency="constant", delay=1.0), 16)
    # a pool smaller than one fire's 4N candidates can overflow -> simulate
    assert not ok(CFG, events.EventConfig(capacity=CFG.n_units), 16)


def test_fused_kernel_requires_fast_path_regime():
    """kernel='fused' is a fast-path-only override: the config rejects any
    regime the megakernel cannot bitwise-replay, and an undersized pool
    (which disqualifies the fast path after validation) fails loudly at
    runner build instead of silently falling back to the staged engine."""
    for bad in (dict(latency="constant", delay=1.0),
                dict(engine="event"), dict(max_rounds=100)):
        with pytest.raises(ValueError, match="fast-path"):
            events.EventConfig(kernel="fused", **bad)
    with pytest.raises(ValueError, match="kernel must be one of"):
        events.EventConfig(kernel="mega")
    from repro.core.placement import MeshPlacement, SinglePool
    undersized = events.EventConfig(kernel="fused",
                                    capacity=CFG.n_units)
    with pytest.raises(ValueError, match="capacity"):
        SinglePool().build_runner(CFG, undersized, 16, afm.search_exact,
                                  events._default_p, events._default_l_c)
    # the multi-shard mesh rejects a fused kernel before touching devices
    with pytest.raises(ValueError, match="single-pool"):
        MeshPlacement(shards=2).build_runner(
            CFG, events.EventConfig(kernel="fused"), 16,
            afm.search_exact, events._default_p, events._default_l_c)


def test_async_backend_fused_kernel_option_bitwise():
    """TopoMap(backend='async', kernel='fused') trains bitwise-identically
    to the default staged fast path."""
    x = _tiny_data()
    key = jax.random.PRNGKey(5)
    base = TopoMap(CFG, backend="async").fit(x, key=key)
    fused = TopoMap(CFG, backend="async",
                    backend_options={"kernel": "fused"}).fit(x, key=key)
    assert np.array_equal(np.asarray(base.state_.w).view(np.uint32),
                          np.asarray(fused.state_.w).view(np.uint32))
    assert np.array_equal(np.asarray(base.state_.c),
                          np.asarray(fused.state_.c))
    rb, rf = base.backend.last_report, fused.backend.last_report
    assert int(rb.rounds) == int(rf.rounds)
    assert int(rb.deliveries) == int(rf.deliveries)
    assert np.array_equal(np.asarray(rb.nevents), np.asarray(rf.nevents))


def test_pool_min_lex_survives_generations_near_int32_max():
    """Regression for the old ``2**30`` sentinel: the lexicographic min must
    select correctly when gen/cid meet or exceed the old magic fill (the
    dense engine returned an empty selection there and the round loop
    spun)."""
    inf, imax = jnp.inf, jnp.iinfo(jnp.int32).max
    t = jnp.asarray([1.0, 1.0, inf, 1.0, 2.0], jnp.float32)
    gen = jnp.asarray([2 ** 30 + 5, 2 ** 30 + 3, 0, 2 ** 30 + 3, 1],
                      jnp.int32)
    cid = jnp.asarray([7, 9, 0, 3, 0], jnp.int32)
    tmin, gmin, cmin, sel, have = pool.pool_min_lex(t, gen, cid)
    assert bool(have) and float(tmin) == 1.0
    assert int(gmin) == 2 ** 30 + 3 and int(cmin) == 3
    assert list(np.asarray(sel)) == [False, False, False, True, False]
    # the fill value itself is a legal gen: selection must still be exact
    t2 = jnp.asarray([3.0, 3.0], jnp.float32)
    g2 = jnp.asarray([imax, imax], jnp.int32)
    c2 = jnp.asarray([5, 2], jnp.int32)
    _, gmin2, cmin2, sel2, have2 = pool.pool_min_lex(t2, g2, c2)
    assert bool(have2) and int(gmin2) == imax and int(cmin2) == 2
    assert list(np.asarray(sel2)) == [False, True]
    # empty pool: have must be False
    assert not bool(pool.pool_min_lex(
        jnp.full((3,), inf), jnp.zeros(3, jnp.int32),
        jnp.zeros(3, jnp.int32))[-1])


def test_packed_key_and_lex_fallback_agree_bitwise():
    """A huge ``max_waves`` overflows the packed uint32 lane, statically
    selecting the lexicographic path; with a cap no cascade ever reaches,
    both engines must produce identical runs."""
    num_events = 48
    packed_cfg = dataclasses.replace(CFG, max_waves=288)
    lex_cfg = dataclasses.replace(CFG, max_waves=2 ** 27)
    assert pool.key_scale(num_events, 288) == num_events
    assert pool.key_scale(num_events, 2 ** 27) is None
    x = _tiny_data()
    keys = jax.random.split(jax.random.PRNGKey(5), num_events)
    state = afm.init(jax.random.PRNGKey(1), CFG, x)
    ecfg = events.EventConfig(latency="constant", delay=0.5)
    outs = []
    for cfg in (packed_cfg, lex_cfg):
        st, aux, rep = events.run_events(state, x[:num_events], keys, cfg,
                                         ecfg, p_fn=_p_one,
                                         l_c_fn=_l_c_const)
        outs.append((st, aux, rep))
    (st_p, aux_p, rep_p), (st_l, aux_l, rep_l) = outs
    np.testing.assert_array_equal(np.asarray(st_p.w), np.asarray(st_l.w))
    np.testing.assert_array_equal(np.asarray(st_p.c), np.asarray(st_l.c))
    np.testing.assert_array_equal(np.asarray(aux_p.cascade_size),
                                  np.asarray(aux_l.cascade_size))
    assert int(rep_p.deliveries) == int(rep_l.deliveries) > 0
    assert int(rep_p.rounds) == int(rep_l.rounds)


def test_zero_fast_path_equals_engine_on_seeded_10x10():
    """Live invariant behind the fast path: on a seeded 10x10 run the fused
    scan and the forced discrete-event engine agree bitwise — state, aux,
    and the EventReport field for field. ``narrow_rounds``,
    ``narrow_fires`` and ``wide_fires`` count how the engine ran its
    delivery rounds and enqueues, of which the fused scan runs none."""
    cfg = AFMConfig(side=10, dim=8, i_max=100, batch=1, e_factor=0.3)
    x = _tiny_data(dim=8, n=512, seed=11)
    key = jax.random.PRNGKey(42)
    fast = TopoMap(cfg, backend="async").fit(x, key=key)
    slow = TopoMap(cfg, backend="async",
                   backend_options={"engine": "event"}).fit(x, key=key)
    np.testing.assert_array_equal(np.asarray(fast.state_.w),
                                  np.asarray(slow.state_.w))
    rf, rs = fast.backend.last_report, slow.backend.last_report
    widths = ("narrow_rounds", "narrow_fires", "wide_fires")
    assert int(rf.narrow_rounds) == 0 < int(rs.narrow_rounds)
    assert int(rf.narrow_fires) == 0 < int(rs.narrow_fires)
    assert int(rf.wide_fires) == 0
    for field in events.EventReport._fields:
        if field in widths:
            continue
        np.testing.assert_array_equal(
            np.asarray(getattr(rf, field)), np.asarray(getattr(rs, field)),
            err_msg=f"EventReport.{field}")


def test_run_events_donate_smoke():
    """``donate=True`` (the fit path on accelerators) must not change
    results; on CPU donation is a no-op."""
    x = _tiny_data()
    keys = jax.random.split(jax.random.PRNGKey(9), 16)
    ecfg = events.EventConfig(latency="constant", delay=0.5)
    state = afm.init(jax.random.PRNGKey(1), CFG, x)
    st0, _, _ = events.run_events(state, x[:16], keys, CFG, ecfg)
    st1, _, _ = events.run_events(state, x[:16], keys, CFG, ecfg,
                                  donate=True)
    np.testing.assert_array_equal(np.asarray(st0.w), np.asarray(st1.w))


def test_reference_run_jit_cached_across_fits():
    """ISSUE 5 satellite: the reference/batched run scan is traced once and
    reused — repeated one-shot fits no longer pay a retrace."""
    x = _tiny_data()
    for backend in ("reference", "batched"):
        tm = TopoMap(CFG, backend=backend)
        tm.fit(x, key=jax.random.PRNGKey(0))
        fn = tm.backend._jit_run
        assert fn is not None
        # same jitted callable across fits -> same trace cache; the count
        # check uses a private jax hook, so skip it gracefully if renamed
        if hasattr(fn, "_cache_size"):
            with TraceGuard(fn):           # re-fitting must not retrace
                tm.fit(x, key=jax.random.PRNGKey(1))
                tm.fit(x, key=jax.random.PRNGKey(2))
        else:
            tm.fit(x, key=jax.random.PRNGKey(1))
            tm.fit(x, key=jax.random.PRNGKey(2))
        assert tm.backend._jit_run is fn


# ------------------------------------------------------------- plumbing


def test_backend_argument_helper_tracks_registry():
    import argparse
    from repro.api.backends import add_backend_argument
    ap = argparse.ArgumentParser()
    add_backend_argument(ap, default="batched")
    assert ap.parse_args(["--backend", "async"]).backend == "async"
    with pytest.raises(SystemExit):
        ap.parse_args(["--backend", "warp-drive"])


def test_async_artifact_roundtrip(tmp_path):
    """Async-trained maps persist/load like any other backend's."""
    x = _tiny_data()
    tm = TopoMap(CFG, backend="async").fit(x, key=jax.random.PRNGKey(2))
    path = str(tmp_path / "async-map")
    tm.save(path)
    tm2 = TopoMap.load(path)
    np.testing.assert_array_equal(np.asarray(tm.transform(x[:9])),
                                  np.asarray(tm2.transform(x[:9])))
    assert tm2.backend.name == "async"


@pytest.mark.slow
def test_async_quality_on_dataset():
    """End-to-end: async training reaches batched-level map quality."""
    xtr, ytr, xte, yte = make_dataset("satimage", train_size=600,
                                      test_size=150)
    cfg = AFMConfig(side=6, dim=36, i_max=720, e_factor=1.0)
    key = jax.random.PRNGKey(0)
    q_asy = TopoMap(cfg, backend="async").fit(xtr, key=key) \
        .quantization_error(xte)
    q_bat = TopoMap(cfg, backend="batched", batch=8).fit(xtr, key=key) \
        .quantization_error(xte)
    assert abs(q_asy - q_bat) / q_bat < 0.25, (q_asy, q_bat)


def test_run_events_empty_batch():
    state = afm.init(jax.random.PRNGKey(0), CFG)
    st, aux, rep = events.run_events(
        state, jnp.zeros((0, CFG.dim)), jnp.zeros((0, 2), jnp.uint32), CFG)
    assert st is state and aux.cascade_size.shape == (0,)
    assert int(rep.rounds) == 0
