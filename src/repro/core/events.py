"""Discrete-event asynchronous training runtime (the paper's execution model).

Every other backend steps a global synchronous loop; this module executes the
paper's *actual* model: N autonomous units with local logical clocks that
interact only through messages. Two message kinds exist —

- **sample delivery**: the heuristic search routes a sample to its GMU, which
  adapts by Eq. (3) and increments its cascading counter with probability
  ``p_i`` (Eq. 6);
- **weight broadcast**: a unit whose counter reaches ``theta`` fires — it
  resets the counter and sends its *current* weight vector to its 4 lattice
  neighbours; a receiver adapts by ``w_j += l_c (w_k - w_j)`` (Eq. 5 rate)
  and is driven with probability ``p_i``, possibly firing in turn.

Those are exactly the paper's two rules (adapt on receipt of a sample or a
neighbour's weights; broadcast after ``theta`` adaptations), implemented as
event handlers over a fixed-capacity message pool. Messages carry their
payload (the sender's weights *at send time*) plus a delivery timestamp from
a configurable latency model (``zero`` / ``constant`` / ``exponential``), so
stale-weight effects — the thing bulk-async approximations cannot express —
are first-class.

Execution pops *rounds* — all messages sharing the minimal ``(time,
generation, cascade-id)`` key, or the next sample arrival — and each round's
handler is data-parallel over the messages actually in the round, not over
the whole map. The pool itself — round selection, the send side and the
delivery round's receiver side, each at the width its own count asks for —
is ``repro.core.pool``, which the mesh placement's bands share; this module
holds the single pool's state, its packed round key and its runners. Three
statically-chosen runners implement the same round semantics (DESIGN.md §7
"round cost model"):

- **fused zero-latency scan** — ``latency='zero'`` runs replay the
  ``reference`` backend's fused step scan op-for-op (plus an accounting
  sidecar for the ``EventReport``), so the common case pays no
  event-simulation tax. Bitwise-equal to the engine by the PR-4 parity
  argument; ``tests/test_async_trainer.py`` enforces it.
- **sample-scan engine** (the default) — an outer ``lax.scan`` over sample
  arrivals with an inner ``while_loop`` that drains due messages before each
  arrival. Per-round work is sized by the active message set: a packed
  single-key min finds the round, a free-list ring allocates pool slots in
  O(1) amortized, and delivery gathers/scatters only the ≤K selected slots
  and their receiver rows instead of rewriting the dense (N, D) state.
- **budgeted loop** — only when ``EventConfig.max_rounds`` is set: the
  original single ``while_loop`` with a global round budget, preserving the
  exact truncation accounting (stranded messages count as dropped).

Under zero latency a round is precisely one cascade wave, the handlers
consume the PRNG stream in the same order and shapes as
``core.cascade.drive_and_cascade``, and every runner reproduces the
``reference`` backend **bitwise** on the same sample order (DESIGN.md §7
gives the argument). Avalanche sizes are accounted per originating sample
with the same firing-incident definition as ``core.cascade`` /
``core.sandpile``, so the event engine's cascade-size distribution is
directly comparable to the BTW-sandpile oracle (and equals it exactly at
p = 1).

``repro.training.async_trainer`` wraps this engine as the ``async`` backend
of ``TopoMap``; ``repro.launch.stream_train`` runs it as a continuous
train-and-serve loop.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import afm as afm_lib
from repro.core import cascade as cascade_lib
from repro.core import pool as pool_lib
from repro.core import schedules
from repro.core.afm import AFMConfig, AFMState
from repro.faults import FaultPlan

LATENCIES = ("zero", "constant", "exponential")
ENGINES = ("auto", "event")
KERNELS = ("staged", "fused", "fused-interpret")


@dataclasses.dataclass(frozen=True)
class EventConfig:
    """Static configuration of the event engine (hashable: keys a jit cache).

    latency:        message latency model — 'zero' (cascades complete between
                    sample arrivals; recovers ``reference`` bitwise),
                    'constant' (every message takes ``delay`` time units), or
                    'exponential' (i.i.d. Exp(mean=``delay``) per message).
    delay:          the latency scale, in the same units as sample spacing.
    sample_spacing: simulated time between consecutive sample arrivals (1.0
                    by default, so ``delay`` is measured in sample periods).
    capacity:       message-pool slots; ``None`` -> 8 * N. Overflowing
                    messages are dropped and counted (``EventReport.dropped``
                    stays 0 in every supported regime; a nonzero value means
                    the pool is undersized for the latency/traffic mix).
    max_rounds:     safety bound on total simulation rounds; ``None`` (the
                    default) lets the engine run to quiescence — cascades are
                    intrinsically bounded by ``max_waves`` — and enables the
                    fast scan-structured runners. Setting a value selects the
                    budgeted loop with exact truncation accounting.
    engine:         'auto' (default) dispatches eligible ``latency='zero'``
                    runs to the fused reference scan; 'event' always runs the
                    discrete-event simulation (benchmarks and the parity
                    suite use it to measure/pin the engine itself).
    kernel:         step execution inside the zero-latency fast path —
                    'staged' (default: the inline jnp scan), 'fused' (the
                    ``kernels.fused`` training megakernel: compiled on TPU,
                    its jnp oracle elsewhere), or 'fused-interpret' (the
                    real megakernel body in the Pallas interpreter — slow;
                    the golden/CI parity runs). All three are
                    bitwise-identical (DESIGN.md §11); a fused kernel
                    requires the fast-path regime (latency='zero',
                    engine='auto', max_rounds=None, single pool).
    faults:         ``repro.faults.FaultPlan`` to inject (seeded message
                    loss, unit dropout windows, shard stragglers, pool
                    pressure) — or ``None``/``FaultPlan.none()`` for the
                    bitwise-pinned fault-free engine. An active plan
                    disables the fused fast path (faults are simulated,
                    so the discrete-event engine runs) and is rejected
                    with a fused kernel.
    """
    latency: str = "zero"
    delay: float = 0.0
    sample_spacing: float = 1.0
    capacity: int | None = None
    max_rounds: int | None = None
    engine: str = "auto"
    kernel: str = "staged"
    faults: FaultPlan | None = None

    def __post_init__(self):
        if self.faults is not None and not isinstance(self.faults, FaultPlan):
            raise ValueError(
                "faults must be a repro.faults.FaultPlan or None, got "
                f"{self.faults!r} (dict specs are resolved by the backend "
                "layer: backend_options={'faults': {...}})")
        if self.latency not in LATENCIES:
            raise ValueError(f"latency must be one of {LATENCIES}, got "
                             f"{self.latency!r}")
        if self.engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, got "
                             f"{self.engine!r}")
        if self.kernel not in KERNELS:
            raise ValueError(f"kernel must be one of {KERNELS}, got "
                             f"{self.kernel!r}")
        if self.kernel != "staged" and (
                self.latency != "zero" or self.engine != "auto"
                or self.max_rounds is not None):
            raise ValueError(
                "kernel='fused' runs only in the zero-latency fast-path "
                "regime: latency='zero', engine='auto', max_rounds=None")
        if self.kernel != "staged" and self.fault_active:
            raise ValueError(
                "kernel='fused' runs only in the zero-latency fast-path "
                "regime, which an active FaultPlan disqualifies (faults are "
                "simulated by the discrete-event engine)")
        if self.delay < 0:
            raise ValueError(f"delay must be >= 0, got {self.delay}")
        if self.latency == "zero" and self.delay:
            raise ValueError("latency='zero' takes no delay; use 'constant'")
        if self.sample_spacing <= 0:
            raise ValueError("sample_spacing must be > 0")

    @property
    def fault_active(self) -> bool:
        """True when a fault plan with at least one active axis is set."""
        return self.faults is not None and not self.faults.is_none()

    @property
    def plan(self) -> FaultPlan:
        """The effective plan (``faults`` or the fault-free default)."""
        return self.faults if self.faults is not None else FaultPlan.none()


class EventState(NamedTuple):
    """The full simulation state carried through the round loop.

    The lattice tables (``far`` / ``near``) are loop-invariant and live as
    closures of the compiled runner, not in the carry."""
    # AFM core (the dense trainable state)
    w: jnp.ndarray          # (N, D) f32
    c: jnp.ndarray          # (N,)  i32 cascading counters
    i: jnp.ndarray          # () i32 — samples consumed (drives schedules)
    # per-unit locality
    clock: jnp.ndarray      # (N,) f32 — each unit's last-event time
    nevents: jnp.ndarray    # (N,) i32 — events processed per unit
    # message pool (capacity M; time = +inf marks a free slot)
    msg_t: jnp.ndarray      # (M,) f32 delivery time
    msg_key: jnp.ndarray    # (M,) u32 packed gen·E+cid lane (packed mode)
    msg_gen: jnp.ndarray    # (M,) i32 sub-time generation (lex mode)
    msg_cid: jnp.ndarray    # (M,) i32 originating sample event (lex mode)
    msg_dst: jnp.ndarray    # (M,) i32 receiving unit
    msg_dir: jnp.ndarray    # (M,) i32 receiver-side direction code (0..3)
    msg_w: jnp.ndarray      # (M, D) f32 payload: sender weights at send time
    # O(1)-amortized slot allocator: ring queue of free slot ids.
    # Invariant: entries [free_head, free_head + free_n) (mod M) are the ids
    # of exactly the free pool slots; free_n == M - #active messages.
    free_ring: jnp.ndarray  # (M,) i32
    free_head: jnp.ndarray  # () i32
    free_n: jnp.ndarray     # () i32
    # per-cascade bookkeeping (one row per sample event of this run)
    casc_key: jnp.ndarray   # (E, 2) u32 — per-cascade PRNG chain
    wcount: jnp.ndarray     # (E,) i32 — delivery rounds so far (== waves)
    sizes: jnp.ndarray      # (E,) i32 — firing incidents (a_i)
    gmu: jnp.ndarray        # (E,) i32 aux
    q2: jnp.ndarray         # (E,) f32 aux
    greedy: jnp.ndarray     # (E,) i32 aux
    # global simulation counters
    ev: jnp.ndarray         # () i32 — next sample event index
    t: jnp.ndarray          # () f32 — last processed round time
    rounds: jnp.ndarray     # () i32
    deliveries: jnp.ndarray  # () i32 — weight messages delivered
    dropped: jnp.ndarray    # () i32 — messages lost to pool overflow
    lat_key: jnp.ndarray    # (2,) u32 — exponential-latency stream (separate
    #                         from the training chains, so zero/constant runs
    #                         consume exactly the reference PRNG stream)
    # fault-injection sidecar (repro.faults): pure integer/PRNG accounting,
    # zeros (and an untouched key) when the plan is inactive — the fault-free
    # graph stays op-identical to the pre-fault engine
    sent: jnp.ndarray          # () i32 — broadcast candidates attempted
    dropped_fault: jnp.ndarray  # () i32 — injected losses + dead receivers
    samples_dead: jnp.ndarray  # () i32 — samples routed to a dead GMU
    fault_key: jnp.ndarray     # (2,) u32 — the plan's own PRNG stream
    narrow_rounds: jnp.ndarray  # () i32 — delivery rounds run at k_narrow
    narrow_fires: jnp.ndarray   # () i32 — fire() enqueues run narrow
    wide_fires: jnp.ndarray     # () i32 — fire() enqueues run at 4N


class EventReport(NamedTuple):
    """Per-run accounting (event-throughput benchmarks read this).

    The trailing fault/accounting fields (PR 10) default so historical
    positional construction stays valid; every runner populates them. The
    conservation identity — checked by the fault suite and ``fault_bench``
    — is ``sent == deliveries + dropped_overflow + dropped_fault +
    stranded`` where ``dropped_overflow = dropped - stranded``.
    """
    rounds: jnp.ndarray      # () i32 — simulation rounds executed
    samples: jnp.ndarray     # () i32 — sample deliveries actually consumed
    #                          (< the requested E only on a max_rounds exit)
    deliveries: jnp.ndarray  # () i32 — weight-broadcast deliveries
    dropped: jnp.ndarray    # () i32 — pool-overflow drops + messages
    #                          stranded by a max_rounds exit (0 in practice)
    t_end: jnp.ndarray       # () f32 — final simulated time
    clock: jnp.ndarray       # (N,) f32 — per-unit logical clocks
    nevents: jnp.ndarray     # (N,) i32 — per-unit event counts
    sent: jnp.ndarray = 0          # () i32 — broadcast candidates attempted
    dropped_fault: jnp.ndarray = 0  # () i32 — injected loss + dead receivers
    stranded: jnp.ndarray = 0      # () i32 — in-flight at exit (also summed
    #                                into ``dropped`` for PR-4 compatibility)
    samples_dead: jnp.ndarray = 0  # () i32 — samples routed to a dead GMU
    shard_counts: jnp.ndarray = 0  # (K, 5) i32 — per-shard [sent, delivered,
    #                                dropped_overflow, dropped_fault,
    #                                stranded]; K=1 off-mesh
    narrow_rounds: jnp.ndarray = 0  # () i32 — delivery rounds served at the
    #                                narrow width (hit share: narrow_rounds /
    #                                (rounds - samples))
    exchanges: jnp.ndarray = 0     # () i32 — mesh collective steps: lockstep
    #                                drain iterations plus sample rounds (0 on
    #                                the single pool)
    halo_sent: jnp.ndarray = 0     # () i32 — mesh messages enqueued from
    #                                another shard's outbox (0 on the single
    #                                pool)
    narrow_fires: jnp.ndarray = 0  # () i32 — fire() enqueues run at the
    #                                narrow width (hit share: narrow_fires /
    #                                (narrow_fires + wide_fires)); summed
    #                                over shards
    wide_fires: jnp.ndarray = 0    # () i32 — fire() enqueues run over every
    #                                candidate (4N, or 4L on a mesh shard)

    @property
    def events(self):
        """Total events processed (samples + weight deliveries)."""
        return self.samples + self.deliveries

    @property
    def dropped_overflow(self):
        """Pool-overflow drops alone (``dropped`` minus the stranded tail)."""
        return self.dropped - self.stranded


def wave_cap(cfg: AFMConfig) -> int:
    """The engine's effective cascade wave bound (``None`` -> 8·side²)."""
    return 8 * cfg.side * cfg.side if cfg.max_waves is None else cfg.max_waves


def _resolve(cfg: AFMConfig, ecfg: EventConfig, num_events: int):
    """Static derived quantities of the single pool: (pool size M, one
    fire's candidates K, wave cap, round cap)."""
    m = pool_lib.capacity(ecfg, cfg.n_units)
    k = min(4 * cfg.n_units, m)
    max_waves = wave_cap(cfg)
    max_rounds = (ecfg.max_rounds if ecfg.max_rounds is not None
                  else num_events * (max_waves + 2) + 1)
    # the round counter is int32; a huge max_waves would overflow the
    # derived budget (it is a safety net, not a semantic bound)
    return m, k, max_waves, min(int(max_rounds), 2 ** 31 - 1)


def init_events(state: AFMState, cfg: AFMConfig, ecfg: EventConfig,
                num_events: int, lat_key: jax.Array) -> EventState:
    """Fresh simulation state around an ``AFMState`` for ``num_events``
    sample arrivals. Simulated time restarts at 0 per run; ``state.i``
    (samples consumed historically) keeps driving the schedules."""
    n, d, e = cfg.n_units, cfg.dim, num_events
    m, _, _, _ = _resolve(cfg, ecfg, num_events)
    z = jnp.zeros
    return EventState(
        w=state.w, c=state.c,
        i=jnp.asarray(state.i, jnp.int32),
        clock=z((n,), jnp.float32), nevents=z((n,), jnp.int32),
        msg_t=jnp.full((m,), jnp.inf, jnp.float32),
        msg_key=jnp.full((m,), 0xFFFFFFFF, jnp.uint32),
        msg_gen=z((m,), jnp.int32), msg_cid=z((m,), jnp.int32),
        msg_dst=z((m,), jnp.int32), msg_dir=z((m,), jnp.int32),
        msg_w=z((m, d), jnp.float32),
        free_ring=jnp.arange(m, dtype=jnp.int32),
        free_head=jnp.int32(0), free_n=jnp.int32(m),
        casc_key=z((e, 2), jnp.uint32), wcount=z((e,), jnp.int32),
        sizes=z((e,), jnp.int32), gmu=z((e,), jnp.int32),
        q2=z((e,), jnp.float32), greedy=z((e,), jnp.int32),
        ev=jnp.int32(0), t=jnp.float32(0.0), rounds=jnp.int32(0),
        deliveries=jnp.int32(0), dropped=jnp.int32(0),
        lat_key=jnp.asarray(lat_key, jnp.uint32),
        sent=jnp.int32(0), dropped_fault=jnp.int32(0),
        samples_dead=jnp.int32(0),
        fault_key=(jax.random.PRNGKey(ecfg.plan.seed)
                   if ecfg.fault_active else z((2,), jnp.uint32)),
        narrow_rounds=jnp.int32(0), narrow_fires=jnp.int32(0),
        wide_fires=jnp.int32(0),
    )


def _default_p(i, cfg: AFMConfig):
    return schedules.cascade_probability(i, cfg.total_samples, cfg.n_units,
                                         cfg.c_m, cfg.c_d)


def _default_l_c(i, cfg: AFMConfig):
    return schedules.cascade_learning_rate(i, cfg.total_samples, cfg.c_o,
                                           cfg.c_s)


def _make_round_fns(cfg: AFMConfig, ecfg: EventConfig, num_events: int,
                    search: Callable, p_fn: Callable, l_c_fn: Callable,
                    i0, far, near):
    """Build (sample_round, delivery_round, pool_min, fire) as closures.

    ``i0`` is the run's starting sample count: cascade ``cid`` uses the
    schedules evaluated at ``i0 + cid`` throughout its lifetime — exactly
    the value its own sample round saw, matching the reference semantics
    where one step's cascade runs entirely under that step's l_c / p_i.
    ``far`` / ``near`` are the loop-invariant lattice tables. The pool's
    send and receive sides are ``core.pool``'s; what is the single pool's
    own is the packed round key, round counting and wave gating on
    ``wcount``.
    """
    n, side, theta = cfg.n_units, cfg.side, cfg.theta
    m, k_sel, max_waves, _ = _resolve(cfg, ecfg, num_events)
    # fault-plan closures (repro.faults): each axis is a *static* Python
    # branch, so an inactive plan builds the exact fault-free graph — the
    # golden-bitwise contract is structural, not numeric luck
    plan = ecfg.plan
    dead_on = ecfg.fault_active and plan.dropout_active
    if dead_on:
        dead_sel = plan.dead_units(n)
        d_lo = plan.dropout_start
        d_hi = plan.dropout_start + plan.dropout_len

        def dead_at(t):
            """(N,) bool — units dead at simulated time ``t``."""
            return dead_sel & (t >= d_lo) & (t < d_hi)
    # round selection: the packed single-lane min whenever (gen, cid) fits
    # one uint32, else the exact lexicographic 3-field min
    scale = pool_lib.key_scale(num_events, max_waves)
    widths = pool_lib.round_widths(ecfg, m, k_sel)
    tables = pool_lib.routes(near.reshape(-1))
    fire_units = pool_lib.fire_units(n)

    def pool_min(es: EventState):
        with jax.named_scope(obs.EVENTS_POOL):
            if scale is None:
                return pool_lib.pool_min_lex(es.msg_t, es.msg_gen, es.msg_cid)
            return pool_lib.pool_min_packed(es.msg_t, es.msg_key, scale)

    def round_key(gen, cid) -> dict:
        gen = jnp.asarray(gen, jnp.int32)
        cid = jnp.asarray(cid, jnp.int32)
        if scale is None:
            return {"msg_gen": gen, "msg_cid": cid}
        return {"msg_key": gen.astype(jnp.uint32) * jnp.uint32(scale)
                + cid.astype(jnp.uint32)}

    def fire(es: EventState, fired, cid, t, gen) -> EventState:
        """Broadcast-after-theta (``core.pool.fire``): ``fired`` units reset
        their counters and enqueue weight messages to their near neighbours
        (payload = the sender's current w), timestamped by the latency
        model, at a width that follows the fire's own count.

        Faults: dead units neither fire nor count as firing incidents (a
        unit whose counter crossed ``theta`` while dead fires on rejoin at
        the next round that drives it into ``fired``); ``p_loss`` losses
        come off the plan's own key chain *after* ``sent`` is counted, so
        the conservation identity sees every attempted broadcast."""
        if dead_on:
            fired = fired & ~dead_at(t)
        return pool_lib.fire(es, fired, cid, t, round_key(gen, cid), tables,
                             fire_units, ecfg)

    def sample_round(es: EventState, sample, step_key) -> EventState:
        """Deliver the next sample: search routes it, the GMU adapts
        (Eq. 3) and is driven w.p. p_i; a threshold crossing fires.

        PRNG discipline is byte-for-byte the reference step's:
        ``split(step_key) -> (k_search, k_cascade)``, then
        ``split(k_cascade) -> (k_drive, k_cascade_chain)`` with the drive's
        (8, side, side) uniform tensor — so at zero latency the whole round
        sequence replays ``afm._step`` exactly.
        """
        ev = es.ev
        t_s = ev.astype(jnp.float32) * ecfg.sample_spacing
        k_search, k_cascade = jax.random.split(step_key)
        p_i = p_fn(es.i, cfg)
        st = AFMState(es.w, es.c, far, near, es.i)
        res = search(st, sample[None, :], k_search, cfg)
        w, counts = afm_lib.adapt_gmu(st, sample[None, :], res.gmu, cfg)
        k_drive, k_chain = jax.random.split(k_cascade)
        gmu_mask = counts.astype(jnp.int32).reshape(side, side)
        draws = jax.random.uniform(k_drive, (8, side, side)) < p_i
        inc = jnp.sum(
            draws.astype(jnp.int32)
            * (jnp.arange(8)[:, None, None] < jnp.minimum(gmu_mask, 8)),
            axis=0)
        c = es.c + inc.reshape(-1)
        g = res.gmu[0]
        extra = {}
        if dead_on:
            # a dead GMU neither adapts nor is driven (the search still
            # routes and the PRNG stream still advances — determinism is
            # per-plan, not per-fault-outcome); the sample is consumed and
            # counted in ``samples_dead``
            alive_g = ~dead_at(t_s)[g]
            w = jnp.where(alive_g, w, es.w)
            c = jnp.where(alive_g, c, es.c)
            extra["samples_dead"] = (es.samples_dead + 1
                                     - alive_g.astype(jnp.int32))
            clock = es.clock.at[g].set(
                jnp.where(alive_g, t_s, es.clock[g]))
            nevents = es.nevents.at[g].add(alive_g.astype(jnp.int32))
        else:
            clock = es.clock.at[g].set(t_s)
            nevents = es.nevents.at[g].add(1)
        fired0 = c >= theta
        es = es._replace(
            w=w, c=c, i=es.i + 1, ev=ev + 1, t=t_s,
            clock=clock,
            nevents=nevents,
            casc_key=es.casc_key.at[ev].set(k_chain),
            gmu=es.gmu.at[ev].set(g), q2=es.q2.at[ev].set(res.q2[0]),
            greedy=es.greedy.at[ev].set(res.greedy_steps[0]),
            rounds=es.rounds + 1,
            **extra,
        )
        if max_waves >= 1:
            es = fire(es, fired0, ev, t_s, jnp.int32(1))
        return es

    def delivery_round(es: EventState, tmin, gmin, cmin, sel) -> EventState:
        """Deliver one round of weight broadcasts (one cascade wave,
        ``core.pool.deliver``): every receiver adapts by the merged rule, is
        Bernoulli-driven once per received message, and newly
        super-threshold receivers fire while the cascade is under its wave
        cap. The (4, side, side) Bernoulli tensor comes whole from the
        cascade's own key chain — PRNG shapes are part of the bitwise
        contract."""
        cid = cmin
        sched_i = i0 + cid
        k_wave = es.wcount[cid] + 1
        es, received = pool_lib.deliver(
            es, tmin, cid, sel, (side, side), p_fn(sched_i, cfg),
            l_c_fn(sched_i, cfg), widths, dead_at(tmin) if dead_on else None)
        es = es._replace(t=tmin, wcount=es.wcount.at[cid].set(k_wave),
                         rounds=es.rounds + 1)
        allowed = (es.c >= theta) & received & (k_wave < max_waves)
        return fire(es, allowed, cid, tmin, gmin + 1)

    return sample_round, delivery_round, pool_min, fire


def _finish(es: EventState, far, near):
    """Package the end-of-run (state, aux, report) triple. A max_rounds exit
    can strand in-flight messages and unconsumed samples; the former count
    as dropped and the latter show through the true consumed count, so
    truncation is never silent."""
    final = AFMState(es.w, es.c, far, near, es.i)
    aux = afm_lib.StepAux(
        gmu=es.gmu[:, None], q2=es.q2[:, None], cascade_size=es.sizes,
        waves=es.wcount, greedy_steps=es.greedy[:, None])
    stranded = es.msg_t.shape[0] - es.free_n     # pool-size invariant
    report = EventReport(
        rounds=es.rounds, samples=es.ev,
        deliveries=es.deliveries, dropped=es.dropped + stranded,
        t_end=es.t, clock=es.clock, nevents=es.nevents,
        sent=es.sent, dropped_fault=es.dropped_fault, stranded=stranded,
        samples_dead=es.samples_dead,
        shard_counts=jnp.stack([es.sent, es.deliveries, es.dropped,
                                es.dropped_fault, stranded])[None, :],
        narrow_rounds=es.narrow_rounds, narrow_fires=es.narrow_fires,
        wide_fires=es.wide_fires)
    return final, aux, report


def _zero_fast_ok(cfg: AFMConfig, ecfg: EventConfig, num_events: int) -> bool:
    """True when the fused reference scan is bitwise-equivalent to simulating
    the rounds: zero latency (the parity regime), no explicit round budget
    (no truncation to account), auto engine, and a pool that cannot overflow
    (at zero latency occupancy peaks at one fire's ≤ 4N messages). An
    active fault plan always disqualifies it: faults are simulated, so the
    discrete-event engine must run."""
    m, _, _, _ = _resolve(cfg, ecfg, num_events)
    return (ecfg.latency == "zero" and ecfg.engine == "auto"
            and ecfg.max_rounds is None and m >= 4 * cfg.n_units
            and not ecfg.fault_active)


def _make_fused_zero(cfg: AFMConfig, ecfg: EventConfig, num_events: int,
                     search: Callable, p_fn: Callable, l_c_fn: Callable):
    """Zero-latency fast path: the ``reference`` backend's fused step scan
    (identical op sequence, so bitwise-identical weights/counters/aux) plus
    an accounting sidecar that reproduces the engine's ``EventReport``
    exactly — rounds, per-unit clocks/event counts, delivery totals.

    ``ecfg.kernel`` swaps the per-step body: 'staged' keeps the inline jnp
    scan below; 'fused' / 'fused-interpret' delegate the post-search step to
    the ``kernels.fused`` megakernel (one HBM pass over W), whose receive
    sidecar and tail loop reproduce the same accounting bitwise."""
    from repro.kernels.bmu import ops as bmu_ops
    from repro.kernels.fused import ops as fused_ops

    n, d, side, theta = cfg.n_units, cfg.dim, cfg.side, cfg.theta
    _, _, max_waves, _ = _resolve(cfg, ecfg, num_events)
    e = num_events
    spacing = ecfg.sample_spacing
    if ecfg.kernel == "fused-interpret":
        kflags = (True, True)             # real kernel body, interpreted
    else:
        kflags = bmu_ops.resolve_flags(None, None)

    def go(state: AFMState, samples, step_keys, lat_key):
        del lat_key                       # zero latency consumes no delays
        far, near = state.far, state.near
        i0 = jnp.asarray(state.i, jnp.int32)

        def body_fused(carry, xs):
            # megakernel step: search stays external (the engine's per-event
            # relay race / exact pass), the kernel fuses adapt + drive +
            # waves; ``recv0=nev`` threads the receipt sidecar through it
            w, c, nev, clock = carry
            sample, key, ev = xs
            i = i0 + ev
            t_s = ev.astype(jnp.float32) * spacing
            k_search, k_cascade = jax.random.split(key)
            st = AFMState(w, c, far, near, i)
            res = search(st, sample[None, :], k_search, cfg)
            parts = fused_ops.fused_step_parts(
                w, c, sample[None, :], k_cascade, cfg,
                l_c=l_c_fn(i, cfg), p_i=p_fn(i, cfg), search_result=res,
                use_pallas=kflags[0], interpret=kflags[1], recv0=nev)
            clock = jnp.where(parts.recv != nev, t_s, clock)
            carry = (parts.w, parts.c, parts.recv, clock)
            ys = (res.gmu[0], res.q2[0], res.greedy_steps[0],
                  parts.size, parts.waves)
            return carry, ys

        def body(carry, xs):
            # per-unit accounting stays out of the per-step path: the
            # sample-event contributions to clock/nevents are vectorized
            # after the scan from the aux trajectory; only the (rare) wave
            # loop accumulates its receiver counts inline
            w, c, nev, clock = carry
            sample, key, ev = xs
            i = i0 + ev
            t_s = ev.astype(jnp.float32) * spacing
            k_search, k_cascade = jax.random.split(key)
            l_c = l_c_fn(i, cfg)
            p_i = p_fn(i, cfg)
            st = AFMState(w, c, far, near, i)
            res = search(st, sample[None, :], k_search, cfg)
            w2, counts = afm_lib.adapt_gmu(st, sample[None, :], res.gmu, cfg)
            k_drive, k_chain = jax.random.split(k_cascade)
            gmu_mask = counts.astype(jnp.int32).reshape(side, side)
            draws = jax.random.uniform(k_drive, (8, side, side)) < p_i
            inc = jnp.sum(
                draws.astype(jnp.int32)
                * (jnp.arange(8)[:, None, None] < jnp.minimum(gmu_mask, 8)),
                axis=0)
            cg = c.reshape(side, side) + inc
            fired0 = cg >= theta
            wg = w2.reshape(side, side, d)

            # wave loop: op-for-op ``core.cascade.cascade`` (the sidecar
            # counters consume no PRNG and touch no w/c math)
            def wcond(cc):
                return jnp.any(cc[2]) & (cc[5] < max_waves)

            def wbody(cc):
                wv, cv, fired, kk, size, waves, ne = cc
                kk, sub = jax.random.split(kk)
                firedf = fired.astype(wv.dtype)
                sum_wk = cascade_lib._shift_sum(wv * firedf[..., None])
                bern = jax.random.uniform(sub, (4, side, side)) < p_i
                cv, new_fired, n_recv = cascade_lib._wave_jnp(
                    cv, fired, bern, theta)
                nf = n_recv.astype(wv.dtype)
                wv = wv + l_c * (sum_wk - nf[..., None] * wv)
                return (wv, cv, new_fired, kk,
                        size + fired.sum(dtype=jnp.int32), waves + 1,
                        ne + n_recv.reshape(-1))

            (wg, cg, _, _, size, waves, ne2) = jax.lax.while_loop(
                wcond, wbody,
                (wg, cg, fired0, k_chain, jnp.int32(0), jnp.int32(0), nev))
            # receipts this step (ne only grows) stamp the receiver clocks
            clock = jnp.where(ne2 != nev, t_s, clock)
            carry = (wg.reshape(n, d), cg.reshape(-1), ne2, clock)
            ys = (res.gmu[0], res.q2[0], res.greedy_steps[0], size, waves)
            return carry, ys

        carry0 = (state.w, jnp.asarray(state.c, jnp.int32),
                  jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.float32))
        xs = (samples, step_keys, jnp.arange(e, dtype=jnp.int32))
        step = body if ecfg.kernel == "staged" else body_fused
        (w, c, nev, clock), (gmu, q2, greedy, sizes, waves) = \
            jax.lax.scan(step, carry0, xs)
        deliv = jnp.sum(nev)            # wave receipts only, pre gmu fold-in
        final = AFMState(w, c, far, near, i0 + jnp.int32(e))
        aux = afm_lib.StepAux(
            gmu=gmu[:, None], q2=q2[:, None], cascade_size=sizes,
            waves=waves, greedy_steps=greedy[:, None])
        # fold the sample events into the per-unit accounting: one event
        # per step at its GMU, at time ev * spacing ("last event" == max
        # over event times, and a unit's wave clock is its max delivery
        # time, so elementwise max merges the two histories)
        t_ev = jnp.arange(e, dtype=jnp.float32) * spacing
        nev = nev.at[gmu].add(1)
        clock = jnp.maximum(clock, jnp.zeros((n,), jnp.float32)
                            .at[gmu].max(t_ev))
        # zero latency + a 4N-capable pool never drops, loses, or strands:
        # every attempted broadcast is delivered, so sent == deliveries
        # (the engine counts the same totals — the fast-path parity test
        # compares the report field for field)
        zero = jnp.int32(0)
        report = EventReport(
            rounds=jnp.int32(e) + jnp.sum(waves),
            samples=jnp.int32(e), deliveries=deliv, dropped=zero,
            t_end=jnp.float32((e - 1) * spacing),
            clock=clock, nevents=nev,
            sent=deliv, dropped_fault=zero, stranded=zero,
            samples_dead=zero,
            shard_counts=jnp.stack([deliv, deliv, zero, zero,
                                    zero])[None, :])
        return final, aux, report

    return go


def _make_engine(cfg: AFMConfig, ecfg: EventConfig, num_events: int,
                 search: Callable, p_fn: Callable, l_c_fn: Callable):
    """The default runner: an outer scan over the E sample arrivals with an
    inner while_loop that drains all due messages before each arrival (and a
    final drain to quiescence). Identical round order to the budgeted loop:
    pop min(message key, next arrival), messages first on a time tie."""
    e = num_events
    _, _, _, round_cap = _resolve(cfg, ecfg, num_events)
    spacing = ecfg.sample_spacing

    def go(state: AFMState, samples, step_keys, lat_key):
        es0 = init_events(state, cfg, ecfg, e, lat_key)
        sample_round, delivery_round, pool_min, _ = _make_round_fns(
            cfg, ecfg, e, search, p_fn, l_c_fn, i0=es0.i,
            far=state.far, near=state.near)

        def drain(es, t_limit):
            # round_cap is a safety net against engine bugs, not a semantic
            # budget (max_rounds=None here); a trip shows up as stranded
            # messages in report.dropped
            def cond(carry):
                es_, tmin, _g, _c, _sel, have = carry
                return have & (tmin <= t_limit) & (es_.rounds < round_cap)

            def body(carry):
                es_, tmin, g, ci, sel, _ = carry
                es_ = delivery_round(es_, tmin, g, ci, sel)
                return (es_,) + pool_min(es_)

            out = jax.lax.while_loop(cond, body, (es,) + pool_min(es))
            return out[0]

        def body(es, xs):
            sample, key = xs
            es = drain(es, es.ev.astype(jnp.float32) * spacing)
            return sample_round(es, sample, key), None

        es, _ = jax.lax.scan(body, es0, (samples, step_keys))
        es = drain(es, jnp.inf)
        return _finish(es, state.far, state.near)

    return go


def _make_budgeted(cfg: AFMConfig, ecfg: EventConfig, num_events: int,
                   search: Callable, p_fn: Callable, l_c_fn: Callable):
    """Budgeted runner (``max_rounds`` set): one while_loop popping a round
    per iteration under a global round budget — the original PR-4 loop
    structure, kept for its exact truncation accounting."""
    e = num_events
    m, _, _, max_rounds = _resolve(cfg, ecfg, num_events)
    spacing = ecfg.sample_spacing

    def go(state: AFMState, samples, step_keys, lat_key):
        es0 = init_events(state, cfg, ecfg, e, lat_key)
        sample_round, delivery_round, pool_min, _ = _make_round_fns(
            cfg, ecfg, e, search, p_fn, l_c_fn, i0=es0.i,
            far=state.far, near=state.near)

        def cond(es):
            return ((es.ev < e) | (es.free_n < m)) & (es.rounds < max_rounds)

        def body(es):
            tmin, gmin, cmin, sel, have = pool_min(es)
            t_next = jnp.where(es.ev < e,
                               es.ev.astype(jnp.float32) * spacing,
                               jnp.inf)
            # messages first on a time tie: an in-flight cascade front is
            # older than a fresh arrival at the same instant
            do_msg = have & (tmin <= t_next)
            return jax.lax.cond(
                do_msg,
                lambda s: delivery_round(s, tmin, gmin, cmin, sel),
                lambda s: sample_round(s, samples[s.ev], step_keys[s.ev]),
                es)

        es = jax.lax.while_loop(cond, body, es0)
        return _finish(es, state.far, state.near)

    return go


@functools.lru_cache(maxsize=32)
def _compiled_runner(cfg: AFMConfig, ecfg: EventConfig, num_events: int,
                     search: Callable, p_fn: Callable, l_c_fn: Callable,
                     donate: bool, placement):
    """One jitted simulation loop per static (config, latency, E, stages,
    placement) — placements are frozen dataclasses, hashable like the
    configs.

    Execution dispatch belongs to the placement: ``SinglePool`` statically
    picks the fused zero-latency scan, the sample-scan engine, or the
    budgeted loop (all three implement the same round semantics, pinned
    bitwise by ``tests/test_async_trainer.py``'s golden suite);
    ``MeshPlacement`` builds the shard_map runner (shards=1 delegates to
    ``SinglePool``). ``donate=True`` donates the input ``AFMState`` buffers
    to the run (the caller must own them — ``AsyncBackend.run`` does);
    donation is a no-op on CPU."""
    go = placement.build_runner(cfg, ecfg, num_events, search, p_fn, l_c_fn)
    return jax.jit(go, donate_argnums=(0,) if donate else ())


def run_events(state: AFMState, samples: jnp.ndarray, step_keys: jnp.ndarray,
               cfg: AFMConfig, ecfg: EventConfig = EventConfig(), *,
               search: Callable = afm_lib.search_heuristic,
               p_fn: Callable = _default_p, l_c_fn: Callable = _default_l_c,
               lat_key: jax.Array | None = None, lat_seed: int = 0,
               donate: bool = False, placement=None,
               shards: int | None = None,
               ) -> tuple[AFMState, afm_lib.StepAux, EventReport]:
    """Simulate ``E`` sample-delivery events (plus their cascades) to
    quiescence: the queue drains completely before returning, so the result
    is a plain dense ``AFMState`` with no in-flight messages. The only
    exception is the ``max_rounds`` safety bound firing early — messages
    stranded by that exit are counted into ``report.dropped`` so the
    truncation is never silent.

    Args:
      state:     dense starting state.
      samples:   (E, D) — the explicit per-event sample sequence.
      step_keys: (E, 2) uint32 — one PRNG key per sample event, split
                 exactly as the caller's training loop would (the ``async``
                 backend mirrors ``reference``'s key discipline, which is
                 what makes the zero-latency bitwise contract testable).
      cfg/ecfg:  AFM dynamics + event-engine configuration.
      search:    the search stage (``afm.search_heuristic`` or
                 ``afm.search_exact`` signature). A multi-shard mesh
                 placement maps ``search_exact`` to the sharded exact BMU
                 and anything else to the SPMD probe-and-reduce search.
      p_fn/l_c_fn: schedule overrides ``(i, cfg) -> scalar`` — the sandpile
                 parity tests pin p = 1 through these.
      lat_key:   PRNG key for the exponential latency stream (ignored by
                 the zero/constant models, which consume no extra bits).
      lat_seed:  seed for the latency stream when ``lat_key`` is not given;
                 the default (0) reproduces the historical golden
                 fingerprints. Ignored when ``lat_key`` is passed.
      donate:    donate the input state's buffers to the jitted run — only
                 safe when the caller owns them and drops the old state
                 (no-op on CPU, saves the dense-state copy on accelerators).
      placement: ``None`` / ``'single'`` (one pool, one device — the
                 default), ``'mesh'``, or a ``Placement`` instance
                 (``repro.core.placement``).
      shards:    shard count for ``placement='mesh'`` (``None`` -> 1).

    Seeding under a placement: ``lat_seed``/``lat_key`` name the *root* of
    the latency stream. ``SinglePool`` (and a 1-shard mesh, which runs the
    identical single-pool runner) consumes it directly; a multi-shard
    ``MeshPlacement`` derives one independent stream per shard as
    ``fold_in(lat_key, shard_id)`` — as it does for every other per-shard
    stream (probe, drive, cascade chains). The shard count is therefore
    part of the seeding contract: the same ``(lat_seed, shards)`` replays
    bitwise-identical weights (asserted by
    ``tests/test_placement.py::test_mesh_determinism_quality_accounting``),
    while a different ``shards`` draws a different — equally valid —
    sample of the same dynamics.
    """
    e = int(samples.shape[0])
    if e == 0:
        zero = jnp.int32(0)
        n = cfg.n_units
        return state, afm_lib.StepAux(
            gmu=jnp.zeros((0, 1), jnp.int32), q2=jnp.zeros((0, 1)),
            cascade_size=jnp.zeros((0,), jnp.int32),
            waves=jnp.zeros((0,), jnp.int32),
            greedy_steps=jnp.zeros((0, 1), jnp.int32)), EventReport(
                zero, zero, zero, zero, jnp.float32(0),
                jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.int32),
                sent=zero, dropped_fault=zero, stranded=zero,
                samples_dead=zero,
                shard_counts=jnp.zeros((1, 5), jnp.int32),
                narrow_rounds=zero, narrow_fires=zero, wide_fires=zero)
    if lat_key is None:
        lat_key = jax.random.PRNGKey(lat_seed)
    # the one reference from the engine up to the placements above it
    from repro.core.placement import base as placement_base

    pl = placement_base.resolve_placement(placement, shards=shards)
    fn = _compiled_runner(cfg, ecfg, e, search, p_fn, l_c_fn, bool(donate),
                          pl)
    out = fn(state, jnp.asarray(samples, jnp.float32),
             jnp.asarray(step_keys, jnp.uint32), lat_key)
    if ecfg.max_rounds is None and ecfg.latency != "zero":
        # Quiescence watchdog (ISSUE 10 satellite): with no explicit round
        # budget the engine is supposed to drain completely — its internal
        # round cap is a safety net against engine bugs, not a semantic
        # bound. Tripping it strands in-flight messages; silently returning
        # a truncated run here would violate the PR-4 truncation-visibility
        # contract, so raise instead. Callers who *want* budgeted
        # truncation set ``max_rounds`` and get the exact accounting.
        stranded = int(out[2].stranded)
        if stranded > 0:
            raise RuntimeError(
                f"run_events round budget exhausted at quiescence drain: "
                f"{stranded} message(s) stranded after "
                f"{int(out[2].rounds)} rounds (E={e}, "
                f"latency={ecfg.latency!r}, delay={ecfg.delay}). The "
                f"per-run safety cap of ~E*(max_waves+2) rounds was hit "
                f"before the pool drained — the latency/traffic mix is "
                f"generating more rounds than useful work. Set "
                f"EventConfig.max_rounds for budgeted truncation with "
                f"exact accounting, or reduce delay/sample_spacing ratio.")
    return out
