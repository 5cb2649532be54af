"""``MeshPlacement`` — the event engine partitioned across a device mesh.

The paper's cascade is local in space (a firing unit talks to its 4 lattice
neighbours) and sparse in time (messages exist only while a cascade runs),
which is exactly what makes the event engine partitionable: split the
lattice into contiguous row bands, give every shard its *own* message pool,
free-list ring, logical clocks, and round keys, and the only traffic that
ever crosses a shard boundary is a weight broadcast from a boundary-row
unit — at most ``2 · side`` candidate messages per round, batched into one
halo exchange (the ``ppermute`` idiom of ``core.distributed``).

Execution model (DESIGN.md §10):

- **per-shard rounds** — each drain iteration, every shard pops *its own*
  minimal ``(time, generation, cascade-id)`` round from its local pool and
  delivers it; shards working on different cascades in the same iteration
  is the intended semantics, not a race. The loop continues while any
  shard still has a due message (one scalar ``psum`` per iteration).
- **widths** — the band's pool is a ``core.pool`` pool, as the single
  pool is: a round's receiver side runs at ``core.pool.NARROW_WIDTH`` when
  the round selects at most that many messages and at the worst-case width
  otherwise (``EventReport.narrow_rounds`` counts the narrow ones, over
  shards); ``fire()``'s enqueue runs at that width when at most a quarter
  as many of the band's units fire, over all 4L candidates when more do,
  and not at all when none does (``narrow_fires`` and ``wide_fires``); a
  halo's arrivals enqueue at that width when at most that many are valid,
  and not at all when none is. The width never shows in the result.
- **halo exchange** — a delivery round's refires (and each sample round's
  threshold crossing) return an *outbox*: boundary-row fire masks plus the
  boundary-row weights. The exchange itself runs unconditionally every
  iteration (collectives cannot sit inside a data-dependent branch); an
  empty outbox exchanges zero masks. Receivers enqueue arriving halo
  messages into their own pool and draw the latency delay from their own
  stream. ``EventReport.exchanges`` counts the collective steps (drain
  iterations plus sample rounds) and ``halo_sent`` the messages enqueued
  from another shard's outbox.
- **collective search** — a sample round runs on all shards: each probes
  ``e / K`` of its local units, a min-reduce elects the winner, and each
  greedy hop is one more min-reduce over the incumbent's neighbours
  evaluated by their owners (the ``core.distributed`` search, at B = 1).
  ``search=afm.search_exact`` instead runs a full local distance pass per
  shard + one min-reduce. The GMU's Eq. (3) adaptation, counter drive,
  clock stamp, and any resulting fire happen on the owning shard only.
- **PRNG** — every per-shard stream derives by ``fold_in(key, shard_id)``:
  the probe key, the drive key, the per-cascade chain key, and the latency
  stream (``fold_in(lat_key, shard_id)``). Same seed + same shard count ⇒
  bitwise-identical weights; a different shard count is a different (but
  equally valid) sample of the same dynamics.

``MeshPlacement(shards=1)`` is served by the ``SinglePool`` runner: a
1-shard mesh has no partition boundary, so delegating makes the required
"shards=1 ≡ single" equivalence true by construction (and keeps the golden
bitwise contract exact rather than merely close).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import afm as afm_lib
from repro.core import events as events_lib
from repro.core import pool as pool_lib
from repro.core.distributed import _argmin_over_axis
from repro.core.placement.single import SinglePool
from repro.sharding import compat

#: Mesh axis name the event engine shards over.
AXIS = "shards"

GUARDED_BY = {"_MeshCache": {"_meshes": "_lock"}}


class _MeshCache:
    """Process-wide cache of event-engine device meshes.

    Placement state shared across threads: the stream-train loop rebuilds
    runners from its trainer thread while serving clients keep the main
    thread busy, and ``jax.make_mesh`` enumerates devices — one mesh per
    shard count, built once, handed out under the lock (REP301-checked
    via the module's ``GUARDED_BY``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._meshes: dict[int, object] = {}

    def get(self, shards: int):
        with self._lock:
            mesh = self._meshes.get(shards)
            if mesh is None:
                avail = len(jax.devices())
                if shards > avail:
                    raise ValueError(
                        f"MeshPlacement(shards={shards}) needs {shards} "
                        f"devices but only {avail} are visible (on CPU, set "
                        f"XLA_FLAGS=--xla_force_host_platform_device_count="
                        f"{shards} before importing jax)")
                mesh = compat.make_mesh((shards,), (AXIS,))
                self._meshes[shards] = mesh
            return mesh


_MESHES = _MeshCache()


class _Carry(NamedTuple):
    """Per-shard simulation state carried through the mesh round loop
    (the sharded analogue of ``events.EventState``; L = local units,
    m = per-shard pool slots)."""
    w: jnp.ndarray          # (L, D) f32 local unit weights
    c: jnp.ndarray          # (L,) i32 cascading counters
    clock: jnp.ndarray      # (L,) f32 per-unit logical clocks
    nevents: jnp.ndarray    # (L,) i32 events processed per unit
    msg_t: jnp.ndarray      # (m,) f32 delivery time (+inf = free slot)
    msg_gen: jnp.ndarray    # (m,) i32 round key: generation
    msg_cid: jnp.ndarray    # (m,) i32 round key: originating sample event
    msg_dst: jnp.ndarray    # (m,) i32 receiving unit (local index)
    msg_dir: jnp.ndarray    # (m,) i32 receiver-side direction code (0..3)
    msg_w: jnp.ndarray      # (m, D) f32 payload: sender weights at send time
    free_ring: jnp.ndarray  # (m,) i32 ring queue of free slot ids
    free_head: jnp.ndarray  # () i32
    free_n: jnp.ndarray     # () i32
    casc_key: jnp.ndarray   # (E, 2) u32 per-cascade local PRNG chain
    wcount: jnp.ndarray     # (E,) i32 max generation delivered locally
    sizes: jnp.ndarray      # (E,) i32 local firing incidents per cascade
    gmu: jnp.ndarray        # (E,) i32 aux (identical on every shard)
    q2: jnp.ndarray         # (E,) f32 aux (identical on every shard)
    greedy: jnp.ndarray     # (E,) i32 aux (identical on every shard)
    t: jnp.ndarray          # () f32 last locally processed round time
    drounds: jnp.ndarray    # () i32 local delivery rounds
    deliveries: jnp.ndarray  # () i32 local weight-message deliveries
    dropped: jnp.ndarray    # () i32 local pool-overflow drops
    lat_key: jnp.ndarray    # (2,) u32 per-shard latency stream
    # fault-injection sidecar (repro.faults) — zeros / untouched key when
    # the plan is inactive. Every counter is *pool-owner-side*: a halo
    # message's sent/loss/overflow accounting lands on the receiving shard
    # (the one that enqueues it), so per-shard identities hold exactly.
    sent: jnp.ndarray          # () i32 broadcast candidates enqueued here
    dropped_fault: jnp.ndarray  # () i32 injected losses + dead receivers
    samples_dead: jnp.ndarray  # () i32 samples owned here with a dead GMU
    fault_key: jnp.ndarray     # (2,) u32 per-shard fault stream
    narrow_rounds: jnp.ndarray  # () i32 local delivery rounds at k_narrow
    narrow_fires: jnp.ndarray   # () i32 local fire() enqueues run narrow
    wide_fires: jnp.ndarray     # () i32 local fire() enqueues run at 4L
    exchanges: jnp.ndarray      # () i32 collective steps (same on every
    #                              shard): drain iterations + sample rounds
    halo_sent: jnp.ndarray      # () i32 halo candidates enqueued here


#: The round key's lanes in a band's pool: halo metadata travels unpacked,
#: so the bands select by the exact lexicographic min.
_LANES = ("msg_gen", "msg_cid")


class _Outbox(NamedTuple):
    """One round's cross-shard traffic: boundary-row fire masks and the
    firing rows' weights, stamped with the round's (t, gen, cid). Masks are
    int32 (collectives), already zeroed at the global lattice boundary."""
    up_mask: jnp.ndarray    # (side,) i32 — top-row firings, for shard me-1
    up_w: jnp.ndarray       # (side, D) f32
    dn_mask: jnp.ndarray    # (side,) i32 — bottom-row firings, for me+1
    dn_w: jnp.ndarray       # (side, D) f32
    t: jnp.ndarray          # (1,) f32 send time
    gen: jnp.ndarray        # (1,) i32
    cid: jnp.ndarray        # (1,) i32


@dataclasses.dataclass(frozen=True)
class MeshPlacement:
    """Units + message pool partitioned over a ``shards``-device mesh.

    ``cfg.side`` must divide by ``shards`` (contiguous row bands); the pool
    ``capacity`` is split evenly per shard (default 8 · N/K slots each).
    ``max_rounds`` (the budgeted single-pool runner) is not supported —
    a global round budget has no per-shard meaning.
    """

    name = "mesh"
    shards: int = 1

    def __post_init__(self):
        if self.shards < 1:
            raise ValueError(f"shards must be >= 1, got {self.shards}")

    def pool_capacity(self, cfg, ecfg) -> int:
        """Per-shard pool slots: an even split of ``capacity``, or 8 · L.
        An active fault plan's ``pool_reserve`` withholds slots from every
        shard's pool (forced overflow pressure, counted as overflow)."""
        return pool_lib.capacity(ecfg, max(1, cfg.n_units // self.shards),
                                 self.shards)

    def build_runner(self, cfg, ecfg, num_events: int, search, p_fn, l_c_fn):
        if self.shards == 1:
            # no partition boundary: the single-pool runner IS the 1-shard
            # mesh, making shards=1 ≡ SinglePool bitwise by construction
            return SinglePool().build_runner(
                cfg, ecfg, num_events, search, p_fn, l_c_fn)
        if cfg.side % self.shards:
            raise ValueError(
                f"side={cfg.side} must divide into shards={self.shards} "
                f"contiguous row bands")
        if ecfg.max_rounds is not None:
            raise ValueError(
                "max_rounds (the budgeted runner) is single-pool only; a "
                "global round budget has no per-shard meaning under "
                "placement='mesh'")
        if ecfg.kernel != "staged":
            raise ValueError(
                "kernel='fused' is single-pool only (the megakernel holds "
                "the whole lattice in one program); use shards=1")
        if ecfg.fault_active and ecfg.plan.shard_latency_mult \
                and len(ecfg.plan.shard_latency_mult) != self.shards:
            raise ValueError(
                f"FaultPlan.shard_latency_mult has "
                f"{len(ecfg.plan.shard_latency_mult)} entries but the mesh "
                f"has shards={self.shards}; one multiplier per shard")
        return _build_mesh_runner(self, cfg, ecfg, num_events,
                                  search, p_fn, l_c_fn)


def _build_mesh_runner(pl: MeshPlacement, cfg, ecfg, num_events: int,
                       search, p_fn, l_c_fn):
    """Compile-time construction of the sharded runner ``go(state, samples,
    step_keys, lat_key)``. See the module docstring for the execution model;
    every closure below is per-shard code inside one ``shard_map``."""
    k_shards = pl.shards
    side, d, theta = cfg.side, cfg.dim, cfg.theta
    n = cfg.n_units
    rows = side // k_shards           # local lattice rows per shard
    length = rows * side              # L: local units per shard
    e = num_events
    spacing = ecfg.sample_spacing
    m = pl.pool_capacity(cfg, ecfg)
    # a round's selection: one local fire (≤ 4L) plus one halo burst
    # (≤ 2·side) at zero/constant latency
    widths = pool_lib.round_widths(ecfg, m, 4 * length + 2 * side)
    # fires of at most fire_units units and halos of at most halo_width
    # arrivals enqueue at the narrow width
    fire_units = pool_lib.fire_units(length)
    halo_width = (pool_lib.NARROW_WIDTH
                  if pool_lib.NARROW_WIDTH < 2 * side else 0)
    max_waves = events_lib.wave_cap(cfg)
    iter_cap = min(e * (max_waves + 2) + 1, 2 ** 31 - 1)
    e_local = max(1, cfg.e // k_shards)
    exact = search is afm_lib.search_exact
    use_far = cfg.greedy_use_far
    mesh = _MESHES.get(k_shards)
    # fault-plan closures (repro.faults): static Python branches, so an
    # inactive plan builds the exact fault-free graph (same contract as the
    # single-pool engine)
    plan = ecfg.plan
    loss_on = ecfg.fault_active and plan.p_loss > 0.0
    dead_on = ecfg.fault_active and plan.dropout_active
    straggle_on = ecfg.fault_active and bool(plan.shard_latency_mult)
    if dead_on:
        dead_global = plan.dead_units(n)
        d_lo = plan.dropout_start
        d_hi = plan.dropout_start + plan.dropout_len

    # --- static local-lattice tables (shard-relative, boundary rows route
    # through the halo, off-lattice columns are dropped) ---
    uu = jnp.arange(length, dtype=jnp.int32)
    rr, ss = uu // side, uu % side
    # candidate order (up, down, left, right) == receiver direction codes
    # (0 from-below, 1 from-above, 2 from-right, 3 from-left), as in
    # core.pool.routes
    tables = pool_lib.routes(jnp.stack([
        jnp.where(rr > 0, uu - side, -1),
        jnp.where(rr < rows - 1, uu + side, -1),
        jnp.where(ss > 0, uu - 1, -1),
        jnp.where(ss < side - 1, uu + 1, -1),
    ], axis=1).reshape(-1))                                      # (4L,)
    # halo arrival tables: from-above lands on my row 0 (dir 1 = from
    # row-1), from-below lands on my last row (dir 0 = from row+1)
    halo_dst = jnp.concatenate([
        jnp.arange(side, dtype=jnp.int32),
        length - side + jnp.arange(side, dtype=jnp.int32)])
    halo_dir = jnp.concatenate([
        jnp.full((side,), 1, jnp.int32), jnp.full((side,), 0, jnp.int32)])
    dn_perm = [(i, (i + 1) % k_shards) for i in range(k_shards)]
    up_perm = [(i, (i - 1) % k_shards) for i in range(k_shards)]

    def straggle():
        """This shard's latency factor under a straggler fault, else None:
        everything entering shard k's pool takes mult[k]x longer (a slow
        host delays the messages it owns — halo arrivals draw delays
        receiver-side, so this covers cross-shard traffic into the
        straggler too)."""
        if not straggle_on:
            return None
        mults = jnp.asarray(plan.shard_latency_mult, jnp.float32)
        return mults[jax.lax.axis_index(AXIS)]

    def empty_outbox():
        zi = jnp.zeros((side,), jnp.int32)
        zw = jnp.zeros((side, d), jnp.float32)
        return _Outbox(zi, zw, zi, zw, jnp.zeros((1,), jnp.float32),
                       jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32))

    def skip(pool):
        # a fire of no unit: only the loss stream moves, as it did in the
        # unconditional enqueue this skip replaced
        if loss_on:
            pool = dict(pool, fault_key=jax.random.split(pool["fault_key"])[0])
        return pool

    def fire(cy: _Carry, me, fired, cid, t, gen):
        """Broadcast-after-theta on the local band (``core.pool.fire``):
        reset counters, enqueue the in-shard neighbour messages, and emit
        the boundary-row firings as this round's outbox (delivered by the
        caller's exchange). Fault accounting is owner-side: ``sent`` counts
        every valid candidate before the loss draw, so sent == delivered +
        overflow + fault + stranded per shard."""
        gi = jnp.asarray(gen, jnp.int32)
        ci = jnp.asarray(cid, jnp.int32)
        cy = pool_lib.fire(cy, fired, cid, t, {"msg_gen": gi, "msg_cid": ci},
                           tables, fire_units, ecfg, skip=skip,
                           mult=straggle())
        out = _Outbox(
            up_mask=(fired[:side] & (me > 0)).astype(jnp.int32),
            up_w=cy.w[:side],
            dn_mask=(fired[length - side:]
                     & (me < k_shards - 1)).astype(jnp.int32),
            dn_w=cy.w[length - side:],
            t=jnp.asarray(t, jnp.float32).reshape(1),
            gen=gi.reshape(1), cid=ci.reshape(1))
        return cy, out

    def exchange(cy: _Carry, out: _Outbox) -> _Carry:
        """The batched per-round halo: every shard's outbox crosses one
        partition boundary in each direction (one ppermute pair), and the
        receiver enqueues what arrives, drawing latency delays from its own
        stream. Runs unconditionally every round iteration — an idle round
        exchanges zero masks — because collectives cannot live inside a
        data-dependent branch."""
        with jax.named_scope(obs.MESH_EXCHANGE):
            return _exchange(cy, out)

    def _exchange(cy: _Carry, out: _Outbox) -> _Carry:
        def shift(x, perm):
            return jax.lax.ppermute(x, AXIS, perm)
        # what I receive "from above" is the shard-above's down-outbox
        a_mask, a_w, a_t, a_gen, a_cid = (
            shift(out.dn_mask, dn_perm), shift(out.dn_w, dn_perm),
            shift(out.t, dn_perm), shift(out.gen, dn_perm),
            shift(out.cid, dn_perm))
        b_mask, b_w, b_t, b_gen, b_cid = (
            shift(out.up_mask, up_perm), shift(out.up_w, up_perm),
            shift(out.t, up_perm), shift(out.gen, up_perm),
            shift(out.cid, up_perm))
        # senders zero their boundary masks at the lattice edge, so the
        # ring wrap (shard K-1 -> 0 and 0 -> K-1) arrives all-invalid
        valid = jnp.concatenate([a_mask, b_mask]) != 0
        lat_key, lat_sub = pool_lib.split_latency(ecfg, cy.lat_key)
        tv = jnp.concatenate([jnp.full((side,), a_t[0]),
                              jnp.full((side,), b_t[0])])
        tv = tv + pool_lib.delays(ecfg, lat_sub, 2 * side, straggle())
        genv = jnp.concatenate([jnp.full((side,), a_gen[0]),
                                jnp.full((side,), b_gen[0])])
        cidv = jnp.concatenate([jnp.full((side,), a_cid[0]),
                                jnp.full((side,), b_cid[0])])
        wv = jnp.concatenate([a_w, b_w], axis=0)
        nvalid = jnp.sum(valid, dtype=jnp.int32)
        fault_key, keep = pool_lib.lose(ecfg, cy.fault_key, 2 * side)
        cy = cy._replace(lat_key=lat_key, fault_key=fault_key,
                         exchanges=cy.exchanges + 1,
                         halo_sent=cy.halo_sent + nvalid)

        def arrive(narrow: bool, pool):
            if narrow:
                # the valid arrivals in ascending order, as the wide
                # enqueue ranks them
                idx = pool_lib.compress(valid, halo_width)
                ii = jnp.minimum(idx, 2 * side - 1)
                return pool_lib.enqueue(
                    pool, cy.free_ring, idx < 2 * side,
                    None if keep is None else keep[ii], halo_dst[ii],
                    halo_dir[ii], wv[ii], tv[ii],
                    {"msg_gen": genv[ii], "msg_cid": cidv[ii]})
            return pool_lib.enqueue(pool, cy.free_ring, valid, keep,
                                    halo_dst, halo_dir, wv, tv,
                                    {"msg_gen": genv, "msg_cid": cidv})

        # most iterations carry no arrival, and a burst seldom more than
        # halo_width: skip the enqueue, or run it at that width
        pool, _ = pool_lib.enqueue_by_count(
            nvalid, halo_width, lambda p: p, arrive, pool_lib.view(cy, _LANES))
        return cy._replace(**pool)

    def make_round_fns(me, i0, near_g, far_g):
        """Per-shard round handlers (closures over the shard index, the
        run's starting sample count, and the replicated link tables — all
        loop-invariant)."""
        if dead_on:
            # the local band of the plan's global dead-unit mask: the dead
            # set is shard-layout-independent, only its ownership is sliced
            dead_band = jax.lax.dynamic_slice(
                dead_global.astype(jnp.int32), (me * length,),
                (length,)) != 0

            def dead_at(t):
                """(L,) bool — local units dead at simulated time ``t``."""
                return dead_band & (t >= d_lo) & (t < d_hi)

        def delivery_round(cy: _Carry, tmin, gmin, cmin, sel):
            """Deliver one local round (``core.pool.deliver``, the single
            pool's delivery on the local band). Refire gating uses the
            message generation (``gmin < max_waves``), which is the globally
            consistent wave depth regardless of how many rounds this shard
            happened to process."""
            cid = cmin
            sched_i = i0 + cid
            dead = dead_at(tmin) if dead_on else None
            cy, received = pool_lib.deliver(
                cy, tmin, cid, sel, (rows, side), p_fn(sched_i, cfg),
                l_c_fn(sched_i, cfg), widths, dead)
            cy = cy._replace(
                t=jnp.maximum(cy.t, tmin),
                wcount=cy.wcount.at[cid].set(
                    jnp.maximum(cy.wcount[cid], gmin)),
                drounds=cy.drounds + 1)
            allowed = (cy.c >= theta) & received & (gmin < max_waves)
            if dead_on:
                allowed = allowed & ~dead
            return fire(cy, me, allowed, cid, tmin, gmin + 1)

        def greedy(w_loc, sample, jstar, qstar):
            """Min-reduce greedy descent at B=1: each hop's candidates are
            evaluated by their owning shard, one argmin-reduce elects the
            global winner. The loop predicate derives from the collective
            result, so every shard iterates in lockstep."""
            lo = me * length

            def gbody(carry):
                j, q, active, steps = carry
                cands = (jnp.concatenate([near_g[j], far_g[j]], axis=-1)
                         if use_far else near_g[j])
                is_valid = cands >= 0
                local = is_valid & (cands >= lo) & (cands < lo + length)
                lidx = jnp.clip(cands - lo, 0, length - 1)
                dq = jnp.sum((w_loc[lidx] - sample[None, :]) ** 2, axis=-1)
                dq = jnp.where(local, dq, jnp.inf)
                kb = jnp.argmin(dq)
                q_glob, j_glob = _argmin_over_axis(
                    dq[kb][None], cands[kb][None].astype(jnp.int32), AXIS)
                improve = active & (q_glob[0] < q)
                return (jnp.where(improve, j_glob[0], j),
                        jnp.where(improve, q_glob[0], q),
                        improve, steps + 1)

            def gcond(carry):
                return carry[2] & (carry[3] < jnp.int32(n))

            j, q, _, steps = jax.lax.while_loop(
                gcond, gbody,
                (jstar, qstar, jnp.bool_(True), jnp.int32(0)))
            return j, q, steps

        def search_round(w_loc, sample, k_search):
            """(global unit, its squared distance, greedy hops) of the
            sample: the local distance pass, then the min-reduce."""
            if exact:
                q = jnp.sum((w_loc - sample[None, :]) ** 2, axis=-1)
                jl = jnp.argmin(q)
                q2v, gmu_g = _argmin_over_axis(
                    q[jl][None], (me * length + jl).astype(jnp.int32)[None],
                    AXIS)
                return gmu_g[0], q2v[0], jnp.int32(0)
            kp = jax.random.fold_in(k_search, me)
            probes = jax.random.randint(kp, (e_local,), 0, length)
            q = jnp.sum((w_loc[probes] - sample[None, :]) ** 2, axis=-1)
            kb = jnp.argmin(q)
            qstar, jstar = _argmin_over_axis(
                q[kb][None],
                (me * length + probes[kb]).astype(jnp.int32)[None], AXIS)
            return greedy(w_loc, sample, jstar[0], qstar[0])

        def sample_round(cy: _Carry, sample, step_key, ev):
            """Deliver the next sample collectively: probe-and-reduce (or
            exact) search elects the GMU, the owning shard applies Eq. (3),
            draws the counter drive, and fires on a threshold crossing."""
            t_s = ev.astype(jnp.float32) * spacing
            i_now = i0 + ev
            k_search, k_cascade = jax.random.split(step_key)
            p_i = p_fn(i_now, cfg)
            with jax.named_scope(obs.MESH_SEARCH):
                gmu_g, q2v, gsteps = search_round(cy.w, sample, k_search)
            # Eq. (3) at the owner (index `length` is out-of-band -> drop)
            lo = me * length
            mine = (gmu_g >= lo) & (gmu_g < lo + length)
            lu = jnp.clip(gmu_g - lo, 0, length - 1)
            extra = {}
            if dead_on:
                # a dead GMU neither adapts nor is driven; the sample is
                # consumed and counted by the owning shard (search + PRNG
                # streams advance identically — determinism is per-plan)
                alive_g = ~dead_at(t_s)[lu]
                mine_live = mine & alive_g
                extra["samples_dead"] = (
                    cy.samples_dead
                    + (mine & ~alive_g).astype(jnp.int32))
            else:
                mine_live = mine
            owner_at = jnp.where(mine_live, lu, length)
            upd = cy.w[lu] + cfg.l_s * (sample - cy.w[lu])
            w = cy.w.at[owner_at].set(upd, mode="drop")
            # counter drive: one Bernoulli at the GMU from the owner's
            # per-shard drive stream
            k_drive, k_chain = jax.random.split(k_cascade)
            hit = jax.random.uniform(jax.random.fold_in(k_drive, me),
                                     ()) < p_i
            c = cy.c.at[jnp.where(mine_live & hit, lu, length)].add(
                1, mode="drop")
            fired0 = c >= theta
            if dead_on:
                fired0 = fired0 & ~dead_at(t_s)
            cy = cy._replace(
                w=w, c=c, t=jnp.maximum(cy.t, t_s),
                clock=cy.clock.at[owner_at].set(t_s, mode="drop"),
                nevents=cy.nevents.at[owner_at].add(1, mode="drop"),
                casc_key=cy.casc_key.at[ev].set(
                    jax.random.fold_in(k_chain, me)),
                gmu=cy.gmu.at[ev].set(gmu_g),
                q2=cy.q2.at[ev].set(q2v),
                greedy=cy.greedy.at[ev].set(gsteps),
                **extra)
            if max_waves >= 1:
                cy, out = fire(cy, me, fired0, ev, t_s, jnp.int32(1))
            else:
                out = empty_outbox()
            return exchange(cy, out)

        def drain(cy: _Carry, t_limit):
            """Run delivery rounds until no shard holds a due message.
            Each iteration: shards with a due round deliver it (local
            branch — no collectives inside), then all shards exchange
            halos unconditionally and re-select."""
            def select(cy):
                with jax.named_scope(obs.EVENTS_POOL):
                    return pool_lib.pool_min_lex(cy.msg_t, cy.msg_gen,
                                                 cy.msg_cid)

            def dcond(st):
                cy_, (tmin, _g, _c, _s, have), it = st
                due = have & (tmin <= t_limit)
                with jax.named_scope(obs.MESH_EXCHANGE):
                    anydue = jax.lax.psum(due.astype(jnp.int32), AXIS) > 0
                return anydue & (it < iter_cap)

            def dbody(st):
                cy_, (tmin, g, ci, sel, have), it = st
                due = have & (tmin <= t_limit)
                cy_, out = jax.lax.cond(
                    due,
                    lambda c: delivery_round(c, tmin, g, ci, sel),
                    lambda c: (c, empty_outbox()),
                    cy_)
                cy_ = exchange(cy_, out)
                return (cy_, select(cy_), it + 1)

            st = jax.lax.while_loop(dcond, dbody,
                                    (cy, select(cy), jnp.int32(0)))
            return st[0]

        return sample_round, drain

    def local_body(w, c, near_g, far_g, i0, samples, step_keys, lat_key):
        # per-device views: w (rows, side, D); everything else replicated
        me = jax.lax.axis_index(AXIS)
        sample_round, drain = make_round_fns(me, i0, near_g, far_g)
        z = jnp.zeros
        cy = _Carry(
            w=w.reshape(length, d), c=c,
            clock=z((length,), jnp.float32), nevents=z((length,), jnp.int32),
            msg_t=jnp.full((m,), jnp.inf, jnp.float32),
            msg_gen=z((m,), jnp.int32), msg_cid=z((m,), jnp.int32),
            msg_dst=z((m,), jnp.int32), msg_dir=z((m,), jnp.int32),
            msg_w=z((m, d), jnp.float32),
            free_ring=jnp.arange(m, dtype=jnp.int32),
            free_head=jnp.int32(0), free_n=jnp.int32(m),
            casc_key=z((e, 2), jnp.uint32), wcount=z((e,), jnp.int32),
            sizes=z((e,), jnp.int32), gmu=z((e,), jnp.int32),
            q2=z((e,), jnp.float32), greedy=z((e,), jnp.int32),
            t=jnp.float32(0.0), drounds=jnp.int32(0),
            deliveries=jnp.int32(0), dropped=jnp.int32(0),
            lat_key=jax.random.fold_in(lat_key, me),
            sent=jnp.int32(0), dropped_fault=jnp.int32(0),
            samples_dead=jnp.int32(0),
            fault_key=(jax.random.fold_in(
                jax.random.PRNGKey(plan.seed), me)
                if ecfg.fault_active else z((2,), jnp.uint32)),
            narrow_rounds=jnp.int32(0), exchanges=jnp.int32(0),
            halo_sent=jnp.int32(0), narrow_fires=jnp.int32(0),
            wide_fires=jnp.int32(0))

        def sbody(cy, xs):
            sample, key, ev = xs
            cy = drain(cy, ev.astype(jnp.float32) * spacing)
            return sample_round(cy, sample, key, ev), None

        cy, _ = jax.lax.scan(
            sbody, cy, (samples, step_keys, jnp.arange(e, dtype=jnp.int32)))
        cy = drain(cy, jnp.inf)
        stranded = m - cy.free_n       # nonzero only on an iter_cap trip
        # per-shard accounting row [sent, delivered, overflow, fault,
        # stranded]: gathered sharded into the report's (K, 5) table so the
        # conservation identity is checkable per shard, not just globally
        shard_row = jnp.stack([cy.sent, cy.deliveries, cy.dropped,
                               cy.dropped_fault, stranded])[None, :]
        return (cy.w.reshape(rows, side, d), cy.c, cy.clock, cy.nevents,
                jax.lax.psum(cy.sizes, AXIS),
                jax.lax.pmax(cy.wcount, AXIS),
                cy.gmu, cy.q2, cy.greedy,
                jnp.int32(e) + jax.lax.psum(cy.drounds, AXIS),
                jax.lax.psum(cy.deliveries, AXIS),
                jax.lax.psum(cy.dropped + stranded, AXIS),
                jax.lax.pmax(cy.t, AXIS),
                jax.lax.psum(cy.sent, AXIS),
                jax.lax.psum(cy.dropped_fault, AXIS),
                jax.lax.psum(stranded, AXIS),
                jax.lax.psum(cy.samples_dead, AXIS),
                jax.lax.psum(cy.narrow_rounds, AXIS),
                jax.lax.pmax(cy.exchanges, AXIS),
                jax.lax.psum(cy.halo_sent, AXIS),
                jax.lax.psum(cy.narrow_fires, AXIS),
                jax.lax.psum(cy.wide_fires, AXIS),
                shard_row)

    sharded = P(AXIS)
    repl = P()
    mapped = compat.shard_map(
        local_body, mesh=mesh,
        in_specs=(sharded, sharded, repl, repl, repl, repl, repl, repl),
        out_specs=(sharded, sharded, sharded, sharded,
                   repl, repl, repl, repl, repl,
                   repl, repl, repl, repl,
                   repl, repl, repl, repl, repl, repl, repl, repl, repl,
                   sharded))

    def go(state, samples, step_keys, lat_key):
        (w, c, clock, nevents, sizes, waves, gmu, q2, greedy,
         rounds, deliveries, dropped, t_end,
         sent, dropped_fault, stranded, samples_dead, narrow_rounds,
         exchanges, halo_sent, narrow_fires, wide_fires,
         shard_counts) = mapped(
            state.w.reshape(side, side, d),
            jnp.asarray(state.c, jnp.int32),
            state.near, state.far, jnp.asarray(state.i, jnp.int32),
            samples, step_keys, jnp.asarray(lat_key, jnp.uint32))
        final = afm_lib.AFMState(
            w=w.reshape(n, d), c=c, far=state.far, near=state.near,
            i=jnp.asarray(state.i, jnp.int32) + jnp.int32(e))
        aux = afm_lib.StepAux(
            gmu=gmu[:, None], q2=q2[:, None], cascade_size=sizes,
            waves=waves, greedy_steps=greedy[:, None])
        report = events_lib.EventReport(
            rounds=rounds, samples=jnp.int32(e), deliveries=deliveries,
            dropped=dropped, t_end=t_end, clock=clock, nevents=nevents,
            sent=sent, dropped_fault=dropped_fault, stranded=stranded,
            samples_dead=samples_dead, shard_counts=shard_counts,
            narrow_rounds=narrow_rounds, exchanges=exchanges,
            halo_sent=halo_sent, narrow_fires=narrow_fires,
            wide_fires=wide_fires)
        return final, aux, report

    return go
