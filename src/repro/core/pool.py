"""The discrete-event engine's message pool, under both placements.

A pool is a fixed set of message slots. A slot holds a delivery time (+inf
marks a free slot), a round key, the receiving unit, the receiver-side
direction code and the sender's weights at send time; a ring of free slot
ids allocates them. The single pool (``core.events``) and each row band of
the mesh (``core.placement.mesh``) carry one under the same field names,
and the functions here read and write a carry by those names:

- ``msg_t``, ``msg_dst``, ``msg_dir``, ``msg_w`` — the messages;
- the round key's lanes: ``msg_key`` (``gen · E + cid`` packed into one
  uint32 where ``key_scale`` allows) or ``msg_gen`` and ``msg_cid``;
- ``free_ring``, ``free_head``, ``free_n`` — the free-slot ring: entries
  ``[free_head, free_head + free_n)`` (mod M) are exactly the free slots;
- ``dropped``, ``sent``, ``dropped_fault``, ``fault_key`` — overflow and
  fault accounting, and the fault plan's PRNG stream;
- for a fire and a delivery round, also the units' ``w``, ``c``,
  ``clock``, ``nevents``, the per-cascade ``sizes`` and ``casc_key``, the
  latency stream ``lat_key`` and the counters ``deliveries``,
  ``narrow_rounds``, ``narrow_fires`` and ``wide_fires``.

What both placements share lives here once: the capacity rule, round
selection, the send side (the latency and loss draws, slot allocation, the
enqueue and the width it runs at) and the delivery round's receiver side
and its width. The callers keep what differs: round counting and wave
gating, the key lanes they use, the halo exchange.

**Widths.** Work is sized by the messages a round or a fire touches, not
by the pool: when at most ``NARROW_WIDTH`` of them are touched the work
runs at that static width, otherwise at the worst case, and both widths
add the same operands in the same order and take the same slots, so the
width never shows in the result. Runners read ``NARROW_WIDTH`` when they
are built. PRNG draws keep their full shapes at either width: the shapes
are part of the bitwise contract that the golden suite
(``tests/golden/async_engine.npz``) and the mesh's plain reference pin.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs

#: Static width of the work of a round or a fire that touches few messages.
#: An exponential-latency round delivers one message and a constant-latency
#: one the output of one ``fire()`` (≤ 4 per fired unit), so 64 covers every
#: exponential round and a fire of up to 16 units; wider ones run at the
#: worst-case width.
NARROW_WIDTH = 64

#: Bit pattern of float32 +inf. ``msg_t`` is always ≥ 0 (sample times and
#: delays are non-negative), so bit-casting it to uint32 is order-preserving
#: and a free slot (t = +inf) carries the largest key — the round-selection
#: min needs no separate ``isfinite`` mask.
INF_BITS = 0x7F800000

#: The carry fields an enqueue writes besides the key lanes (``fault_key``,
#: which its loss draw advances, among them).
_SEND_FIELDS = ("msg_t", "msg_dst", "msg_dir", "msg_w", "free_head",
                "free_n", "dropped", "sent", "dropped_fault", "fault_key")


def capacity(ecfg, units: int, parts: int = 1) -> int:
    """Slots of one pool serving ``units`` units: an even ``parts``-way
    split of ``ecfg.capacity``, or 8 · ``units``, at least 4.

    An active fault plan's ``pool_reserve`` withholds slots (forced
    overflow pressure — the drops land in ``dropped_overflow``, never the
    fault counter, pinning the accounting split)."""
    m = ecfg.capacity // parts if ecfg.capacity is not None else 8 * units
    if ecfg.fault_active:
        m = int(m) - ecfg.plan.pool_reserve
    return max(int(m), 4)


def round_widths(ecfg, slots: int, most: int) -> tuple[int, int]:
    """(k_narrow, k_round): the static widths of a delivery round. A round
    selects one (t, gen, cid): at zero/constant latency that is at most
    ``most`` messages (one fire's output, plus a halo burst on a mesh
    band); exponential delays can in principle tie across fires, so the
    worst case covers the pool. Rounds that select at most k_narrow
    messages run at that width."""
    k_round = slots if ecfg.latency == "exponential" else min(most, slots)
    return min(k_round, NARROW_WIDTH), k_round


# --- round selection ---------------------------------------------------------

def key_scale(num_events: int, max_waves: int) -> int | None:
    """E if ``(gen, cid)`` packs losslessly into one uint32 lane (the common
    case: key = gen · E + cid with gen ≤ max_waves + 1 and cid < E), else
    ``None`` — the engine then falls back to the exact 3-field lexicographic
    min, which is correct for any int32 gen/cid (no magic sentinel)."""
    if num_events <= 0:
        return None
    if (max_waves + 2) * num_events <= 2 ** 32:
        return num_events
    return None


def pool_min_lex(msg_t, msg_gen, msg_cid):
    """Exact lexicographic min over active messages: (t, gen, cid) -> round.

    The time lane is compared through its uint32 bit pattern (valid because
    ``msg_t`` ≥ 0 and free slots are +inf — see ``INF_BITS``); gen/cid use
    ``iinfo(int32).max`` as the masked fill, which stays correct even when a
    real gen/cid equals the fill (the old engine's ``2**30`` sentinel broke
    there — see the regression test)."""
    hi = jax.lax.bitcast_convert_type(msg_t, jnp.uint32)
    hi_min = jnp.min(hi)
    have = hi_min != jnp.uint32(INF_BITS)
    imax = jnp.int32(jnp.iinfo(jnp.int32).max)
    m1 = hi == hi_min
    gmin = jnp.min(jnp.where(m1, msg_gen, imax))
    m2 = m1 & (msg_gen == gmin)
    cmin = jnp.min(jnp.where(m2, msg_cid, imax))
    sel = m2 & (msg_cid == cmin)
    tmin = jax.lax.bitcast_convert_type(hi_min, jnp.float32)
    return tmin, gmin, cmin, sel, have


def pool_min_packed(msg_t, msg_key, scale: int):
    """Packed round-key min: 2 reduction passes instead of 3.

    Lane 1 is the bit-cast time, lane 2 the packed ``gen · scale + cid``
    (``scale`` == E, statically guaranteed not to overflow uint32 by
    ``key_scale``)."""
    hi = jax.lax.bitcast_convert_type(msg_t, jnp.uint32)
    hi_min = jnp.min(hi)
    have = hi_min != jnp.uint32(INF_BITS)
    lo_min = jnp.min(jnp.where(hi == hi_min, msg_key,
                               jnp.uint32(0xFFFFFFFF)))
    sel = (hi == hi_min) & (msg_key == lo_min)
    tmin = jax.lax.bitcast_convert_type(hi_min, jnp.float32)
    gmin = (lo_min // jnp.uint32(scale)).astype(jnp.int32)
    cmin = (lo_min % jnp.uint32(scale)).astype(jnp.int32)
    return tmin, gmin, cmin, sel, have


# --- widths ------------------------------------------------------------------

def compress(mask, width: int):
    """Ascending positions of the first ``width`` True entries of the 1-D
    ``mask``, filled with ``mask.size``: ``jnp.nonzero(mask, size=width,
    fill_value=mask.size)[0]``. ``jnp.nonzero`` counts the entries with a
    scatter-add over all of them, which the TPU runs serially (about 116 µs
    at 12,800 entries on a v5e); at a narrow width, the j-th position is
    the count of entries whose running True count is at most j, one fused
    ``width × size`` compare and sum (about 3 µs there)."""
    if width > NARROW_WIDTH:
        return jnp.nonzero(mask, size=width, fill_value=mask.size)[0]
    rank = jnp.cumsum(mask, dtype=jnp.int32)
    return jnp.sum(rank[None, :] <= jnp.arange(width, dtype=jnp.int32)[:, None],
                   axis=1, dtype=jnp.int32)


def fire_units(n_local: int) -> int:
    """Most fired units an enqueue takes at the narrow width (each sends at
    most 4 candidates), or 0 where ``n_local`` units' ``4·n_local``
    candidates are no wider than that: the enqueue then has one width."""
    units = NARROW_WIDTH // 4
    return units if NARROW_WIDTH < 4 * n_local else 0


def fire_candidates(fired, units: int):
    """The candidates of the first ``units`` fired units at a static width
    ``4 · units``: ids ``4·unit + direction`` in (unit, direction) order,
    which is the order of the wide enqueue's ``(units, 4)`` reshape, so the
    r-th valid candidate is the same at both widths. Returns (ids clipped
    into range, real) — fill entries past the fired units are not real."""
    unit = compress(fired, units)
    ids = (unit[:, None] * 4 + jnp.arange(4, dtype=jnp.int32)).reshape(-1)
    size = 4 * fired.shape[0]
    return jnp.minimum(ids, size - 1), ids < size


def enqueue_by_count(count, most: int, skip, enqueue, pool):
    """Run an enqueue at the width its ``count`` (of fired units, or of
    valid arrivals) asks for: ``skip(pool)`` when the count is 0,
    ``enqueue(True, pool)`` when it is at most ``most``, and
    ``enqueue(False, pool)`` when it is more (every nonzero count where
    ``most`` is 0). Returns (pool, whether the narrow width ran)."""
    wide = functools.partial(enqueue, False)
    if not most:
        return jax.lax.cond(count > 0, wide, skip, pool), jnp.bool_(False)
    return (jax.lax.switch(jnp.minimum(count, 1) + (count > most),
                           (skip, functools.partial(enqueue, True), wide),
                           pool),
            (count > 0) & (count <= most))


# --- the send side -----------------------------------------------------------

def routes(dst4):
    """The static candidate tables (source unit, destination unit,
    receiver-side direction code) of L units' outgoing broadcasts, from
    ``dst4``, their 4·L destinations in (unit, direction) order. A sender's
    4 messages go up, down, left, right, and land on the receiver direction
    codes 0 = from row+1 (below), 1 = from row-1 (above), 2 = from col+1
    (right), 3 = from col-1 (left) in that same slot order, which is the
    slot order of ``core.cascade._shift4``: the same (4, side, side)
    Bernoulli tensor indexes the event engine and the cascade alike."""
    units = dst4.shape[0] // 4
    src4 = jnp.repeat(jnp.arange(units, dtype=jnp.int32), 4)
    dirs4 = jnp.tile(jnp.arange(4, dtype=jnp.int32), (units, 1)).reshape(-1)
    return src4, dst4, dirs4


def split_latency(ecfg, lat_key):
    """(next latency key, this draw site's key). The exponential stream
    advances once per draw site whether or not anything is sent; zero and
    constant delays draw no bits, so their stream never moves."""
    if ecfg.latency == "exponential":
        return jax.random.split(lat_key)
    return lat_key, lat_key


def delays(ecfg, key, count: int, mult=None):
    """The latency model's delays of a site's ``count`` candidates, drawn
    at the site's full width whatever width its enqueue runs at. ``mult``
    scales them: a mesh band's straggler factor, or None."""
    if ecfg.latency == "exponential":
        base = jax.random.exponential(key, (count,)) * ecfg.delay
    elif ecfg.latency == "constant":
        base = jnp.full((count,), ecfg.delay, jnp.float32)
    else:
        base = jnp.zeros((count,), jnp.float32)
    return base if mult is None else base * mult


def lose(ecfg, fault_key, count: int):
    """The fault plan's loss draw over a site's ``count`` candidates, at the
    site's full width: (fault key, keep), or (the key untouched, None)
    without an active ``p_loss``."""
    plan = ecfg.plan
    if not (ecfg.fault_active and plan.p_loss > 0.0):
        return fault_key, None
    fault_key, sub = jax.random.split(fault_key)
    return fault_key, jax.random.uniform(sub, (count,)) >= plan.p_loss


def view(carry, lanes: tuple[str, ...]) -> dict:
    """The fields of ``carry`` an enqueue writes, by name: the key
    ``lanes`` and ``_SEND_FIELDS``. An enqueue's conditional branches take
    only these, so its skip branch is a no-op over small operands."""
    return {f: getattr(carry, f) for f in lanes + _SEND_FIELDS}


def allocate(valid, free_ring, free_head, free_n):
    """Pool slots off the free ring for the candidates ``valid`` marks, at
    its static width: the r-th valid candidate takes the r-th free slot and
    candidates past the free count get the out-of-range slot (their
    ``mode="drop"`` scatters write nothing). Returns (slot, allocated,
    overflow)."""
    m = free_ring.shape[0]
    rank = jnp.cumsum(valid, dtype=jnp.int32) - 1
    can = valid & (rank < free_n)
    slot = jnp.where(can, free_ring[(free_head + rank) % m], m)
    nalloc = jnp.sum(can, dtype=jnp.int32)
    return slot, nalloc, jnp.sum(valid, dtype=jnp.int32) - nalloc


def enqueue(pool: dict, free_ring, valid, keep, dst, dirs, w, t,
            keys: dict) -> dict:
    """Scatter the ``valid`` candidates into slots off the free ring
    (``allocate``); candidates past the free count are dropped and counted
    in ``dropped``. ``pool`` holds the fields an enqueue writes (``view``),
    ``keys`` the round key's value for each key lane the caller keeps.

    ``sent`` counts every valid candidate before the loss draw ``keep``
    (None without loss), whose losses count in ``dropped_fault``, so
    ``sent == delivered + overflow + fault + stranded`` holds for the
    pool."""
    sent = pool["sent"] + jnp.sum(valid, dtype=jnp.int32)
    dropped_fault = pool["dropped_fault"]
    if keep is not None:
        dropped_fault = dropped_fault + jnp.sum(valid & ~keep,
                                                dtype=jnp.int32)
        valid = valid & keep
    slot, nalloc, drop = allocate(valid, free_ring, pool["free_head"],
                                  pool["free_n"])
    out = dict(pool, sent=sent, dropped_fault=dropped_fault,
               msg_t=pool["msg_t"].at[slot].set(t, mode="drop"),
               msg_dst=pool["msg_dst"].at[slot].set(dst, mode="drop"),
               msg_dir=pool["msg_dir"].at[slot].set(dirs, mode="drop"),
               msg_w=pool["msg_w"].at[slot].set(w, mode="drop"),
               free_head=(pool["free_head"] + nalloc) % free_ring.shape[0],
               free_n=pool["free_n"] - nalloc,
               dropped=pool["dropped"] + drop)
    for lane, value in keys.items():
        out[lane] = pool[lane].at[slot].set(value, mode="drop")
    return out


def fire(carry, fired, cid, t, keys: dict, tables, units: int, ecfg,
         skip=None, mult=None):
    """Broadcast-after-theta over ``carry``'s units: the ``fired`` units
    reset their counters and count as firing incidents of cascade ``cid``,
    and their messages to their lattice neighbours (``tables``, from
    ``routes``; payload: the sender's current weights) enter the pool at
    ``t`` plus their delays, under the round key ``keys``.

    The enqueue's static width follows the fire's own count: a fire of at
    most ``units`` units enqueues their ≤ 4·``units`` candidates, a larger
    one all of them, and a fire of none runs ``skip`` on the pool fields
    (default: leave them). The narrow candidates are the wide ones in the
    same order, with their latency and loss draws gathered from the
    full-width tensors, so the width never shows in the pool
    (``narrow_fires`` / ``wide_fires`` count it). The latency stream
    advances once per call whether or not anything fired."""
    nfired = jnp.sum(fired, dtype=jnp.int32)
    sizes = carry.sizes.at[cid].add(nfired)
    c = jnp.where(fired, 0, carry.c)
    lat_key, lat_sub = split_latency(ecfg, carry.lat_key)
    src4, dst4, dirs4 = tables
    count = dst4.shape[0]

    def send(narrow: bool, pool):
        fault_key, keep = lose(ecfg, pool["fault_key"], count)
        pool = dict(pool, fault_key=fault_key)
        tv = t + delays(ecfg, lat_sub, count, mult)
        if narrow:
            ids, real = fire_candidates(fired, units)
            dst = dst4[ids]
            return enqueue(pool, carry.free_ring, real & (dst >= 0),
                           None if keep is None else keep[ids], dst,
                           dirs4[ids], carry.w[src4[ids]], tv[ids], keys)
        valid = (fired[:, None] & (dst4.reshape(-1, 4) >= 0)).reshape(-1)
        return enqueue(pool, carry.free_ring, valid, keep, dst4, dirs4,
                       carry.w[src4], tv, keys)

    # most rounds fire nothing: skip the pool scatters entirely then
    with jax.named_scope(obs.EVENTS_POOL):
        pool, narrow = enqueue_by_count(
            nfired, units, skip or (lambda p: p), send,
            view(carry, tuple(keys)))
    return carry._replace(
        c=c, sizes=sizes, lat_key=lat_key,
        narrow_fires=carry.narrow_fires + narrow.astype(jnp.int32),
        wide_fires=carry.wide_fires
        + ((nfired > 0) & ~narrow).astype(jnp.int32),
        **pool)


# --- the receiver side -------------------------------------------------------

def receive(carry, sel, nsel, width: int, bern, l_c, dead=None):
    """A delivery round's receiver side at static ``width`` ≥ ``nsel``, the
    count of selected slots ``sel``: returns (w, c, n_recv, ndeliv,
    free_ring).

    Work is sized by the round, not the map: the selected slots are
    compressed out of the pool, their payloads segment-summed per receiver
    in direction-slot order (bitwise the same sum order as
    ``core.cascade._shift_sum``), and the weight update is a row scatter
    over the receiver units. Each received message drives its receiver
    with the Bernoulli of its (direction, receiver) in ``bern``, (4, L).
    Messages to a unit ``dead`` marks are consumed (their slots free
    normally) but not delivered: no adapt, no drive, no stamp."""
    m = carry.msg_t.shape[0]
    n = carry.c.shape[0]
    # compress the selected messages: (width,) slot ids, fill = m
    with jax.named_scope(obs.EVENTS_POOL):
        idx = compress(sel, width)
        taken = idx < m
        ii = jnp.minimum(idx, m - 1)
        dsts = jnp.where(taken, carry.msg_dst[ii], n)  # n -> dropped
        dirs = jnp.where(taken, carry.msg_dir[ii], 0)
        ws = carry.msg_w[ii]                           # (width, D)
    with jax.named_scope(obs.EVENTS_DELIVER):
        ok = taken
        if dead is not None:
            ok = ok & ~dead[jnp.minimum(dsts, n - 1)]
        # counter drive: one Bernoulli per received message, from the
        # wave's (4, L) tensor by (direction, receiver)
        drive = jnp.where(ok, bern[dirs, jnp.minimum(dsts, n - 1)], False)
        c = carry.c.at[dsts].add(drive.astype(jnp.int32), mode="drop")
        n_recv = jnp.zeros((n,), jnp.int32).at[dsts].add(
            ok.astype(jnp.int32), mode="drop")
        # unique receiver rows (sorted, fill = n), ≤ one per message
        ridx = compress(n_recv > 0, width)
        pos = jnp.searchsorted(ridx, dsts)      # msg -> receiver row
        acc = jnp.zeros((width, carry.w.shape[1]), jnp.float32)
        for s4 in range(4):                     # direction-slot order
            acc = acc.at[jnp.where(ok & (dirs == s4), pos, width)
                         ].add(ws, mode="drop")
        # full receiver rows via the same elementwise chain as the dense
        # form (w + l_c*(S - nf*w)) so XLA emits the same fma pattern,
        # then a row scatter-set (ridx rows are unique)
        rv = jnp.minimum(ridx, n - 1)
        nf = n_recv[rv].astype(carry.w.dtype)
        wr = carry.w[rv]
        w_rows = wr + l_c * (acc - nf[:, None] * wr)
        w = carry.w.at[ridx].set(w_rows, mode="drop")
    # ``ok`` excludes dead receivers; the gap vs the nsel consumed slots is
    # the dead-receiver fault count
    ndeliv = nsel if dead is None else jnp.sum(ok, dtype=jnp.int32)
    # free the taken slots (dead receivers' too): push their ids onto the
    # ring tail, in ascending slot order
    with jax.named_scope(obs.EVENTS_POOL):
        tail = jnp.where(
            taken,
            (carry.free_head + carry.free_n
             + jnp.arange(width, dtype=jnp.int32)) % m, m)
        free_ring = carry.free_ring.at[tail].set(idx, mode="drop")
    return w, c, n_recv, ndeliv, free_ring


def deliver(carry, tmin, cid, sel, grid, p_i, l_c, widths, dead=None):
    """One delivery round of cascade ``cid``'s weight broadcasts at time
    ``tmin`` over ``carry``'s pool and units: every receiver adapts by the
    merged rule and is Bernoulli-driven once per received message. Returns
    (carry, received) — the caller stamps its round, gates the refires
    and fires them.

    The (4, *grid) Bernoulli tensor comes whole from the cascade's own key
    chain, and the receiver side (``receive``) runs at ``k_narrow`` when
    the round's own count allows it (every exponential round, most
    constant ones) and at ``k_round`` otherwise (``widths``), one
    ``lax.cond`` choosing; ``narrow_rounds`` counts the narrow ones. The
    taken slots are cleared and freed, receivers stamped at ``tmin``, and
    messages to ``dead`` receivers counted in ``dropped_fault``."""
    k_narrow, k_round = widths
    ck, sub = jax.random.split(carry.casc_key[cid])
    bern = (jax.random.uniform(sub, (4, *grid)) < p_i).reshape(4, -1)
    nsel = jnp.sum(sel, dtype=jnp.int32)
    recv = functools.partial(receive, carry, sel, nsel, bern=bern, l_c=l_c,
                             dead=dead)
    narrow_rounds = carry.narrow_rounds
    if k_narrow < k_round:
        narrow = nsel <= k_narrow
        w, c, n_recv, ndeliv, free_ring = jax.lax.cond(
            narrow, lambda: recv(k_narrow), lambda: recv(k_round))
        narrow_rounds = narrow_rounds + narrow.astype(jnp.int32)
    else:
        w, c, n_recv, ndeliv, free_ring = recv(k_round)
    received = n_recv > 0
    if dead is not None:
        carry = carry._replace(
            dropped_fault=carry.dropped_fault + (nsel - ndeliv))
    with jax.named_scope(obs.EVENTS_POOL):
        msg_t = jnp.where(sel, jnp.inf, carry.msg_t)
    return carry._replace(
        w=w, c=c,
        clock=jnp.where(received, tmin, carry.clock),
        nevents=carry.nevents + n_recv,
        msg_t=msg_t,
        free_ring=free_ring,
        free_n=carry.free_n + nsel,
        casc_key=carry.casc_key.at[cid].set(ck),
        deliveries=carry.deliveries + ndeliv,
        narrow_rounds=narrow_rounds), received
