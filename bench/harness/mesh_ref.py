"""Plain reference of the paper's event model partitioned over K row bands,
as the mesh placement states it (``core.placement.mesh``, module docstring;
DESIGN.md §10): the lattice is cut into K bands of ``side / K`` rows, and
every band has its own message pool, free-slot count, logical clocks and
key streams.

One sample arrives per ``spacing`` time units; before each arrival the
bands drain in lockstep iterations. In an iteration every band whose least
(time, generation, cascade id) message is due pops that round of its own
pool and delivers it: a delivery moves the receiver by ``l_c`` towards the
sender's weights as they were at send time and drives its counter with
probability p_i from the band's own draw; a receiver that reaches theta
fires, unless the round's generation has reached the wave cap. A firing
unit sends to its four lattice neighbours: messages to a unit of its own
band go into the band's pool, those across a band boundary are the band's
halo. Then every band takes the halo its neighbours sent in that
iteration into its own pool, each message delayed by the receiver's own
draw. The iterations go on while any band has a due message. A sample
round runs on every band: the best unit over the whole map (ties to the
lower index) adapts by Eq. (3), its owner draws the counter drive, every
band fires the units whose counter has reached theta, and the halo is
exchanged once more.

Key streams (``fold_in(., band)`` of the run's keys): per sample
``split(step_key) -> (search, cascade)``, ``split(cascade) -> (drive,
chain)``; the owner's drive is ``uniform(fold_in(drive, band))``; each
band's chain of cascade ``e`` starts at ``fold_in(chain, band)`` and
advances one split per delivery round of that cascade on the band, which
draws a (4, side / K, side) uniform tensor. Each band's latency stream
starts at ``fold_in(lat_key, band)`` and advances one split at every fire
(one Exp(1) per candidate message of the band: four per unit, in unit
order) and at every exchange (one per column, from above then from below),
whether or not anything fired or arrived.

Departures from the docstring: only exact search is replayed (the cell
searches exactly; the probe-and-reduce search and its greedy hops are
not); no fault plan; pool slots are counted, not kept (a band drops the
candidates past its free count, in candidate order), and a round's
messages are taken in the order they were sent, where the program takes
them in slot order (they differ only when one round delivers two messages
from the same direction to the same unit, which a continuous latency draw
does not make); the weights stay on one device, whole.

The bookkeeping is plain Python over one heap per band; the weights stay
on the device, where each update is one small jitted call.
"""
from __future__ import annotations

import heapq

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference as ref

#: Largest round the program runs at its narrow width (``_NARROW_WIDTH``).
NARROW_WIDTH = 64


def _bucket(k: int) -> int:
    return 1 << max(0, (k - 1).bit_length())


def _padded(values, size: int, fill, dtype):
    out = np.full((size,), fill, dtype)
    out[:len(values)] = values
    return out


class MeshReplay:
    """Replays ``partial_fit`` calls of the event engine under the mesh
    placement with ``shards`` bands.

    ``forced`` calls adapt the units the program chose and judge them with
    the reference's distances; free calls (the control) choose their own.
    ``fault`` plants a fault in the replay: ``frozen`` (the state left
    unchanged), ``half`` (half of each chunk left out), ``altered`` (each
    answer's unit moved by one) or ``nohalo`` (no band takes the halo its
    neighbours sent).
    """

    def __init__(self, p_items: tuple, *, shards: int, delay: float,
                 latency: str = "exponential", spacing: float = 1.0,
                 capacity: int | None = None, precision: str = "highest",
                 fault: str = "none"):
        p = dict(p_items)
        self.p = p
        self.n, self.side, self.theta = p["n"], p["side"], p["theta"]
        n, side, d = self.n, self.side, p["dim"]
        if side % shards:
            raise ValueError(f"side {side} does not split into {shards} "
                             f"bands")
        self.k = shards
        self.rows = side // shards
        self.length = self.rows * side
        self.latency, self.delay = latency, np.float32(delay)
        self.spacing = np.float32(spacing)
        self.cap = (capacity // shards if capacity is not None
                    else 8 * self.length)
        self.max_waves = p["max_waves"]
        # the program's widths: a round's worst case, and the narrow one
        k_round = (self.cap if latency == "exponential"
                   else min(4 * self.length + 2 * side, self.cap))
        self.narrow_width = (min(k_round, NARROW_WIDTH)
                             if NARROW_WIDTH < k_round else None)
        self.fault = fault
        prec = ref.PRECISIONS[precision]
        rows, length, bands = self.rows, self.length, jnp.arange(shards)

        def sched(i):
            return (ref.l_c(i, p["i_max"], p["c_o"], p["c_s"]),
                    ref.p_i(i, p["i_max"], n, p["c_m"], p["c_d"]))

        def sample(w, x, g_forced, step_key, i):
            """A sample round's draws and distances: the unit (``g_forced``,
            or the best where it is negative) adapts by Eq. (3); returns the
            weights, the best unit, its distance, the unit's distance, the
            owner's drive and each band's cascade chain."""
            _, k_cascade = jax.random.split(step_key)
            k_drive, k_chain = jax.random.split(k_cascade)
            dd = ref.sq_dists(x[None], w, prec)[0]
            own = jnp.argmin(dd).astype(jnp.int32)
            g = jnp.where(g_forced < 0, own, g_forced)
            hit = jax.random.uniform(jax.random.fold_in(k_drive, g // length),
                                     ()) < sched(i)[1]
            chains = jax.vmap(lambda b: jax.random.fold_in(k_chain, b))(bands)
            if fault != "frozen":
                w = w.at[g].set(w[g] + p["l_s"] * (x - w[g]))
            return w, (own, jnp.min(dd), dd[g], hit, chains)

        def round_draws(chain, i, dirs, dsts):
            """A delivery round's rates, the next link of its cascade chain,
            and the drive of each message (by direction and receiver)."""
            lc, pi = sched(i)
            ck, sub = jax.random.split(chain)
            hits = (jax.random.uniform(sub, (4, rows, side))
                    < pi).reshape(4, length)[dirs, dsts]
            return ck, lc, hits

        def split_bands(keys, mask):
            """Advances the latency streams of the bands in ``mask``; returns
            the streams and each band's draw key."""
            new, sub = jax.vmap(jax.random.split, out_axes=1)(keys)
            return jnp.where(mask[:, None], new, keys), sub

        self._sample_fn = jax.jit(sample, donate_argnums=0)
        self._round = jax.jit(round_draws)
        self._fold_bands = jax.jit(jax.vmap(jax.random.fold_in, (None, 0)))
        self._split_bands = jax.jit(split_bands)
        self._exp = jax.jit(
            lambda subs, b, idx, count: jax.random.exponential(
                subs[b], (count,))[idx], static_argnums=3)
        self._write = jax.jit(
            lambda store, w, ids, srcs: store.at[ids].set(w[srcs],
                                                          mode="drop"),
            donate_argnums=0)

        def deliver(w, store, rows_, pos, ids, nf, lc):
            acc = jnp.zeros((rows_.shape[0], d), jnp.float32)
            for s4 in range(4):
                acc = acc.at[pos[s4]].add(store[ids[s4]], mode="drop")
            wr = w[jnp.minimum(rows_, n - 1)]
            return w.at[rows_].set(wr + lc * (acc - nf[:, None] * wr),
                                   mode="drop")

        self._deliver = jax.jit(deliver, donate_argnums=0)
        self.d = d
        self.w = None
        self.c = np.zeros((n,), np.int64)
        self.i = 0

    # ----------------------------------------------------------- one call

    def call(self, x, key, lat_key, gmu=None, init_key=None):
        """One ``partial_fit`` of the chunk ``x`` (E, D) with step key
        ``key`` and latency key ``lat_key``; ``init_key`` (first call only)
        initialises the map from the chunk. ``gmu`` (E,) forces the units.
        Returns the call's counts and per-sample results."""
        if init_key is not None:
            self.w = ref.init_map(init_key, x, self.n)
            self.c = np.zeros((self.n,), np.int64)
            self.i = 0
        step_keys = jax.random.split(key, x.shape[0])
        if self.fault == "half":
            e = x.shape[0] // 2
            x, step_keys = x[:e], step_keys[:e]
        return self.run(x, step_keys, lat_key, gmu)

    def run(self, x, step_keys, lat_key, gmu=None):
        """One run of the engine over the samples ``x`` (E, D) with their
        step keys, from the current weights and counters."""
        e = x.shape[0]
        self.i0 = self.i
        self.heaps = [[] for _ in range(self.k)]
        self.free = [self.cap] * self.k
        self.lat = self._fold_bands(lat_key, jnp.arange(self.k))
        self.casc = [[None] * e for _ in range(self.k)]
        self.sizes = np.zeros((e,), np.int64)
        self.waves = np.zeros((e,), np.int64)
        self.store = jnp.zeros((1024, self.d), jnp.float32)
        self.stored = 0
        self.seq = 0
        self.count = dict.fromkeys(
            ("drounds", "deliveries", "sent", "dropped", "narrow_rounds",
             "exchanges", "halo_sent"), 0)
        out = {k: np.zeros((e,), np.float64)
               for k in ("gmu", "q2", "best", "at_g")}
        for ev in range(e):
            self._drain(np.float32(ev) * self.spacing)
            g = None if gmu is None or ev >= len(gmu) else int(gmu[ev])
            res = self._sample(ev, x[ev], step_keys[ev], g)
            for k, v in zip(("gmu", "q2", "best", "at_g"), res):
                out[k][ev] = v
        self._drain(np.float32(np.inf))
        cnt = self.count
        return {"rounds": e + cnt["drounds"], "samples": e,
                "deliveries": cnt["deliveries"], "sent": cnt["sent"],
                "dropped": cnt["dropped"], "exchanges": cnt["exchanges"],
                "halo_sent": cnt["halo_sent"],
                "narrow_rounds": cnt["narrow_rounds"],
                "sizes": self.sizes, "waves": self.waves, **out}

    # ------------------------------------------------------------- rounds

    def _sample(self, ev, x, step_key, g_forced):
        t_s = np.float32(ev) * self.spacing
        self.w, out = self._sample_fn(
            self.w, x, -1 if g_forced is None else g_forced, step_key,
            self.i)
        own, best, at_g, hit, chains = jax.device_get(out)
        g = int(own) if g_forced is None else g_forced
        if self.fault != "frozen":
            self.c[g] += int(hit)
        for b in range(self.k):
            self.casc[b][ev] = chains[b]
        self.i += 1
        outs = [None] * self.k
        if self.max_waves >= 1:
            subs = self._draw(range(self.k))
            for b in range(self.k):
                lo = b * self.length
                band = self.c[lo:lo + self.length]
                outs[b] = self._fire(b, lo + np.nonzero(band >= self.theta)[0],
                                     ev, t_s, 1, subs)
        self._exchange(outs)
        shown = (g + 1) % self.n if self.fault == "altered" else g
        return shown, max(float(at_g), 0.0), float(best), float(at_g)

    def _draw(self, bands):
        """Advances the latency streams of ``bands`` one split; returns the
        draw keys of every band (those of the others unused)."""
        if self.latency != "exponential":
            return None
        mask = np.zeros((self.k,), bool)
        mask[list(bands)] = True
        self.lat, subs = self._split_bands(self.lat, mask)
        return subs

    def _delays(self, subs, b, idx, count):
        """Band ``b``'s delays at ``idx`` of its ``count`` draws from its
        key in ``subs``."""
        if self.latency != "exponential":
            return np.full((len(idx),), self.delay if self.latency ==
                           "constant" else 0.0, np.float32)
        if not len(idx):
            return np.zeros((0,), np.float32)
        ix = _padded(idx, _bucket(len(idx)), 0, np.int32)
        return (np.asarray(self._exp(subs, b, ix, count))[:len(idx)]
                * self.delay).astype(np.float32)

    def _enqueue(self, b, msgs):
        """Puts ``msgs`` [(t, gen, cid, dst, dir, payload)] into band ``b``'s
        pool, in order, while it has free slots."""
        self.count["sent"] += len(msgs)
        take = msgs[:self.free[b]]
        self.count["dropped"] += len(msgs) - len(take)
        self.free[b] -= len(take)
        for t, gen, cid, dst, s, pid in take:
            heapq.heappush(self.heaps[b], (t, gen, cid, self.seq, dst, s,
                                           pid))
            self.seq += 1

    def _keep(self, units):
        """Payload ids of the rows of ``units`` as they are now."""
        k = len(units)
        if self.stored + k > self.store.shape[0]:
            grow = max(self.store.shape[0], k)
            self.store = jnp.concatenate(
                [self.store, jnp.zeros((grow, self.d), jnp.float32)])
        ids = np.arange(self.stored, self.stored + k, dtype=np.int32)
        self.stored += k
        kb = _bucket(k)
        self.store = self._write(
            self.store, self.w,
            _padded(ids, kb, self.store.shape[0], np.int32),
            _padded(units, kb, 0, np.int32))
        return ids

    def _fire(self, b, fired, cid, t, gen, subs):
        """Band ``b``'s units ``fired`` (global ids, ascending) fire, with
        its latency draw key in ``subs``: their counters reset, the messages
        to units of the band go into its pool, and the returned outbox holds
        those across its boundaries."""
        self.sizes[cid] += len(fired)
        self.c[fired] = 0
        lo, side, rows = b * self.length, self.side, self.rows
        cand = []
        for u in fired:
            r, col = divmod(int(u) - lo, side)
            for s, ok, dst in ((0, r > 0, u - side),
                               (1, r < rows - 1, u + side),
                               (2, col > 0, u - 1), (3, col < side - 1,
                                                     u + 1)):
                if ok:
                    cand.append((4 * (int(u) - lo) + s, int(dst) - lo, s,
                                 int(u)))
        delays = self._delays(subs, b, [c[0] for c in cand],
                              4 * self.length)
        pid = (dict(zip(fired.tolist(), self._keep(fired).tolist()))
               if len(fired) else {})
        self._enqueue(b, [(np.float32(t) + dl, gen, cid, dst, s, pid[u])
                          for (_, dst, s, u), dl in zip(cand, delays)])
        top = [u for u in pid if b > 0 and u - lo < side]
        bottom = [u for u in pid
                  if b < self.k - 1 and u - lo >= self.length - side]
        return {"t": np.float32(t), "gen": gen, "cid": cid,
                "up": [(u - lo, pid[u]) for u in top],
                "dn": [(u - lo - (self.length - side), pid[u])
                       for u in bottom]}

    def _exchange(self, outs):
        """Every band takes its neighbours' halo, with its own delays."""
        side = self.side
        self.count["exchanges"] += 1
        subs = self._draw(range(self.k))
        for b in range(self.k):
            arrivals = []             # (index into the 2·side draw, ...)
            above = outs[b - 1] if b > 0 else None
            below = outs[b + 1] if b < self.k - 1 else None
            if above is not None:
                arrivals += [(col, col, 1, pid, above)
                             for col, pid in above["dn"]]
            if below is not None:
                arrivals += [(side + col, self.length - side + col, 0, pid,
                              below) for col, pid in below["up"]]
            if self.fault == "nohalo":
                arrivals = []        # the exchange between bands left out
            delays = self._delays(subs, b, [a[0] for a in arrivals],
                                  2 * side)
            self.count["halo_sent"] += len(arrivals)
            self._enqueue(b, [(o["t"] + dl, o["gen"], o["cid"], dst, s, pid)
                              for (_, dst, s, pid, o), dl
                              in zip(arrivals, delays)])

    def _drain(self, t_limit):
        while True:
            due = [bool(h) and h[0][0] <= t_limit for h in self.heaps]
            if not any(due):
                return
            subs = self._draw([b for b in range(self.k) if due[b]])
            outs = [self._deliver_round(b, subs) if due[b] else None
                    for b in range(self.k)]
            self._exchange(outs)

    def _deliver_round(self, b, subs):
        heap = self.heaps[b]
        top = heap[0][:3]
        msgs = []
        while heap and heap[0][:3] == top:
            msgs.append(heapq.heappop(heap))
        tmin, gmin, cid = top
        lo = b * self.length
        kb = _bucket(len(msgs))
        ck, lc, hits = self._round(
            self.casc[b][cid], self.i0 + cid,
            _padded([m[5] for m in msgs], kb, 0, np.int32),
            _padded([m[4] for m in msgs], kb, 0, np.int32))
        recv = sorted({m[4] for m in msgs})
        row = {u: k for k, u in enumerate(recv)}
        nf = np.zeros((len(recv),), np.float32)
        by_dir = [[] for _ in range(4)]
        for m in msgs:
            nf[row[m[4]]] += 1
            by_dir[m[5]].append((row[m[4]], m[6]))
        if self.fault != "frozen":
            for m, hit in zip(msgs, np.asarray(hits)):
                self.c[lo + m[4]] += int(hit)
            rb = _bucket(len(recv))
            kb = _bucket(max(len(v) for v in by_dir))
            pos = np.full((4, kb), rb, np.int32)
            ids = np.zeros((4, kb), np.int32)
            for s4, lst in enumerate(by_dir):
                for k, (r, pid) in enumerate(lst):
                    pos[s4, k], ids[s4, k] = r, pid
            self.w = self._deliver(
                self.w, self.store,
                _padded([lo + u for u in recv], rb, self.n, np.int32), pos,
                ids, _padded(nf, rb, 0, np.float32), lc)
        self.free[b] += len(msgs)
        self.casc[b][cid] = ck
        self.waves[cid] = max(self.waves[cid], gmin)
        self.count["deliveries"] += len(msgs)
        self.count["drounds"] += 1
        if self.narrow_width is not None and len(msgs) <= self.narrow_width:
            self.count["narrow_rounds"] += 1
        recv_g = lo + np.asarray(recv)
        fired = recv_g[self.c[recv_g] >= self.theta]
        if gmin >= self.max_waves:
            fired = fired[:0]
        return self._fire(b, np.sort(fired), cid, tmin, gmin + 1, subs)
