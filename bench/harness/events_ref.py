"""Plain reference of the paper's event model: units that adapt on receipt of
a sample or of a neighbour's weights, and broadcast after theta adaptations,
with every message delayed by an exponential latency.

One sample arrives per ``spacing`` time units. Before each arrival every
message due by then is delivered, earliest first: a round delivers all
messages that share the least (time, generation, cascade id). A delivery
moves the receiver by ``l_c`` towards the sender's weights as they were at
send time and drives its counter with probability p_i; a receiver that
reaches theta fires in turn. After the last arrival the queue drains.

The order of events and every random draw follow the seed's key chains:
per sample ``split(step_key) -> (search, cascade)``, ``split(cascade) ->
(drive, chain)``; per delivery round the cascade's chain advances one split
and draws a (4, side, side) uniform tensor; per firing round the latency
chain advances one split and draws one Exp(1) delay per candidate message.
The bookkeeping is plain Python over a heap; the weights stay on the device,
where each update is one small jitted call.
"""
from __future__ import annotations

import collections
import heapq

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference as ref


def _bucket(k: int) -> int:
    return 1 << max(0, (k - 1).bit_length())


class EventReplay:
    """Replays ``partial_fit`` calls of the event engine.

    ``forced`` calls adapt the units the program chose and judge them with
    the reference's distances; free calls (the control) choose their own.
    """

    def __init__(self, p_items: tuple, *, delay: float, spacing: float = 1.0,
                 capacity: int | None = None, precision: str = "highest",
                 fault: str = "none"):
        p = dict(p_items)
        self.p = p
        self.n, self.side, self.theta = p["n"], p["side"], p["theta"]
        self.delay = float(delay)
        self.spacing = np.float32(spacing)
        self.capacity = capacity or 8 * self.n
        self.max_waves = p["max_waves"]
        self.fault = fault
        prec = ref.PRECISIONS[precision]
        n, side, d = self.n, self.side, p["dim"]
        r = np.arange(n)
        rr, cc = r // side, r % side
        self.near = np.stack([np.where(rr > 0, r - side, -1),
                              np.where(rr < side - 1, r + side, -1),
                              np.where(cc > 0, r - 1, -1),
                              np.where(cc < side - 1, r + 1, -1)], 1)
        self._split = jax.jit(jax.random.split)
        self._sched = jax.jit(lambda i: (
            ref.l_c(i, p["i_max"], p["c_o"], p["c_s"]),
            ref.p_i(i, p["i_max"], n, p["c_m"], p["c_d"])))

        def search(w, x, g):
            dd = ref.sq_dists(x[None], w, prec)[0]
            return jnp.argmin(dd).astype(jnp.int32), jnp.min(dd), dd

        self._search = jax.jit(search)
        self._at = jax.jit(lambda dd, g: dd[g])
        self._adapt = jax.jit(
            lambda w, g, x: w.at[g].set(w[g] + p["l_s"] * (x - w[g])),
            donate_argnums=0)
        self._drive = jax.jit(
            lambda k, pi, g: (jax.random.uniform(k, (8, side, side))
                              < pi).reshape(8, n)[0, g])
        self._bern = jax.jit(
            lambda k, pi: (jax.random.uniform(k, (4, side, side))
                           < pi).reshape(4, n))
        self._exp = jax.jit(lambda k: jax.random.exponential(k, (4 * n,)))
        self._write = jax.jit(
            lambda pool, w, slots, srcs: pool.at[slots].set(w[srcs],
                                                            mode="drop"),
            donate_argnums=0)

        def deliver(w, pool, rows, pos, slots, nf, lc):
            acc = jnp.zeros((rows.shape[0], d), jnp.float32)
            for s4 in range(4):
                acc = acc.at[pos[s4]].add(pool[slots[s4]], mode="drop")
            wr = w[jnp.minimum(rows, n - 1)]
            return w.at[rows].set(wr + lc * (acc - nf[:, None] * wr),
                                  mode="drop")

        self._deliver = jax.jit(deliver, donate_argnums=0)
        self.pool = jnp.zeros((self.capacity, d), jnp.float32)
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
        e = x.shape[0]
        if self.fault == "half":
            x = x[: e // 2]
        step_keys = jax.random.split(key, e)
        self.lat_key = lat_key
        self.free = collections.deque(range(self.capacity))
        self.heap = []
        self.seq = 0
        self.i0 = self.i
        self.sizes = np.zeros((e,), np.int64)
        self.wcount = np.zeros((e,), np.int64)
        self.casc_key = [None] * e
        self.rounds = self.deliveries = self.sent = self.dropped = 0
        out_g = np.zeros((e,), np.int64)
        out_q2 = np.zeros((e,), np.float64)
        out_best = np.zeros((e,), np.float64)
        out_at = np.zeros((e,), np.float64)
        for ev in range(x.shape[0]):
            self._drain(np.float32(ev) * self.spacing)
            g = None if gmu is None or ev >= len(gmu) else int(gmu[ev])
            out_g[ev], out_q2[ev], out_best[ev], out_at[ev] = self._sample(
                ev, x[ev], step_keys[ev], g)
        self._drain(np.float32(np.inf))
        return {"rounds": self.rounds, "samples": x.shape[0],
                "deliveries": self.deliveries, "sent": self.sent,
                "dropped": self.dropped, "sizes": self.sizes[:x.shape[0]],
                "waves": self.wcount[:x.shape[0]], "gmu": out_g[:x.shape[0]],
                "q2": out_q2[:x.shape[0]], "best": out_best[:x.shape[0]],
                "at_g": out_at[:x.shape[0]]}

    # ------------------------------------------------------------- rounds

    def _sample(self, ev, x, step_key, g_forced):
        t_s = np.float32(ev) * self.spacing
        _, k_cascade = self._split(step_key)
        _, pi = self._sched(self.i)
        own, best, dd = self._search(self.w, x, 0)
        g = int(own) if g_forced is None else g_forced
        at_g = float(self._at(dd, g))
        best = float(best)
        k_drive, k_chain = self._split(k_cascade)
        if self.fault != "frozen":
            self.w = self._adapt(self.w, g, x)
            self.c[g] += int(bool(self._drive(k_drive, pi, g)))
        self.casc_key[ev] = k_chain
        self.rounds += 1
        self.i += 1
        fired = np.nonzero(self.c >= self.theta)[0]
        if self.max_waves >= 1:
            self._fire(fired, ev, t_s, 1)
        shown = (g + 1) % self.n if self.fault == "altered" else g
        return shown, max(at_g, 0.0), best, at_g

    def _fire(self, fired, cid, t, gen):
        self.sizes[cid] += len(fired)
        self.c[fired] = 0
        self.lat_key, lat_sub = self._split(self.lat_key)
        if not len(fired):
            return
        cand = [(u, s) for u in fired for s in range(4)
                if self.near[u, s] >= 0]
        self.sent += len(cand)
        delays = np.asarray(self._exp(lat_sub)) * np.float32(self.delay)
        take = cand[:len(self.free)]
        self.dropped += len(cand) - len(take)
        slots = [self.free.popleft() for _ in take]
        if not take:
            return
        kb = _bucket(len(take))
        sl = np.full((kb,), self.capacity, np.int32)
        src = np.zeros((kb,), np.int32)
        sl[:len(take)] = slots
        src[:len(take)] = [u for u, _ in take]
        self.pool = self._write(self.pool, self.w, sl, src)
        for (u, s), slot in zip(take, slots):
            tm = np.float32(t) + np.float32(delays[4 * u + s])
            heapq.heappush(self.heap, (tm, gen, cid, self.seq,
                                       int(self.near[u, s]), s, slot))
            self.seq += 1

    def _drain(self, t_limit):
        while self.heap and self.heap[0][0] <= t_limit:
            top = self.heap[0][:3]
            msgs = []
            while self.heap and self.heap[0][:3] == top:
                msgs.append(heapq.heappop(self.heap))
            self._deliver_round(top, msgs)

    def _deliver_round(self, top, msgs):
        tmin, gmin, cid = top
        lc, pi = self._sched(self.i0 + cid)
        ck, sub = self._split(self.casc_key[cid])
        k_wave = self.wcount[cid] + 1
        bern = np.asarray(self._bern(sub, pi))
        recv = sorted({m[4] for m in msgs})
        row = {u: k for k, u in enumerate(recv)}
        nf = np.zeros((len(recv),), np.float32)
        by_dir = [[] for _ in range(4)]
        for m in msgs:
            dst, s, slot = m[4], m[5], m[6]
            nf[row[dst]] += 1
            by_dir[s].append((row[dst], slot))
            if self.fault != "frozen":
                self.c[dst] += int(bern[s, dst])
        if self.fault != "frozen":
            rb = _bucket(len(recv))
            kb = _bucket(max(len(v) for v in by_dir))
            rows = np.full((rb,), self.n, np.int32)
            rows[:len(recv)] = recv
            nfp = np.zeros((rb,), np.float32)
            nfp[:len(recv)] = nf
            pos = np.full((4, kb), rb, np.int32)
            slots = np.full((4, kb), self.capacity - 1, np.int32)
            for s4, lst in enumerate(by_dir):
                for k, (r, slot) in enumerate(lst):
                    pos[s4, k], slots[s4, k] = r, slot
            self.w = self._deliver(self.w, self.pool, rows, pos, slots, nfp,
                                   lc)
        self.free.extend(m[6] for m in msgs)
        self.casc_key[cid] = ck
        self.wcount[cid] = k_wave
        self.deliveries += len(msgs)
        self.rounds += 1
        recv_a = np.asarray(recv)
        fired = recv_a[self.c[recv_a] >= self.theta]
        if k_wave >= self.max_waves:
            fired = fired[:0]
        self._fire(np.sort(fired), cid, tmin, gmin + 1)
