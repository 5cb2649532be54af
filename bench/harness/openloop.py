"""Open-loop arrivals fixed up front, and the timing of each request from
when it was due.

Independent callers send on a schedule whether or not earlier requests have
finished, so a backlog shows as latency rather than as less offered load.
Every seed gets the same multiset of inter-arrival gaps (the quantiles of
the exponential distribution at the cell's rate), of request sizes (the
mix's exact shares) and of endpoints, in an order drawn from the seed: the
seed changes the order, not the amount of work.
"""
from __future__ import annotations

import math
import time

import numpy as np


def schedule(rng: np.random.Generator, rate_hz: float, seconds: float,
             sizes: list, shares: list, kinds: list) -> dict:
    """Arrival times (seconds from the start), sizes and endpoints of the
    ``round(rate_hz * seconds)`` requests of one window."""
    n = max(1, int(round(rate_hz * seconds)))
    counts = [int(math.floor(s * n)) for s in shares]
    for i in range(n - sum(counts)):
        counts[i % len(counts)] += 1
    size = rng.permutation(np.repeat(np.asarray(sizes), counts))
    kind = rng.permutation(np.arange(n) % len(kinds))
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate_hz)
    return {"t": np.cumsum(gaps) - gaps[0], "size": size,
            "kind": [kinds[k] for k in kind]}


class Tracker:
    """Due time, submit time and completion time of every request.

    ``done`` is set from whatever thread completes the request's future."""

    def __init__(self, n: int):
        self.due = np.zeros(n)
        self.sent = np.zeros(n)
        self.done = np.full(n, np.nan)
        self.futures = [None] * n

    def run(self, sched: dict, submit, seconds: float) -> float:
        """Submit each request at its due time (or at once, when behind);
        returns the start of the window on the host clock."""
        t0 = time.perf_counter()
        for i, t in enumerate(sched["t"]):
            if t >= seconds:
                break
            due = t0 + t
            now = time.perf_counter()
            if due > now:
                time.sleep(due - now)
            self.due[i] = due
            self.sent[i] = time.perf_counter()
            fut = submit(i)
            self.futures[i] = fut
            fut.add_done_callback(
                lambda f, i=i: self.done.__setitem__(i, time.perf_counter()))
        return t0

    def wait(self, timeout: float) -> None:
        """Wait for every submitted request, up to ``timeout`` seconds."""
        end = time.perf_counter() + timeout
        for fut in self.futures:
            if fut is None:
                continue
            try:
                fut.result(max(0.0, end - time.perf_counter()))
            except Exception:  # noqa: BLE001 — a failure is counted, below
                pass

    def outcome(self, give_up: float) -> dict:
        """Latency of each submitted request from its due time; a request
        that failed or never came back counts as answered at ``give_up``."""
        lat, failed = [], 0
        for i, fut in enumerate(self.futures):
            if fut is None:
                continue
            ok = (fut.done() and not fut.cancelled()
                  and fut.exception() is None and not np.isnan(self.done[i]))
            if not ok:
                failed += 1
            end = self.done[i] if ok else give_up
            lat.append(end - self.due[i])
        late = [self.sent[i] - self.due[i]
                for i, f in enumerate(self.futures) if f is not None]
        return {"latency_s": lat, "failed": failed, "late_s": late}
