"""``SinglePool`` — one dense message pool on one device.

The historical engine layout behind the placement seam
(``repro.core.placement.base``): its pool holds 8·N slots by default
(``core.pool.capacity``), and ``build_runner`` dispatches to the engine's
three runners in ``core.events`` (fused zero-latency scan / sample-scan
engine / budgeted loop). The golden fingerprint suite
(``tests/golden/async_engine.npz``) pins this placement bitwise across all
three latency models.
"""
from __future__ import annotations

import dataclasses

from repro.core import events
from repro.core import pool as pool_lib


@dataclasses.dataclass(frozen=True)
class SinglePool:
    """One pool, one device — the golden-suite-pinned default placement.

    A frozen no-field dataclass: every instance is equal and hashes alike,
    so runner caching behaves as if the placement were a config constant.
    """

    name = "single"

    @property
    def shards(self) -> int:
        return 1

    def pool_capacity(self, cfg, ecfg) -> int:
        return pool_lib.capacity(ecfg, cfg.n_units)

    def build_runner(self, cfg, ecfg, num_events: int, search, p_fn, l_c_fn):
        """Statically dispatch to the engine's three runners — fused
        zero-latency scan, sample-scan engine, or budgeted loop — exactly
        as the pre-seam engine did (DESIGN.md §7)."""
        if ecfg.fault_active and ecfg.plan.shard_latency_mult:
            raise ValueError(
                "FaultPlan.shard_latency_mult injects per-shard stragglers "
                "and needs placement='mesh' with shards == len(mult) >= 2; "
                "the single-pool placement has no shards to slow down")
        if events._zero_fast_ok(cfg, ecfg, num_events):
            return events._make_fused_zero(cfg, ecfg, num_events,
                                           search, p_fn, l_c_fn)
        if ecfg.kernel != "staged":
            # EventConfig validation already pins latency/engine/max_rounds;
            # the only way to land here is an explicit undersized capacity
            raise ValueError(
                "kernel='fused' needs the zero-latency fast path, but "
                "capacity < 4*N disqualifies it (a fire's 4N messages must "
                "fit the pool); raise capacity or drop the kernel override")
        if ecfg.max_rounds is None:
            return events._make_engine(cfg, ecfg, num_events,
                                       search, p_fn, l_c_fn)
        return events._make_budgeted(cfg, ecfg, num_events,
                                     search, p_fn, l_c_fn)
