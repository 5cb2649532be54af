"""Work counts from shapes: the algorithm's own operations and bytes, counted
once, whatever kernel does the work.

One AFM step over B samples on an N x D map:

- search: the distance of every sample to every unit, 2·N·D per sample
  (the cross term as a multiply-add; the squared norms are O(N·D + B·D)
  and left out);
- Eq. (3) adapt: 3·D per sample (subtract, scale, add);
- cascade: 3·D per broadcast receipt (w_j += l_c (w_k - w_j)).

Bytes are the least any implementation moves through HBM: W read and
written once (2·N·D·4), the B samples read once, and the counters read and
written once.
"""
from __future__ import annotations

F32 = 4
#: Near neighbours a firing unit broadcasts to (fewer on the lattice edge,
#: so 4 per firing counts receipts from above).
NEAR_DEGREE = 4


def step_flops(n: int, d: int, samples: int, receipts: int) -> float:
    """Algorithmic FLOPs of AFM steps that trained ``samples`` samples and
    delivered ``receipts`` weight broadcasts."""
    return 2.0 * n * d * samples + 3.0 * d * (samples + receipts)


def step_bytes(n: int, d: int, b: int, steps: int) -> float:
    """Least HBM bytes of ``steps`` AFM steps of ``b`` samples each."""
    return steps * (2.0 * n * d * F32 + b * d * F32 + 2.0 * n * F32)


def bmu_flops(rows: int, n: int, d: int) -> float:
    """FLOPs of an exact BMU search of ``rows`` rows."""
    return 2.0 * rows * n * d


def bmu_bytes(calls: int, rows: int, n: int, d: int) -> float:
    """Least HBM bytes of ``calls`` BMU calls over ``rows`` rows in all:
    each call reads W and its rows once and writes an index and a distance
    per row."""
    return calls * n * d * F32 + rows * (d * F32 + 2 * F32)


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """(seconds, bound) of the roofline: the larger of compute time at the
    bf16 peak and memory time at the HBM peak, and which of the two it is."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops, "compute")
