"""The distributed heuristic search (paper §2.1).

A sample's search is a relay race over the map:

1. **Random exploration** — for ``e`` iterations the sample hops from its
   current holder to a uniformly random far neighbour (or stays, each of the
   ``phi + 1`` choices uniform), tracking the best unit seen so far.
2. **Greedy exploitation** — from the best unit ``j*``, repeatedly move to the
   neighbour (near links; optionally also far links, per the §2.1 text) with
   the smallest distance to the sample, until no neighbour improves.

All functions are batched over B concurrent samples (``vmap`` semantics):
running B relay races at once is exactly the paper's "more samples processed
simultaneously" future-work direction, and each race follows the paper's
per-sample dynamics.

Distances are squared Euclidean internally (argmin-equivalent to Eq. (1)).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class SearchResult(NamedTuple):
    gmu: jnp.ndarray          # (B,) int32 — good-matching unit per sample
    q2: jnp.ndarray           # (B,) float32 — squared distance |w_gmu - s|^2
    greedy_steps: jnp.ndarray  # (B,) int32 — greedy-descent hop count
    explored: jnp.ndarray      # (B,) int32 — exploration hops (== e)


def _sqdist(w_rows: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    d = w_rows - s
    return jnp.sum(d * d, axis=-1)


def exploration_phase(w, far, samples, key, e: int):
    """Random exploration: (B,) start units hop over far links for e steps."""
    b = samples.shape[0]
    n, phi = far.shape
    k0, k1 = jax.random.split(key)
    j0 = jax.random.randint(k0, (b,), 0, n)
    q0 = _sqdist(w[j0], samples)

    # all e hop choices are drawn up front (vmap over the per-step keys is
    # bitwise-identical to drawing inside the loop — each step's randint
    # consumes only its own key) so the sequential part is pure gathers
    choices = jax.vmap(
        lambda k: jax.random.randint(k, (b,), 0, phi + 1)
    )(jax.random.split(k1, e))                                     # (e, B)

    def step(carry, choice):
        j, jstar, qstar = carry
        hop = jnp.where(choice < phi, far[j, jnp.minimum(choice, phi - 1)], j)
        q = _sqdist(w[hop], samples)
        better = q < qstar
        return (hop, jnp.where(better, hop, jstar), jnp.where(better, q, qstar)), None

    (j, jstar, qstar), _ = jax.lax.scan(step, (j0, j0, q0), choices)
    del j
    return jstar, qstar


def greedy_phase(w, near, far, samples, jstar, qstar, use_far: bool = True,
                 max_steps: int | None = None):
    """Greedy exploitation from jstar; returns (gmu, q2, steps)."""
    b = samples.shape[0]
    n = w.shape[0]
    max_steps = n if max_steps is None else max_steps

    def candidates(j):
        cands = near[j]
        if use_far:
            cands = jnp.concatenate([cands, far[j]], axis=-1)
        return cands

    def body(carry):
        j, q, active, steps = carry
        cands = jax.vmap(candidates)(j)                    # (B, C)
        valid = cands >= 0
        cq = jax.vmap(_sqdist)(w[jnp.maximum(cands, 0)], samples)
        cq = jnp.where(valid, cq, jnp.inf)
        kbest = jnp.argmin(cq, axis=-1)
        qbest = jnp.take_along_axis(cq, kbest[:, None], axis=-1)[:, 0]
        jbest = jnp.take_along_axis(cands, kbest[:, None], axis=-1)[:, 0]
        improve = active & (qbest < q)
        return (
            jnp.where(improve, jbest, j),
            jnp.where(improve, qbest, q),
            improve,
            steps + improve.astype(jnp.int32),
        )

    def cond(carry):
        _, _, active, steps = carry
        return jnp.any(active) & (steps.max() < max_steps)

    active0 = jnp.ones((b,), dtype=bool)
    steps0 = jnp.zeros((b,), dtype=jnp.int32)
    j, q, _, steps = jax.lax.while_loop(cond, body, (jstar, qstar, active0, steps0))
    return j, q, steps


def heuristic_search(w, near, far, samples, key, e: int,
                     greedy_use_far: bool = True) -> SearchResult:
    """Full §2.1 search for a batch of samples. w: (N,D); samples: (B,D)."""
    jstar, qstar = exploration_phase(w, far, samples, key, e)
    gmu, q2, steps = greedy_phase(w, near, far, samples, jstar, qstar, greedy_use_far)
    explored = jnp.full(samples.shape[:1], e, dtype=jnp.int32)
    return SearchResult(gmu, q2, steps, explored)


#: Unit-axis chunk applied when ``exact_bmu`` is called without an explicit
#: ``unit_chunk``: maps up to this many units materialise one (B, N) block;
#: larger maps stream (B, 4096) blocks with a running argmin.
DEFAULT_UNIT_CHUNK = 4096

#: The exact search's distance matmuls run at full f32. On TPU the default
#: precision rounds f32 operands to bf16; on CPU the flag changes nothing.
HIGHEST = jax.lax.Precision.HIGHEST


def _bmu_block(w_rows, samples, base):
    """Best unit within one block of ``w`` rows; indices offset by ``base``."""
    s2 = jnp.sum(samples * samples, axis=-1)                # (B,)
    w2 = jnp.sum(w_rows * w_rows, axis=-1)                  # (n_block,)
    cross = jnp.matmul(samples, w_rows.T, precision=HIGHEST)
    q2 = s2[:, None] - 2.0 * cross + w2[None, :]
    idx = jnp.argmin(q2, axis=-1)
    best = jnp.take_along_axis(q2, idx[:, None], axis=-1)[:, 0]
    return (base + idx).astype(jnp.int32), best


def exact_bmu(w, samples, *, unit_chunk: int | None = None):
    """Exact best-matching unit (the search's ground truth). (B,) idx, (B,) q2.

    Chunked over units to bound memory for large maps: the (B, N) distance
    matrix is materialised at most ``unit_chunk`` columns at a time
    (``DEFAULT_UNIT_CHUNK`` when None), folded with a running strict-min so
    ties resolve to the lowest index exactly like a global argmin. Maps at
    or under the chunk — every config in this repo — take the single-block
    path, so chunking changes nothing there. Across block geometries XLA
    may tile the distance matmul differently, so chunked q2 can wobble in
    the last ulp at wide feature dims (bitwise parity is tested at the
    AFM's dims; indices agree unless two units tie within that ulp). A
    block is never a single row — that lowers to a matvec with a reliably
    different reduction order. The Pallas kernel in ``repro.kernels.bmu``
    is the TPU fast path for this same computation.
    """
    n = w.shape[0]
    # Blocks must never have a single row: XLA lowers a one-unit block to a
    # matvec kernel whose reduction order differs in the last ulp, breaking
    # bitwise parity. Hence the floor of 2 on the chunk AND merging a 1-row
    # remainder (n % chunk == 1) into the preceding block.
    chunk = DEFAULT_UNIT_CHUNK if unit_chunk is None else max(2, int(unit_chunk))
    bounds = list(range(chunk, n, chunk))
    if bounds and n - bounds[-1] < 2:
        bounds.pop()
    idx, best = _bmu_block(w[:bounds[0] if bounds else n], samples, 0)
    for lo, hi in zip(bounds, bounds[1:] + [n]):
        idx_c, best_c = _bmu_block(w[lo:hi], samples, lo)
        better = best_c < best
        idx = jnp.where(better, idx_c, idx)
        best = jnp.where(better, best_c, best)
    return idx, jnp.maximum(best, 0.0)


def second_bmu(w, samples):
    """Indices of best and second-best matching units (for topological error)."""
    s2 = jnp.sum(samples * samples, axis=-1)
    w2 = jnp.sum(w * w, axis=-1)
    cross = jnp.matmul(samples, w.T, precision=HIGHEST)
    q2 = s2[:, None] - 2.0 * cross + w2[None, :]
    top2 = jax.lax.top_k(-q2, 2)[1]
    return top2[:, 0].astype(jnp.int32), top2[:, 1].astype(jnp.int32)
