"""Pallas TPU kernel: tiled pairwise-distance + running argmin (BMU search).

The AFM's hot spot (Eq. 1: exact BMU for search-error/metrics/classification,
and the probe's fast path) is ``argmin_j |w_j - s_i|^2``. On TPU this is an
MXU problem: |w - s|^2 = |w|^2 - 2 w.s + |s|^2, with the cross term a matmul.

Tiling: grid = (B // bb, N // bn); the unit axis is the minor (sequential)
grid dimension, so each sample tile keeps a running (min, argmin) accumulator
in its output block while streaming unit tiles through VMEM — one HBM pass
over W per sample tile, MXU-aligned block shapes (multiples of 128 on the
contracting/lane dims). Every block is 2-D, as TPU tiling requires: ``|w|^2``
rides lane-major as a (1, bn) row and the running (min, argmin) are (bb, 1)
columns.

The exact tier runs the cross term at ``Precision.HIGHEST`` (full f32 on the
MXU; the default would round the operands to bf16 on TPU).

|s|^2 is dropped inside the kernel (constant in j — argmin-invariant) and
added back by the wrapper, which also polishes the returned distance with one
exact gather (numerical parity with the f32 oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bmu_kernel(w_ref, s_ref, w2_ref, min_ref, idx_ref, *, block_n: int,
                precision: str = "exact"):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, jnp.float32(jnp.inf))
        idx_ref[...] = jnp.zeros_like(idx_ref)

    s = s_ref[...]                                   # (bb, D)
    w = w_ref[...]                                   # (bn, D)
    if precision == "bf16":
        # tolerance tier: bf16 MXU inputs, f32 accumulate (the wrapper
        # polishes the winner's distance with one exact-f32 gather)
        s = s.astype(jnp.bfloat16)
        w = w.astype(jnp.bfloat16)
    cross = jax.lax.dot_general(
        s, w, (((1,), (1,)), ((), ())),
        precision=(None if precision == "bf16"
                   else jax.lax.Precision.HIGHEST),
        preferred_element_type=jnp.float32)          # (bb, bn)
    q = w2_ref[...] - 2.0 * cross                    # |w|^2 - 2 w.s
    local_min = jnp.min(q, axis=1, keepdims=True)    # (bb, 1)
    local_arg = (jnp.argmin(q, axis=1, keepdims=True).astype(jnp.int32)
                 + j * block_n)
    better = local_min < min_ref[...]
    idx_ref[...] = jnp.where(better, local_arg, idx_ref[...])
    min_ref[...] = jnp.where(better, local_min, min_ref[...])


@functools.partial(jax.jit, static_argnames=("block_b", "block_n",
                                             "interpret", "precision"))
def bmu_pallas(w: jnp.ndarray, s: jnp.ndarray, *, block_b: int = 128,
               block_n: int = 128, interpret: bool = False,
               precision: str = "exact"):
    """w: (N, D); s: (B, D). Returns (idx (B,) int32, q2 (B,) f32).

    N, B, D are padded to block multiples by the wrapper (`ops.bmu`).
    ``precision='bf16'`` selects the bf16-cross tolerance tier (the wrapper
    replaces the returned distance with an exact-f32 gather polish).
    """
    n, d = w.shape
    b, _ = s.shape
    assert n % block_n == 0 and b % block_b == 0, (n, b)
    w2 = jnp.sum(w.astype(jnp.float32) ** 2, axis=-1)[None, :]
    grid = (b // block_b, n // block_n)
    min_out, idx_out = pl.pallas_call(
        functools.partial(_bmu_kernel, block_n=block_n, precision=precision),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),   # w tile
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),   # s tile
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),   # |w|^2 tile
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),   # running min
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),   # running argmin
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        interpret=interpret,
        name="bmu_pallas",
    )(w, s, w2)
    s2 = jnp.sum(s.astype(jnp.float32) ** 2, axis=-1)
    return idx_out[:, 0], jnp.maximum(min_out[:, 0] + s2, 0.0)
