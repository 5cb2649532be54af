"""Pallas TPU kernel: one cascade toppling wave on the unit lattice.

The cascade's counter update is a 4-neighbour stencil on an (n, n) int32
lattice — a VMEM-resident problem for any practical map (n = 512 is 1 MB per
array). The kernel runs as a single program (grid=()) with the whole lattice
in VMEM; boundary handling is done with 2-D iota masks (TPU requires >= 2-D
iota), and neighbour shifts with lattice rolls + masking, which lower to
cheap vector rotates on TPU.

For sharded maps (``core.distributed``) each shard's local rows plus two halo
rows are passed; the wrapper slices the halo contributions off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _shift_from(x, direction: str):
    """Value arriving from the given neighbour, zero at the boundary."""
    n_r, n_c = x.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (n_r, n_c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n_r, n_c), 1)
    if direction == "below":    # contribution from row r+1
        return jnp.where(row < n_r - 1, jnp.roll(x, -1, axis=0), 0)
    if direction == "above":    # from row r-1
        return jnp.where(row > 0, jnp.roll(x, 1, axis=0), 0)
    if direction == "right":    # from col c+1
        return jnp.where(col < n_c - 1, jnp.roll(x, -1, axis=1), 0)
    if direction == "left":     # from col c-1
        return jnp.where(col > 0, jnp.roll(x, 1, axis=1), 0)
    raise ValueError(direction)


def _wave_kernel(c_ref, fired_ref, bern_ref,
                 c_out, fired_out, recv_out, *, theta: int):
    c = c_ref[...]
    fired = fired_ref[...].astype(jnp.int32)
    c = jnp.where(fired > 0, 0, c)
    recv = jnp.zeros_like(c)
    inc = jnp.zeros_like(c)
    for k, d in enumerate(("below", "above", "right", "left")):
        r = _shift_from(fired, d)
        recv = recv + r
        inc = inc + bern_ref[k] * r
    new_c = c + inc
    c_out[...] = new_c
    fired_out[...] = ((new_c >= theta) & (recv > 0)).astype(jnp.int32)
    recv_out[...] = recv


@functools.partial(jax.jit, static_argnames=("theta", "interpret"))
def cascade_wave_pallas(c: jnp.ndarray, fired: jnp.ndarray, bern: jnp.ndarray,
                        theta: int, *, interpret: bool = False):
    """c: (n, n) int32; fired: (n, n) bool; bern: (4, n, n) bool/int.

    Returns (new_c, new_fired (bool), n_recv) — the full lattice in VMEM.
    """
    n = c.shape[0]
    new_c, new_fired, recv = pl.pallas_call(
        functools.partial(_wave_kernel, theta=int(theta)),
        grid=(),
        in_specs=[
            pl.BlockSpec(c.shape, lambda: (0, 0)),
            pl.BlockSpec(c.shape, lambda: (0, 0)),
            pl.BlockSpec((4,) + c.shape, lambda: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(c.shape, lambda: (0, 0)),
            pl.BlockSpec(c.shape, lambda: (0, 0)),
            pl.BlockSpec(c.shape, lambda: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, n), jnp.int32),
            jax.ShapeDtypeStruct((n, n), jnp.int32),
            jax.ShapeDtypeStruct((n, n), jnp.int32),
        ],
        interpret=interpret,
        name="cascade_wave_pallas",
    )(c.astype(jnp.int32), fired.astype(jnp.int32), bern.astype(jnp.int32))
    return new_c, new_fired.astype(bool), recv
