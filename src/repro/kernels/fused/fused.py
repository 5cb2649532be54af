"""Pallas TPU megakernel: one fused AFM training step.

The staged hot path reads the (N, D) weight matrix from HBM three times per
step — once for the BMU distance pass, once for the Eq. (3) GMU merge, once
per cascade wave for the broadcast stencil. This kernel runs the whole
post-sample pipeline as a single program (grid=()) with the weight matrix
resident in VMEM: search (optional — the heuristic relay race stays outside),
GMU adaptation, the counter drive, and a block-unrolled cascade wave loop
(SNIPPETS.md Snippet 3 idiom: a ``while_loop`` whose body is ``unroll``
straight-line waves with per-wave activity masking), for **one** HBM read and
one write of W per step.

PRNG stays outside: the drive draws ((8, side, side)) and the first
``w_cap`` waves' Bernoulli tensors ((w_cap, 4, side, side)) are precomputed
by the wrapper from the same key chain as ``core.cascade`` — each wave's
draw depends only on its position in the chain, never on the lattice state,
so precomputation is bitwise-free. Cascades outliving ``w_cap`` waves are
finished by the wrapper's jnp tail loop (``ops.fused_step_parts``).

Two distance tiers for the in-kernel search (``precision``):

- ``"exact"`` — f32 expanded form, op-for-op ``core.search.exact_bmu``'s
  single-block path: bitwise against the staged pipeline.
- ``"bf16"``  — bf16 cross term with f32 accumulation on the MXU, then an
  exact-f32 gather polish of the winner's distance: half the VMEM/HBM
  traffic for W in the distance pass, tolerance-tested (index agreement +
  q2 ULP bound) rather than bitwise. See ``kernels.bmu.ref.bmu_bf16_ref``.

The exact tier's distance matmul runs at ``Precision.HIGHEST``: on TPU the
default rounds f32 operands to bf16, which is the bf16 tier, not the exact
one. On CPU the flag changes nothing.

Lattice shifts use rolls + 2-D iota masks (the ``kernels.cascade`` idiom —
TPU-friendly) summed in ``core.cascade._shift_sum``'s exact order, so the
float weight updates stay bitwise against the concatenate-based oracle.

Mosaic lowers neither gathers nor scatter-adds, so the kernel has none:

- the searched distance is the row minimum (the value at the argmin);
- the bf16 tier's polish gathers the winners' rows with a one-hot matmul at
  ``HIGHEST`` (an exact row copy);
- the Eq. (3) merge walks the batch in order and adds sample ``k`` to the
  rows whose unit equals ``gmu[k]`` with a compare-and-select. That is the
  scatter-add's own summation order, so the merge's sums and counts stay
  bitwise against ``afm.adapt_merge`` even when several samples share a
  unit (DESIGN.md §11 has the one CPU caveat, on the mean).

The whole map lives in VMEM (``grid=()``), so the map size is capped:
``VMEM_LIMIT_BYTES`` is the scoped-VMEM budget the kernel asks Mosaic for,
``vmem_bytes`` what a map needs (``ops.fused_step_parts`` refuses a map
that needs more, with an error). At the paper's dim 784 the largest side
that fits is 56.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Scoped-VMEM budget the kernel asks Mosaic for (v5e has 128 MiB of VMEM).
VMEM_LIMIT_BYTES = 100 * 2**20
#: Whole-map f32 copies the kernel keeps in VMEM at once: input, output, the
#: merge's target sum, the wave loop's carries and temporaries. Fitted to
#: what Mosaic allocates on v5e at dim 784: side 56 compiles within
#: ``VMEM_LIMIT_BYTES``, side 64 asks for 111 MiB.
W_COPIES = 8


def _tiled_bytes(rows: int, cols: int) -> int:
    """Bytes of a 32-bit (rows, cols) array padded to (8, 128) tiles."""
    return (-(-rows // 8) * 8) * (-(-cols // 128) * 128) * 4


def vmem_bytes(side: int, dim: int, wave_cap: int) -> int:
    """Estimated VMEM the kernel needs for a side x side map at ``dim``:
    ``W_COPIES`` weight matrices plus the wave draws and lattice arrays."""
    return (W_COPIES * _tiled_bytes(side * side, dim)
            + (4 * wave_cap + 16) * _tiled_bytes(side, side))


def _masks(side: int):
    row = jax.lax.broadcasted_iota(jnp.int32, (side, side), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (side, side), 1)
    return row, col


def _shift_sum3(x3):
    """4-neighbour sum for (side, side, D), zero beyond the boundary —
    value-identical to ``cascade._shift_sum`` (same shifted arrays, same
    ``((up + dn) + lf) + rt`` addition order). The masks are built as
    (side, side, 1) iotas: Mosaic cannot add a unit lane dim to a bool."""
    side = x3.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (side, side, 1), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (side, side, 1), 1)
    up = jnp.where(row < side - 1, jnp.roll(x3, -1, axis=0), 0.0)
    dn = jnp.where(row > 0, jnp.roll(x3, 1, axis=0), 0.0)
    lf = jnp.where(col < side - 1, jnp.roll(x3, -1, axis=1), 0.0)
    rt = jnp.where(col > 0, jnp.roll(x3, 1, axis=1), 0.0)
    return up + dn + lf + rt


def _shift4_i32(x, row, col):
    """(4, side, side) neighbour stack of an int32 lattice, in
    ``cascade._shift4`` slot order (below, above, right, left)."""
    side = x.shape[0]
    return jnp.stack([
        jnp.where(row < side - 1, jnp.roll(x, -1, axis=0), 0),
        jnp.where(row > 0, jnp.roll(x, 1, axis=0), 0),
        jnp.where(col < side - 1, jnp.roll(x, -1, axis=1), 0),
        jnp.where(col > 0, jnp.roll(x, 1, axis=1), 0),
    ], axis=0)


def _fused_kernel(*refs, b: int, side: int, d: int, theta: int, budget: int,
                  w_cap: int, unroll: int, has_search: bool, precision: str):
    if has_search:
        (w_ref, c_ref, s_ref, scal_ref, drive_ref, bern_ref, gmu_ref,
         w_out, c_out, fired_out, stats_out, recv_out) = refs
    else:
        (w_ref, c_ref, s_ref, scal_ref, drive_ref, bern_ref,
         w_out, c_out, fired_out, stats_out, recv_out,
         gmu_ref, q2_out) = refs
    n = side * side
    w = w_ref[...]                                   # (N, D) — the HBM read
    s = s_ref[...]                                   # (B, D)
    l_s = scal_ref[0]
    l_c = scal_ref[1]
    row, col = _masks(side)

    # ---- search (Eq. 1) — skipped when the relay race ran outside; the
    # winners land in ``gmu_ref`` ((B, 1) i32) either way
    if not has_search:
        s2 = jnp.sum(s * s, axis=-1)
        w2 = jnp.sum(w * w, axis=-1)
        if precision == "exact":
            # op-for-op ``search.exact_bmu``'s single-block path (bitwise)
            q2m = s2[:, None] - 2.0 * jax.lax.dot_general(
                s, w, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST) + w2[None, :]
            gmu_ref[...] = jnp.argmin(q2m, axis=-1,
                                      keepdims=True).astype(jnp.int32)
            q2_out[...] = jnp.maximum(jnp.min(q2m, axis=-1, keepdims=True),
                                      0.0)
        else:
            # bf16 tier: cross term on bf16 inputs, f32 accumulate, then an
            # exact-f32 polish of the winner (``kernels.bmu.ref.bmu_bf16_ref``)
            cross = jax.lax.dot_general(
                s.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            q2a = s2[:, None] - 2.0 * cross + w2[None, :]
            gmu = jnp.argmin(q2a, axis=-1, keepdims=True).astype(jnp.int32)
            onehot = (jax.lax.broadcasted_iota(jnp.int32, (b, n), 1)
                      == gmu).astype(jnp.float32)
            dw = jax.lax.dot_general(
                onehot, w, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST) - s   # w[gmu] - s
            gmu_ref[...] = gmu
            q2_out[...] = jnp.maximum(jnp.sum(dw * dw, axis=-1,
                                              keepdims=True), 0.0)

    # ---- Eq. (3) GMU merge — ``afm.adapt_merge``'s scatter-adds as an
    # in-order compare-and-select walk over the batch (same summation order)
    unit = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)

    def merge_one(k, acc):
        target_sum, counts = acc
        hit_k = unit == gmu_ref[pl.ds(k, 1), :]      # (N, 1)
        return (jnp.where(hit_k, target_sum + s_ref[pl.ds(k, 1), :],
                          target_sum),
                jnp.where(hit_k, counts + 1.0, counts))

    target_sum, counts = jax.lax.fori_loop(
        0, b, merge_one, (jnp.zeros((n, d), jnp.float32),
                          jnp.zeros((n, 1), jnp.float32)))
    hit = counts > 0
    mean = target_sum / jnp.maximum(counts, 1.0)
    mean_target = jnp.where(hit, mean, w)
    w = w + l_s * (mean_target - w)

    # ---- counter drive (precomputed draws)
    gmu_mask = counts.astype(jnp.int32).reshape(side, side)
    k8 = jax.lax.broadcasted_iota(jnp.int32, (8, side, side), 0)
    inc = jnp.sum(drive_ref[...] * (k8 < jnp.minimum(gmu_mask, 8)).astype(
        jnp.int32), axis=0)
    c = c_ref[...] + inc
    fired = (c >= theta).astype(jnp.int32)           # i32: Mosaic selects no i1
    w3 = w.reshape(side, side, d)

    # ---- block-unrolled wave loop: while over blocks of ``unroll``
    # straight-line waves; inactive waves are full-array selects (never
    # arithmetic no-ops — ``w + l_c*0`` would flip -0.0 to +0.0)
    def wave_once(w3, c, fired, widx):
        firedf = fired.astype(jnp.float32)
        sum_wk = _shift_sum3(w3 * firedf[..., None])
        bern = bern_ref[widx]                        # (4, side, side)
        cr = jnp.where(fired > 0, 0, c)
        recv4 = _shift4_i32(fired, row, col)
        n_recv = recv4.sum(axis=0)
        cn = cr + jnp.sum(bern * recv4, axis=0)
        new_fired = ((cn >= theta) & (n_recv > 0)).astype(jnp.int32)
        nf = n_recv.astype(jnp.float32)
        w3n = w3 + l_c * (sum_wk - nf[..., None] * w3)
        return w3n, cn, new_fired, n_recv

    def bcond(cc):
        return jnp.any(cc[2] > 0) & (cc[4] < budget)

    def bbody(cc):
        w3, c, fired, size, waves, recv = cc
        for _ in range(unroll):
            active = jnp.any(fired > 0) & (waves < budget)
            widx = jnp.minimum(waves, w_cap - 1)     # clamp inactive lanes
            w3n, cn, fn, n_recv = wave_once(w3, c, fired, widx)
            size = size + jnp.where(active, fired.sum(dtype=jnp.int32), 0)
            recv = recv + jnp.where(active, n_recv, 0)
            waves = waves + jnp.where(active, jnp.int32(1), jnp.int32(0))
            w3 = jnp.where(active, w3n, w3)
            c = jnp.where(active, cn, c)
            fired = jnp.where(active, fn, fired)
        return (w3, c, fired, size, waves, recv)

    w3, c, fired, size, waves, recv = jax.lax.while_loop(
        bcond, bbody,
        (w3, c, fired, jnp.int32(0), jnp.int32(0),
         jnp.zeros((side, side), jnp.int32)))

    w_out[...] = w3.reshape(n, d)                    # the one HBM write
    c_out[...] = c
    fired_out[...] = fired
    stats_out[0] = size
    stats_out[1] = waves
    recv_out[...] = recv


@functools.partial(jax.jit, static_argnames=(
    "theta", "budget", "unroll", "precision", "interpret"))
def fused_step_pallas(w, c2, s, scal, drive, bern, gmu=None, *, theta: int,
                      budget: int, unroll: int = 4, precision: str = "exact",
                      interpret: bool = False):
    """One fused post-sample step. Shapes: w (N, D) f32; c2 (side, side)
    i32; s (B, D) f32; scal (2,) f32 = [l_s, l_c]; drive (8, side, side)
    i32; bern (w_cap, 4, side, side) i32; gmu (B,) i32 or None (None fuses
    the exact/bf16 distance search into the kernel).

    Returns ``(w, c2, fired, stats, recv[, gmu, q2])`` — ``fired`` is the
    still-super-threshold front after the last executed wave (int32 lattice;
    the wrapper's tail loop continues it), ``stats`` is (2,) i32
    [size, waves], ``recv`` the per-unit receive counts.
    """
    side = c2.shape[0]
    n, d = w.shape
    b = s.shape[0]
    w_cap = bern.shape[0]
    has_search = gmu is not None
    full = lambda shape: pl.BlockSpec(shape, lambda: (0,) * len(shape))  # noqa: E731
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [full(w.shape), full(c2.shape), full(s.shape), smem,
                full(drive.shape), full(bern.shape)]
    args = [w, c2.astype(jnp.int32), s, scal,
            drive.astype(jnp.int32), bern.astype(jnp.int32)]
    if has_search:  # lint: tracer-ok(static arg-presence flag, not a tracer)
        in_specs.append(full((b, 1)))
        args.append(gmu.astype(jnp.int32).reshape(b, 1))
    out_specs = [full((n, d)), full((side, side)), full((side, side)),
                 smem, full((side, side))]
    out_shape = [
        jax.ShapeDtypeStruct((n, d), jnp.float32),
        jax.ShapeDtypeStruct((side, side), jnp.int32),
        jax.ShapeDtypeStruct((side, side), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((side, side), jnp.int32),
    ]
    if not has_search:  # lint: tracer-ok(static arg-presence flag)
        out_specs += [full((b, 1)), full((b, 1))]
        out_shape += [jax.ShapeDtypeStruct((b, 1), jnp.int32),
                      jax.ShapeDtypeStruct((b, 1), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(
            _fused_kernel, b=b, side=side, d=d, theta=int(theta),
            budget=int(budget), w_cap=int(w_cap), unroll=int(unroll),
            has_search=has_search, precision=precision),
        grid=(),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="fused_step_pallas",
    )(*args)
    if has_search:  # lint: tracer-ok(static arg-presence flag)
        return out
    return (*out[:5], out[5][:, 0], out[6][:, 0])
