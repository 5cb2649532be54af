"""Public wrapper for the fused training megakernel.

``fused_step_parts`` is the step-sized op: everything after the key split —
search (in-kernel exact/bf16, or an externally-supplied ``SearchResult``
when the paper's relay race runs outside), Eq. (3) adapt, drive, and the
cascade wave loop. Dispatch follows the repo's kernel policy
(``kernels.bmu.ops.resolve_flags``): the Pallas kernel on TPU or under
``interpret=True``, the jnp oracle (``kernels.fused.ref``) elsewhere —
both bitwise-identical on the exact tier.

The kernel path precomputes the PRNG outside the kernel: the drive draws
and the first ``wave_cap`` waves' Bernoulli tensors come from the same
sequential key chain as ``core.cascade.cascade`` (each wave's subkey is a
function of chain position only, never of lattice state, so extra splits
beyond quiescence are unobservable). Cascades outliving ``wave_cap`` waves
— rare by construction; the committed cascade-stats benchmarks top out far
below the default — continue in a jnp tail loop from chain position
``wave_cap``, op-identical to the oracle, so semantics never depend on the
cap. ``make_fused_stage`` adapts the op to the ``afm.Stages.fused`` seam.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import afm as afm_lib
from repro.core import schedules
from repro.core import search as search_lib
from repro.kernels.bmu import ops as bmu_ops
from repro.kernels.bmu import ref as bmu_ref
from repro.kernels.fused import ref
from repro.kernels.fused import fused as fused_lib

PRECISIONS = ("exact", "bf16")
#: Default in-kernel wave budget. The quick-config cascade-stats tables cap
#: out well under 16 waves; deeper cascades spill into the jnp tail loop
#: (bitwise-equal continuation), so this is a perf knob, not a semantic one.
DEFAULT_WAVE_CAP = 16
DEFAULT_UNROLL = 4


class FusedStep(NamedTuple):
    """One full training step's outputs (flat layout)."""
    w: jnp.ndarray       # (N, D) f32
    c: jnp.ndarray       # (N,) i32
    gmu: jnp.ndarray     # (B,) i32
    q2: jnp.ndarray      # (B,) f32
    greedy: jnp.ndarray  # (B,) i32 (zeros unless an external search ran)
    size: jnp.ndarray    # () i32
    waves: jnp.ndarray   # () i32
    recv: jnp.ndarray    # (N,) i32 per-unit broadcast receipts


def wave_budget(cfg) -> int:
    """The step's effective cascade wave bound (``None`` -> 8·side²) —
    the same rule as ``cascade.cascade`` / the event engine."""
    return (8 * cfg.side * cfg.side if cfg.max_waves is None
            else cfg.max_waves)


def fused_step_parts(w, c, samples, k_cascade, cfg, *, l_c, p_i,
                     search_result=None, precision: str = "exact",
                     use_pallas: bool = False, interpret: bool = False,
                     wave_cap: int = DEFAULT_WAVE_CAP,
                     unroll: int = DEFAULT_UNROLL,
                     recv0=None) -> FusedStep:
    """The post-split step body (traceable; callers jit).

    Args:
      w / c:         flat (N, D) f32 weights and (N,) i32 counters.
      samples:       (B, D) f32.
      k_cascade:     the step's cascade key — split internally into
                     (drive, chain) exactly like ``cascade.drive_and_cascade``.
      l_c / p_i:     the step's schedule values (traced scalars).
      search_result: a ``SearchResult`` when search ran outside (heuristic
                     relay race, or the async engine's per-event search);
                     ``None`` fuses the distance search into the step.
      precision:     'exact' (bitwise tier) or 'bf16' (tolerance tier) for
                     the fused search; ignored when ``search_result`` given.
      use_pallas / interpret: resolved kernel flags (see
                     ``bmu_ops.resolve_flags``); ``use_pallas=False`` runs
                     the jnp oracle.
      wave_cap / unroll: kernel wave-budget and block-unroll factors.
      recv0:         optional (N,) i32 receive-count accumulator to seed
                     (the async fused-zero runner threads it across steps).
    """
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, got "
                         f"{precision!r}")
    side, d, theta = cfg.side, cfg.dim, cfg.theta
    b = samples.shape[0]
    max_waves = wave_budget(cfg)
    zeros_b = jnp.zeros((b,), jnp.int32)

    if search_result is not None:
        gmu = search_result.gmu.astype(jnp.int32)
        q2 = search_result.q2
        greedy = search_result.greedy_steps
    elif not use_pallas:
        if precision == "exact":
            gmu, q2 = search_lib.exact_bmu(w, samples)
        else:
            gmu, q2 = bmu_ref.bmu_bf16_ref(w, samples)
        greedy = zeros_b
    else:
        gmu = q2 = None                   # fused into the kernel below
        greedy = zeros_b

    if not use_pallas:
        core = ref.adapt_drive_cascade(w, c, samples, gmu, k_cascade, cfg,
                                       l_c=l_c, p_i=p_i,
                                       max_waves=max_waves, recv0=recv0)
        return FusedStep(core.w, core.c, gmu, q2, greedy,
                         core.size, core.waves, core.recv)

    need = fused_lib.vmem_bytes(side, d, wave_cap)
    if need > fused_lib.VMEM_LIMIT_BYTES:
        raise ValueError(
            f"kernel='fused' keeps the whole map in VMEM: side {side} at dim "
            f"{d} needs about {need / 2**20:.0f} MiB, over the kernel's "
            f"{fused_lib.VMEM_LIMIT_BYTES // 2**20} MiB limit (side 56 is the "
            f"largest that fits at dim 784); use kernel='staged'")

    # ---- kernel path: precompute the PRNG, run the megakernel, finish any
    # over-budget cascade with the oracle's tail loop from chain position
    # ``wave_cap`` (the kernel consumed draws 0..wave_cap-1)
    def chain(k, _):
        k, sub = jax.random.split(k)
        return k, sub

    with jax.named_scope(obs.FUSED_WAVE_KEYS):
        k_drive, k_chain = jax.random.split(k_cascade)
        draws = jax.random.uniform(k_drive, (8, side, side)) < p_i
        k_after, subs = jax.lax.scan(chain, k_chain, None, length=wave_cap)
        # vmap over explicit per-wave keys is bitwise-identical to drawing
        # inside the loop (the ``search.exploration_phase`` precedent)
        bern = jax.vmap(
            lambda sk: jax.random.uniform(sk, (4, side, side)) < p_i)(subs)

    scal = jnp.stack([jnp.float32(cfg.l_s), jnp.asarray(l_c, jnp.float32)])
    budget = min(wave_cap, max_waves)
    out = fused_lib.fused_step_pallas(
        w, c.reshape(side, side), samples, scal, draws, bern, gmu,
        theta=theta, budget=budget, unroll=unroll, precision=precision,
        interpret=interpret)
    if search_result is not None:
        wk, ck, firedk, stats, recvk = out
    else:
        wk, ck, firedk, stats, recvk, gmu, q2 = out
    rec0 = recvk if recv0 is None else recvk + recv0.reshape(side, side)
    w3, c2, size, waves, recv = ref.wave_loop(
        wk.reshape(side, side, d), ck, firedk.astype(bool), k_after,
        l_c=l_c, p_i=p_i, theta=theta, max_waves=max_waves,
        size0=stats[0], waves0=stats[1], recv0=rec0)
    return FusedStep(w3.reshape(-1, d), c2.reshape(-1), gmu, q2, greedy,
                     size, waves, recv.reshape(-1))


def make_fused_stage(*, search: str = "exact", precision: str = "exact",
                     use_pallas: bool | None = None,
                     interpret: bool | None = None,
                     wave_cap: int = DEFAULT_WAVE_CAP,
                     unroll: int = DEFAULT_UNROLL):
    """Build an ``afm.Stages.fused`` callable: one fused train step with the
    same key discipline and schedule evaluation as ``afm._step`` (bitwise on
    the exact tier). ``search='heuristic'`` keeps the paper's relay race
    outside the kernel and fuses adapt + drive + cascade."""
    if search not in ("heuristic", "exact"):
        raise ValueError(
            f"search must be 'heuristic' or 'exact', got {search!r}")
    use_pallas, interpret = bmu_ops.resolve_flags(use_pallas, interpret)
    step = functools.partial(
        fused_step_parts, precision=precision, use_pallas=use_pallas,
        interpret=interpret, wave_cap=wave_cap, unroll=unroll)

    def fused(state, samples, key, cfg):
        n = cfg.n_units
        b = samples.shape[0]
        k_search, k_cascade = jax.random.split(key)
        i = state.i
        l_c = schedules.cascade_learning_rate(i, cfg.total_samples, cfg.c_o,
                                              cfg.c_s)
        p_i = schedules.cascade_probability(i, cfg.total_samples, n, cfg.c_m,
                                            cfg.c_d)
        res = (afm_lib.search_heuristic(state, samples, k_search, cfg)
               if search == "heuristic" else None)
        parts = step(state.w, state.c, samples, k_cascade, cfg,
                     l_c=l_c, p_i=p_i, search_result=res)
        new_state = afm_lib.AFMState(w=parts.w, c=parts.c, far=state.far,
                                     near=state.near, i=i + b)
        aux = afm_lib.StepAux(parts.gmu, parts.q2, parts.size, parts.waves,
                              parts.greedy)
        return new_state, aux

    return fused
