"""AFM — the asynchronously-trained feature map (paper §2), as a JAX module.

``AFMConfig`` holds the paper's hyper-parameters with the §3 defaults.
``AFMState`` is the trainable pytree. Two train-step flavours:

- ``train_step``      — faithful per-sample dynamics (B = 1 semantics).
- ``train_step_batch``— B concurrent samples (bulk-asynchronous): B relay-race
  searches run at once, conflicting GMU updates merge by averaging Eq. (3)
  applied once per sample, and the batch's threshold crossings seed a single
  cascade. B = 1 recovers ``train_step`` exactly.

``train`` scans either step over the sample stream.

A step decomposes into three injectable stages (see DESIGN.md §2) so the
``repro.api`` backends can swap implementations without re-deriving the step:

- **search**  (state, samples, key, cfg) -> SearchResult — which unit adapts;
- **adapt**   (state, samples, gmu, cfg) -> (w, counts)  — Eq. (3) merge;
- **cascade** (w, c, counts, l_c, p, key, cfg) -> CascadeResult — drive + waves.

``Stages`` bundles the three; ``DEFAULT_STAGES`` is the paper-faithful
heuristic-search pipeline, ``EXACT_STAGES`` replaces the relay-race search
with the exact BMU (the probe / Pallas fast path).

A third execution route exists beside the two step flavours: the
discrete-event runtime (``repro.core.events``, the ``async`` backend)
replays the *same* search/adapt stages per timestamped message instead of
per global step, and reduces to ``train_step`` bitwise when message
latency is zero. The equation numbers used throughout follow
``repro.core.schedules``: Eq. (1) sample-unit distance, Eq. (3) GMU
adaptation, Eq. (5) cascading learning rate l_c(i), Eq. (6) cascading
probability p_i, Eq. (7) unit labelling.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import cascade as cascade_lib
from repro.core import links, schedules
from repro.core import search as search_lib


@dataclasses.dataclass(frozen=True)
class AFMConfig:
    """Paper §3 'Default configuration' unless overridden.

    ``batch`` and ``max_waves`` interact: one step seeds **one** cascade
    from all B threshold crossings of the batch (the bulk-asynchronous
    merge), and ``max_waves`` caps that cascade's wave count
    (``None`` -> 8·side², effectively quiescence). When the cap cuts a
    cascade short, the cut units keep their super-threshold counters and
    fire at the start of the *next* step's cascade — firings are
    deferred, never lost. The event engine (``repro.core.events``)
    applies ``max_waves`` per cascade id; under per-message delivery
    (exponential latency) each round delivers one message, so the cap
    counts delivery rounds there.
    """
    side: int = 30                 # map is side x side units (N = side^2)
    dim: int = 784                 # sample-space dimensionality
    phi: int = 20                  # far links per unit
    theta: int = 4                 # cascading threshold (= |N_j|, BTW mapping)
    l_s: float = 0.05              # sample learning rate (Eq. 3)
    c_o: float = 0.5               # l_c offset (Eq. 5)
    c_s: float = 0.5               # l_c slope (Eq. 5)
    c_m: float = 0.1               # early characteristic cascade size (Eq. 6)
    c_d: float = 100.0             # cascade decay rate (Eq. 6)
    e_factor: float = 3.0          # exploration iterations e = e_factor * N
    i_max: int = 0                 # total training samples; 0 -> 600 * N
    greedy_use_far: bool = True    # §2.1 step 3: compare near AND far neighbours
    batch: int = 1                 # samples in flight per step
    max_waves: int | None = None   # cascade safety bound

    @property
    def n_units(self) -> int:
        return self.side * self.side

    @property
    def e(self) -> int:
        return max(1, int(self.e_factor * self.n_units))

    @property
    def total_samples(self) -> int:
        return self.i_max if self.i_max > 0 else 600 * self.n_units

    @property
    def num_steps(self) -> int:
        return self.total_samples // self.batch


class AFMState(NamedTuple):
    w: jnp.ndarray      # (N, D) float32 unit weights
    c: jnp.ndarray      # (N,) int32 cascading counters
    far: jnp.ndarray    # (N, phi) int32 far-link table
    near: jnp.ndarray   # (N, 4) int32 near-link table (-1 padded)
    i: jnp.ndarray      # () int32 — samples consumed so far


class StepAux(NamedTuple):
    gmu: jnp.ndarray           # (B,) int32
    q2: jnp.ndarray            # (B,) float32
    cascade_size: jnp.ndarray  # () int32 (a_i for the step)
    waves: jnp.ndarray         # () int32
    greedy_steps: jnp.ndarray  # (B,) int32


def init(key: jax.Array, cfg: AFMConfig,
         samples: jnp.ndarray | None = None) -> AFMState:
    """Initialise weights (uniform in sample bounding box, or N(0, 0.1))."""
    kw, kf = jax.random.split(key)
    n = cfg.n_units
    if samples is not None:
        lo = samples.min(axis=0)
        hi = samples.max(axis=0)
        w = jax.random.uniform(kw, (n, cfg.dim), minval=lo, maxval=hi)
    else:
        w = 0.1 * jax.random.normal(kw, (n, cfg.dim))
    return AFMState(
        w=w.astype(jnp.float32),
        c=jnp.zeros((n,), jnp.int32),
        far=links.far_links(kf, cfg.side, cfg.phi),
        near=links.near_neighbor_table(cfg.side),
        i=jnp.int32(0),
    )


class Stages(NamedTuple):
    """The three injectable phases of one AFM step (DESIGN.md §2), plus an
    optional whole-step fusion seam: when ``fused`` is set, ``_step``
    delegates the entire step to it — ``(state, samples, key, cfg) ->
    (AFMState, StepAux)`` — and the three staged callables are bypassed
    (the fused Pallas megakernel, ``repro.kernels.fused``, plugs in here;
    DESIGN.md §11). A fused implementation owns the step's key split and
    schedule evaluation and must reproduce the staged contract (bitwise on
    the exact tier)."""
    search: Callable    # (state, samples, key, cfg) -> SearchResult
    adapt: Callable     # (state, samples, gmu, cfg) -> (w (N,D), counts (N,))
    cascade: Callable   # (w, c, counts, l_c, p, key, cfg) -> CascadeResult
    fused: Callable | None = None  # (state, samples, key, cfg) -> (state, aux)


def search_heuristic(state: AFMState, samples: jnp.ndarray, key: jax.Array,
                     cfg: AFMConfig) -> search_lib.SearchResult:
    """Paper §2.1: far-link relay-race exploration + greedy exploitation."""
    return search_lib.heuristic_search(
        state.w, state.near, state.far, samples, key, cfg.e,
        greedy_use_far=cfg.greedy_use_far,
    )


def search_exact(state: AFMState, samples: jnp.ndarray, key: jax.Array,
                 cfg: AFMConfig) -> search_lib.SearchResult:
    """Exact BMU via a full distance pass (key unused — deterministic)."""
    del key
    gmu, q2 = search_lib.exact_bmu(state.w, samples)
    zeros = jnp.zeros(samples.shape[:1], jnp.int32)
    return search_lib.SearchResult(gmu, q2, zeros, zeros)


def adapt_merge(w: jnp.ndarray, samples: jnp.ndarray, gmu: jnp.ndarray,
                cfg: AFMConfig) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. (3) on a flat (N, D) weight matrix — the state-free body of
    ``adapt_gmu`` (the fused kernel's oracle shares it op-for-op)."""
    n = cfg.n_units
    b = samples.shape[0]
    ones = jnp.ones((b,), jnp.float32)
    counts = jnp.zeros((n,), jnp.float32).at[gmu].add(ones)
    target_sum = jnp.zeros((n, cfg.dim), jnp.float32).at[gmu].add(samples)
    hit = counts > 0
    mean = target_sum / jnp.maximum(counts, 1.0)[:, None]
    mean_target = jnp.where(hit[:, None], mean, w)
    return w + cfg.l_s * (mean_target - w), counts


def adapt_gmu(state: AFMState, samples: jnp.ndarray, gmu: jnp.ndarray,
              cfg: AFMConfig) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. (3) — GMU adaptation; conflicting GMUs merge by averaging the
    per-sample targets (B=1: exactly Eq. 3). Returns (w, per-unit counts)."""
    return adapt_merge(state.w, samples, gmu, cfg)


def cascade_default(w: jnp.ndarray, c: jnp.ndarray, counts: jnp.ndarray,
                    l_c, p_i, key: jax.Array, cfg: AFMConfig,
                    wave_fn=None) -> cascade_lib.CascadeResult:
    """Drive + cascade on the lattice view. ``wave_fn`` lets the Pallas
    cascade kernel replace the counter-wave stencil (bit-identical dynamics)."""
    side = cfg.side
    return cascade_lib.drive_and_cascade(
        w.reshape(side, side, cfg.dim), c.reshape(side, side),
        counts.astype(jnp.int32).reshape(side, side),
        l_c=l_c, p=p_i, theta=cfg.theta, key=key, max_waves=cfg.max_waves,
        wave_fn=wave_fn,
    )


DEFAULT_STAGES = Stages(search_heuristic, adapt_gmu, cascade_default)
EXACT_STAGES = Stages(search_exact, adapt_gmu, cascade_default)


def _step(state: AFMState, samples: jnp.ndarray, key: jax.Array,
          cfg: AFMConfig, stages: Stages = DEFAULT_STAGES
          ) -> tuple[AFMState, StepAux]:
    """Shared body for faithful (B=1) and batched (B>1) steps."""
    if stages.fused is not None:
        return stages.fused(state, samples, key, cfg)
    n = cfg.n_units
    b = samples.shape[0]
    k_search, k_cascade = jax.random.split(key)
    i = state.i
    l_c = schedules.cascade_learning_rate(i, cfg.total_samples, cfg.c_o, cfg.c_s)
    p_i = schedules.cascade_probability(i, cfg.total_samples, n, cfg.c_m, cfg.c_d)

    with jax.named_scope(obs.AFM_SEARCH):
        res = stages.search(state, samples, k_search, cfg)
    with jax.named_scope(obs.AFM_ADAPT):
        w, counts = stages.adapt(state, samples, res.gmu, cfg)
    with jax.named_scope(obs.AFM_CASCADE):
        out = stages.cascade(w, state.c, counts, l_c, p_i, k_cascade, cfg)

    new_state = AFMState(
        w=out.w.reshape(n, cfg.dim),
        c=out.c.reshape(n),
        far=state.far,
        near=state.near,
        i=i + b,
    )
    aux = StepAux(res.gmu, res.q2, out.size, out.waves, res.greedy_steps)
    return new_state, aux


def train_step(state: AFMState, sample: jnp.ndarray, key: jax.Array,
               cfg: AFMConfig, stages: Stages = DEFAULT_STAGES
               ) -> tuple[AFMState, StepAux]:
    """Faithful per-sample step. sample: (D,)."""
    return _step(state, sample[None, :], key, cfg, stages)


def train_step_batch(state: AFMState, samples: jnp.ndarray, key: jax.Array,
                     cfg: AFMConfig, stages: Stages = DEFAULT_STAGES
                     ) -> tuple[AFMState, StepAux]:
    """Bulk-asynchronous step over (B, D) samples."""
    return _step(state, samples, key, cfg, stages)


def train(state: AFMState, data: jnp.ndarray, key: jax.Array, cfg: AFMConfig,
          num_steps: int | None = None, stages: Stages = DEFAULT_STAGES
          ) -> tuple[AFMState, StepAux]:
    """Scan the batched step over a sample stream.

    data: (num_samples, D) — sampled with replacement each step.
    Returns final state and stacked per-step aux.
    """
    num_steps = cfg.num_steps if num_steps is None else num_steps

    def body(state, key):
        ks, kd = jax.random.split(key)
        idx = jax.random.randint(kd, (cfg.batch,), 0, data.shape[0])
        return _step(state, data[idx], ks, cfg, stages)

    keys = jax.random.split(key, num_steps)
    return jax.lax.scan(body, state, keys)
