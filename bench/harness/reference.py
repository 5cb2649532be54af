"""Plain float32 ``jax.numpy`` references of the map's semantics.

Nothing here imports the program or takes what it made: the references start
from the seed (the benchmark's data and keys) and from the paper's rules. The
distance matmul runs at the precision the caller names: ``HIGHEST`` for the
reference, ``HIGH`` (three bf16 passes) for the control, the next precision
below the configuration's float32 at ``HIGHEST``.

Paper rules (arXiv 2301.08379 §2), per step of B samples:

- search: the best-matching unit (BMU) of each sample, by squared distance;
- Eq. (3) adapt: a hit unit moves by ``l_s`` towards the mean of its samples;
- drive: each adaptation increments the unit's counter with probability p_i
  (Eq. 6);
- cascade: a unit whose counter reaches theta fires: its counter resets and
  each near neighbour moves by ``l_c(i)`` (Eq. 5) towards it and is driven
  with probability p_i, wave by wave until no unit fires.

The replays are teacher-forced: each step adapts the unit the program chose,
so the integer dynamics (counters, cascades) follow the program's exactly,
and the reference's own distances judge that choice. A choice that lies
further from the sample than the reference's best shows as a gap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
HIGH = jax.lax.Precision.HIGH
DEFAULT = jax.lax.Precision.DEFAULT
PRECISIONS = {"highest": HIGHEST, "high": HIGH, "default": DEFAULT,
              "bf16x3": "bf16x3", "bf16": "bf16"}
#: Floor of a distance used as a denominator.
EPS = 1e-6


def _bf16_parts(a):
    """(hi, lo) bfloat16 parts of float32 ``a`` with ``a ~= hi + lo``;
    ``reduce_precision`` keeps the compiler from folding the split away."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def _cross(x, w, precision):
    """x @ w.T at ``precision``. On a TPU the matmul unit takes ``HIGH`` and
    ``DEFAULT`` itself; XLA:CPU computes every float32 matmul in float32,
    so there they are written out: three bf16 passes (hi·hi + hi·lo +
    lo·hi) and one (hi·hi), accumulated in float32. ``"bf16x3"`` and
    ``"bf16"`` ask for the written-out forms on any backend (a TPU runs a
    one-row matmul in float32 whatever precision it is asked for)."""
    if precision == HIGHEST or (jax.default_backend() == "tpu"
                                and precision in (HIGH, DEFAULT)):
        return jnp.matmul(x, w.T, precision=precision)
    dot = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
    (xh, xl), (wh, wl) = _bf16_parts(x), _bf16_parts(w)
    if precision in (HIGH, "bf16x3"):
        return dot(xh, wh.T) + dot(xh, wl.T) + dot(xl, wh.T)
    return dot(xh, wh.T)


def sq_dists(x, w, precision):
    """(B, N) squared distances of samples ``x`` (B, D) to units ``w``."""
    s2 = jnp.sum(x * x, axis=-1)
    w2 = jnp.sum(w * w, axis=-1)
    return s2[:, None] - 2.0 * _cross(x, w, precision) + w2[None, :]


def readings(best, at_g, q2_prog) -> dict:
    """Compared numbers of the units and distances a program reported.

    ``best`` is the reference's least distance of each sample, ``at_g`` the
    reference's distance to the unit the program chose, ``q2_prog`` the
    distance the program reported. Gaps are over the larger of the
    sample's best distance and the median one, so that a sample lying on a
    unit does not turn rounding into a large ratio."""
    best = np.asarray(best, np.float64).ravel()
    at_g = np.asarray(at_g, np.float64).ravel()
    q2 = np.asarray(q2_prog, np.float64).ravel()
    med = max(float(np.median(best)), EPS)
    scale = np.maximum(best, med)
    err = np.abs(q2 - np.maximum(at_g, 0.0))
    return {"bmu_gap": float(np.max((at_g - best) / scale)),
            "q2_gap": float(np.max(err / scale)),
            "q2_mae": float(np.mean(err) / med),
            "bmu_flips": float(np.mean(at_g > best))}


def l_c(i, i_max, c_o, c_s):
    """Eq. (5): (1 + tanh((c_o - i/i_max) / c_s)) / 2."""
    frac = jnp.asarray(i, jnp.float32) / jnp.float32(i_max)
    return (1.0 + jnp.tanh((c_o - frac) / c_s)) / 2.0


def p_i(i, i_max, n, c_m, c_d):
    """Eq. (6): (1 - 1/sqrt(c_m N)) (1 - i/i_max)^(c_d / N)."""
    frac = jnp.asarray(i, jnp.float32) / jnp.float32(i_max)
    base = 1.0 - 1.0 / jnp.sqrt(jnp.float32(c_m * n))
    decay = jnp.power(jnp.clip(1.0 - frac, 1e-12, 1.0),
                      jnp.float32(c_d) / jnp.float32(n))
    return base * decay


def init_map(key, x, n):
    """The map's initial weights: uniform in the bounding box of ``x``,
    from the first half of ``split(key)``."""
    kw, _ = jax.random.split(key)
    return jax.random.uniform(kw, (n, x.shape[1]), minval=x.min(axis=0),
                              maxval=x.max(axis=0))


def _shift4(x):
    """(4, side, side[, D]) values of the neighbour below, above, right and
    left of each unit (zero beyond the edge)."""
    z, zc = jnp.zeros_like(x[:1]), jnp.zeros_like(x[:, :1])
    return jnp.stack([jnp.concatenate([x[1:], z], 0),
                      jnp.concatenate([z, x[:-1]], 0),
                      jnp.concatenate([x[:, 1:], zc], 1),
                      jnp.concatenate([zc, x[:, :-1]], 1)])


def cascade(w, c, fired, lc, p, theta, key, max_waves):
    """Waves of firings to quiescence. ``w`` (side, side, D), ``c`` and
    ``fired`` (side, side). Returns (w, c, firings, waves)."""
    side = c.shape[0]

    def body(carry):
        w, c, fired, key, size, waves = carry
        key, sub = jax.random.split(key)
        sum_wk = _shift4(w * fired[..., None].astype(w.dtype)).sum(axis=0)
        bern = jax.random.uniform(sub, (4, side, side)) < p
        c = jnp.where(fired, 0, c)
        recv4 = _shift4(fired.astype(jnp.int32))
        n_recv = recv4.sum(axis=0)
        c = c + jnp.sum(bern.astype(jnp.int32) * recv4, axis=0)
        new_fired = (c >= theta) & (n_recv > 0)
        w = w + lc * (sum_wk - n_recv.astype(w.dtype)[..., None] * w)
        return w, c, new_fired, key, size + fired.sum(dtype=jnp.int32), \
            waves + 1

    def cond(carry):
        return jnp.any(carry[2]) & (carry[5] < max_waves)

    w, c, _, _, size, waves = jax.lax.while_loop(
        cond, body, (w, c, fired, key, jnp.int32(0), jnp.int32(0)))
    return w, c, size, waves


def _step(p, w, c, i, x, g, key, fault):
    """One step of B samples adapting units ``g``; returns (w, c, size,
    waves). ``fault`` plants a fault for the fault readings: "frozen" (the
    state returned unchanged), "half" (the second half of the batch left
    out), "altered" (handled by the caller: the next unit reported)."""
    n, side, theta = p["n"], p["side"], p["theta"]
    if fault == "frozen":
        return w, c, jnp.int32(0), jnp.int32(0)
    if fault == "half":
        x, g = x[: x.shape[0] // 2], g[: g.shape[0] // 2]
    k_cascade = jax.random.split(key)[1]
    lc = l_c(i, p["i_max"], p["c_o"], p["c_s"])
    pi = p_i(i, p["i_max"], n, p["c_m"], p["c_d"])
    counts = jnp.zeros((n,), jnp.float32).at[g].add(1.0)
    tsum = jnp.zeros_like(w).at[g].add(x)
    mean = jnp.where((counts > 0)[:, None],
                     tsum / jnp.maximum(counts, 1.0)[:, None], w)
    w = w + p["l_s"] * (mean - w)
    k0, k1 = jax.random.split(k_cascade)
    hits = jnp.minimum(counts.astype(jnp.int32).reshape(side, side), 8)
    draws = jax.random.uniform(k0, (8, side, side)) < pi
    c = c.reshape(side, side) + jnp.sum(
        draws.astype(jnp.int32) * (jnp.arange(8)[:, None, None] < hits), 0)
    w3, c, size, waves = cascade(w.reshape(side, side, -1), c, c >= theta,
                                 lc, pi, theta, k1, p["max_waves"])
    return w3.reshape(n, -1), c.reshape(n), size, waves


@functools.partial(jax.jit, static_argnames=("p_items", "steps", "free",
                                             "precision", "fault"))
def fit_replay(x, key, gmu, *, p_items, steps, free, precision="highest",
               fault="none"):
    """Replay ``TopoMap.fit(x, key=key)``'s ``steps`` steps.

    ``free=False`` forces each step's units to ``gmu`` (S, B), the program's
    choices; ``free=True`` lets the reference choose its own (the control).
    Returns the final (w, c) and per step: the units adapted, their squared
    distance, the best unit's distance, firings and waves.
    """
    p = dict(p_items)
    prec = PRECISIONS[precision]
    b = p["batch"]
    k_init, k_run = jax.random.split(key)
    w = init_map(k_init, x, p["n"])
    c = jnp.zeros((p["n"],), jnp.int32)

    def body(carry, xs):
        w, c, i = carry
        kk, g_forced = xs
        ks, kd = jax.random.split(kk)
        xb = x[jax.random.randint(kd, (b,), 0, x.shape[0])]
        d = sq_dists(xb, w, prec)
        best = jnp.min(d, axis=1)
        g = jnp.argmin(d, axis=1).astype(jnp.int32) if free else g_forced
        at_g = jnp.take_along_axis(d, g[:, None], axis=1)[:, 0]
        w, c, size, waves = _step(p, w, c, i, xb, g, ks, fault)
        shown = (g + 1) % p["n"] if fault == "altered" else g
        return (w, c, i + b), (shown, jnp.maximum(at_g, 0.0), best, at_g,
                               size, waves)

    keys = jax.random.split(k_run, steps)
    (w, c, _), (g, q2, best, at_g, size, waves) = jax.lax.scan(
        body, (w, c, jnp.int32(0)), (keys, gmu))
    return {"w": w, "c": c, "gmu": g, "q2": q2, "best": best, "at_g": at_g,
            "size": size, "waves": waves}


def map_params(afm: dict) -> tuple:
    """The hashable parameter tuple the replays take, from a config's
    ``afm`` section."""
    side = int(afm["side"])
    return tuple(sorted({
        "side": side, "n": side * side, "dim": int(afm["dim"]),
        "batch": int(afm["batch"]), "theta": int(afm["theta"]),
        "l_s": float(afm["l_s"]), "c_o": float(afm["c_o"]),
        "c_s": float(afm["c_s"]), "c_m": float(afm["c_m"]),
        "c_d": float(afm["c_d"]), "i_max": int(afm["i_max"]),
        "max_waves": 8 * side * side,
    }.items()))


@functools.partial(jax.jit, static_argnames=("classes", "precision",
                                             "chunk"))
def class_min_dists(w, x, y, *, classes, precision="highest", chunk=4096):
    """(N, classes) least squared distance of each unit to a sample of each
    class, over all of ``x`` in chunks of samples."""
    prec = PRECISIONS[precision]
    n = w.shape[0]
    pad = (-x.shape[0]) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    yp = jnp.pad(y, (0, pad), constant_values=-1)
    xs = xp.reshape(-1, chunk, x.shape[1])
    ys = yp.reshape(-1, chunk)

    def body(acc, xy):
        xc, yc = xy
        d = sq_dists(xc, w, prec).T                         # (N, chunk)
        per = jnp.stack([jnp.min(jnp.where(yc[None, :] == k, d, jnp.inf),
                                 axis=1) for k in range(classes)], axis=1)
        return jnp.minimum(acc, per), None

    acc0 = jnp.full((n, classes), jnp.inf, jnp.float32)
    return jax.lax.scan(body, acc0, (xs, ys))[0]


def label_gap(cmin, labels):
    """Largest relative gap between the nearest sample of the label a unit
    was given and the nearest sample of any label (Eq. 7 picks the latter)."""
    best = jnp.min(cmin, axis=1)
    got = jnp.take_along_axis(cmin, labels[:, None].astype(jnp.int32),
                              axis=1)[:, 0]
    scale = jnp.maximum(best, jnp.maximum(jnp.median(best), EPS))
    return float(jnp.max((got - best) / scale))


def w_gap(w_prog, w_ref):
    """Largest per-unit distance between two maps, over the median norm of
    the reference's unit weights."""
    diff = jnp.linalg.norm(w_prog - w_ref, axis=1)
    return float(jnp.max(diff) / jnp.median(jnp.linalg.norm(w_ref, axis=1)))


@functools.partial(jax.jit, static_argnames=("precision",))
def row_dists(x, w, *, precision="highest"):
    """(R, N) squared distances, for the serving checks."""
    return sq_dists(x, w, PRECISIONS[precision])
