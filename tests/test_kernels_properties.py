"""Property-based kernel parity suite (DESIGN.md §11).

Random shapes — including non-multiples of the 128 MXU block — with
NaN/inf-free random inputs, pinning each Pallas kernel's interpret-mode
output against its jnp oracle and the fused training megakernel against the
staged step:

- **bmu**: winning index bitwise; q2 to a tight tolerance (the tiled kernel
  sums ``(|w|² - 2w·s) + |s|²`` while the monolithic oracle sums
  ``(|s|² - 2w·s) + |w|²`` — same values, different association, so the
  magnitudes differ by a few ULP while the argmin-relevant ordering agrees).
- **cascade**: integer wave dynamics fully bitwise.
- **swa**: online-softmax accumulation — tight allclose (association again).
- **fused**: the whole training step bitwise against the staged ``Stages``
  path on the exact tier, oracle and interpret kernel alike. Both sides run
  under ``jax.jit`` — that is the deployed regime (backends jit every step),
  and XLA's FMA contraction makes jitted-vs-eager differ by design.
- **bf16 tier**: tolerance contract at the paper's dim 784 — index
  agreement ≥ 0.95 and polished q2 within 8 ULP of the f32 oracle where the
  indices agree (measured: ≥ 0.988 and ≤ 2 ULP on seeded normals) — plus a
  regression proving the exact tier is never silently downgraded.

Runs property-style under ``hypothesis`` when installed; otherwise the same
strategies are sampled deterministically (seeded) so the suite still
executes everywhere the repo's no-new-deps rule applies.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hyp_st
    HAS_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAS_HYPOTHESIS = False

from repro.core import afm
from repro.kernels.bmu import ops as bmu_ops
from repro.kernels.bmu import ref as bmu_ref
from repro.kernels.cascade import ops as cas_ops
from repro.kernels.cascade import ref as cas_ref
from repro.kernels.fused import ops as fused_ops
from repro.kernels.swa import ops as swa_ops
from repro.kernels.swa import ref as swa_ref


# --------------------------------------------------------- property harness
# hypothesis when available; otherwise each strategy is sampled with a
# per-example seeded Generator, so case k is identical on every run/machine.

class _Ints:
    def __init__(self, lo, hi):
        self.lo, self.hi = lo, hi

    def sample(self, rng):
        return int(rng.integers(self.lo, self.hi + 1))


class _Floats:
    def __init__(self, lo, hi):
        self.lo, self.hi = lo, hi

    def sample(self, rng):
        return float(rng.uniform(self.lo, self.hi))


if HAS_HYPOTHESIS:
    def integers(lo, hi):
        return hyp_st.integers(lo, hi)

    def floats(lo, hi):
        return hyp_st.floats(lo, hi)

    def property_test(max_examples=10, **strats):
        def deco(fn):
            return settings(max_examples=max_examples, deadline=None)(
                given(**strats)(fn))
        return deco
else:
    integers, floats = _Ints, _Floats

    def property_test(max_examples=10, **strats):
        names = sorted(strats)

        def deco(fn):
            cases = []
            for ex in range(max_examples):
                rng = np.random.default_rng(0xAF00 + 7919 * ex)
                cases.append(tuple(strats[k].sample(rng) for k in names))
            return pytest.mark.parametrize(",".join(names), cases)(fn)
        return deco


def bits_equal(x, y):
    x, y = np.asarray(x), np.asarray(y)
    if x.dtype.kind == "f":
        return np.array_equal(x.view(np.uint32), y.view(np.uint32))
    return np.array_equal(x, y)


def assert_bits_equal(x, y, msg=""):
    assert bits_equal(x, y), msg


# ------------------------------------------------------- per-kernel parity


@property_test(max_examples=12, n=integers(3, 400), b=integers(1, 80),
               d=integers(1, 300))
def test_bmu_interpret_matches_ref(n, b, d):
    """Exact tier, random (B, N, D) incl. non-block-multiple tails: index
    bitwise, q2 tight (association differs across the tile boundary)."""
    key = jax.random.PRNGKey(n * 7919 + b * 31 + d)
    kw, ks = jax.random.split(key)
    w = jax.random.normal(kw, (n, d), jnp.float32)
    s = jax.random.normal(ks, (b, d), jnp.float32)
    i1, q1 = bmu_ops.bmu(w, s, use_pallas=True, interpret=True)
    i2, q2 = bmu_ref.bmu_ref(w, s)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(q1), np.asarray(q2),
                               rtol=1e-4, atol=1e-4)
    assert i1.dtype == jnp.int32 and q1.dtype == jnp.float32


@property_test(max_examples=10, side=integers(3, 40), p=floats(0.0, 1.0),
               theta=integers(2, 6))
def test_cascade_wave_interpret_bitwise(side, p, theta):
    """Integer wave dynamics: fully bitwise, any lattice size."""
    key = jax.random.PRNGKey(int(side + theta * 101 + p * 997))
    k1, k2, k3 = jax.random.split(key, 3)
    c = jax.random.randint(k1, (side, side), 0, theta + 2)
    fired = jax.random.uniform(k2, (side, side)) < 0.25
    bern = jax.random.uniform(k3, (4, side, side)) < p
    a = cas_ops.cascade_wave(c, fired, bern, theta, interpret=True)
    b = cas_ref.cascade_wave_ref(c, fired, bern, theta)
    for got, want in zip(a, b):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@property_test(max_examples=8, b=integers(1, 4), h=integers(1, 8),
               hd_pow=integers(6, 7), w_pow=integers(7, 10),
               pos=integers(0, 70_000))
def test_swa_decode_matches_ref(b, h, hd_pow, w_pow, pos):
    """Sliding-window decode: online softmax vs dense — tight allclose."""
    hd, w = 2 ** hd_pow, 2 ** w_pow
    key = jax.random.PRNGKey(b * h * hd + w + pos)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, hd), jnp.float32)
    k = jax.random.normal(kk, (b, w, h, hd), jnp.float32)
    v = jax.random.normal(kv, (b, w, h, hd), jnp.float32)
    posv = jnp.full((b,), pos, jnp.int32)
    o1 = swa_ops.swa_decode(q, k, v, posv, interpret=True)
    o2 = swa_ref.swa_decode_ref(q, k, v, posv, window=w)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------- fused megakernel vs staged stages


def _train_compare(cfg, stages_a, stages_b, steps=12, seed=0):
    """Run the same seeded stream through two stage-sets, both jitted,
    and return the final (state, summed aux) pairs."""
    data = jax.random.normal(jax.random.PRNGKey(seed + 7),
                             (64, cfg.dim), jnp.float32)
    outs = []
    for stages in (stages_a, stages_b):
        step = jax.jit(functools.partial(afm.train_step_batch, cfg=cfg,
                                         stages=stages))
        st = afm.init(jax.random.PRNGKey(seed + 1), cfg, data)
        key = jax.random.PRNGKey(seed + 3)
        waves = sizes = 0
        for _ in range(steps):
            key, ks, kd = jax.random.split(key, 3)
            idx = jax.random.randint(kd, (cfg.batch,), 0, data.shape[0])
            st, aux = step(st, data[idx], ks)
            waves += int(aux.waves)
            sizes += int(aux.cascade_size)
        outs.append((st, waves, sizes))
    return outs


#: Cascades must actually fire for the wave loop to be exercised: low theta,
#: early-schedule p_i kept high via c_m/c_d, and a bounded wave budget so
#: the interpret-mode run stays CI-sized.
def _hot_cfg(side, d, b, theta, max_waves=None):
    return afm.AFMConfig(side=side, dim=d, batch=b, i_max=50 * side * side,
                         theta=theta, c_m=0.3, c_d=50.0, max_waves=max_waves)


@property_test(max_examples=6, side=integers(4, 8), d=integers(3, 24),
               b=integers(1, 5), theta=integers(2, 4))
def test_fused_oracle_step_bitwise_vs_staged(side, d, b, theta):
    """Exact tier, oracle dispatch: the fused step is the staged step."""
    cfg = _hot_cfg(side, d, b, theta)
    fstage = fused_ops.make_fused_stage(search="exact", use_pallas=False)
    (s1, w1, a1), (s2, w2, a2) = _train_compare(
        cfg, afm.EXACT_STAGES, afm.EXACT_STAGES._replace(fused=fstage),
        seed=side * 100 + d)
    assert w1 == w2 and a1 == a2
    for f in s1._fields:
        assert_bits_equal(getattr(s1, f), getattr(s2, f), f)


@pytest.mark.parametrize("side,d,b,theta,max_waves", [
    (5, 8, 1, 2, None),
    (6, 12, 4, 3, 40),
    (4, 5, 3, 2, 3),       # binding wave cap: deferred-firing continuation
])
def test_fused_interpret_kernel_bitwise_vs_staged(side, d, b, theta,
                                                  max_waves):
    """Exact tier, real kernel body (Pallas interpreter): still bitwise —
    including when the cascade outlives the in-kernel wave budget and the
    tail loop continues it, and when ``max_waves`` cuts cascades short."""
    cfg = _hot_cfg(side, d, b, theta, max_waves=max_waves)
    fstage = fused_ops.make_fused_stage(search="exact", use_pallas=True,
                                        interpret=True, wave_cap=4)
    (s1, w1, a1), (s2, w2, a2) = _train_compare(
        cfg, afm.EXACT_STAGES, afm.EXACT_STAGES._replace(fused=fstage),
        seed=side + d)
    assert w1 == w2 and a1 == a2 and w1 > 0
    for f in s1._fields:
        assert_bits_equal(getattr(s1, f), getattr(s2, f), f)


def test_fused_heuristic_search_stays_external_and_bitwise():
    """search='heuristic' keeps the paper's relay race outside the kernel;
    the fused remainder must still replay the staged step bitwise."""
    cfg = _hot_cfg(6, 10, 1, 3)
    fstage = fused_ops.make_fused_stage(search="heuristic", use_pallas=True,
                                        interpret=True)
    (s1, w1, _), (s2, w2, _) = _train_compare(
        cfg, afm.DEFAULT_STAGES, afm.DEFAULT_STAGES._replace(fused=fstage))
    assert w1 == w2
    for f in s1._fields:
        assert_bits_equal(getattr(s1, f), getattr(s2, f), f)


def _shared_unit_step(search, groups, p_i):
    """One fused step, interpreted kernel and oracle, on a batch whose
    samples crowd onto shared units: ``groups`` samples per crowd."""
    from repro.core import search as search_lib

    b = sum(groups)
    cfg = _hot_cfg(4, 24, b, 2)
    kw, kc = jax.random.split(jax.random.PRNGKey(5))
    w = jax.random.normal(kw, (cfg.n_units, cfg.dim), jnp.float32)
    # each crowd sits on its own unit's weights (units 1, 4, 7, ...)
    unit = 3 * np.repeat(np.arange(len(groups)), groups) + 1
    noise = 1e-3 * jax.random.normal(kc, (b, cfg.dim), jnp.float32)
    samples = w[unit] + noise
    c = jnp.zeros((cfg.n_units,), jnp.int32)
    res = None
    if search == "external":
        zeros = jnp.zeros((b,), jnp.int32)
        res = search_lib.SearchResult(jnp.asarray(unit, jnp.int32),
                                      jnp.zeros((b,)), zeros, zeros)
    precision = "bf16" if search == "bf16" else "exact"

    def step(use_pallas):
        return jax.jit(lambda w, c, s, k: fused_ops.fused_step_parts(
            w, c, s, k, cfg, l_c=0.3, p_i=p_i, search_result=res,
            precision=precision, use_pallas=use_pallas,
            interpret=use_pallas))(w, c, samples, jax.random.PRNGKey(9))

    got, want = step(True), step(False)
    counts = np.bincount(np.asarray(want.gmu), minlength=cfg.n_units)
    assert sorted(counts[counts > 0].tolist()) == sorted(groups), counts
    return got, want, counts


@pytest.mark.parametrize("search", ["external", "exact", "bf16"])
def test_fused_merge_bitwise_when_samples_share_a_unit(search):
    """The kernel's one-hot merge walks the batch in order, so it keeps the
    scatter-add's summation order: the whole step stays bitwise against
    the oracle when four samples land on one unit (where a reordered sum
    of four terms would round differently), cascade included."""
    got, want, _ = _shared_unit_step(search, (4, 2, 1), p_i=0.9)
    assert int(want.waves) > 0
    for f in got._fields:
        assert_bits_equal(getattr(got, f), getattr(want, f), f)


def test_fused_merge_mean_within_an_ulp_at_odd_counts():
    """A unit hit by 3 (5, 6, 7, ...) samples divides its summed target by
    a count that is not a power of two. XLA:CPU turns that division by a
    broadcast count into a multiply by its reciprocal in some programs and
    not in others, so the interpreted kernel and the oracle may round that
    unit's mean one ULP apart. Every other value stays bitwise."""
    got, want, counts = _shared_unit_step("external", (3, 2, 1), p_i=0.0)
    odd = counts == 3
    ulp = _q2_ulp(np.asarray(got.w)[odd], np.asarray(want.w)[odd])
    assert ulp.max() <= 2, ulp.max()
    assert_bits_equal(np.asarray(got.w)[~odd], np.asarray(want.w)[~odd])
    for f in ("c", "gmu", "size", "waves", "recv"):
        assert_bits_equal(getattr(got, f), getattr(want, f), f)


def test_fused_kernel_refuses_a_map_over_its_vmem_budget():
    """Above the VMEM ceiling the fused kernel raises, naming the limit —
    it never quietly falls back to another path."""
    from repro.kernels.fused import fused as fused_lib

    cfg = afm.AFMConfig(side=64, dim=784, batch=16)
    assert (fused_lib.vmem_bytes(56, 784, fused_ops.DEFAULT_WAVE_CAP)
            <= fused_lib.VMEM_LIMIT_BYTES
            < fused_lib.vmem_bytes(64, 784, fused_ops.DEFAULT_WAVE_CAP))
    args = (jax.ShapeDtypeStruct((cfg.n_units, cfg.dim), jnp.float32),
            jax.ShapeDtypeStruct((cfg.n_units,), jnp.int32),
            jax.ShapeDtypeStruct((cfg.batch, cfg.dim), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
    with pytest.raises(ValueError, match="100 MiB limit"):
        jax.eval_shape(lambda w, c, s, k: fused_ops.fused_step_parts(
            w, c, s, k, cfg, l_c=0.5, p_i=0.5, use_pallas=True,
            interpret=False), *args)


def test_fused_stage_validates_options():
    with pytest.raises(ValueError, match="search"):
        fused_ops.make_fused_stage(search="nope")
    with pytest.raises(ValueError, match="precision"):
        fused_ops.fused_step_parts(
            jnp.zeros((4, 2)), jnp.zeros((4,), jnp.int32),
            jnp.zeros((1, 2)), jax.random.PRNGKey(0),
            afm.AFMConfig(side=2, dim=2), l_c=0.1, p_i=0.5,
            precision="fp8")


# ------------------------------------------------- bf16 tolerance contract

#: The documented tier contract at the paper's dim 784 (DESIGN.md §11).
#: Measured on seeded normals: agreement ≥ 0.988, ULP ≤ 2 — the bounds
#: below leave headroom without ever letting a broken tier slip through.
BF16_MIN_AGREEMENT = 0.95
BF16_Q2_ULP_BOUND = 8


def _q2_ulp(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.abs(a.view(np.int32).astype(np.int64)
                  - b.view(np.int32).astype(np.int64))


@pytest.mark.parametrize("seed", [0, 4, 7])
def test_bf16_tier_tolerance_contract_dim784(seed):
    """bf16 BMU vs the f32 oracle at dim 784: index agreement above the
    documented floor; polished q2 within the documented ULP bound wherever
    the winners agree; dtypes identical to the exact tier."""
    k = jax.random.PRNGKey(seed)
    kw, ks = jax.random.split(k)
    w = jax.random.normal(kw, (400, 784), jnp.float32)
    s = jax.random.normal(ks, (256, 784), jnp.float32)
    ie, qe = bmu_ref.bmu_ref(w, s)
    ib, qb = bmu_ops.bmu(w, s, use_pallas=True, interpret=True,
                         precision="bf16")
    assert ib.dtype == jnp.int32 and qb.dtype == jnp.float32
    agree = np.asarray(ie) == np.asarray(ib)
    assert agree.mean() >= BF16_MIN_AGREEMENT, agree.mean()
    ulp = _q2_ulp(np.asarray(qe)[agree], np.asarray(qb)[agree])
    assert ulp.max() <= BF16_Q2_ULP_BOUND, ulp.max()
    # the interpreted kernel is pinned bitwise to the tier's own oracle
    ir, qr = bmu_ref.bmu_bf16_ref(w, s)
    np.testing.assert_array_equal(np.asarray(ib), np.asarray(ir))
    assert_bits_equal(qb, qr)


def test_exact_tier_never_silently_downgraded():
    """Find a seeded case where the two tiers' oracles disagree on the
    winner, then assert each ``precision`` flag reproduces its own tier
    exactly — no silent substitution in either direction."""
    found = False
    for seed in range(40):
        kw, ks = jax.random.split(jax.random.PRNGKey(seed))
        w = jax.random.normal(kw, (512, 784), jnp.float32)
        s = jax.random.normal(ks, (512, 784), jnp.float32)
        ie, qe = bmu_ref.bmu_ref(w, s)
        ib, qb = bmu_ref.bmu_bf16_ref(w, s)
        if not np.array_equal(np.asarray(ie), np.asarray(ib)):
            found = True
            break
    assert found, "no tier disagreement in 40 seeds — widen the search"
    i_x, q_x = bmu_ops.bmu(w, s, use_pallas=True, interpret=True,
                           precision="exact")
    i_b, q_b = bmu_ops.bmu(w, s, use_pallas=True, interpret=True,
                           precision="bf16")
    np.testing.assert_array_equal(np.asarray(i_x), np.asarray(ie))
    np.testing.assert_array_equal(np.asarray(i_b), np.asarray(ib))
    assert not np.array_equal(np.asarray(i_x), np.asarray(i_b))
    for q in (q_x, q_b):
        assert q.dtype == jnp.float32
    with pytest.raises(ValueError, match="precision"):
        bmu_ops.bmu(w, s, precision="fp16")


def test_fused_bf16_tier_matches_staged_bf16_search():
    """The tolerance tier only replaces the distance *search*; adapt, drive,
    and the cascade stay on the exact ops. A fused bf16 run must therefore
    equal a staged run whose search stage is the bf16 oracle — bitwise."""
    cfg = _hot_cfg(6, 16, 2, 3)

    def bf16_search(state, samples, key, cfg):
        del key
        gmu, q2 = bmu_ref.bmu_bf16_ref(state.w, samples)
        zeros = jnp.zeros(samples.shape[:1], jnp.int32)
        from repro.core import search as search_lib
        return search_lib.SearchResult(gmu, q2, zeros, zeros)

    staged_bf16 = afm.EXACT_STAGES._replace(search=bf16_search)
    for kw in (dict(use_pallas=False),
               dict(use_pallas=True, interpret=True)):
        fstage = fused_ops.make_fused_stage(search="exact",
                                            precision="bf16", **kw)
        (s1, w1, _), (s2, w2, _) = _train_compare(
            cfg, staged_bf16, afm.EXACT_STAGES._replace(fused=fstage))
        assert w1 == w2
        for f in s1._fields:
            assert_bits_equal(getattr(s1, f), getattr(s2, f),
                              f"{kw}: {f}")
