"""MapService / BmuEngine: batched-inference parity, compile-count contract,
online-update swap semantics, and the serve_map CLI smoke test.

ISSUE 2 acceptance: ``MapService`` batched inference matches
``TopoMap.transform`` exactly while compiling at most once per
(bucket, map-shape) — verified via the engine's trace counter.
ISSUE 3: compiled signatures live in a process-wide ``CompileCache``
(same-shape engines share every compile), the ``cap`` escape hatch is
clamped into the bucket ladder, and ``ServiceStats`` keeps busy time and
the wall-clock window on separate clocks. Compile-count tests pin a fresh
cache so counts don't depend on what earlier tests warmed.
"""
import json
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.runtime import LockOrderRecorder, TraceGuard
from repro.api import AFMConfig, TopoMap
from repro.core import metrics
from repro.launch import serve_map as serve_map_cli
from repro.serving import BmuEngine, CompileCache, MapService

CFG = AFMConfig(side=6, dim=12, i_max=48, batch=4, e_factor=0.5)


def _engine(**kwargs):
    """A ``BmuEngine`` with an isolated compile cache (deterministic counts)."""
    kwargs.setdefault("cache", CompileCache())
    return BmuEngine(**kwargs)


def _data(n=256, seed=3):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, CFG.dim))
    y = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, 4)
    return x, y


@pytest.fixture(scope="module")
def fitted():
    x, y = _data()
    return TopoMap(CFG).fit(x, y, key=jax.random.PRNGKey(7)), x, y


# --------------------------------------------------------------- BmuEngine


def test_engine_matches_oracle_on_ragged_sizes(fitted):
    tm, x, _ = fitted
    engine = _engine(buckets=(8, 64))
    from repro.core import search as search_lib
    for n in (1, 3, 8, 9, 64, 100):
        idx, q2 = engine.bmu(tm.state_.w, x[:n])
        ref_idx, ref_q2 = search_lib.exact_bmu(tm.state_.w, x[:n])
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
        # padding changes the matmul shape, so q2 may differ in the last ulp
        np.testing.assert_allclose(np.asarray(q2), np.asarray(ref_q2),
                                   rtol=1e-5)


def test_engine_compiles_once_per_bucket(fitted):
    """Acceptance: at most one compile per (bucket, map-shape)."""
    tm, x, _ = fitted
    engine = _engine(buckets=(8, 64, 512))
    with TraceGuard(engine, expect=1):
        for n in (3, 5, 8, 1, 7):      # all land in the 8-bucket
            engine.bmu(tm.state_.w, x[:n])
    with TraceGuard(engine, expect=1):
        engine.bmu(tm.state_.w, x[:33])    # 64-bucket
        engine.bmu(tm.state_.w, x[:64])
    with TraceGuard(engine, expect=1):
        engine.bmu(tm.state_.w, x[:200])   # 512-bucket
    # 1060 = 512 + 512 + 36-tail-in-64: every chunk reuses a signature
    big = jnp.tile(x, (5, 1))[:1060]
    with TraceGuard(engine):
        engine.bmu(tm.state_.w, big)


def test_engine_new_map_shape_recompiles(fitted):
    tm, x, _ = fitted
    engine = _engine(buckets=(8,))
    with TraceGuard(engine, expect=1):
        engine.bmu(tm.state_.w, x[:4])
    w_small = tm.state_.w[:16]         # different map shape -> one more
    with TraceGuard(engine, expect=1):
        engine.bmu(w_small, x[:4])


def test_engine_cap_clamps_into_ladder(fitted):
    """ISSUE 3 regression: no ``cap`` value may add a jit signature or an
    oversized (memory-ceiling-raising) chunk — the ladder bounds both."""
    tm, x, _ = fitted
    cache = CompileCache()
    engine = _engine(buckets=(8, 64), cache=cache)
    from repro.core import search as search_lib
    big = jnp.tile(x, (2, 1))[:300]
    ref_idx, _ = search_lib.exact_bmu(tm.state_.w, big)
    # bounded by the ladder, and every traced batch dim IS a ladder bucket
    with TraceGuard(engine, max_new=len(engine.buckets)):
        for cap in (1, 5, 8, 9, 33, 64, 100, 5000):
            idx, _ = engine.bmu(tm.state_.w, big, cap=cap)
            np.testing.assert_array_equal(np.asarray(idx),
                                          np.asarray(ref_idx))
    assert {k[0] for k in cache.keys} <= set(engine.buckets)


def test_engines_share_process_wide_compile_cache(fitted):
    """ISSUE 3 acceptance: K same-shape engines compile the ladder once —
    total compiles stay <= ladder size, not K x ladder."""
    tm, x, _ = fitted
    cache = CompileCache()
    engines = [_engine(buckets=(8, 64), cache=cache) for _ in range(4)]
    with TraceGuard(cache, max_new=2):  # == ladder size, shared by all four
        for engine in engines:
            for n in (3, 8, 40, 64):
                engine.bmu(tm.state_.w, x[:n])
    assert engines[0].trace_count == 2
    assert all(e.trace_count == 0 for e in engines[1:])


def test_services_can_share_one_engine(fitted):
    """MapService(engine=...) pools signatures AND padding/compile stats."""
    tm, x, _ = fitted
    engine = _engine(buckets=(8, 64))
    a = MapService(CFG, tm.state_, engine=engine)
    b = MapService(CFG, tm.state_, engine=engine)
    with TraceGuard(engine, expect=1):     # one shared 8-bucket compile
        a.transform(x[:5])
        b.transform(x[:6])
    assert a.engine is b.engine
    assert a.compiles == b.compiles == 1


def test_engine_empty_request(fitted):
    tm, x, _ = fitted
    engine = _engine()
    with TraceGuard(engine):               # empty batch never compiles
        idx, q2 = engine.bmu(tm.state_.w, x[:0])
    assert idx.shape == (0,) and q2.shape == (0,)


def test_engine_rejects_bad_shapes(fitted):
    tm, x, _ = fitted
    with pytest.raises(ValueError, match=r"expected \(B, D\)"):
        _engine().bmu(tm.state_.w, x[0])
    with pytest.raises(ValueError, match="buckets"):
        _engine(buckets=())


def test_topomap_transform_compiles_once_per_bucket(fitted, monkeypatch):
    """The estimator's own inference rides the same bucketed engine."""
    from repro.serving import maps as maps_lib
    monkeypatch.setattr(maps_lib, "GLOBAL_COMPILE_CACHE", CompileCache())
    x, y = _data()
    tm = TopoMap(CFG).fit(x, y, key=jax.random.PRNGKey(7))
    with TraceGuard(tm.engine, expect=1):
        for n in (5, 7, 3, 8):
            tm.transform(x[:n])
    with TraceGuard(tm.engine):        # same bucket: no new compile
        tm.predict(x[:6])
    # a second same-shape estimator reuses the process-wide cache entirely
    tm2 = TopoMap.from_state(tm.state_, CFG)
    with TraceGuard(tm2.engine, maps_lib.GLOBAL_COMPILE_CACHE):
        tm2.transform(x[:4])
    assert maps_lib.GLOBAL_COMPILE_CACHE.trace_count == 1


# -------------------------------------------------------------- MapService


def test_service_matches_topomap_exactly(fitted):
    """Acceptance: service batched inference == TopoMap.transform."""
    tm, x, _ = fitted
    svc = MapService.from_estimator(tm)
    for n in (1, 17, 64, 200):
        np.testing.assert_array_equal(np.asarray(svc.transform(x[:n])),
                                      np.asarray(tm.transform(x[:n])))
    np.testing.assert_array_equal(
        np.asarray(svc.transform(x[:10], lattice=True)),
        np.asarray(tm.transform(x[:10], lattice=True)))
    np.testing.assert_array_equal(np.asarray(svc.predict(x[:50])),
                                  np.asarray(tm.predict(x[:50])))
    assert svc.stats.requests == 6
    assert svc.stats.samples == 1 + 17 + 64 + 200 + 10 + 50


def test_service_quantization_error_and_u_matrix(fitted):
    tm, x, _ = fitted
    svc = MapService.from_estimator(tm)
    q_svc = svc.quantization_error(x)
    q_ref = float(metrics.quantization_error(tm.state_.w, x))
    assert abs(q_svc - q_ref) < 1e-5 * max(1.0, q_ref)
    np.testing.assert_allclose(svc.u_matrix(), tm.u_matrix())


def test_service_predict_needs_labels(fitted):
    tm, x, _ = fitted
    svc = MapService(CFG, tm.state_)
    with pytest.raises(RuntimeError, match="unit labels"):
        svc.predict(x[:4])


def test_service_from_artifact_and_store(tmp_path, fitted):
    tm, x, _ = fitted
    path = str(tmp_path / "art")
    tm.save(path)
    svc = MapService.from_artifact(path)
    np.testing.assert_array_equal(np.asarray(svc.transform(x[:13])),
                                  np.asarray(tm.transform(x[:13])))
    from repro.api import MapStore
    store = MapStore(str(tmp_path / "store"))
    store.save(tm, "toy")
    svc2 = MapService.from_store(str(tmp_path / "store"), "toy")
    np.testing.assert_array_equal(np.asarray(svc2.predict(x[:13])),
                                  np.asarray(tm.predict(x[:13])))


def test_service_rejects_mismatched_state(fitted):
    tm, _, _ = fitted
    bad_cfg = AFMConfig(side=5, dim=12)
    with pytest.raises(ValueError, match="does not match config"):
        MapService(bad_cfg, tm.state_)


def test_service_rejects_mismatched_labels_at_construction(fitted):
    tm, _, _ = fitted
    with pytest.raises(ValueError, match="unit_labels shape"):
        MapService(CFG, tm.state_, unit_labels=jnp.zeros((3,), jnp.int32))


# ------------------------------------------------------------ hot updates


def test_online_update_matches_partial_fit(fitted):
    """`update` applies exactly one backend partial_fit step, then swaps."""
    tm, x, _ = fitted
    svc = MapService.from_estimator(tm)
    key = jax.random.PRNGKey(5)
    svc.update(x[:8], key=key)
    mirror = TopoMap.from_state(tm.state_, CFG)
    mirror.partial_fit(x[:8], key=key)
    state, labels = svc.snapshot()
    np.testing.assert_array_equal(np.asarray(state.w),
                                  np.asarray(mirror.state_.w))
    np.testing.assert_array_equal(np.asarray(labels),
                                  np.asarray(tm.unit_labels_))
    assert svc.stats.updates == 1 and svc.stats.swaps == 1
    # the estimator that produced the service is untouched
    assert tm.state_ is not state


def test_update_does_not_recompile_inference(fitted):
    tm, x, _ = fitted
    svc = MapService.from_estimator(tm)
    svc.transform(x[:8])
    with TraceGuard(svc.engine):
        svc.update(x[:8])
        svc.transform(x[:8])


def test_swap_replaces_state_and_labels(fitted):
    tm, x, _ = fitted
    svc = MapService.from_estimator(tm)
    before = np.asarray(svc.transform(x[:40]))
    new_state = tm.state_._replace(w=jnp.flip(tm.state_.w, axis=0))
    new_labels = jnp.flip(tm.unit_labels_)
    svc.swap(new_state, new_labels)
    after = np.asarray(svc.transform(x[:40]))
    np.testing.assert_array_equal(after, CFG.n_units - 1 - before)
    np.testing.assert_array_equal(np.asarray(svc.predict(x[:40])),
                                  np.asarray(tm.predict(x[:40])))


def test_swap_validates_shapes(fitted):
    tm, _, _ = fitted
    svc = MapService.from_estimator(tm)
    with pytest.raises(ValueError, match="does not match config"):
        svc.swap(tm.state_._replace(w=tm.state_.w[:, :4]))
    with pytest.raises(ValueError, match="unit_labels shape"):
        svc.swap(tm.state_, jnp.zeros((3,), jnp.int32))


# ------------------------------------------------------------------ stats


def test_stats_track_busy_and_wall_window(fitted):
    """ISSUE 3: busy time (summed request spans) and the wall-clock window
    are separate clocks; throughput() divides by the window."""
    tm, x, _ = fitted
    svc = MapService.from_estimator(tm)
    svc.transform(x[:8])
    svc.transform(x[:40])
    s = svc.stats
    assert s.requests == 2 and s.samples == 48
    assert s.busy_seconds > 0
    # the window spans both requests including the gap between them, so it
    # is at least as long as the summed sequential spans
    assert s.window_seconds() >= s.busy_seconds
    assert s.throughput() == pytest.approx(48 / s.window_seconds())
    assert s.busy_throughput() == pytest.approx(48 / s.busy_seconds)


def test_stats_throughput_not_understated_under_concurrency(fitted):
    """Overlapping requests used to sum their spans into the throughput
    denominator; the wall window must not exceed the outer elapsed time."""
    import time as time_lib
    tm, x, _ = fitted
    svc = MapService.from_estimator(tm)
    svc.transform(x[:8])                       # warm up compiles
    svc.stats = type(svc.stats)()              # reset counters
    n_threads, per_thread = 4, 20

    def client():
        for _ in range(per_thread):
            svc.transform(x[:8])

    threads = [threading.Thread(target=client) for _ in range(n_threads)]
    t0 = time_lib.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    outer = time_lib.perf_counter() - t0
    s = svc.stats
    assert s.requests == n_threads * per_thread
    assert s.window_seconds() <= outer + 1e-3
    # wall-window throughput >= the old summed-span number under overlap
    assert s.throughput() >= s.busy_throughput() * 0.99


# ----------------------------------------------------- concurrent serving


def test_concurrent_reads_with_hot_swaps_and_updates(fitted):
    """ISSUE 3 satellite: threads hammer transform/predict while swaps and
    updates land — no torn (state, labels) reads, every result is a valid
    full-map answer, and same-shape swaps never recompile."""
    tm, x, _ = fitted
    svc = MapService.from_estimator(tm)
    state_a, labels_a = svc.snapshot()
    # a flipped map with flipped labels: transform flips, predict is
    # invariant — so a torn (weights, labels) pairing is detectable
    state_b = state_a._replace(w=jnp.flip(state_a.w, axis=0))
    labels_b = jnp.flip(labels_a)
    batch = x[:16]
    t_a = np.asarray(svc.transform(batch))
    t_b = CFG.n_units - 1 - t_a
    p_ok = np.asarray(svc.predict(batch))
    guard = TraceGuard(svc.engine)         # same-shape: no recompiles, ever
    guard.__enter__()
    rec = LockOrderRecorder()
    rec.wrap(svc, "_lock")
    rec.wrap(svc, "_update_lock")
    stop = threading.Event()
    failures = []

    def reader():
        while not stop.is_set():
            t = np.asarray(svc.transform(batch))
            if not (np.array_equal(t, t_a) or np.array_equal(t, t_b)):
                failures.append(("torn transform", t))
            p = np.asarray(svc.predict(batch))
            if not np.array_equal(p, p_ok):
                failures.append(("torn predict", p))

    def writer():
        flipped = False
        while not stop.is_set():
            flipped = not flipped
            if flipped:
                svc.swap(state_b, labels_b)
            else:
                svc.swap(state_a, labels_a)

    threads = [threading.Thread(target=reader) for _ in range(3)]
    threads.append(threading.Thread(target=writer))
    for t in threads:
        t.start()
    deadline = 100
    while svc.stats.swaps < 6 and deadline:
        deadline -= 1
        threads[0].join(0.01)
    stop.set()
    for t in threads:
        t.join()
    assert not failures, failures[:3]
    assert svc.stats.swaps >= 2
    guard.__exit__(None, None, None)       # same-shape: no recompiles
    rec.assert_no_inversions()

    # phase 2: hot updates land while readers hammer — updates keep labels,
    # so every prediction must still come from the served label set, and
    # same-shape update swaps must not add compiles either
    svc.swap(state_a, labels_a)
    valid_labels = set(np.asarray(labels_a).tolist())
    stop2 = threading.Event()

    def update_reader():
        while not stop2.is_set():
            t = np.asarray(svc.transform(batch))
            if not ((0 <= t).all() and (t < CFG.n_units).all()):
                failures.append(("out-of-range transform", t))
            p = np.asarray(svc.predict(batch))
            if not set(p.tolist()) <= valid_labels:
                failures.append(("labels torn from map", p))

    readers = [threading.Thread(target=update_reader) for _ in range(3)]
    with TraceGuard(svc.engine):           # update swaps must not compile
        for t in readers:
            t.start()
        for _ in range(3):
            svc.update(x[:8])
        stop2.set()
        for t in readers:
            t.join()
    assert not failures, failures[:3]
    assert svc.stats.updates == 3
    rec.assert_no_inversions()


# ------------------------------------------------------------- CLI smoke


def _run_cli(monkeypatch, capsys, argv):
    monkeypatch.setattr(sys, "argv", ["serve_map"] + argv)
    serve_map_cli.main()
    return capsys.readouterr().out


def test_serve_map_cli_random_batch(tmp_path, monkeypatch, capsys, fitted):
    tm, _, _ = fitted
    path = str(tmp_path / "art")
    tm.save(path)
    out = _run_cli(monkeypatch, capsys,
                   ["--artifact", path, "--random", "32"])
    assert "output shape: (32,)" in out
    # one bucket's worth at most — and 0 when the process-wide CompileCache
    # is already warm for this map shape from earlier requests
    m = re.search(r"(\d+) compiles", out)
    assert m and int(m.group(1)) <= 1


def test_serve_map_cli_jsonl_predict(tmp_path, monkeypatch, capsys, fitted):
    tm, x, _ = fitted
    path = str(tmp_path / "art")
    tm.save(path)
    reqs = tmp_path / "reqs.jsonl"
    with open(reqs, "w") as f:
        for row in np.asarray(x[:5]):
            f.write(json.dumps(row.tolist()) + "\n")
        f.write(json.dumps({"x": np.asarray(x[5]).tolist()}) + "\n")
    out_npy = str(tmp_path / "out.npy")
    out = _run_cli(monkeypatch, capsys,
                   ["--artifact", path, "--requests", str(reqs),
                    "--endpoint", "predict", "--output", out_npy])
    assert "output shape: (6,)" in out
    np.testing.assert_array_equal(np.load(out_npy),
                                  np.asarray(tm.predict(x[:6])))


def test_serve_map_cli_npy_store_umatrix(tmp_path, monkeypatch, capsys,
                                         fitted):
    tm, x, _ = fitted
    from repro.api import MapStore
    store_root = str(tmp_path / "store")
    MapStore(store_root).save(tm, "toy")
    npy = str(tmp_path / "reqs.npy")
    np.save(npy, np.asarray(x[:9]))
    out = _run_cli(monkeypatch, capsys,
                   ["--store", store_root, "--map", "toy",
                    "--requests", npy])
    assert "output shape: (9,)" in out
    out = _run_cli(monkeypatch, capsys,
                   ["--store", store_root, "--map", "toy@1",
                    "--endpoint", "u-matrix"])
    assert f"output shape: ({CFG.side}, {CFG.side})" in out


def test_serve_map_cli_rejects_map_with_artifact(tmp_path, monkeypatch,
                                                 capsys, fitted):
    """ISSUE 3 hardening: --map used to be silently ignored with --artifact."""
    tm, _, _ = fitted
    path = str(tmp_path / "art")
    tm.save(path)
    with pytest.raises(SystemExit, match="--map"):
        _run_cli(monkeypatch, capsys,
                 ["--artifact", path, "--map", "toy", "--random", "4"])


def test_serve_map_cli_quantization_error_per_sample(tmp_path, monkeypatch,
                                                     capsys, fitted):
    """The quantization-error endpoint emits (B,) per-sample distances."""
    tm, x, _ = fitted
    path = str(tmp_path / "art")
    tm.save(path)
    npy = str(tmp_path / "reqs.npy")
    np.save(npy, np.asarray(x[:11]))
    out_npy = str(tmp_path / "qe.npy")
    out = _run_cli(monkeypatch, capsys,
                   ["--artifact", path, "--requests", npy,
                    "--endpoint", "quantization-error", "--output", out_npy])
    assert "output shape: (11,)" in out
    per_sample = np.load(out_npy)
    svc = MapService.from_estimator(tm)
    np.testing.assert_allclose(per_sample,
                               np.asarray(svc.quantization_errors(x[:11])),
                               rtol=1e-6)
    assert float(per_sample.mean()) == pytest.approx(
        svc.quantization_error(x[:11]), rel=1e-5)


def test_serve_map_cli_concurrent_gateway(tmp_path, monkeypatch, capsys,
                                          fitted):
    """Threaded clients through the coalescing gateway produce the same
    outputs in request order."""
    tm, x, _ = fitted
    path = str(tmp_path / "art")
    tm.save(path)
    npy = str(tmp_path / "reqs.npy")
    np.save(npy, np.asarray(x[:64]))
    out_npy = str(tmp_path / "out.npy")
    out = _run_cli(monkeypatch, capsys,
                   ["--artifact", path, "--requests", npy, "--batch", "4",
                    "--concurrency", "4", "--gateway", "--output", out_npy])
    assert "output shape: (64,)" in out
    assert "gateway:" in out and "clients" in out
    np.testing.assert_array_equal(np.load(out_npy),
                                  np.asarray(tm.transform(x[:64])))
