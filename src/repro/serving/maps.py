"""MapService — batched inference serving for trained topographic maps.

The paper decouples training from use; this module is the "use" half. Three
layers:

``CompileCache``
    A process-wide jit cache keyed ``(bucket, n_units, dim, flags)``. Every
    ``BmuEngine`` dispatches through it, so serving K same-shape maps — or
    mixing ``TopoMap`` inference with ``MapService`` endpoints — compiles
    the bucket ladder **once per shape for the whole process**, not once
    per engine. A trace-time counter makes the contract testable.

``BmuEngine``
    The shared batched-inference hot path: requests are padded up to a
    small set of **buckets** and dispatched through one jit-compiled BMU
    search, so the engine compiles at most once per (bucket, map-shape)
    instead of once per ragged request size. On TPU the search runs the
    ``kernels.bmu`` Pallas kernel; elsewhere the jnp oracle.
    ``TopoMap.transform`` / ``predict`` run on this same engine.

``MapService``
    A serving front end over one map: ``transform`` / ``predict`` /
    ``quantization_error`` / ``u_matrix`` endpoints, request statistics,
    and **hot online updates** — ``update`` advances the served map by one
    ``partial_fit``-style training step and atomically swaps the new state
    in (readers always see a consistent map; in-flight requests finish on
    the old weights). Construct from a fitted estimator, an artifact
    directory, or a ``MapStore`` entry (``repro.api.persistence``).

``repro.serving.gateway.MapGateway`` fronts many services and coalesces
concurrent requests into bucket-sized dispatches.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import metrics
from repro.core import search as search_lib
from repro.core.afm import AFMConfig, AFMState
from repro.kernels.bmu import ops as bmu_ops

#: Request sizes are padded up to the smallest fitting bucket; larger
#: requests are chunked by the top bucket. Geometric spacing bounds padding
#: waste at ~8x worst case while keeping the compile count at four.
DEFAULT_BUCKETS = (8, 64, 512, 4096)

#: Lock-discipline declarations checked by ``repro.analysis`` (REP301):
#: every ``self.<attr>`` access outside ``with self.<lock>`` is flagged
#: unless annotated ``# lint: unlocked-ok(reason)``. ``__init__`` is exempt
#: (construction happens-before sharing).
GUARDED_BY = {
    "CompileCache": {"_fns": "_lock", "_claimed": "_lock",
                     "keys": "_lock", "trace_count": "_lock"},
    "BmuEngine": {"trace_count": "_counter_lock",
                  "padded": "_counter_lock"},
    "LatencyHistogram": {"_counts": "_lock", "count": "_lock",
                         "total_seconds": "_lock"},
    "MapService": {"_state": "_lock", "_unit_labels": "_lock",
                   "stats": "_lock", "_update_backend": "_update_lock",
                   "_next_key": "_update_lock"},
}


class CompileCache:
    """Process-wide jit cache for the bucketed BMU search.

    One jitted callable exists per kernel-flag pair; jax keys its own cache
    on argument shapes, so the effective signature is
    ``(bucket, n_units, dim, use_pallas, interpret)``. ``trace_count``
    increments inside the traced function — it counts real compilations,
    not calls — and ``keys`` records every traced signature.

    ``GLOBAL_COMPILE_CACHE`` is the default shared by every ``BmuEngine``
    (and therefore every ``TopoMap`` / ``MapService`` / ``MapGateway`` in
    the process); pass a fresh instance for isolated compile accounting.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._fns: dict[tuple[bool, bool], callable] = {}
        self._claimed: set[tuple] = set()
        self.keys: set[tuple] = set()
        self.trace_count = 0

    def _record(self, key: tuple) -> None:
        with self._lock:
            self.trace_count += 1
            self.keys.add(key)

    def claim(self, key: tuple) -> bool:
        """Atomically claim first-dispatch attribution for ``key`` — True
        for exactly one caller per key, ever. Engines use this to count
        the compiles they triggered without racing on concurrent cold
        dispatches of the same signature."""
        with self._lock:
            if key in self._claimed:
                return False
            self._claimed.add(key)
            return True

    def fn(self, use_pallas: bool, interpret: bool):
        """The jitted BMU callable for one resolved flag pair."""
        flags = (bool(use_pallas), bool(interpret))
        with self._lock:
            cached = self._fns.get(flags)
        if cached is not None:
            return cached

        def traced(w, s):
            # Runs only when jax traces a new (bucket, map-shape) signature,
            # so this side effect counts compilations, not calls.
            self._record((s.shape[0], w.shape[0], w.shape[1]) + flags)
            if flags[0]:
                return bmu_ops.bmu(w, s, use_pallas=True, interpret=flags[1])
            return search_lib.exact_bmu(w, s)

        jitted = jax.jit(traced)
        with self._lock:
            # lost a construction race: keep the first, it owns the jit cache
            return self._fns.setdefault(flags, jitted)


#: Default process-wide cache — see ``CompileCache``.
GLOBAL_COMPILE_CACHE = CompileCache()


class BmuEngine:
    """Bucket-padded, jit-compiled exact-BMU search over a dense map.

    ``use_pallas`` / ``interpret`` default to auto: the Pallas kernel on
    TPU, the jnp oracle elsewhere (matching ``kernels.bmu.ops``). Compiled
    code lives in ``cache`` (the process-wide ``GLOBAL_COMPILE_CACHE`` by
    default), so same-shape engines share every signature.

    ``trace_count`` counts the compilations *this engine* caused — cache
    hits left behind by other engines don't inflate it.
    """

    def __init__(self, *, buckets=DEFAULT_BUCKETS,
                 use_pallas: bool | None = None,
                 interpret: bool | None = None,
                 cache: CompileCache | None = None):
        self.use_pallas, self.interpret = bmu_ops.resolve_flags(use_pallas,
                                                                interpret)
        buckets = tuple(sorted({int(b) for b in buckets}))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets!r}")
        self.buckets = buckets
        self.cache = cache if cache is not None else GLOBAL_COMPILE_CACHE
        self.trace_count = 0      # compiles attributed to this engine
        self.padded = 0           # total pad rows added across calls
        self._counter_lock = threading.Lock()
        self._call = self.cache.fn(self.use_pallas, self.interpret)

    def _plan(self, cap: int | None) -> tuple[int, ...]:
        """The bucket ladder under an optional chunk ``cap``.

        ``cap`` clamps the largest chunk to the biggest ladder bucket
        ``<= cap`` — never to ``cap`` itself — so every dispatch reuses an
        existing bucket signature and no ``cap`` value can append an
        oversized bucket or a fresh jit signature. A ``cap`` below the
        smallest bucket still pads up to it (the ladder floor).
        """
        if cap is None:
            return self.buckets
        cap = max(1, int(cap))
        eligible = tuple(b for b in self.buckets if b <= cap)
        return eligible or self.buckets[:1]

    def bmu(self, w: jnp.ndarray, data: jnp.ndarray, *,
            cap: int | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
        """argmin_j |w_j - s_i|^2 for a (B, D) request of any B.

        Returns (idx (B,) int32, q2 (B,) float32). ``cap`` bounds the
        largest chunk (legacy ``chunk=`` escape hatch for memory ceilings);
        it is clamped into the bucket ladder — see ``_plan``.
        """
        data = jnp.asarray(data, jnp.float32)
        if data.ndim != 2:
            raise ValueError(f"expected (B, D) request, got shape "
                             f"{data.shape}")
        n = data.shape[0]
        if n == 0:
            return jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32)
        w = jnp.asarray(w, jnp.float32)
        buckets = self._plan(cap)
        idxs, q2s = [], []
        pos = 0
        while pos < n:
            take = min(n - pos, buckets[-1])
            bucket = next(b for b in buckets if b >= take)
            block = data[pos:pos + take]
            if take < bucket:
                block = jnp.pad(block, ((0, bucket - take), (0, 0)))
                with self._counter_lock:
                    self.padded += bucket - take
            key = (bucket, w.shape[0], w.shape[1], self.use_pallas,
                   self.interpret)
            if self.cache.claim(key):
                with self._counter_lock:
                    self.trace_count += 1
            idx, q2 = self._call(w, block)
            idxs.append(idx[:take].astype(jnp.int32))
            q2s.append(q2[:take])
            pos += take
        if len(idxs) == 1:
            return idxs[0], q2s[0]
        return jnp.concatenate(idxs), jnp.concatenate(q2s)


class LatencyHistogram:
    """Streaming latency percentiles over fixed log-spaced buckets.

    SLO percentiles (p50/p95/p99) without an unbounded request log: spans
    land in one of ``n_buckets`` geometrically spaced buckets covering
    ``[lo, hi)`` seconds (default 1 µs .. 100 s, so every bucket is the
    same ~±15% wide in relative terms), plus an overflow bucket. A
    percentile reads back the **upper edge** of the bucket holding that
    quantile — conservative by at most one bucket width, monotone in the
    quantile, and always > 0 for a non-empty histogram, so
    ``p99 >= p50 > 0`` holds by construction.

    Thread-safe: ``record`` / ``merge`` / readers all take the instance
    lock, and replica histograms merge into fleet-wide ones with
    ``merge`` (bucket-wise integer adds — merging never loses precision,
    unlike merging precomputed percentiles).
    """

    N_BUCKETS = 128
    LO = 1e-6     # seconds; spans below land in bucket 0
    HI = 100.0    # seconds; spans at/above land in the overflow bucket

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = [0] * (self.N_BUCKETS + 1)   # +1: overflow
        self._scale = self.N_BUCKETS / math.log(self.HI / self.LO)
        self.count = 0
        self.total_seconds = 0.0

    def _bucket(self, seconds: float) -> int:
        if seconds < self.LO:
            return 0
        if seconds >= self.HI:
            return self.N_BUCKETS
        return min(int(math.log(seconds / self.LO) * self._scale),
                   self.N_BUCKETS - 1)

    def _edge(self, bucket: int) -> float:
        """Upper edge of ``bucket`` in seconds (HI for the overflow)."""
        return self.LO * math.exp((min(bucket, self.N_BUCKETS - 1) + 1)
                                  / self._scale)

    def record(self, seconds: float) -> None:
        with self._lock:
            self._counts[self._bucket(seconds)] += 1
            self.count += 1
            self.total_seconds += seconds

    def merge(self, other: "LatencyHistogram") -> "LatencyHistogram":
        """Fold ``other``'s buckets into this histogram (returns self)."""
        with other._lock:
            counts = list(other._counts)
            n, total = other.count, other.total_seconds
        with self._lock:
            for i, c in enumerate(counts):
                self._counts[i] += c
            self.count += n
            self.total_seconds += total
        return self

    def percentile(self, q: float) -> float:
        """Seconds at quantile ``q`` in [0, 1]; 0.0 when empty."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile must be in [0, 1], got {q}")
        with self._lock:
            if self.count == 0:
                return 0.0
            rank = max(1, math.ceil(q * self.count))
            seen = 0
            for bucket, c in enumerate(self._counts):
                seen += c
                if seen >= rank:
                    return self._edge(bucket)
        return self.HI                      # unreachable; counts sum to count

    def mean(self) -> float:
        with self._lock:
            return self.total_seconds / self.count if self.count else 0.0

    def quantiles(self) -> dict[str, float]:
        """The SLO trio, in seconds: ``{"p50": ..., "p95": ..., "p99": ...}``."""
        return {"p50": self.percentile(0.50), "p95": self.percentile(0.95),
                "p99": self.percentile(0.99)}

    def summary(self, unit: float = 1e3) -> str:
        """One-line human summary (default unit: milliseconds)."""
        qs = self.quantiles()
        n = self.count  # lint: unlocked-ok(single int read, display only)
        return (f"p50={qs['p50'] * unit:.2f} p95={qs['p95'] * unit:.2f} "
                f"p99={qs['p99'] * unit:.2f} (n={n})")

    def __repr__(self):
        return f"LatencyHistogram({self.summary()})"


@dataclasses.dataclass
class ServiceStats:
    """Rolling counters for one ``MapService``.

    Two clocks, because concurrent requests overlap:

    ``busy_seconds``
        Summed per-request engine spans (dispatch + device time, lock wait
        excluded). Under concurrency the spans overlap, so this can exceed
        wall time — it measures work attributed, not elapsed.
    ``window_seconds()``
        The wall-clock window from the first request's start to the latest
        request's end. ``throughput()`` divides by this, so it stays honest
        under concurrent load; ``busy_throughput()`` is the per-request
        serial rate.

    ``latency`` is a ``LatencyHistogram`` of per-request engine spans
    (same clock as ``busy_seconds``): p50/p95/p99 without a request log,
    mergeable across replicas (``repro.serving.fleet``).
    """
    requests: int = 0
    samples: int = 0
    busy_seconds: float = 0.0
    updates: int = 0
    swaps: int = 0
    window_start: float | None = None
    window_end: float | None = None
    latency: LatencyHistogram = dataclasses.field(
        default_factory=LatencyHistogram)

    def window_seconds(self) -> float:
        if self.window_start is None or self.window_end is None:
            return 0.0
        return self.window_end - self.window_start

    def throughput(self) -> float:
        """Samples/s over the wall-clock request window."""
        w = self.window_seconds()
        return self.samples / w if w > 0 else 0.0

    def busy_throughput(self) -> float:
        """Samples/s per second of attributed engine time."""
        return (self.samples / self.busy_seconds
                if self.busy_seconds > 0 else 0.0)


class _Unset:
    pass


_UNSET = _Unset()


def postprocess(side: int, kind: str, lattice: bool, idx, q2, labels, *,
                xp=jnp):
    """One request's endpoint view of a BMU dispatch (idx, q2, labels).

    The single postprocessing implementation behind both ``MapService``
    endpoints (``xp=jnp``) and the gateway's numpy-native coalesced
    dispatches (``xp=np``) — predict/lattice/QE semantics and error
    messages cannot drift between the two surfaces.
    """
    if kind == "predict":
        if labels is None:
            raise RuntimeError("predict endpoint needs unit labels — serve a "
                               "labelled map or swap labels in")
        return labels[idx]
    if kind == "quantization_errors":
        return xp.sqrt(q2)
    if kind != "transform":
        raise ValueError(f"unknown endpoint kind {kind!r}")
    if lattice:
        return xp.stack([idx // side, idx % side], axis=-1)
    return idx


class MapService:
    """Batched-inference service over one trained map.

    State (``AFMState`` + optional unit labels) lives behind an atomic
    swap: endpoints snapshot it once per request, ``swap``/``update``
    replace it wholesale, so readers never observe a half-updated map.
    Because the engine's jit cache is keyed on shapes only, swapping
    same-shape weights never recompiles.

    Pass ``engine`` to share one ``BmuEngine`` (and its padding/compile
    stats) across services; by default each service gets its own engine,
    which still shares compiled code through the process-wide
    ``CompileCache``.
    """

    def __init__(self, cfg: AFMConfig, state: AFMState, *,
                 unit_labels=None, labeling: str = "nearest",
                 buckets=DEFAULT_BUCKETS, use_pallas: bool | None = None,
                 interpret: bool | None = None,
                 engine: BmuEngine | None = None,
                 update_backend: str = "batched",
                 update_backend_options: dict | None = None, seed: int = 0):
        self._validate_state(cfg, state)
        self.cfg = cfg
        self.labeling = labeling
        self.engine = engine if engine is not None else BmuEngine(
            buckets=buckets, use_pallas=use_pallas, interpret=interpret)
        self.stats = ServiceStats()
        self._state = state
        self._unit_labels = self._validate_labels(cfg, unit_labels)
        self._lock = threading.Lock()           # guards the state snapshot
        # serialises writers (update and external swap) against each other so
        # an update's read-step-swap can't silently overwrite a concurrent
        # swap; re-entrant because update() calls swap() while holding it
        self._update_lock = threading.RLock()
        self._update_backend_name = update_backend
        self._update_backend_options = dict(update_backend_options or {})
        self._update_backend = None
        self._next_key = jax.random.PRNGKey(seed)

    # --------------------------------------------------------- constructors

    @classmethod
    def from_estimator(cls, tm, **kwargs) -> "MapService":
        """Serve a fitted ``TopoMap`` (shares no mutable state with it).

        The estimator's resolved kernel flags carry over so the service's
        BMU path is bit-identical to ``tm.transform`` on every platform
        (and, through the shared ``CompileCache``, reuses its compiles).
        """
        kwargs.setdefault("labeling", tm.labeling)
        kwargs.setdefault("use_pallas", tm.engine.use_pallas)
        kwargs.setdefault("interpret", tm.engine.interpret)
        return cls(tm.cfg, tm.state_, unit_labels=tm.unit_labels_, **kwargs)

    @classmethod
    def from_artifact(cls, path: str, **kwargs) -> "MapService":
        """Serve a saved artifact directory (``TopoMap.save`` output)."""
        from repro.api import persistence
        art = persistence.load_artifact(path)
        kwargs.setdefault("labeling", art.labeling)
        return cls(art.cfg, art.state, unit_labels=art.unit_labels, **kwargs)

    @classmethod
    def from_store(cls, root: str, spec: str, **kwargs) -> "MapService":
        """Serve ``name[@version]`` out of a ``MapStore`` directory."""
        from repro.api import persistence
        return cls.from_artifact(persistence.MapStore(root).path(spec),
                                 **kwargs)

    # ------------------------------------------------------------ endpoints

    def serve_bmu(self, data) -> tuple[jnp.ndarray, jnp.ndarray,
                                       jnp.ndarray | None]:
        """One snapshot-consistent BMU dispatch: (idx, q2, unit_labels).

        The building block under every read endpoint (and the gateway's
        coalesced dispatches): weights and labels come from a single
        snapshot, so the triple is consistent even when a swap lands
        mid-request.
        """
        state, labels = self.snapshot()
        idx, q2 = self._serve(state.w, data)
        return idx, q2, labels

    def transform(self, data, *, lattice: bool = False) -> jnp.ndarray:
        """BMU projection: (B,) flat unit indices, or (B, 2) lattice
        coordinates when ``lattice=True``."""
        idx, q2, labels = self.serve_bmu(data)
        return postprocess(self.cfg.side, "transform", lattice, idx, q2,
                           labels)

    def predict(self, data) -> jnp.ndarray:
        """Classify each sample with its BMU's unit label."""
        # one snapshot: weights and labels are always from the same map
        # version, even when a swap lands mid-request
        idx, q2, labels = self.serve_bmu(data)
        return postprocess(self.cfg.side, "predict", False, idx, q2, labels)

    def quantization_errors(self, data) -> jnp.ndarray:
        """(B,) per-sample Euclidean distance of each sample to its BMU."""
        idx, q2, labels = self.serve_bmu(data)
        return postprocess(self.cfg.side, "quantization_errors", False, idx,
                           q2, labels)

    def quantization_error(self, data) -> float:
        """Mean Euclidean distance of the request batch to its BMUs."""
        return float(jnp.mean(self.quantization_errors(data)))

    def u_matrix(self) -> np.ndarray:
        """(side, side) mean neighbour distance of the served map."""
        state, _ = self.snapshot()
        return metrics.u_matrix(state.w, self.cfg.side)

    def _serve(self, w, data):
        t0 = time.perf_counter()
        with obs.span(obs.ENGINE_BMU):
            idx, q2 = self.engine.bmu(w, data)
            idx = jax.block_until_ready(idx)
        t1 = time.perf_counter()          # span ends before any lock wait
        with self._lock:
            st = self.stats
            st.requests += 1
            st.samples += int(idx.shape[0])
            st.busy_seconds += t1 - t0
            st.window_start = t0 if st.window_start is None else min(
                st.window_start, t0)
            st.window_end = t1 if st.window_end is None else max(
                st.window_end, t1)
        st.latency.record(t1 - t0)
        return idx, q2

    # --------------------------------------------------------- live updates

    def snapshot(self) -> tuple[AFMState, jnp.ndarray | None]:
        """Consistent (state, unit_labels) view of the served map."""
        with self._lock:
            return self._state, self._unit_labels

    def swap(self, state: AFMState, unit_labels=_UNSET) -> None:
        """Atomically replace the served map (and optionally its labels).

        The new state must match the served (n_units, dim) so clients'
        compiled signatures — and the meaning of unit indices — survive
        the swap.
        """
        self._validate_state(self.cfg, state)
        if unit_labels is not _UNSET:
            unit_labels = self._validate_labels(self.cfg, unit_labels)
        with self._update_lock:
            with self._lock:
                self._state = state
                if unit_labels is not _UNSET:
                    self._unit_labels = unit_labels
                self.stats.swaps += 1

    def update(self, batch, *, key: jax.Array | None = None):
        """Hot online update: one ``partial_fit`` training step on the
        served state, swapped in atomically. Returns the step's aux.

        Unit labels are kept as-is (swap new ones in via ``swap`` after
        relabeling offline). Updates are serialised; inference is never
        blocked beyond the final swap.
        """
        batch = jnp.asarray(batch, jnp.float32)
        with self._update_lock:
            if key is None:
                self._next_key, key = jax.random.split(self._next_key)
            backend = self._backend()
            state, _ = self.snapshot()
            new_state, aux = backend.step(backend.from_dense(state), batch,
                                          key)
            self.swap(backend.to_dense(new_state))
            with self._lock:
                self.stats.updates += 1
        return aux

    def _backend(self):
        # re-entrant: update() already holds _update_lock when it calls this
        with self._update_lock:
            if self._update_backend is None:
                from repro.api import backends as backends_lib
                self._update_backend = backends_lib.get_backend(
                    self._update_backend_name, self.cfg,
                    **self._update_backend_options)
            return self._update_backend

    # ------------------------------------------------------------- plumbing

    @property
    def compiles(self) -> int:
        """How many (bucket, map-shape) compiles this service triggered."""
        return self.engine.trace_count

    @staticmethod
    def _validate_state(cfg: AFMConfig, state: AFMState) -> None:
        n = cfg.n_units
        want = {"w": (n, cfg.dim), "c": (n,), "far": (n, cfg.phi),
                "near": (n, 4)}
        for field, shape in want.items():
            got = tuple(getattr(state, field).shape)
            if got != shape:
                raise ValueError(f"state {field} shape {got} does not match "
                                 f"config {shape}")

    @staticmethod
    def _validate_labels(cfg: AFMConfig, unit_labels):
        if unit_labels is None:
            return None
        unit_labels = jnp.asarray(unit_labels, jnp.int32)
        if unit_labels.shape != (cfg.n_units,):
            raise ValueError(f"unit_labels shape {unit_labels.shape} != "
                             f"({cfg.n_units},)")
        return unit_labels

    def __repr__(self):
        labels = self._unit_labels  # lint: unlocked-ok(display-only read)
        served = self.stats.samples  # lint: unlocked-ok(stale ok in repr)
        labelled = "labelled" if labels is not None else "unlabelled"
        return (f"MapService(side={self.cfg.side}, dim={self.cfg.dim}, "
                f"{labelled}, buckets={self.engine.buckets}, "
                f"served={served})")
