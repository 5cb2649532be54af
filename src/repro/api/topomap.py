"""``TopoMap`` — the single front door for training and using an AFM.

The paper's one-algorithm claim, as one estimator: the same ``fit`` /
``transform`` / ``predict`` surface drives every execution backend, from the
faithful single-sample reference to shard_map mesh training (see
``repro.api.backends``). Sklearn-flavoured but jax-native: state is an
immutable ``AFMState`` pytree, all randomness flows from explicit keys.

    from repro.api import TopoMap
    tm = TopoMap(side=10, dim=36).fit(xtr, ytr)
    units = tm.transform(xte)          # BMU projection
    pred = tm.predict(xte)             # majority/nearest unit-label classify
    q = tm.quantization_error(xte)
    tm.save("artifacts/satimage-map")  # versioned artifact; TopoMap.load()

Inference (``transform`` / ``predict`` / ``quantization_error``) runs on the
same bucket-padded jit engine that backs ``repro.serving.maps.MapService``:
ragged request sizes are padded up to a small set of buckets so the hot
path compiles once per bucket, not once per request shape.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api import backends as backends_lib
from repro.core import classifier, metrics
from repro.core.afm import AFMConfig, AFMState


class TopoMap:
    """Topographic-map estimator over pluggable execution backends.

    Args:
      cfg: an ``AFMConfig``; omit to build one from ``**overrides``
           (e.g. ``TopoMap(side=12, dim=36, batch=16)``).
      backend: registry key — any entry of ``available_backends()``
           ('reference', 'batched', 'pallas', 'sharded', 'async', ...).
      backend_options: forwarded to the backend constructor (e.g.
           ``{"mesh": mesh}`` for 'sharded', ``{"interpret": True}`` for
           'pallas', ``{"latency": "exponential", "delay": 0.5}`` for
           'async').
      seed: default PRNG seed when ``fit`` is not given an explicit key.
      labeling: unit-labelling rule for ``predict`` — 'nearest' (Eq. 7) or
           'majority' (vote of the unit's basin, Eq.-7 fallback when empty).

    Fitted attributes: ``state_`` (dense ``AFMState``), ``fit_aux_`` (stacked
    per-step aux), ``unit_labels_`` (when ``fit`` received labels).
    """

    def __init__(self, cfg: AFMConfig | None = None, *,
                 backend: str = "batched",
                 backend_options: dict[str, Any] | None = None,
                 seed: int = 0, labeling: str = "nearest", **overrides):
        if cfg is None:
            cfg = AFMConfig(**overrides)
        elif overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        if labeling not in ("nearest", "majority"):
            raise ValueError(f"labeling must be 'nearest' or 'majority', "
                             f"got {labeling!r}")
        self.cfg = cfg
        self.backend = backends_lib.get_backend(backend, cfg,
                                                **(backend_options or {}))
        self.seed = seed
        self.labeling = labeling
        self.state_: AFMState | None = None
        self.fit_aux_ = None
        self.unit_labels_: jnp.ndarray | None = None
        self._backend_state = None
        self._next_key = None
        self._engine = None

    # ------------------------------------------------------------------ fit

    def fit(self, data, labels=None, *, key: jax.Array | None = None,
            num_steps: int | None = None) -> "TopoMap":
        """Train on (num_samples, D) data (sampled with replacement).

        ``num_steps`` defaults to the config's full sample budget. Passing
        ``labels`` (num_samples,) also labels the units for ``predict``.
        """
        with obs.span(obs.TOPOMAP_FIT):
            data = jnp.asarray(data, jnp.float32)
            key = jax.random.PRNGKey(self.seed) if key is None else key
            k_init, k_run = jax.random.split(key)
            state = self.backend.init(k_init, data)
            with obs.span(obs.BACKEND_RUN):
                state, aux = self.backend.run(state, data, k_run, num_steps)
            self._backend_state = state
            self.fit_aux_ = aux
            self.state_ = self.backend.to_dense(state)
            self._next_key = jax.random.fold_in(key, 0x5eed)
            if labels is not None:
                self.label(data, labels)
        return self

    def partial_fit(self, batch, *, key: jax.Array | None = None) -> "TopoMap":
        """One training step on an explicit (B, D) batch (online usage)."""
        batch = jnp.asarray(batch, jnp.float32)
        if key is None:
            if self._next_key is None:
                self._next_key = jax.random.PRNGKey(self.seed)
            self._next_key, key = jax.random.split(self._next_key)
        if self._backend_state is None:
            k_init, key = jax.random.split(key)
            self._backend_state = self.backend.init(k_init, batch)
        self._backend_state, aux = self.backend.step(self._backend_state,
                                                     batch, key)
        self.fit_aux_ = aux
        self.state_ = self.backend.to_dense(self._backend_state)
        return self

    def label(self, data, labels, num_classes: int | None = None) -> "TopoMap":
        """(Re)label units from a labelled sample set (paper Eq. 7 /
        majority vote, per the ``labeling`` setting)."""
        self._check_fitted()
        with obs.span(obs.TOPOMAP_LABEL):
            data = jnp.asarray(data, jnp.float32)
            labels = jnp.asarray(labels, jnp.int32)
            if self.labeling == "majority":
                self.unit_labels_ = classifier.label_units_majority(
                    self.state_.w, data, labels, num_classes)
            else:
                self.unit_labels_ = classifier.label_units(
                    self.state_.w, data, labels)
        return self

    @classmethod
    def from_state(cls, state: AFMState, cfg: AFMConfig, *,
                   unit_labels=None, **kwargs) -> "TopoMap":
        """Wrap an existing trained dense ``AFMState`` (e.g. an ``AFMProbe``'s
        map) in the estimator surface — transform/predict/metrics work
        immediately, and ``partial_fit`` continues training through the
        chosen backend. Passing ``unit_labels`` (N,) restores a classifier
        map: ``predict`` works without relabeling."""
        tm = cls(cfg, **kwargs)
        tm.state_ = state
        tm._backend_state = tm.backend.from_dense(state)
        if unit_labels is not None:
            tm.unit_labels_ = jnp.asarray(unit_labels, jnp.int32)
        return tm

    # ---------------------------------------------------------- persistence

    def save(self, path: str, *, extra_meta: dict | None = None) -> str:
        """Write the fitted map as a versioned artifact directory (config,
        dense state, unit labels, labeling/backend metadata) — see
        ``repro.api.persistence``. Returns ``path``."""
        self._check_fitted()
        from repro.api import persistence
        return persistence.save_artifact(
            path, cfg=self.cfg, state=self.state_,
            unit_labels=self.unit_labels_, labeling=self.labeling,
            backend=self.backend.name, extra_meta=extra_meta)

    @classmethod
    def load(cls, path: str, *, backend: str | None = None,
             **kwargs) -> "TopoMap":
        """Load a saved artifact back into an estimator.

        The stored backend and labeling are used unless overridden; the
        round-trip is bit-identical on ``transform`` and ``predict``.
        """
        from repro.api import persistence
        art = persistence.load_artifact(path)
        kwargs.setdefault("labeling", art.labeling)
        return cls.from_state(art.state, art.cfg,
                              unit_labels=art.unit_labels,
                              backend=backend or art.backend, **kwargs)

    # ------------------------------------------------------------ inference

    @property
    def engine(self):
        """The bucket-padded jit BMU engine shared with ``MapService``.

        Built lazily from the backend's kernel flags: the pallas backend
        serves through the same kernel path it trains with; flagless
        backends auto-resolve exactly like ``MapService`` (the kernel on
        TPU, the jnp oracle elsewhere), so the two surfaces stay one
        hot path on every platform. Compiled signatures live in the
        process-wide ``repro.serving.maps.CompileCache``: every estimator,
        service, and gateway serving this map shape reuses one compile of
        the bucket ladder instead of compiling per object.
        """
        if self._engine is None:
            from repro.serving import maps as maps_lib
            self._engine = maps_lib.BmuEngine(
                use_pallas=getattr(self.backend, "use_pallas", None),
                interpret=getattr(self.backend, "interpret", None))
        return self._engine

    def _bmu(self, data, cap: int | None = None):
        """The engine's BMU search over the fitted map. A Pallas kernel
        cannot be partitioned, so for the kernel a map spread over several
        devices (the mesh placement's row bands, the sharded backend's
        lattice rows) is searched whole on the lowest-id one of them."""
        w = self.state_.w
        devs = w.sharding.device_set
        if self.engine.use_pallas and len(devs) > 1:
            w = jax.device_put(w, min(devs, key=lambda dev: dev.id))
        return self.engine.bmu(w, jnp.asarray(data, jnp.float32), cap=cap)

    def transform(self, data, *, lattice: bool = False,
                  chunk: int | None = None) -> jnp.ndarray:
        """BMU projection. Returns (B,) flat unit indices, or (B, 2)
        lattice (row, col) coordinates when ``lattice=True``. ``chunk``
        optionally caps the engine's largest bucket (memory ceiling); it is
        clamped to the bucket ladder so no ``chunk`` value can add a jit
        signature or an oversized dispatch."""
        self._check_fitted()
        flat, _ = self._bmu(data, cap=chunk)
        if not lattice:
            return flat
        return jnp.stack([flat // self.cfg.side, flat % self.cfg.side], axis=-1)

    def predict(self, data, chunk: int | None = None) -> jnp.ndarray:
        """Classify each sample with its BMU's unit label."""
        self._check_fitted()
        if self.unit_labels_ is None:
            raise RuntimeError("predict() needs unit labels — fit with "
                               "labels, or call label(data, labels) first")
        data = jnp.asarray(data, jnp.float32)
        return self.unit_labels_[self.transform(data, chunk=chunk)]

    # -------------------------------------------------------------- metrics

    def quantization_error(self, data) -> float:
        """Q: mean Euclidean distance of samples to their BMU weight."""
        self._check_fitted()
        _, q2 = self._bmu(data)
        return float(jnp.mean(jnp.sqrt(q2)))

    def topographic_error(self, data) -> float:
        """T: fraction of samples whose two best units are not adjacent."""
        self._check_fitted()
        return float(metrics.topological_error(
            self.state_.w, jnp.asarray(data, jnp.float32), self.cfg.side))

    def search_error(self, data, *, key: jax.Array | None = None) -> float:
        """F: heuristic-search GMU vs exact BMU disagreement rate."""
        self._check_fitted()
        key = jax.random.PRNGKey(self.seed) if key is None else key
        s = self.state_
        f, _ = metrics.search_error(s.w, s.near, s.far,
                                    jnp.asarray(data, jnp.float32), key,
                                    self.cfg.e)
        return float(f)

    def u_matrix(self) -> np.ndarray:
        """(side, side) mean distance of each unit to its lattice neighbours
        (low = coherent region) — the classic U-matrix view of the map."""
        self._check_fitted()
        return metrics.u_matrix(self.state_.w, self.cfg.side)

    # ------------------------------------------------------------- plumbing

    @property
    def weights_(self) -> jnp.ndarray:
        self._check_fitted()
        return self.state_.w

    def _check_fitted(self):
        if self.state_ is None:
            raise RuntimeError("TopoMap is not fitted yet — call fit() or "
                               "partial_fit() first")

    def __repr__(self):
        fitted = "fitted" if self.state_ is not None else "unfitted"
        return (f"TopoMap(side={self.cfg.side}, dim={self.cfg.dim}, "
                f"backend={self.backend.name!r}, {fitted})")
