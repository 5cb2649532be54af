"""Topographic-map training launcher — the ``TopoMap`` estimator as a CLI.

Trains an AFM on any Table-1 dataset through any registered backend and
reports map quality + classification metrics:

    PYTHONPATH=src python -m repro.launch.train_map --dataset satimage \
        --side 10 --backend batched

    # mesh training (rows over 'model', samples over 'data'); on CPU give
    # XLA virtual devices first: XLA_FLAGS=--xla_force_host_platform_device_count=8
    PYTHONPATH=src python -m repro.launch.train_map --dataset satimage \
        --backend sharded --mesh 2x4

    # Pallas kernels in interpreter mode (slow; CPU validation):
    PYTHONPATH=src python -m repro.launch.train_map --dataset letters \
        --backend pallas --interpret

    # event-driven asynchronous training (zero latency == reference bitwise;
    # nonzero delay lets cascades overlap and broadcasts go stale):
    PYTHONPATH=src python -m repro.launch.train_map --dataset satimage \
        --backend async --latency exponential --delay 0.5

    # the same event engine partitioned over a device mesh (row bands of
    # the lattice, per-shard pools, batched halo exchange):
    PYTHONPATH=src python -m repro.launch.train_map --dataset satimage \
        --backend async --shards 2

    # persist the fitted map for repro.launch.serve_map:
    PYTHONPATH=src python -m repro.launch.train_map --dataset satimage \
        --save-artifact /tmp/satimage-map           # one artifact dir
    PYTHONPATH=src python -m repro.launch.train_map --dataset satimage \
        --store /tmp/maps                           # versioned MapStore entry
"""
from __future__ import annotations

import argparse
import time

import jax

from repro import compile_cache
from repro.api import AFMConfig, TopoMap, precision_recall
from repro.api.backends import add_backend_argument
from repro.data import DATASETS, make_dataset


def build_backend_options(args) -> dict:
    opts: dict = {}
    if args.backend == "sharded":
        if args.search:
            raise SystemExit("--search is not supported by the sharded "
                             "backend (it uses mesh probe-and-reduce search)")
        if args.interpret:
            raise SystemExit("--interpret only applies to the pallas backend")
        from repro.sharding import compat
        try:
            n_data, n_model = (int(x) for x in args.mesh.split("x"))
        except ValueError:
            raise SystemExit(
                f"--mesh must be 'DATAxMODEL' (e.g. 2x4), got {args.mesh!r}")
        opts["mesh"] = compat.make_mesh((n_data, n_model), ("data", "model"))
        return opts
    if args.interpret:
        if args.backend != "pallas":
            raise SystemExit("--interpret only applies to the pallas backend")
        opts.update(interpret=True, use_pallas=True)
    if args.backend == "async":
        opts.update(latency=args.latency, delay=args.delay,
                    lat_seed=args.lat_seed)
        if args.shards > 1:
            opts.update(placement="mesh", shards=args.shards)
    elif args.latency != "zero" or args.delay or args.lat_seed:
        raise SystemExit("--latency/--delay/--lat-seed only apply to the "
                         "async backend")
    elif args.shards > 1:
        raise SystemExit("--shards only applies to the async backend "
                         "(sharded uses --mesh)")
    if args.search:
        opts["search"] = args.search
    return opts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="satimage", choices=sorted(DATASETS))
    add_backend_argument(ap, default="batched")
    ap.add_argument("--side", type=int, default=10)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--e-factor", type=float, default=1.0)
    ap.add_argument("--i-max", type=int, default=0,
                    help="total samples (0 -> 40N reduced budget; paper: 600N)")
    ap.add_argument("--c-d", type=float, default=100.0)
    ap.add_argument("--train-size", type=int, default=3000)
    ap.add_argument("--test-size", type=int, default=600)
    ap.add_argument("--mesh", default="1x1",
                    help="sharded backend mesh, 'DATAxMODEL' (e.g. 2x4)")
    ap.add_argument("--interpret", action="store_true",
                    help="pallas backend: run kernels in interpreter mode")
    ap.add_argument("--latency", default="zero",
                    choices=("zero", "constant", "exponential"),
                    help="async backend: message latency model")
    ap.add_argument("--delay", type=float, default=0.0,
                    help="async backend: latency scale in sample periods")
    ap.add_argument("--lat-seed", type=int, default=0,
                    help="async backend: seed of the exponential-latency "
                         "stream (independent of --seed)")
    ap.add_argument("--shards", type=int, default=1,
                    help="async backend: partition the event engine over "
                         "this many devices (placement='mesh'; must divide "
                         "--side; on CPU export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=K first)")
    ap.add_argument("--search", default=None,
                    choices=(None, "heuristic", "exact"),
                    help="override the backend's search stage")
    ap.add_argument("--labeling", default="nearest",
                    choices=("nearest", "majority"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--save-artifact", default=None,
                    help="write the fitted map to this artifact directory")
    ap.add_argument("--store", default=None,
                    help="register the fitted map in this MapStore root")
    ap.add_argument("--name", default=None,
                    help="store key name (default: DATASET-SIDExSIDE)")
    args = ap.parse_args()
    compile_cache.enable()

    spec = DATASETS[args.dataset]
    xtr, ytr, xte, yte = make_dataset(
        args.dataset, train_size=min(spec.train, args.train_size),
        test_size=min(spec.test, args.test_size))

    n = args.side * args.side
    cfg = AFMConfig(side=args.side, dim=spec.features, batch=args.batch,
                    e_factor=args.e_factor, c_d=args.c_d,
                    i_max=args.i_max or 40 * n)
    tm = TopoMap(cfg, backend=args.backend,
                 backend_options=build_backend_options(args),
                 seed=args.seed, labeling=args.labeling)
    # the backend may rewrite the config (reference forces batch=1)
    print(f"dataset={args.dataset} map={args.side}x{args.side} "
          f"backend={tm.backend.name} steps={tm.backend.cfg.num_steps} "
          f"devices={len(jax.devices())}")

    t0 = time.time()
    tm.fit(xtr, ytr, key=jax.random.PRNGKey(args.seed))
    dt = time.time() - t0
    rate = cfg.total_samples / dt
    print(f"trained {cfg.total_samples} samples in {dt:.1f}s "
          f"({rate:.0f} samples/s); largest cascade "
          f"a_i = {int(tm.fit_aux_.cascade_size.max())}")

    print(f"quantization error  Q: {tm.quantization_error(xte):.4f}")
    print(f"topological error   T: {tm.topographic_error(xte):.4f}")
    # eval stream derived from (not equal to) the training seed's key
    eval_key = jax.random.fold_in(jax.random.PRNGKey(args.seed), 1)
    print(f"search error        F: "
          f"{tm.search_error(xte[:256], key=eval_key):.4f}")
    pred = tm.predict(xte)
    acc = float((pred == yte).mean())
    prec, rec = precision_recall(pred, yte, spec.classes)
    print(f"classification: acc={acc:.3f} precision={float(prec):.3f} "
          f"recall={float(rec):.3f} (chance={1.0 / spec.classes:.3f})")

    meta = {"dataset": args.dataset, "accuracy": acc}
    if args.save_artifact:
        tm.save(args.save_artifact, extra_meta=meta)
        print(f"saved artifact -> {args.save_artifact}")
    if args.store:
        from repro.api import MapStore
        name = args.name or f"{args.dataset}-{args.side}x{args.side}"
        spec_key = MapStore(args.store).save(tm, name, extra_meta=meta)
        print(f"saved to store {args.store} as {spec_key}")


if __name__ == "__main__":
    main()
