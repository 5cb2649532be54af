"""Continuous train-and-serve loop — a map that learns online while serving.

The trainer consumes a sample stream (any registered backend; the
event-driven ``async`` backend by default) and periodically publishes its
dense state into the serving stack, while client threads keep reading
through a ``MapGateway``. Publication reuses the PR-3 atomic swap paths, so
readers never observe a torn map:

- **in-memory** (default): ``MapService.swap`` on the attached service —
  in-flight requests finish on the old weights, compiled signatures
  survive, zero disk traffic;
- **store-backed** (``--store``): each publication saves a new artifact
  version and calls ``MapGateway.reload`` — the same hot-reload a separate
  serving process would use, so the loop doubles as an integration test of
  the store/reload path.

    PYTHONPATH=src python -m repro.launch.stream_train --dataset satimage \
        --side 6 --events 1024 --swap-every 256 --clients 2

    # store-backed publication (artifact version per swap + gateway reload)
    PYTHONPATH=src python -m repro.launch.stream_train --dataset satimage \
        --side 6 --events 1024 --store /tmp/stream-maps

The run reports training-event throughput, swap count, client request
count, and the final per-sample quantization error of the served map —
``qe ... finite=True`` is the line CI's smoke step asserts on.

**Crash resume** (ISSUE 10): with ``--checkpoint-dir`` the trainer writes a
``TrainCheckpoint`` (dense state + latency-key position + sample cursor,
SHA-256-manifested) every ``--checkpoint-every`` consumed samples, and a
SIGTERM checkpoints once more and stops cleanly (``--die-after N`` raises
that SIGTERM from inside the loop for deterministic kill tests). Rerunning
with ``--resume`` verifies the checkpoint's checksums ("checkpoint checksum
verified" is CI's assert line), restores state/keys/cursor, and continues —
because per-chunk training keys are step-indexed (``fold_in(seed, step)``)
and the latency chain position is saved, the resumed run reproduces the
uninterrupted run **bitwise** at zero message latency.
"""
from __future__ import annotations

import argparse
import dataclasses
import signal
import threading
import time

import jax
import numpy as np

from repro import compile_cache
from repro.api import AFMConfig, MapStore, TopoMap
from repro.api.backends import add_backend_argument
from repro.api.persistence import _state_like
from repro.data import DATASETS, make_dataset
from repro.serving import GatewayStats, MapGateway, MapService
from repro.training.checkpoint import (load_train_checkpoint,
                                       save_train_checkpoint)


@dataclasses.dataclass
class StreamReport:
    """Outcome of one ``run_stream`` — returned to callers and printed by
    the CLI (tests assert on it directly)."""
    events: int                 # training samples consumed
    seconds: float              # trainer wall time
    swaps: int                  # publications into the serving stack
    client_requests: int        # gateway reads served during training
    client_errors: list         # exceptions raised in client threads
    qe: np.ndarray              # final per-sample quantization errors
    gateway: GatewayStats
    interrupted: bool = False   # stopped early on SIGTERM / --die-after
    checkpoint_path: str | None = None   # last checkpoint written (if any)
    resumed_from: dict | None = None     # resumed cursor (if --resume hit)

    @property
    def events_per_sec(self) -> float:
        return self.events / self.seconds if self.seconds > 0 else 0.0

    @property
    def qe_finite(self) -> bool:
        return bool(np.isfinite(self.qe).all())


def run_stream(cfg: AFMConfig, train_data, eval_data, *,
               backend: str = "async", backend_options: dict | None = None,
               events: int = 1024, chunk: int = 64, swap_every: int = 256,
               clients: int = 2, client_batch: int = 8,
               store_root: str | None = None, name: str = "stream",
               max_delay: float = 0.001, seed: int = 0,
               min_client_reads: int = 1,
               checkpoint_dir: str | None = None, checkpoint_every: int = 0,
               resume: bool = False, die_after: int | None = None,
               log=None) -> StreamReport:
    """Train on ``events`` samples while serving concurrent gateway reads.

    The stream is ``train_data`` cycled in ``chunk``-sized
    ``partial_fit`` steps; every ``swap_every`` consumed samples the
    trainer publishes its state (see module docstring for the two
    publication paths). ``clients`` reader threads issue
    ``client_batch``-sized ``quantization_errors`` requests against the
    gateway for the whole duration — the concurrency that makes this a
    torn-read test, not just a loop. A fast trainer can finish before a
    client completes its first (compile-paying) read, so the loop keeps
    serving until at least ``min_client_reads`` requests landed (bounded
    wait) — the report always reflects genuine train/serve overlap.

    ``checkpoint_dir`` turns on crash resume: a ``TrainCheckpoint`` lands
    there every ``checkpoint_every`` consumed samples (default
    ``swap_every``) and once more on SIGTERM. Checkpoints are cut at chunk
    boundaries, where the event engine is drained to quiescence — the dense
    state plus the latency-key position plus the cursor is the complete
    in-flight state, which is what makes ``resume=True`` bitwise-faithful
    (per-chunk keys are step-indexed, so the resumed run consumes the
    identical PRNG streams the uninterrupted run would have).
    ``die_after=N`` raises SIGTERM from inside the loop once N samples are
    consumed — the deterministic stand-in for an external kill.
    """
    log = log or (lambda *_: None)
    train_data = np.asarray(train_data, np.float32)
    eval_data = np.asarray(eval_data, np.float32)
    chunk = max(1, min(chunk, events))
    if checkpoint_dir and checkpoint_every <= 0:
        checkpoint_every = swap_every
    if (resume or die_after is not None) and not checkpoint_dir:
        raise ValueError("resume/die_after need checkpoint_dir set")

    # SIGTERM lands as a graceful stop flag checked at chunk boundaries;
    # the previous handler is restored on exit. Off the main thread (or
    # under a non-default handler policy) --die-after falls back to setting
    # the flag directly.
    interrupt = threading.Event()
    prev_handler = None
    handler_installed = False
    if checkpoint_dir and threading.current_thread() is threading.main_thread():
        prev_handler = signal.signal(signal.SIGTERM,
                                     lambda *_: interrupt.set())
        handler_installed = True

    resumed_from = None
    consumed = 0
    cursor = {"pos": 0, "step": 1, "since_swap": 0, "swaps": 0}
    if resume:
        tc = load_train_checkpoint(checkpoint_dir,
                                   state_like=_state_like(cfg),
                                   expect_config=dataclasses.asdict(cfg))
        tm = TopoMap.from_state(tc.state, cfg, backend=backend,
                                backend_options=dict(backend_options or {}),
                                seed=seed)
        if tc.lat_key is not None and hasattr(tm.backend, "lat_key"):
            tm.backend.lat_key = tc.lat_key
        consumed = int(tc.cursor.get("consumed", 0))
        cursor = {k: int(tc.cursor.get(k, cursor[k])) for k in cursor}
        resumed_from = dict(tc.cursor)
        log(f"resume: checkpoint checksum verified — continuing at event "
            f"{consumed} (step {cursor['step']}, "
            f"{len(tc.checksums)} payload files)")
    else:
        tm = TopoMap(cfg, backend=backend,
                     backend_options=dict(backend_options or {}), seed=seed)
        # warm start: the serving stack needs a fitted state to open with
        first = train_data[:chunk]
        tm.partial_fit(first,
                       key=jax.random.fold_in(jax.random.PRNGKey(seed), 0))
        consumed += len(first)

    last_ckpt = consumed
    checkpoint_path = None

    def save_ckpt() -> None:
        nonlocal last_ckpt, checkpoint_path
        cur = {"consumed": consumed, **cursor}
        save_train_checkpoint(
            checkpoint_dir, config=dataclasses.asdict(cfg),
            state=jax.tree.map(np.asarray, tm.state_), cursor=cur,
            lat_key=getattr(tm.backend, "lat_key", None),
            meta={"name": name, "events_target": events, "seed": seed})
        last_ckpt = consumed
        checkpoint_path = checkpoint_dir
        log(f"  checkpoint at {consumed} events -> {checkpoint_dir}")

    store = MapStore(store_root) if store_root else None
    svc = None
    if store is not None:
        store.save(tm, name)
        gw = MapGateway(store=store, max_delay=max_delay)
        gw.open(name)
    else:
        gw = MapGateway(max_delay=max_delay)
        svc = MapService.from_estimator(tm)
        gw.attach(name, svc)

    stop = threading.Event()
    requests = [0] * max(clients, 1)
    errors: list = []

    def client(worker: int):
        rng = np.random.default_rng(seed + 1 + worker)
        try:
            while not stop.is_set():
                lo = int(rng.integers(0, max(1, len(eval_data) - client_batch)))
                q = gw.quantization_errors(name, eval_data[lo:lo + client_batch])
                if not np.isfinite(q).all():
                    raise AssertionError(f"non-finite QE from client {worker}")
                requests[worker] += 1
        except BaseException as e:  # noqa: BLE001 — reported to the caller
            errors.append(e)

    threads = [threading.Thread(target=client, args=(w,), daemon=True)
               for w in range(clients)]

    def publish() -> None:
        if store is not None:
            store.save(tm, name)
            gw.reload(name)
        else:
            svc.swap(tm.state_)

    interrupted = False
    t0 = time.perf_counter()
    try:
        for t in threads:
            t.start()
        if not resume:
            cursor["pos"] = consumed % len(train_data)
            cursor["since_swap"] = consumed
        while consumed < events:
            take = min(chunk, events - consumed)
            batch = np.take(train_data,
                            range(cursor["pos"], cursor["pos"] + take),
                            axis=0, mode="wrap")
            cursor["pos"] = (cursor["pos"] + take) % len(train_data)
            tm.partial_fit(batch, key=jax.random.fold_in(
                jax.random.PRNGKey(seed), cursor["step"]))
            consumed += take
            cursor["since_swap"] += take
            cursor["step"] += 1
            if cursor["since_swap"] >= swap_every:
                publish()
                cursor["swaps"] += 1
                cursor["since_swap"] = 0
                log(f"  published after {consumed} events "
                    f"(swap {cursor['swaps']}, {sum(requests)} reads "
                    f"served)")
            if checkpoint_dir and consumed - last_ckpt >= checkpoint_every:
                save_ckpt()
            if die_after is not None and consumed >= die_after:
                die_after = None        # deliver the kill exactly once
                if handler_installed:   # exercise the real signal path
                    signal.raise_signal(signal.SIGTERM)
                else:
                    interrupt.set()
            if interrupt.is_set():
                interrupted = True
                save_ckpt()             # the state the resume picks up
                log(f"  interrupted at {consumed} events — checkpoint "
                    f"saved, resume with --resume")
                break
        if not interrupted and cursor["since_swap"]:
            publish()                   # final state always reaches serving
            cursor["swaps"] += 1
        seconds = time.perf_counter() - t0
        if clients > 0 and not interrupted:
            deadline = time.perf_counter() + 30.0
            while (sum(requests) < min_client_reads and not errors
                   and time.perf_counter() < deadline):
                time.sleep(0.002)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        # the served map answers the final QE — reads go through the same
        # gateway the clients used, against the just-published state
        qe = np.asarray(gw.quantization_errors(name, eval_data))
        stats = dataclasses.replace(gw.stats)
    finally:
        stop.set()
        gw.close()
        if handler_installed:
            signal.signal(signal.SIGTERM, prev_handler or signal.SIG_DFL)
    return StreamReport(events=consumed, seconds=seconds,
                        swaps=cursor["swaps"],
                        client_requests=sum(requests), client_errors=errors,
                        qe=qe, gateway=stats, interrupted=interrupted,
                        checkpoint_path=checkpoint_path,
                        resumed_from=resumed_from)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="satimage", choices=sorted(DATASETS))
    add_backend_argument(ap, default="async")
    ap.add_argument("--side", type=int, default=6)
    ap.add_argument("--events", type=int, default=1024,
                    help="total training samples to stream")
    ap.add_argument("--chunk", type=int, default=64,
                    help="samples per partial_fit step")
    ap.add_argument("--swap-every", type=int, default=256,
                    help="publish the map into serving every N samples")
    ap.add_argument("--clients", type=int, default=2,
                    help="concurrent gateway reader threads")
    ap.add_argument("--client-batch", type=int, default=8)
    ap.add_argument("--store", default=None,
                    help="MapStore root: publish as artifact versions + "
                         "gateway reload (default: in-memory atomic swap)")
    ap.add_argument("--name", default=None,
                    help="served map name (default: DATASET-SIDExSIDE)")
    ap.add_argument("--latency", default="zero",
                    choices=("zero", "constant", "exponential"),
                    help="async backend: message latency model")
    ap.add_argument("--delay", type=float, default=0.0,
                    help="async backend: latency scale (sample periods)")
    ap.add_argument("--lat-seed", type=int, default=0,
                    help="async backend: seed of the exponential-latency "
                         "stream (independent of --seed)")
    ap.add_argument("--engine", default="auto", choices=("auto", "event"),
                    help="async backend: 'auto' fuses zero-latency chunks "
                         "into the reference scan, 'event' always runs the "
                         "discrete-event simulation")
    ap.add_argument("--shards", type=int, default=1,
                    help="async backend: partition the event engine over "
                         "this many devices (placement='mesh'; must divide "
                         "--side)")
    ap.add_argument("--search", default=None,
                    choices=(None, "heuristic", "exact"))
    ap.add_argument("--checkpoint-dir", default=None,
                    help="write crash-resume TrainCheckpoints here (every "
                         "--checkpoint-every samples and on SIGTERM)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="samples between checkpoints (default: "
                         "--swap-every)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from --checkpoint-dir (verifies checksums; "
                         "bitwise-faithful at zero latency)")
    ap.add_argument("--die-after", type=int, default=None,
                    help="raise SIGTERM after consuming N samples "
                         "(deterministic kill for resume tests)")
    ap.add_argument("--p-loss", type=float, default=0.0,
                    help="async backend: fault injection — broadcast loss "
                         "probability per message")
    ap.add_argument("--dropout-frac", type=float, default=0.0,
                    help="async backend: fault injection — fraction of "
                         "units dead during the dropout window")
    ap.add_argument("--dropout-start", type=float, default=0.0)
    ap.add_argument("--dropout-len", type=float, default=0.0)
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the fault plan's own PRNG stream")
    ap.add_argument("--e-factor", type=float, default=0.5)
    ap.add_argument("--train-size", type=int, default=2000)
    ap.add_argument("--eval-size", type=int, default=256)
    ap.add_argument("--coalesce-ms", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    spec = DATASETS[args.dataset]
    xtr, _, xte, _ = make_dataset(args.dataset,
                                  train_size=min(spec.train, args.train_size),
                                  test_size=min(spec.test, args.eval_size))
    cfg = AFMConfig(side=args.side, dim=spec.features,
                    e_factor=args.e_factor, i_max=args.events)
    faults = None
    if args.p_loss or (args.dropout_frac and args.dropout_len):
        faults = {"seed": args.fault_seed, "p_loss": args.p_loss,
                  "dropout_frac": args.dropout_frac,
                  "dropout_start": args.dropout_start,
                  "dropout_len": args.dropout_len}
    opts: dict = {}
    if args.backend == "async":
        opts.update(latency=args.latency, delay=args.delay,
                    engine=args.engine, lat_seed=args.lat_seed)
        if args.shards > 1:
            opts.update(placement="mesh", shards=args.shards)
        if faults:
            opts["faults"] = faults
    elif (args.latency != "zero" or args.delay or args.engine != "auto"
          or args.lat_seed or args.shards > 1 or faults):
        raise SystemExit("--latency/--delay/--engine/--lat-seed/--shards/"
                         "--p-loss/--dropout-* only apply to the async "
                         "backend")
    if args.search:
        if args.backend == "sharded":
            raise SystemExit("--search is not supported by the sharded "
                             "backend")
        opts["search"] = args.search
    name = args.name or f"{args.dataset}-{args.side}x{args.side}"

    print(f"streaming {args.events} events into a {args.side}x{args.side} "
          f"map (backend={args.backend}, latency={args.latency}), serving "
          f"{args.clients} clients, publish every {args.swap_every}")
    rep = run_stream(cfg, xtr, xte, backend=args.backend,
                     backend_options=opts, events=args.events,
                     chunk=args.chunk, swap_every=args.swap_every,
                     clients=args.clients, client_batch=args.client_batch,
                     store_root=args.store, name=name,
                     max_delay=args.coalesce_ms / 1000.0, seed=args.seed,
                     checkpoint_dir=args.checkpoint_dir,
                     checkpoint_every=args.checkpoint_every,
                     resume=args.resume, die_after=args.die_after,
                     log=print)
    if rep.interrupted:
        print(f"stream interrupted at {rep.events} events — checkpoint "
              f"saved to {rep.checkpoint_path}; rerun with --resume to "
              f"continue")
    print(f"stream: trained {rep.events} events in {rep.seconds:.2f}s "
          f"({rep.events_per_sec:.0f} events/s), {rep.swaps} swaps, "
          f"{rep.client_requests} client reads "
          f"({rep.gateway.dispatches} coalesced dispatches)")
    print(f"stream qe: mean={float(rep.qe.mean()):.4f} over {len(rep.qe)} "
          f"samples, finite={rep.qe_finite}")
    if rep.client_errors:
        raise SystemExit(f"client errors: {rep.client_errors!r}")


if __name__ == "__main__":
    main()
