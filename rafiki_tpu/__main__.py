"""CLI: run a rafiki-tpu platform node.

Parity: SURVEY.md §2 "Ops scripts" — the upstream ``scripts/start.sh``
brings up Postgres/Redis/Admin/Web containers; the TPU rebuild's resident-
runner deployment (one process owns the host's chips, SURVEY.md §7) makes
that a single long-running process:

    python -m rafiki_tpu serve --workdir /var/rafiki --port 3000

which serves the Admin REST API + web dashboard and executes train /
inference services in-process on chip groups. ``scripts/start.sh`` /
``stop.sh`` wrap this with pid/log management, and the dockerfiles run the
same command as a container entrypoint.
"""

from __future__ import annotations

import argparse
import logging
import signal
import sys
import threading


def _serve(args: argparse.Namespace) -> None:
    # One validated config object per node (SURVEY.md §5 config plan):
    # CLI args override RAFIKI_TPU_* env vars override defaults; the
    # resolved tunables are exported back to env so workers (threads or
    # subprocess services) inherit exactly what was validated.
    from .config import NodeConfig

    cfg = NodeConfig.from_env(
        workdir=args.workdir, port=args.port, n_chips=args.chips,
        bus_uri=args.bus, log_level=args.log_level,
        coordinator=args.coordinator or None,
        num_processes=args.num_processes, process_id=args.process_id)
    cfg.apply_env()
    logging.basicConfig(
        level=getattr(logging, cfg.log_level.upper()),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")

    # Multi-host slice membership (SURVEY.md §2.10): every host of a pod
    # slice runs serve with the same coordinator address; JAX wires the
    # ICI/DCN topology and jax.devices() becomes the global device list,
    # which the chip allocator then partitions into per-trial groups.
    # Must precede the first backend touch, i.e. ensure_platform.
    if cfg.coordinator:
        import jax

        jax.distributed.initialize(
            coordinator_address=cfg.coordinator,
            num_processes=cfg.num_processes,
            process_id=cfg.process_id)

    # Resolve the JAX platform: JAX_PLATFORMS=cpu pins the CPU; anything
    # else must find a TPU or the node refuses to start.
    from .jaxenv import ensure_platform
    print(f"rafiki-tpu platform: {ensure_platform()}", flush=True)

    from .platform import LocalPlatform
    platform = LocalPlatform.from_config(cfg, http=True)
    app = platform.app
    print(f"rafiki-tpu admin on http://{app.host}:{app.port} "
          f"(workdir={platform.workdir})", flush=True)

    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    try:
        stop.wait()
    finally:
        print("shutting down...", flush=True)
        platform.shutdown()


def _join(args: argparse.Namespace) -> None:
    """Worker node: attach elastic capacity to a running train job.

    Shares the primary node's meta store (``--workdir`` on a shared
    filesystem), params dir and TCP bus; its workers pull proposals
    from the job's existing advisor so the search stays one search
    (SURVEY.md §2.10 multi-host plan).
    """
    logging.basicConfig(
        level=getattr(logging, args.log_level.upper()),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")
    if not args.bus:
        raise SystemExit("join needs --bus tcp://host:port (the primary "
                         "node's broker) — an in-process bus cannot span "
                         "nodes")

    from .jaxenv import ensure_platform
    print(f"rafiki-tpu platform: {ensure_platform()}", flush=True)

    from .platform import LocalPlatform

    # A join node shares the primary's workdir, so it needs its OWN
    # node identity (the workdir-stable default would collide with the
    # primary's); shutdown stops this node's services either way, so a
    # departing joiner leaves no RUNNING rows behind.
    import os
    import socket

    platform = LocalPlatform(workdir=args.workdir, http=False,
                             n_chips=args.chips, bus_uri=args.bus,
                             stop_jobs_on_shutdown=False,
                             node_id=f"{socket.gethostname()}"
                                     f"/join-{os.getpid()}",
                             adopt_unowned=False)
    try:
        if args.train_job:
            attached = platform.admin.attach_workers(
                args.train_job, chips_per_trial=args.chips_per_trial)
            if not attached:
                raise SystemExit("no chips available on this node")
            print(f"attached {len(attached)} worker(s) to "
                  f"{args.train_job}", flush=True)
            ok = platform.admin.wait_until_train_job_done(
                args.train_job, timeout=args.timeout)
            print("train job done" if ok else "timed out waiting",
                  flush=True)
            if not ok:
                raise SystemExit(1)
        else:
            # Serving replicas: extra copies of the served trial bins
            # on this node; the Predictor round-robins across them.
            attached = platform.admin.attach_inference_workers(
                args.inference_job,
                chips_per_worker=args.chips_per_trial)
            if not attached:
                raise SystemExit("no chips available on this node")
            print(f"attached {len(attached)} replica worker(s) to "
                  f"{args.inference_job}", flush=True)
            import time

            deadline = time.monotonic() + args.timeout
            while time.monotonic() < deadline:
                job = platform.meta.get_inference_job(args.inference_job)
                if job is None or job["status"] != "RUNNING":
                    print("inference job stopped", flush=True)
                    break
                time.sleep(2.0)
            else:
                # Leaving on timeout tears this node's replicas down
                # mid-serve — be loud about it.
                print("timed out while the inference job is still "
                      "RUNNING; withdrawing this node's replicas",
                      flush=True)
                raise SystemExit(1)
    finally:
        platform.shutdown()


def _broker(args: argparse.Namespace) -> None:
    from .bus import NativeBusServer, serve_broker

    server = serve_broker(args.host, args.port,
                          native=False if args.python else None)
    kind = type(server).__name__
    print(f"bus broker ({kind}) on {server.uri}", flush=True)
    try:
        if isinstance(server, NativeBusServer):
            server.serve_forever()  # raises if the child broker crashes
        else:
            # The Python BusServer already serves on its own daemon
            # thread; a second serve_forever loop would fight it over
            # socketserver's shutdown state — just block.
            threading.Event().wait()
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(prog="rafiki_tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)

    # Defaults are None = "not given on the CLI": NodeConfig.from_env
    # then falls through to RAFIKI_TPU_* env vars, then its dataclass
    # defaults (CLI > env > default precedence).
    serve = sub.add_parser("serve", help="run an Admin + worker node")
    serve.add_argument("--workdir", default=None,
                       help="state directory (sqlite meta + params)")
    serve.add_argument("--port", type=int, default=None)
    serve.add_argument("--chips", type=int, default=None,
                       help="limit to the first N chips (default: all)")
    serve.add_argument("--bus", default=None,
                       help="bus URI ('' = in-process; 'tcp://host:port')")
    serve.add_argument("--log-level", default=None)
    serve.add_argument("--coordinator", default=None,
                       help="jax.distributed coordinator host:port "
                            "(multi-host slices; empty = single host)")
    serve.add_argument("--num-processes", type=int, default=None,
                       help="total serve processes in the slice")
    serve.add_argument("--process-id", type=int, default=None,
                       help="this process's rank in the slice")
    serve.set_defaults(fn=_serve)

    join = sub.add_parser(
        "join", help="attach this node's chips to a running train job "
                     "(shared workdir + tcp bus)")
    join.add_argument("--workdir", required=True,
                      help="the PRIMARY node's state directory "
                           "(shared filesystem)")
    join.add_argument("--bus", required=True,
                      help="primary node's bus URI (tcp://host:port)")
    join.add_argument("--train-job", default=None,
                      help="attach train workers to this RUNNING job")
    join.add_argument("--inference-job", default=None,
                      help="attach serving REPLICA workers to this "
                           "RUNNING inference job")
    join.add_argument("--chips", type=int, default=None,
                      help="limit to the first N local chips")
    join.add_argument("--chips-per-trial", type=int, default=1)
    join.add_argument("--timeout", type=float, default=3600.0)
    join.add_argument("--log-level", default="info")
    join.set_defaults(fn=_join)

    broker = sub.add_parser(
        "broker", help="run a standalone bus broker (multi-process / "
                       "multi-host deployments point --bus at it)")
    broker.add_argument("--host", default="127.0.0.1")
    broker.add_argument("--port", type=int, default=6380)
    broker.add_argument("--python", action="store_true",
                        help="force the Python broker (default: the C++ "
                             "broker when a toolchain exists)")
    broker.set_defaults(fn=_broker)

    args = parser.parse_args(argv)
    if args.cmd == "join":
        if bool(args.train_job) == bool(args.inference_job):
            parser.error("give exactly one of --train-job / "
                         "--inference-job")
    if args.cmd == "serve":
        n_set = sum([args.coordinator is not None,
                     args.num_processes is not None,
                     args.process_id is not None])
        if n_set not in (0, 3):
            parser.error(
                "--coordinator, --num-processes and --process-id must be "
                "given together (all three, or none)")
    args.fn(args)


if __name__ == "__main__":
    main(sys.argv[1:])
