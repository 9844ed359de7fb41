"""Coordinator daemon CLI — the front-end process the reference README
describes (``README.md:56-60``) but never shipped.

    python -m distributed_inference_engine_tpu.cli.coordinator \
        --host 0.0.0.0 --port 8000 \
        --worker w0=10.0.0.1:9000 --worker w1=10.0.0.2:9000 \
        --deploy name=tiny,architecture=llama,size=llama-tiny

Workers can also be added at runtime via the ``add_worker`` RPC
(``CoordinatorClient.add_worker``); ``--config`` loads the full tree.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
from typing import List, Tuple

from ..api.coordinator import Coordinator, CoordinatorConfig
from ..api.frontend import CoordinatorServer
from ..config import ServerConfig, load_config
from .worker import parse_model_arg


def parse_worker_arg(text: str) -> Tuple[str, str, int]:
    """``w0=10.0.0.1:9000`` → (id, host, port)."""
    if "=" not in text or ":" not in text.split("=", 1)[1]:
        raise ValueError(f"worker spec {text!r} is not id=host:port")
    wid, addr = text.split("=", 1)
    host, port = addr.rsplit(":", 1)
    return wid.strip(), host.strip(), int(port)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="distributed_inference_engine_tpu.cli.coordinator",
        description="serving coordinator (cache -> batcher -> router/LB -> workers)",
    )
    p.add_argument("--host", default=None,
                   help="bind host (default 127.0.0.1; overrides --config)")
    p.add_argument("--port", type=int, default=None,
                   help="bind port (default 0 = OS-assigned; overrides "
                        "--config)")
    p.add_argument("--worker", action="append", default=[],
                   metavar="ID=HOST:PORT", help="worker to register (repeatable)")
    p.add_argument("--deploy", action="append", default=[],
                   metavar="K=V[,K=V...]",
                   help="model to deploy across workers at startup (repeatable)")
    p.add_argument("--config", default="", help="config file (.json/.toml/.yaml)")
    p.add_argument("--lb-strategy", default="round_robin",
                   choices=["round_robin", "least_connections", "random",
                            "least_latency"])
    p.add_argument("--state", default="",
                   help="state snapshot file: restored (with redeploy) at "
                        "startup if present, saved after deploys and on "
                        "shutdown")
    p.add_argument("--log-level", default="INFO")
    return p


async def amain(args: argparse.Namespace) -> None:
    if args.config:
        tree = load_config(args.config)
        ccfg = CoordinatorConfig.from_config(tree)
        ccfg.lb_strategy = args.lb_strategy   # flag applies in config mode too
        # explicit --host/--port beat the file (lets one committed config
        # serve both the pinned-port demo and port-0 test harnesses)
        server_cfg = ServerConfig(
            worker_id="coordinator",
            host=args.host if args.host is not None else tree.server.host,
            port=args.port if args.port is not None else tree.server.port)
        deploys = tree.models + [parse_model_arg(m) for m in args.deploy]
    else:
        ccfg = CoordinatorConfig(lb_strategy=args.lb_strategy)
        server_cfg = ServerConfig(worker_id="coordinator",
                                  host=args.host or "127.0.0.1",
                                  port=args.port or 0)
        deploys = [parse_model_arg(m) for m in args.deploy]

    coord = Coordinator(ccfg)
    server = CoordinatorServer(coord, server_cfg)
    # register + deploy BEFORE announcing the address — the "listening" line
    # is the readiness signal (same convention as cli/worker.py), so a script
    # that waits on it can generate immediately
    await coord.start()
    import os

    if args.state and os.path.isfile(args.state):
        try:
            n = await coord.restore_state(args.state, redeploy=True)
            print(f"restored state from {args.state} ({n} workers added)",
                  flush=True)
        except Exception as e:
            # a bad snapshot must not make restart WORSE than a fresh
            # start — serve whatever the flags configure
            print(f"state restore failed ({e}) — starting fresh", flush=True)
    for spec in args.worker:
        wid, whost, wport = parse_worker_arg(spec)
        coord.add_worker(wid, whost, wport)
        print(f"registered worker {wid} at {whost}:{wport}", flush=True)
    for m in deploys:
        n = await coord.deploy_model(m)
        print(f"deployed {m.name} across {n} workers", flush=True)
    if args.state:
        coord.save_state(args.state)
        print(f"state saved to {args.state}", flush=True)
    host, port = await server.start()
    print(f"coordinator listening on {host}:{port}", flush=True)

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    try:
        import signal

        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
    except NotImplementedError:
        pass
    await stop.wait()
    if args.state:
        coord.save_state(args.state)
        print(f"state saved to {args.state}", flush=True)
    await server.stop()


def main(argv: List[str] | None = None) -> None:
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=args.log_level.upper(),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    asyncio.run(amain(args))


if __name__ == "__main__":
    main()
