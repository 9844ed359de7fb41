"""Interactive worker CLI — heir of the reference's
``examples/worker_demo.py`` (an interactive worker + registry REPL).

Starts one worker in-process, then reads commands:

    load <name> <architecture> [size]   e.g. load tiny llama llama-tiny
    unload <name>
    models
    generate <name> <max_new> <tok> [tok ...]
    metrics
    quit

Non-interactive: --script "load tiny llama llama-tiny; generate tiny 4 1 2 3"

    JAX_PLATFORMS=cpu python examples/worker_demo.py --script "..."
"""

import argparse
import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from distributed_inference_engine_tpu.cluster.worker import (  # noqa: E402
    WorkerClient, WorkerServer,
)
from distributed_inference_engine_tpu.config import (  # noqa: E402
    ModelConfig, ServerConfig,
)
from distributed_inference_engine_tpu.engine.types import (  # noqa: E402
    GenerationRequest,
)


async def handle(client: WorkerClient, line: str) -> bool:
    parts = line.split()
    if not parts:
        return True
    cmd, args = parts[0], parts[1:]
    try:
        if cmd in ("quit", "exit"):
            return False
        elif cmd == "load":
            name, arch = args[0], args[1]
            meta = {"size": args[2]} if len(args) > 2 else {}
            cfg = ModelConfig(name=name, architecture=arch, max_seq_len=128,
                              dtype="float32", metadata=meta)
            print(await client.call("load_model", config=cfg.to_dict(),
                                    timeout=600))
        elif cmd == "unload":
            print(await client.call("unload_model", model=args[0]))
        elif cmd == "models":
            print(json.dumps(await client.call("list_models"), indent=2))
        elif cmd == "generate":
            name, max_new = args[0], int(args[1])
            prompt = [int(t) for t in args[2:]] or [1, 2, 3]
            out = await client.generate(name, [GenerationRequest(
                prompt=prompt, max_new_tokens=max_new, temperature=0.0)],
                timeout=600)
            r = out[0]
            print(f"tokens={r.tokens} finish={r.finish_reason} "
                  f"ttft={r.ttft_s * 1e3:.1f}ms")
        elif cmd == "metrics":
            print(json.dumps(await client.call("metrics"), indent=2,
                             default=str))
        elif cmd == "ping":
            print(await client.ping())
        else:
            print(f"unknown command {cmd!r} "
                  "(load/unload/models/generate/metrics/ping/quit)")
    except Exception as e:
        print(f"error: {type(e).__name__}: {e}")
    return True


async def amain(script: str) -> None:
    from _repl import run_repl

    w = WorkerServer(ServerConfig(worker_id="demo-worker", host="127.0.0.1",
                                  port=0))
    host, port = await w.start()
    print(f"worker on {host}:{port}")
    client = WorkerClient(host, port, timeout=600.0)
    try:
        await run_repl(lambda line: handle(client, line), "worker> ", script)
    finally:
        await client.close()
        await w.stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--script", default="", help="semicolon-separated commands")
    args = ap.parse_args()
    asyncio.run(amain(args.script))


if __name__ == "__main__":
    main()
