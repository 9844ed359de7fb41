"""Worker daemon CLI — heir of the reference's ``worker.main()``
(``src/worker.py:211-250``): argparse flags for id/host/port, model preload,
signal-handled serve-forever loop.

    python -m distributed_inference_engine_tpu.cli.worker \
        --worker-id w0 --host 0.0.0.0 --port 9000 \
        --model name=gpt2,architecture=gpt2 \
        --model name=tiny,architecture=llama,size=llama-tiny,continuous=1

Each ``--model`` is ``key=value`` pairs; unknown keys land in
``ModelConfig.metadata`` (that is where engine knobs like ``continuous``,
``page_size`` and ``size`` live). A ``--config file.{json,toml,yaml}`` loads
the full config tree instead (the config file the reference README promised
at ``README.md:39`` but never shipped).
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import time
from typing import Any, Dict, List

from ..config import Config, ModelConfig, ServerConfig, load_config
from ..cluster.worker import WorkerServer, warmup_line

_MODEL_FIELDS = {
    "name", "path", "version", "architecture", "dtype", "batch_size",
    "max_batch_size", "max_seq_len", "quantized",
}
_INT_FIELDS = {"batch_size", "max_batch_size", "max_seq_len",
               "page_size", "num_pages", "decode_steps_per_call"}
_BOOL_FIELDS = {"quantized", "continuous"}


def parse_model_arg(text: str) -> ModelConfig:
    """``name=tiny,architecture=llama,size=llama-tiny,continuous=1`` →
    ModelConfig (unknown keys go to metadata)."""
    fields: Dict[str, Any] = {}
    metadata: Dict[str, Any] = {}
    for part in text.split(","):
        if "=" not in part:
            raise ValueError(f"model spec part {part!r} is not key=value")
        k, v = part.split("=", 1)
        k = k.strip()
        val: Any = v.strip()
        if k in _INT_FIELDS:
            val = int(val)
        elif k in _BOOL_FIELDS:
            val = val.lower() in ("1", "true", "yes", "on")
        (fields if k in _MODEL_FIELDS else metadata)[k] = val
    if "name" not in fields:
        raise ValueError(f"model spec {text!r} missing name=")
    fields["metadata"] = metadata
    return ModelConfig(**fields)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="distributed_inference_engine_tpu.cli.worker",
        description="TPU inference worker (framed-RPC server)",
    )
    p.add_argument("--worker-id", default="worker-0")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="0 = OS-assigned (printed at startup)")
    p.add_argument("--model", action="append", default=[],
                   metavar="K=V[,K=V...]",
                   help="model to preload (repeatable)")
    p.add_argument("--config", default="",
                   help="config file (.json/.toml/.yaml): server/model "
                        "settings come from the file; explicit multihost "
                        "flags still override its multihost section")
    p.add_argument("--artifact-dir", default="",
                   help="pre-fused serving-artifact root: each preloaded "
                        "model cold-starts from <dir>/<name> when a "
                        "committed artifact exists there (and writes one "
                        "after a slow-path load, so the NEXT boot is "
                        "fast); per-model metadata artifact= wins")
    p.add_argument("--multihost", action="store_true",
                   help="join the jax.distributed runtime before loading "
                        "models (TPU pod slices: run one worker per host; "
                        "Cloud TPU auto-discovers the coordinator)")
    p.add_argument("--coordinator-address", default="",
                   help="explicit jax.distributed coordinator (host:port) "
                        "for bring-your-own clusters")
    p.add_argument("--num-processes", type=int, default=0)
    p.add_argument("--process-id", type=int, default=-1)
    p.add_argument("--log-level", default="INFO")
    return p


async def amain(args: argparse.Namespace,
                boot: Dict[str, float] | None = None) -> None:
    if args.config:
        cfg = load_config(args.config)
        server_cfg = cfg.server
        models = cfg.models
        mh = cfg.multihost
        # flags still force multihost on top of a config file
        mh_enabled = mh.enabled or args.multihost
        mh_addr = args.coordinator_address or mh.coordinator_address
        mh_np = args.num_processes or mh.num_processes
        mh_pid = args.process_id if args.process_id >= 0 else mh.process_id
    else:
        server_cfg = ServerConfig(worker_id=args.worker_id, host=args.host,
                                  port=args.port)
        models = [parse_model_arg(m) for m in args.model]
        mh_enabled = args.multihost
        mh_addr = args.coordinator_address
        mh_np = args.num_processes
        mh_pid = args.process_id

    if mh_enabled:
        # pod-slice mode: join jax.distributed FIRST so engine init sees
        # the global device set (parallel/multihost.py)
        from ..parallel.multihost import initialize_multihost

        idx = initialize_multihost(
            coordinator_address=mh_addr or None,
            num_processes=mh_np or None,
            process_id=mh_pid if mh_pid >= 0 else None,
        )
        print(f"multihost: process {idx}", flush=True)

    if args.artifact_dir:
        import os

        for m in models:
            # per-model metadata artifact= wins over the shared root
            m.metadata.setdefault(
                "artifact", os.path.join(args.artifact_dir, m.name))

    worker = WorkerServer(server_cfg)
    worker.boot.update(boot or {})   # what main() passed before this existed
    # preload BEFORE announcing the address: the "listening" line is the
    # readiness signal orchestration scripts wait on, and Ctrl-C during a
    # long checkpoint load still gets default KeyboardInterrupt handling
    # (signal handlers are only installed once serving starts)
    for m in models:
        print(f"loading model {m.name} ({m.architecture})...", flush=True)
        worker.mark_boot("load_begin")
        await worker.load_model_async(m)
        worker.mark_boot("load_end")
        load_s = worker._last_load_s.get(m.name, 0.0)
        warm_s = worker._last_warmup_s.get(m.name, 0.0)
        build_s = load_s - warm_s   # the factory, the backend's start in it
        engine = worker.engines.get(m.name)
        hit = getattr(engine, "artifact_manifest", None) is not None
        print(f"loaded model {m.name} in {load_s:.2f}s "
              f"(build {build_s:.2f}s, warm-up {warm_s:.2f}s"
              f"{warmup_line(worker._engine_warmup(engine))})"
              f"{' [artifact cold-start]' if hit else ''}", flush=True)
    host, port = await worker.start(install_signal_handlers=True)
    # the device goes BEFORE the address: scripts read the port as the
    # text after the line's last colon
    dev = worker.device_report()
    where = ""
    if dev:
        ids = sorted({i for p in dev["models"].values()
                      for i in p["device_ids"]})
        chips = dev["visible_chips"]
        where = (f" [platform={dev['platform']} "
                 f"device_kind={dev['device_kind']!r} "
                 f"n_devices={dev['n_devices']} device_ids={ids}"
                 f"{f' visible_chips={chips}' if chips else ''}]")
    print(f"worker {worker.worker_id}{where} listening on {host}:{port}",
          flush=True)
    await worker.serve_forever()


def main(argv: List[str] | None = None) -> None:
    boot = {"main_entered": time.perf_counter()}
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=args.log_level.upper(),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    from ..utils.compile_cache import configure_compile_cache

    logging.getLogger(__name__).info(
        "compile cache: %s", configure_compile_cache())   # imports jax
    boot["jax_imported"] = time.perf_counter()
    asyncio.run(amain(args, boot))


if __name__ == "__main__":
    main()
