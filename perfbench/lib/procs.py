"""Child processes of a run: ``cli.worker`` (holds a chip) and
``cli.coordinator``, started the way ``chip_smoke.py`` starts them (copied,
not imported: the yardstick may not change under a later PR). The parent
never imports jax — a parent that touched JAX would hold the chip."""

from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Sequence

PKG = "distributed_inference_engine_tpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MODEL = "bench"


class BenchFailure(Exception):
    """A child died, a wait timed out, or a check did not hold."""


class Child:
    """One child process with its output in a log file."""

    def __init__(self, name: str, argv: Sequence[str], env: Dict[str, str],
                 log_dir: str) -> None:
        self.name = name
        self.log_path = os.path.join(log_dir, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            list(argv), stdout=self._log, stderr=subprocess.STDOUT,
            env=env, cwd=ROOT)

    def tail(self, n: int = 30) -> str:
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def wait_line(self, pattern: str, timeout: float) -> "re.Match[str]":
        deadline = time.monotonic() + timeout
        rx = re.compile(pattern)
        while True:
            with open(self.log_path, errors="replace") as f:
                for line in f:
                    m = rx.search(line)
                    if m:
                        return m
            rc = self.proc.poll()
            if rc is not None:
                raise BenchFailure(f"{self.name} exited {rc} before "
                                   f"{pattern!r}:\n{self.tail()}")
            if time.monotonic() > deadline:
                raise BenchFailure(f"{self.name}: no {pattern!r} within "
                                   f"{timeout:.0f}s:\n{self.tail()}")
            time.sleep(0.2)

    def wait_exit(self, timeout: float) -> int:
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.kill()
            raise BenchFailure(f"{self.name} still running after "
                               f"{timeout:.0f}s:\n{self.tail()}") from None
        self._log.close()
        return rc

    def stop(self, timeout: float = 30.0) -> None:
        """SIGTERM, then SIGKILL if ignored; always waits for the end."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        if not self._log.closed:
            self._log.close()

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
        if not self._log.closed:
            self._log.close()


class Children:
    """Every child a run started; ``stop_all`` runs on every exit path."""

    def __init__(self, log_dir: str) -> None:
        self.log_dir = log_dir
        self.live: List[Child] = []

    def start(self, name: str, argv: Sequence[str],
              env: Dict[str, str]) -> Child:
        child = Child(name, argv, env, self.log_dir)
        self.live.append(child)
        return child

    def run_to_end(self, name: str, argv: Sequence[str], env: Dict[str, str],
                   timeout: float) -> Child:
        child = self.start(name, argv, env)
        try:
            child.wait_exit(timeout)
        finally:
            self.live.remove(child)
        return child

    def stop_all(self) -> None:
        # coordinator first (started last), then the workers
        for child in reversed(self.live):
            child.stop()
        self.live.clear()


def child_env(platform: str, **extra: str) -> Dict[str, str]:
    """``JAX_PLATFORMS`` names the backend the child MUST find: a worker
    without it dies at its first jax call instead of serving from another."""
    env = dict(os.environ, JAX_PLATFORMS=platform, PYTHONUNBUFFERED="1")
    env.pop("BENCH_RUN", None)
    env.update(extra)
    return env


def is_quantized(serve: Dict[str, Any]) -> bool:
    """``serve.quantized`` from the file; a file that gives only
    ``weight_bits`` (the dense int4 family's) means a quantized tree."""
    return bool(serve.get("quantized", "weight_bits" in serve))


def model_dict(serve: Dict[str, Any], weight_seed: int) -> Dict[str, Any]:
    """The worker's model entry: the ``serve`` block whole. ``quantized``,
    ``weight_bits`` and ``dtype`` are the file's; ``serve.metadata`` is
    passed through as it is, over the keys the harness sets (a cut's kept
    layers or held experts, a KV dtype, an attention path)."""
    meta = {"size": serve["size"], "continuous": 1,
            "page_size": serve["page_size"], "num_pages": serve["num_pages"],
            "prefill_buckets": serve["prefill_buckets"], "warmup": 1,
            "seed": weight_seed}
    if "weight_bits" in serve:
        meta["weight_bits"] = serve["weight_bits"]
    meta.update(serve.get("metadata") or {})
    out = {"name": MODEL, "architecture": serve["architecture"],
           "quantized": is_quantized(serve),
           "max_batch_size": serve["max_batch_size"],
           "max_seq_len": serve["max_seq_len"], "metadata": meta}
    if "dtype" in serve:
        out["dtype"] = serve["dtype"]
    return out


def deploy_spec(serve: Dict[str, Any]) -> str:
    """The coordinator's ``--deploy`` string for the same model: what
    decides WHICH model a worker serves (``_model_identity``: architecture,
    size, dtype, quantized) and every scalar of ``serve.metadata``. Its
    ``k=v,k=v`` grammar has no list or object: those reach the worker, which
    holds the model before the coordinator deploys it, and no further."""
    parts = [f"name={MODEL}", f"architecture={serve['architecture']}",
             f"size={serve['size']}",
             f"quantized={int(is_quantized(serve))}", "continuous=1"]
    if "weight_bits" in serve:
        parts.append(f"weight_bits={serve['weight_bits']}")
    if "dtype" in serve:
        parts.append(f"dtype={serve['dtype']}")
    parts += [f"max_batch_size={serve['max_batch_size']}",
              f"max_seq_len={serve['max_seq_len']}"]
    for key, val in (serve.get("metadata") or {}).items():
        if isinstance(val, (list, dict)):
            continue
        if isinstance(val, bool):
            val = int(val)
        if any(c in f"{key}{val}" for c in ",="):
            raise BenchFailure(f"serve.metadata {key}={val!r} cannot go "
                               f"into a --deploy string")
        parts.append(f"{key}={val}")
    return ",".join(parts)


def start_worker(children: Children, serve: Dict[str, Any], worker_id: str,
                 env: Dict[str, str], weight_seed: int) -> Child:
    path = os.path.join(children.log_dir, f"{worker_id}.json")
    with open(path, "w") as f:
        json.dump({"server": {"worker_id": worker_id, "host": "127.0.0.1",
                              "port": 0},
                   "models": [model_dict(serve, weight_seed)]}, f, indent=1)
    return children.start(worker_id, [sys.executable, "-m",
                                      f"{PKG}.cli.worker", "--config", path],
                          env)


def worker_port(worker: Child, timeout: float) -> int:
    m = worker.wait_line(r"worker \S+.* listening on [^:\s]+:(\d+)\s*$",
                         timeout)
    return int(m.group(1))


def start_coordinator(children: Children, serve: Dict[str, Any],
                      platform: str, ports: Dict[str, int]) -> int:
    """The coordinator with every ``CoordinatorConfig`` default (default
    load-balancing strategy included); returns its port."""
    argv = [sys.executable, "-m", f"{PKG}.cli.coordinator", "--port", "0",
            "--deploy", deploy_spec(serve), "--log-level", "WARNING"]
    for wid, port in ports.items():
        argv += ["--worker", f"{wid}=127.0.0.1:{port}"]
    coord = children.start("coordinator", argv, child_env(platform))
    coord.wait_line(rf"deployed {MODEL} across {len(ports)} workers", 120.0)
    m = coord.wait_line(r"coordinator listening on [^:\s]+:(\d+)\s*$", 60.0)
    return int(m.group(1))
