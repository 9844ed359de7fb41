"""Chip smoke: the normal serving path, once, at full width, on the TPU.

    python chip_smoke.py                   # on a machine with a TPU
    python chip_smoke.py --kernels         # every Pallas kernel vs XLA, there
    JAX_PLATFORMS=cpu python chip_smoke.py --preset tiny    # CPU debug run

Serves ``mistral-7b`` (v0.3 widths, all 32 layers, random-init packed int4,
128 slots, 256-token contexts) the way the README's "Full cluster" does: one
``cli.worker`` process that owns the chip, one ``cli.coordinator`` process in
front of it, and a client over the framed RPC. It sends 16 concurrent
128-token prompts for 64 greedy tokens each, one streamed request and repeats
of an earlier prompt, and checks what comes back. Then it starts the worker a
second time to show the warm-up compile hitting the compile cache. With four
or more chips visible it also runs four one-chip replicas behind one
coordinator and a tp=4 deploy.

This process never imports jax: a parent that touched JAX would hold the
chip. Every process that needs the chip is a child started through the normal
``python -m ...cli.worker`` entry point, one at a time per chip. Any child
exiting non-zero, any time-out, any failed check, any exception makes this
script exit non-zero and print no result line. On success the last line of
stdout is ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}`` with the device as the worker's JAX reports it.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import re
import signal
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, List, Optional, Sequence

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "chiprun_out", "chip_smoke")
PKG = "distributed_inference_engine_tpu"

# One preset = one deploy + the traffic sent to it. ``platform`` is what the
# children's JAX must find (exported to them as JAX_PLATFORMS, so a worker
# without that backend dies at its first jax call instead of serving from
# another one).
PRESETS: Dict[str, Dict[str, Any]] = {
    "mistral-7b": dict(
        platform="tpu", architecture="mistral", size="mistral-7b",
        vocab_size=32768, max_batch_size=128, max_seq_len=256,
        page_size=128, num_pages=264, prefill_buckets=[128],
        prompt_len=128, new_tokens=64, n_requests=16, n_replica_requests=64,
        ready_timeout_s=1000.0),
    "tiny": dict(
        platform="cpu", architecture="llama", size="llama-tiny",
        vocab_size=1024, max_batch_size=8, max_seq_len=64,
        page_size=16, num_pages=40, prefill_buckets=[16],
        prompt_len=16, new_tokens=8, n_requests=16, n_replica_requests=16,
        ready_timeout_s=240.0),
}
MODEL = "smoke"
LEGS = ("server", "restart", "replicas", "tp4")


class SmokeFailure(Exception):
    """A check did not hold, a child died, or a wait timed out."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def say(msg: str) -> None:
    print(msg, flush=True)


def first_diff(a: Sequence[int], b: Sequence[int]) -> str:
    """"" when equal, else where two token chains part."""
    if list(a) == list(b):
        return ""
    k = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
             min(len(a), len(b)))
    return f" [chains part at token {k} of {len(a)}/{len(b)}]"


# ------------------------------------------------------------------ children


class Child:
    """One child process with its output in a log file under WORK."""

    def __init__(self, name: str, argv: Sequence[str],
                 env: Dict[str, str]) -> None:
        self.name = name
        self.port = 0                     # set once the readiness line shows
        self.log_path = os.path.join(WORK, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            list(argv), stdout=self._log, stderr=subprocess.STDOUT,
            env=env, cwd=ROOT)

    def tail(self, n: int = 30) -> str:
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def wait_line(self, pattern: str, timeout: float) -> "re.Match[str]":
        """Block until a log line matches; the child dying first, or the
        time-out, is a failure with the log's tail attached."""
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
                raise SmokeFailure(
                    f"{self.name} exited {rc} before {pattern!r}:\n"
                    f"{self.tail()}")
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f"{self.name}: no {pattern!r} within {timeout:.0f}s:\n"
                    f"{self.tail()}")
            time.sleep(0.5)

    def stop(self, timeout: float = 60.0) -> int:
        """SIGTERM (both CLIs shut down cleanly on it), then the exit code;
        SIGKILL only if the child ignores it."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
                self._log.close()
                raise SmokeFailure(
                    f"{self.name} ignored SIGTERM for {timeout:.0f}s")
        self._log.close()
        return self.proc.returncode

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
        if not self._log.closed:
            self._log.close()


class Children:
    """Every child this run started; ``kill_all`` runs on every exit path."""

    def __init__(self) -> None:
        self.live: List[Child] = []

    def start(self, name: str, argv: Sequence[str],
              env: Dict[str, str]) -> Child:
        child = Child(name, argv, env)
        self.live.append(child)
        return child

    def stop(self, child: Child) -> None:
        rc = child.stop()
        self.live.remove(child)
        if rc != 0:
            raise SmokeFailure(
                f"{child.name} exited {rc} on shutdown:\n{child.tail()}")

    def run_to_end(self, name: str, argv: Sequence[str],
                   env: Dict[str, str], timeout: float) -> Child:
        """A child that does its work and exits (it may take the chip: no
        server is alive when this is called). Returns it once it ended;
        running past ``timeout`` is a failure."""
        child = self.start(name, argv, env)
        try:
            child.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{name} still running after {timeout:.0f}s:"
                               f"\n{child.tail()}") from None
        self.live.remove(child)
        child.kill()                      # closes its log
        return child

    def kill_all(self) -> None:
        for child in self.live:
            child.kill()
        self.live.clear()


# ------------------------------------------------------------- deploy config


def model_dict(preset: Dict[str, Any], **extra_meta: Any) -> Dict[str, Any]:
    meta = {"size": preset["size"], "continuous": 1, "weight_bits": 4,
            "page_size": preset["page_size"],
            "num_pages": preset["num_pages"],
            "prefill_buckets": preset["prefill_buckets"], "warmup": 1}
    meta.update(extra_meta)
    return {"name": MODEL, "architecture": preset["architecture"],
            "quantized": True, "max_batch_size": preset["max_batch_size"],
            "max_seq_len": preset["max_seq_len"], "metadata": meta}


def write_worker_config(preset: Dict[str, Any], worker_id: str,
                        **extra_meta: Any) -> str:
    """The worker's ``--config`` file (a list value — prefill_buckets —
    needs one; the key=value spec parses lists as strings)."""
    path = os.path.join(WORK, f"{worker_id}.json")
    with open(path, "w") as f:
        json.dump({"server": {"worker_id": worker_id, "host": "127.0.0.1",
                              "port": 0},
                   "models": [model_dict(preset, **extra_meta)]}, f, indent=1)
    return path


def deploy_spec(preset: Dict[str, Any]) -> str:
    """The coordinator's ``--deploy`` spec: the same model identity the
    workers preloaded, so the deploy is the idempotent re-load."""
    return (f"name={MODEL},architecture={preset['architecture']},"
            f"size={preset['size']},quantized=1,continuous=1,weight_bits=4,"
            f"max_batch_size={preset['max_batch_size']},"
            f"max_seq_len={preset['max_seq_len']}")


def child_env(preset: Dict[str, Any], **extra: str) -> Dict[str, str]:
    env = dict(os.environ, JAX_PLATFORMS=preset["platform"],
               PYTHONUNBUFFERED="1")
    env.update(extra)
    return env


def one_chip_env(chip: int) -> Dict[str, str]:
    """libtpu's chip-visibility variables: confine a process to ONE chip of
    a multi-chip host, so ``len(jax.devices()) == 1`` inside it. Each
    process also gets its own controller port — independent one-chip
    processes on one host otherwise collide on the default."""
    port = str(8476 + chip)
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
            "TPU_MESH_CONTROLLER_PORT": port}


def start_worker(children: Children, preset: Dict[str, Any], worker_id: str,
                 env: Dict[str, str], **extra_meta: Any) -> Child:
    cfg = write_worker_config(preset, worker_id, **extra_meta)
    return children.start(
        worker_id, [sys.executable, "-m", f"{PKG}.cli.worker",
                    "--config", cfg], env)


def worker_port(worker: Child, timeout: float) -> int:
    m = worker.wait_line(r"worker \S+.* listening on [^:\s]+:(\d+)\s*$",
                         timeout)
    return int(m.group(1))


def start_coordinator(children: Children, preset: Dict[str, Any], name: str,
                      worker_ports: Dict[str, int]) -> Child:
    argv = [sys.executable, "-m", f"{PKG}.cli.coordinator", "--port", "0",
            "--deploy", deploy_spec(preset)]
    for wid, port in worker_ports.items():
        argv += ["--worker", f"{wid}=127.0.0.1:{port}"]
    # the coordinator never initialises a JAX backend; it gets the same
    # environment as any other child
    coord = children.start(name, argv, child_env(preset))
    coord.wait_line(rf"deployed {MODEL} across {len(worker_ports)} workers",
                    120.0)
    m = coord.wait_line(r"coordinator listening on [^:\s]+:(\d+)\s*$", 60.0)
    coord.port = int(m.group(1))
    return coord


# ------------------------------------------------------------------- traffic


def make_prompts(preset: Dict[str, Any], n: int, seed: int) -> List[List[int]]:
    rng = random.Random(seed)
    return [[rng.randrange(1, preset["vocab_size"])
             for _ in range(preset["prompt_len"])] for _ in range(n)]


def check_result(res: Dict[str, Any], preset: Dict[str, Any],
                 label: str) -> None:
    toks = res.get("tokens", [])
    if len(toks) != preset["new_tokens"]:
        raise SmokeFailure(f"{label}: {len(toks)} tokens, asked "
                           f"{preset['new_tokens']}")
    bad = [t for t in toks if not 0 <= int(t) < preset["vocab_size"]]
    if bad:
        raise SmokeFailure(f"{label}: token ids outside the vocab: "
                           f"{bad[:8]}")
    if res.get("finish_reason") != "length":
        raise SmokeFailure(f"{label}: finish_reason "
                           f"{res.get('finish_reason')!r}, expected 'length'")


async def generate_batch(port: int, preset: Dict[str, Any],
                         prompts: List[List[int]], tag: str,
                         **kw: Any) -> List[Dict[str, Any]]:
    """All prompts concurrently through ``coordinator.submit`` (the
    ``generate`` verb), one pooled connection each."""
    from distributed_inference_engine_tpu.api.frontend import (
        CoordinatorClient,
    )

    client = CoordinatorClient("127.0.0.1", port, timeout=600.0)
    client.max_connections = max(8, len(prompts))
    try:
        results = await asyncio.gather(*(
            client.generate(MODEL, prompt=p,
                            max_new_tokens=preset["new_tokens"],
                            temperature=0.0, request_id=f"{tag}-{i}", **kw)
            for i, p in enumerate(prompts)))
    finally:
        await client.close()
    for i, res in enumerate(results):
        check_result(res, preset, f"{tag}-{i}")
    return results


async def generate_streamed(port: int, preset: Dict[str, Any],
                            prompt: List[int]) -> List[int]:
    from distributed_inference_engine_tpu.api.frontend import (
        CoordinatorClient,
    )

    chunks: List[List[int]] = []
    client = CoordinatorClient("127.0.0.1", port, timeout=600.0)
    try:
        res = await client.generate_stream(
            MODEL, chunks.append, prompt=prompt,
            max_new_tokens=preset["new_tokens"], temperature=0.0,
            request_id="stream-0")
    finally:
        await client.close()
    check_result(res, preset, "stream-0")
    streamed = [t for c in chunks for t in c]
    check(len(chunks) >= 1 and streamed == list(res["tokens"]),
          f"streamed request: {len(chunks)} chunk(s) concatenate to the "
          f"packed result ({len(streamed)} tokens)")
    return streamed


async def worker_report(port: int) -> Dict[str, Any]:
    """The worker's own account of itself: ping (device) + metrics."""
    from distributed_inference_engine_tpu.cluster.worker import WorkerClient

    client = WorkerClient("127.0.0.1", port, timeout=60.0)
    try:
        ping = await client.ping()
        metrics = await client.metrics()
    finally:
        await client.close()
    return {"ping": ping, "metrics": metrics}


async def worker_generate(port: int, preset: Dict[str, Any],
                          prompt: List[int]) -> List[int]:
    from distributed_inference_engine_tpu.cluster.worker import WorkerClient
    from distributed_inference_engine_tpu.engine.types import (
        GenerationRequest,
    )

    client = WorkerClient("127.0.0.1", port, timeout=600.0)
    try:
        out = await client.generate(MODEL, [GenerationRequest(
            prompt=prompt, max_new_tokens=preset["new_tokens"],
            temperature=0.0, request_id="restart-0")])
    finally:
        await client.close()
    return list(out[0].tokens)


# -------------------------------------------------------------------- checks


def describe_worker(report: Dict[str, Any], preset: Dict[str, Any],
                    label: str, state: Dict[str, Any]) -> Dict[str, Any]:
    """Print what the worker says about itself; check platform and the
    int4 kernel paths. Returns the device report, and keeps the widest one
    seen (a replica confined to one chip sees one device) for the result
    line."""
    dev = report["ping"].get("device")
    if not dev:
        raise SmokeFailure(f"{label}: worker reports no device")
    if dev["n_devices"] > state.get("device", {}).get("n_devices", 0):
        state["device"] = dev
    place = dev["models"][MODEL]
    setup = report["metrics"]["model_setup"][MODEL]
    say(f"  {label}: platform={dev['platform']} "
        f"device_kind={dev['device_kind']!r} n_devices={dev['n_devices']} "
        f"device_ids={place['device_ids']} coords={place.get('coords')} "
        f"visible_chips={dev.get('visible_chips')}")
    say(f"  {label}: model={preset['size']} quant=int4 "
        f"batch={preset['max_batch_size']} max_seq_len="
        f"{preset['max_seq_len']} param_bytes={place['param_bytes']} "
        f"int4_paths={place['int4_paths']} "
        f"decode_attention={place.get('decode_attention')}")
    say(f"  {label}: set-up load {setup['load_s']:.1f}s of which warm-up "
        f"compile {setup['warmup_s']:.1f}s (information)")
    check(dev["platform"] == preset["platform"],
          f"{label}: platform == {preset['platform']!r}")
    paths = place["int4_paths"]
    if dev["platform"] == "cpu":
        say(f"  {label}: int4 Mosaic check skipped on platform=cpu (the "
            f"kernel only interprets there; paths {paths})")
    else:
        check(paths["xla"] == 0 and paths["direct"] + paths["cp"] > 0,
              f"{label}: every int4 matmul rides the Mosaic kernel "
              f"(paths {paths})")
    # attention_impl "auto" as the engine resolved it: on the chip, with
    # the pool on one device, decode attention reads K/V in place from
    # the pages (the flash-decode kernel); on the CPU and over a mesh the
    # dense XLA path. A fallback to the dense path on the chip fails here.
    one_device = len(place["device_ids"]) == 1
    want = ("pallas-decode" if dev["platform"] == "tpu" and one_device
            else "xla")
    check(place.get("decode_attention") == want,
          f"{label}: decode attention resolved to {want!r} "
          f"(reports {place.get('decode_attention')!r})")
    return dev


def check_decode_path(report: Dict[str, Any], label: str) -> None:
    """The decode chunks the engine dispatched, by how attention reached
    the context: all of them on the path the worker reported."""
    m = report["metrics"]["models"][MODEL]
    in_place, dense = m["decode_chunks_in_place"], m["decode_chunks_dense"]
    say(f"  {label}: decode chunks in place {in_place}, dense {dense} "
        f"(attn_impl {m['attn_impl']})")
    if m["attn_impl"].startswith("pallas"):
        check(in_place > 0 and dense == 0,
              f"{label}: every decode chunk read K/V in place")
    else:
        check(dense > 0 and in_place == 0,
              f"{label}: every decode chunk ran the dense XLA path")


def decoded(report: Dict[str, Any]) -> int:
    """Requests the worker's engine has admitted so far (warm-up included:
    callers take differences)."""
    return report["metrics"]["models"][MODEL]["total_requests"]


def check_no_errors(report: Dict[str, Any], label: str) -> None:
    m = report["metrics"]
    check(m["error_count"] == 0,
          f"{label}: worker error_count == 0 after "
          f"{m['request_count']} generate RPCs")


def verify_chains(children: Children, preset: Dict[str, Any],
                  cases: List[Dict[str, Any]], what: str) -> None:
    """Every step of every chain against teacher-forced single-device
    reference logits (``scripts/chip_parity.py``): two greedy chains that
    part at a near-tie cannot be compared token by token."""
    path = os.path.join(WORK, "parity_cases.json")
    with open(path, "w") as f:
        json.dump({"architecture": preset["architecture"],
                   "size": preset["size"],
                   "max_seq_len": preset["max_seq_len"], "cases": cases}, f)
    parity = children.run_to_end(
        "parity", [sys.executable, "-m", "scripts.chip_parity", path],
        child_env(preset), timeout=900.0)
    say(parity.tail(len(cases) + 4).rstrip())
    check(parity.proc.returncode == 0,
          f"{what}: every step is the reference argmax or inside its "
          f"numeric tie set ({len(cases)} chains)")


# ---------------------------------------------------------------------- legs


def leg_server(children: Children, preset: Dict[str, Any],
               state: Dict[str, Any]) -> None:
    """One worker (owns the chip) + one coordinator + client."""
    say("== leg server: cli.worker + cli.coordinator + client")
    t0 = time.monotonic()
    worker = start_worker(children, preset, "w0", child_env(preset))
    wport = worker_port(worker, preset["ready_timeout_s"])
    say(f"  worker ready after {time.monotonic() - t0:.1f}s")
    before = asyncio.run(worker_report(wport))
    describe_worker(before, preset, "w0", state)
    state["cold_warmup_s"] = before["metrics"]["model_setup"][MODEL][
        "warmup_s"]
    coordinator = start_coordinator(children, preset, "coordinator",
                                    {"w0": wport})
    cport = coordinator.port

    n = preset["n_requests"]
    prompts = make_prompts(preset, 2 * n + 1, seed=21)
    solo = prompts[2 * n]

    def burst(tag: str, batch: List[List[int]]):
        t0 = time.monotonic()
        results = asyncio.run(generate_batch(cport, preset, batch, tag))
        wall = time.monotonic() - t0
        check(all(not r.get("cached") for r in results),
              f"{n} concurrent requests x {preset['new_tokens']} tokens "
              f"returned, ids < vocab, finish_reason 'length' ({tag})")
        return results, wall

    first, wall_first = burst("req", prompts[:n])
    _, wall_second = burst("again", prompts[n:2 * n])
    say(f"  first batch wall {wall_first:.2f}s (may compile a decode program "
        f"the warm-up grid did not reach), second batch {wall_second:.2f}s "
        f"-> {n * preset['new_tokens'] / wall_second:.0f} tok/s end to end "
        f"through the coordinator at {n} of {preset['max_batch_size']} slots "
        f"(rough, information)")
    state["tokens"] = {i: list(r["tokens"]) for i, r in enumerate(first)}

    streamed = asyncio.run(generate_streamed(cport, preset, solo))
    state["solo"] = (solo, streamed)

    again = asyncio.run(generate_batch(cport, preset, prompts[:1], "hit"))[0]
    check(bool(again.get("cached")) and again["tokens"] == first[0]["tokens"],
          "repeat of prompt 0: answered by the response cache, same tokens")
    # the engine-level repeat compares like with like: the streamed prompt
    # was decoded alone, and is decoded alone again (bf16 results depend on
    # the prefill batch shape, so a chain decoded inside a batch of 16 may
    # part from the same prompt decoded alone at a near-tie — reported
    # below, and both chains verified against reference logits at the end
    # of this leg)
    again = asyncio.run(generate_batch(cport, preset, [solo], "rerun",
                                       no_cache=True))[0]
    check(not again.get("cached") and again["tokens"] == streamed,
          "repeat of the streamed prompt with no_cache: decoded again by "
          "the engine, identical greedy tokens" + first_diff(
              again["tokens"], streamed))
    alone = asyncio.run(generate_batch(cport, preset, prompts[:1], "alone",
                                       no_cache=True))[0]
    say(f"  prompt 0 decoded alone vs inside the batch of {n}:"
        + (first_diff(alone["tokens"], first[0]["tokens"])
           or " identical"))

    after = asyncio.run(worker_report(wport))
    check_no_errors(after, "w0")
    check_decode_path(after, "w0")
    served = decoded(after) - decoded(before)
    check(served == 2 * n + 3,
          f"w0 engine decoded {served} requests since warm-up (two batches, "
          f"the stream, two no_cache repeats; not the cache hit)")
    children.stop(coordinator)
    children.stop(worker)            # frees the chip for the parity child
    verify_chains(children, preset, [
        {"label": "prompt0-in-batch", "prompt": prompts[0],
         "tokens": first[0]["tokens"]},
        {"label": "prompt0-alone", "prompt": prompts[0],
         "tokens": alone["tokens"]},
        {"label": "streamed", "prompt": solo, "tokens": streamed},
    ], "served chains vs the plain forward pass")


def leg_restart(children: Children, preset: Dict[str, Any],
                state: Dict[str, Any]) -> None:
    """Start the same worker again: its warm-up compile should now be read
    from the compile cache the first start wrote."""
    say("== leg restart: second worker start, same deploy")
    t0 = time.monotonic()
    worker = start_worker(children, preset, "w0-restart", child_env(preset))
    wport = worker_port(worker, preset["ready_timeout_s"])
    say(f"  worker ready after {time.monotonic() - t0:.1f}s")
    report = asyncio.run(worker_report(wport))
    describe_worker(report, preset, "w0-restart", state)
    warm = report["metrics"]["model_setup"][MODEL]["warmup_s"]
    from distributed_inference_engine_tpu.utils.compile_cache import (
        CHECKOUT_CACHE_DIR,
        ENV_VAR,
    )

    cache = os.environ.get(ENV_VAR) or CHECKOUT_CACHE_DIR
    n_entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    say(f"  warm-up compile: first start {state['cold_warmup_s']:.1f}s -> "
        f"second start {warm:.1f}s; compile cache {cache} holds "
        f"{n_entries} entries (information)")
    solo, streamed = state["solo"]
    toks = asyncio.run(worker_generate(wport, preset, solo))
    check(toks == streamed,
          "restarted worker decodes the streamed prompt, alone again, to "
          "the same greedy tokens" + first_diff(toks, streamed))
    check_no_errors(asyncio.run(worker_report(wport)), "w0-restart")
    children.stop(worker)


def leg_replicas(children: Children, preset: Dict[str, Any],
                 state: Dict[str, Any]) -> None:
    """Four one-chip worker processes behind one coordinator."""
    say("== leg replicas: four one-chip cli.worker processes, one "
        "coordinator")
    workers = {}
    for chip in range(4):
        wid = f"r{chip}"
        workers[wid] = start_worker(
            children, preset, wid, child_env(preset, **one_chip_env(chip)))
    ports = {wid: worker_port(w, preset["ready_timeout_s"])
             for wid, w in workers.items()}
    reports = {wid: asyncio.run(worker_report(p)) for wid, p in ports.items()}
    chips = []
    for wid, rep in reports.items():
        dev = describe_worker(rep, preset, wid, state)
        chips.append(dev.get("visible_chips"))
        if dev["platform"] != "cpu":
            check(dev["n_devices"] == 1
                  and len(dev["models"][MODEL]["device_ids"]) == 1,
                  f"{wid}: confined to one chip (n_devices == 1)")
    if preset["platform"] == "cpu":
        say("  distinct-device check skipped on platform=cpu (the "
            "visibility variables only mean something to libtpu)")
    else:
        # device ids restart at 0 inside a confined process, so each worker
        # reports the chip libtpu showed it; and the four hold their chips
        # AT THE SAME TIME, which one chip does not allow two processes
        check(sorted(chips) == ["0", "1", "2", "3"],
              f"four workers alive at once, each on a different chip "
              f"(visible_chips {chips})")
    coordinator = start_coordinator(children, preset, "coordinator-replicas",
                                    ports)
    cport = coordinator.port
    n = preset["n_replica_requests"]
    prompts = make_prompts(preset, n, seed=21)    # same leading prompts
    t0 = time.monotonic()
    results = asyncio.run(generate_batch(cport, preset, prompts, "rep"))
    wall = time.monotonic() - t0
    say(f"  {n} requests wall {wall:.2f}s -> "
        f"{sum(len(r['tokens']) for r in results) / wall:.0f} tok/s over "
        f"four replicas (rough, information)")
    total = 0
    for wid, port in ports.items():
        rep = asyncio.run(worker_report(port))
        check_no_errors(rep, wid)
        served = decoded(rep) - decoded(reports[wid])
        total += served
        check(served > 0, f"{wid} decoded {served} of {n} requests")
    check(total == n, f"the four replicas decoded all {n} requests between "
                      f"them")
    if "tokens" in state:
        same = sum(results[i]["tokens"] == t
                   for i, t in state["tokens"].items())
        say(f"  replicas vs the server leg on its {len(state['tokens'])} "
            f"prompts: {same} identical chains (admission groups differ; "
            f"information)")
    else:
        state["tokens"] = {i: list(results[i]["tokens"])
                           for i in range(preset["n_requests"])}
    children.stop(coordinator)
    for w in workers.values():
        children.stop(w)


def leg_tp4(children: Children, preset: Dict[str, Any],
            state: Dict[str, Any]) -> None:
    """The same deploy plus tp=4, one process over four chips."""
    say("== leg tp4: one cli.worker, tp=4 over four chips")
    env = child_env(preset)
    if preset["platform"] == "cpu":
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=4")
    worker = start_worker(children, preset, "w-tp4", env, tp=4)
    wport = worker_port(worker, preset["ready_timeout_s"])
    report = asyncio.run(worker_report(wport))
    dev = describe_worker(report, preset, "w-tp4", state)
    place = dev["models"][MODEL]
    check(len(place["device_ids"]) == 4,
          f"mesh spans four distinct devices {place['device_ids']}")
    shares = {d: b / place["param_bytes"]
              for d, b in place["param_bytes_by_device"].items()}
    say("  parameter bytes by device: "
        + ", ".join(f"{d}: {b} ({shares[d]:.1%})" for d, b in
                    sorted(place["param_bytes_by_device"].items())))
    check(all(0.2 <= s <= 0.4 for s in shares.values()),
          "each device holds about a quarter of the parameter bytes")
    for d, stats in sorted(report["metrics"]["device"]["memory"].items()):
        say(f"  device {d} memory_stats bytes_in_use="
            f"{(stats or {}).get('bytes_in_use')} (information)")
    if dev["platform"] != "cpu":
        check(place["int4_paths"]["cp"] > 0
              and place["int4_paths"]["direct"] == 0,
              "sharded int4 weights take the custom_partitioning (cp) "
              "kernel path")
    coordinator = start_coordinator(children, preset, "coordinator-tp4",
                                    {"w-tp4": wport})
    cport = coordinator.port
    prompts = make_prompts(preset, preset["n_requests"], seed=21)
    results = asyncio.run(generate_batch(cport, preset, prompts, "tp4"))
    check_no_errors(asyncio.run(worker_report(wport)), "w-tp4")
    children.stop(coordinator)
    children.stop(worker)            # frees the chips for the parity child
    tp1 = state.get("tokens", {})    # from leg server or replicas, if run
    differ = [i for i, r in enumerate(results)
              if r["tokens"] != tp1.get(i)]
    if not differ:
        say(f"  ok: tp=4 greedy tokens match tp=1 exactly on all "
            f"{len(results)} prompts")
        return
    say(f"  tp=4 tokens equal tp=1 on {len(results) - len(differ)}/"
        f"{len(results)} prompts — verifying every step of the other "
        f"chains, tp=1 and tp=4, against teacher-forced single-device "
        f"logits (a near-tie may flip a chain)")
    cases = []
    for i in differ:
        if i in tp1:
            cases.append({"label": f"tp1-{i}", "prompt": prompts[i],
                          "tokens": tp1[i]})
        cases.append({"label": f"tp4-{i}", "prompt": prompts[i],
                      "tokens": results[i]["tokens"]})
    verify_chains(children, preset, cases, "tp=1 and tp=4 chains")


def run_kernels(children: Children, preset: Dict[str, Any]) -> Dict[str, Any]:
    say("== kernels: every Pallas kernel in ops/, compiled, vs its XLA path")
    argv = [sys.executable, "-m", "scripts.chip_kernels"]
    if preset["platform"] == "cpu":
        argv.append("--tiny")
    child = children.run_to_end("kernels", argv, child_env(preset),
                                timeout=1100.0)
    say(child.tail(80).rstrip())
    m = re.search(r"platform=(\S+) device_kind='([^']*)' n_devices=(\d+)",
                  child.tail(200))
    if child.proc.returncode != 0 or not m:
        raise SmokeFailure(f"kernel checks exited {child.proc.returncode}")
    return {"platform": m.group(1), "device_kind": m.group(2),
            "n_devices": int(m.group(3))}


# ---------------------------------------------------------------------- main


def run(args: argparse.Namespace, children: Children) -> Dict[str, Any]:
    preset = PRESETS[args.preset]
    want = os.environ.get("JAX_PLATFORMS", "")
    if preset["platform"] == "cpu" and "cpu" not in want.split(","):
        raise SmokeFailure("--preset tiny is the CPU debug mode: run it "
                           "with JAX_PLATFORMS=cpu")
    os.makedirs(WORK, exist_ok=True)
    say(f"chip_smoke: preset={args.preset} expects platform="
        f"{preset['platform']} (work dir {WORK})")
    if args.kernels:
        return run_kernels(children, preset)
    legs = [leg for leg in (args.legs or ",".join(LEGS)).split(",") if leg]
    unknown = [leg for leg in legs if leg not in LEGS]
    if unknown or not legs:
        raise SmokeFailure(f"legs {legs}: choose from {LEGS}")
    if "restart" in legs and "server" not in legs:
        raise SmokeFailure("leg restart needs leg server before it")
    state: Dict[str, Any] = {}
    if "server" in legs:
        leg_server(children, preset, state)
    if "restart" in legs:
        leg_restart(children, preset, state)
    # the multichip legs: by default only where the worker saw >=4 devices;
    # always when --legs names them
    if not args.legs and state["device"]["n_devices"] < 4:
        say(f"multichip legs skipped: {state['device']['n_devices']} "
            f"device(s)")
        return state["device"]
    if "replicas" in legs:
        leg_replicas(children, preset, state)
    if "tp4" in legs:
        leg_tp4(children, preset, state)
    return state["device"]


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--preset", default="mistral-7b", choices=sorted(PRESETS),
                    help="mistral-7b (default, needs a TPU) | tiny (CPU "
                         "debug mode, needs JAX_PLATFORMS=cpu)")
    ap.add_argument("--kernels", action="store_true",
                    help="run the per-kernel compile-and-compare checks "
                         "(scripts/chip_kernels.py) instead of the server")
    ap.add_argument("--legs", default="",
                    help="comma-separated subset of " + ",".join(LEGS)
                         + " (default: server,restart, then replicas,tp4 "
                           "where >=4 devices are visible; naming a leg "
                           "runs it regardless)")
    args = ap.parse_args(argv)
    children = Children()
    t0 = time.monotonic()
    try:
        dev = run(args, children)
        device = {"platform": dev["platform"], "kind": dev["device_kind"],
                  "count": dev["n_devices"]}
        if device["platform"] != PRESETS[args.preset]["platform"]:
            raise SmokeFailure(f"ran on {device}, not on "
                               f"{PRESETS[args.preset]['platform']}")
    except BaseException as e:
        children.kill_all()
        if not isinstance(e, SmokeFailure):
            traceback.print_exc()
        print(f"chip_smoke FAILED after {time.monotonic() - t0:.0f}s: "
              f"{type(e).__name__}: {e}", flush=True)
        return 1
    children.kill_all()
    say(f"chip_smoke passed in {time.monotonic() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
