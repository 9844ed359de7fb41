"""One served system under test and one measured run on it.

``Session`` starts the configuration's worker(s) and coordinator, checks the
device, primes the programs the traffic will use, and ``measure`` drives one
schedule (ramp, window, tail) through the coordinator from the client's side.
``run.py`` makes one measurement per process; ``sweep.py`` makes several on
one set-up."""

from __future__ import annotations

import asyncio
import json
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from . import families, procs, traffic
from .loadgen import Record, run_schedule, send_one
from .procs import BenchFailure, MODEL

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SAMPLE_EVERY_S = 0.5
TRACE_S = 4.0          # traced slice of the window (``--trace 1`` only)
SETTLE_S = 8.0         # a request due this long before the slice has run


def load_config(name: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    for key in ("source", "reduced", "assumed", "deployment", "serve",
                "platform"):
        if key not in cfg:
            raise ValueError(f"config {name}: missing {key!r}")
    cfg["name"] = name
    return cfg


@dataclass
class RunData:
    """Everything one measured run saw; the per-layer readers take it."""

    config: Dict[str, Any]
    mix: Dict[str, Any]
    records: List[Record]
    t_open: float
    t_close: float
    setup: Dict[str, float]
    device: Dict[str, Any]
    workers_before: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    workers_after: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    coord_before: Dict[str, Any] = field(default_factory=dict)
    coord_after: Dict[str, Any] = field(default_factory=dict)
    samples: List[Dict[str, Any]] = field(default_factory=list)
    trace_dirs: Dict[str, str] = field(default_factory=dict)
    trace: Optional[Dict[str, Any]] = None      # reduced, see tracered.py

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def judged(self) -> List[Record]:
        """Requests DUE inside the window. In a traced run, only those due
        SETTLE_S or more before the traced slice began: the profiler slows
        the host, and a request still running then would be timed under
        it."""
        cut = self.t_close
        if self.trace_dirs:
            cut -= min(TRACE_S + SETTLE_S, self.window_s / 2)
        return [r for r in self.records
                if r.req.phase == "window" and r.due < cut]

    def failures(self) -> List[str]:
        v = int(self.config["vocab_size"])
        return [f for f in (r.failure(v) for r in self.judged()) if f]

    def frames(self):
        return [fr for r in self.records for fr in r.frames]


class Session:
    def __init__(self, config: Dict[str, Any], work_dir: str, seed: int,
                 t_start: float) -> None:
        self.config = config
        self.serve = dict(config["serve"])
        self.platform = config["platform"]
        self.work_dir = work_dir
        self.seed = seed
        self.t_start = t_start
        shutil.rmtree(work_dir, ignore_errors=True)
        os.makedirs(work_dir)
        self.children = procs.Children(work_dir)
        self.worker_ports: Dict[str, int] = {}
        self.coord_port = 0
        self.client: Any = None
        self.worker_clients: Dict[str, Any] = {}
        self.device: Dict[str, Any] = {}
        self.setup: Dict[str, float] = {}

    # ------------------------------------------------------------ set-up

    def start(self) -> None:
        worker = procs.start_worker(self.children, self.serve, "w0",
                                    procs.child_env(self.platform),
                                    self.seed % (2 ** 31 - 1))
        self.worker_ports["w0"] = procs.worker_port(worker, 1100.0)
        self.setup["workers_ready_s"] = time.monotonic() - self.t_start
        self.coord_port = procs.start_coordinator(
            self.children, self.serve, self.platform, self.worker_ports)

    async def connect(self) -> None:
        from distributed_inference_engine_tpu.api.frontend import (
            CoordinatorClient,
        )
        from distributed_inference_engine_tpu.cluster.worker import (
            WorkerClient,
        )

        self.client = CoordinatorClient("127.0.0.1", self.coord_port,
                                        timeout=300.0)
        # one connection per stream in flight: the client must never be
        # what caps the offered load
        self.client.max_connections = 4096
        for wid, port in self.worker_ports.items():
            self.worker_clients[wid] = WorkerClient("127.0.0.1", port,
                                                    timeout=60.0)
        await self.check_device()

    async def check_device(self) -> None:
        """The device as the workers' JAX reports it; a worker on another
        platform, or fewer chips than the configuration asks, is fatal. The
        parameter bytes each worker holds must be what the configuration
        file's widths give by its family's count (``counts/<family>.py``
        ``param_bytes``). A tree that holds int4 tensors runs every one on
        the Mosaic kernel; one that holds none is not asked."""
        kinds, load_s, warm_s = set(), [], []
        want = families.counts(self.config).param_bytes(self.config)
        for wid, wc in self.worker_clients.items():
            m = await wc.metrics()
            dev = m.get("device") or {}
            if dev.get("platform") != self.platform:
                raise BenchFailure(f"{wid} runs on {dev.get('platform')!r}, "
                                   f"the cell needs {self.platform!r}")
            kinds.add(dev["device_kind"])
            place = dev["models"][MODEL]
            if abs(place["param_bytes"] - want) > 0.001 * want:
                raise BenchFailure(
                    f"{wid} holds {place['param_bytes']} parameter bytes; "
                    f"the configuration file's widths give {want}")
            int4 = place.get("int4_paths") or {}
            if self.platform == "tpu" and int4.get("xla"):
                raise BenchFailure(f"{wid}: int4 matmuls off the Mosaic "
                                   f"kernel: {int4}")
            setup = m["model_setup"][MODEL]
            load_s.append(setup["load_s"] - setup["warmup_s"])
            warm_s.append(setup["warmup_s"])
        if len(kinds) != 1:
            raise BenchFailure(f"workers on different devices: {kinds}")
        self.device = {"platform": self.platform, "kind": kinds.pop(),
                       "count": len(self.worker_clients)}
        self.setup["load_s"] = max(load_s)
        self.setup["warmup_s"] = max(warm_s)

    async def ask(self, reqs: List[traffic.Request], tag: str
                  ) -> List[Record]:
        """Send requests now, together, outside any schedule; a reply that
        is not exactly what was asked is fatal."""
        recs = [Record(req=r, due=time.monotonic()) for r in reqs]
        await asyncio.gather(*(send_one(self.client, rec, tag)
                               for rec in recs))
        for rec in recs:
            bad = rec.failure(int(self.config["vocab_size"]))
            if bad:
                raise BenchFailure(f"{tag} request {rec.req.index}: {bad}")
        return recs

    async def prime(self) -> None:
        """Touch, through the served path, every decode program the traffic
        uses that the engine's own warm-up grid (admission batch x prefill
        bucket, two tokens each) does not reach: one request alone per pow2
        context-page bucket, then eight at once (a batched admission
        over live slots). The first request is sent twice: a prompt decoded
        alone must give the same greedy tokens both times."""
        t0 = time.monotonic()
        rng = traffic.token_rng("prime", self.seed)
        vocab = int(self.config["vocab_size"])
        page = int(self.serve["page_size"])
        max_pages = int(self.serve["max_seq_len"]) // page
        out_len = 24

        def req(i: int, n_prompt: int, n_out: int = out_len):
            return traffic.Request(i, "prime", 0.0, [
                rng.randrange(1, vocab) for _ in range(n_prompt)], n_out)

        longest = int(self.serve["max_seq_len"]) - out_len - 1
        singles = [req(0, page // 2)]
        singles.append(traffic.Request(1, "prime", 0.0,
                                       list(singles[0].prompt), out_len))
        pages = 2
        while pages <= max_pages:
            # a prompt that ends inside the bucket's last page
            singles.append(req(len(singles),
                               min((pages // 2) * page + page // 4,
                                   longest)))
            pages *= 2
        recs = [(await self.ask([r], "prime"))[0] for r in singles]
        await self.ask([req(100 + i, min(page // 2 + 37 * i, longest), 16)
                        for i in range(8)], "prime")
        if recs[0].tokens != recs[1].tokens:
            raise BenchFailure("one prompt decoded alone twice gave "
                               "different greedy tokens")
        self.setup["prime_s"] = time.monotonic() - t0

    # ----------------------------------------------------------- measure

    async def _snapshot(self) -> Dict[str, Any]:
        return {"t": time.monotonic(),
                "workers": await self._worker_metrics(),
                "coord": await self.client.stats()}

    async def _sample_loop(self, t_open: float, t_close: float,
                           out: List[Dict[str, Any]]) -> None:
        await asyncio.sleep(max(0.0, t_open - time.monotonic()))
        while time.monotonic() < t_close:
            out.append(await self._snapshot())
            await asyncio.sleep(SAMPLE_EVERY_S)

    async def _worker_metrics(self) -> Dict[str, Any]:
        return {wid: await wc.metrics()
                for wid, wc in self.worker_clients.items()}

    async def _trace_slice(self, t_open: float, window_s: float,
                           dirs: Dict[str, str]) -> None:
        """Profile every worker for the window's last TRACE_S seconds (only
        the process that holds a chip can trace it). At the end, because
        tracing slows the host and writing the trace out stalls the worker
        for seconds: both then fall after what the host-clock readers
        judge (``RunData.judged``)."""
        start = t_open + max(0.0, window_s - TRACE_S)
        await asyncio.sleep(max(0.0, start - time.monotonic()))
        for wid, wc in self.worker_clients.items():
            d = os.path.join(self.work_dir, f"trace-{wid}")
            await wc.call("profile", action="start", trace_dir=d)
            dirs[wid] = d
        await asyncio.sleep(min(TRACE_S, window_s))
        for wc in self.worker_clients.values():
            await wc.call("profile", action="stop", timeout=120.0)

    async def measure(self, mix: Dict[str, Any], seed: int, window_s: float,
                      trace: bool, rate_rps: float = 0.0,
                      tag: str = "r", sample: bool = False) -> RunData:
        reqs = traffic.schedule(mix, seed, window_s,
                                int(self.config["vocab_size"]), rate_rps)
        ramp_s = float(mix["ramp_s"])
        t_open = time.monotonic() + ramp_s + 0.05
        t_close = t_open + window_s
        samples: List[Dict[str, Any]] = []
        dirs: Dict[str, str] = {}
        side: List["asyncio.Task[None]"] = []
        if trace or sample:
            side.append(asyncio.ensure_future(
                self._sample_loop(t_open, t_close, samples)))
        if trace:
            side.append(asyncio.ensure_future(
                self._trace_slice(t_open, window_s, dirs)))

        async def bracket() -> Dict[str, Any]:
            """Counters at the window's edges."""
            await asyncio.sleep(max(0.0, t_open - time.monotonic()))
            before = await self._snapshot()
            await asyncio.sleep(max(0.0, t_close - time.monotonic()))
            return {"before": before, "after": await self._snapshot()}

        edge = asyncio.ensure_future(bracket())
        records = await run_schedule(self.client, reqs, t_open, tag,
                                     drain_timeout_s=120.0)
        edges = await edge
        for t in side:
            await t
        setup = dict(self.setup, ramp_s=ramp_s,
                     setup_s=t_open - self.t_start)
        return RunData(
            config=self.config, mix=mix, records=records, t_open=t_open,
            t_close=t_close, setup=setup, device=dict(self.device),
            workers_before=edges["before"]["workers"],
            workers_after=edges["after"]["workers"],
            coord_before=edges["before"]["coord"],
            coord_after=edges["after"]["coord"],
            samples=samples, trace_dirs=dirs)

    async def peak_memory_bytes(self) -> int:
        """``peak_bytes_in_use`` on the fullest chip, over all workers."""
        peak = 0
        for wc in self.worker_clients.values():
            mem = ((await wc.metrics()).get("device") or {}).get("memory")
            for stats in (mem or {}).values():
                peak = max(peak, int((stats or {}).get(
                    "peak_bytes_in_use", 0)))
        return peak

    async def wait_idle(self, timeout_s: float = 120.0) -> None:
        """Until no worker has a request in flight (between sweep rates)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            ms = await self._worker_metrics()
            if all(m["pumps"][MODEL]["in_flight"] == 0
                   for m in ms.values()):
                return
            await asyncio.sleep(0.5)
        raise BenchFailure("workers still busy after the drain")

    async def disconnect(self) -> None:
        for c in [self.client, *self.worker_clients.values()]:
            if c is not None:
                await c.close()

    def stop(self) -> None:
        self.children.stop_all()
