"""Worker: the framed RPC server hosting inference engines on a TPU-VM.

Capability heir of the reference's ``src/worker.py``: an asyncio TCP server
with model load/unload lifecycle (``src/worker.py:164-184``), per-request
logging (``:126-133``), process + per-model metrics (``:186-209``), signal
handling (``:44-49``) and OS-assigned ports (``:58-59``). Three reference
defects are deliberately fixed (SURVEY.md §2.4, §5):

- **Framing.** The reference reads a single ``read(4096)`` per request
  (``src/worker.py:93``), silently truncating large payloads. Here every
  message is a length-prefixed frame (``utils/framing.py``).
- **Persistent connections.** The reference closes after one request
  (``src/worker.py:117-124``); this server loops frames on one connection,
  so the coordinator keeps a warm connection pool instead of paying a TCP
  handshake per request.
- **Probe pollution.** Reference health probes inflate the worker's request
  counter (``src/worker.py:87``) and the LB's latency stats
  (``src/load_balancer.py:334-339``). Here ``ping`` is a distinct method
  counted separately from ``generate``.

The engine behind each model is real JAX (``engine.Engine``) or the fake
(``models/fake.FakeEngine``) per ``ModelConfig.architecture``. Engine calls
are synchronous XLA dispatches, so they run on a single-thread executor:
the event loop stays responsive for pings while the device crunches, and
device access is serialized (one program on the chip at a time).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import logging
import os
import signal
import time
from typing import Any, Awaitable, Callable, Dict, List, Optional, Tuple

from ..config import ModelConfig, ServerConfig
from ..engine.types import GenerationRequest, GenerationResult
from .model_manager import ModelManager, ModelProbeError, ModelStageError
from ..utils.files import atomic_write_json
from ..utils.framing import FrameError, read_frame, write_frame
from ..utils.rpc import (
    FramedRPCClient,
    FramedServerMixin,
    RPCError,
    pool_for_slots,
    relay_stream,
)
from ..obs import collectors as obs_collectors
from ..obs.events import EventLog
from ..obs.timeline import clock_anchor
from ..obs.registry import OPENMETRICS_CONTENT_TYPE, MetricsRegistry
from ..utils.tracing import LatencyStats

logger = logging.getLogger(__name__)

# machine-readable error class for the disaggregated relay: the decode peer
# could not be reached / died mid-decode. The coordinator reacts by marking
# the DECODE worker and retrying on an alternate shard (the prefill worker
# that reports this is itself healthy).
DECODE_PEER_UNREACHABLE = "decode_peer_unreachable"


class DecodePeerError(RuntimeError):
    """Transport failure between a prefill worker and its decode peer."""

    rpc_error_kind = DECODE_PEER_UNREACHABLE


class WorkerDrainingError(RuntimeError):
    """Admission refused: this worker is draining (finishing in-flight work
    before removal). Wire kind is ``overloaded`` with detail ``draining`` so
    the coordinator's existing shed machinery retries on an alternate replica
    — and, because sheds bypass health accounting, the drain doesn't dent
    this worker's health while it finishes."""

    rpc_error_kind = "overloaded"
    rpc_error_detail = "draining"


# --------------------------------------------------------------------------
# request/result wire marshalling (token-id space; tokenization is a client/
# coordinator concern)

def request_to_dict(r: GenerationRequest) -> Dict[str, Any]:
    return {
        "prompt": list(r.prompt),
        "max_new_tokens": r.max_new_tokens,
        "temperature": r.temperature,
        "top_k": r.top_k,
        "top_p": r.top_p,
        "min_p": r.min_p,
        "request_id": r.request_id,
        "eos_id": r.eos_id,
        "stop_ids": list(r.stop_ids),
        "stop_sequences": [list(s) for s in r.stop_sequences],
        "deadline_s": r.deadline_s,
    }


def request_from_dict(d: Dict[str, Any]) -> GenerationRequest:
    return GenerationRequest(
        prompt=list(d["prompt"]),
        max_new_tokens=int(d.get("max_new_tokens", 16)),
        temperature=float(d.get("temperature", 0.0)),
        top_k=int(d.get("top_k", 0)),
        top_p=float(d.get("top_p", 1.0)),
        min_p=float(d.get("min_p", 0.0)),
        request_id=str(d.get("request_id", "")),
        eos_id=int(d.get("eos_id", -1)),
        stop_ids=[int(t) for t in d.get("stop_ids", [])],
        stop_sequences=[[int(t) for t in s]
                        for s in d.get("stop_sequences", [])],
        deadline_s=(float(d["deadline_s"])
                    if d.get("deadline_s") is not None else None),
    )


def result_to_dict(r: GenerationResult) -> Dict[str, Any]:
    return {
        "request_id": r.request_id,
        "tokens": list(r.tokens),
        "finish_reason": r.finish_reason,
        "prompt_tokens": r.prompt_tokens,
        "logprobs": [float(x) for x in r.logprobs],
        "ttft_s": r.ttft_s,
        "decode_s": r.decode_s,
        "metadata": dict(r.metadata),
    }


def result_from_dict(d: Dict[str, Any]) -> GenerationResult:
    return GenerationResult(
        request_id=str(d.get("request_id", "")),
        tokens=list(d.get("tokens", [])),
        finish_reason=str(d.get("finish_reason", "")),
        prompt_tokens=int(d.get("prompt_tokens", 0)),
        logprobs=[float(x) for x in d.get("logprobs", [])],
        ttft_s=float(d.get("ttft_s", 0.0)),
        decode_s=float(d.get("decode_s", 0.0)),
        metadata=dict(d.get("metadata", {})),
    )


# --------------------------------------------------------------------------
# engine factory

def build_engine(cfg: ModelConfig):
    """Default engine factory — delegates to the single shared
    config-driven factory (``models.engine_from_config``); imported lazily
    so jax-free control planes can import this module."""
    from ..models import engine_from_config

    return engine_from_config(cfg)


EngineFactory = Callable[[ModelConfig], Any]


_born_at: Optional[float] = None    # perf_counter when the process began


def process_born_at() -> Optional[float]:
    """This process's start on ``perf_counter``: what turns the ``boot``
    marks (``perf_counter`` stamps) into seconds since the operating
    system started the process. Read once, and only when a ``metrics``
    reply needs it. From ``/proc`` (start time against uptime, to a clock
    tick) where there is one: psutil's ``create_time()`` adds the start to
    a boot time of whole seconds and read 0.6 s off here. ``None`` without
    either."""
    global _born_at
    if _born_at is None:
        try:
            with open("/proc/self/stat") as f:
                ticks = float(f.read().rsplit(")", 1)[1].split()[19])
            with open("/proc/uptime") as f:
                age = float(f.read().split()[0]) - ticks / os.sysconf(
                    "SC_CLK_TCK")
        # graftlint: ok[swallowed-transport-error] a file of /proc, no peer involved; psutil answers where there is none
        except (OSError, ValueError, IndexError):
            try:
                import psutil
            except ImportError:
                return None
            age = time.time() - psutil.Process().create_time()
        _born_at = time.perf_counter() - age
    return _born_at


def warmup_line(warm: Dict[str, Any]) -> str:
    """An engine's warm-up totals on one line, for the worker's own log."""
    if not warm.get("rounds"):
        return ""
    return (" [trace {trace_s:.2f}s lower {lower_s:.2f}s compile "
            "{compile_s:.2f}s (cache reads {cache_retrieval_s:.2f}s, "
            "{cache_hits} hits, {cache_misses} misses) run {run_s:.2f}s]"
            .format(**warm))


def _model_identity(cfg: ModelConfig):
    """The fields that determine WHICH model an engine serves. Engine-impl
    knobs (continuous mode, page sizes, batch limits, schemas) are worker-
    local choices and deliberately excluded — see ``load_model``."""
    return (cfg.name, cfg.version, cfg.architecture, cfg.path, cfg.dtype,
            cfg.quantized, str(cfg.metadata.get("size", "")))


def _engine_features(cfg: ModelConfig) -> frozenset:
    """The RPC surface an engine config provides. Idempotent re-load is
    allowed only when the hosted engine provides a SUPERSET of what the new
    deploy needs — unlike the engine knobs ``_model_identity`` ignores, a
    missing feature silently blackholes a pool's traffic (e.g. a static
    engine in a decode pool can't serve ``generate_prefilled``). The check
    is directional: a continuous preload is a fine target for a plain
    deploy, the reverse is not."""
    if cfg.metadata.get("role") == "prefill":
        return frozenset({"prefill"})
    if cfg.metadata.get("continuous"):
        return frozenset({"generate", "generate_prefilled"})
    return frozenset({"generate"})


def _stop_trace_xplane_only() -> None:
    """``jax.profiler.stop_trace`` without the legacy ``trace.json.gz``:
    the profiler session's ``stop_and_export`` writes the ``.xplane.pb``
    (what XProf, ``jax.profiler.ProfileData`` and every reader here load)
    AND converts it to a trace-viewer JSON nothing reads, which for a 4 s
    slice of 1.5 M device events took 120 of the export's 182 s (PERF.md
    section 6, PR 27). Falls back to ``stop_trace`` where jax's internals
    are not as expected."""
    import socket

    import jax
    from jax._src import profiler as _jp

    from ..utils.files import atomic_write

    state = getattr(_jp, "_profile_state", None)
    sess = getattr(state, "profile_session", None)
    if sess is None or not hasattr(sess, "stop"):
        jax.profiler.stop_trace()
        return
    with state.lock:
        xspace = sess.stop()
        run_dir = os.path.join(str(state.log_dir), "plugins", "profile",
                               time.strftime("%Y_%m_%d_%H_%M_%S"))
        os.makedirs(run_dir, exist_ok=True)
        atomic_write(os.path.join(
            run_dir, f"{socket.gethostname()}.xplane.pb"),
            lambda f: f.write(xspace), binary=True)
        state.reset()


def _engine_placement(engine) -> Dict[str, Any]:
    """Where an engine's params live — device ids (with the chips' mesh
    coordinates where the backend has them), the bytes resident on each,
    the tree's logical bytes — and its int4 kernel paths. Empty for
    engines that hold no params (the fakes)."""
    params = getattr(engine, "params", None)
    if params is None:
        return {}
    import jax

    from ..ops.quant import (
        int4_kernel_blocks,
        int4_kernel_paths,
        param_bytes,
    )

    by_device: Dict[int, int] = {}
    coords: Dict[int, Any] = {}
    for leaf in jax.tree_util.tree_leaves(params):
        for shard in getattr(leaf, "addressable_shards", ()):
            d = shard.device
            by_device[d.id] = by_device.get(d.id, 0) + shard.data.nbytes
            coords[d.id] = getattr(d, "coords", None)
    ids = sorted(by_device)
    return {"device_ids": ids,
            "coords": [coords[i] for i in ids],
            "param_bytes": param_bytes(params),
            "param_bytes_by_device": {str(i): by_device[i] for i in ids},
            "int4_paths": int4_kernel_paths(params),
            # the (bk, bn) each kernel-borne int4 shape streams in
            "int4_blocks": int4_kernel_blocks(params),
            # the attention path the engine resolved "auto" to
            "decode_attention": getattr(engine, "attn_impl", None),
            # the body that moves a recurrent family's per-slot state in a
            # decode step ("inplace": the kernel; None: no such state)
            "state_step_body": getattr(engine, "state_step_body", None),
            # requests the engine runs at once (the coordinator's pool to
            # this worker follows it) and the residual a per-layer spec has
            # around its sublayers ("mhc": hyper-connection streams)
            "slots": _engine_slots(engine),
            "residual": getattr(getattr(engine, "spec", None), "residual",
                                None)}


def _engine_slots(engine) -> Optional[int]:
    slots = getattr(engine, "max_slots", None)
    return int(slots) if slots else None


# --------------------------------------------------------------------------
# server

class WorkerServer(FramedServerMixin):
    """Framed-RPC worker host (heir of reference ``Worker``, src/worker.py:26-209).

    Connection loop + dispatch envelope live in ``FramedServerMixin``
    (shared with ``CoordinatorServer``); this class supplies the worker
    policy via the mixin hooks."""

    def __init__(
        self,
        config: Optional[ServerConfig] = None,
        engine_factory: EngineFactory = build_engine,
    ) -> None:
        self.config = config or ServerConfig()
        self.worker_id = self.config.worker_id
        self.engine_factory = engine_factory
        # multi-model residency (cluster/model_manager.py): the manager
        # owns the resident set + staging/swap/eviction policy; the worker
        # aliases its dicts so every RPC path reads the same state
        self.model_manager = ModelManager(
            self._build_engine,
            max_resident_models=self.config.max_resident_models,
            resident_bytes=self.config.resident_bytes,
            busy_fn=self._model_busy,
            on_evict=self._on_model_evicted,
        )
        self.engines: Dict[str, Any] = self.model_manager.engines
        self.model_configs: Dict[str, ModelConfig] = self.model_manager.configs
        self._pumps: Dict[str, Any] = {}    # model -> EnginePump (continuous)
        self._server: Optional[asyncio.base_events.Server] = None
        self._conn_writers: set = set()
        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"{self.worker_id}-engine"
        )
        self._started_at = 0.0
        self._shutdown_event = asyncio.Event()
        # generate-path counters, kept apart from probe counters (see module doc)
        self._request_count = 0
        self._error_count = 0
        self._overloaded_count = 0     # load sheds, apart from real errors
        self._handoff_bytes_shipped = 0  # relay KV actually sent (deltas
                                         # make this < prefill engine's
                                         # total_handoff_bytes)
        self._ping_count = 0
        self._active_connections = 0
        # graceful drain: when set, admission verbs refuse new work (typed
        # as a "draining" shed) while in-flight requests run to completion
        self._draining = False
        self._busy = 0                 # admission RPCs currently executing
        self._drain_count = 0
        self._deadline_expired_count = 0
        self.latency = LatencyStats()
        # elastic lifecycle (engine/artifact.py): engine-construction wall
        # time per load_model, and whether each artifact-configured load
        # actually cold-started from its artifact (hit) or fell back to
        # from-scratch init (miss) — the respawn-latency receipts
        self.model_load_stats = LatencyStats()
        self._last_load_s: Dict[str, float] = {}
        self._last_warmup_s: Dict[str, float] = {}
        # ``perf_counter`` when this process first passed each point of
        # its start-up (``mark_boot``; ``cli.worker`` adds the ones before
        # this object exists); ``boot_report`` turns them into seconds
        # since the process started
        self.boot: Dict[str, float] = {}
        # where each real (jax) engine's params landed — platform, device
        # kind, device ids, int4 kernel paths — so a deploy can ASSERT its
        # placement instead of inferring it (see device_report)
        self._placements: Dict[str, Dict[str, Any]] = {}
        self._artifact_hits = 0
        self._artifact_misses = 0
        # KV fabric (engine/kv_fabric.py): pages migrated in/out of this
        # worker's host tier over the kv_export/kv_import verbs
        self._kv_fabric_exports = 0
        self._kv_fabric_imports = 0
        self._kv_fabric_export_bytes = 0
        self._kv_fabric_import_bytes = 0
        self._kv_fabric_import_fallbacks = 0
        self._methods: Dict[str, Callable[[Dict[str, Any]], Awaitable[Any]]] = {
            "ping": self._rpc_ping,
            "generate": self._rpc_generate,
            "prefill": self._rpc_prefill,
            "generate_prefilled": self._rpc_generate_prefilled,
            "prefill_generate": self._rpc_prefill_generate,
            "prefix_probe": self._rpc_prefix_probe,
            "kv_export": self._rpc_kv_export,
            "kv_import": self._rpc_kv_import,
            "load_model": self._rpc_load_model,
            "stage_model": self._rpc_stage_model,
            "swap_model": self._rpc_swap_model,
            "resident_models": self._rpc_resident_models,
            "unload_model": self._rpc_unload_model,
            "list_models": self._rpc_list_models,
            "metrics": self._rpc_metrics,
            "metrics_text": self._rpc_metrics_text,
            "profile": self._rpc_profile,
            "drain": self._rpc_drain,
            "shutdown": self._rpc_shutdown,
            "events": self._rpc_events,
        }
        # flight recorder (obs/events.py): bounded typed event ring,
        # collected on demand over the ``events`` verb and merged into the
        # coordinator's fleet trace
        self.events = EventLog(self.worker_id,
                               capacity=self.config.event_ring_capacity)
        # unified telemetry: this worker's dict metrics (incl. every loaded
        # engine's) mirrored into stable metric families at scrape time,
        # exposed as OpenMetrics text via the metrics_text RPC verb and
        # plain-HTTP GET /metrics on the same port (utils/rpc.py sniff)
        self.obs_registry = MetricsRegistry()
        obs_collectors.ensure_families(self.obs_registry)
        self.obs_registry.add_collector(self._obs_collect)
        # streaming methods write chunk frames ahead of the final envelope
        self._stream_methods = {
            "generate_stream": self._rpc_generate_stream,
        }
        self._profiling_dir: Optional[str] = None
        self._profile_counters: Dict[str, Any] = {}
        self._profile_lock = asyncio.Lock()     # one start/stop at a time
        # prefill-pool side: persistent clients to decode-pool peers,
        # keyed by (host, port) — the KV handoff goes peer-to-peer over
        # DCN, not back through the coordinator
        self._peer_clients: Dict[Tuple[str, int], "WorkerClient"] = {}

    # -- lifecycle ---------------------------------------------------------

    @property
    def address(self) -> Tuple[str, int]:
        if self._server is None:
            raise RuntimeError("worker not started")
        sock = self._server.sockets[0]
        host, port = sock.getsockname()[:2]
        return host, port

    def mark_boot(self, name: str) -> None:
        """Note when start-up first passed ``name`` (a later pass — a
        second model's load — leaves the mark where it is)."""
        self.boot.setdefault(name, time.perf_counter())

    def boot_report(self) -> Dict[str, float]:
        """The marks as seconds since the operating system started this
        process; empty where that start cannot be read."""
        born = process_born_at()
        if born is None:
            return {}
        return {name: at - born for name, at in self.boot.items()}

    async def start(self, install_signal_handlers: bool = False) -> Tuple[str, int]:
        self._server = await asyncio.start_server(
            self._handle_connection, self.config.host, self.config.port
        )
        self._started_at = time.time()
        self.mark_boot("listening")
        if install_signal_handlers:
            loop = asyncio.get_running_loop()
            for sig in (signal.SIGINT, signal.SIGTERM):
                loop.add_signal_handler(sig, self._shutdown_event.set)
        host, port = self.address
        if self.fault_plan is not None:
            # flight recorder: record injections aimed at THIS worker in
            # its own event ring (the plan is shared fleet-wide)
            self.fault_plan.subscribe(self._on_injected_fault)
        logger.info("worker %s listening on %s:%d", self.worker_id, host, port)
        return host, port

    def _on_injected_fault(self, fault) -> None:
        """FaultPlan listener: mirror injections scoped to this worker
        into the event ring (the plan notifies on every injection)."""
        if fault.scope == self._fault_scope():
            self.events.emit("fault.injected", site=fault.site,
                             verb=fault.verb, kind=fault.kind,
                             ordinal=fault.ordinal)

    async def stop(self) -> None:
        if self.fault_plan is not None:
            self.fault_plan.unsubscribe(self._on_injected_fault)
        if self._server is not None:
            self._server.close()
            # persistent connections never exit on their own — close them, or
            # wait_closed() (which awaits all handlers on py3.12+) never returns
            self._close_all_connections()
            await self._server.wait_closed()
            self._server = None
        for pump in self._pumps.values():
            pump.shutdown_nowait()
        for client in self._peer_clients.values():
            await client.close()
        self._peer_clients.clear()
        self._executor.shutdown(wait=False, cancel_futures=True)
        self._shutdown_event.set()
        logger.info("worker %s stopped", self.worker_id)

    async def serve_forever(self) -> None:
        """Run until shutdown RPC or signal (reference src/worker.py:243-244)."""
        await self._shutdown_event.wait()
        await self.stop()

    # -- model lifecycle (reference src/worker.py:164-184) ------------------

    def _build_engine(self, cfg: ModelConfig):
        """Factory + artifact accounting + warmup — the full engine build,
        shared by the cold ``load_model`` path and the background staging
        thread (so a staged engine arrives pre-warmed: the swap installs
        it, it never compiles on the serving clock)."""
        engine = self.engine_factory(cfg)
        artifact_hit = getattr(engine, "artifact_manifest", None) is not None
        if cfg.metadata.get("artifact"):
            if artifact_hit:
                self._artifact_hits += 1
            else:
                self._artifact_misses += 1
        if cfg.metadata.get("warmup") and hasattr(engine, "warmup"):
            # pre-compile the serving programs at load time so the first
            # real request doesn't pay the XLA compile (metadata warmup=1).
            # An artifact cold-start warms only the bucket shapes its
            # writer recorded — the respawn path compiles what the dead
            # worker actually served, not the full grid.
            t0 = time.perf_counter()
            if artifact_hit and hasattr(engine, "warmup_from_manifest"):
                n = engine.warmup_from_manifest()
            else:
                n = engine.warmup()
            self._last_warmup_s[cfg.name] = time.perf_counter() - t0
            logger.info("worker %s warmed %s (%d rounds, %.2fs)",
                        self.worker_id, cfg.name, n,
                        self._last_warmup_s[cfg.name])
        return engine

    @staticmethod
    def _engine_warmup(engine) -> Dict[str, Any]:
        """An engine's own account of its warm-up grid (``ContinuousEngine.
        get_metrics()["warmup"]``); empty for an engine that keeps none."""
        rounds = getattr(engine, "warmup_metrics", None)
        return rounds() if rounds is not None else {}

    def _model_busy(self, name: str) -> bool:
        """Eviction guard: a model with queued or decoding work is pinned
        resident — evicting it would drop in-flight generations."""
        pump = self._pumps.get(name)
        if pump is not None and pump.get_stats().get("in_flight", 0) > 0:
            return True
        engine = self.engines.get(name)
        if engine is not None and (getattr(engine, "n_live", 0)
                                   or getattr(engine, "n_waiting", 0)):
            return True
        return False

    def _on_model_evicted(self, name: str, engine) -> None:
        pump = self._pumps.pop(name, None)
        if pump is not None:
            pump.shutdown_nowait()
        logger.info("worker %s evicted model %s (resident budget)",
                    self.worker_id, name)

    def _install_engine(self, cfg: ModelConfig, engine) -> None:
        """Admit a built engine into the resident set (budget-evicting idle
        LRU models) and give continuous engines their rolling-batch pump."""
        self.model_manager.admit(cfg, engine)
        self._placements[cfg.name] = _engine_placement(engine)
        if hasattr(engine, "submit") and hasattr(engine, "step"):
            from ..serving.pump import EnginePump

            self._pumps[cfg.name] = EnginePump(
                engine, event_log=self.events, model=cfg.name)

    def _check_idempotent(self, cfg: ModelConfig) -> bool:
        """True when ``cfg`` is already loaded with a compatible config;
        raises on an identity/feature mismatch (silently serving mismatched
        weights corrupts placement)."""
        if cfg.name not in self.engines:
            return False
        # idempotent when the MODEL IDENTITY matches (a worker preloaded
        # via CLI is a valid deploy target even if its engine knobs —
        # continuous, page sizes, batcher limits — differ from the deploy
        # request's defaults); a different identity is a real error
        have = self.model_configs[cfg.name]
        if _model_identity(have) != _model_identity(cfg):
            raise ValueError(
                f"model {cfg.name!r} already loaded with a different config"
            )
        need, got = _engine_features(cfg), _engine_features(have)
        if not need <= got:
            raise ValueError(
                f"model {cfg.name!r} already loaded with features "
                f"{sorted(got)} but this deploy needs {sorted(need)} "
                "— unload it first"
            )
        return True

    def load_model(self, cfg: ModelConfig) -> None:
        if self._check_idempotent(cfg):
            logger.info("worker %s: model %s already loaded (idempotent)",
                        self.worker_id, cfg.name)
            self.model_manager.touch(cfg.name)
            return
        t0 = time.perf_counter()
        engine = self._build_engine(cfg)
        artifact_hit = getattr(engine, "artifact_manifest", None) is not None
        self._install_engine(cfg, engine)
        load_s = time.perf_counter() - t0
        self.model_load_stats.add(load_s)
        self._last_load_s[cfg.name] = load_s
        logger.info("worker %s loaded model %s (%s) in %.2fs%s",
                    self.worker_id, cfg.name, cfg.architecture, load_s,
                    " [artifact cold-start]" if artifact_hit else "")

    async def load_model_async(self, cfg: ModelConfig) -> None:
        """Load off the event loop, on the single engine thread — serializes
        with in-flight generates (one program on the chip at a time) and two
        concurrent loads of the same name can't race the already-loaded
        check. Used by both the RPC handler and the CLI."""
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(self._executor, self.load_model, cfg)

    def unload_model(self, name: str) -> bool:
        engine = self.model_manager.remove(name)
        pump = self._pumps.pop(name, None)
        if pump is not None:
            pump.shutdown_nowait()
        if engine is None:
            return False
        logger.info("worker %s unloaded model %s", self.worker_id, name)
        return True

    # -- background staging + hot swap (cluster/model_manager.py) -----------

    def _serving_steps(self) -> int:
        """Total pump steps across every resident continuous engine — the
        step-timeline clock staging overlap is accounted against."""
        return sum(int(p.get_stats().get("steps", 0))
                   for p in self._pumps.values())

    def stage_model(self, cfg: ModelConfig):
        """Begin staging ``cfg`` in the background (side thread; the
        serving pumps keep dispatching). Idempotent while in flight; a
        no-op returning None when the model is already resident."""
        if cfg.name in self.engines and self._check_idempotent(cfg):
            return None
        return self.model_manager.stage(cfg,
                                        serving_steps=self._serving_steps)

    def swap_model(self, name: str,
                   probe_expected: Optional[List[int]] = None,
                   timeout: Optional[float] = None) -> Dict[str, Any]:
        """Activate a staged model: wait for its build, golden-gate it,
        admit it (budget-evicting idle LRU models), give it a pump.
        Synchronous — call off the event loop."""
        receipt = self.model_manager.swap(name, probe_expected=probe_expected,
                                          timeout=timeout)
        if not receipt.get("already_resident"):
            engine = self.engines[name]
            cfg = self.model_configs[name]
            self._placements[name] = _engine_placement(engine)
            if hasattr(engine, "submit") and hasattr(engine, "step"):
                from ..serving.pump import EnginePump

                self._pumps[name] = EnginePump(
                    engine, event_log=self.events, model=name)
        return receipt

    # -- connection handling (loop + envelope in FramedServerMixin) -----------

    @property
    def max_frame_bytes(self) -> int:
        return self.config.max_frame_bytes

    async def _handle_connection(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        peer = writer.get_extra_info("peername")
        self._active_connections += 1
        try:
            await super()._handle_connection(reader, writer)
        finally:
            self._active_connections -= 1
            logger.debug("worker %s connection from %s closed",
                         self.worker_id, peer)

    async def _run_handler(self, method: str, handler, msg) -> Any:
        # generate/load_model legitimately run for minutes (first-call XLA
        # compile, checkpoint load) — their deadline belongs to the caller.
        # The server-side timeout only guards the cheap control methods.
        # drain carries its own timeout_s in the message; profile's stop
        # writes a trace out (tens of seconds under load).
        if method in ("generate", "load_model", "swap_model", "prefill",
                      "generate_prefilled", "prefill_generate", "drain",
                      "profile"):
            return await handler(msg)
        return await asyncio.wait_for(
            handler(msg), timeout=self.config.request_timeout
        )

    def _envelope_extra(self) -> Dict[str, Any]:
        return {"worker_id": self.worker_id}

    def _timeout_error(self, method: str) -> str:
        # only control methods are wait_for-wrapped, so a timeout is probe
        # trouble, not a generate failure — it stays out of _error_count
        return f"request timed out after {self.config.request_timeout}s"

    def _on_handler_error(self, method: str, exc: Exception) -> None:
        if method in ("generate", "generate_stream"):
            # load sheds are the engine WORKING as configured, not a fault:
            # counting them would let sustained overload trip the same
            # error-rate signals a sick worker trips
            kind = getattr(exc, "rpc_error_kind", "")
            if kind == "overloaded":
                self._overloaded_count += 1
                return
            if kind == "deadline":
                # caller-imposed budget expired in OUR queue — policy, not
                # a fault; it has its own counter so dashboards can see it
                self._deadline_expired_count += 1
                return
            self._error_count += 1

    def _after_dispatch(self, method: str, req_id: str,
                        duration_s: float, response: Dict[str, Any]) -> None:
        if method in ("generate", "generate_stream"):
            self.latency.add(duration_s)
            logger.info("worker %s: %s id=%s %.1fms ok=%s",
                        self.worker_id, method, req_id, duration_s * 1e3,
                        response["success"])

    # -- RPC methods ---------------------------------------------------------

    async def _rpc_ping(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        self._ping_count += 1
        # "mono": this process's perf_counter — the coordinator's clock-sync
        # pairs it with its own send/recv stamps (obs/clocksync.py)
        return {"worker_id": self.worker_id, "time": time.time(),
                "mono": time.perf_counter(),
                "models": sorted(self.engines),
                "staged": self.model_manager.staged_names(),
                "draining": self._draining,
                **self.capacity_report(),
                "device": self.device_report()}

    def _admit(self) -> None:
        """Admission gate for work-carrying verbs (generate/prefill family):
        a draining worker refuses new work with the typed draining shed."""
        if self._draining:
            raise WorkerDrainingError(
                f"worker {self.worker_id} is draining — retry on another "
                "replica")

    def _attach_worker_trace(self, result: GenerationResult, t_recv: float,
                             t_first_frame_sent: Optional[float] = None
                             ) -> None:
        """Worker-side phase marks, riding the result's metadata back to
        the coordinator (cross-process tracing: ISSUE 4 leg 3). Offsets
        are seconds RELATIVE TO THIS WORKER'S RECEIVE TIME, all from this
        process's ``perf_counter`` — the two processes share no clock, so
        the coordinator anchors them at the moment it had a connection
        (``RequestTrace.add_offsets``). A continuous engine hands its own
        stamps up on the result: ``submitted`` (pump thread, at
        ``engine.submit()``), ``admitted`` (slot held, prefill dispatched)
        and ``first_token`` (first token on the host). An engine that
        stamps nothing (static, fake) reports ``first_token`` as its
        ``ttft_s``, which it counts from the dispatch it ran at receive."""
        offsets = {"received": 0.0}
        for phase in ("submitted", "admitted", "first_token"):
            if phase in result.stamps:
                offsets[phase] = result.stamps[phase] - t_recv
        offsets.setdefault("first_token", float(result.ttft_s))
        if t_first_frame_sent is not None:
            offsets["first_frame_sent"] = t_first_frame_sent - t_recv
        offsets["done"] = time.perf_counter() - t_recv
        result.metadata.setdefault("worker_trace", {
            "worker_id": self.worker_id, "offsets": offsets})

    async def _rpc_generate(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        t_recv = time.perf_counter()
        self._admit()
        name, engine = self._engine_for(msg, "generate")
        reqs = [request_from_dict(d) for d in msg.get("requests", [])]
        if not reqs:
            raise ValueError("empty 'requests'")
        self._request_count += 1
        self._busy += 1
        try:
            pump = self._pumps.get(name)
            if pump is not None:
                # continuous engine: requests join the rolling decode batch —
                # concurrent connections share chunks instead of serializing
                # whole generations behind the executor
                results = await pump.generate(reqs)
            else:
                loop = asyncio.get_running_loop()
                results = await loop.run_in_executor(
                    self._executor, engine.generate, reqs
                )
        finally:
            self._busy -= 1
        # sheds are per-request RESULTS (finish_reason "overloaded"), so
        # they bypass _on_handler_error — count them here, still apart
        # from real errors
        self._overloaded_count += sum(
            1 for r in results if r.finish_reason == "overloaded")
        self._deadline_expired_count += sum(
            1 for r in results if r.finish_reason == "deadline")
        for r in results:
            self._attach_worker_trace(r, t_recv)
        return {"model": name, "results": [result_to_dict(r) for r in results]}

    # -- streaming (token chunks ahead of the final result) -----------------

    async def _rpc_generate_stream(self, msg: Dict[str, Any], send) -> Dict[str, Any]:
        """Stream one request's tokens as they decode: chunk frames
        ``{"tokens": [...]}`` ride the connection ahead of the final
        result envelope. Continuous engines only (the rolling batch emits
        per-chunk; a static engine runs to completion in one call — use
        ``generate`` there)."""
        t_recv = time.perf_counter()
        self._admit()
        name, _engine = self._engine_for(msg, "generate")
        pump = self._pumps.get(name)
        if pump is None:
            raise ValueError(
                f"model {name!r} is not a continuous engine — streaming "
                "needs metadata.continuous=1")
        req = request_from_dict(msg.get("request") or {})
        self._request_count += 1
        self._busy += 1
        sent_at: List[float] = []       # when the first frame's send returned

        async def send_stamped(obj: Dict[str, Any]) -> None:
            await send(obj)
            if not sent_at:
                sent_at.append(time.perf_counter())

        try:
            queue: asyncio.Queue = asyncio.Queue()
            fut = asyncio.ensure_future(
                pump.generate_streaming(req, queue.put_nowait))
            result = await relay_stream(fut, queue, send_stamped)
        finally:
            self._busy -= 1
        self._attach_worker_trace(result, t_recv,
                                  sent_at[0] if sent_at else None)
        return {"model": name, "result": result_to_dict(result)}

    # -- profiling (SURVEY.md §5 tracing plan: XLA/TPU timeline capture) ----

    async def _rpc_profile(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Start/stop a ``jax.profiler`` trace on this worker. The trace
        directory is loadable in TensorBoard/XProf for XLA timelines —
        the real-engine upgrade of the reference's wall-clock-only
        "tracing" (``src/worker.py:126-133``).

        ``start`` takes ``python_tracer`` (default on, as ever): off, the
        host side of the trace is the program's own spans
        (``obs.timeline.host_span``) and the host runs at its own pace.
        ``start`` and ``stop`` each write one ``clock.anchor`` event
        carrying this process's ``perf_counter_ns`` into the trace, and
        the step-timeline dumps carry the same anchors: ring records and
        ``worker_trace`` offsets map onto the trace's clock through them.
        ``stop`` also writes ``counters.json`` into the trace directory:
        every engine's ``get_metrics()`` as it stood when the trace began
        and when it was asked to end, so that a reader divides the slice's
        device seconds by the SLICE's counts (a chunk's counters move at its
        harvest, at most one chunk after its programs ran).
        Writing the trace out takes over a minute on the chip; it runs off
        the event loop, so the worker's streams keep flowing."""
        action = msg.get("action")
        if action not in ("start", "stop"):
            raise ValueError(f"unknown profile action {action!r} "
                             "(use 'start' or 'stop')")
        async with self._profile_lock:
            if action == "start":
                return self._profile_start(msg)
            return await self._profile_stop()

    def _engine_counters(self) -> Dict[str, Any]:
        return {"models": {name: eng.get_metrics()
                           for name, eng in self.engines.items()}}

    def _profile_start(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        import jax

        if self._profiling_dir is not None:
            raise ValueError(
                f"profiling already active -> {self._profiling_dir}")
        trace_dir = msg.get("trace_dir") or f"/tmp/{self.worker_id}-trace"
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = int(bool(msg.get("python_tracer", True)))
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        self._profiling_dir = trace_dir
        self._profile_counters = {"start": self._engine_counters()}
        anchor = clock_anchor("start")
        # bracket the engine step timelines to the same window: the
        # jax trace shows the XLA/device side, the step timeline the
        # engine thread's view of the SAME interval
        for engine in self.engines.values():
            tl = getattr(engine, "timeline", None)
            if tl is not None:
                tl.start_capture()
                tl.add_anchor(anchor)
        return {"profiling": True, "trace_dir": trace_dir,
                "python_tracer": bool(options.python_tracer_level)}

    async def _profile_stop(self) -> Dict[str, Any]:
        import jax

        if self._profiling_dir is None:
            raise ValueError("profiling is not active")
        anchor = clock_anchor("stop")
        counters = dict(self._profile_counters, stop=self._engine_counters())
        t0 = time.perf_counter()
        await asyncio.get_running_loop().run_in_executor(
            None, _stop_trace_xplane_only)
        stop_s = time.perf_counter() - t0
        out, self._profiling_dir = self._profiling_dir, None
        try:
            os.makedirs(out, exist_ok=True)
            atomic_write_json(os.path.join(out, "counters.json"), counters)
        except (OSError, TypeError, ValueError) as e:  # must not fail stop
            logger.warning("worker %s: counters.json not written: %s",
                           self.worker_id, e)
        written: List[str] = []
        for name, engine in self.engines.items():
            tl = getattr(engine, "timeline", None)
            if tl is None:
                continue
            tl.add_anchor(anchor)
            try:
                os.makedirs(out, exist_ok=True)
                path = os.path.join(out, f"step_timeline_{name}.json")
                written.append(tl.dump(path, tl.stop_capture()))
            except Exception as e:  # timeline dump must not fail stop
                logger.warning("worker %s: step-timeline dump for %s "
                               "failed: %s", self.worker_id, name, e)
        logger.info("worker %s: profile written to %s in %.1fs",
                    self.worker_id, out, stop_s)
        return {"profiling": False, "trace_dir": out, "stop_s": stop_s,
                "step_timelines": written}

    # -- disaggregated prefill/decode (engine/disagg.py; SURVEY.md §2.3) ----

    def _engine_for(self, msg: Dict[str, Any], capability: str):
        name = msg.get("model")
        if not name:
            raise ValueError("missing 'model'")
        engine = self.engines.get(name)
        if engine is None:
            raise ValueError(f"model {name!r} not loaded "
                             f"(have: {sorted(self.engines)})")
        if not hasattr(engine, capability):
            raise ValueError(
                f"model {name!r} engine ({type(engine).__name__}) does not "
                f"support {capability!r} — wrong pool role?"
            )
        # every routed request refreshes the model's LRU position, so the
        # residency budget evicts genuinely idle models, not busy ones
        self.model_manager.touch(name)
        return name, engine

    async def _rpc_prefill(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Prefill-pool op: run the prompt, return KV handoffs to the caller."""
        from ..engine.disagg import handoff_to_wire

        self._admit()
        name, engine = self._engine_for(msg, "prefill")
        reqs = [request_from_dict(d) for d in msg.get("requests", [])]
        if not reqs:
            raise ValueError("empty 'requests'")
        self._request_count += 1
        self._busy += 1
        try:
            loop = asyncio.get_running_loop()
            handoffs = await loop.run_in_executor(
                self._executor, engine.prefill, reqs
            )
        finally:
            self._busy -= 1
        return {"model": name,
                "handoffs": [handoff_to_wire(h) for h in handoffs]}

    async def _rpc_prefix_probe(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Decode-pool op: how many leading prompt tokens (page-aligned)
        does this engine's prefix cache already hold, per prompt? The
        disaggregated prefill worker uses the answer to ship delta
        handoffs (KV tail only). Advisory — admission re-checks and a
        shortfall surfaces as the typed ``stale_prefix`` result."""
        from ..engine.paged_kv import page_chain_hashes

        name, engine = self._engine_for(msg, "submit_prefilled")
        kv = getattr(engine, "kv", None)
        enabled = kv is not None and getattr(engine, "prefix_cache", False)
        # advertise this pool's page size so the sender can hash with it
        # on later probes even when its own config disagrees
        my_page = kv.page_size if enabled else 0
        out: List[int] = []
        if "hashes" in msg:
            # preferred form: 16-byte-per-page chain hashes (the
            # page_chain_hashes contract) — the sender never ships the
            # prompt twice. Hashes chain over page-sized token chunks, so
            # a page-size mismatch means no entry can match: answer 0s
            # (the sender re-hashes with the advertised size next probe).
            if not enabled or msg.get("page_size") != kv.page_size:
                out = [0] * len(msg["hashes"])
            else:
                out = [kv.probe_prefix([bytes(h) for h in hs])
                       * kv.page_size
                       for hs in msg["hashes"]]
        else:
            for prompt in msg.get("prompts", []):  # legacy full-prompt probe
                if not enabled:
                    out.append(0)
                    continue
                matchable = (len(prompt) - 1) // kv.page_size
                hashes = page_chain_hashes(prompt, matchable, kv.page_size)
                out.append(kv.probe_prefix(hashes) * kv.page_size)
        # the relay's pool to this peer follows the capacity report
        return {"model": name, "cached_tokens": out, "page_size": my_page,
                **self.capacity_report()}

    # -- KV fabric (engine/kv_fabric.py) ------------------------------------

    async def _rpc_kv_export(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Fabric op: serialize the longest locally-resident full-page
        prefix of ``tokens`` as a checksummed wire dict (None when cold).
        Deliberately NOT gated by ``_admit()``: a DRAINING worker must
        keep exporting — the drain handoff pulls its hot prefixes out
        while in-flight work finishes."""
        from ..engine.kv_fabric import wire_nbytes

        name, engine = self._engine_for(msg, "kv_export")
        tokens = [int(t) for t in msg.get("tokens", [])]
        if not tokens:
            raise ValueError("missing 'tokens'")
        max_pages = int(msg.get("max_pages", 0))
        loop = asyncio.get_running_loop()
        wire = await loop.run_in_executor(
            self._executor, engine.kv_export, tokens, max_pages)
        if wire is not None:
            self._kv_fabric_exports += 1
            self._kv_fabric_export_bytes += wire_nbytes(wire)
            self.events.emit("fabric.export", model=name,
                             pages=len(wire.get("pages", ())) if isinstance(wire, dict) else 0)
        return {"model": name, "wire": wire}

    async def _rpc_kv_import(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Fabric op: validate + land an exported prefix in the local host
        tier and start its layer-wise restage. A rejected wire (checksum /
        geometry mismatch) stores NOTHING and reports ``rejected`` in the
        payload — the caller counts a fallback and the next admission pays
        normal prefill; wrong KV is never served. Not ``_admit()``-gated:
        pre-warm runs before the worker takes traffic (half-open)."""
        from ..engine.kv_fabric import FabricRejected, wire_nbytes

        name, engine = self._engine_for(msg, "kv_import")
        wire = msg.get("wire")
        if not wire:
            raise ValueError("missing 'wire'")
        loop = asyncio.get_running_loop()
        try:
            imported = await loop.run_in_executor(
                self._executor, engine.kv_import, wire)
        except FabricRejected as exc:
            self._kv_fabric_import_fallbacks += 1
            return {"model": name, "imported_pages": 0,
                    "rejected": str(exc)}
        self._kv_fabric_imports += 1
        self._kv_fabric_import_bytes += wire_nbytes(wire)
        self.events.emit("fabric.import", model=name, pages=int(imported))
        return {"model": name, "imported_pages": int(imported)}

    async def _rpc_generate_prefilled(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Decode-pool op: admit handed-off KV, decode to completion."""
        from ..engine.disagg import handoff_from_wire

        self._admit()
        name, _engine = self._engine_for(msg, "submit_prefilled")
        pump = self._pumps.get(name)
        if pump is None:
            raise ValueError(
                f"model {name!r} is not a continuous engine — the decode "
                "pool needs metadata.continuous=1"
            )
        reqs = [request_from_dict(d) for d in msg.get("requests", [])]
        handoffs = [handoff_from_wire(d) for d in msg.get("handoffs", [])]
        if len(reqs) != len(handoffs) or not reqs:
            raise ValueError("requests and handoffs must align and be non-empty")
        self._request_count += 1
        self._busy += 1
        try:
            results = await pump.generate_prefilled(list(zip(reqs, handoffs)))
        finally:
            self._busy -= 1
        return {"model": name, "results": [result_to_dict(r) for r in results]}

    async def _rpc_prefill_generate(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Prefill-pool op: prefill locally, hand the KV to the decode peer
        at (decode_host, decode_port), relay its finished results.

        One KV hop (prefill → decode over DCN) — the coordinator only
        carries requests and token results.

        ``pipeline_groups`` (default 1 = off) overlaps the KV transfer
        with prefill AND decode: the request batch splits into contiguous
        groups, and because all prefill compute serializes on the single
        engine-executor thread, group g+1's prefill runs while group g's
        KV is in flight to the peer and its decode slots are already
        admitted into the rolling batch. The first group's TTFT stops
        paying for the whole batch's prefill + one monolithic transfer
        (VERDICT r2 item 3's overlap). Worth it when per-request prefill +
        transfer is substantial (long prompts at scale); for short cheap
        prompts the early groups decode at low occupancy and the overlap
        buys nothing — measured per-config in examples/disagg_bench.py.
        """
        from ..engine.disagg import handoff_to_wire

        self._admit()
        name, engine = self._engine_for(msg, "prefill")
        host, port = msg.get("decode_host"), msg.get("decode_port")
        if not host or not port:
            raise ValueError("missing 'decode_host'/'decode_port'")
        reqs_wire = msg.get("requests", [])
        reqs = [request_from_dict(d) for d in reqs_wire]
        if not reqs:
            raise ValueError("empty 'requests'")
        self._request_count += 1
        loop = asyncio.get_running_loop()
        peer = self._peer_clients.get((host, int(port)))
        if peer is None:
            peer = WorkerClient(host, int(port),
                                max_frame=self.config.max_frame_bytes)
            self._peer_clients[(host, int(port))] = peer

        # envelope headroom of 1 MiB, but never below half the frame for
        # small configured limits (budget must stay usable, not negative)
        budget = max(self.config.max_frame_bytes - 1_048_576,
                     self.config.max_frame_bytes // 2)
        # peer_timeout travels IN the message (the client-side ``timeout``
        # kwarg only bounds the caller's own read and is never serialized)
        peer_timeout = float(msg.get("peer_timeout", 300.0))
        decode_model = msg.get("decode_model", name)
        n_groups = max(1, min(int(msg.get("pipeline_groups", 1)),
                              len(reqs)))
        gsize = -(-len(reqs) // n_groups)
        groups = [list(range(a, min(a + gsize, len(reqs))))
                  for a in range(0, len(reqs), gsize)]

        # oversize-handoff config errors must fire BEFORE any group ships:
        # a mid-pipeline raise would orphan earlier groups' decodes on the
        # peer (r3 review finding). Handoff size is deterministic from the
        # prompt length — 2·L·Hkv·Dh·itemsize bytes/token — so no prefill
        # is needed to validate every request up front.
        spec = engine.spec
        tok_bytes = (2 * spec.n_layers * spec.n_kv_heads * spec.head_dim
                     * engine.kv_dtype.itemsize)
        for r in reqs:
            # the engine tail-truncates overlong prompts, so cap the
            # estimate the same way
            s = min(len(r.prompt), engine.max_seq_len - 1) * tok_bytes + 4096
            if s > budget:
                raise ValueError(
                    f"handoff for request {r.request_id!r} would be ~{s} "
                    f"bytes — exceeds the {self.config.max_frame_bytes}"
                    "-byte frame limit; raise ServerConfig.max_frame_bytes "
                    "on both pools"
                )

        async def run_group(g_idxs: List[int]) -> List[Any]:
            # prefill THIS group (serializes with other groups on the
            # engine thread — that serialization is the pipeline)
            handoffs = await loop.run_in_executor(
                self._executor, engine.prefill, [reqs[i] for i in g_idxs]
            )
            # prefix-aware delta handoff: probe which page-aligned prompt
            # heads the decode pool's prefix cache already holds and ship
            # only the KV tails. The probe ships 16-byte-per-page chain
            # hashes (page_chain_hashes — the prompt itself is shipped
            # exactly once, inside generate_prefilled). Advisory — a
            # reclaimed page surfaces as a typed per-request stale_prefix
            # result below, answered by re-shipping that request's full KV.
            from ..engine.disagg import trim_handoff
            from ..engine.paged_kv import page_chain_hashes

            full_handoffs = handoffs             # kept for stale re-sends
            # hash with the DECODE pool's page size: its prefix index is
            # what the chain hashes must match. Learned from the peer's
            # probe responses (cached on the peer client); until the first
            # response, fall back to this pool's configured page_size —
            # the pools share EngineConfig on a standard disagg deploy.
            # PrefillEngine has no kv, so the config is the only local
            # source (r4 review finding).
            page_size = (getattr(peer, "probe_page_size", 0)
                         or getattr(getattr(engine, "kv", None),
                                    "page_size", 0)
                         or getattr(engine.config, "page_size", 0))
            cached: List[int] = []
            if page_size > 0:
                try:
                    probe = await peer.call(
                        "prefix_probe", model=decode_model,
                        page_size=page_size,
                        hashes=[page_chain_hashes(
                                    reqs[i].prompt[-h.prompt_len:],
                                    (h.prompt_len - 1) // page_size,
                                    page_size)
                                for i, h in zip(g_idxs, handoffs)],
                        timeout=peer_timeout,
                    )
                    cached = peer._follow_slots(probe).get(
                        "cached_tokens", [])
                    if int(probe.get("page_size", 0)) > 0:
                        peer.probe_page_size = int(probe["page_size"])
                except RPCError:
                    cached = []                  # peer predates the probe op
            cached = cached + [0] * (len(handoffs) - len(cached))
            # probe counts are page-aligned and capped below prompt_len by
            # construction ((len-1)//P pages) — the guard is belt/braces
            handoffs = [trim_handoff(h, c) if 0 < c < h.prompt_len else h
                        for h, c in zip(handoffs, cached)]
            # KV handoffs are big (≈2·L·Hkv·Dh·itemsize bytes/token) —
            # pack into as many generate_prefilled frames as the limit
            # needs. An oversize SINGLE handoff is a config error (raise
            # as one), never a DecodePeerError: misclassifying it would
            # dent the healthy decode worker's health on every long prompt
            wires = [handoff_to_wire(h) for h in handoffs]
            sizes = [len(w["k"]) + len(w["v"]) + 4096 for w in wires]
            self._handoff_bytes_shipped += sum(
                len(w["k"]) + len(w["v"]) for w in wires)
            # the up-front prompt-length estimate already bounds every
            # wire (trimming only shrinks them) — a violation would be an
            # accounting bug, but it must stay a REAL check (not an
            # assert, which -O strips): an oversized frame would otherwise
            # surface as a raw framing error mid-pipeline, orphaning
            # already-shipped groups. Nothing from THIS group has shipped
            # yet, so raising here is safe.
            if any(s > budget for s in sizes):
                raise ValueError(
                    "handoff wire exceeded the up-front size bound "
                    f"({max(sizes)} > {budget} bytes) — the per-token "
                    "estimate in generate_remote_decode has drifted from "
                    "handoff_to_wire; fix the estimate"
                )
            frames: List[List[int]] = []
            cur: List[int] = []
            cur_bytes = 0
            for j, s in enumerate(sizes):
                if cur and cur_bytes + s > budget:
                    frames.append(cur)
                    cur, cur_bytes = [], 0
                cur.append(j)
                cur_bytes += s
            if cur:
                frames.append(cur)

            async def _send(js: List[int]) -> Any:
                return await peer.call(
                    "generate_prefilled", model=decode_model,
                    requests=[reqs_wire[g_idxs[j]] for j in js],
                    handoffs=[wires[j] for j in js],
                    timeout=peer_timeout,
                )

            parts = await asyncio.gather(
                *(asyncio.ensure_future(_send(js)) for js in frames))
            out: List[Any] = [None] * len(g_idxs)
            for js, part in zip(frames, parts):
                for j, r in zip(js, part["results"]):
                    out[j] = r
            # a delta handoff can lose its race (prefix pages reclaimed
            # between probe and admission): re-ship those requests' FULL
            # KV, one call each — the rare path buys simplicity
            stale = [j for j, r in enumerate(out)
                     if isinstance(r, dict)
                     and r.get("finish_reason") == "stale_prefix"]
            for j in stale:
                full_wire = handoff_to_wire(full_handoffs[j])
                self._handoff_bytes_shipped += (len(full_wire["k"])
                                                + len(full_wire["v"]))
                retry = await peer.call(
                    "generate_prefilled", model=decode_model,
                    requests=[reqs_wire[g_idxs[j]]],
                    handoffs=[full_wire],
                    timeout=peer_timeout,
                )
                out[j] = retry["results"][0]
            return out

        self._busy += 1
        tasks = [asyncio.ensure_future(run_group(g)) for g in groups]
        try:
            group_outs = await asyncio.gather(*tasks)
        except BaseException as e:
            # one group failing must CANCEL the siblings — the caller
            # will re-dispatch the whole batch elsewhere, and an orphaned
            # group would keep burning decode slots for discarded output
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            if isinstance(e, (OSError, ConnectionError, asyncio.TimeoutError,
                              asyncio.IncompleteReadError, EOFError,
                              FrameError)):
                raise DecodePeerError(
                    f"decode peer {host}:{port} unreachable: "
                    f"{type(e).__name__}: {e}"
                ) from e
            raise
        finally:
            self._busy -= 1
        results: List[Any] = [None] * len(reqs_wire)
        for g_idxs, outs in zip(groups, group_outs):
            for i, r in zip(g_idxs, outs):
                results[i] = r
        return {"model": name, "results": results,
                "decode_worker": f"{host}:{port}"}

    async def _rpc_load_model(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        cfg = ModelConfig.from_dict(msg["config"])
        await self.load_model_async(cfg)
        return {"loaded": cfg.name,
                # measured engine-construction wall time (idempotent
                # re-loads report the original) — demo/supervisor receipts
                "load_s": self._last_load_s.get(cfg.name, 0.0),
                # what the caller's pool to this worker follows
                **self.capacity_report()}

    async def _rpc_stage_model(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Begin background staging; returns immediately (the build runs on
        a side thread — dispatch is never displaced). ``swap_model`` later
        waits for it, probes it, and installs it."""
        cfg = ModelConfig.from_dict(msg["config"])
        rec = self.stage_model(cfg)
        if rec is not None:
            self.events.emit("model.stage", model=cfg.name)
        return {"staging": cfg.name,
                "already_resident": rec is None}

    async def _rpc_swap_model(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Activate a staged model (probe-gated). Runs on the engine
        executor: the wait for the staging thread happens off the event
        loop, and installation serializes with in-flight loads."""
        name = msg.get("model")
        if not name:
            raise ValueError("missing 'model'")
        probe = msg.get("probe")
        timeout = msg.get("timeout_s")
        loop = asyncio.get_running_loop()
        try:
            receipt = await loop.run_in_executor(
                self._executor,
                lambda: self.swap_model(
                    name,
                    probe_expected=([int(t) for t in probe]
                                    if probe else None),
                    timeout=float(timeout) if timeout else None))
            if not receipt.get("already_resident"):
                self.events.emit("model.swap", model=name)
            return receipt
        except (ModelProbeError, ModelStageError) as e:
            # typed application errors — the RPC envelope carries them as
            # failures without denting transport-level health
            raise ValueError(str(e)) from e

    async def _rpc_resident_models(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        return {"worker_id": self.worker_id,
                "resident": sorted(self.engines),
                "staged": self.model_manager.staged_names(),
                "resident_bytes": self.model_manager.resident_bytes_used(),
                "max_resident_models": self.config.max_resident_models,
                "resident_bytes_budget": self.config.resident_bytes}

    async def _rpc_unload_model(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        return {"unloaded": self.unload_model(msg["model"])}

    async def _rpc_list_models(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        return {"models": {n: c.to_dict() for n, c in self.model_configs.items()}}

    async def _rpc_metrics(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        return self.get_metrics()

    def _obs_collect(self) -> None:
        obs_collectors.clear_worker_labelled(self.obs_registry)
        obs_collectors.apply_worker(self.obs_registry, self.get_metrics())
        obs_collectors.apply_event_log(self.obs_registry,
                                       self.events.get_stats(),
                                       proc=self.worker_id)

    def metrics_text(self) -> str:
        """This worker's metrics as OpenMetrics exposition text. The
        render is self-timed (obs_scrape_seconds / obs_scrape_ok) — the
        sample lands on the NEXT exposition, it can't time itself into
        its own output."""
        t0 = time.perf_counter()
        try:
            text = self.obs_registry.render()
        except Exception:
            obs_collectors.record_scrape(self.obs_registry, self.worker_id,
                                         time.perf_counter() - t0, ok=False)
            raise
        obs_collectors.record_scrape(self.obs_registry, self.worker_id,
                                     time.perf_counter() - t0, ok=True)
        return text

    async def _rpc_metrics_text(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        return {"content_type": OPENMETRICS_CONTENT_TYPE,
                "text": self.metrics_text()}

    async def _http_get(self, path: str) -> Optional[Tuple[str, bytes]]:
        if path == "/metrics":
            return (OPENMETRICS_CONTENT_TYPE,
                    self.metrics_text().encode("utf-8"))
        return None

    async def _rpc_drain(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Graceful drain: stop admitting (new work gets the typed
        ``draining`` shed, probes see ``draining`` in ping), wait for
        in-flight work — pumps' inboxes/futures and the ``_busy`` admission
        counter — to empty, then report a per-model summary so the caller
        can account for what this worker was holding (KV/prefix/token
        counters) before removing it. Idempotent; ``timeout_s`` rides in
        the message (this verb is exempt from the server-side timeout)."""
        timeout_s = float(msg.get("timeout_s", 30.0))
        if not self._draining:
            self._draining = True
            self._drain_count += 1
            self.events.emit("drain.begin")
            logger.info("worker %s draining (timeout %.1fs)",
                        self.worker_id, timeout_s)
        deadline = time.monotonic() + timeout_s
        drained = True
        for pump in self._pumps.values():
            remaining = max(0.0, deadline - time.monotonic())
            if not await pump.drain(remaining):
                drained = False
        while self._busy > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        if self._busy > 0:
            drained = False
        summary: Dict[str, Any] = {}
        for name, engine in self.engines.items():
            m = engine.get_metrics()
            summary[name] = {
                k: v for k, v in m.items()
                if isinstance(v, (int, float)) and any(
                    t in k for t in ("prefix", "kv", "page", "token",
                                     "request", "waiting", "live"))
            }
        self.events.emit("drain.done", drained=drained,
                         in_flight=self._busy)
        return {"worker_id": self.worker_id, "drained": drained,
                "in_flight": self._busy, "models": summary}

    async def _rpc_events(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Flight-recorder collection verb: this worker's event ring plus
        every resident continuous engine's step timeline (perf_counter
        axis), with a fresh ``mono`` stamp so the caller can re-anchor."""
        timelines: Dict[str, List[Dict[str, Any]]] = {}
        for name, engine in self.engines.items():
            tl = getattr(engine, "timeline", None)
            if tl is not None:
                timelines[name] = tl.events()
        return {"worker_id": self.worker_id,
                "mono": time.perf_counter(),
                "wall": time.time(),
                "ring": self.events.snapshot(),
                "timelines": timelines}

    async def _rpc_shutdown(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        self._shutdown_event.set()
        return {"shutting_down": True}

    # -- metrics (reference src/worker.py:186-209) ----------------------------

    def capacity_report(self) -> Dict[str, Any]:
        """What a caller's pool to this worker is sized from
        (``utils.rpc.pool_for_slots``). ``slots``: requests the engines run
        at once, summed over the resident models (``None`` where no engine
        says). ``queue``: the requests an engine here keeps waiting before
        it sheds: its ``max_waiting``, 0 where it sheds by
        ``queue_deadline_s`` (a request that waits here can then be shed
        where one that waits at the caller is not), the least over the
        engines, ``None`` where none sheds."""
        slots = [_engine_slots(e) for e in self.engines.values()]
        bounds = []
        for engine in self.engines.values():
            cfg = getattr(engine, "config", None)
            if getattr(cfg, "queue_deadline_s", 0):
                bounds.append(0)
            elif getattr(cfg, "max_waiting", 0):
                bounds.append(int(cfg.max_waiting))
        return {"slots": sum(s for s in slots if s) or None,
                "queue": min(bounds) if bounds else None}

    def device_report(self, memory: bool = False) -> Optional[Dict[str, Any]]:
        """Where this worker's engines run, as JAX reports it: platform,
        device kind, visible device count, and per resident model the ids
        of the devices its params live on plus its int4 kernel paths.
        ``None`` until a real (jax) engine is resident — a fake-engine
        worker never touches a backend. ``memory`` adds each used device's
        live ``memory_stats()`` (``None`` where the backend has none) and
        the process's compile counters (``utils.compile_cache``)."""
        models = {name: p for name, p in self._placements.items()
                  if p and name in self.engines}
        if not models:
            return None
        import jax

        devices = jax.devices()
        report: Dict[str, Any] = {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "n_devices": len(devices),
            # the chip(s) libtpu was told to show this process (README
            # "One worker per chip"): device ids restart at 0 inside a
            # confined process, so this is what tells replicas apart
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "models": models,
        }
        if memory:
            used = {i for p in models.values() for i in p["device_ids"]}
            report["memory"] = {
                str(d.id): d.memory_stats() for d in devices if d.id in used}
            # the compiler's own count since process start (the metrics
            # RPC's view; ping stays light)
            from ..utils.compile_cache import compile_counters

            report["compile"] = compile_counters()
        return report

    def get_metrics(self) -> Dict[str, Any]:
        process: Dict[str, Any] = {}
        try:
            import psutil

            p = psutil.Process()
            process = {
                "rss_bytes": p.memory_info().rss,
                "cpu_percent": p.cpu_percent(interval=None),
                "num_threads": p.num_threads(),
            }
        # graftlint: ok[swallowed-transport-error] psutil is optional (undeclared reference dep); process introspection, no peer involved
        except Exception:
            pass
        return {
            "worker_id": self.worker_id,
            "uptime_s": time.time() - self._started_at if self._started_at else 0.0,
            "request_count": self._request_count,
            "error_count": self._error_count,
            "overloaded_count": self._overloaded_count,
            "deadline_expired_count": self._deadline_expired_count,
            "draining": 1 if self._draining else 0,
            "drain_count": self._drain_count,
            "injected_faults": (
                self.fault_plan.injected_count(self._fault_scope())
                if self.fault_plan is not None else 0),
            "handoff_bytes_shipped": self._handoff_bytes_shipped,
            "kv_fabric_exports": self._kv_fabric_exports,
            "kv_fabric_imports": self._kv_fabric_imports,
            "kv_fabric_export_bytes": self._kv_fabric_export_bytes,
            "kv_fabric_import_bytes": self._kv_fabric_import_bytes,
            "kv_fabric_import_fallbacks": self._kv_fabric_import_fallbacks,
            "ping_count": self._ping_count,          # probes counted apart
            "active_connections": self._active_connections,
            "latency": self.latency.snapshot(),
            "model_load": self.model_load_stats.snapshot(),
            # this process's perf_counter now: the clock of the compile
            # log's and the step ring's ``t0``
            "mono": time.perf_counter(),
            # seconds since process start at each mark of start-up
            "boot": self.boot_report(),
            # per-model set-up split: load_s less warmup_s is the engine
            # factory (parameters drawn / quantised / placed, pools
            # allocated; on a process's first load the backend's start
            # too); ``warmup`` is the engine's own account of its grid,
            # round by round
            "model_setup": {
                name: {"load_s": self._last_load_s.get(name, 0.0),
                       "warmup_s": self._last_warmup_s.get(name, 0.0),
                       "warmup": self._engine_warmup(engine)}
                for name, engine in self.engines.items()},
            "device": self.device_report(memory=True),
            "artifact_hits": self._artifact_hits,
            "artifact_misses": self._artifact_misses,
            # multi-model residency (cluster/model_manager.py): resident/
            # staged gauges, stage/swap latency histograms, eviction and
            # probe-reject counters, measured staging↔dispatch overlap
            **self.model_manager.get_stats(),
            "models": {name: eng.get_metrics()
                       for name, eng in self.engines.items()},
            # pump stats without the engine sub-dict ("models" above
            # already carries every engine's metrics once)
            "pumps": {name: {k: v for k, v in pump.get_stats().items()
                             if k != "engine"}
                      for name, pump in self._pumps.items()},
            "process": process,
        }


# --------------------------------------------------------------------------
# client

class WorkerClient(FramedRPCClient):
    """Persistent framed-RPC client for one worker.

    The reference has no client class at all — callers hand-roll
    ``asyncio.open_connection`` (only the health probes do,
    ``src/router.py:287-292``). One connection is reused across calls and
    transparently re-established after a drop (``utils/rpc.py``).
    """

    # the worker's last report of its capacity (``_follow_slots``)
    _capacity_seen: Optional[Tuple[int, Optional[int]]] = None

    # convenience wrappers -----------------------------------------------

    async def ping(self, timeout: Optional[float] = None) -> Dict[str, Any]:
        return self._follow_slots(await self.call("ping", timeout=timeout))

    def _follow_slots(self, reply: Any) -> Any:
        """Size this client's pool from what the worker just reported (a
        ``ping``, a ``load_model`` receipt, a decode peer's ``prefix_probe``
        answer): a stream holds a connection for its life, so the pool is
        the engines' slots plus the look-ahead (``pool_for_slots``). A reply
        with no ``slots`` (an older worker) leaves the pool. The first
        report sizes the pool whatever ``max_connections`` the client was
        built with; after it only a changed report does."""
        if isinstance(reply, dict) and reply.get("slots"):
            seen = (reply["slots"], reply.get("queue"))
            if seen != self._capacity_seen:
                self._capacity_seen = seen
                self.resize_pool(pool_for_slots(*seen))
        return reply

    async def events(self, timeout: Optional[float] = None) -> Dict[str, Any]:
        """Flight-recorder collection: event ring + step timelines."""
        return await self.call("events", timeout=timeout)

    async def generate(
        self, model: str, requests: List[GenerationRequest],
        timeout: Optional[float] = None,
    ) -> List[GenerationResult]:
        result = await self.call(
            "generate", model=model,
            requests=[request_to_dict(r) for r in requests],
            timeout=timeout,
        )
        return [result_from_dict(d) for d in result["results"]]

    async def generate_stream(
        self, model: str, request: GenerationRequest, on_tokens,
        timeout: Optional[float] = None,
        on_acquired: Optional[Callable[[float], None]] = None,
    ) -> GenerationResult:
        """Stream one request: ``on_tokens(tokens)`` fires per decoded
        chunk; returns the final (authoritative) result. ``timeout``
        bounds the gap between frames, not the whole generation;
        ``on_acquired`` is ``call_stream``'s."""
        result = await self.call_stream(
            "generate_stream",
            lambda frame: on_tokens(list(frame.get("tokens", []))),
            model=model, request=request_to_dict(request),
            timeout=timeout, on_acquired=on_acquired,
        )
        return result_from_dict(result["result"])

    async def prefill(self, model: str, requests: List[GenerationRequest],
                      timeout: Optional[float] = None) -> List[Any]:
        """Prefill-pool call: returns ``PrefillHandoff`` objects."""
        from ..engine.disagg import handoff_from_wire

        result = await self.call(
            "prefill", model=model,
            requests=[request_to_dict(r) for r in requests],
            timeout=timeout,
        )
        return [handoff_from_wire(d) for d in result["handoffs"]]

    async def generate_prefilled(
        self, model: str, requests: List[GenerationRequest],
        handoffs: List[Any], timeout: Optional[float] = None,
    ) -> List[GenerationResult]:
        """Decode-pool call: requests + KV handoffs → finished results."""
        from ..engine.disagg import handoff_to_wire

        result = await self.call(
            "generate_prefilled", model=model,
            requests=[request_to_dict(r) for r in requests],
            handoffs=[handoff_to_wire(h) for h in handoffs],
            timeout=timeout,
        )
        return [result_from_dict(d) for d in result["results"]]

    async def prefill_generate(
        self, model: str, requests: List[GenerationRequest],
        decode_host: str, decode_port: int,
        decode_model: Optional[str] = None,
        timeout: Optional[float] = None,
        pipeline_groups: int = 1,
    ) -> List[GenerationResult]:
        """Disaggregated end-to-end: prefill here, decode at the peer.

        ``timeout`` is the decode budget (serialized as ``peer_timeout``
        for the prefill worker's wait on its peer); this call itself waits
        2× that, leaving headroom for prefill + KV transfer — otherwise a
        decode that finishes inside its allowance could still time out
        here and falsely dent the healthy prefill worker.
        ``pipeline_groups`` > 1 overlaps prefill with KV transfer + decode
        admission (see ``WorkerServer._rpc_prefill_generate``)."""
        budget = timeout if timeout is not None else self.timeout
        result = await self.call(
            "prefill_generate", model=model,
            requests=[request_to_dict(r) for r in requests],
            decode_host=decode_host, decode_port=decode_port,
            decode_model=decode_model or model,
            peer_timeout=budget, pipeline_groups=pipeline_groups,
            timeout=2.0 * budget,
        )
        return [result_from_dict(d) for d in result["results"]]

    async def load_model(self, cfg: ModelConfig,
                         timeout: Optional[float] = None) -> Dict[str, Any]:
        """Load ``cfg`` on the worker; returns the measured-load receipt
        ({loaded, load_s}) — the cold-start half of the staged-swap
        latency comparison."""
        return self._follow_slots(await self.call(
            "load_model", config=cfg.to_dict(),
            timeout=timeout if timeout is not None else 300.0))

    async def unload_model(self, name: str) -> bool:
        result = await self.call("unload_model", model=name)
        return bool(result["unloaded"])

    async def stage_model(self, cfg: ModelConfig,
                          timeout: Optional[float] = None) -> Dict[str, Any]:
        """Begin background staging on the worker; returns immediately."""
        return await self.call("stage_model", config=cfg.to_dict(),
                               timeout=timeout)

    async def swap_model(self, name: str,
                         probe: Optional[List[int]] = None,
                         timeout: Optional[float] = None) -> Dict[str, Any]:
        """Activate a staged model; ``probe`` is the expected golden-probe
        token list for engines without an artifact manifest. Returns the
        worker's swap receipt ({swapped, stage_s, swap_s, evicted})."""
        budget = timeout if timeout is not None else 300.0
        return await self.call(
            "swap_model", model=name,
            probe=[int(t) for t in probe] if probe else None,
            timeout_s=budget, timeout=budget + 10.0)

    async def resident_models(self) -> Dict[str, Any]:
        """The worker's resident + staged model sets and byte budget."""
        return await self.call("resident_models")

    async def kv_export(self, model: str, tokens: List[int],
                        max_pages: int = 0,
                        timeout: Optional[float] = None
                        ) -> Optional[Dict[str, Any]]:
        """Fabric pull: the worker's wire dict for ``tokens``' longest
        resident full-page prefix, or None when it holds nothing."""
        result = await self.call(
            "kv_export", model=model, tokens=[int(t) for t in tokens],
            max_pages=int(max_pages), timeout=timeout)
        return result.get("wire")

    async def kv_import(self, model: str, wire: Dict[str, Any],
                        timeout: Optional[float] = None) -> Dict[str, Any]:
        """Fabric push: land an exported wire in the worker's host tier.
        Returns ``{imported_pages, rejected?}`` — a checksum/geometry
        reject comes back typed in the payload, not as a transport error."""
        return await self.call("kv_import", model=model, wire=wire,
                               timeout=timeout)

    async def drain(self, timeout_s: float = 30.0) -> Dict[str, Any]:
        """Gracefully drain the worker: stop admission, wait for in-flight
        work, return its per-model summary. The RPC read allowance adds
        headroom over the worker-side wait."""
        return await self.call("drain", timeout_s=timeout_s,
                               timeout=timeout_s + 10.0)

    async def metrics(self) -> Dict[str, Any]:
        return await self.call("metrics")

    async def metrics_text(self) -> str:
        """The worker's OpenMetrics exposition text (``/metrics`` body)."""
        result = await self.call("metrics_text")
        return str(result["text"])

    async def shutdown(self) -> None:
        await self.call("shutdown")


# worker-reported request failure (distinct from transport failure)
WorkerRPCError = RPCError
