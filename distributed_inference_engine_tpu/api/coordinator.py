"""Coordinator: the front-end that composes cache → batcher → router/LB → worker.

The reference *documents* this component — ``README.md:56-60`` ("coordinator
consults kvstore for cache hits; on miss pushes to batcher") and the mermaid
flow ``docs/router_vs_load_balancer.md:43-57`` (client → coordinator → router
→ load balancer → worker) — but never implemented it; each layer only ran in
its own demo (SURVEY.md §1 "missing-but-declared layer"). This class is that
glue, delivered:

1. **Cache.** Deterministic requests (temperature == 0) are answered from the
   response cache when possible and populate it on the way out.
2. **Batcher.** Misses are coalesced per ``model:version`` with the
   size-OR-latency flush policy; the flushed batch is the XLA dispatch unit.
3. **Placement.** If the registry holds shards for the model, each request's
   affinity key picks its shard via consistent hashing (router, with
   deterministic failover); otherwise the load balancer spreads batches over
   equivalent replicas. This is exactly the router-vs-LB role split the
   reference's docs prescribe.
4. **Dispatch.** Framed RPC to the chosen worker's engine; transport failures
   mark worker health and retry once on the alternate placement — with real
   device state, failover means the prefix cache is cold on the new worker,
   which is why failover is deterministic per key (SURVEY.md §7 hard-part #5).
"""

from __future__ import annotations

import asyncio
import copy
import dataclasses
import logging
import random
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..config import BatcherConfig, CacheConfig, Config, HealthConfig, ModelConfig
from ..cluster.load_balancer import (
    LoadBalancer,
    LoadBalancerStrategy,
    NoHealthyWorkerError,
)
from ..cluster.registry import ModelRegistry, ModelStatus
from ..cluster.router import Router, RoutingError, WorkerHealth
from ..cluster.worker import (
    DECODE_PEER_UNREACHABLE,
    WorkerClient,
    WorkerRPCError,
    request_from_dict,
    result_to_dict,
)
from ..engine.types import (
    DeadlineExceededError,
    EngineOverloadedError,
    GenerationResult,
)
from ..obs import collectors as obs_collectors
from ..obs import clocksync as obs_clocksync
from ..obs import postmortem as obs_postmortem
from ..obs.events import EventLog
from ..obs.registry import MetricsRegistry
from ..serving.batcher import PAD_INPUT, Batcher
from ..serving.cache import ResponseCache
# typed failure taxonomy (utils/errors.py): TRANSPORT_ERRORS ⇒ health
# signal + retry elsewhere; shed_reason reads the envelope's error_detail
# structurally — "queue_full" (retry elsewhere now) vs "deadline" (the
# request aged out) vs "draining" (the worker is retiring; any other
# replica can take it). Application errors propagate untouched.
from ..utils.errors import REASON_DRAINING, TRANSPORT_ERRORS, shed_reason
from ..utils.tracing import LatencyStats, RequestTrace, new_request_id

logger = logging.getLogger(__name__)


@dataclass
class CoordinatorConfig:
    batcher: BatcherConfig = field(default_factory=BatcherConfig)
    cache: CacheConfig = field(default_factory=CacheConfig)
    health: HealthConfig = field(default_factory=HealthConfig)
    lb_strategy: str = LoadBalancerStrategy.ROUND_ROBIN.value
    dispatch_timeout_s: float = 120.0
    cache_enabled: bool = True
    # prefix-affinity routing (lb_strategy="prefix_affinity"): the affinity
    # key is the chain hash of the request's leading FULL prompt pages —
    # the same page_chain_hashes the prefix cache and host-KV tier key on,
    # so "same key" means "that worker's cache is warm for this prefix".
    # affinity_pages caps how many pages the key commits to: requests that
    # share a long system prefix but diverge in the tail still co-locate.
    affinity_pages: int = 4
    affinity_page_size: int = 64
    # retry budget: how many RE-dispatches a failed batch/stream gets
    # (transport failures and draining sheds only — queue_full sheds keep
    # the one-alternate contract and deadlines never retry), each preceded
    # by exponential backoff with jitter so a mass failover doesn't
    # thundering-herd the survivors
    max_dispatch_retries: int = 3
    retry_backoff_base_s: float = 0.05
    retry_backoff_max_s: float = 1.0
    retry_jitter_frac: float = 0.25
    retry_seed: Optional[int] = None      # None ⇒ nondeterministic jitter
    drain_timeout_s: float = 30.0         # default budget for drain_worker
    # KV fabric (engine/kv_fabric.py): coordinator-mediated KV page
    # migration under prefix_affinity — drain hands hot prefixes (and
    # their bindings) to a survivor, respawn/scale-up pre-warms the new
    # worker BEFORE half-open, and stream failover imports the dead
    # stream's pages into the alternate instead of re-prefilling.
    kv_fabric: bool = True
    prewarm_top_k: int = 8                # bindings migrated per pre-warm
    fabric_timeout_s: float = 10.0        # per kv_export/kv_import RPC
    fabric_cache_capacity: int = 128      # wires held for failover resume
    fabric_snapshot_delay_s: float = 0.05  # let admission land before the
                                           # opportunistic background pull
    # supervisor loop (start_supervisor): auto-respawn workers the health
    # machinery declares dead, via a pluggable restart hook. Backoff
    # between failed attempts is seeded by retry_seed (same jitter source
    # as dispatch retries, so chaos runs reproduce); the crash-loop
    # breaker gives up after `threshold` failed respawns inside `window`
    # and marks the worker's shards degraded instead of flapping forever.
    supervisor_interval_s: float = 1.0
    supervisor_backoff_base_s: float = 0.5
    supervisor_backoff_max_s: float = 15.0
    supervisor_crashloop_threshold: int = 3
    supervisor_crashloop_window_s: float = 60.0
    supervisor_load_timeout_s: float = 600.0
    # flight recorder (ISSUE 19): typed event ring capacity, clock-sync
    # ping samples for the fleet-trace merge, and the post-mortem bundle
    # destination ("" disables dumping — supervision paths fire bundles
    # best-effort only when a directory is configured)
    event_ring_capacity: int = 2048
    clocksync_samples: int = 5
    events_timeout_s: float = 2.0         # per-worker events/ping RPC
    postmortem_dir: str = ""

    @classmethod
    def from_config(cls, cfg: Config) -> "CoordinatorConfig":
        return cls(batcher=cfg.batcher, cache=cfg.cache, health=cfg.health)


@dataclass
class _DisaggPool:
    """Pool membership for one disaggregated deployment. Decode placement
    lives in the registry (decode workers are the model's shards, so KV
    affinity and failover reuse the router); prefill workers are stateless
    and picked round-robin over the healthy subset."""

    prefill_ids: List[str]
    decode_ids: List[str]
    rr: int = 0


@dataclass
class _SupervisedWorker:
    """Per-worker respawn bookkeeping for the supervisor loop."""

    failures: List[float] = field(default_factory=list)  # failed-attempt
                                                         # monotonic stamps
    attempts: int = 0            # consecutive failures (backoff exponent)
    next_attempt: float = 0.0    # monotonic gate for the next try
    respawning: bool = False     # an attempt is in flight this sweep
    death_dumped: bool = False   # post-mortem fired for this incident


class Coordinator:
    """The engine-of-engines: one object that owns the whole control plane."""

    def __init__(self, config: Optional[CoordinatorConfig] = None) -> None:
        self.config = config or CoordinatorConfig()
        self.registry = ModelRegistry()
        self.router = Router(self.registry, health=self.config.health)
        self.lb = LoadBalancer(
            strategy=LoadBalancerStrategy(self.config.lb_strategy),
            health=self.config.health,
        )
        self.cache = ResponseCache(
            max_size=self.config.cache.max_size,
            policy=self.config.cache.policy,
            default_ttl=self.config.cache.default_ttl,
        )
        persist = self.config.cache.persist_path
        if persist:
            import os

            if os.path.exists(persist):
                # best-effort: a stale/corrupt snapshot must not block
                # startup — the cache is an optimization, not state of
                # record. persist_allow_pickle migrates pre-r3 pickle
                # snapshots (the next snapshot rewrites them as JSON)
                try:
                    n = self.cache.load(
                        persist,
                        allow_pickle=self.config.cache.persist_allow_pickle)
                    logger.info("restored %d cache entries from %s",
                                n, persist)
                # graftlint: ok[swallowed-transport-error] local persistence, no peer involved; a cold cache is the documented fallback
                except Exception:
                    logger.exception("cache restore from %s failed — "
                                     "starting cold", persist)
        self.batcher = Batcher(
            batch_callback=self._run_batch,
            max_batch_size=self.config.batcher.max_batch_size,
            max_latency_ms=self.config.batcher.max_latency_ms,
        )
        self._running = False
        self._cache_hits = 0
        self._submitted = 0
        self._overload_rejections = 0   # worker sheds seen (typed error)
        self._dispatch_retries = 0      # re-dispatches (transport/draining)
        self._stream_resumes = 0        # mid-stream failovers with replay
        # streaming ITL as the CONSUMER sees it (ISSUE 13): inter-frame
        # gaps measured where submit_stream delivers each frame, i.e.
        # after engine ring, worker RPC and coordinator relay. Gaps
        # never span a failover: the timer resets per dispatch attempt.
        self.stream_itl_stats = LatencyStats()
        self._stream_frames = 0         # frames relayed to consumers
        self._streams_in_flight = 0     # stream dispatches not yet done
        # a stream's wait for a pooled connection to its worker: the
        # queue between this process and the worker's slots
        self.pool_wait_stats = LatencyStats()
        # worker_id -> last observed inter-frame gap (emit lag): a
        # worker whose gauge grows is buffering frames somewhere
        self._stream_emit_lag: Dict[str, float] = {}
        self._deadline_expired = 0      # client-visible deadline outcomes
        self._drains = 0                # graceful worker drains completed
        # fleet-level graceful degradation (set_admission_shed): when the
        # autoscaler is at max fleet and still SLO-violating, requests are
        # refused AT ADMISSION with the typed overloaded outcome + a
        # retry-after hint, instead of queueing into a fleet that cannot
        # absorb them
        self._admission_shed: Optional[Dict[str, Any]] = None
        self._admission_sheds = 0       # requests refused by fleet shed
        # supervisor loop state (start_supervisor arms it)
        self._restart_hook = None
        self._supervisor_task: Optional[asyncio.Task] = None
        self._supervised: Dict[str, _SupervisedWorker] = {}
        self._degraded: set = set()     # crash-looped ids (given up)
        self._supervisor_respawns = 0
        self._supervisor_crashloop_opens = 0
        # seeded jitter source for retry backoff (retry_seed pins it for
        # reproducible chaos runs)
        self._retry_rand = random.Random(self.config.retry_seed)
        self._model_configs: Dict[str, ModelConfig] = {}
        self._tokenizers: Dict[Tuple[str, str], Any] = {}  # (model, path) -> tokenizer
        # -- KV fabric state: the prompt head behind each affinity key (so
        # the coordinator can ask a worker to export without re-learning
        # the prompt), and a bounded LRU of exported wires — the failover
        # import source when the bound worker is already dead
        self._affinity_prompts: "OrderedDict[str, Tuple[int, ...]]" = (
            OrderedDict())
        self._affinity_prompts_cap = 4096
        self._fabric_cache: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        self._fabric_prewarm_pushes = 0
        self._fabric_prewarm_failures = 0
        self._fabric_failover_imports = 0
        self._fabric_snapshot_tasks: set = set()
        # disaggregated deployments: model -> (prefill worker ids, rr cursor)
        self._disagg: Dict[str, "_DisaggPool"] = {}
        # -- observability: unified metrics + recent request traces --------
        # the registry mirrors this process's stats dicts at scrape time;
        # worker families come from the last best-effort fleet poll
        # (refreshed by metrics_text)
        self.obs_registry = MetricsRegistry()
        obs_collectors.ensure_families(self.obs_registry)
        self.obs_registry.add_collector(self._obs_collect)
        self._worker_metrics: Dict[str, Dict[str, Any]] = {}
        self._recent_traces: "OrderedDict[str, RequestTrace]" = OrderedDict()
        self._recent_traces_cap = 256
        # -- flight recorder (ISSUE 19): this process's typed event ring,
        # the collection cache of every worker's last-fetched ring (the
        # post-mortem source for DEAD workers), per-worker clock offsets
        # for the fleet-trace merge, and which worker served each recent
        # trace (so remove_worker can prune half-open traces)
        self.events = EventLog("coordinator",
                               capacity=self.config.event_ring_capacity)
        self._worker_rings: Dict[str, Dict[str, Any]] = {}
        self._clock_offsets: Dict[str, Dict[str, float]] = {}
        self._trace_worker: Dict[str, str] = {}
        self._postmortem_tasks: set = set()
        self._postmortems_written = 0
        self._last_scrape_t: Optional[float] = None
        self._scrape_count = 0
        # chaos harnesses share their FaultPlan here so bundles carry the
        # authoritative injected-fault ledger
        self.fault_plan = None
        # breaker transitions become typed events (the LB itself stays
        # obs-agnostic — it just reports state flips)
        self.lb.on_transition = self._on_breaker_transition

    # -- lifecycle ----------------------------------------------------------

    async def start(self) -> None:
        if self._running:
            return
        self._running = True
        await self.batcher.start()
        await self.router.start()
        await self.lb.start()
        if self._restart_hook is not None and self._supervisor_task is None:
            self._supervisor_task = asyncio.create_task(
                self._supervisor_loop())

    async def stop(self) -> None:
        if not self._running:
            return
        self._running = False
        await self.stop_supervisor()
        if self._postmortem_tasks:
            # let in-flight evidence dumps land (bounded), then cut them
            done, pending = await asyncio.wait(
                list(self._postmortem_tasks), timeout=5.0)
            for t in pending:
                t.cancel()
            if pending:
                await asyncio.gather(*pending, return_exceptions=True)
            self._postmortem_tasks.clear()
        if self._fabric_snapshot_tasks:
            for t in list(self._fabric_snapshot_tasks):
                t.cancel()
            await asyncio.gather(*self._fabric_snapshot_tasks,
                                 return_exceptions=True)
            self._fabric_snapshot_tasks.clear()
        await self.batcher.stop()
        await self.router.stop()
        await self.lb.stop()

    # -- fleet membership ---------------------------------------------------

    def add_worker(self, worker_id: str, host: str, port: int,
                   **metadata: Any) -> None:
        """Register a worker with both placement (router) and spreading (LB)."""
        self.router.register_worker(worker_id, host, port, **metadata)
        self.lb.register_worker(worker_id, host, port, **metadata)

    def remove_worker(self, worker_id: str) -> bool:
        """Immediate removal from both planes. Unregistering aborts the
        pooled clients' in-flight calls so anything queued against this
        worker fails fast as a transport error and requeues through the
        retry budget — instead of timing out against a gone target. For a
        graceful exit use ``drain_worker``."""
        a = self.router.unregister_worker(worker_id)
        b = self.lb.unregister_worker(worker_id)
        # a departed worker's half-open traces will never gain their
        # terminal mark — prune them so the LRU holds finished evidence,
        # not ghosts (ISSUE 19 satellite). Its last-collected event ring
        # stays in _worker_rings: that cache IS the post-mortem source.
        self._prune_traces_for_worker(worker_id)
        return a or b

    def _prune_traces_for_worker(self, worker_id: str) -> None:
        """Drop recent traces bound to ``worker_id`` that never reached a
        terminal mark (``done``) — they are half-open spans that would
        otherwise sit in the LRU until capacity evicts them."""
        stale = [rid for rid, wid in self._trace_worker.items()
                 if wid == worker_id
                 and rid in self._recent_traces
                 and "done" not in self._recent_traces[rid].marks]
        for rid in stale:
            self._recent_traces.pop(rid, None)
            self._trace_worker.pop(rid, None)

    def _on_breaker_transition(self, worker_id: str, state: str) -> None:
        """LB circuit-breaker flips, recorded as typed events."""
        etype = {"open": "breaker.open", "half_open": "breaker.half_open",
                 "closed": "breaker.close"}.get(state)
        if etype is not None:
            self.events.emit(etype, worker_id=worker_id)

    async def drain_worker(self, worker_id: str,
                           timeout_s: Optional[float] = None,
                           remove: bool = True) -> Dict[str, Any]:
        """Gracefully retire a worker: quarantine it in the LB (breaker
        force-open, so spreading stops immediately), issue the ``drain``
        verb (the worker stops admitting — new work gets the typed
        ``draining`` shed, which the retry budget moves to another replica
        — and finishes its in-flight requests), then unregister it from
        both planes. Returns the worker's drain summary (per-model
        KV/prefix/token counters) so the caller can account for what the
        worker was holding."""
        if timeout_s is None:
            timeout_s = self.config.drain_timeout_s
        # KV fabric: hand the retiree's hot prefixes off BEFORE quarantine
        # (quarantine invalidates its bindings — after that the affinity
        # table no longer remembers what this worker was serving)
        self.events.emit("drain.begin", worker_id=worker_id)
        handed_off = await self._fabric_drain_handoff(worker_id)
        self.lb.quarantine(worker_id)
        client = (self.router.client_for(worker_id)
                  if worker_id in self.router.workers
                  else self.lb.client_for(worker_id))
        summary = await client.drain(timeout_s=timeout_s)
        if handed_off:
            summary = dict(summary or {})
            summary["kv_fabric_handoff"] = handed_off
        self._drains += 1
        self.events.emit("drain.done", worker_id=worker_id)
        if remove:
            self.remove_worker(worker_id)
        return summary

    # -- fleet-level graceful degradation -----------------------------------

    def set_admission_shed(self, active: bool,
                           reason: str = "fleet_overloaded",
                           retry_after_s: float = 1.0) -> None:
        """Engage/disengage fleet-level admission shedding. While active,
        ``submit``/``submit_stream`` raise the typed ``overloaded`` outcome
        (with ``retry_after_s`` as the client backoff hint) instead of
        dispatching — the autoscaler flips this on when the fleet is at
        ``max_workers`` and still SLO-violating, and off once pressure
        clears. Cache hits are still served: they cost no engine steps."""
        if active:
            self._admission_shed = {"reason": reason,
                                    "retry_after_s": float(retry_after_s)}
        else:
            self._admission_shed = None

    def _check_admission(self, request_id: str) -> None:
        shed = self._admission_shed
        if shed is None:
            return
        self._admission_sheds += 1
        self.events.emit("admission.shed", request_id=request_id,
                         reason=shed["reason"])
        raise EngineOverloadedError(
            f"request {request_id} shed at admission: fleet at max size "
            f"and SLO-violating; retry after {shed['retry_after_s']:.2f}s",
            reason=shed["reason"], retry_after_s=shed["retry_after_s"])

    # -- supervisor: auto-respawn dead workers ------------------------------

    def start_supervisor(self, restart_hook) -> None:
        """Arm the auto-respawn loop (the elastic half of the PR 7 health
        machinery): when the router declares a worker UNHEALTHY, the
        supervisor calls ``await restart_hook(worker_id, info)`` — which
        must bring a replacement process up (typically a seconds-scale
        artifact cold-start, ``engine/artifact.py``) and return its
        ``(host, port)`` — then re-registers the worker under its ORIGINAL
        id (registry shards stay valid), reloads its models, and re-enters
        it into LB rotation half-open so the first real request is the
        trial probe. Failed attempts back off exponentially with seeded
        jitter; ``supervisor_crashloop_threshold`` failures inside
        ``supervisor_crashloop_window_s`` open the crash-loop breaker —
        the worker's shards are marked FAILED, it leaves both planes, and
        the survivors keep serving (``supervisor_reset`` re-arms it)."""
        self._restart_hook = restart_hook
        if self._running and self._supervisor_task is None:
            self._supervisor_task = asyncio.create_task(
                self._supervisor_loop())

    async def stop_supervisor(self) -> None:
        task, self._supervisor_task = self._supervisor_task, None
        if task is not None:
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass

    def respawns_in_flight(self) -> int:
        """Workers the supervisor is (or is about to be) fighting for:
        respawn attempts in flight plus routers-declared-UNHEALTHY workers
        awaiting a sweep. The autoscaler holds while this is non-zero —
        replacing capacity is the supervisor's job, not a load signal."""
        n = sum(1 for st in self._supervised.values() if st.respawning)
        n += sum(1 for info in self.router.workers.values()
                 if info.health is WorkerHealth.UNHEALTHY)
        return n

    def supervisor_reset(self, worker_id: str) -> bool:
        """Operator re-arm after a crash-loop open (e.g. the artifact was
        repaired): clears the breaker and failure window so the supervisor
        will try ``worker_id`` again. Returns True if it was degraded."""
        was = worker_id in self._degraded
        self._degraded.discard(worker_id)
        self._supervised.pop(worker_id, None)
        return was

    async def _supervisor_loop(self) -> None:
        while self._running:
            try:
                await self._supervisor_sweep()
            # graftlint: ok[swallowed-transport-error] per-attempt failures are handled (counted + backoff) inside the sweep; this guards the loop itself from dying
            except Exception:
                logger.exception("supervisor sweep failed")
            await asyncio.sleep(self.config.supervisor_interval_s)

    async def _supervisor_sweep(self) -> None:
        now = time.monotonic()
        for wid, info in list(self.router.workers.items()):
            if info.health is not WorkerHealth.UNHEALTHY:
                continue
            if wid in self._degraded:
                continue
            st = self._supervised.setdefault(wid, _SupervisedWorker())
            if not st.death_dumped:
                # first sweep that sees this incident: capture the
                # evidence while the survivors still hold it (the dead
                # worker's ring comes from the collection cache)
                st.death_dumped = True
                self._fire_postmortem("worker_death", dead_workers=(wid,))
            if st.respawning or now < st.next_attempt:
                continue
            window = self.config.supervisor_crashloop_window_s
            st.failures = [t for t in st.failures if now - t <= window]
            if len(st.failures) >= self.config.supervisor_crashloop_threshold:
                self._open_crashloop(wid)
                continue
            st.respawning = True
            try:
                await self._respawn_worker(wid, info)
                st.failures.clear()
                st.attempts = 0
                st.death_dumped = False   # next death is a new incident
            except Exception as e:
                t = time.monotonic()
                st.failures.append(t)
                st.attempts += 1
                delay = self._supervisor_backoff_s(st.attempts - 1)
                st.next_attempt = t + delay
                logger.warning(
                    "supervisor: respawn of %s failed (%s: %s) — "
                    "attempt %d, next try in %.2fs (%d/%d failures in "
                    "window)", wid, type(e).__name__, e, st.attempts,
                    delay, len(st.failures),
                    self.config.supervisor_crashloop_threshold)
                if (len(st.failures)
                        >= self.config.supervisor_crashloop_threshold):
                    # open NOW rather than waiting out the backoff: the
                    # verdict is already in
                    self._open_crashloop(wid)
            finally:
                st.respawning = False

    async def _respawn_worker(self, worker_id: str, info) -> None:
        """One respawn attempt: hook → re-register (same id) → reload this
        worker's models → rejoin LB rotation half-open."""
        if self._restart_hook is None:
            raise RuntimeError("supervisor armed without a restart hook")
        logger.warning("supervisor: worker %s is unhealthy — respawning",
                       worker_id)
        self.events.emit("respawn.begin", worker_id=worker_id)
        host_port = await self._restart_hook(worker_id, info)
        if not host_port:
            raise RuntimeError(
                f"restart hook returned {host_port!r} for {worker_id}")
        host, port = host_port
        meta = dict(info.metadata)
        # tear down the old registration only once the hook has produced a
        # replacement — keeping the id stable keeps registry shards valid
        self.remove_worker(worker_id)
        self.add_worker(worker_id, host, int(port), **meta)
        for name, mcfg in self._model_configs.items():
            shards = [s for s in self.registry.all_shards(name, mcfg.version)
                      if s.worker_id == worker_id]
            if not shards and self.registry.all_shards(name, mcfg.version):
                # sharded model, none of its shards on this worker
                continue
            # a successful load RPC is the proof of life — a hook that
            # spawned a zombie fails here and counts as a failed attempt.
            # LB-placed (register_shards=False) models have no shard rows
            # at all but still need reloading, or the replacement rejoins
            # unable to serve (and the fabric pre-warm has no engine to
            # import into).
            await self.router.client_for(worker_id).load_model(
                mcfg, timeout=self.config.supervisor_load_timeout_s)
            self.lb.add_resident_model(worker_id, name)
            for s in shards:
                s.status = ModelStatus.READY
        self.router.mark_worker_success(worker_id)
        # pre-warm BEFORE half-open: the trial probe should land against
        # imported KV, not a cold prefix cache
        if self._fabric_on():
            await self.prewarm_worker(worker_id)
        # rejoin CAUTIOUSLY: half-open means the next pick is the one
        # trial probe — success closes the circuit, failure re-opens it
        self.lb.enter_half_open(worker_id)
        self._supervisor_respawns += 1
        self.events.emit("respawn.done", worker_id=worker_id)
        logger.warning("supervisor: respawned %s at %s:%s (LB half-open)",
                       worker_id, host, port)

    def _open_crashloop(self, worker_id: str) -> None:
        if worker_id in self._degraded:
            return
        self._degraded.add(worker_id)
        self._supervisor_crashloop_opens += 1
        self.events.emit("crashloop.open", worker_id=worker_id)
        self._fire_postmortem("crashloop_open", dead_workers=(worker_id,))
        failed = 0
        for name, mcfg in self._model_configs.items():
            for s in self.registry.all_shards(name, mcfg.version):
                if s.worker_id == worker_id:
                    s.status = ModelStatus.FAILED
                    failed += 1
        # out of both planes: routing fails over deterministically to the
        # survivors instead of retrying a corpse
        self.remove_worker(worker_id)
        logger.error(
            "supervisor: crash-loop breaker OPEN for %s (%d failed "
            "respawns in %.0fs) — giving up; %d shard(s) marked FAILED, "
            "surviving workers keep serving. supervisor_reset(%r) re-arms.",
            worker_id, self.config.supervisor_crashloop_threshold,
            self.config.supervisor_crashloop_window_s, failed, worker_id)

    def _supervisor_backoff_s(self, attempt: int) -> float:
        """Exponential backoff with seeded jitter for respawn ``attempt``
        (0-based) — same jitter source as dispatch retries, so chaos runs
        reproduce."""
        base = self.config.supervisor_backoff_base_s
        if base <= 0:
            return 0.0
        delay = min(self.config.supervisor_backoff_max_s,
                    base * (2 ** attempt))
        return delay * (1.0 + self.config.retry_jitter_frac
                        * self._retry_rand.random())

    async def deploy_model(
        self,
        cfg: ModelConfig,
        worker_ids: Optional[Sequence[str]] = None,
        load_timeout_s: float = 600.0,
        register_shards: bool = True,
    ) -> int:
        """Load ``cfg`` onto workers and register one shard per worker.

        The registry's consistent hashing then spreads affinity keys across
        those shards (reference deploy flow scattered across
        ``examples/worker_demo.py`` + ``examples/router_demo.py``, unified).
        Returns the number of shards deployed.

        With ``register_shards=False`` the model is loaded as a pure replica
        set instead: every worker hosts the full model and no shards are
        registered, so requests route through the load balancer (including
        the ``prefix_affinity`` strategy) rather than the registry's
        consistent hashing. This is the deployment mode the replicated and
        affinity legs of ``examples/fleet_sweep.py`` measure.
        """
        targets = list(worker_ids) if worker_ids else list(self.router.workers)
        if not targets:
            raise RoutingError("no workers to deploy to")
        if self.registry.get_model_version(cfg.name, cfg.version) is None:
            self.registry.register_model(cfg)
        self._model_configs[cfg.name] = cfg
        # idempotent scale-out: skip workers already hosting a shard, number
        # new shards after the existing ones
        existing = self.registry.all_shards(cfg.name, cfg.version)
        hosted = {s.worker_id for s in existing}
        next_id = max((s.shard_id for s in existing), default=-1) + 1
        deployed = 0
        for wid in targets:
            if wid in hosted:
                continue
            client = self.router.client_for(wid)
            # worker-side load is idempotent for an identical config and
            # errors on a mismatched one — no error-text sniffing needed
            await client.load_model(cfg, timeout=load_timeout_s)
            # deploy-time residency hint so the LB's cold-key placement
            # prefers this worker before the next health ping lands
            self.lb.add_resident_model(wid, cfg.name)
            if register_shards:
                self.registry.add_shard(
                    cfg.name, cfg.version, shard_id=next_id,
                    worker_id=wid, status=ModelStatus.READY)
                next_id += 1
            deployed += 1
        return deployed

    async def stage_model(
        self,
        cfg: ModelConfig,
        worker_ids: Optional[Sequence[str]] = None,
        timeout_s: Optional[float] = None,
    ) -> int:
        """Start BACKGROUND staging of ``cfg`` on workers: each worker reads
        the artifact and builds the engine on a side thread while its current
        models keep serving (the stage never enters the dispatch executor).
        Returns the number of workers that began staging (workers already
        hosting an identical ``cfg.name`` are skipped). The model enters the
        coordinator catalog immediately so model-qualified affinity keys and
        tokenizer lookups resolve before the first swap lands.
        """
        targets = list(worker_ids) if worker_ids else list(self.router.workers)
        if not targets:
            raise RoutingError("no workers to stage onto")
        if self.registry.get_model_version(cfg.name, cfg.version) is None:
            self.registry.register_model(cfg)
        self._model_configs[cfg.name] = cfg
        staging = 0
        for wid in targets:
            res = await self.router.client_for(wid).stage_model(
                cfg, timeout=timeout_s)
            if not res.get("already_resident"):
                staging += 1
                self.lb.add_staged_model(wid, cfg.name)
        return staging

    async def swap_model(
        self,
        name: str,
        worker_ids: Optional[Sequence[str]] = None,
        probe: Optional[Sequence[int]] = None,
        timeout_s: Optional[float] = None,
    ) -> List[Dict[str, Any]]:
        """Hot-swap a previously staged model in on workers: wait for the
        background stage, run the golden-token probe gate, then admit the
        engine (LRU-evicting idle residents over budget). Returns the
        per-worker swap receipts (``stage_s``/``swap_s``/``evicted``...).
        """
        targets = list(worker_ids) if worker_ids else list(self.router.workers)
        if not targets:
            raise RoutingError("no workers to swap on")
        receipts = []
        for wid in targets:
            rec = await self.router.client_for(wid).swap_model(
                name, probe=probe, timeout=timeout_s)
            rec["worker_id"] = wid
            self.lb.add_resident_model(wid, name)
            receipts.append(rec)
        return receipts

    async def deploy_model_disaggregated(
        self,
        cfg: ModelConfig,
        prefill_worker_ids: Sequence[str],
        decode_worker_ids: Sequence[str],
        load_timeout_s: float = 600.0,
    ) -> Tuple[int, int]:
        """Disaggregated deployment (BASELINE.json configs[4]; SURVEY.md §2.3
        last row): load a prefill-only engine onto the prefill pool and a
        continuous decode engine onto the decode pool.

        Requests then flow coordinator → prefill worker → (KV over DCN) →
        decode worker → results back. Decode workers are registered as the
        model's shards, so affinity routing and deterministic failover apply
        to the stateful half of the pair; prefill workers are stateless and
        rotate round-robin. Returns (#prefill, #decode) workers loaded.
        """
        if not prefill_worker_ids or not decode_worker_ids:
            raise ValueError("both pools need at least one worker")
        overlap = set(prefill_worker_ids) & set(decode_worker_ids)
        if overlap:
            raise ValueError(f"workers in both pools: {sorted(overlap)}")
        unknown = [w for w in (*prefill_worker_ids, *decode_worker_ids)
                   if w not in self.router.workers]
        if unknown:
            raise RoutingError(f"unknown workers: {unknown}")

        pcfg = ModelConfig.from_dict(cfg.to_dict())
        pcfg.metadata = dict(cfg.metadata, role="prefill")
        pcfg.metadata.pop("continuous", None)
        dcfg = ModelConfig.from_dict(cfg.to_dict())
        dcfg.metadata = dict(cfg.metadata, continuous=1)
        dcfg.metadata.pop("role", None)

        if self.registry.get_model_version(cfg.name, cfg.version) is None:
            self.registry.register_model(cfg)
        self._model_configs[cfg.name] = cfg
        for wid in prefill_worker_ids:
            await self.router.client_for(wid).load_model(
                pcfg, timeout=load_timeout_s)
        existing = self.registry.all_shards(cfg.name, cfg.version)
        hosted = {s.worker_id for s in existing}
        next_id = max((s.shard_id for s in existing), default=-1) + 1
        for wid in decode_worker_ids:
            # a worker preloaded with a static engine is rejected by the
            # worker's own load_model (feature-superset check) — a failure
            # here leaves a partial deploy that is safe to resume: _disagg
            # is not set yet and re-deploy skips already-hosted shards
            await self.router.client_for(wid).load_model(
                dcfg, timeout=load_timeout_s)
            self.lb.add_resident_model(wid, cfg.name)
            if wid not in hosted:
                self.registry.add_shard(cfg.name, cfg.version,
                                        shard_id=next_id, worker_id=wid,
                                        status=ModelStatus.READY)
                next_id += 1
        self._disagg[cfg.name] = _DisaggPool(
            prefill_ids=list(prefill_worker_ids),
            decode_ids=list(decode_worker_ids),
        )
        return len(prefill_worker_ids), len(decode_worker_ids)

    def _pick_prefill_worker(self, pool: _DisaggPool) -> str:
        """Round-robin over prefill workers the router considers usable."""
        from ..cluster.router import WorkerHealth

        n = len(pool.prefill_ids)
        for i in range(n):
            wid = pool.prefill_ids[(pool.rr + i) % n]
            info = self.router.workers.get(wid)
            if info is not None and info.health is not WorkerHealth.UNHEALTHY:
                pool.rr = (pool.rr + i + 1) % n
                return wid
        raise RoutingError("no healthy prefill worker")

    def _prefix_affinity_key(self, model: str,
                             prompt: Sequence[int]) -> Optional[str]:
        """The request's routing key under ``prefix_affinity``: the MODEL
        id plus the chain hash of its leading full prompt pages (capped at
        ``affinity_pages``), as ``"<model>:<hex>"`` so it rides
        ``inputs["key"]`` over the wire. Qualifying the key by model keeps
        multi-model fleets honest twice over: identical prompts under
        different models never share a binding (their KV chains differ),
        and the LB's cold-key placement can read the model id back out of
        the key to prefer workers already holding (or staging) that model.
        ``None`` when the strategy is different or the prompt is shorter
        than one page — those requests spread normally."""
        if self.lb.strategy is not LoadBalancerStrategy.PREFIX_AFFINITY:
            return None
        page = self.config.affinity_page_size
        n_pages = min(len(prompt) // page, self.config.affinity_pages) \
            if page > 0 else 0
        if n_pages <= 0:
            return None
        from ..engine.paged_kv import page_chain_hashes

        head = [int(t) for t in prompt[:n_pages * page]]
        key = f"{model}:{page_chain_hashes(head, n_pages, page)[-1].hex()}"
        if self.config.kv_fabric:
            # remember the tokens behind the key: kv_export is asked by
            # prompt head, not by hash — the fabric needs both directions
            self._affinity_prompts[key] = tuple(head)
            self._affinity_prompts.move_to_end(key)
            while len(self._affinity_prompts) > self._affinity_prompts_cap:
                self._affinity_prompts.popitem(last=False)
        return key

    # -- KV fabric: coordinator-mediated page migration ---------------------
    #
    # Workers never talk to each other; the coordinator is the fabric.
    # It snapshots hot prefixes off their bound workers (kv_export), keeps
    # a bounded wire cache, and re-lands the pages (kv_import) on three
    # triggers: graceful drain (handoff to a survivor), respawn/scale-up
    # (pre-warm BEFORE half-open), and stream failover (resume warm
    # instead of re-prefilling). Every path is best-effort — a failed or
    # rejected import degrades to the pre-fabric behaviour, a cold prefill.

    def _fabric_on(self) -> bool:
        return (self.config.kv_fabric
                and self.lb.strategy is LoadBalancerStrategy.PREFIX_AFFINITY)

    def _fabric_client(self, worker_id: str):
        return (self.router.client_for(worker_id)
                if worker_id in self.router.workers
                else self.lb.client_for(worker_id))

    def _fabric_default_model(self) -> Optional[str]:
        return next(iter(self._model_configs), None)

    def _model_of_key(self, key: str) -> Optional[str]:
        """The model a composite affinity key belongs to. KV pages move
        through the fabric strictly under this model id, so a migration or
        pre-warm can never land one model's pages in another model's cache.
        Legacy bare-hash keys fall back to the single-model default."""
        model = self.lb.model_of_key(key)
        if model is not None and model in self._model_configs:
            return model
        return self._fabric_default_model()

    def _fabric_cache_put(self, key: str, wire: Dict[str, Any]) -> None:
        self._fabric_cache[key] = wire
        self._fabric_cache.move_to_end(key)
        while len(self._fabric_cache) > self.config.fabric_cache_capacity:
            self._fabric_cache.popitem(last=False)

    async def fabric_pull(self, model: str, key: str,
                          source_worker_id: str) -> Optional[Dict[str, Any]]:
        """Export ``key``'s prefix pages off ``source_worker_id`` into the
        coordinator's wire cache. Returns the wire, or None when the
        prompt behind the key is unknown, the export comes back empty
        (worker never prefilled it), or the RPC fails — all non-fatal."""
        tokens = self._affinity_prompts.get(key)
        if tokens is None:
            return None
        try:
            wire = await self._fabric_client(source_worker_id).kv_export(
                model, list(tokens), timeout=self.config.fabric_timeout_s)
        except TRANSPORT_ERRORS + (WorkerRPCError,):  # graftlint: ok[swallowed-transport-error] best-effort snapshot; the fallback is a normal prefill
            return None
        if wire:
            self._fabric_cache_put(key, wire)
        return wire or None

    async def prewarm_worker(self, worker_id: str,
                             model: Optional[str] = None,
                             top_k: Optional[int] = None) -> int:
        """Push the fleet's hottest bound prefixes into ``worker_id``'s
        host KV tier. Called before ``enter_half_open`` on respawn and
        scale-up so the trial probe admits against imported pages. Wires
        come from the snapshot cache, else a live export from the bound
        worker. Each key's pages move under ITS OWN model (derived from
        the composite key) — an explicit ``model`` argument instead
        restricts the pre-warm to that model's bindings. Never raises;
        returns the number of prefixes landed."""
        if not self._fabric_on():
            return 0
        k = self.config.prewarm_top_k if top_k is None else top_k
        pushed = 0
        for key, bound in self.lb.top_bindings(k):
            if bound == worker_id:
                continue
            kmodel = self._model_of_key(key)
            if kmodel is None or (model is not None and kmodel != model):
                continue
            wire = self._fabric_cache.get(key)
            if wire is None:
                wire = await self.fabric_pull(kmodel, key, bound)
            if wire is None:
                self._fabric_prewarm_failures += 1
                continue
            if await self._fabric_push(kmodel, key, worker_id, wire):
                pushed += 1
        return pushed

    async def _fabric_push(self, model: str, key: str, worker_id: str,
                           wire: Dict[str, Any]) -> bool:
        """One kv_import, fully accounted: a transport failure or a typed
        checksum reject counts as a pre-warm failure (the target simply
        stays cold), success as a push."""
        try:
            res = await self._fabric_client(worker_id).kv_import(
                model, wire, timeout=self.config.fabric_timeout_s)
        except TRANSPORT_ERRORS + (WorkerRPCError,):  # graftlint: ok[swallowed-transport-error] pre-warm is advisory; the target serves cold
            self._fabric_prewarm_failures += 1
            return False
        if res.get("rejected"):
            # the worker refused the wire (checksum/shape mismatch) —
            # never install suspect KV, fall back to prefill
            self._fabric_prewarm_failures += 1
            return False
        self._fabric_prewarm_pushes += 1
        return True

    async def _fabric_failover_import(self, model: str, key: str,
                                      worker_id: str) -> bool:
        """Failover resume: land the dead stream's cached wire on the
        alternate so the prefix replay admits warm. Cache-only — the
        bound worker just died, there is nobody left to export from."""
        wire = self._fabric_cache.get(key)
        if wire is None:
            return False
        if not await self._fabric_push(model, key, worker_id, wire):
            return False
        self._fabric_failover_imports += 1
        return True

    def _spawn_fabric_snapshot(self, model: str, key: str,
                               worker_id: str) -> None:
        """Background snapshot of a freshly-routed prefix off its bound
        worker — the failover import source. Delayed slightly, then retried
        a few times: the snapshot races the prefill that creates the pages,
        and an export taken too early is simply empty."""

        async def _snap():
            try:
                delay = self.config.fabric_snapshot_delay_s
                for attempt in range(4):
                    gap = delay if attempt == 0 else max(delay, 0.02)
                    if gap > 0:
                        await asyncio.sleep(gap)
                    if await self.fabric_pull(model, key, worker_id):
                        return
            except asyncio.CancelledError:
                raise
            except Exception:  # graftlint: ok[swallowed-transport-error] fire-and-forget snapshot; a miss only means a colder failover
                pass

        task = asyncio.get_running_loop().create_task(_snap())
        self._fabric_snapshot_tasks.add(task)
        task.add_done_callback(self._fabric_snapshot_tasks.discard)

    async def _fabric_drain_handoff(self,
                                    worker_id: str) -> Optional[Dict[str, Any]]:
        """Migrate the retiree's bound prefixes to the least-loaded
        survivor: export while the retiree is still alive, import into the
        target, then REBIND (not drop) the affinity entries so the next
        request for each prefix routes straight to the warm copy."""
        if not self._fabric_on():
            return None
        keys = self.lb.bindings_for(worker_id)[:self.config.prewarm_top_k]
        if not keys:
            return None
        survivors = [s for s in self.lb.healthy_workers()
                     if s.worker_id != worker_id]
        if not survivors:
            return None
        target = min(survivors,
                     key=lambda s: s.active_connections).worker_id
        warmed = 0
        for key in keys:
            # each key migrates under its own model — a drain of a
            # multi-model worker hands every model's pages off correctly
            model = self._model_of_key(key)
            if model is None:
                continue
            wire = self._fabric_cache.get(key)
            if wire is None:
                wire = await self.fabric_pull(model, key, worker_id)
            if wire is None:
                continue
            if await self._fabric_push(model, key, target, wire):
                warmed += 1
        # hand off ALL bindings, warm or not: the target is the new owner
        # either way and routing there keeps the table stable
        moved = self.lb.rebind_affinity(worker_id, target)
        if not moved and not warmed:
            return None
        logger.info("kv fabric: drained %s — %d binding(s) handed to %s, "
                    "%d prefix(es) imported warm", worker_id, moved,
                    target, warmed)
        return {"target": target, "bindings_moved": moved,
                "prefixes_warmed": warmed}

    # -- request path -------------------------------------------------------

    async def submit(
        self,
        model: str,
        prompt: Optional[Sequence[int]] = None,
        version: str = "1.0",
        max_new_tokens: int = 16,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        min_p: float = 0.0,
        eos_id: int = -1,
        stop_ids: Optional[Sequence[int]] = None,
        stop_sequences: Optional[Sequence[Sequence[int]]] = None,
        key: Optional[str] = None,
        request_id: Optional[str] = None,
        no_cache: bool = False,
        text: Optional[str] = None,
        deadline_s: Optional[float] = None,
    ) -> Dict[str, Any]:
        """One generation request, end to end. Returns a result dict
        (``result_to_dict`` schema) plus trace/cache metadata.

        ``text`` is the preproc/postproc path the reference README declares
        (``README.md:96-98``): the coordinator tokenizes it host-side
        (``utils/tokenizer.py``) and the result carries a detokenized
        ``"text"`` field alongside the raw tokens.

        ``deadline_s`` is an end-to-end budget in seconds. The coordinator
        spends part of it queueing in the batcher (an expired request is
        rejected before any dispatch), forwards the REMAINDER in the
        request so the worker's engine sheds it from its own queue rather
        than spending decode steps on an answer nobody is waiting for, and
        raises the typed ``DeadlineExceededError`` on expiry. Deadline
        outcomes are never retried — the budget is gone wherever it runs.
        """
        if not self._running:
            raise RuntimeError("coordinator is not running")
        tokenizer = None
        if text is not None:
            if prompt is not None:
                raise ValueError("pass prompt or text, not both")
            tokenizer = self._tokenizer_for(model)
            prompt = tokenizer.encode(text)
        if not prompt:
            raise ValueError("empty prompt")
        self._submitted += 1
        request_id = request_id or new_request_id()
        # two routing handles: "key" feeds the sharded path's consistent
        # hashing (always non-None), "affinity" feeds the LB's
        # prefix_affinity strategy -- None for short/keyless prompts, which
        # must spread via the keyless fallback instead of polluting the
        # binding table with one-shot request ids
        affinity = key if key is not None else \
            self._prefix_affinity_key(model, prompt)
        trace = RequestTrace(request_id=request_id)
        trace.mark("received")

        cacheable = (self.config.cache_enabled and not no_cache
                     and temperature == 0.0)
        cache_key: Optional[Tuple] = None
        if cacheable:
            cache_key = (model, version, tuple(prompt), max_new_tokens,
                         top_k, top_p, min_p, eos_id,
                         tuple(stop_ids or ()),
                         tuple(tuple(sq) for sq in (stop_sequences or ())))
            hit = self.cache.get(cache_key)
            if hit is not None:
                self._cache_hits += 1
                trace.mark("done")
                # deep copy: callers may mutate result['tokens']/['metadata'],
                # which must not corrupt the cached entry
                out = copy.deepcopy(hit)
                out["request_id"] = request_id
                out["cached"] = True
                out["trace"] = trace.to_dict()
                self._remember_trace(trace)
                if tokenizer is not None:
                    # entries are cached in token space only; text is derived
                    # per-request so token- and text-mode callers can share
                    # one entry and each get a consistent schema
                    out["text"] = tokenizer.decode(out.get("tokens", []))
                return out

        # fleet-level degradation gate sits AFTER the cache lookup (hits
        # cost no engine steps) and BEFORE any dispatch work
        self._check_admission(request_id)
        inputs = {
            "prompt": list(prompt),
            "max_new_tokens": max_new_tokens,
            "temperature": temperature,
            "top_k": top_k,
            "top_p": top_p,
            "min_p": min_p,
            "eos_id": eos_id,
            "stop_ids": list(stop_ids or ()),
            "stop_sequences": [list(sq) for sq in (stop_sequences or ())],
            "request_id": request_id,
            "key": affinity if affinity is not None else request_id,
            "affinity": affinity,
            "deadline_s": deadline_s,
            # coordinator-local keys (request_from_dict ignores them, they
            # never cross the wire): the live trace so _run_batch can mark
            # routing/dispatch phases and merge the worker-side spans, and
            # _t0 anchoring the deadline budget at submission time
            "trace": trace,
            "_t0": time.monotonic(),
        }
        future = await self.batcher.add_request(
            model, version, inputs, request_id=request_id, trace=trace
        )
        result: Dict[str, Any] = await future
        if result.get("finish_reason") == "deadline":
            # typed outcome, never cached, never retried: the budget is
            # spent whether it expired in our batcher queue, the worker's
            # engine queue, or mid-decode
            self._deadline_expired += 1
            raise DeadlineExceededError(
                f"request {request_id} deadline ({deadline_s}s) expired "
                "before completion", request_id=request_id)
        if result.get("finish_reason") == "overloaded":
            # client-visible typed outcome (VERDICT r2 item 2): every
            # replica the dispatch tried shed this request — the caller
            # must back off, and the outcome must never enter the cache
            raise EngineOverloadedError(
                f"request {request_id} shed by every tried replica "
                f"({result.get('metadata', {}).get('overload_reason', '?')})"
                "; back off and retry",
                reason=result.get("metadata", {}).get("overload_reason",
                                                      "queue_full"))
        trace.mark("done")
        self._remember_trace(trace)
        result = dict(result)
        result["cached"] = False
        result["trace"] = trace.to_dict()
        if tokenizer is not None:
            result["text"] = tokenizer.decode(result.get("tokens", []))
        if cacheable and cache_key is not None:
            stripped = {k: v for k, v in result.items()
                        if k not in ("trace", "cached", "text")}
            self.cache.set(cache_key, stripped)
        return result

    async def submit_stream(
        self,
        model: str,
        prompt: Optional[Sequence[int]] = None,
        on_tokens=None,
        version: str = "1.0",
        max_new_tokens: int = 16,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        min_p: float = 0.0,
        eos_id: int = -1,
        stop_ids: Optional[Sequence[int]] = None,
        stop_sequences: Optional[Sequence[Sequence[int]]] = None,
        key: Optional[str] = None,
        request_id: Optional[str] = None,
        text: Optional[str] = None,
        deadline_s: Optional[float] = None,
    ) -> Dict[str, Any]:
        """Streaming variant of ``submit``: ``on_tokens(tokens)`` fires as
        the worker decodes. Bypasses the response cache and the batcher —
        a streaming request is dispatched immediately on its own (it still
        shares the worker's rolling decode batch with everything else).
        Not yet supported on disaggregated deployments.

        A worker dying MID-stream is no longer terminal: the coordinator
        resumes on an alternate replica by replaying prompt + the already-
        delivered prefix as the new prompt (greedy decode is a pure
        function of context, so the continuation is token-for-token what
        the dead worker would have produced) — the caller's ``on_tokens``
        never sees a duplicate or a gap."""
        if not self._running:
            raise RuntimeError("coordinator is not running")
        if model in self._disagg:
            raise ValueError(
                "streaming is not supported on disaggregated deployments")
        tokenizer = None
        if text is not None:
            if prompt is not None:
                raise ValueError("pass prompt or text, not both")
            tokenizer = self._tokenizer_for(model)
            prompt = tokenizer.encode(text)
        if not prompt:
            raise ValueError("empty prompt")
        self._submitted += 1
        request_id = request_id or new_request_id()
        # two routing handles: "key" feeds the sharded path's consistent
        # hashing (always non-None), "affinity" feeds the LB's
        # prefix_affinity strategy -- None for short/keyless prompts, which
        # must spread via the keyless fallback instead of polluting the
        # binding table with one-shot request ids
        affinity = key if key is not None else \
            self._prefix_affinity_key(model, prompt)
        trace = RequestTrace(request_id=request_id)
        trace.mark("received")
        # streams bypass the cache, so the degradation gate is the first
        # stop after admission bookkeeping
        self._check_admission(request_id)

        route_key = affinity if affinity is not None else request_id
        sharded = bool(self.registry.all_shards(model, version))
        if sharded:
            worker_id = self.router.route_request(
                model, version, route_key).worker.worker_id
        else:
            worker_id = self.lb.get_worker(affinity=affinity).worker_id
        trace.mark("routed")
        if (affinity is not None and self._fabric_on()
                and affinity not in self._fabric_cache):
            # opportunistic snapshot: pull this prefix's pages off the bound
            # worker in the background so a later failover can import them
            # even though the binding's owner is dead by then
            self._spawn_fabric_snapshot(model, affinity, worker_id)

        req = request_from_dict({
            "prompt": list(prompt), "max_new_tokens": max_new_tokens,
            "temperature": temperature, "top_k": top_k, "top_p": top_p,
            "min_p": min_p, "eos_id": eos_id,
            "stop_ids": list(stop_ids or ()),
            "stop_sequences": [list(sq) for sq in (stop_sequences or ())],
            "request_id": request_id,
        })
        delivered: List[int] = []
        cb = on_tokens or (lambda toks: None)
        # streaming ITL (ISSUE 13): stamp the gap between consecutive
        # frames AS DELIVERED to the consumer — after the engine ring,
        # the worker RPC relay and this coordinator hop. The timer
        # resets before every dispatch attempt so a failover's detect +
        # replay delay lands in stream_resumes/the trace, never here.
        _last_frame = [0.0]

        def counting_cb(toks):
            now = time.perf_counter()
            if not delivered:
                trace.mark("first_frame")
            if _last_frame[0]:
                gap = now - _last_frame[0]
                self.stream_itl_stats.add(gap)
                self._stream_emit_lag[worker_id] = gap
            _last_frame[0] = now
            self._stream_frames += 1
            delivered.extend(toks)
            cb(toks)

        trace.mark("dispatched")
        t0 = time.monotonic()
        tried = {worker_id}
        attempt = 0
        while True:
            prefix = len(delivered)
            remaining_budget: Optional[float] = None
            if deadline_s is not None:
                remaining_budget = deadline_s - (time.monotonic() - t0)
                if remaining_budget <= 0:
                    self._deadline_expired += 1
                    raise DeadlineExceededError(
                        f"request {request_id} deadline ({deadline_s}s) "
                        "expired before completion", request_id=request_id)
            if prefix and max_new_tokens - prefix <= 0:
                # the stream died delivering its very last token — nothing
                # left to generate, so synthesize the final result from
                # what already streamed
                result = GenerationResult(
                    request_id=request_id, tokens=list(delivered),
                    finish_reason="length", prompt_tokens=len(prompt),
                    metadata={"stream_resumed": attempt})
                break
            # resume: replay prompt + delivered prefix as the new prompt;
            # greedy decode continues with exactly the tokens the dead
            # worker would have produced next
            run_req = dataclasses.replace(
                req,
                prompt=(list(prompt) + list(delivered)) if prefix
                else list(prompt),
                max_new_tokens=max_new_tokens - prefix,
                deadline_s=remaining_budget)
            try:
                _last_frame[0] = 0.0     # new attempt: no cross-attempt gap
                result = await self._stream_once(model, worker_id, run_req,
                                                 counting_cb, trace)
            except TRANSPORT_ERRORS as e:
                alt = (None if attempt >= self.config.max_dispatch_retries
                       else self._pick_alternate(model, version, worker_id,
                                                 route_key, sharded,
                                                 exclude=tried))
                if alt is None:
                    raise
                tried.add(alt)
                # the replay lands the prefix on the alternate: any affinity
                # binding still pointing at the dead worker is known-stale
                # even though its breaker may not have tripped yet
                self.lb.invalidate_affinity(worker_id)
                if affinity is not None and self._fabric_on():
                    # resume WARM: import the dead stream's KV pages from
                    # the snapshot cache so the prefix replay admits against
                    # imported pages instead of re-prefilling cold — and
                    # hand the binding to the importer
                    if await self._fabric_failover_import(model, affinity,
                                                          alt):
                        self.lb.bind_affinity(affinity, alt)
                attempt += 1
                self._dispatch_retries += 1
                if delivered:
                    self._stream_resumes += 1
                    self.events.emit("dispatch.failover",
                                     request_id=request_id,
                                     from_worker=worker_id, to_worker=alt,
                                     prefix_tokens=len(delivered))
                    logger.warning(
                        "stream to %s died after %d tokens (%s) — resuming "
                        "on %s with prefix replay", worker_id,
                        len(delivered), type(e).__name__, alt)
                else:
                    logger.warning("stream dispatch to %s failed (%s) — "
                                   "retrying on %s", worker_id,
                                   type(e).__name__, alt)
                delay = self._retry_backoff_s(attempt - 1)
                if delay:
                    await asyncio.sleep(delay)
                worker_id = alt
                continue
            except WorkerRPCError as e:
                kind = getattr(e, "kind", "")
                if kind == "deadline":
                    # the worker's engine expired it in-queue: typed
                    # outcome, never retried
                    self._deadline_expired += 1
                    raise DeadlineExceededError(
                        f"request {request_id} deadline expired before "
                        "completion", request_id=request_id) from e
                if kind != "overloaded":
                    raise
                reason = shed_reason(e)
                if reason == REASON_DRAINING:
                    # admission refused while the worker retires — nothing
                    # streamed on THIS attempt (draining rejects before
                    # admission), so any other replica can take it, even
                    # mid-resume
                    alt = (None
                           if attempt >= self.config.max_dispatch_retries
                           else self._pick_alternate(model, version,
                                                     worker_id, route_key,
                                                     sharded, exclude=tried))
                    if alt is not None:
                        tried.add(alt)
                        attempt += 1
                        self._dispatch_retries += 1
                        logger.info("worker %s draining — moving stream "
                                    "to %s", worker_id, alt)
                        worker_id = alt
                        continue
                # queue_full (or draining with nowhere to go): one
                # alternate, then the typed error + counter — the batch
                # path's contract
                if delivered:
                    self._overload_rejections += 1
                    raise EngineOverloadedError(
                        f"request {request_id} shed after {len(delivered)} "
                        "tokens streamed; back off and retry",
                        reason=reason) from e
                alt = self._pick_alternate(model, version, worker_id,
                                           route_key, sharded, exclude=tried)
                if alt is None:
                    self._overload_rejections += 1
                    raise EngineOverloadedError(
                        f"request {request_id} shed ({e}); back off and "
                        "retry", reason=reason) from e
                tried.add(alt)
                logger.info("stream shed by %s — retrying on %s",
                            worker_id, alt)
                try:
                    worker_id = alt
                    _last_frame[0] = 0.0
                    result = await self._stream_once(model, worker_id,
                                                     run_req, counting_cb,
                                                     trace)
                except WorkerRPCError as e2:
                    if getattr(e2, "kind", "") != "overloaded":
                        raise
                    self._overload_rejections += 1
                    raise EngineOverloadedError(
                        f"request {request_id} shed by every tried "
                        "replica; back off and retry",
                        reason=shed_reason(e2)) from e2
            if prefix:
                # the resumed worker only saw the continuation — stitch
                # the full token sequence (matching what streamed) and the
                # original prompt length back together
                result.tokens = list(delivered[:prefix]) + list(result.tokens)
                result.prompt_tokens = len(prompt)
                result.metadata["stream_resumed"] = attempt
            break
        trace.mark("done")
        out = result_to_dict(result)
        out["cached"] = False
        out["streamed"] = True
        out["metadata"]["worker_id"] = worker_id
        self._merge_worker_trace({"trace": trace}, out)
        self._bind_trace_worker(trace.request_id, worker_id)
        self._remember_trace(trace)
        out["trace"] = trace.to_dict()
        if tokenizer is not None:
            out["text"] = tokenizer.decode(out.get("tokens", []))
        return out

    async def _stream_once(self, model: str, worker_id: str, req,
                           on_tokens, trace: RequestTrace) -> Any:
        """One streaming dispatch with the same health accounting as
        ``_dispatch_once``. Marks ``conn_acquired`` on ``trace`` when the
        dispatch holds its pooled connection to the worker (first attempt
        only: marks are first-wins) and records the wait."""
        client = (self.router.client_for(worker_id)
                  if worker_id in self.router.workers
                  else self.lb.client_for(worker_id))

        def acquired(wait_s: float) -> None:
            trace.mark("conn_acquired")
            self.pool_wait_stats.add(wait_s)

        self.lb.acquire(worker_id)
        self._streams_in_flight += 1
        t0 = time.perf_counter()
        try:
            result = await client.generate_stream(
                model, req, on_tokens,
                timeout=self.config.dispatch_timeout_s,
                on_acquired=acquired,
            )
        except Exception as e:
            # overloaded: neither an LB failure nor a health event (see
            # _dispatch_once) — the streaming handler relays the engine's
            # typed shed as an RPC error with kind="overloaded"
            if getattr(e, "kind", "") != "overloaded":
                self.lb.update_stats(worker_id, success=False,
                                     latency_s=time.perf_counter() - t0)
            if not isinstance(e, WorkerRPCError):
                self.router.mark_worker_failure(worker_id)
            raise
        finally:
            self._streams_in_flight -= 1
            self.lb.release(worker_id)
        self.lb.update_stats(worker_id, success=True,
                             latency_s=time.perf_counter() - t0)
        self.router.mark_worker_success(worker_id)
        return result

    def _tokenizer_for(self, model: str):
        """Per-model tokenizer keyed by (name, path) so a redeploy with a new
        checkpoint path picks up fresh vocab files."""
        cfg = self._model_configs.get(model)
        path = cfg.path if cfg else ""
        key = (model, path)
        tok = self._tokenizers.get(key)
        if tok is None:
            from ..utils.tokenizer import ByteTokenizer, build_tokenizer

            tok = build_tokenizer(path)
            if (isinstance(tok, ByteTokenizer) and cfg is not None
                    and cfg.architecture != "fake"
                    and cfg.metadata.get("tokenizer") != "byte"):
                logger.warning(
                    "model %s has no vocab.json/merges.txt under %r — text "
                    "requests use the byte-level tokenizer, whose ids do NOT "
                    "match a trained BPE vocab (set metadata.tokenizer='byte' "
                    "to silence)", model, path,
                )
            self._tokenizers[key] = tok
        return tok

    # -- batch dispatch (the batcher's backend) -----------------------------

    async def _run_batch(self, model: str, version: str,
                         inputs: List[Any]) -> List[Dict[str, Any]]:
        reals = [i for i in inputs if i is not PAD_INPUT
                 and not (isinstance(i, dict) and i.get("__pad__"))]
        if not reals:
            return []
        sharded = bool(self.registry.all_shards(model, version))
        results: List[Any] = [None] * len(reals)
        # group requests by target worker; a routing failure is isolated to
        # its own request (other requests in the batch still dispatch)
        groups: Dict[str, List[int]] = {}
        if sharded:
            for idx, inp in enumerate(reals):
                try:
                    route = self.router.route_request(model, version, inp["key"])
                except Exception as e:
                    results[idx] = e
                    continue
                self._trace_mark(inp, "routed")
                groups.setdefault(route.worker.worker_id, []).append(idx)
        elif self.lb.strategy is LoadBalancerStrategy.PREFIX_AFFINITY:
            # per-request affinity picks: same-prefix requests in one batch
            # group onto the same (warm) worker, cold prefixes spread
            for idx, inp in enumerate(reals):
                try:
                    picked = self.lb.get_worker(affinity=inp.get("affinity"))
                except Exception as e:
                    results[idx] = e
                    continue
                self._trace_mark(inp, "routed")
                aff = inp.get("affinity")
                if (aff is not None and self._fabric_on()
                        and aff not in self._fabric_cache):
                    # snapshot the freshly-bound prefix off its worker so a
                    # later failover/pre-warm can land it somewhere else
                    self._spawn_fabric_snapshot(model, aff, picked.worker_id)
                groups.setdefault(picked.worker_id, []).append(idx)
        else:
            picked = self.lb.get_worker()
            for inp in reals:
                self._trace_mark(inp, "routed")
            groups[picked.worker_id] = list(range(len(reals)))

        async def run_group(worker_id: str, idxs: List[int]) -> None:
            # deadline gate BEFORE dispatch: a request whose budget expired
            # while queued in the batcher is answered locally — no RPC, no
            # decode step, typed "deadline" outcome. Survivors carry the
            # REMAINING budget so the worker's engine can expire them from
            # its own queue.
            now = time.monotonic()
            live: List[int] = []
            for i in idxs:
                inp = reals[i]
                dl = inp.get("deadline_s")
                if dl is not None and now - inp.get("_t0", now) >= dl:
                    results[i] = {
                        "request_id": inp["request_id"], "tokens": [],
                        "finish_reason": "deadline",
                        "prompt_tokens": len(inp["prompt"]), "logprobs": [],
                        "ttft_s": 0.0, "decode_s": 0.0,
                        "metadata": {"deadline_s": dl,
                                     "expired": "coordinator_queue"},
                    }
                    continue
                live.append(i)
            if not live:
                return
            idxs = live
            reqs = []
            for i in idxs:
                req = request_from_dict(reals[i])
                if req.deadline_s is not None:
                    req.deadline_s = max(
                        0.0, req.deadline_s
                        - (now - reals[i].get("_t0", now)))
                reqs.append(req)
            for i in idxs:
                self._trace_mark(reals[i], "dispatched")
            try:
                outs = await self._dispatch_with_retry(
                    model, version, worker_id, reqs,
                    keys=[reals[i]["key"] for i in idxs], sharded=sharded,
                )
            except Exception as e:
                # isolate the failure to this group's requests — other
                # groups' completed generations must not be discarded (the
                # batcher fans an Exception entry to just that future)
                for i in idxs:
                    results[i] = e
                return
            for i, out in zip(idxs, outs):
                results[i] = out
            # sheds come back as per-request "overloaded" results while
            # their siblings' generations stand: retry JUST the shed
            # subset, once, on one alternate replica — an overloaded
            # worker is busy, not unhealthy, and retry loops would only
            # move the overload around the fleet
            shed = [i for i, out in zip(idxs, outs)
                    if isinstance(out, dict)
                    and out.get("finish_reason") == "overloaded"]
            if not shed:
                return
            alt = self._pick_alternate(model, version, worker_id,
                                       reals[shed[0]]["key"], sharded)
            if alt is not None:
                logger.info("%d request(s) shed by %s — retrying on %s",
                            len(shed), worker_id, alt)
                try:
                    retry_outs = await self._dispatch_once(
                        model, alt, [request_from_dict(reals[i])
                                     for i in shed])
                    for i, out in zip(shed, retry_outs):
                        results[i] = out
                # graftlint: ok[swallowed-transport-error] _dispatch_once already dented the alternate's LB/router health before raising; surfacing the original typed shed is the one-alternate contract
                except Exception:
                    logger.warning("shed-retry on %s failed — surfacing "
                                   "the original overloaded outcome", alt)
            self._overload_rejections += sum(
                1 for i in shed
                if isinstance(results[i], dict)
                and results[i].get("finish_reason") == "overloaded")

        await asyncio.gather(*(run_group(w, idxs)
                               for w, idxs in groups.items()))
        # anchor worker-reported phase offsets onto each request's local
        # trace timeline (after shed-retries settled, so the span set
        # reflects the dispatch that actually produced the result)
        for inp, out in zip(reals, results):
            self._merge_worker_trace(inp, out)
            # remember which worker served each trace so remove_worker can
            # prune the half-open ones bound to a departed worker
            if isinstance(inp, dict) and isinstance(out, dict):
                tr = inp.get("trace")
                wid = out.get("metadata", {}).get("worker_id")
                if isinstance(tr, RequestTrace) and wid:
                    self._bind_trace_worker(tr.request_id, str(wid))
        return results  # aligned with the real inputs, pads dropped

    def _retry_backoff_s(self, attempt: int) -> float:
        """Exponential backoff with jitter for re-dispatch ``attempt``
        (0-based): ``min(max, base·2^attempt)·(1 + jitter·U[0,1))``. The
        jitter source is seeded by ``retry_seed`` so chaos runs reproduce."""
        base = self.config.retry_backoff_base_s
        if base <= 0:
            return 0.0
        delay = min(self.config.retry_backoff_max_s, base * (2 ** attempt))
        return delay * (1.0 + self.config.retry_jitter_frac
                        * self._retry_rand.random())

    async def _dispatch_with_retry(
        self, model: str, version: str, worker_id: str,
        reqs: List, keys: List[str], sharded: bool,
    ) -> List[Dict[str, Any]]:
        """Budgeted dispatch. Transport failures, dead decode peers and
        ``draining`` sheds retry on an UNTRIED replica with exponential
        backoff + jitter, at most ``max_dispatch_retries`` re-dispatches.
        ``queue_full`` sheds keep the one-alternate contract — an
        overloaded worker is busy, not broken, and retry loops would only
        move the overload around the fleet. Application errors (and
        deadline outcomes, which come back as per-request results) never
        retry."""
        tried = {worker_id}
        wid = worker_id
        attempt = 0
        while True:
            try:
                return await self._dispatch_once(model, wid, reqs)
            except TRANSPORT_ERRORS as e:
                # _dispatch_once already marked the failure — don't
                # double-count health here
                err: Exception = e
            except WorkerRPCError as e:
                kind = getattr(e, "kind", "")
                if (model in self._disagg
                        and kind == DECODE_PEER_UNREACHABLE):
                    # disaggregated relay reporting its decode peer down:
                    # the decode worker was already marked in
                    # _dispatch_disagg_once — move to an alternate shard
                    err = e
                elif kind == "overloaded" and shed_reason(e) == REASON_DRAINING:
                    # a draining worker refused admission while finishing
                    # its in-flight work: not overload, just "not here" —
                    # any untried replica can take it
                    err = e
                elif kind == "overloaded":
                    return await self._dispatch_shed_alternate(
                        model, version, wid, reqs, keys, sharded, e)
                else:
                    raise
            if attempt >= self.config.max_dispatch_retries:
                raise err
            if (model in self._disagg
                    and isinstance(err, TRANSPORT_ERRORS)):
                # disaggregated: the failure was the (stateless) prefill
                # worker, already marked; re-dispatch re-picks a prefill
                # from the healthy remainder — decode target unchanged
                alt = wid
            else:
                alt = self._pick_alternate(model, version, wid, keys[0],
                                           sharded, exclude=tried)
                if alt is None:
                    raise err
                tried.add(alt)
                # moving the batch off wid: its affinity bindings are stale
                self.lb.invalidate_affinity(wid)
                if self._fabric_on():
                    # resume warm on the alternate: land each dead prefix's
                    # cached wire there and hand the binding over, so the
                    # retry (and everything after it) admits against
                    # imported KV instead of re-prefilling cold
                    for akey in dict.fromkeys(keys):
                        if (akey in self._affinity_prompts
                                and await self._fabric_failover_import(
                                    model, akey, alt)):
                            self.lb.bind_affinity(akey, alt)
            attempt += 1
            self._dispatch_retries += 1
            self.events.emit("dispatch.retry", from_worker=wid,
                             to_worker=alt, attempt=attempt)
            delay = self._retry_backoff_s(attempt - 1)
            logger.warning(
                "dispatch to %s failed (%s: %s) — retry %d/%d on %s in "
                "%.0fms", wid, type(err).__name__, err, attempt,
                self.config.max_dispatch_retries, alt, delay * 1e3)
            if delay:
                await asyncio.sleep(delay)
            wid = alt

    async def _dispatch_shed_alternate(
        self, model: str, version: str, worker_id: str,
        reqs: List, keys: List[str], sharded: bool, exc: Exception,
    ) -> List[Dict[str, Any]]:
        """Whole-call ``queue_full`` shed: one alternate, then surface.
        Batch-path sheds normally arrive as per-request results (run_group
        handles those); a whole-call overloaded error reaches here only
        from the streaming handler's typed raise relayed through a batch
        call — defense in depth. ``_overload_rejections`` counts FINAL
        client-visible sheds only (same meaning as run_group's per-request
        count), so a successful alternate dispatch is not a rejection."""
        alt = self._pick_alternate(model, version, worker_id,
                                   keys[0], sharded)
        if alt is None:
            self._overload_rejections += 1
            raise exc
        logger.info("worker %s overloaded — trying alternate %s",
                    worker_id, alt)
        try:
            return await self._dispatch_once(model, alt, reqs)
        except WorkerRPCError as e2:
            if getattr(e2, "kind", "") != "overloaded":
                raise
            # both replicas shed: count + typed error, same contract as
            # the streaming path
            self._overload_rejections += 1
            raise EngineOverloadedError(
                "request shed by every tried replica; back off "
                "and retry", reason=shed_reason(e2)) from e2

    def _pick_alternate(self, model: str, version: str, failed: str,
                        key: str, sharded: bool,
                        exclude: Optional[set] = None) -> Optional[str]:
        """An untried replacement for ``failed``. ``exclude`` carries every
        worker the retry budget has already tried (the failed one is always
        excluded) so a multi-attempt retry walks the fleet instead of
        ping-ponging between two hosts."""
        excluded = set(exclude) if exclude else set()
        excluded.add(failed)
        if sharded:
            if not self.config.health.enable_failover:
                return None
            # exclude the WORKERS, not just one shard — a failed host may
            # hold several shards and the deterministic backup must not land
            # on any of them
            alt = self.router._find_alternative_shard(
                model, version, key, exclude=-1, exclude_worker=excluded,
            )
            return alt.worker_id if alt else None
        candidates = [s for s in self.lb.healthy_workers()
                      if s.worker_id not in excluded]
        if not candidates:
            return None
        return min(candidates, key=lambda s: s.active_connections).worker_id

    async def _dispatch_once(self, model: str, worker_id: str,
                             reqs: List) -> List[Dict[str, Any]]:
        pool = self._disagg.get(model)
        if pool is not None:
            return await self._dispatch_disagg_once(model, pool,
                                                    worker_id, reqs)
        client = (self.router.client_for(worker_id)
                  if worker_id in self.router.workers
                  else self.lb.client_for(worker_id))
        self.lb.acquire(worker_id)
        t0 = time.perf_counter()
        try:
            results = await client.generate(
                model, reqs, timeout=self.config.dispatch_timeout_s
            )
        except Exception as e:
            # every failed request counts against the worker's LB stats
            # (reference update_stats semantics); only transport-level
            # trouble additionally dents router health — an app error
            # (e.g. bad model name) doesn't mean the worker is down.
            # Overload sheds count as NEITHER: success=False feeds the
            # LB's consecutive-failure eviction, and evicting the busiest
            # worker shifts its load onto the rest and cascades (r3
            # review finding) — a shed worker served exactly what it was
            # asked to: a fast typed refusal
            if getattr(e, "kind", "") != "overloaded":
                self.lb.update_stats(worker_id, success=False,
                                     latency_s=time.perf_counter() - t0)
            if not isinstance(e, WorkerRPCError):
                self.router.mark_worker_failure(worker_id)
            raise
        finally:
            self.lb.release(worker_id)
        self.lb.update_stats(worker_id, success=True,
                             latency_s=time.perf_counter() - t0)
        self.router.mark_worker_success(worker_id)
        out = []
        for r in results:
            d = result_to_dict(r)
            d["metadata"]["worker_id"] = worker_id   # end-to-end trace: who served
            out.append(d)
        return out

    async def _dispatch_disagg_once(
        self, model: str, pool: _DisaggPool, decode_wid: str, reqs: List,
    ) -> List[Dict[str, Any]]:
        """One disaggregated dispatch: requests go to a prefill worker,
        which hands the KV to ``decode_wid`` (the router-chosen shard) and
        relays the finished results.

        Health accounting targets the prefill worker — it is the peer this
        coordinator actually talked to. A decode worker that died mid-decode
        surfaces as a ``WorkerRPCError`` relayed by the prefill worker; the
        router's own health probes (not this path) take the decode worker
        out of the shard rotation within a probe interval.
        """
        pwid = self._pick_prefill_worker(pool)
        pclient = self.router.client_for(pwid)
        dinfo = self.router.workers.get(decode_wid)
        if dinfo is None:
            # stale shard (worker removed between routing and dispatch):
            # same error class as a dead peer, so the retry path moves the
            # group to an alternate decode shard
            raise WorkerRPCError(
                f"decode worker {decode_wid!r} is no longer registered",
                kind=DECODE_PEER_UNREACHABLE,
            )
        self.lb.acquire(pwid)
        t0 = time.perf_counter()
        try:
            cfg = self._model_configs.get(model)
            results = await pclient.prefill_generate(
                model, reqs, decode_host=dinfo.host, decode_port=dinfo.port,
                timeout=self.config.dispatch_timeout_s,
                # deploy knob: metadata.pipeline_groups > 1 overlaps the
                # prefill pool's compute with KV transfer + decode
                # admission (long-prompt deploys; examples/disagg_bench.py
                # measures the crossover)
                pipeline_groups=int(
                    (cfg.metadata.get("pipeline_groups", 1)) if cfg else 1),
            )
        except Exception as e:
            if getattr(e, "kind", "") == DECODE_PEER_UNREACHABLE:
                # the prefill worker is fine — it reported its decode peer
                # down; dent the DECODE worker so routing moves off it now
                # instead of waiting for a health-probe interval
                self.router.mark_worker_failure(decode_wid)
                self.lb.update_stats(decode_wid, success=False,
                                     latency_s=time.perf_counter() - t0)
            else:
                self.lb.update_stats(pwid, success=False,
                                     latency_s=time.perf_counter() - t0)
                if not isinstance(e, WorkerRPCError):
                    self.router.mark_worker_failure(pwid)
            raise
        finally:
            self.lb.release(pwid)
        self.lb.update_stats(pwid, success=True,
                             latency_s=time.perf_counter() - t0)
        self.router.mark_worker_success(pwid)
        self.router.mark_worker_success(decode_wid)  # round-trip proves it live
        out = []
        for r in results:
            d = result_to_dict(r)
            d["metadata"]["worker_id"] = f"{pwid}+{decode_wid}"
            d["metadata"]["prefill_worker"] = pwid
            d["metadata"]["decode_worker"] = decode_wid
            out.append(d)
        return out

    # -- state snapshot / resume (SURVEY.md §5 checkpoint row) --------------

    def save_state(self, path: str) -> str:
        """Snapshot the control plane to a JSON file: registry (shards,
        versions, hashes — the reference's ``to_dict`` round-trip,
        ``src/model_registry.py:192-249``, finally given file IO), fleet
        membership, model configs and disaggregated pools."""
        import json

        from ..utils.files import atomic_write

        state = {
            "version": 1,
            "registry": self.registry.to_dict(),
            "workers": {
                wid: {"host": info.host, "port": info.port,
                      "metadata": dict(info.metadata)}
                for wid, info in self.router.workers.items()
            },
            "model_configs": {name: cfg.to_dict()
                              for name, cfg in self._model_configs.items()},
            "disaggregated": {
                m: {"prefill": p.prefill_ids, "decode": p.decode_ids}
                for m, p in self._disagg.items()
            },
        }
        # atomic replace: a crash mid-write must not corrupt the snapshot
        atomic_write(path, lambda f: json.dump(state, f, indent=2))
        if self.config.cache.persist_path:
            # cache snapshot rides the state snapshot in its own file —
            # entry payloads (and their volume) don't belong inside the
            # control-plane record. Best-effort, symmetric with the
            # startup-side load: the cache is an optimization — its save
            # failing must not fail the control-plane snapshot that
            # already landed
            try:
                self.cache.save(self.config.cache.persist_path)
            # graftlint: ok[swallowed-transport-error] local persistence, no peer involved; the control-plane snapshot already landed
            except Exception:
                logger.exception("cache snapshot to %s failed — control-"
                                 "plane state was saved",
                                 self.config.cache.persist_path)
        return path

    async def restore_state(self, path: str, redeploy: bool = False,
                            load_timeout_s: float = 600.0) -> int:
        """Rebuild the control plane from a ``save_state`` snapshot.

        Re-registers workers and the registry/pool metadata. With
        ``redeploy=True`` it also pushes ``load_model`` to every worker
        again — the recovery path when the fleet restarted empty (loads
        are idempotent on workers that kept their engines). Redeploys are
        BEST-EFFORT per model: a worker that isn't back yet is logged and
        skipped (health probes + later deploys catch it up) rather than
        aborting the whole restore. Returns the number of workers newly
        registered.
        """
        import json

        from ..cluster.registry import ModelRegistry

        # parse EVERYTHING before mutating self: a malformed snapshot must
        # leave the coordinator exactly as it was (the CLI then truly
        # "starts fresh" instead of serving a half-restored registry)
        with open(path) as f:
            state = json.load(f)
        registry = ModelRegistry.from_dict(state["registry"])
        workers = {wid: (w["host"], int(w["port"]),
                         dict(w.get("metadata", {})))
                   for wid, w in state.get("workers", {}).items()}
        model_configs = {
            name: ModelConfig.from_dict(d)
            for name, d in state.get("model_configs", {}).items()
        }
        disagg = {
            m: _DisaggPool(prefill_ids=list(p["prefill"]),
                           decode_ids=list(p["decode"]))
            for m, p in state.get("disaggregated", {}).items()
        }

        self.registry = registry
        self.router.registry = registry
        added = 0
        for wid, (host, port, meta) in workers.items():
            if wid not in self.router.workers:
                self.add_worker(wid, host, port, **meta)
                added += 1
        self._model_configs = model_configs
        self._disagg = disagg

        if redeploy:
            # best-effort per model: application errors (RPCError — e.g. a
            # worker that kept a mismatched engine) AND transport errors
            # are logged, never fatal to the rest of the restore
            recoverable = (*TRANSPORT_ERRORS, WorkerRPCError)
            for name, cfg in self._model_configs.items():
                pool = self._disagg.get(name)
                try:
                    if pool is not None:
                        await self.deploy_model_disaggregated(
                            cfg, pool.prefill_ids, pool.decode_ids,
                            load_timeout_s=load_timeout_s)
                        continue
                    shards = self.registry.all_shards(cfg.name, cfg.version)
                    # push engines back; shards already registered, so only
                    # the load (idempotent on live workers) is repeated
                    targets = ([s.worker_id for s in shards]
                               or list(self.router.workers))
                    for wid in targets:
                        try:
                            await self.router.client_for(wid).load_model(
                                cfg, timeout=load_timeout_s)
                        except recoverable as e:
                            logger.warning(
                                "restore: load of %s on worker %s failed "
                                "(%s) — will catch up via health/deploy",
                                name, wid, e)
                except recoverable as e:
                    logger.warning("restore: redeploy of %s failed (%s) — "
                                   "continuing", name, e)
        return added

    # -- request tracing ----------------------------------------------------

    @staticmethod
    def _trace_mark(inp: Any, phase: str) -> None:
        """Mark a phase on the trace riding a batcher input, if any."""
        if isinstance(inp, dict):
            tr = inp.get("trace")
            if isinstance(tr, RequestTrace):
                tr.mark(phase)

    @staticmethod
    def _merge_worker_trace(inp: Any, out: Any) -> None:
        """Anchor the worker-reported phase offsets (attached by the worker
        as ``metadata['worker_trace']``) onto the request's local trace as
        ``worker.*`` marks, pinned at ``conn_acquired`` where the trace has
        it (a stream: the wait for a pooled connection lies before the
        worker saw anything), else at ``dispatched``."""
        if not isinstance(inp, dict) or not isinstance(out, dict):
            return
        tr = inp.get("trace")
        if not isinstance(tr, RequestTrace):
            return
        wt = out.get("metadata", {}).get("worker_trace")
        if isinstance(wt, dict) and isinstance(wt.get("offsets"), dict):
            tr.add_offsets("worker.", wt["offsets"])

    def _remember_trace(self, trace: RequestTrace) -> None:
        """Retain the trace for the trace-dump endpoint (bounded LRU)."""
        self._recent_traces[trace.request_id] = trace
        self._recent_traces.move_to_end(trace.request_id)
        while len(self._recent_traces) > self._recent_traces_cap:
            rid, _ = self._recent_traces.popitem(last=False)
            self._trace_worker.pop(rid, None)

    def _bind_trace_worker(self, request_id: str, worker_id: str) -> None:
        """Record which worker served a trace (bounded alongside the
        trace LRU — orphans from never-remembered traces age out here)."""
        self._trace_worker[request_id] = worker_id
        while len(self._trace_worker) > 2 * self._recent_traces_cap:
            self._trace_worker.pop(next(iter(self._trace_worker)))

    def get_trace(self, request_id: str) -> Optional[Dict[str, Any]]:
        """The recorded trace of a recent request (coordinator marks plus
        anchored ``worker.*`` spans), or ``None`` if it has aged out."""
        tr = self._recent_traces.get(request_id)
        return tr.to_dict() if tr is not None else None

    # -- flight recorder: event collection, clock sync, fleet trace,
    # post-mortem bundles (ISSUE 19) ---------------------------------------

    def _any_client(self, worker_id: str) -> WorkerClient:
        return (self.router.client_for(worker_id)
                if worker_id in self.router.workers
                else self.lb.client_for(worker_id))

    def _fleet_ids(self) -> List[str]:
        return sorted(set(self.router.workers) | set(self.lb.workers))

    async def collect_events(self,
                             timeout_s: Optional[float] = None,
                             ) -> Dict[str, Dict[str, Any]]:
        """Pull every live worker's event ring (the ``events`` RPC verb)
        into the collection cache. Best-effort per worker: an unreachable
        worker keeps its LAST collected ring — which is exactly what a
        post-mortem needs when that worker is dead."""
        if timeout_s is None:
            timeout_s = self.config.events_timeout_s

        async def fetch(wid: str):
            try:
                return wid, await self._any_client(wid).call(
                    "events", timeout=timeout_s)
            # graftlint: ok[swallowed-transport-error] best-effort collection — a dead worker keeps its cached ring, which IS the post-mortem source
            except Exception:
                return wid, None

        fetched = await asyncio.gather(*(fetch(w) for w in self._fleet_ids()))
        for wid, snap in fetched:
            if isinstance(snap, dict):
                self._worker_rings[wid] = snap
        return dict(self._worker_rings)

    async def estimate_offsets(self, samples: Optional[int] = None,
                               ) -> Dict[str, Dict[str, float]]:
        """Refresh per-worker clock offsets (ping midpoint method,
        ``obs/clocksync.py``). Unreachable workers keep their last
        estimate — good enough to place a dead worker's cached ring on
        the fleet timeline."""
        if samples is None:
            samples = self.config.clocksync_samples
        timeout_s = self.config.events_timeout_s

        async def probe(wid: str):
            try:
                client = self._any_client(wid)
                est = await obs_clocksync.estimate_offset(
                    lambda: client.call("ping", timeout=timeout_s),
                    samples=samples)
                return wid, est
            # graftlint: ok[swallowed-transport-error] best-effort probe — a dead worker keeps its last offset estimate
            except Exception:
                return wid, None

        probed = await asyncio.gather(*(probe(w) for w in self._fleet_ids()))
        for wid, est in probed:
            if isinstance(est, dict) and est.get("samples"):
                self._clock_offsets[wid] = est
        return dict(self._clock_offsets)

    def _coordinator_track(self) -> Dict[str, Any]:
        spans: List[Dict[str, Any]] = []
        for rid, tr in self._recent_traces.items():
            spans.extend(obs_clocksync.spans_from_trace_marks(tr.marks, rid))
        return {"name": "coordinator", "offset_s": 0.0, "steps": [],
                "spans": spans, "events": self.events.events()}

    def _worker_track(self, wid: str, ring: Dict[str, Any]) -> Dict[str, Any]:
        steps: List[Dict[str, Any]] = []
        timelines = ring.get("timelines")
        if isinstance(timelines, dict):
            for model, evs in sorted(timelines.items()):
                for e in evs or ():
                    args = dict(e.get("args") or {})
                    args.setdefault("model", model)
                    steps.append({"name": e["name"], "t": e["t"],
                                  "dur": e.get("dur"), "args": args})
        events = (ring.get("ring") or {}).get("events", [])
        off = self._clock_offsets.get(wid, {}).get("offset_s", 0.0)
        return {"name": wid, "offset_s": off, "steps": steps,
                "spans": [], "events": events}

    async def fleet_trace(self, label: str = "fleet",
                          refresh: bool = True,
                          include_dead: bool = True) -> Dict[str, Any]:
        """ONE Perfetto-loadable trace for the whole fleet: coordinator
        request spans + typed events, and each worker's engine step
        timelines + event ring, clock-corrected onto the coordinator's
        axis — a chaos kill → failover → respawn reads end-to-end on a
        single timeline. ``include_dead`` keeps tracks for workers that
        only exist in the collection cache (their last-known ring)."""
        if refresh:
            await self.estimate_offsets()
            await self.collect_events()
        live = set(self._fleet_ids())
        tracks = [self._coordinator_track()]
        for wid in sorted(self._worker_rings):
            if wid not in live and not include_dead:
                continue
            tracks.append(self._worker_track(wid, self._worker_rings[wid]))
        return obs_clocksync.merge_fleet_trace(tracks, label=label)

    async def write_postmortem(self, reason: str,
                               dead_workers: Sequence[str] = (),
                               dir_path: Optional[str] = None,
                               ) -> Optional[str]:
        """Dump a crash post-mortem bundle (``obs/postmortem.py``) and
        return its directory, or ``None`` when no destination is
        configured. Survivor rings are re-collected first; dead workers'
        rings come from the collection cache — the whole point of
        collecting periodically is that this cache outlives them."""
        if dir_path is None:
            dir_path = self.config.postmortem_dir
        if not dir_path:
            return None
        dead = set(dead_workers)
        await self.estimate_offsets()
        await self.collect_events()
        live = set(self._fleet_ids())
        dead |= set(self._worker_rings) - live
        trace = await self.fleet_trace(label=f"postmortem:{reason}",
                                       refresh=False)
        rings: Dict[str, Dict[str, Any]] = {
            "coordinator": self.events.snapshot()}
        dead_rings: Dict[str, Dict[str, Any]] = {}
        for wid, ring in self._worker_rings.items():
            (dead_rings if wid in dead else rings)[wid] = ring
        ledger = (self.fault_plan.sequence()
                  if self.fault_plan is not None else None)
        bundle = obs_postmortem.write_bundle(
            dir_path, reason,
            trace=trace,
            metrics_text=self.obs_registry.render(),
            event_rings=rings,
            dead_rings=dead_rings,
            fault_ledger=ledger,
            dead_workers=sorted(dead),
        )
        self._postmortems_written += 1
        self.events.emit("postmortem.bundle", reason=reason)
        logger.warning("post-mortem bundle (%s) written to %s", reason,
                       bundle)
        return bundle

    def _fire_postmortem(self, reason: str,
                         dead_workers: Sequence[str] = ()) -> None:
        """Best-effort background dump from supervision paths — a failed
        dump must never take down the control loop."""
        if not self.config.postmortem_dir:
            return

        async def run() -> None:
            try:
                await self.write_postmortem(reason, dead_workers)
            # graftlint: ok[swallowed-transport-error] post-mortem dumping is best-effort evidence capture; supervision must keep running
            except Exception:
                logger.exception("post-mortem dump (%s) failed", reason)

        t = asyncio.create_task(run())
        self._postmortem_tasks.add(t)
        t.add_done_callback(self._postmortem_tasks.discard)

    # -- metrics exposition -------------------------------------------------

    def _obs_collect(self) -> None:
        """Scrape-time collector: rebuild worker-labelled series from the
        last fleet poll, then mirror this process's stats dicts.

        The poll cache is pruned against CURRENT membership first: a
        worker unregistered since the last refresh must drop out of the
        exposition at the next scrape, not linger as ghost series until
        someone happens to scrape with ``refresh_workers=True``."""
        live = set(self.router.workers) | set(self.lb.workers)
        self._worker_metrics = {wid: wm
                                for wid, wm in self._worker_metrics.items()
                                if wid in live}
        obs_collectors.clear_worker_labelled(self.obs_registry)
        obs_collectors.apply_coordinator(self.obs_registry, self.get_stats())
        obs_collectors.apply_event_log(self.obs_registry,
                                       self.events.get_stats(),
                                       proc="coordinator")
        for wid, wm in self._worker_metrics.items():
            obs_collectors.apply_worker(self.obs_registry, wm, worker_id=wid)

    async def metrics_text(self, refresh_workers: bool = True,
                           timeout_s: float = 2.0) -> str:
        """The unified OpenMetrics exposition (``GET /metrics`` body).

        Best-effort polls every registered worker's ``metrics`` RPC first
        (short timeout, failures ignored — a dead worker must not fail the
        scrape; its series simply go stale-then-cleared).

        The scrape observes ITSELF (``obs_scrape_seconds`` /
        ``obs_scrape_ok``): collect+render wall time is recorded AFTER
        rendering, so it surfaces on the NEXT exposition — the guard
        that watches ``scrape_ok`` is thereby itself observable."""
        t_scrape0 = time.perf_counter()
        if refresh_workers:
            wids = list(self.router.workers)

            async def fetch(wid: str):
                try:
                    client = (self.router.client_for(wid)
                              if wid in self.router.workers
                              else self.lb.client_for(wid))
                    return wid, await client.call("metrics",
                                                  timeout=timeout_s)
                # graftlint: ok[swallowed-transport-error] best-effort scrape probe — an unreachable worker shows up as absent families; the health loops own the marking
                except Exception:
                    return wid, None

            fetched = await asyncio.gather(*(fetch(w) for w in wids))
            self._worker_metrics = {wid: wm for wid, wm in fetched
                                    if isinstance(wm, dict)}
        try:
            text = self.obs_registry.render()
        except Exception:
            obs_collectors.record_scrape(
                self.obs_registry, "coordinator",
                time.perf_counter() - t_scrape0, ok=False)
            raise
        obs_collectors.record_scrape(self.obs_registry, "coordinator",
                                     time.perf_counter() - t_scrape0,
                                     ok=True)
        self._last_scrape_t = time.monotonic()
        self._scrape_count += 1
        return text

    # -- introspection ------------------------------------------------------

    def get_stats(self) -> Dict[str, Any]:
        return {
            "submitted": self._submitted,
            "cache_hits": self._cache_hits,
            "overload_rejections": self._overload_rejections,
            "dispatch_retries": self._dispatch_retries,
            "stream_resumes": self._stream_resumes,
            "stream_frames": self._stream_frames,
            "streams_in_flight": self._streams_in_flight,
            **self._pool_gauges(),
            "pool_wait": self.pool_wait_stats.snapshot(),
            "stream_itl": self.stream_itl_stats.snapshot(),
            "stream_emit_lag": dict(self._stream_emit_lag),
            "deadline_expired": self._deadline_expired,
            "drains": self._drains,
            "admission_sheds": self._admission_sheds,
            "admission_shed_active": 1 if self._admission_shed else 0,
            "supervisor_respawns": self._supervisor_respawns,
            "supervisor_crashloop_opens": self._supervisor_crashloop_opens,
            "kv_fabric_prewarm_pushes": self._fabric_prewarm_pushes,
            "kv_fabric_prewarm_failures": self._fabric_prewarm_failures,
            "kv_fabric_failover_imports": self._fabric_failover_imports,
            "kv_fabric_cached_wires": len(self._fabric_cache),
            "supervisor": {
                "armed": self._restart_hook is not None,
                "degraded_workers": sorted(self._degraded),
            },
            # flight recorder (ISSUE 19): ring pressure, collection-cache
            # size, bundle count, and how stale the last /metrics scrape is
            "events": self.events.get_stats(),
            "collected_rings": len(self._worker_rings),
            "postmortems_written": self._postmortems_written,
            "scrapes": self._scrape_count,
            "last_scrape_age_s": (
                round(time.monotonic() - self._last_scrape_t, 3)
                if self._last_scrape_t is not None else -1.0),
            "cache": self.cache.get_stats(),
            "batcher": self.batcher.get_stats(),
            "router": self.router.get_stats(),
            "load_balancer": self.lb.get_all_stats(),
            "registry": self.registry.get_stats(),
            "disaggregated": {
                m: {"prefill": p.prefill_ids, "decode": p.decode_ids}
                for m, p in self._disagg.items()
            },
            "worker_roles": self._worker_roles(),
        }

    def _pool_gauges(self) -> Dict[str, int]:
        """Connection-pool gauges over the router's and the load balancer's
        worker clients: calls holding a connection, calls waiting for one,
        and the connections the pools may hold: the requests kept AT the
        workers (each pool is its worker's slots plus a look-ahead,
        ``utils.rpc.pool_for_slots``)."""
        pools = (self.router.pool_stats(), self.lb.pool_stats())
        # a worker's streams ride ONE of its two clients (the router's
        # where it is registered there): its pool counts once
        sizes = {**pools[1]["size_by_worker"], **pools[0]["size_by_worker"]}
        return {"pool_in_use": sum(p["in_use"] for p in pools),
                "pool_waiting": sum(p["waiting"] for p in pools),
                "pool_size": sum(sizes.values())}

    def _worker_roles(self) -> Dict[str, str]:
        """Fleet role per registered worker for the scrape: pool membership
        wins (a disaggregated deploy is authoritative), then the worker's
        registration metadata, then the plain-replica default."""
        roles: Dict[str, str] = {}
        for pool in self._disagg.values():
            for wid in pool.prefill_ids:
                if wid in self.router.workers:
                    roles[wid] = "prefill"
            for wid in pool.decode_ids:
                if wid in self.router.workers:
                    roles[wid] = "decode"
        for wid, info in self.router.workers.items():
            roles.setdefault(wid, str(info.metadata.get("role", "replica")))
        return roles
