"""Router: key→shard placement routing with health tracking and failover.

Capability heir of the reference's ``src/router.py``: consistent-hash shard
lookup through the registry (``src/router.py:160``), per-worker health state
with an N-consecutive-failures threshold (``:223-245``), a periodic health
loop (``:247-306``), and deterministic failover to an alternate healthy shard
— hash(key) mod healthy-count, so the same key always retries the same backup
(``:186-221``).

Two deliberate upgrades over the reference (SURVEY.md §5):

- Health probes are a real ``ping`` RPC through ``WorkerClient``, not a bare
  TCP connect (``src/router.py:287-292``) — a wedged worker process whose
  socket still accepts would pass the reference's probe forever.
- Workers recover: a successful probe resets the failure count and flips the
  worker back to HEALTHY (re-admission), where the reference only healed on
  request traffic it would no longer send to an unhealthy worker.

TPU reinterpretation: a "shard" here is a mesh-placement record
(``registry.ModelShard.mesh_axes``), so routing a key means choosing which
TPU worker host — and which model partition living on its mesh — serves the
request; prefix-cache affinity falls out of the key hashing.
"""

from __future__ import annotations

import asyncio
import enum
import logging
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from ..config import HealthConfig
from .registry import ModelRegistry, ModelShard, stable_key_hash
from .worker import WorkerClient

logger = logging.getLogger(__name__)


class WorkerHealth(str, enum.Enum):
    """Reference ``src/router.py:27-31``."""

    HEALTHY = "healthy"
    UNHEALTHY = "unhealthy"
    UNKNOWN = "unknown"


@dataclass
class WorkerInfo:
    """Reference ``src/router.py:34-43``."""

    worker_id: str
    host: str
    port: int
    health: WorkerHealth = WorkerHealth.UNKNOWN
    consecutive_failures: int = 0
    last_check: float = 0.0
    last_healthy: float = 0.0
    metadata: Dict[str, Any] = field(default_factory=dict)

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"


@dataclass
class RouteResult:
    """Outcome of ``route_request`` — which shard/worker takes the key."""

    shard: ModelShard
    worker: WorkerInfo
    failover: bool = False            # True when the primary was bypassed


class RoutingError(RuntimeError):
    pass


class Router:
    """Key-affinity placement routing over registry shards
    (reference ``src/router.py:46-358``)."""

    def __init__(
        self,
        registry: ModelRegistry,
        health: Optional[HealthConfig] = None,
    ) -> None:
        self.registry = registry
        self.health_config = health or HealthConfig()
        self.workers: Dict[str, WorkerInfo] = {}
        self._clients: Dict[str, WorkerClient] = {}
        self._health_task: Optional[asyncio.Task] = None
        # asyncio keeps only weak refs to tasks: retain close() tasks here
        # or they can be garbage-collected before the socket is closed
        self._bg_tasks: set = set()
        self._running = False
        self._route_count = 0
        self._failover_count = 0
        self._routing_errors = 0
        self._routes_by_worker: Dict[str, int] = {}

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        """Spawn the health loop (reference ``src/router.py:88-99``)."""
        if self._running:
            return
        self._running = True
        self._health_task = asyncio.create_task(self._health_loop())

    async def stop(self) -> None:
        self._running = False
        if self._health_task is not None:
            self._health_task.cancel()
            try:
                await self._health_task
            except asyncio.CancelledError:
                pass
            self._health_task = None
        for client in self._clients.values():
            await client.close()
        self._clients.clear()

    def pool_stats(self) -> Dict[str, Any]:
        """Connection-pool gauges summed over this router's worker clients:
        calls holding a connection, callers waiting for one, and each
        worker's pool size."""
        pools = {w: c.pool_stats() for w, c in self._clients.items()}
        return {"in_use": sum(p["in_use"] for p in pools.values()),
                "waiting": sum(p["waiting"] for p in pools.values()),
                # each pool's bound (it follows the worker's slots:
                # ``WorkerClient._follow_slots``)
                "size_by_worker": {w: p["size"] for w, p in pools.items()}}

    # -- membership (reference src/router.py:109-138) -----------------------

    def register_worker(self, worker_id: str, host: str, port: int,
                        **metadata: Any) -> WorkerInfo:
        info = WorkerInfo(worker_id=worker_id, host=host, port=port,
                          metadata=metadata)
        self.workers[worker_id] = info
        logger.info("router: registered worker %s at %s", worker_id, info.address)
        return info

    def unregister_worker(self, worker_id: str) -> bool:
        info = self.workers.pop(worker_id, None)
        client = self._clients.pop(worker_id, None)
        if client is not None:
            # tear in-flight calls NOW so they fail fast as transport
            # errors (requeued by the coordinator's retry budget) instead
            # of timing out against a deregistered target
            client.abort_inflight()
            # best-effort close; caller may not be in a loop
            try:
                loop = asyncio.get_running_loop()
                task = loop.create_task(client.close())
                self._bg_tasks.add(task)
                task.add_done_callback(self._bg_tasks.discard)
            except RuntimeError:
                pass
        return info is not None

    def client_for(self, worker_id: str) -> WorkerClient:
        """Pooled persistent client for a registered worker."""
        info = self.workers.get(worker_id)
        if info is None:
            raise RoutingError(f"unknown worker {worker_id!r}")
        client = self._clients.get(worker_id)
        if client is None:
            client = WorkerClient(info.host, info.port,
                                  timeout=self.health_config.check_timeout * 10)
            self._clients[worker_id] = client
        return client

    # -- routing (reference src/router.py:140-221) ---------------------------

    def route_request(self, model: str, version: str, key: str) -> RouteResult:
        """Key → primary shard via registry hashing; failover to the
        deterministic healthy alternate when the primary's worker is down."""
        self._route_count += 1
        shard = self.registry.get_shard_for_key(model, version, key)
        if shard is None:
            self._routing_errors += 1
            raise RoutingError(f"no shards for {model}:{version}")
        worker = self.workers.get(shard.worker_id)
        if worker is not None and worker.health is not WorkerHealth.UNHEALTHY:
            self._routes_by_worker[worker.worker_id] = (
                self._routes_by_worker.get(worker.worker_id, 0) + 1)
            return RouteResult(shard=shard, worker=worker)
        if not self.health_config.enable_failover:
            self._routing_errors += 1
            raise RoutingError(
                f"worker {shard.worker_id!r} unavailable and failover disabled"
            )
        alt = self._find_alternative_shard(model, version, key,
                                           exclude=shard.shard_id)
        if alt is None:
            self._routing_errors += 1
            raise RoutingError(
                f"no healthy shard for {model}:{version} "
                f"(primary worker {shard.worker_id!r} is "
                f"{worker.health.value if worker else 'unregistered'})"
            )
        self._failover_count += 1
        logger.warning("router: failover %s:%s key=%r shard %d→%d",
                       model, version, key, shard.shard_id, alt.shard_id)
        self._routes_by_worker[alt.worker_id] = (
            self._routes_by_worker.get(alt.worker_id, 0) + 1)
        return RouteResult(shard=alt, worker=self.workers[alt.worker_id],
                           failover=True)

    def _find_alternative_shard(
        self, model: str, version: str, key: str, exclude: int,
        exclude_worker=None,
    ) -> Optional[ModelShard]:
        """Deterministic backup: hash(key) mod healthy-shard-count
        (reference ``src/router.py:186-221``) — stable per key GIVEN the
        same healthy set, so failover keeps prefix-cache affinity too.
        ``exclude_worker`` (one id or a collection of ids) drops every
        shard hosted by those workers — a transport-failure retry must not
        land on another shard of the same dead host, and the retry budget
        accumulates already-tried workers here."""
        if exclude_worker is None:
            excluded = ()
        elif isinstance(exclude_worker, str):
            excluded = (exclude_worker,)
        else:
            excluded = tuple(exclude_worker)
        healthy: List[ModelShard] = []
        for shard in self.registry.all_shards(model, version):
            if shard.shard_id == exclude:
                continue
            if shard.worker_id in excluded:
                continue
            w = self.workers.get(shard.worker_id)
            if w is not None and w.health is not WorkerHealth.UNHEALTHY:
                healthy.append(shard)
        if not healthy:
            return None
        healthy.sort(key=lambda s: s.shard_id)
        return healthy[stable_key_hash(key) % len(healthy)]

    # -- health bookkeeping (reference src/router.py:223-245) -----------------

    def mark_worker_success(self, worker_id: str) -> None:
        info = self.workers.get(worker_id)
        if info is None:
            return
        info.consecutive_failures = 0
        info.health = WorkerHealth.HEALTHY
        info.last_healthy = time.monotonic()

    def mark_worker_failure(self, worker_id: str) -> None:
        info = self.workers.get(worker_id)
        if info is None:
            return
        info.consecutive_failures += 1
        if info.consecutive_failures >= self.health_config.max_consecutive_failures:
            if info.health is not WorkerHealth.UNHEALTHY:
                logger.warning("router: worker %s marked UNHEALTHY after %d failures",
                               worker_id, info.consecutive_failures)
            info.health = WorkerHealth.UNHEALTHY

    # -- health loop (reference src/router.py:247-306) ------------------------

    async def _health_loop(self) -> None:
        while self._running:
            try:
                await self.check_all_workers()
            # graftlint: ok[swallowed-transport-error] per-worker failures are marked inside check_worker; this guards the sweep loop itself from dying
            except Exception:
                logger.exception("router: health sweep failed")
            await asyncio.sleep(self.health_config.check_interval)

    async def check_all_workers(self) -> None:
        if self.workers:
            await asyncio.gather(*(self.check_worker(w)
                                   for w in list(self.workers)))

    async def check_worker(self, worker_id: str) -> bool:
        """Ping-RPC probe; marks success/failure like request traffic does."""
        info = self.workers.get(worker_id)
        if info is None:
            return False
        info.last_check = time.monotonic()
        try:
            pong = await self.client_for(worker_id).ping(
                timeout=self.health_config.check_timeout
            )
        except Exception as e:
            logger.debug("router: probe of %s failed: %s", worker_id, e)
            self.mark_worker_failure(worker_id)
            return False
        if isinstance(pong, dict) and pong.get("draining"):
            # alive but refusing admission — keep it out of rotation
            self.mark_worker_failure(worker_id)
            return False
        self.mark_worker_success(worker_id)
        return True

    # -- introspection (reference src/router.py:308-358) ----------------------

    def get_worker(self, worker_id: str) -> Optional[WorkerInfo]:
        return self.workers.get(worker_id)

    def healthy_workers(self) -> List[WorkerInfo]:
        return [w for w in self.workers.values()
                if w.health is WorkerHealth.HEALTHY]

    def get_stats(self) -> Dict[str, Any]:
        by_health: Dict[str, int] = {h.value: 0 for h in WorkerHealth}
        for w in self.workers.values():
            by_health[w.health.value] += 1
        return {
            "workers": len(self.workers),
            "workers_by_health": by_health,
            "route_count": self._route_count,
            "failover_count": self._failover_count,
            "routing_errors": self._routing_errors,
            "worker_detail": {
                w.worker_id: {
                    "address": w.address,
                    "health": w.health.value,
                    "consecutive_failures": w.consecutive_failures,
                    "routes": self._routes_by_worker.get(w.worker_id, 0),
                }
                for w in self.workers.values()
            },
        }
