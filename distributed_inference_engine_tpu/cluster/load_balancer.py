"""Load balancer: strategy-based worker selection over the healthy set.

Capability heir of the reference's ``src/load_balancer.py``: four selection
strategies — round-robin (``:231-244``), least-connections (``:246-261``),
random (``:263-274``), least-latency (``:276-291``) — applied over workers
whose consecutive-failure count is under the threshold (``:150-153``), with
runtime register/unregister (``:97-126``), per-worker request/latency/error
stats (``:166-226``), and a periodic health loop (``:293-348``).

Reference pitfall fixed (SURVEY.md §5 failure-detection row): the reference's
health probes write their own timings into the same ``request_count``/
``total_latency`` fields the LEAST_LATENCY strategy reads
(``src/load_balancer.py:334-339``), so an idle worker's latency profile is
probe noise. Here probe outcomes only touch health fields; request stats come
only from ``update_stats`` calls on real traffic. Probes are also a real
``ping`` RPC rather than a bare TCP connect.

Role split vs the router (reference ``docs/router_vs_load_balancer.md``): the
router answers "which shard *must* serve this key" (placement/affinity); the
LB answers "which of the equivalent replicas *should* take the next request"
(spreading). In TPU terms: the router picks the mesh partition, the LB picks
among data-parallel replicas of it.
"""

from __future__ import annotations

import asyncio
import enum
import itertools
import logging
import random
import time
from collections import Counter, OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Hashable, List, Optional, Tuple

from ..config import HealthConfig
from .worker import WorkerClient

logger = logging.getLogger(__name__)


class LoadBalancerStrategy(str, enum.Enum):
    """Reference ``src/load_balancer.py:18-23``."""

    ROUND_ROBIN = "round_robin"
    LEAST_CONNECTIONS = "least_connections"
    RANDOM = "random"
    LEAST_LATENCY = "least_latency"
    # KV-locality-aware spreading (PRESERVE-style): requests carrying the
    # same prefix-chain hash stick to the worker whose prefix cache is warm;
    # cold prefixes fall back to least-connections
    PREFIX_AFFINITY = "prefix_affinity"


# per-worker circuit breaker states (docs/design.md "Failure model"):
# CLOSED = normal traffic; OPEN = excluded from selection, cooling down;
# HALF_OPEN = cooldown over, exactly one trial probe outstanding.
BREAKER_CLOSED = "closed"
BREAKER_HALF_OPEN = "half_open"
BREAKER_OPEN = "open"
_BREAKER_CODE = {BREAKER_CLOSED: 0, BREAKER_HALF_OPEN: 1, BREAKER_OPEN: 2}


@dataclass
class WorkerStats:
    """Reference ``src/load_balancer.py:25-37`` — with probe stats separated."""

    worker_id: str
    host: str
    port: int
    active_connections: int = 0
    request_count: int = 0
    error_count: int = 0
    total_latency_s: float = 0.0
    consecutive_failures: int = 0
    last_probe: float = 0.0
    probe_count: int = 0
    probe_failures: int = 0
    breaker_state: str = BREAKER_CLOSED
    breaker_opened_at: float = 0.0
    breaker_opens: int = 0
    metadata: Dict[str, Any] = field(default_factory=dict)

    @property
    def avg_latency_s(self) -> float:
        """Reference ``src/load_balancer.py:34-37`` — real traffic only."""
        return self.total_latency_s / self.request_count if self.request_count else 0.0

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"


class NoHealthyWorkerError(RuntimeError):
    pass


class LoadBalancer:
    """Reference ``src/load_balancer.py:39-348``."""

    def __init__(
        self,
        strategy: LoadBalancerStrategy = LoadBalancerStrategy.ROUND_ROBIN,
        health: Optional[HealthConfig] = None,
        seed: Optional[int] = None,
        affinity_capacity: int = 4096,
    ) -> None:
        self.strategy = LoadBalancerStrategy(strategy)
        self.health_config = health or HealthConfig()
        self.workers: Dict[str, WorkerStats] = {}
        self._rr = itertools.count()
        self._rand = random.Random(seed)
        self._clients: Dict[str, WorkerClient] = {}
        self._health_task: Optional[asyncio.Task] = None
        # asyncio keeps only weak refs to tasks: retain close() tasks here
        # or they can be garbage-collected before the socket is closed
        self._bg_tasks: set = set()
        self._running = False
        self._pick_count = 0
        # prefix-affinity binding table: prefix key -> worker_id, LRU-bounded
        # so a long-tail of one-shot prefixes can't grow it without bound
        self._affinity: "OrderedDict[Hashable, str]" = OrderedDict()
        self._affinity_capacity = affinity_capacity
        self._affinity_hits = 0
        self._affinity_misses = 0
        self._affinity_rebinds = 0
        self._affinity_handoffs = 0   # bindings MOVED (KV fabric), not dropped
        # model+prefix placement (multi-model fleets): composite keys are
        # "<model>:<prefix-hash>", so hits/misses split per model, and the
        # cold-prefix placement prefers workers that already hold (or are
        # staging) the key's model — learned from ping payloads and
        # coordinator deploy/stage notifications
        self._model_affinity: Dict[str, Dict[str, int]] = {}
        self._resident_models: Dict[str, set] = {}   # worker -> resident
        self._staged_models: Dict[str, set] = {}     # worker -> staging
        # breaker-transition observer (flight recorder): called as
        # on_transition(worker_id, new_state) for every CLOSED/HALF_OPEN/
        # OPEN flip; must be cheap and must not raise (guarded anyway)
        self.on_transition: Optional[Callable[[str, str], None]] = None
        self._strategies = {
            LoadBalancerStrategy.ROUND_ROBIN: self._round_robin,
            LoadBalancerStrategy.LEAST_CONNECTIONS: self._least_connections,
            LoadBalancerStrategy.RANDOM: self._random,
            LoadBalancerStrategy.LEAST_LATENCY: self._least_latency,
            # keyless requests under prefix_affinity spread like
            # least-connections; keyed picks short-circuit in get_worker
            LoadBalancerStrategy.PREFIX_AFFINITY: self._least_connections,
        }

    # -- lifecycle ----------------------------------------------------------

    async def start(self) -> None:
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
        """Connection-pool gauges summed over this load balancer's worker clients:
        calls holding a connection, callers waiting for one, and each
        worker's pool size."""
        pools = {w: c.pool_stats() for w, c in self._clients.items()}
        return {"in_use": sum(p["in_use"] for p in pools.values()),
                "waiting": sum(p["waiting"] for p in pools.values()),
                # each pool's bound (it follows the worker's slots:
                # ``WorkerClient._follow_slots``)
                "size_by_worker": {w: p["size"] for w, p in pools.items()}}

    # -- membership (reference src/load_balancer.py:97-126) -------------------

    def register_worker(self, worker_id: str, host: str, port: int,
                        **metadata: Any) -> WorkerStats:
        stats = WorkerStats(worker_id=worker_id, host=host, port=port,
                            metadata=metadata)
        self.workers[worker_id] = stats
        logger.info("lb: registered worker %s at %s", worker_id, stats.address)
        return stats

    def unregister_worker(self, worker_id: str) -> bool:
        stats = self.workers.pop(worker_id, None)
        self._resident_models.pop(worker_id, None)
        self._staged_models.pop(worker_id, None)
        if stats is not None:
            self.invalidate_affinity(worker_id)
        client = self._clients.pop(worker_id, None)
        if client is not None:
            # tear in-flight calls NOW: their pending reads fail fast as
            # transport errors and the coordinator's retry budget requeues
            # the work, instead of queued dispatches timing out against a
            # deregistered target
            client.abort_inflight()
            try:
                task = asyncio.get_running_loop().create_task(client.close())
                self._bg_tasks.add(task)
                task.add_done_callback(self._bg_tasks.discard)
            except RuntimeError:
                pass
        return stats is not None

    def client_for(self, worker_id: str) -> WorkerClient:
        stats = self.workers.get(worker_id)
        if stats is None:
            raise NoHealthyWorkerError(f"unknown worker {worker_id!r}")
        client = self._clients.get(worker_id)
        if client is None:
            client = WorkerClient(stats.host, stats.port)
            self._clients[worker_id] = client
        return client

    # -- selection (reference src/load_balancer.py:128-164) -------------------

    def _is_healthy(self, s: WorkerStats) -> bool:
        return (s.breaker_state == BREAKER_CLOSED
                and s.consecutive_failures
                < self.health_config.max_consecutive_failures)

    # -- circuit breaker ------------------------------------------------------

    def _record_failure(self, s: WorkerStats) -> None:
        s.consecutive_failures += 1
        if s.breaker_state == BREAKER_HALF_OPEN:
            # the one trial probe failed: re-open and restart the cooldown
            self._open_breaker(s)
        elif (s.breaker_state == BREAKER_CLOSED
              and s.consecutive_failures
              >= self.health_config.max_consecutive_failures):
            self._open_breaker(s)

    def _notify_transition(self, worker_id: str, state: str) -> None:
        cb = self.on_transition
        if cb is None:
            return
        try:
            cb(worker_id, state)
        # graftlint: ok[swallowed-transport-error] observer hook — telemetry must never break breaker bookkeeping
        except Exception:
            logger.exception("lb: on_transition observer failed")

    def _record_success(self, s: WorkerStats) -> None:
        s.consecutive_failures = 0
        if s.breaker_state != BREAKER_CLOSED:
            logger.info("lb: circuit for %s closed", s.worker_id)
            s.breaker_state = BREAKER_CLOSED
            self._notify_transition(s.worker_id, BREAKER_CLOSED)

    def _open_breaker(self, s: WorkerStats) -> None:
        was = s.breaker_state
        s.breaker_state = BREAKER_OPEN
        s.breaker_opened_at = time.monotonic()
        s.breaker_opens += 1
        logger.info("lb: circuit for %s opened (%d consecutive failures)",
                    s.worker_id, s.consecutive_failures)
        if was != BREAKER_OPEN:
            self._notify_transition(s.worker_id, BREAKER_OPEN)

    def quarantine(self, worker_id: str) -> bool:
        """Administratively open a worker's circuit (the drain/remove path):
        it drops out of selection immediately; a successful half-open probe
        or real-traffic success re-admits it."""
        s = self.workers.get(worker_id)
        if s is None:
            return False
        self._open_breaker(s)
        self.invalidate_affinity(worker_id)
        return True

    def enter_half_open(self, worker_id: str) -> bool:
        """Put a worker straight into HALF_OPEN (the supervisor's rejoin
        path after a respawn): the next selection or health probe is its
        one trial — success closes the circuit, failure re-opens it. Skips
        the usual OPEN→cooldown wait because the respawn itself is the
        evidence the process is fresh."""
        s = self.workers.get(worker_id)
        if s is None:
            return False
        s.consecutive_failures = 0
        if s.breaker_state != BREAKER_HALF_OPEN:
            self._notify_transition(worker_id, BREAKER_HALF_OPEN)
        s.breaker_state = BREAKER_HALF_OPEN
        s.breaker_opened_at = time.monotonic()
        return True

    def healthy_workers(self) -> List[WorkerStats]:
        return [s for s in self.workers.values() if self._is_healthy(s)]

    def get_worker(self, pinned: Optional[str] = None,
                   affinity: Optional[Hashable] = None) -> WorkerStats:
        """Pick a worker; ``pinned`` forces a specific healthy worker
        (reference pinned-worker path, ``src/load_balancer.py:144-147``).

        Under ``PREFIX_AFFINITY``, ``affinity`` is the request's prefix-chain
        hash: a live binding to a healthy worker is a *hit* (same-prefix
        traffic lands on the warm cache), a cold key is a *miss* (bound to
        the least-loaded worker), and a binding whose worker has died,
        drained, or tripped its breaker is *rebound* to a healthy one —
        requests are never dropped for affinity's sake."""
        self._pick_count += 1
        if pinned is not None:
            s = self.workers.get(pinned)
            if s is None or not self._is_healthy(s):
                raise NoHealthyWorkerError(f"pinned worker {pinned!r} unavailable")
            return s
        healthy = self.healthy_workers()
        if not healthy:
            raise NoHealthyWorkerError("no healthy workers registered")
        healthy.sort(key=lambda s: s.worker_id)   # deterministic strategy input
        if (self.strategy == LoadBalancerStrategy.PREFIX_AFFINITY
                and affinity is not None):
            return self._affine_pick(affinity, healthy)
        return self._strategies[self.strategy](healthy)

    # -- model residency (multi-model fleets) --------------------------------

    @staticmethod
    def model_of_key(key: Hashable) -> Optional[str]:
        """The model id a composite ``"<model>:<prefix-hash>"`` affinity
        key names; None for legacy bare-hash keys."""
        if isinstance(key, str) and ":" in key:
            return key.split(":", 1)[0]
        return None

    def note_models(self, worker_id: str, resident=None, staged=None) -> None:
        """Record which models a worker holds (and is staging) — fed by the
        health loop's ping payloads and by the coordinator after deploys/
        stage requests, and read by the cold-key placement preference."""
        if worker_id not in self.workers:
            return
        if resident is not None:
            self._resident_models[worker_id] = set(resident)
        if staged is not None:
            self._staged_models[worker_id] = set(staged)

    def add_resident_model(self, worker_id: str, model: str) -> None:
        """Merge one model into a worker's known-resident set (deploy-time
        hint; the health loop's ping payloads overwrite with ground truth).
        A model that just became resident is no longer merely staged."""
        if worker_id not in self.workers:
            return
        self._resident_models.setdefault(worker_id, set()).add(model)
        self._staged_models.get(worker_id, set()).discard(model)

    def add_staged_model(self, worker_id: str, model: str) -> None:
        """Merge one model into a worker's staging set — cold keys for that
        model prefer a worker already staging it over a fully cold one."""
        if worker_id not in self.workers:
            return
        self._staged_models.setdefault(worker_id, set()).add(model)

    def workers_with_model(self, model: str) -> set:
        return {wid for wid, models in self._resident_models.items()
                if model in models}

    def _model_count(self, model: Optional[str], field: str) -> None:
        if model is None:
            return
        rec = self._model_affinity.setdefault(
            model, {"hits": 0, "misses": 0, "rebinds": 0})
        rec[field] += 1

    def _affine_pick(self, key: Hashable,
                     healthy: List[WorkerStats]) -> WorkerStats:
        model = self.model_of_key(key)
        bound = self._affinity.get(key)
        if bound is not None:
            s = self.workers.get(bound)
            if s is not None and self._is_healthy(s):
                self._affinity_hits += 1
                self._model_count(model, "hits")
                self._affinity.move_to_end(key)
                return s
            # bound worker is gone/unhealthy: rebind, don't drop the request
            self._affinity_rebinds += 1
            self._model_count(model, "rebinds")
        else:
            self._affinity_misses += 1
            self._model_count(model, "misses")
        # cold-key placement: prefer workers where the key's MODEL is
        # already resident (swap is free) over ones merely staging it
        # (swap is cheap and imminent) over the rest (placement triggers a
        # cold load) — a cold-model request should not displace a resident
        # model elsewhere when a warm replica has capacity. Within a tier:
        # least-connections, tie-broken by how many bindings each worker
        # already holds — bare active_connections ties to the first worker
        # on an idle fleet, piling every cold prefix onto one replica
        candidates = healthy
        if model is not None:
            resident = [w for w in healthy
                        if model in self._resident_models.get(w.worker_id, ())]
            staging = [w for w in healthy
                       if model in self._staged_models.get(w.worker_id, ())]
            candidates = resident or staging or healthy
        held = Counter(self._affinity.values())
        s = min(candidates, key=lambda w: (w.active_connections,
                                           held.get(w.worker_id, 0),
                                           w.request_count))
        self._bind_affinity(key, s.worker_id)
        return s

    def _bind_affinity(self, key: Hashable, worker_id: str) -> None:
        self._affinity[key] = worker_id
        self._affinity.move_to_end(key)
        while len(self._affinity) > self._affinity_capacity:
            self._affinity.popitem(last=False)

    def invalidate_affinity(self, worker_id: Optional[str] = None) -> int:
        """Drop bindings to ``worker_id`` (or all when None); subsequent
        same-prefix picks rebind fresh. Called automatically on unregister/
        quarantine, and explicitly by the coordinator when a streaming
        failover replays a prefix onto an alternate (the old binding is
        known-stale even though the breaker may not have tripped yet).
        Each dropped binding counts as a rebind."""
        stale = [k for k, w in self._affinity.items()
                 if worker_id is None or w == worker_id]
        for k in stale:
            del self._affinity[k]
        self._affinity_rebinds += len(stale)
        return len(stale)

    def bindings_for(self, worker_id: str) -> List[Hashable]:
        """One worker's bound prefix keys, most-recently-used first — the
        drain handoff's export list."""
        return [k for k in reversed(self._affinity)
                if self._affinity[k] == worker_id]

    def top_bindings(self, k: int = 0) -> List[Tuple[Hashable, str]]:
        """The hottest (MRU-first) affinity bindings fleet-wide as
        ``(key, worker_id)`` pairs; all of them when ``k <= 0``. The
        coordinator's pre-warm source set."""
        out = [(key, self._affinity[key]) for key in reversed(self._affinity)]
        return out[:k] if k > 0 else out

    def bind_affinity(self, key: Hashable, worker_id: str) -> bool:
        """Explicitly (re)bind one key — the stream-failover handoff after
        the alternate imported the prefix KV. False when the worker is not
        registered. Counts as a handoff, not a rebind: the KV moved with
        the binding."""
        if worker_id not in self.workers:
            return False
        self._bind_affinity(key, worker_id)
        self._affinity_handoffs += 1
        return True

    def rebind_affinity(self, from_worker: str, to_worker: str) -> int:
        """HAND OFF every binding from one worker to another (the drain
        path, after the target imported the prefixes' KV) instead of
        dropping them cold. Recency is preserved — the moved bindings keep
        their LRU positions. No-op when the target is unregistered."""
        if to_worker not in self.workers:
            return 0
        moved = 0
        for key, bound in self._affinity.items():
            if bound == from_worker:
                self._affinity[key] = to_worker
                moved += 1
        self._affinity_handoffs += moved
        return moved

    def _round_robin(self, healthy: List[WorkerStats]) -> WorkerStats:
        return healthy[next(self._rr) % len(healthy)]

    def _least_connections(self, healthy: List[WorkerStats]) -> WorkerStats:
        return min(healthy, key=lambda s: s.active_connections)

    def _random(self, healthy: List[WorkerStats]) -> WorkerStats:
        return self._rand.choice(healthy)

    def _least_latency(self, healthy: List[WorkerStats]) -> WorkerStats:
        # cold workers (no real traffic yet) sort first so they get sampled
        return min(healthy, key=lambda s: s.avg_latency_s)

    # -- traffic accounting (reference src/load_balancer.py:166-191) ----------

    def acquire(self, worker_id: str) -> None:
        s = self.workers.get(worker_id)
        if s is not None:
            s.active_connections += 1

    def release(self, worker_id: str) -> None:
        s = self.workers.get(worker_id)
        if s is not None and s.active_connections > 0:
            s.active_connections -= 1

    def update_stats(self, worker_id: str, success: bool,
                     latency_s: float) -> None:
        s = self.workers.get(worker_id)
        if s is None:
            return
        s.request_count += 1
        s.total_latency_s += latency_s
        if success:
            self._record_success(s)        # reference :187-191
        else:
            s.error_count += 1
            self._record_failure(s)

    # -- health loop (reference src/load_balancer.py:293-348) -----------------

    async def _health_loop(self) -> None:
        while self._running:
            try:
                await self.check_all_workers()
            # graftlint: ok[swallowed-transport-error] per-worker failures are marked inside check_worker; this guards the sweep loop itself from dying
            except Exception:
                logger.exception("lb: health sweep failed")
            await asyncio.sleep(self.health_config.check_interval)

    async def check_all_workers(self) -> None:
        if self.workers:
            await asyncio.gather(*(self.check_worker(w)
                                   for w in list(self.workers)))

    async def check_worker(self, worker_id: str) -> bool:
        """Ping probe. Touches only health/probe fields — never the request
        stats the LEAST_LATENCY strategy reads (fixed reference pitfall).

        Breaker-aware: an OPEN circuit is probed only after its cooldown
        (half-open, one trial) — no hammering a host that just failed N
        times in a row. A ping that reports ``draining: true`` counts as a
        failed probe: the worker is alive but refusing admission, so it
        must stay out of rotation until the drain finishes."""
        s = self.workers.get(worker_id)
        if s is None:
            return False
        s.last_probe = time.monotonic()
        if s.breaker_state == BREAKER_OPEN:
            cooled = (time.monotonic() - s.breaker_opened_at
                      >= self.health_config.breaker_cooldown_s)
            if not cooled:
                return False
            s.breaker_state = BREAKER_HALF_OPEN
            self._notify_transition(worker_id, BREAKER_HALF_OPEN)
        s.probe_count += 1
        try:
            pong = await self.client_for(worker_id).ping(
                timeout=self.health_config.check_timeout
            )
        except Exception as e:
            logger.debug("lb: probe of %s failed: %s", worker_id, e)
            s.probe_failures += 1
            self._record_failure(s)
            return False
        if isinstance(pong, dict):
            # pings advertise the worker's resident + staging model sets —
            # the model-aware cold-key placement's knowledge source
            self.note_models(worker_id, resident=pong.get("models"),
                             staged=pong.get("staged"))
        if isinstance(pong, dict) and pong.get("draining"):
            logger.debug("lb: %s is draining — held out of rotation",
                         worker_id)
            s.probe_failures += 1
            self._record_failure(s)
            return False
        self._record_success(s)
        return True

    # -- introspection (reference src/load_balancer.py:193-226) ---------------

    def get_worker_stats(self, worker_id: str) -> Optional[Dict[str, Any]]:
        s = self.workers.get(worker_id)
        if s is None:
            return None
        return {
            "worker_id": s.worker_id,
            "address": s.address,
            "healthy": self._is_healthy(s),
            "active_connections": s.active_connections,
            "request_count": s.request_count,
            "error_count": s.error_count,
            "avg_latency_s": s.avg_latency_s,
            "consecutive_failures": s.consecutive_failures,
            "probe_count": s.probe_count,
            "probe_failures": s.probe_failures,
            "breaker_state": s.breaker_state,
            "breaker_state_code": _BREAKER_CODE[s.breaker_state],
            "breaker_opens": s.breaker_opens,
        }

    def get_all_stats(self) -> Dict[str, Any]:
        return {
            "strategy": self.strategy.value,
            "pick_count": self._pick_count,
            "workers": {wid: self.get_worker_stats(wid) for wid in self.workers},
            "healthy_count": len(self.healthy_workers()),
            "affinity_hits": self._affinity_hits,
            "affinity_misses": self._affinity_misses,
            "affinity_rebinds": self._affinity_rebinds,
            "affinity_handoffs": self._affinity_handoffs,
            "affinity_bindings": len(self._affinity),
            # per-model split of the composite-key hits/misses/rebinds
            # (multi-model fleets; legacy bare-hash keys are unlabelled)
            "affinity_models": {m: dict(rec) for m, rec
                                in self._model_affinity.items()},
        }
