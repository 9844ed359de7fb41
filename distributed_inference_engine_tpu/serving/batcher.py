"""Request batcher: coalesce per-(model, version), flush at size OR latency.

Capability heir of the reference's ``src/batcher.py:37-269``: requests are
grouped per ``model:version``; a batch flushes when it reaches
``max_batch_size`` (``src/batcher.py:140-147``) or when ``max_latency_ms``
elapses since the batch opened (``src/batcher.py:151-166``); each request gets
an ``asyncio.Future`` resolved from the batch result (``src/batcher.py:202-240``).

Concurrency invariants carried over from the reference (SURVEY.md §3.2):
batch state is mutated only under the lock, the backend callback runs
*outside* the lock, and futures are guarded with ``done()`` checks so a
result and a timeout can't double-resolve.

TPU-first addition: optional bucket padding. XLA compiles one program per
input shape (SURVEY.md §7 hard-part #1), so the batcher can pad every flushed
batch up to the next bucket size — the backend then sees only
``len(bucket_sizes)`` distinct batch shapes instead of an unbounded set.
Fixed reference bugs: no duplicate ``pending_batches`` stats key
(``src/batcher.py:263,268``), and exact result-count mismatches fan an error
to every future rather than hanging some of them.

The continuous-engine path does not coalesce here (``serving.pump``): this
module's size/latency flush knobs only govern the static-``Engine`` backend
path.
"""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass, field
from typing import Any, Awaitable, Callable, Dict, List, Optional, Sequence, Tuple

from ..utils.tracing import LatencyStats, RequestTrace

logger = logging.getLogger(__name__)

# An inference backend: async (model, version, inputs) -> list of outputs,
# one per input (reference ``src/batcher.py:42`` contract).
BatchCallback = Callable[[str, str, List[Any]], Awaitable[List[Any]]]

PAD_INPUT = {"__pad__": True}


@dataclass
class BatchedRequest:
    """Reference ``src/batcher.py:17-24``."""

    request_id: str
    inputs: Any
    future: "asyncio.Future[Any]"
    enqueued_at: float = field(default_factory=time.monotonic)
    trace: Optional[RequestTrace] = None


@dataclass
class Batch:
    """Reference ``src/batcher.py:27-35``."""

    model: str
    version: str
    requests: List[BatchedRequest] = field(default_factory=list)
    created_at: float = field(default_factory=time.monotonic)

    @property
    def key(self) -> Tuple[str, str]:
        return (self.model, self.version)


class Batcher:
    def __init__(
        self,
        batch_callback: BatchCallback,
        max_batch_size: int = 8,
        max_latency_ms: float = 50.0,
        bucket_sizes: Optional[Sequence[int]] = None,
    ) -> None:
        if max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        if max_latency_ms < 0:
            raise ValueError("max_latency_ms must be >= 0")
        self.batch_callback = batch_callback
        self.max_batch_size = max_batch_size
        self.max_latency_ms = max_latency_ms
        if bucket_sizes:
            bucket_sizes = sorted(set(bucket_sizes))
            if bucket_sizes[-1] < max_batch_size:
                raise ValueError("largest bucket must cover max_batch_size")
        self.bucket_sizes = list(bucket_sizes) if bucket_sizes else None

        self._pending: Dict[Tuple[str, str], Batch] = {}
        self._timers: Dict[Tuple[str, str], asyncio.Task] = {}
        self._inflight: set[asyncio.Task] = set()
        self._lock = asyncio.Lock()
        self._running = False
        # stats
        self._total_requests = 0
        self._total_batches = 0
        self._total_batched_requests = 0
        self._total_errors = 0
        self._batch_size_sum = 0
        self._queue_wait = LatencyStats()

    # ------------------------------------------------------------ lifecycle

    async def start(self) -> None:
        self._running = True
        logger.info(
            "batcher started (max_batch=%d, max_latency=%.1fms)",
            self.max_batch_size,
            self.max_latency_ms,
        )

    async def stop(self) -> None:
        """Stop accepting requests and drain: pending batches are flushed and
        in-flight callbacks awaited (reference ``src/batcher.py:70-100``)."""
        self._running = False
        async with self._lock:
            keys = list(self._pending.keys())
        for key in keys:
            await self._flush(key, reason="drain")
        while self._inflight:
            tasks = list(self._inflight)
            await asyncio.gather(*tasks, return_exceptions=True)
            # gather on already-done tasks may not yield to the loop, so the
            # done-callbacks that discard them can starve — drop them here
            self._inflight.difference_update(t for t in tasks if t.done())

    # -------------------------------------------------------------- intake

    async def add_request(
        self,
        model: str,
        version: str,
        inputs: Any,
        request_id: Optional[str] = None,
        trace: Optional[RequestTrace] = None,
    ) -> "asyncio.Future[Any]":
        """Enqueue one request; returns a Future resolved with its output
        (reference ``src/batcher.py:102-149``)."""
        if not self._running:
            raise RuntimeError("batcher is not running")
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        key = (model, version)
        full_batch: Optional[Batch] = None
        timer: Optional[asyncio.Task] = None
        async with self._lock:
            # id minted under the lock so concurrent adds can't collide
            self._total_requests += 1
            req = BatchedRequest(
                request_id=request_id or f"req-{self._total_requests}",
                inputs=inputs,
                future=fut,
                trace=trace,
            )
            if trace is not None:
                trace.mark("queued")
            batch = self._pending.get(key)
            if batch is None:
                batch = Batch(model=model, version=version)
                self._pending[key] = batch
                self._timers[key] = asyncio.ensure_future(self._latency_timer(key))
            batch.requests.append(req)
            if len(batch.requests) >= self.max_batch_size:
                # detach the full batch HERE, not after re-acquiring the lock —
                # a lock-waiting add could otherwise grow it past max_batch_size
                full_batch = self._pending.pop(key)
                timer = self._timers.pop(key, None)
        if full_batch is not None:
            if timer is not None and not timer.done():
                timer.cancel()
            self._dispatch(full_batch, reason="size")
        return fut

    # ------------------------------------------------------------- flushing

    async def _latency_timer(self, key: Tuple[str, str]) -> None:
        """Latency trigger (reference ``src/batcher.py:151-166``)."""
        try:
            await asyncio.sleep(self.max_latency_ms / 1000.0)
            await self._flush(key, reason="latency")
        except asyncio.CancelledError:
            pass

    async def _flush(self, key: Tuple[str, str], reason: str) -> None:
        """Detach the pending batch under the lock, dispatch outside it
        (timer and drain paths; the size path detaches in add_request)."""
        async with self._lock:
            batch = self._pending.pop(key, None)
            timer = self._timers.pop(key, None)
        if timer is not None and not timer.done():
            timer.cancel()
        if batch is None or not batch.requests:
            return
        self._dispatch(batch, reason)

    def _dispatch(self, batch: Batch, reason: str) -> None:
        self._total_batches += 1
        self._total_batched_requests += len(batch.requests)
        self._batch_size_sum += len(batch.requests)
        logger.debug(
            "flush %s:%s n=%d reason=%s", batch.model, batch.version,
            len(batch.requests), reason,
        )
        task = asyncio.ensure_future(self._process(batch))
        self._inflight.add(task)
        task.add_done_callback(self._inflight.discard)

    def _padded_size(self, n: int) -> int:
        if not self.bucket_sizes:
            return n
        for b in self.bucket_sizes:
            if n <= b:
                return b
        return n

    async def _process(self, batch: Batch) -> None:
        """Invoke the backend and fan results out to futures (reference
        ``src/batcher.py:202-240``)."""
        reqs = batch.requests
        inputs = [r.inputs for r in reqs]
        n_real = len(inputs)
        n_padded = self._padded_size(n_real)
        inputs = inputs + [PAD_INPUT] * (n_padded - n_real)
        t_dispatch = time.monotonic()
        for r in reqs:
            self._queue_wait.add(t_dispatch - r.enqueued_at)
            if r.trace is not None:
                r.trace.mark("batched")
        try:
            results = await self.batch_callback(batch.model, batch.version, inputs)
            if results is None or len(results) < n_real:
                raise RuntimeError(
                    f"backend returned {0 if results is None else len(results)} "
                    f"results for {n_real} requests"
                )
            for req, result in zip(reqs, results):
                if req.future.done():
                    continue
                if isinstance(result, BaseException):
                    # backend may fail a subset (e.g. one worker group of a
                    # split batch) without discarding the others' results
                    req.future.set_exception(result)
                else:
                    req.future.set_result(result)
        except Exception as exc:  # fan the error out to every waiter
            self._total_errors += 1
            logger.warning("batch %s:%s failed: %s", batch.model, batch.version, exc)
            for req in reqs:
                if not req.future.done():
                    req.future.set_exception(exc)

    # ---------------------------------------------------------------- stats

    def get_stats(self) -> Dict[str, Any]:
        """Schema-stable stats (the reference's version shipped a duplicate
        key and its demo read a key that didn't exist — SURVEY.md §5)."""
        return {
            "running": self._running,
            "total_requests": self._total_requests,
            "total_batches": self._total_batches,
            "total_batched_requests": self._total_batched_requests,
            "total_errors": self._total_errors,
            "avg_batch_size": (
                self._batch_size_sum / self._total_batches if self._total_batches else 0.0
            ),
            "pending_batches": len(self._pending),
            "pending_requests": sum(len(b.requests) for b in self._pending.values()),
            "inflight_batches": len(self._inflight),
            "max_batch_size": self.max_batch_size,
            "max_latency_ms": self.max_latency_ms,
            "queue_wait": self._queue_wait.snapshot(),
        }
