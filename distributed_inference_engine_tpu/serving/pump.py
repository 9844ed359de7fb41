"""EnginePump: async facade over the continuous engine's synchronous pump.

The missing piece between the asyncio serving plane and the slot-based
engine: ``ContinuousEngine`` is single-threaded synchronous (XLA dispatch),
while the worker serves many concurrent RPC connections. The pump owns a
dedicated engine thread; RPC handlers ``await generate(...)`` and their
requests are admitted into the SAME rolling decode batch — concurrent
connections share chunks instead of serializing whole generations behind the
executor (which is what the static ``Engine`` path does).

This is continuous batching made visible at the serving layer: the
reference's batcher coalesced requests *before* dispatch
(``src/batcher.py:140-166``); here coalescing happens *inside* the engine
continuously, so a request arriving mid-flight starts its prefill at the
next chunk boundary instead of waiting for the previous batch to finish.

Thread discipline: every engine method runs on the pump thread only. The
asyncio side talks through a thread-safe inbox + ``call_soon_threadsafe``
future resolution — the same single-writer rule the reference kept with its
one-loop asyncio design (SURVEY.md §5 race-detection row).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..engine.types import (
    DeadlineExceededError,
    EngineOverloadedError,
    GenerationRequest,
    GenerationResult,
)
from ..obs.timeline import HostSpan, host_span
from ..utils.tracing import LatencyStats

logger = logging.getLogger(__name__)


class EnginePump:
    """Drives a ``ContinuousEngine`` on a dedicated thread; asyncio-facing
    ``generate`` joins requests into the rolling batch."""

    def __init__(self, engine: Any, idle_wait_s: float = 0.25,
                 error_backoff_s: float = 0.05,
                 overlap_forms: bool = True,
                 event_log: Any = None, model: str = "") -> None:
        self.engine = engine
        # flight recorder (obs/events.py): admission accept/reject land in
        # the owning worker's event ring. EventLog is lock-guarded, so
        # emitting from the pump thread is safe.
        self._events = event_log
        self._model = model
        self.idle_wait_s = idle_wait_s          # safety-net poll when idle
        self.error_backoff_s = error_backoff_s  # pause after a failed step
        self._overlap_admitted = 0
        if overlap_forms and hasattr(engine, "overlap_hook"):
            # batch-formation overlap (ISSUE 5c): the engine calls this
            # right after dispatching a decode chunk, while the
            # device is busy — the inbox drain (request validation,
            # submit, prefetch probes) runs in the step's shadow instead
            # of the host gap between steps. Thread-safe by construction:
            # the hook fires inside engine.step(), which only ever runs
            # on the pump thread, and _drain_inbox only touches the
            # engine via submit()/submit_prefilled() (enqueue-only).
            def _overlap() -> None:
                self._overlap_admitted += self._drain_inbox()

            engine.overlap_hook = _overlap
        # (request, optional handoff, optional stream cb, future, loop,
        #  perf_counter stamp of the enqueue)
        self._inbox: List[Tuple[GenerationRequest, Any, Any, asyncio.Future,
                                asyncio.AbstractEventLoop, float]] = []
        self._inbox_lock = threading.Lock()
        # engine-side id -> (future, loop, caller's original request id)
        self._futures: Dict[str, Tuple[asyncio.Future,
                                       asyncio.AbstractEventLoop, str]] = {}
        self._renamed = 0               # ids the pump had to make unique
        # enqueue (an RPC handler, any loop) -> engine.submit() on the pump
        # thread: the wait for the engine thread to come back to the inbox
        self.inbox_wait = LatencyStats()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._step_errors = 0
        self._steps = 0
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------ asyncio

    async def generate(self, requests: List[GenerationRequest]
                       ) -> List[GenerationResult]:
        """Submit into the rolling batch; resolves when all finish.

        Overload is a PER-REQUEST outcome: a shed request comes back as a
        result with ``finish_reason="overloaded"`` (zero tokens) while its
        batch siblings complete normally — an exception here would discard
        siblings' generations and push callers into whole-batch retries
        that duplicate work during the very overload being shed (r3 review
        finding). Single-request surfaces (``generate_streaming``, the
        coordinator's ``submit``) convert the outcome to the typed
        ``EngineOverloadedError``."""
        return await self._submit_all([(r, None) for r in requests])

    async def generate_prefilled(
        self, pairs: List[Tuple[GenerationRequest, Any]]
    ) -> List[GenerationResult]:
        """Disaggregated admission: (request, PrefillHandoff) pairs join the
        rolling batch via ``engine.submit_prefilled`` — no local prefill."""
        return await self._submit_all(pairs)

    async def generate_streaming(
        self, request: GenerationRequest, on_tokens,
    ) -> GenerationResult:
        """Like ``generate`` for one request, but ``on_tokens(tokens)`` is
        invoked on THIS loop with each batch of fresh tokens as the engine
        produces them (trimmed like the final result). A shed request
        raises the typed ``EngineOverloadedError`` (single-request surface
        — there are no siblings to protect)."""
        results = await self._submit_all([(request, None)],
                                         on_tokens=on_tokens)
        res = results[0]
        if res.finish_reason == "overloaded":
            reason = res.metadata.get("overload_reason", "queue_full")
            raise EngineOverloadedError(
                f"request {res.request_id} shed ({reason}); retry on "
                "another replica or later", reason=reason)
        if res.finish_reason == "deadline":
            raise DeadlineExceededError(
                f"request {res.request_id} deadline expired while queued",
                request_id=res.request_id)
        return res

    async def _submit_all(
        self, pairs: List[Tuple[GenerationRequest, Any]], on_tokens=None,
    ) -> List[GenerationResult]:
        self._ensure_thread()
        loop = asyncio.get_running_loop()
        cb = None
        if on_tokens is not None:
            # engine thread -> caller's loop
            def cb(tokens, _loop=loop, _cb=on_tokens):
                _loop.call_soon_threadsafe(_cb, tokens)
        futs: List[asyncio.Future] = []
        t_in = time.perf_counter()
        with self._inbox_lock:
            for r, handoff in pairs:
                fut: asyncio.Future = loop.create_future()
                self._inbox.append((r, handoff, cb, fut, loop, t_in))
                futs.append(fut)
        self._wake.set()
        results = await asyncio.gather(*futs)
        return list(results)

    async def drain(self, timeout_s: float = 30.0) -> bool:
        """Wait until nothing is queued or in flight (the caller must have
        stopped admission first — the worker's drain verb does). Returns
        True if fully drained within the budget, False on timeout with
        work still pending."""
        deadline = time.monotonic() + max(0.0, timeout_s)
        while True:
            with self._inbox_lock:
                busy = bool(self._inbox)
            busy = busy or bool(self._futures)
            if not busy:
                return True
            if time.monotonic() >= deadline:
                return False
            await asyncio.sleep(0.02)

    async def stop(self) -> None:
        self.shutdown_nowait()
        t = self._thread
        if t is not None:
            await asyncio.get_running_loop().run_in_executor(None, t.join, 5.0)

    def shutdown_nowait(self) -> None:
        """Synchronous shutdown signal (usable from non-async callers, e.g.
        ``WorkerServer.stop``): stops the thread and fails every in-flight
        and queued future so no RPC client awaits forever."""
        self._stop.set()
        self._wake.set()
        exc = RuntimeError("engine pump shut down")
        with self._inbox_lock:
            pending, self._inbox = self._inbox, []
        for _req, _handoff, _cb, fut, loop, _t in pending:
            loop.call_soon_threadsafe(self._set_exc, fut, exc)
        self._fail_all(exc)

    # ------------------------------------------------------------- thread

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name="engine-pump", daemon=True
            )
            self._thread.start()

    def _run(self) -> None:
        logger.info("engine pump started")
        while not self._stop.is_set():
            admitted = self._drain_inbox()
            live = 0
            try:
                if admitted or self.engine.n_live or self.engine.n_waiting:
                    self._steps += 1
                    live = self.engine.step()
                    finished = self.engine.drain_finished()
                    if finished:
                        with self._span("pump.resolve", results=len(finished)):
                            for res in finished:
                                self._resolve(res)
            except Exception as e:  # engine failure fans to all in-flight
                self._step_errors += 1
                logger.exception("engine pump step failed")
                self._fail_all(e)
                # drop the broken batch so n_live can't spin the loop hot,
                # then back off before serving fresh submissions
                try:
                    self.engine.abort_all()
                # graftlint: ok[swallowed-transport-error] engine-local best-effort abort during error recovery; no peer involved and the step error was already counted
                except Exception:
                    logger.exception("engine abort_all failed")
                # graftlint: ok[async-blocking-call] _run executes only on the dedicated pump thread (started in start()), never on an event loop
                time.sleep(self.error_backoff_s)
                continue
            if not live and not self.engine.n_waiting:
                # idle: block until new work arrives
                with self._span("pump.idle_wait"):
                    self._wake.wait(timeout=self.idle_wait_s)
                self._wake.clear()
        # tokens the device has produced and the engine has not read (the
        # chunk in flight) reach their streams, and what finished in them
        # its future, before the rest fail: no frame is lost
        flush = getattr(self.engine, "flush_stream", None)
        if flush is not None:
            try:
                flush()         # a failing callback is the engine's to log
                for res in self.engine.drain_finished():
                    self._resolve(res)
            # graftlint: ok[swallowed-transport-error] engine-local read of the chunk in flight at shutdown; no peer involved, and every future left is failed right below
            except Exception:
                logger.exception("engine pump: final flush failed")
        # fail anything still in flight so no caller hangs on shutdown
        self._fail_all(RuntimeError("engine pump shut down"))
        logger.info("engine pump stopped")

    def _span(self, name: str, **args: Any) -> HostSpan:
        """A host span of the engine thread, in the engine's own ring
        (``obs.timeline.host_span``)."""
        return host_span(getattr(self.engine, "timeline", None), name,
                         **args)

    def _drain_inbox(self) -> int:
        with self._inbox_lock:
            batch, self._inbox = self._inbox, []
        if not batch:
            return 0
        with self._span("pump.drain_inbox", requests=len(batch)):
            self._submit_batch(batch)
        return len(batch)

    def _submit_batch(self, batch) -> None:
        for req, handoff, cb, fut, loop, t_in in batch:
            # the engine keys its results by request id, so two in flight
            # may not share one; the caller's id is kept wherever it is
            # unique, so that spans and marks below the pump carry it
            original_id = req.request_id
            pump_id = original_id
            if not pump_id or pump_id in self._futures:
                self._renamed += 1
                pump_id = f"{original_id or 'anon'}~{self._renamed}"
            req.request_id = pump_id
            self._futures[pump_id] = (fut, loop, original_id)
            try:
                if handoff is not None:
                    self.engine.submit_prefilled(req, handoff, on_tokens=cb)
                else:
                    self.engine.submit(req, on_tokens=cb)
                    # host-tier prefetch (kv_offload): start host→device
                    # uploads for cached prefix pages NOW, so the PCIe
                    # copy overlaps queue wait + batch formation instead
                    # of the admission critical path
                    prefetch = getattr(self.engine, "prefetch_probe", None)
                    if prefetch is not None:
                        prefetch(req)
                self.inbox_wait.add(time.perf_counter() - t_in)
                if self._events is not None:
                    self._events.emit("admission.accept", model=self._model,
                                      request_id=original_id or pump_id)
            except EngineOverloadedError as e:
                # per-request outcome, not an exception: batch siblings
                # already submitted must keep their futures resolvable
                # with real results (see generate())
                del self._futures[pump_id]
                shed = GenerationResult(
                    request_id=original_id or pump_id, tokens=[],
                    finish_reason="overloaded",
                    prompt_tokens=len(req.prompt),
                    metadata={"overload_reason": e.reason},
                )
                if self._events is not None:
                    self._events.emit("admission.reject", model=self._model,
                                      request_id=original_id or pump_id,
                                      reason=e.reason)
                loop.call_soon_threadsafe(self._set_result, fut, shed)
            except Exception as e:
                del self._futures[pump_id]
                loop.call_soon_threadsafe(self._set_exc, fut, e)

    def _resolve(self, res: GenerationResult) -> None:
        entry = self._futures.pop(res.request_id, None)
        if entry is None:
            logger.warning("pump: no future for %s", res.request_id)
            return
        fut, loop, original_id = entry
        res.request_id = original_id or res.request_id
        loop.call_soon_threadsafe(self._set_result, fut, res)

    def _fail_all(self, exc: Exception) -> None:
        futures, self._futures = self._futures, {}
        for fut, loop, _orig in futures.values():
            loop.call_soon_threadsafe(self._set_exc, fut, exc)

    @staticmethod
    def _set_result(fut: asyncio.Future, value: Any) -> None:
        if not fut.done():
            fut.set_result(value)

    @staticmethod
    def _set_exc(fut: asyncio.Future, exc: Exception) -> None:
        if not fut.done():
            fut.set_exception(exc)

    # ------------------------------------------------------------- stats

    def get_stats(self) -> Dict[str, Any]:
        with self._inbox_lock:
            inbox_depth = len(self._inbox)
        return {
            "in_flight": len(self._futures),
            "thread_alive": bool(self._thread and self._thread.is_alive()),
            "steps": self._steps,
            "step_errors": self._step_errors,
            "inbox_depth": inbox_depth,
            "inbox_wait": self.inbox_wait.snapshot(),
            # requests admitted INSIDE a device step's shadow via the
            # engine's overlap hook (vs the top-of-loop drain)
            "overlap_admitted": self._overlap_admitted,
            "engine": self.engine.get_metrics(),
        }
