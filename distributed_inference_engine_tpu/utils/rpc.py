"""Shared framed-RPC plumbing: client class + server connection loop.

One implementation of connect/reconnect/locking/call for every framed-RPC
peer (worker client, coordinator client) — the reference had no client class
at all, and two hand-rolled copies would drift (they briefly did: one copy
lost the malformed-response guard; later the two hand-rolled *server* loops
drifted the same way, hence ``FramedServerMixin``).
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Any, Awaitable, Callable, Dict, Optional, Tuple

from .framing import (
    HEADER_SIZE,
    FrameError,
    read_frame,
    read_frame_after_header,
    write_frame,
)

logger = logging.getLogger(__name__)

# a framed message starts with magic 0xD17E — never printable ASCII — so a
# connection whose first four bytes spell an HTTP verb is unambiguously a
# plain HTTP client (curl/Prometheus hitting GET /metrics on the RPC port)
_HTTP_VERB_PREFIXES = (b"GET ", b"HEAD", b"POST", b"PUT ", b"DELE",
                       b"OPTI", b"PATC")


class RPCError(RuntimeError):
    """Peer-reported request failure (distinct from transport failure).

    ``kind`` carries the peer's machine-readable error class (the
    envelope's ``error_kind``, from the handler exception's
    ``rpc_error_kind`` attribute) so callers can react to specific
    failures — e.g. a relay's unreachable decode peer — without sniffing
    error text. ``detail`` is the optional machine-readable sub-reason
    (envelope ``error_detail``, from ``rpc_error_detail``) — e.g. an
    overloaded worker's "queue_full" vs "deadline".
    """

    def __init__(self, message: str, kind: str = "",
                 detail: str = "") -> None:
        super().__init__(message)
        self.kind = kind
        self.detail = detail


# connections a client keeps to a peer that has not said how many requests
# it runs at once (a worker says: ``pool_for_slots``)
DEFAULT_POOL = 8


def pool_for_slots(slots, queue=None) -> int:
    """The pool a coordinator keeps to a worker whose engines run ``slots``
    requests at once: the slots plus a look-ahead. A stream holds a
    connection for its life, so the pool is how many requests the
    coordinator keeps AT the worker; with only as many as slots, a freed
    slot's successor is a round trip away and the slot runs one decode
    chunk empty for every request it serves. The look-ahead (a quarter of
    the slots, two at least) waits in the engine's own queue and is
    prefilled in the iteration its slot frees; the rest of the backlog stays
    with the coordinator. ``queue`` is the worker's report of how many
    requests its engines keep waiting before they shed (``None``: no bound):
    the look-ahead takes only the room that leaves beyond the slots, so
    it is never what makes an engine shed. ``DEFAULT_POOL`` where the worker
    reports fewer slots than fill it, or nothing."""
    slots = int(slots or 0)
    ahead = max(2, slots // 4)
    if queue is not None:
        ahead = min(ahead, max(0, int(queue) - slots))
    return max(DEFAULT_POOL, slots + ahead)


class FramedRPCClient:
    """Pooled framed-RPC client: concurrent calls each ride their own
    connection (bounded by ``max_connections``), with transparent reconnect
    after a drop and poisoned-connection teardown.

    One frame in flight per connection keeps request/response matching
    trivial (the server answers in frame order per stream); concurrency
    comes from the pool, so N coordinator dispatch groups to one worker —
    or N relays holding a decode peer for a whole generation — overlap
    instead of serializing behind a single socket lock.
    """

    # optional chaos injection oracle (utils/faults.FaultPlan); None in
    # production — the hot path pays one attribute load
    fault_plan = None

    def __init__(self, host: str, port: int,
                 timeout: float = 30.0,
                 max_frame: int = 64 * 1024 * 1024,
                 max_connections: int = DEFAULT_POOL) -> None:
        self.host = host
        self.port = port
        self.timeout = timeout
        self.max_frame = max_frame
        self.max_connections = max(1, max_connections)
        # idle connections ready for reuse; _total counts idle + in-use
        self._free: list = []   # [(reader, writer)]
        self._total = 0
        self._inuse: set = set()  # (reader, writer) with a call in flight
        self._waiting = 0         # callers blocked on a full pool
        self._cond = asyncio.Condition()
        self._seq = 0
        self._closed = False
        # asyncio keeps only weak refs to tasks: retain notify tasks here
        # or they can be garbage-collected before the waiter is woken
        self._bg_tasks: set = set()

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    def pool_stats(self) -> Dict[str, int]:
        """Gauges of the connection pool: calls holding a connection,
        callers blocked because all ``max_connections`` are held, and
        ``max_connections`` itself."""
        return {"in_use": len(self._inuse), "waiting": self._waiting,
                "size": self.max_connections}

    def resize_pool(self, max_connections: int) -> None:
        """A new bound on the pool. Growing wakes the callers that wait;
        shrinking closes nothing: connections over the bound are let go as
        their calls end (``_release_nowait``)."""
        n = max(1, int(max_connections))
        grown = n > self.max_connections
        self.max_connections = n
        if grown and self._waiting:
            self._notify_detached(all_waiters=True)

    async def _acquire(
        self, timeout: float
    ) -> Tuple[asyncio.StreamReader, asyncio.StreamWriter]:
        async def _get() -> Tuple[asyncio.StreamReader, asyncio.StreamWriter]:
            async with self._cond:
                while True:
                    while self._free:
                        reader, writer = self._free.pop()
                        if writer.is_closing():    # died while idle
                            self._total -= 1
                            continue
                        return reader, writer
                    if self._total < self.max_connections:
                        self._total += 1  # reserve before the await below
                        break
                    self._waiting += 1
                    try:
                        await self._cond.wait()
                    finally:
                        self._waiting -= 1
            try:
                return await asyncio.open_connection(self.host, self.port)
            except BaseException:
                async with self._cond:
                    self._total -= 1
                    self._cond.notify()
                raise

        # the timeout must bound the connect/wait too — a blackholed host
        # otherwise hangs the OS TCP connect (~2 min)
        return await asyncio.wait_for(_get(), timeout=timeout)

    def _release_nowait(self, conn) -> None:
        """Synchronous re-pool: no ``await`` means no suspension point at
        which a cancelled caller could leak the slot (the same discipline
        as ``_discard_nowait``). List mutation is loop-thread-atomic;
        waiters are notified by a detached task."""
        self._inuse.discard(conn)
        if self._closed:
            # close() ran while this call was in flight — don't re-pool a
            # socket nobody will ever close again
            self._discard_nowait(conn)
            return
        if self._total > self.max_connections:     # the pool was shrunk
            self._discard_nowait(conn)
            return
        self._free.append(conn)
        self._notify_detached()

    def _discard_nowait(self, conn) -> None:
        """Synchronous discard: safe to run from a CancelledError handler
        (any further ``await`` there could be interrupted again, leaking
        the slot)."""
        self._inuse.discard(conn)
        _reader, writer = conn
        writer.close()
        self._total -= 1
        self._notify_detached()

    def _notify_detached(self, all_waiters: bool = False) -> None:
        """Wake one _acquire waiter (or all of them) from a task that can't
        be cancelled with the caller (Condition.notify needs the lock, which
        needs an await)."""

        async def _notify() -> None:
            async with self._cond:
                if all_waiters:
                    self._cond.notify_all()
                else:
                    self._cond.notify()

        try:
            task = asyncio.get_running_loop().create_task(_notify())
            self._bg_tasks.add(task)
            task.add_done_callback(self._bg_tasks.discard)
        except RuntimeError:      # no running loop (teardown) — no waiters
            pass

    def abort_inflight(self) -> int:
        """Force-close every connection with a call in flight: the pending
        reads fail immediately as transport errors instead of waiting out
        the full dispatch timeout against a peer that is being removed —
        the caller's retry policy then requeues the work on an alternate.
        Slot accounting stays with the in-flight caller (its discard path
        runs when the read fails); this only tears the sockets."""
        n = 0
        for _reader, writer in list(self._inuse):
            writer.close()
            n += 1
        return n

    async def close(self) -> None:
        """Close idle connections and mark the pool closed: in-flight calls
        discard their connection when they finish instead of re-pooling it,
        so the count drains to zero. A later ``call`` reopens the pool
        (reconnect semantics, matching the pre-pool client)."""
        self._closed = True
        async with self._cond:
            free, self._free = self._free, []
            self._total -= len(free)
            self._cond.notify_all()
        for _reader, writer in free:
            writer.close()
            try:
                await writer.wait_closed()
            # graftlint: ok[swallowed-transport-error] pool teardown of an already-closing socket — there is no call left to fail
            except (ConnectionResetError, BrokenPipeError):
                pass

    async def call(self, method: str, *, timeout: Optional[float] = None,
                   **params: Any) -> Any:
        """Send one request frame, await one response frame.

        Raises ``RPCError`` when the peer reports failure; transport trouble
        (``OSError``/``asyncio.TimeoutError``/...) propagates for callers —
        router/LB — to turn into health signals.
        """
        return await self._roundtrip(method, None, timeout, params)

    async def call_stream(self, method: str, on_chunk: Callable[[Dict], None],
                          *, timeout: Optional[float] = None,
                          on_acquired: Optional[Callable[[float], None]] = None,
                          **params: Any) -> Any:
        """Send one request, consume a stream of chunk frames, return the
        final result.

        The server interleaves ``{"stream": true, ...}`` frames (each passed
        to ``on_chunk``) before the usual success/error envelope. ``timeout``
        bounds each individual frame read — a live stream keeps resetting
        it — not the total call. ``on_acquired(wait_s)`` fires once the
        call holds its pooled connection, with the seconds it waited for
        one: a stream holds its connection for its life, so under load
        this wait is a queue.
        """
        return await self._roundtrip(method, on_chunk, timeout, params,
                                     on_acquired)

    async def _roundtrip(self, method: str,
                         on_chunk: Optional[Callable[[Dict], None]],
                         timeout: Optional[float],
                         params: Dict[str, Any],
                         on_acquired: Optional[Callable[[float], None]] = None
                         ) -> Any:
        """One shared request/response cycle for ``call`` and
        ``call_stream`` — a single copy of the acquire/discard discipline
        and envelope validation (two copies drifted once before; see the
        module docstring)."""
        self._seq += 1
        msg = {"method": method, "id": f"{id(self):x}-{self._seq}", **params}
        effective = timeout if timeout is not None else self.timeout
        fault = None
        if self.fault_plan is not None:
            fault = self.fault_plan.draw(self.address, "client", method)
            if fault is not None and fault.kind == "connect_refused":
                raise ConnectionRefusedError(
                    f"chaos: injected connection refusal to {self.address}")
            if fault is not None and fault.kind == "slow":
                await asyncio.sleep(fault.delay_s)
        self._closed = False          # calling a closed client reopens it
        t_wait = time.perf_counter()
        conn = await self._acquire(effective)
        self._inuse.add(conn)
        try:
            if on_acquired is not None:
                on_acquired(time.perf_counter() - t_wait)
            await write_frame(conn[1], msg)
            if fault is not None and fault.kind == "stall":
                # the request frame is on the wire; tear the connection
                # before the response — the worst spot in the exchange
                raise ConnectionResetError(
                    f"chaos: injected mid-frame stall to {self.address}")
            while True:
                frame = await read_frame(
                    conn[0], max_frame=self.max_frame, timeout=effective,
                )
                if isinstance(frame, dict) and frame.get("stream"):
                    if on_chunk is None:
                        raise RPCError(
                            f"unexpected stream frame from {method!r} — "
                            "use call_stream for streaming methods")
                    on_chunk(frame)
                    continue
                response = frame
                break
        except BaseException:
            # BaseException: a cancelled caller must still return its slot
            # (a response may be in flight on the socket — discard it), or
            # the pool leaks towards zero capacity
            self._discard_nowait(conn)
            raise
        else:
            self._release_nowait(conn)
        if not isinstance(response, dict):
            raise RPCError(f"malformed response: {response!r}")
        if not response.get("success"):
            raise RPCError(response.get("error", "unknown peer error"),
                           kind=str(response.get("error_kind", "")),
                           detail=str(response.get("error_detail", "")))
        return response.get("result")


class ClientGone(Exception):
    """The streaming client hung up mid-stream — not a handler failure."""


async def relay_stream(fut: "asyncio.Future", queue: "asyncio.Queue",
                       send) -> Any:
    """Forward token chunks from ``queue`` to ``send`` until ``fut``
    resolves, drain the stragglers, return the result.

    The one copy of the getter/wait/drain/cancel relay both streaming
    servers use (worker and coordinator — the cancellation/ordering logic
    here is exactly the kind that drifts when duplicated). Safe because
    chunk callbacks and the future resolution ride the same
    ``call_soon_threadsafe`` FIFO: when ``fut`` is done, every chunk is
    already queued.
    """
    try:
        while True:
            getter = asyncio.ensure_future(queue.get())
            done, _ = await asyncio.wait(
                {getter, fut}, return_when=asyncio.FIRST_COMPLETED)
            if getter in done:
                await send({"tokens": getter.result()})
                continue
            getter.cancel()
            break
        while not queue.empty():
            await send({"tokens": queue.get_nowait()})
        return await fut
    except BaseException:
        fut.cancel()
        raise


class FramedServerMixin:
    """Framed-RPC server connection loop, shared by ``WorkerServer`` and
    ``CoordinatorServer``.

    Subclass contract: set ``self._methods`` (method name → async handler)
    and ``self._conn_writers`` (a set) before serving, expose
    ``self.max_frame_bytes``. Responses come back in frame order on one
    stream; concurrent clients use concurrent connections.

    Hooks (all optional overrides):
    - ``_run_handler(method, handler, msg)`` — server-side timeout policy.
    - ``_envelope_extra()`` — dict merged into every response envelope.
    - ``_timeout_error(method)`` — message for ``asyncio.TimeoutError``.
    - ``_on_handler_error(method, exc)`` — error accounting.
    - ``_after_dispatch(method, req_id, duration_s, response)`` — metrics.

    Streaming: methods in ``_stream_methods`` get ``handler(msg, send)``
    where ``await send(obj)`` writes a ``{"stream": true, "id": …}`` frame
    ahead of the final envelope; the client consumes them with
    ``FramedRPCClient.call_stream``.
    """

    _methods: Dict[str, Callable[[Dict[str, Any]], Awaitable[Any]]]
    _stream_methods: Dict[str, Callable[..., Awaitable[Any]]] = {}
    _conn_writers: set
    max_frame_bytes: int = 64 * 1024 * 1024
    # optional chaos injection oracle (utils/faults.FaultPlan); None in
    # production
    fault_plan = None

    def _fault_scope(self) -> str:
        """Identity this server reports to the FaultPlan (workers override
        via their ``worker_id`` attribute)."""
        return getattr(self, "worker_id", "") or type(self).__name__

    async def _handle_connection(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        self._conn_writers.add(writer)
        try:
            first = True
            while True:
                try:
                    if first:
                        # sniff the connection's first bytes: an HTTP verb
                        # means a plain-HTTP scraper (GET /metrics) — hand
                        # the connection to the HTTP hook; anything else
                        # must be a frame header (magic-validated below)
                        first = False
                        head = await reader.readexactly(HEADER_SIZE)
                        if head[:4] in _HTTP_VERB_PREFIXES:
                            await self._serve_http(head, reader, writer)
                            break
                        msg = await read_frame_after_header(
                            reader, head, max_frame=self.max_frame_bytes)
                    else:
                        msg = await read_frame(
                            reader, max_frame=self.max_frame_bytes,
                            timeout=None,
                        )
                # graftlint: ok[swallowed-transport-error] client hung up; leaving the serve loop (and closing the connection) IS the handling
                except (asyncio.IncompleteReadError, ConnectionResetError):
                    break  # client closed
                except FrameError as e:
                    await write_frame(writer, {"success": False,
                                               "error": f"bad frame: {e}"})
                    break
                if self.fault_plan is not None and isinstance(msg, dict):
                    spec = self.fault_plan.draw(
                        self._fault_scope(), "server",
                        str(msg.get("method", "")))
                    if spec is not None:
                        if spec.kind == "drop":
                            break   # request consumed, no response, close
                        if spec.kind == "garble":
                            # bytes that fail frame-magic validation: the
                            # client sees FrameError (transport class)
                            writer.write(b"\x00GARBLED\x00FRAME\x00")
                            try:
                                await writer.drain()
                            # graftlint: ok[swallowed-transport-error] injected garble fault: the CLIENT is meant to see the failure (FrameError); the server just tears the conn
                            except (ConnectionResetError, BrokenPipeError):
                                pass
                            break
                        if spec.kind == "slow":
                            await asyncio.sleep(spec.delay_s)
                if (isinstance(msg, dict)
                        and msg.get("method") in self._stream_methods):
                    response = await self._dispatch_stream(msg, writer)
                    if response is None:      # client hung up mid-stream
                        break
                else:
                    response = await self._dispatch(msg)
                try:
                    await write_frame(writer, response)
                # graftlint: ok[swallowed-transport-error] client gone mid-response — nobody left to tell; the conn closes below
                except (ConnectionResetError, BrokenPipeError):
                    break                     # client gone — nobody to tell
        finally:
            self._conn_writers.discard(writer)
            writer.close()
            try:
                await writer.wait_closed()
            # graftlint: ok[swallowed-transport-error] teardown of a socket that is already dead — nothing to mark at this layer
            except (ConnectionResetError, BrokenPipeError):
                pass

    async def _dispatch(self, msg: Any) -> Dict[str, Any]:
        t0 = time.perf_counter()
        if not isinstance(msg, dict) or "method" not in msg:
            return {"success": False,
                    "error": "message must be a dict with 'method'"}
        method = msg["method"]
        handler = self._methods.get(method)
        req_id = msg.get("id", "")
        extra = self._envelope_extra()
        if handler is None:
            return {"id": req_id, "success": False, **extra,
                    "error": f"unknown method {method!r}"}
        try:
            result = await self._run_handler(method, handler, msg)
            response = {"id": req_id, "success": True, **extra,
                        "result": result}
        # graftlint: ok[swallowed-transport-error] the timeout becomes an error response frame — the client sees and counts it
        except asyncio.TimeoutError:
            response = {"id": req_id, "success": False, **extra,
                        "error": self._timeout_error(method)}
        except Exception as e:  # fan any handler error back, keep serving
            self._on_handler_error(method, e)
            logger.warning("%s: %s failed: %s",
                           type(self).__name__, method, e)
            response = {"id": req_id, "success": False, **extra,
                        "error": str(e)}
            kind = getattr(e, "rpc_error_kind", "") or getattr(e, "kind", "")
            if kind:
                response["error_kind"] = kind
            detail = (getattr(e, "rpc_error_detail", "")
                      or getattr(e, "detail", ""))
            if detail:
                response["error_detail"] = detail
        self._after_dispatch(method, req_id, time.perf_counter() - t0,
                             response)
        return response

    async def _dispatch_stream(
        self, msg: Dict[str, Any], writer: asyncio.StreamWriter
    ) -> Optional[Dict[str, Any]]:
        """Run a streaming handler: chunk frames on the wire as the
        handler emits them, then the normal envelope. Returns None when
        the CLIENT hung up mid-stream (routine for aborted generations —
        not a handler failure, and there is nobody left to send an
        envelope to); a downstream ConnectionError from the handler itself
        still produces an error envelope."""
        t0 = time.perf_counter()
        method = msg["method"]
        handler = self._stream_methods[method]
        req_id = msg.get("id", "")
        extra = self._envelope_extra()

        async def send(obj: Dict[str, Any]) -> None:
            try:
                await write_frame(writer,
                                  {"stream": True, "id": req_id, **obj})
            except (ConnectionResetError, BrokenPipeError, OSError) as e:
                raise ClientGone() from e

        try:
            result = await handler(msg, send)
            response = {"id": req_id, "success": True, **extra,
                        "result": result}
        except ClientGone:
            logger.info("%s: client disconnected mid-stream (%s)",
                        type(self).__name__, method)
            return None
        except Exception as e:
            self._on_handler_error(method, e)
            logger.warning("%s: %s failed: %s",
                           type(self).__name__, method, e)
            response = {"id": req_id, "success": False, **extra,
                        "error": str(e)}
            kind = getattr(e, "rpc_error_kind", "") or getattr(e, "kind", "")
            if kind:
                response["error_kind"] = kind
            detail = (getattr(e, "rpc_error_detail", "")
                      or getattr(e, "detail", ""))
            if detail:
                response["error_detail"] = detail
        self._after_dispatch(method, req_id, time.perf_counter() - t0,
                             response)
        return response

    # -- plain-HTTP side door (GET /metrics on the RPC port) ---------------

    async def _serve_http(self, head: bytes, reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter) -> None:
        """Answer ONE plain-HTTP request on the framed port, then let the
        caller close the connection. Only GET/HEAD reach ``_http_get``;
        everything else (and unknown paths) gets a 404. Deliberately
        minimal — this exists so ``curl``/Prometheus can scrape
        ``/metrics`` without speaking the frame protocol, not to be a web
        server."""
        try:
            raw = head
            if b"\r\n\r\n" not in raw:
                raw += await asyncio.wait_for(
                    reader.readuntil(b"\r\n\r\n"), timeout=5.0)
        # graftlint: ok[swallowed-transport-error] best-effort HTTP side-door: a scraper that hangs up mid-request just loses its scrape
        except (asyncio.TimeoutError, asyncio.IncompleteReadError,
                asyncio.LimitOverrunError, ConnectionResetError):
            return
        parts = raw.split(b"\r\n", 1)[0].decode("latin-1").split()
        method = parts[0].upper() if parts else ""
        path = (parts[1] if len(parts) > 1 else "/").split("?", 1)[0]
        status, ctype, body = "404 Not Found", "text/plain; charset=utf-8", \
            b"not found\n"
        if method in ("GET", "HEAD"):
            try:
                got = await self._http_get(path)
            except Exception as e:
                logger.warning("%s: HTTP %s %s failed: %s",
                               type(self).__name__, method, path, e)
                got = None
                status, body = ("500 Internal Server Error",
                                f"{e}\n".encode("utf-8", "replace"))
            if got is not None:
                ctype, body = got[0], got[1]
                status = "200 OK"
        payload = b"" if method == "HEAD" else body
        try:
            writer.write(
                f"HTTP/1.1 {status}\r\n"
                f"Content-Type: {ctype}\r\n"
                f"Content-Length: {len(body)}\r\n"
                "Connection: close\r\n\r\n".encode("latin-1") + payload)
            await writer.drain()
        # graftlint: ok[swallowed-transport-error] scraper disconnected before the HTTP response; the connection closes right after
        except (ConnectionResetError, BrokenPipeError, OSError):
            pass

    async def _http_get(self, path: str) -> Optional[Tuple[str, bytes]]:
        """Override hook: return ``(content_type, body)`` or None for 404."""
        return None

    async def _run_handler(self, method: str, handler, msg) -> Any:
        return await handler(msg)

    def _envelope_extra(self) -> Dict[str, Any]:
        return {}

    def _timeout_error(self, method: str) -> str:
        return f"{method} timed out"

    def _on_handler_error(self, method: str, exc: Exception) -> None:
        pass

    def _after_dispatch(self, method: str, req_id: str,
                        duration_s: float, response: Dict[str, Any]) -> None:
        pass

    def _close_all_connections(self) -> None:
        for w in list(self._conn_writers):
            w.close()
