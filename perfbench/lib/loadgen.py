"""Open-loop load generator: one asyncio loop, one task per request, each
request timed from when it was DUE (so a stall counts against the requests
behind it) and streamed through ``generate_stream`` so the client sees every
frame arrive."""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Tuple

from .procs import MODEL
from .traffic import Request


@dataclass
class Record:
    """What the client saw of one request. Times are ``time.monotonic``."""

    req: Request
    due: float
    sent: float = 0.0
    frames: List[Tuple[float, int]] = field(default_factory=list)
    done: float = 0.0
    tokens: List[int] = field(default_factory=list)
    finish_reason: str = ""
    trace: Dict[str, Any] = field(default_factory=dict)
    worker_id: str = ""
    error: str = ""

    @property
    def n_out(self) -> int:
        return sum(n for _, n in self.frames)

    def failure(self, vocab_size: int) -> str:
        """"" for a good reply: no error, exactly the requested number of
        tokens, all streamed, all inside the vocabulary."""
        if self.error:
            return self.error
        want = self.req.output_len
        if len(self.tokens) != want or self.n_out != want:
            return (f"{len(self.tokens)} tokens in the result and "
                    f"{self.n_out} streamed, asked {want}")
        if any(not 0 <= int(t) < vocab_size for t in self.tokens):
            return "token id outside the vocabulary"
        return ""


async def send_one(client: Any, rec: Record, tag: str) -> None:
    rec.sent = time.monotonic()

    def on_tokens(tokens: List[int]) -> None:
        rec.frames.append((time.monotonic(), len(tokens)))

    try:
        res = await client.generate_stream(
            MODEL, on_tokens, prompt=rec.req.prompt,
            max_new_tokens=rec.req.output_len, temperature=0.0, eos_id=-1,
            request_id=f"{tag}-{rec.req.index}")
        rec.tokens = [int(t) for t in res.get("tokens", [])]
        rec.finish_reason = str(res.get("finish_reason", ""))
        rec.trace = res.get("trace") or {}
        rec.worker_id = str((res.get("metadata") or {}).get("worker_id", ""))
    except asyncio.CancelledError:
        rec.error = "cancelled"
        raise
    except Exception as e:      # a failed request is a result, not a crash
        rec.error = f"{type(e).__name__}: {e}"[:200]
    finally:
        rec.done = time.monotonic()


async def run_schedule(client: Any, reqs: List[Request], t_open: float,
                       tag: str, drain_timeout_s: float) -> List[Record]:
    """Send every request at ``t_open + due_s``. Returns once every request
    due before the tail has finished AND the tail has been sent, or the
    drain times out; requests of the tail still in flight are cancelled
    (they exist to keep the system loaded while the window's drain)."""
    records: List[Record] = []
    tasks: List[Tuple[Record, "asyncio.Task[None]"]] = []
    for r in reqs:
        due = t_open + r.due_s
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        if r.phase == "tail" and all(
                t.done() for rec, t in tasks if rec.req.phase != "tail"):
            break                       # the window has drained: stop loading
        rec = Record(req=r, due=due)
        records.append(rec)
        tasks.append((rec, asyncio.ensure_future(send_one(client, rec, tag))))
    judged = [t for rec, t in tasks if rec.req.phase != "tail"]
    if judged:
        await asyncio.wait(judged, timeout=drain_timeout_s)
    for _, t in tasks:
        if not t.done():
            t.cancel()
    await asyncio.gather(*(t for _, t in tasks), return_exceptions=True)
    return records


def lateness_s(records: List[Record]) -> List[float]:
    """How late each request due in the window was sent."""
    return [rec.sent - rec.due for rec in records
            if rec.sent and rec.req.phase == "window"]


def in_flight_at(records: List[Record], t: float) -> int:
    """Requests sent and not yet finished at time ``t``."""
    return sum(1 for r in records
               if r.sent and r.sent <= t and (not r.done or r.done > t))
