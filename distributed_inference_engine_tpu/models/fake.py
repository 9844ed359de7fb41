"""Fake engine: the real ``Engine`` interface with injectable latency/errors.

Capability heir of the reference's test strategy (SURVEY.md §4): ``FakeModel``
(configurable latency, metric tracking — ``src/mock_models/fake_model.py:11-83``)
and ``mock_batch_inference`` (injectable ``error_rate``/``latency_ms`` —
``src/mock_models/mock_inference.py:31-53``). Every orchestration layer
(worker, batcher, router, coordinator) is tested on CPU against this class, so
their tests never need a TPU or a multi-second jit compile.

Semantics: "generation" echoes the prompt reversed, token by token, up to
``max_new_tokens`` — deterministic, order-sensitive, and cheap, so tests can
assert exact outputs AND detect batch-order mix-ups (an echo that ignored
order couldn't).
"""

from __future__ import annotations

import random
import time
import zlib
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from ..engine.types import (
    EngineOverloadedError,
    GenerationRequest,
    GenerationResult,
)
from ..utils.tracing import LatencyStats


class FakeEngine:
    """Drop-in for ``engine.Engine`` with simulated latency and failures."""

    def __init__(
        self,
        latency_s: float = 0.0,
        per_token_latency_s: float = 0.0,
        error_rate: float = 0.0,
        seed: int = 0,
    ) -> None:
        self.latency_s = latency_s
        self.per_token_latency_s = per_token_latency_s
        self.error_rate = error_rate
        self._rand = random.Random(seed)
        self.prefill_stats = LatencyStats()
        self.decode_stats = LatencyStats()
        self._total_requests = 0
        self._total_generated_tokens = 0
        self._total_errors = 0

    def generate(self, requests: List[GenerationRequest]) -> List[GenerationResult]:
        self._total_requests += len(requests)
        t0 = time.perf_counter()
        if self.error_rate and self._rand.random() < self.error_rate:
            self._total_errors += 1
            raise RuntimeError("injected fake-engine failure")
        n_tokens = sum(min(len(r.prompt), r.max_new_tokens) for r in requests)
        delay = self.latency_s + self.per_token_latency_s * n_tokens
        if delay:
            time.sleep(delay)
        results = []
        for i, r in enumerate(requests):
            toks = list(reversed(r.prompt))[: r.max_new_tokens]
            self._total_generated_tokens += len(toks)
            results.append(
                GenerationResult(
                    request_id=r.request_id or f"fake-{self._total_requests}-{i}",
                    tokens=toks,
                    finish_reason="length",
                    prompt_tokens=len(r.prompt),
                    ttft_s=delay,
                    decode_s=0.0,
                    metadata={"fake": True},
                )
            )
        self.prefill_stats.add(time.perf_counter() - t0)
        return results

    def get_metrics(self) -> Dict[str, Any]:
        return {
            "total_requests": self._total_requests,
            "total_prompt_tokens": 0,
            "total_generated_tokens": self._total_generated_tokens,
            "total_errors": self._total_errors,
            "prefill": self.prefill_stats.snapshot(),
            "decode": self.decode_stats.snapshot(),
            "spec": {"fake": True},
        }


def _chain(state: int, token: int) -> int:
    """Fold one token id into the crc32 context state."""
    return zlib.crc32(b"%d," % token, state)


@dataclass
class FakeEngineConfig:
    """The slice of ``EngineConfig`` the pump/worker plumbing touches."""

    max_waiting: int = 0
    queue_deadline_s: float = 0.0


class FakeContinuousEngine:
    """Continuous-batching fake: the submit/step/drain_finished interface
    ``EnginePump`` drives, deterministic and jax-free.

    The next token is a pure function of the FULL context (prompt +
    tokens generated so far): a crc32 chain over the token ids, mod
    ``vocab_size``. That makes output independent of which worker runs a
    request AND resumable — replaying prompt+generated-prefix on another
    replica continues with exactly the tokens the dead replica would
    have produced next, which is what the chaos harness's token-for-token
    stream-resume assertion checks.

    Overload/deadline semantics mirror ``ContinuousEngine``: a bounded
    waiting queue sheds at submit (``EngineOverloadedError``), the global
    ``queue_deadline_s`` sheds queued requests as ``overloaded``/
    ``deadline``, and a request's own ``deadline_s`` budget expires it
    with ``finish_reason="deadline"`` before any decode step is spent.
    Stop handling covers ``eos_id`` and ``stop_ids`` (no sequences — the
    fleet tests don't use them).
    """

    def __init__(self, step_latency_s: float = 0.0, tokens_per_step: int = 1,
                 max_slots: int = 8, max_waiting: int = 0,
                 queue_deadline_s: float = 0.0, vocab_size: int = 997,
                 admit_latency_per_token_s: float = 0.0,
                 prefix_cache: bool = False,
                 prefix_page_size: int = 64,
                 stream_chunk_tokens: int = 0,
                 stream_dispatch_overhead_s: float = 0.0) -> None:
        self.config = FakeEngineConfig(
            max_waiting=int(max_waiting),
            queue_deadline_s=float(queue_deadline_s))
        self.step_latency_s = float(step_latency_s)
        self.tokens_per_step = max(1, int(tokens_per_step))
        # sub-chunk streaming model (ISSUE 13), mirroring the real
        # engine's EngineConfig.stream_chunk_steps: while any live slot
        # has a callback, the step's wall time splits into
        # ceil(tokens_per_step / stream_chunk_tokens) sub-chunks and
        # callbacks fire per sub-chunk — ITL collapses from one frame
        # per step to one per sub-chunk. Each EXTRA sub-dispatch costs
        # stream_dispatch_overhead_s (the shorter-chunk goodput tax the
        # stream leg measures). 0 = off: byte-identical to the old step.
        self.stream_chunk_tokens = max(0, int(stream_chunk_tokens))
        self.stream_dispatch_overhead_s = float(stream_dispatch_overhead_s)
        self._stream_sub_chunks = 0
        self.max_slots = max(1, int(max_slots))
        self.vocab_size = max(2, int(vocab_size))
        # prefix-cache TTFT model: admission costs
        # admit_latency_per_token_s per UNCACHED prompt token (the fake's
        # stand-in for prefill compute), and with prefix_cache on, page-
        # aligned prompt heads this engine has already admitted are free —
        # so routing same-prefix traffic to the same worker (the LB's
        # prefix_affinity strategy) measurably improves TTFT, exactly the
        # effect the fleet sweep's affinity leg quantifies
        self.admit_latency_per_token_s = float(admit_latency_per_token_s)
        self.prefix_cache = bool(prefix_cache)
        self.prefix_page_size = max(1, int(prefix_page_size))
        self._prefix_seen: set = set()
        self._prefix_cached_tokens = 0
        self._admit_sleep_s = 0.0
        self._fabric_exports = 0
        self._fabric_imports = 0
        self._fabric_imported_tokens = 0
        # waiting: (request, on_tokens, t_submit); live: [req, cb, t_submit,
        # chain state, tokens]
        self._waiting: List[tuple] = []
        self._live: List[list] = []
        self._finished: List[GenerationResult] = []
        self._total_requests = 0
        self._total_generated = 0
        self._steps = 0
        self._rejected_full = 0
        self._shed_deadline = 0
        self._deadline_expired = 0
        self._prefilled_admitted = 0
        # served-request latency distributions, exported as the
        # engine_ttft_seconds / engine_decode_chunk_seconds histogram
        # families — the autoscaler's scrape-time SLO inputs. ttft covers
        # queue wait + admission (recorded at first decode step for a
        # slot); step_stats records per-step wall, the fake's ITL proxy.
        self.ttft_stats = LatencyStats()
        self.step_stats = LatencyStats()

    # ------------------------------------------------------------- submit

    def submit(self, request: GenerationRequest, on_tokens=None) -> str:
        if not request.prompt:
            raise ValueError("empty prompt")
        cap = self.config.max_waiting
        if cap and len(self._waiting) >= cap:
            self._rejected_full += 1
            raise EngineOverloadedError(
                f"waiting queue full ({len(self._waiting)}/{cap}); retry "
                "on another replica or later", reason="queue_full")
        self._total_requests += 1
        if not request.request_id:
            request.request_id = f"fcreq-{self._total_requests}"
        self._waiting.append((request, on_tokens, time.perf_counter(), None))
        return request.request_id

    def submit_prefilled(self, request: GenerationRequest, handoff,
                         on_tokens=None) -> str:
        """Disaggregated admission (the ``submit_prefilled`` capability the
        worker's decode-pool RPCs check for): the handoff's ``first_token``
        was produced by the prefill pool, so this engine seeds the slot
        with it and decodes from position ``prompt_len + 1``. The crc32
        chain makes a ``FakePrefillEngine`` handoff chain-consistent: the
        disaggregated output is token-for-token what a single fake engine
        would have generated."""
        if not request.prompt:
            raise ValueError("empty prompt")
        if int(handoff.prompt_len) != len(request.prompt):
            raise ValueError(
                f"handoff prompt_len {handoff.prompt_len} != prompt length "
                f"{len(request.prompt)} for {request.request_id!r}")
        cap = self.config.max_waiting
        if cap and len(self._waiting) >= cap:
            self._rejected_full += 1
            raise EngineOverloadedError(
                f"waiting queue full ({len(self._waiting)}/{cap}); retry "
                "on another replica or later", reason="queue_full")
        self._total_requests += 1
        self._prefilled_admitted += 1
        if not request.request_id:
            request.request_id = f"fcreq-{self._total_requests}"
        self._waiting.append((request, on_tokens, time.perf_counter(),
                              int(handoff.first_token)))
        return request.request_id

    # --------------------------------------------------------------- step

    def _shed_expired(self) -> None:
        queue_deadline = self.config.queue_deadline_s
        now = time.perf_counter()
        cut = (now - queue_deadline) if queue_deadline else None
        keep = []
        for req, cb, t, first in self._waiting:
            if cut is not None and t <= cut:
                self._shed_deadline += 1
                self._finished.append(GenerationResult(
                    request_id=req.request_id, tokens=[],
                    finish_reason="overloaded", prompt_tokens=len(req.prompt),
                    ttft_s=now - t,
                    metadata={"overload_reason": "deadline"}))
            elif req.deadline_s is not None and now - t >= req.deadline_s:
                self._deadline_expired += 1
                self._finished.append(GenerationResult(
                    request_id=req.request_id, tokens=[],
                    finish_reason="deadline", prompt_tokens=len(req.prompt),
                    ttft_s=now - t, metadata={"deadline_s": req.deadline_s}))
            else:
                keep.append((req, cb, t, first))
        self._waiting = keep

    def _admit_prefix(self, prompt: List[int]) -> int:
        """Return how many prompt tokens this admission must pay for, after
        crediting page-aligned prefixes this engine has already seen (when
        ``prefix_cache`` is on), and record the new prefixes as warm."""
        if not self.prefix_cache:
            return len(prompt)
        page = self.prefix_page_size
        full_pages = len(prompt) // page
        warm_pages = 0
        for j in range(full_pages, 0, -1):
            if tuple(prompt[:j * page]) in self._prefix_seen:
                warm_pages = j
                break
        for j in range(1, full_pages + 1):
            self._prefix_seen.add(tuple(prompt[:j * page]))
        cached = warm_pages * page
        self._prefix_cached_tokens += cached
        return len(prompt) - cached

    # ---------------------------------------------------------- KV fabric

    def kv_export(self, tokens, max_pages: int = 0):
        """Fake-flavored KV-fabric export (``kind: "fake"`` wire,
        engine/kv_fabric.py): the longest page-aligned prefix of
        ``tokens`` this engine has admitted, as tokens + checksum. Speaks
        the same RPC plane / validation / fallback protocol as the real
        engine so fleet tests exercise the fabric without jax pools."""
        from ..engine.kv_fabric import build_fake_wire

        if not self.prefix_cache:
            return None
        toks = [int(t) for t in tokens]
        page = self.prefix_page_size
        full_pages = len(toks) // page
        if max_pages > 0:
            full_pages = min(full_pages, int(max_pages))
        for j in range(full_pages, 0, -1):
            if tuple(toks[:j * page]) in self._prefix_seen:
                self._fabric_exports += 1
                return build_fake_wire(toks[:j * page], page)
        return None

    def kv_import(self, wire) -> int:
        """Validate + admit an exported prefix as locally warm; returns
        pages imported. ``FabricRejected`` (nothing admitted) on any
        mismatch — admission then pays normal prefill, never wrong KV."""
        from ..engine.kv_fabric import FabricRejected, check_fake_wire

        if not self.prefix_cache:
            raise FabricRejected("importer has no prefix cache")
        page = self.prefix_page_size
        toks = check_fake_wire(wire, page_size=page)
        imported = 0
        for j in range(1, len(toks) // page + 1):
            head = tuple(toks[:j * page])
            if head not in self._prefix_seen:
                self._prefix_seen.add(head)
                imported += 1
        self._fabric_imports += 1
        self._fabric_imported_tokens += imported * page
        return imported

    def step(self) -> int:
        """One decode step for every live slot (admitting from the waiting
        queue first); returns the live count, like ``ContinuousEngine``."""
        self._shed_expired()
        while self._waiting and len(self._live) < self.max_slots:
            req, cb, t, first = self._waiting.pop(0)
            if self.admit_latency_per_token_s and first is None:
                uncached = self._admit_prefix(list(req.prompt))
                if uncached:
                    pause = self.admit_latency_per_token_s * uncached
                    self._admit_sleep_s += pause
                    time.sleep(pause)
            state = 0
            for tok in req.prompt:
                state = _chain(state, tok)
            toks: List[int] = []
            if first is not None:
                # prefilled admission: the handoff's first token is this
                # chain state's own next token, so emitting it and folding
                # it in keeps the continuation identical to a single engine
                toks.append(first)
                state = _chain(state, first)
                self._total_generated += 1
                if cb is not None:
                    cb([first])
                self.ttft_stats.add(time.perf_counter() - t)
                if (first == req.eos_id or first in (req.stop_ids or ())
                        or len(toks) >= req.max_new_tokens):
                    now0 = time.perf_counter()
                    stopped = (first == req.eos_id
                               or first in (req.stop_ids or ()))
                    self._finished.append(GenerationResult(
                        request_id=req.request_id, tokens=toks,
                        finish_reason="stop" if stopped else "length",
                        prompt_tokens=len(req.prompt), ttft_s=now0 - t,
                        decode_s=now0 - t, metadata={"fake": True}))
                    continue
            # trailing 0.0 = the slot's speculation accept-credit accumulator
            self._live.append([req, cb, t, state, toks])
        if not self._live:
            return 0
        # sub-chunk split (ISSUE 13): engages only while a live slot is
        # actually streaming, like the real engine's adaptive clamp —
        # pure-batch traffic keeps the single full-step dispatch
        sizes = [self.tokens_per_step]
        if (self.stream_chunk_tokens
                and self.stream_chunk_tokens < self.tokens_per_step
                and any(s[1] is not None for s in self._live)):
            k = self.stream_chunk_tokens
            sizes = [k] * (self.tokens_per_step // k)
            if self.tokens_per_step % k:
                sizes.append(self.tokens_per_step % k)
        sub_sleep = self.step_latency_s / len(sizes)
        t_step = time.perf_counter()
        self._steps += 1
        had = {id(s): bool(s[4]) for s in self._live}
        done_slots: set = set()
        now = t_step
        for si, budget in enumerate(sizes):
            if si and self.stream_dispatch_overhead_s:
                # each extra sub-dispatch pays one more host round trip
                time.sleep(self.stream_dispatch_overhead_s)
            if sub_sleep:
                time.sleep(sub_sleep)
            now = time.perf_counter()
            if len(sizes) > 1:
                self._stream_sub_chunks += 1
            for slot in self._live:
                key = id(slot)
                if key in done_slots:
                    continue
                req, cb, t, state, toks = slot
                fresh: List[int] = []
                done = False
                for _ in range(budget):
                    nxt = state % self.vocab_size
                    state = _chain(state, nxt)
                    toks.append(nxt)
                    fresh.append(nxt)
                    self._total_generated += 1
                    if nxt == req.eos_id or nxt in (req.stop_ids or ()):
                        done = True
                        break
                    if len(toks) >= req.max_new_tokens:
                        done = True
                        break
                slot[3] = state
                if fresh and cb is not None:
                    cb(list(fresh))
                if fresh and not had[key]:
                    had[key] = True
                    self.ttft_stats.add(now - t)
                if done:
                    done_slots.add(key)
                    stopped = bool(toks) and (
                        toks[-1] == req.eos_id
                        or toks[-1] in (req.stop_ids or ()))
                    self._finished.append(GenerationResult(
                        request_id=req.request_id, tokens=list(toks),
                        finish_reason="stop" if stopped else "length",
                        prompt_tokens=len(req.prompt), ttft_s=now - t,
                        decode_s=now - t, metadata={"fake": True}))
        self.step_stats.add(now - t_step)
        if done_slots:
            self._live = [s for s in self._live if id(s) not in done_slots]
        return len(self._live)

    def generate(self, requests: List[GenerationRequest]) -> List[GenerationResult]:
        """Synchronous batch convenience (and the ``generate`` capability
        marker the worker's ``_engine_for`` checks): submit, step to
        completion, return in request order. Serving paths drive
        submit/step through the pump instead."""
        ids = [self.submit(r) for r in requests]
        want = set(ids)
        done: Dict[str, GenerationResult] = {}
        while want - set(done):
            self.step()
            for res in self.drain_finished():
                done[res.request_id] = res
            if not self._live and not self._waiting and want - set(done):
                for res in self.drain_finished():
                    done[res.request_id] = res
                break
        return [done[i] for i in ids]

    def drain_finished(self) -> List[GenerationResult]:
        out, self._finished = self._finished, []
        return out

    def abort_all(self) -> int:
        n = len(self._live) + len(self._waiting)
        self._live.clear()
        self._waiting.clear()
        return n

    @property
    def n_waiting(self) -> int:
        return len(self._waiting)

    @property
    def n_live(self) -> int:
        return len(self._live)

    def get_metrics(self) -> Dict[str, Any]:
        return {
            "total_requests": self._total_requests,
            "total_prompt_tokens": 0,
            "total_generated_tokens": self._total_generated,
            "waiting": len(self._waiting),
            "live_slots": len(self._live),
            "engine_steps": self._steps,
            "rejected_queue_full": self._rejected_full,
            "shed_deadline": self._shed_deadline,
            "deadline_expired": self._deadline_expired,
            "prefilled_admitted": self._prefilled_admitted,
            "prefix_cached_tokens": self._prefix_cached_tokens,
            "admit_sleep_s": self._admit_sleep_s,
            "fabric_exports": self._fabric_exports,
            "fabric_imports": self._fabric_imports,
            "fabric_imported_tokens": self._fabric_imported_tokens,
            "stream_sub_chunks": self._stream_sub_chunks,
            "ttft": self.ttft_stats.snapshot(),
            "decode_chunk": self.step_stats.snapshot(),
            "spec": {"fake": True, "continuous": True},
        }


@dataclass
class _FakePrefillSpec:
    """The spec slice ``_rpc_prefill_generate``'s size estimate reads."""

    n_layers: int = 1
    n_kv_heads: int = 1
    head_dim: int = 8


class FakePrefillEngine:
    """Prefill-pool fake: ``prefill()`` produces chain-consistent
    ``PrefillHandoff``s with placeholder KV tensors, so the REAL wire
    format, frame packing, size accounting, and decode-side admission all
    run jax-free. ``first_token`` is the crc32 chain's next token for the
    prompt — ``FakeContinuousEngine.submit_prefilled`` continues the chain
    from it, making disaggregated output token-exact vs a single fake.

    Carries the ``spec``/``kv_dtype``/``max_seq_len`` attributes the
    worker's up-front handoff-size estimate reads (64 bytes/token at the
    default shape — small on the wire but nonzero, so bytes/s telemetry
    stays meaningful)."""

    def __init__(self, latency_s: float = 0.0,
                 per_token_latency_s: float = 0.0,
                 max_seq_len: int = 2048, vocab_size: int = 997) -> None:
        self.spec = _FakePrefillSpec()
        self.kv_dtype = np.dtype("float32")
        self.max_seq_len = max(2, int(max_seq_len))
        self.config = FakeEngineConfig()
        self.latency_s = float(latency_s)
        self.per_token_latency_s = float(per_token_latency_s)
        self.vocab_size = max(2, int(vocab_size))
        self.prefill_stats = LatencyStats()
        self._total_requests = 0
        self._total_prompt_tokens = 0
        self._total_handoff_bytes = 0

    def prefill(self, requests: List[GenerationRequest]) -> List[Any]:
        from ..engine.disagg import PrefillHandoff

        t0 = time.perf_counter()
        out = []
        n_tokens = 0
        for r in requests:
            if not r.prompt:
                raise ValueError("empty prompt")
            # tail-truncate overlong prompts like the real engine, so the
            # worker's prompt-length size bound stays an upper bound
            prompt = list(r.prompt)[-(self.max_seq_len - 1):]
            state = 0
            for tok in prompt:
                state = _chain(state, tok)
            first = state % self.vocab_size
            t = len(prompt)
            shape = (self.spec.n_layers, t, self.spec.n_kv_heads,
                     self.spec.head_dim)
            h = PrefillHandoff(
                request_id=r.request_id, prompt_len=t, first_token=first,
                k=np.zeros(shape, self.kv_dtype),
                v=np.zeros(shape, self.kv_dtype))
            self._total_requests += 1
            self._total_prompt_tokens += t
            self._total_handoff_bytes += h.nbytes()
            n_tokens += t
            out.append(h)
        delay = self.latency_s + self.per_token_latency_s * n_tokens
        if delay:
            time.sleep(delay)
        self.prefill_stats.add(time.perf_counter() - t0)
        return out

    def get_metrics(self) -> Dict[str, Any]:
        return {
            "role": "prefill",
            "total_requests": self._total_requests,
            "total_prompt_tokens": self._total_prompt_tokens,
            "total_handoff_bytes": self._total_handoff_bytes,
            "prefill": self.prefill_stats.snapshot(),
            "spec": {"fake": True, "prefill": True},
        }
