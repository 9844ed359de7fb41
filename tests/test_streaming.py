"""Streaming + profiler tests: token chunks ride the framed connection
ahead of the final result (multi-frame responses, ``utils/rpc.py``
``_stream_methods``/``call_stream``), end-to-end through worker and
coordinator; ``profile`` wraps jax.profiler trace capture (SURVEY.md §5
tracing plan)."""

import asyncio
import os

import pytest

from distributed_inference_engine_tpu.api import (
    Coordinator,
    CoordinatorClient,
    CoordinatorConfig,
    CoordinatorServer,
)
from distributed_inference_engine_tpu.config import (
    EngineConfig,
    ModelConfig,
    ServerConfig,
)
from distributed_inference_engine_tpu.cluster.worker import (
    WorkerClient,
    WorkerRPCError,
    WorkerServer,
)
from distributed_inference_engine_tpu.engine.continuous import ContinuousEngine
from distributed_inference_engine_tpu.engine.types import GenerationRequest
from distributed_inference_engine_tpu.models.llama import llama_spec

SPEC = llama_spec("llama-tiny", max_seq_len=64)

pytestmark = pytest.mark.streaming


def _model_cfg(name="m", continuous=True):
    meta = {"size": "llama-tiny", "page_size": 16, "num_pages": 64,
            "attention_impl": "xla", "kv_dtype": "float32",
            "decode_steps_per_call": 3}
    if continuous:
        meta["continuous"] = 1
    return ModelConfig(name=name, architecture="llama", dtype="float32",
                       max_seq_len=64, max_batch_size=4, metadata=meta)


# -------------------------------------------------------------- engine level


def test_engine_stream_callback_matches_result():
    eng = ContinuousEngine(SPEC, config=EngineConfig(
        max_slots=2, max_seq_len=64, page_size=16, num_pages=32,
        decode_steps_per_call=3, attention_impl="xla"))
    chunks = []
    eng.submit(GenerationRequest(prompt=[1, 2, 3], max_new_tokens=10,
                                 temperature=0.0, request_id="s"),
               on_tokens=chunks.append)
    res = eng.run_until_idle()[0]
    streamed = [t for c in chunks for t in c]
    assert streamed == res.tokens
    assert len(chunks) >= 2                     # actually incremental


def test_engine_stream_respects_eos_trim():
    eng = ContinuousEngine(SPEC, config=EngineConfig(
        max_slots=2, max_seq_len=64, page_size=16, num_pages=32,
        decode_steps_per_call=4, attention_impl="xla"))
    probe = eng.generate([GenerationRequest(prompt=[1, 2, 3],
                                            max_new_tokens=10,
                                            temperature=0.0)])[0].tokens
    eos = probe[3]
    chunks = []
    eng.submit(GenerationRequest(prompt=[1, 2, 3], max_new_tokens=10,
                                 temperature=0.0, eos_id=eos),
               on_tokens=chunks.append)
    res = eng.run_until_idle()[0]
    streamed = [t for c in chunks for t in c]
    assert streamed == res.tokens               # no post-EOS leakage
    assert res.finish_reason == "stop"


# -------------------------------------------------------------- worker level


@pytest.mark.asyncio
async def test_worker_generate_stream_roundtrip():
    w = WorkerServer(ServerConfig(worker_id="w", port=0))
    await w.start()
    try:
        await w.load_model_async(_model_cfg())
        c = WorkerClient(*w.address, timeout=120.0)
        chunks = []
        req = GenerationRequest(prompt=[4, 5, 6], max_new_tokens=9,
                                temperature=0.0, request_id="r")
        res = await c.generate_stream("m", req, chunks.append)
        assert [t for ch in chunks for t in ch] == res.tokens
        assert len(res.tokens) == 9
        assert len(chunks) >= 2
        # matches non-streaming output
        plain = await c.generate("m", [GenerationRequest(
            prompt=[4, 5, 6], max_new_tokens=9, temperature=0.0)])
        assert plain[0].tokens == res.tokens
        await c.close()
    finally:
        await w.stop()


@pytest.mark.asyncio
async def test_worker_stream_on_static_engine_is_informative():
    w = WorkerServer(ServerConfig(worker_id="w", port=0))
    await w.start()
    try:
        await w.load_model_async(_model_cfg(continuous=False))
        c = WorkerClient(*w.address, timeout=120.0)
        with pytest.raises(WorkerRPCError, match="continuous"):
            await c.generate_stream(
                "m", GenerationRequest(prompt=[1], max_new_tokens=2),
                lambda t: None)
        # server keeps serving afterwards
        assert (await c.ping())["worker_id"] == "w"
        await c.close()
    finally:
        await w.stop()


# --------------------------------------------------------- coordinator level


@pytest.mark.asyncio
async def test_coordinator_stream_end_to_end():
    coord = Coordinator(CoordinatorConfig())
    server = CoordinatorServer(coord, ServerConfig(port=0))
    await server.start()
    workers = []
    try:
        w = WorkerServer(ServerConfig(worker_id="w0", port=0))
        host, port = await w.start()
        workers.append(w)
        coord.add_worker("w0", host, port)
        await coord.deploy_model(_model_cfg())

        chost, cport = server.address
        client = CoordinatorClient(chost, cport)
        chunks = []
        out = await client.generate_stream(
            "m", chunks.append, prompt=[7, 8, 9], max_new_tokens=8)
        assert [t for c in chunks for t in c] == out["tokens"]
        assert out["streamed"] is True
        assert out["metadata"]["worker_id"] == "w0"
        # plain path still works on the same connection
        plain = await client.generate("m", prompt=[7, 8, 9],
                                      max_new_tokens=8)
        assert plain["tokens"] == out["tokens"]
        await client.close()
    finally:
        await server.stop()
        for w in workers:
            await w.stop()


# ------------------------------------------------------------------ profiler


@pytest.mark.asyncio
async def test_profile_start_stop_cycle(tmp_path):
    w = WorkerServer(ServerConfig(worker_id="w", port=0))
    await w.start()
    try:
        c = WorkerClient(*w.address, timeout=60.0)
        trace_dir = str(tmp_path / "trace")
        out = await c.call("profile", action="start", trace_dir=trace_dir)
        assert out["profiling"] is True
        with pytest.raises(WorkerRPCError, match="already active"):
            await c.call("profile", action="start")
        # do some work under the trace
        await w.load_model_async(_model_cfg())
        await c.generate("m", [GenerationRequest(prompt=[1, 2],
                                                 max_new_tokens=2)])
        out = await c.call("profile", action="stop")
        assert out["trace_dir"] == trace_dir
        assert os.path.isdir(trace_dir)
        with pytest.raises(WorkerRPCError, match="not active"):
            await c.call("profile", action="stop")
        await c.close()
    finally:
        await w.stop()


@pytest.mark.asyncio
async def test_coordinator_stream_fails_over_before_first_chunk():
    """A dead worker at dispatch time must not fail the stream — the
    coordinator retries on an alternate as long as nothing has streamed
    (review finding: streaming lacked the non-streaming path's failover)."""
    coord = Coordinator(CoordinatorConfig())
    await coord.start()
    workers = []
    try:
        for i in range(2):
            w = WorkerServer(ServerConfig(worker_id=f"w{i}", port=0))
            host, port = await w.start()
            workers.append(w)
            coord.add_worker(f"w{i}", host, port)
        await coord.deploy_model(_model_cfg())
        await workers[0].stop()          # kill one replica

        seen = []
        for i in range(3):
            out = await coord.submit_stream(
                "m", prompt=[5, 6, 7 + i], max_new_tokens=4,
                on_tokens=lambda t: seen.extend(t), key=f"k{i}")
            assert len(out["tokens"]) == 4
            assert out["metadata"]["worker_id"] == "w1"
        assert len(seen) == 12
    finally:
        await coord.stop()
        await workers[1].stop()


# --------------- the one sequence: chunk k is read and streamed under k+1


class _Log:
    """What a consumer sees, in the order it sees it: ``("frame", id,
    tokens)`` per stream callback, ``("final", id, result)`` per result
    handed out by ``drain_finished`` after a step."""

    def __init__(self):
        self.events = []

    def cb(self, rid):
        return lambda toks: self.events.append(("frame", rid, list(toks)))

    def drive(self, eng, max_steps=10000, until=None):
        """Step until idle (or until ``until(eng)``), as the pump does."""
        for _ in range(max_steps):
            live = eng.step()
            for res in eng.drain_finished():
                self.events.append(("final", res.request_id, res))
            if until is not None and until(eng):
                return
            if live == 0 and not eng.n_waiting:
                return
        raise AssertionError("engine never went idle")

    def frames(self, rid):
        return [e[2] for e in self.events if e[0] == "frame" and e[1] == rid]

    def streamed(self, rid):
        return [t for f in self.frames(rid) for t in f]

    def result(self, rid):
        return next(e[2] for e in self.events
                    if e[0] == "final" and e[1] == rid)

    def check_order(self, rid):
        """Frames in token order, none twice, the final envelope last."""
        res = self.result(rid)
        assert self.streamed(rid) == res.tokens
        kinds = [e[0] for e in self.events if e[1] == rid]
        assert kinds[-1] == "final" and kinds.count("final") == 1
        assert all(f for f in self.frames(rid))          # no empty frame


def _sreq(rid, n_new, prompt=(1, 2, 3), **kw):
    return GenerationRequest(prompt=list(prompt), max_new_tokens=n_new,
                             temperature=0.0, request_id=rid, **kw)


def _counters(eng):
    m = eng.get_metrics()
    return (m["emit_carried_chunks"], m["emit_flushed_chunks"],
            m["decode_chunks"])


def test_a_chunks_tokens_are_read_and_streamed_under_the_next_chunk():
    """The mechanism itself: when ``step()`` returns with the slot alive,
    the chunk it dispatched is in flight, unread; the next ``step()``
    dispatches another and THEN reads and streams it. A request's first
    frame comes from its prefill's own output, in the step that admits
    it."""
    eng = ContinuousEngine(SPEC, config=_ecfg())
    log = _Log()
    eng.submit(_sreq("a", 14), on_tokens=log.cb("a"))
    eng.step()                      # admission, chunk 1, the first token
    state = next(iter(eng._slots.values()))
    assert len(state.tokens) == 1 and log.streamed("a") == state.tokens
    assert eng._pending is not None and _counters(eng) == (0, 0, 1)
    eng.step()                      # dispatch 2, THEN chunk 1's read + frame
    assert len(state.tokens) == 5 and log.streamed("a") == state.tokens
    assert _counters(eng) == (1, 0, 2)
    spans = [e for e in eng.timeline.events()
             if e["name"] in ("engine.emit.carried", "engine.harvest.wait",
                              "engine.decode.dispatch")]
    # the second bracket opens, the blocking read ends it, the emit
    # follows (a ring record is written when its span closes)
    assert [e["name"] for e in spans[-3:]] == [
        "engine.harvest.wait", "engine.decode.dispatch",
        "engine.emit.carried"]
    wait = next(e for e in reversed(spans)
                if e["name"] == "engine.harvest.wait")
    assert wait["parent"] == "engine.decode.dispatch"
    log.drive(eng)
    log.check_order("a")
    carried, flushed, chunks = _counters(eng)
    # the last chunk's slot was handed on: it streams its own, unhidden
    assert (carried, flushed) == (chunks - 1, 1) and chunks == 4
    m = eng.get_metrics()
    assert m["decode_chunk"]["count"] == chunks
    assert m["harvest_wait_s_total"] == pytest.approx(
        m["decode_chunk"]["sum_s"], rel=1e-6, abs=1e-9)


@pytest.mark.parametrize("news", [(6, 14), (9, 10), (13, 5), (2, 11)])
def test_frames_in_token_order_and_the_final_frame_last(news):
    """Two streams of unequal length (and a third nobody streams): one
    finishes in a chunk the other lives through; per stream the frames splice to the result, the final envelope
    comes last, and streaming changes no token, logprob or reason."""
    reqs = [("a", news[0], (1, 2, 3)), ("b", news[1], (4, 5, 6, 7)),
            ("quiet", 7, (8, 9))]

    def run(stream):
        eng = ContinuousEngine(SPEC, config=_ecfg(max_slots=4))
        log = _Log()
        for rid, n, prompt in reqs:
            eng.submit(_sreq(rid, n, prompt), on_tokens=(
                log.cb(rid) if stream and rid != "quiet" else None))
        log.drive(eng)
        return eng, log

    eng, log = run(True)
    _plain_eng, plain = run(False)
    for rid, n, _p in reqs:
        got, want = log.result(rid), plain.result(rid)
        assert (got.tokens, got.logprobs, got.finish_reason) == (
            want.tokens, want.logprobs, want.finish_reason)
        assert len(got.tokens) == n
    log.check_order("a")
    log.check_order("b")
    assert not log.frames("quiet") and not plain.frames("a")
    carried, flushed, chunks = _counters(eng)
    longest = max(news)
    streamed_chunks = -(-(longest - 1) // 4)     # the quiet one may outlive
    assert carried + flushed == streamed_chunks <= chunks
    assert carried >= 1
    assert _counters(_plain_eng)[:2] == (0, 0)


def test_generate_leaves_nothing_in_flight():
    eng = ContinuousEngine(SPEC, config=_ecfg())
    res = eng.generate([_sreq("g", 12)])
    assert len(res[0].tokens) == 12
    assert eng._pending is None and _counters(eng)[:2] == (0, 0)
    assert not eng._first_reads
    assert not [e for e in eng.timeline.events()
                if e["name"].startswith("engine.emit.")]


def _until_in_flight(eng, log, n_chunks=2):
    """Drive until ``n_chunks`` are dispatched and the last is unread."""
    log.drive(eng, until=lambda e: e._pending is not None
              and e.get_metrics()["decode_chunks"] >= n_chunks)
    assert eng._pending is not None


def test_flush_stream_delivers_the_chunk_in_flight_once():
    eng = ContinuousEngine(SPEC, config=_ecfg())
    log = _Log()
    eng.submit(_sreq("f", 16), on_tokens=log.cb("f"))
    _until_in_flight(eng, log)
    state = next(iter(eng._slots.values()))
    read = list(state.tokens)
    assert log.streamed("f") == read            # what is read is streamed
    eng.flush_stream()                          # chunk 2: a blocking read
    assert len(state.tokens) == len(read) + 4 and eng._pending is None
    assert log.streamed("f") == state.tokens
    eng.flush_stream()                          # nothing left: no frame
    assert log.streamed("f") == state.tokens
    log.drive(eng)
    log.check_order("f")
    carried, flushed, chunks = _counters(eng)
    assert carried + flushed == chunks


def test_abort_all_delivers_the_chunk_in_flight():
    eng = ContinuousEngine(SPEC, config=_ecfg())
    log = _Log()
    eng.submit(_sreq("x", 16), on_tokens=log.cb("x"))
    _until_in_flight(eng, log)
    state = next(iter(eng._slots.values()))
    read = len(state.tokens)
    assert eng.abort_all() == 1
    assert len(state.tokens) == read + 4        # the device's, not dropped
    assert log.streamed("x") == state.tokens    # none lost, none twice
    assert eng._pending is None and not eng._slots
    assert eng.step() == 0 and log.streamed("x") == state.tokens


def test_abort_all_delivers_a_handed_on_slots_last_tokens_and_result():
    """A slot handed on behind the chunk in flight is no live request any
    more: an abort between the hand-on and that chunk's read (a step that
    failed after it) still delivers its last tokens and its result."""
    eng = ContinuousEngine(SPEC, config=_ecfg())
    log = _Log()
    eng.submit(_sreq("h", 6), on_tokens=log.cb("h"))
    _until_in_flight(eng, log)                  # 5 read, the 6th in flight
    eng._hand_on_foreseen()
    assert not eng._slots and eng._pending.handed_on
    assert eng.kv.n_free_slots == eng.max_slots
    assert eng.abort_all() == 0
    for res in eng.drain_finished():
        log.events.append(("final", res.request_id, res))
    log.check_order("h")
    assert len(log.result("h").tokens) == 6
    assert log.result("h").finish_reason == "length"


def test_a_slot_retired_before_its_first_token_was_read_gets_it_first():
    """The pool is dry right after an admission (a prompt of one whole
    page, no page to grow into): the capacity loop retires the slot before
    any decode dispatch, reads its first token from the prefill's output
    there, and the result and the stream carry it."""
    eng = ContinuousEngine(SPEC, config=_ecfg(num_pages=1, max_slots=1))
    log = _Log()
    eng.submit(_sreq("r", 8, prompt=range(1, 17)), on_tokens=log.cb("r"))
    log.drive(eng)
    log.check_order("r")
    res = log.result("r")
    assert len(res.tokens) == 1 and res.finish_reason == "length"
    m = eng.get_metrics()
    assert m["capacity_finishes"] == 1 and m["decode_chunks"] == 0
    assert m["ttft"]["count"] == 1


def test_a_slot_retired_by_the_capacity_loop_streams_first():
    """No dispatch follows for a slot the pool cannot grow: the chunk in
    flight is read first (the sync fallback), its tokens go out before the
    result, from the capacity loop."""
    eng = ContinuousEngine(SPEC, config=_ecfg(num_pages=2, max_slots=2))
    log = _Log()
    eng.submit(_sreq("a", 40, prompt=range(1, 12)), on_tokens=log.cb("a"))
    eng.submit(_sreq("b", 40, prompt=range(20, 31)), on_tokens=log.cb("b"))
    log.drive(eng)
    assert eng.get_metrics()["capacity_finishes"] >= 1
    for rid in "ab":
        log.check_order(rid)
        assert log.result(rid).finish_reason == "length"
    carried, flushed, chunks = _counters(eng)
    assert carried + flushed == chunks and flushed >= 1
    assert eng.get_metrics()["sync_fallback_iterations"] >= 1


@pytest.mark.parametrize("end", [6, 7, 9, 10, 12])
def test_host_stop_sequence_found_a_chunk_late_trims(end):
    """A two-token stop sequence (host-side: the device knows single ids
    only) that ends at token ``end``: inside chunk 2 or 3, found at its
    read with the next chunk already in flight. The stream is the result,
    cut after the sequence, exactly as without streaming."""
    probe = ContinuousEngine(SPEC, config=_ecfg()).generate(
        [_sreq("p", 16)])[0].tokens
    seq = probe[end - 2: end]
    cut = next(i + 2 for i in range(len(probe) - 1)
               if probe[i: i + 2] == seq)

    def run(stream):
        eng = ContinuousEngine(SPEC, config=_ecfg())
        log = _Log()
        eng.submit(_sreq("s", 16, stop_sequences=[seq]),
                   on_tokens=log.cb("s") if stream else None)
        log.drive(eng)
        return log

    log, plain = run(True), run(False)
    res = log.result("s")
    assert res.tokens == probe[:cut] == plain.result("s").tokens
    assert res.finish_reason == plain.result("s").finish_reason == "stop"
    log.check_order("s")


def test_a_stream_runs_through_a_page_boundary():
    """Prompt 8 + two chunks of 4 = one page of 16: the grant is taken a
    row past what the chunks write, so the stream crosses the boundary
    without a pause."""
    eng = ContinuousEngine(SPEC, config=_ecfg(max_seq_len=64))
    log = _Log()
    eng.submit(_sreq("edge", 16, prompt=range(1, 9)),
               on_tokens=log.cb("edge"))
    log.drive(eng)
    log.check_order("edge")
    assert len(log.result("edge").tokens) == 16
    assert eng.get_metrics()["capacity_finishes"] == 0


@pytest.mark.asyncio
async def test_pump_shutdown_delivers_the_chunk_in_flight():
    """The pump stops with a slot alive and a chunk in flight: the engine
    thread reads and streams it before the futures fail."""
    from distributed_inference_engine_tpu.serving.pump import EnginePump

    eng = ContinuousEngine(SPEC, config=_ecfg())
    pump = EnginePump(eng)
    seen = []
    step = eng.step

    def step_then_stop():
        live = step()
        if eng._pending is not None and eng._decode_chunks >= 2:
            seen.append(next(iter(eng._slots.values())))
            pump._stop.set()                    # what shutdown_nowait sets
        return live

    eng.step = step_then_stop
    got = []
    with pytest.raises(RuntimeError, match="shut down"):
        await pump.generate_streaming(_sreq("z", 40), got.extend)
    await pump.stop()
    # 1 + 4 read by the steps, 4 more by the final flush: none lost or twice
    assert len(got) == 9 and got == seen[0].tokens


@pytest.mark.asyncio
async def test_pump_shutdown_resolves_a_handed_on_slot():
    """The pump stops between a hand-on and the read of the chunk the old
    request ends in: the final flush streams its last tokens and resolves
    its future with the result."""
    from distributed_inference_engine_tpu.serving.pump import EnginePump

    eng = ContinuousEngine(SPEC, config=_ecfg())
    pump = EnginePump(eng)
    step = eng.step

    def step_then_stop():
        live = step()
        if eng._pending is not None and eng._decode_chunks >= 2:
            eng._hand_on_foreseen()             # as the next step would
            assert eng._pending.handed_on
            pump._stop.set()
        return live

    eng.step = step_then_stop
    got = []
    res = await pump.generate_streaming(_sreq("z", 6), got.extend)
    await pump.stop()
    assert len(res.tokens) == 6 and got == res.tokens
    assert res.finish_reason == "length"


# ------------------------------------------- sub-chunk streaming (ISSUE 13)


def _ecfg(**over):
    kw = dict(max_slots=2, max_seq_len=64, page_size=16, num_pages=32,
              decode_steps_per_call=4, attention_impl="xla")
    kw.update(over)
    return EngineConfig(**kw)


def test_packed_copy_roundtrip_bit_exact():
    """Each chunk's emitted rows ride the packed output's async
    device->host copy and are read one dispatch later; the streamed
    concatenation must equal the result exactly."""
    eng = ContinuousEngine(SPEC, config=_ecfg())
    chunks = []
    eng.submit(GenerationRequest(prompt=[1, 2, 3], max_new_tokens=12,
                                 temperature=0.0, request_id="ring"),
               on_tokens=chunks.append)
    results = []
    for _ in range(10000):
        live = eng.step()
        results.extend(eng.drain_finished())
        if live == 0 and not eng.n_waiting:
            break
    assert results and results[0].tokens
    streamed = [t for c in chunks for t in c]
    assert streamed == results[0].tokens        # bit-exact copy
    m = eng.get_metrics()
    assert m["decode_chunk"]["count"] == m["decode_chunks"] >= 1
    assert m["harvest_wait_s_total"] >= 0.0


def test_subchunk_greedy_parity_with_packed_harvest():
    """Greedy decode is chunking-invariant: 1-step sub-chunks must yield
    token-for-token the same output as the full 4-step megastep, and the
    streamed frames must splice to exactly that."""

    def run(scs, stream):
        eng = ContinuousEngine(SPEC, config=_ecfg(stream_chunk_steps=scs))
        chunks = []
        eng.submit(GenerationRequest(prompt=[1, 2, 3], max_new_tokens=14,
                                     temperature=0.0, request_id="g"),
                   on_tokens=chunks.append if stream else None)
        res = eng.run_until_idle()[0]
        return res.tokens, [t for c in chunks for t in c]

    ref, _ = run(0, stream=False)           # packed-harvest batch path
    sub, streamed = run(1, stream=True)     # 1-step sub-chunks
    assert sub == ref
    assert streamed == sub


def test_subchunk_stream_trims_stops_identically():
    """A stop hit inside a sub-chunk must trim the stream exactly like the
    packed path: stop token included, nothing after it leaks (greedy and
    sampled-with-min_p=1.0, which pins sampling to the argmax)."""
    probe = ContinuousEngine(SPEC, config=_ecfg()).generate(
        [GenerationRequest(prompt=[1, 2, 3], max_new_tokens=12,
                           temperature=0.0)])[0].tokens
    stop = probe[5]
    cut = probe.index(stop) + 1             # first occurrence, inclusive
    for temp, min_p in ((0.0, 0.0), (0.8, 1.0)):
        eng = ContinuousEngine(SPEC, config=_ecfg(stream_chunk_steps=1),
                               seed=0)
        chunks = []
        eng.submit(GenerationRequest(prompt=[1, 2, 3], max_new_tokens=12,
                                     temperature=temp, min_p=min_p,
                                     stop_ids=[stop]),
                   on_tokens=chunks.append)
        res = eng.run_until_idle()[0]
        assert res.tokens == probe[:cut]
        assert res.finish_reason == "stop"
        streamed = [t for c in chunks for t in c]
        assert streamed == res.tokens       # no post-stop leakage


def _programs(eng):
    """Program-shape keys of the dispatches in the engine's step ring,
    which must not have forgotten any."""
    assert eng.timeline.to_chrome_trace()["metadata"]["dropped_events"] == 0
    return {e["args"]["program"] for e in eng.timeline.events()
            if "program" in e["args"]}


def test_adaptive_chunk_compile_count_guard():
    """The streaming clamp is pow2-bucketed: a mixed streaming+batch run
    adds at most ONE new decode chunk length beyond the configured
    megastep, and pure-batch slots keep the full chunk."""
    eng = ContinuousEngine(SPEC, config=_ecfg(max_slots=4,
                                              stream_chunk_steps=1))
    # pure-batch wave first: full 4-step decode program only
    eng.generate([GenerationRequest(prompt=[1, 2], max_new_tokens=8,
                                    temperature=0.0)])
    batch_steps = {p[1] for p in _programs(eng) if p[0] == "decode"}
    assert batch_steps == {4}
    assert eng.get_metrics()["stream_clamped_chunks"] == 0
    # streaming + batch mix: clamp engages, ONE extra length appears
    chunks = []
    eng.submit(GenerationRequest(prompt=[1, 2, 3], max_new_tokens=8,
                                 temperature=0.0), on_tokens=chunks.append)
    eng.submit(GenerationRequest(prompt=[4, 5], max_new_tokens=8,
                                 temperature=0.0))
    eng.run_until_idle()
    decode_steps = {p[1] for p in _programs(eng) if p[0] == "decode"}
    assert decode_steps == {4, 1}, \
        "clamp must add exactly one pow2 decode length"
    assert eng.get_metrics()["stream_clamped_chunks"] >= 1
    assert [t for c in chunks for t in c]


def test_one_first_token_read_a_prefill_dispatch():
    """A whole admission round shares ONE read of its prefill's output
    (``engine.first_tokens`` spans say how many rows each read carried),
    never one a slot."""
    eng = ContinuousEngine(SPEC, config=_ecfg(max_slots=4))
    reqs = [GenerationRequest(prompt=[1 + i, 2, 3], max_new_tokens=6,
                              temperature=0.0) for i in range(3)]
    res = eng.generate(reqs)
    assert all(len(r.tokens) == 6 for r in res)
    reads = [e for e in eng.timeline.events()
             if e["name"] == "engine.first_tokens"]
    assert [e["args"]["rows"] for e in reads] == [3]
    assert eng.get_metrics()["prefill"]["count"] == 1


@pytest.mark.asyncio
async def test_midstream_kill_resumes_subchunk_through_fabric():
    """Sub-chunk frames + mid-stream kill: the resume must replay from the
    ring's high-water mark — token-exact, no duplicate or missing frame —
    through the prefix-affinity/KV-fabric path, and the coordinator ITL
    histogram must have observed the sub-chunk gaps."""
    from distributed_inference_engine_tpu.models.fake import _chain

    def expected(prompt, n, vocab=997):
        st = 0
        for t in prompt:
            st = _chain(st, t)
        out = []
        for _ in range(n):
            nxt = st % vocab
            st = _chain(st, nxt)
            out.append(nxt)
        return out

    coord = Coordinator(CoordinatorConfig(
        lb_strategy="prefix_affinity", affinity_page_size=4,
        affinity_pages=2, retry_seed=7, retry_backoff_base_s=0.01,
        fabric_snapshot_delay_s=0.0))
    await coord.start()
    meta = {"continuous": 1, "max_slots": 4, "prefix_cache": 1,
            "prefix_page_size": 4, "step_latency_s": 0.02,
            "tokens_per_step": 4, "stream_chunk_tokens": 1}
    cfg = ModelConfig(name="m", architecture="fake", metadata=meta)
    workers = {}
    try:
        for i in range(2):
            w = WorkerServer(ServerConfig(host="127.0.0.1", port=0,
                                          worker_id=f"w{i}"))
            host, port = await w.start()
            workers[f"w{i}"] = w
            coord.add_worker(f"w{i}", host, port)
        await coord.deploy_model(cfg)

        got, killed = [], []

        def on_tokens(toks):
            got.append(list(toks))
            if len(got) == 5 and not killed:
                for wid, w in workers.items():
                    if w._request_count:
                        killed.append(wid)
                        asyncio.ensure_future(w.stop())

        prompt = [5, 6, 7, 8]
        r = await coord.submit_stream("m", prompt=prompt, max_new_tokens=24,
                                      on_tokens=on_tokens)
        exp = expected(prompt, 24)
        flat = [t for c in got for t in c]
        assert killed, "the serving worker must have been killed mid-stream"
        assert flat == exp, "replay must start at the ring high-water mark"
        assert r["tokens"] == exp
        assert r["metadata"].get("stream_resumed")
        st = coord.get_stats()
        assert st["stream_resumes"] == 1
        assert st["stream_frames"] >= len(got)
        assert st["stream_itl"]["count"] >= 1
        assert st["stream_emit_lag"]
    finally:
        await coord.stop()
        for w in workers.values():
            try:
                await w.stop()
            except Exception:
                pass


@pytest.mark.asyncio
async def test_client_disconnect_mid_stream_keeps_server_alive():
    """A client hanging up mid-stream is routine (aborted generation) —
    the worker must log-and-continue, not die or count an engine error."""
    import asyncio as aio

    from distributed_inference_engine_tpu.utils.framing import (
        read_frame,
        write_frame,
    )

    w = WorkerServer(ServerConfig(worker_id="w", port=0))
    await w.start()
    try:
        await w.load_model_async(_model_cfg())
        host, port = w.address
        reader, writer = await aio.open_connection(host, port)
        await write_frame(writer, {
            "method": "generate_stream", "id": "x", "model": "m",
            "request": {"prompt": [1, 2, 3], "max_new_tokens": 40,
                        "temperature": 0.0},
        })
        # read one chunk frame, then slam the connection shut
        frame = await read_frame(reader)
        assert frame.get("stream") is True
        writer.close()
        # the server must still answer new connections and requests
        await aio.sleep(0.5)
        c = WorkerClient(host, port, timeout=120.0)
        out = await c.generate("m", [GenerationRequest(
            prompt=[1, 2], max_new_tokens=3)])
        assert len(out[0].tokens) == 3
        await c.close()
    finally:
        await w.stop()
