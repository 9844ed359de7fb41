"""Where a request waits and what the engine thread does (ISSUE 24): the
request marks that tile a stream's time to its first frame, the pool /
inbox / queue waits and their gauges, the engine thread's host spans in the
step ring, and the compile flag that follows the compiler's own counter."""

import asyncio
import os
import subprocess
import sys
import threading
import time

import jax
import jax.monitoring
import numpy as np
import pytest

from distributed_inference_engine_tpu.api.coordinator import (
    Coordinator,
    CoordinatorConfig,
)
from distributed_inference_engine_tpu.cluster.worker import WorkerServer
from distributed_inference_engine_tpu.config import ModelConfig, ServerConfig
from distributed_inference_engine_tpu.engine.continuous import ContinuousEngine
from distributed_inference_engine_tpu.engine.types import GenerationRequest
from distributed_inference_engine_tpu.obs.timeline import (
    StepTimeline,
    busy_gap_split,
    clock_anchor,
    host_span,
)
from distributed_inference_engine_tpu.serving.pump import EnginePump
from distributed_inference_engine_tpu.utils import compile_cache
from distributed_inference_engine_tpu.utils.compile_cache import (
    compile_counters,
)
from tests.test_continuous import SPEC, _cfg

pytestmark = pytest.mark.obs

STREAM_MARKS = ["received", "routed", "dispatched", "conn_acquired",
                "worker.received", "worker.submitted", "worker.admitted",
                "worker.first_token", "worker.first_frame_sent",
                "first_frame", "worker.done", "done"]


async def _fleet(model: ModelConfig):
    coord = Coordinator(CoordinatorConfig())
    await coord.start()
    w = WorkerServer(ServerConfig(worker_id="w0", host="127.0.0.1", port=0))
    host, port = await w.start()
    coord.add_worker("w0", host, port)
    await coord.deploy_model(model)
    return coord, w


def _tiny_llama() -> ModelConfig:
    return ModelConfig(
        name="m", architecture="llama", dtype="float32", max_seq_len=64,
        max_batch_size=4,
        metadata={"size": "llama-tiny", "page_size": 16, "num_pages": 64,
                  "attention_impl": "xla", "kv_dtype": "float32",
                  "decode_steps_per_call": 3, "continuous": 1})


def _fake(step_latency_s: float) -> ModelConfig:
    return ModelConfig(name="m", architecture="fake", metadata={
        "continuous": 1, "max_slots": 4, "step_latency_s": step_latency_s})


# ------------------------------------------------------ request-path marks


async def test_stream_marks_tile_the_time_to_first_frame():
    coord, w = await _fleet(_tiny_llama())
    try:
        out = await coord.submit_stream(
            "m", prompt=[5, 6, 7], on_tokens=lambda t: None,
            max_new_tokens=5, request_id="tile-1")
        tr = out["trace"]
        for phase in STREAM_MARKS:
            assert phase in tr, phase
        # every mark in order, up to first_frame (worker.done and done
        # follow the stream's end)
        chain = [tr[p] for p in STREAM_MARKS[:10]]
        assert chain == sorted(chain), dict(zip(STREAM_MARKS, chain))
        assert tr["worker.first_frame_sent"] <= tr["worker.done"] <= tr["done"]
        # worker marks are anchored where the coordinator had a connection
        assert tr["worker.received"] == pytest.approx(tr["conn_acquired"])
        # the five spans account for received -> first_frame up to routing
        # (before dispatched) and transit (after first_frame_sent)
        spans = (
            (tr["conn_acquired"] - tr["dispatched"])                 # pool
            + (tr["worker.submitted"] - tr["worker.received"])       # inbox
            + (tr["worker.admitted"] - tr["worker.submitted"])       # queue
            + (tr["worker.first_token"] - tr["worker.admitted"])     # prefill
            + (tr["worker.first_frame_sent"] - tr["worker.first_token"]))
        outside = tr["first_frame"] - tr["received"] - spans
        assert 0.0 <= outside < 0.05, (outside, tr)
        # the same waits, as the components' own histograms and gauges
        stats = coord.get_stats()
        assert stats["pool_wait"]["count"] == 1
        assert stats["streams_in_flight"] == 0
        assert stats["pool_waiting"] == 0 and stats["pool_in_use"] == 0
        pump = w._pumps["m"].get_stats()
        assert pump["inbox_wait"]["count"] >= 1
        assert pump["engine"]["queue_wait"]["count"] >= 1
    finally:
        await coord.stop()
        await w.stop()


async def test_inbox_wait_is_not_charged_to_the_pool():
    """A request that arrives while the engine thread is inside a step
    waits in the pump's inbox: ``inbox_wait`` shows it, and the
    coordinator's ``dispatched -> conn_acquired`` does not."""
    step_s = 0.08
    coord, w = await _fleet(_fake(step_s))
    try:
        first_seen = asyncio.Event()
        a = asyncio.ensure_future(coord.submit_stream(
            "m", prompt=[1, 2, 3], on_tokens=lambda t: first_seen.set(),
            max_new_tokens=6, request_id="a"))
        await first_seen.wait()              # the engine thread is stepping
        await asyncio.sleep(step_s / 4)
        b = await coord.submit_stream(
            "m", prompt=[4, 5, 6], on_tokens=lambda t: None,
            max_new_tokens=2, request_id="b")
        await a
        tr = b["trace"]
        assert tr["conn_acquired"] - tr["dispatched"] < step_s / 4
        inbox = w._pumps["m"].get_stats()["inbox_wait"]
        assert inbox["count"] == 2
        assert inbox["p99_s"] > step_s / 4   # b waited out a's step
    finally:
        await coord.stop()
        await w.stop()


async def test_pool_of_one_makes_two_streams_wait():
    step_s = 0.05
    coord, w = await _fleet(_fake(step_s))
    try:
        for client in (coord.router.client_for("w0"),
                       coord.lb.client_for("w0")):
            client.max_connections = 1
        streams = [asyncio.ensure_future(coord.submit_stream(
            "m", prompt=[i + 1, 2, 3], on_tokens=lambda t: None,
            max_new_tokens=3, request_id=f"s{i}")) for i in range(3)]
        await asyncio.sleep(step_s)          # the first is being served
        stats = coord.get_stats()
        assert stats["streams_in_flight"] == 3
        assert stats["pool_in_use"] == 1
        assert stats["pool_waiting"] == 2
        outs = await asyncio.gather(*streams)
        waits = sorted(o["trace"]["conn_acquired"] - o["trace"]["dispatched"]
                       for o in outs)
        assert waits[0] < step_s and waits[1] > step_s and waits[2] > step_s
        stats = coord.get_stats()
        assert stats["pool_wait"]["count"] == 3
        assert stats["pool_waiting"] == 0 and stats["streams_in_flight"] == 0
    finally:
        await coord.stop()
        await w.stop()


# ------------------------------------------------------ engine-thread spans


def _req(i: int, n_new: int = 6) -> GenerationRequest:
    rs = np.random.RandomState(i)
    return GenerationRequest(
        prompt=rs.randint(1, SPEC.vocab_size, size=8).tolist(),
        max_new_tokens=n_new, temperature=0.0, request_id=f"caller-{i}")


async def test_spans_carry_the_callers_request_id():
    engine = ContinuousEngine(SPEC, config=_cfg(max_slots=4), seed=0)
    pump = EnginePump(engine)
    try:
        outs = await asyncio.gather(*(pump.generate([_req(i)])
                                      for i in range(3)))
        # the same id twice at once: both served, both returned under it
        twins = await asyncio.gather(pump.generate([_req(7)]),
                                     pump.generate([_req(7)]))
        assert [r[0].request_id for r in twins] == ["caller-7", "caller-7"]
        assert twins[0][0].tokens == twins[1][0].tokens
    finally:
        await pump.stop()
    assert sorted(r[0].request_id for r in outs) == [
        "caller-0", "caller-1", "caller-2"]
    admits = [e for e in engine.timeline.events()
              if e["name"] == "engine.admit"]
    ids = ";".join(e["args"]["request_ids"] for e in admits).split(";")
    assert {"caller-0", "caller-1", "caller-2", "caller-7"} <= set(ids)
    # the duplicate's suffix holds neither of the characters the profiler's
    # ``#k=v,k=v#`` encoding of a span's args uses
    assert sorted(i for i in ids if i.startswith("caller-7")) == [
        "caller-7", "caller-7~1"], ids
    names = {e["name"] for e in engine.timeline.events()}
    assert {"pump.drain_inbox", "pump.resolve", "engine.admit",
            "engine.prefill.dispatch", "engine.decode.dispatch",
            "engine.harvest.wait", "engine.harvest.book",
            "engine.process_packed"} <= names
    # a result hands the engine's stamps up, in order
    st = outs[0][0].stamps
    assert st["submitted"] <= st["admitted"] <= st["first_token"]


@pytest.mark.parametrize("slots,steps,n_new", [(2, 2, 8), (8, 8, 24)])
def test_a_decode_chunk_adds_a_bounded_number_of_records(slots, steps, n_new):
    engine = ContinuousEngine(
        SPEC, config=_cfg(max_slots=slots, decode_steps_per_call=steps),
        seed=0)
    engine.generate([_req(i, n_new) for i in range(slots)])
    events = engine.timeline.events()
    chunks = [e for e in events if e["name"] == "engine.decode.dispatch"]
    assert len(chunks) >= 2
    # per chunk: its bracket, the blocking read, then the token half and
    # the judgments, siblings under the step (nobody streams here: no
    # engine.emit.* span). Chunk k is read inside the bracket of chunk
    # k+1, which the read ends; the last chunk's read lies in a step that
    # dispatches nothing
    by_name = {n: [e for e in events if e["name"] == n] for n in (
        "engine.harvest.wait", "engine.harvest.book",
        "engine.process_packed")}
    assert all(len(v) == len(chunks) for v in by_name.values())
    assert [e["parent"] for e in by_name["engine.harvest.wait"]] == (
        ["engine.decode.dispatch"] * (len(chunks) - 1) + ["engine.step"])
    assert all(e["parent"] == "engine.step"
               for n in ("engine.harvest.book", "engine.process_packed")
               for e in by_name[n])
    # a bracket ends with the read in it: the token half starts after it
    for c, w, b in zip(chunks[1:], by_name["engine.harvest.wait"],
                       by_name["engine.harvest.book"]):
        assert w["t"] + w["dur"] <= c["t"] + c["dur"] <= b["t"]
    # one read of first tokens an admission round, after its decode dispatch
    firsts = [e for e in events if e["name"] == "engine.first_tokens"]
    assert [e["args"]["rows"] for e in firsts] == [slots]
    assert firsts[0]["t"] >= chunks[0]["t"]
    admits = [e for e in events if e["name"] in (
        "engine.admit", "engine.prefill.dispatch")]
    steps = [e for e in events if e["name"] == "engine.step"]
    assert len(chunks) <= len(steps) <= len(chunks) + 1   # one per step()
    assert all(e["parent"] == "engine.step" for e in chunks)
    # (a chunk that revives paused rows flips their active flags under a
    # span of its own, outside the brackets)
    flips = [e for e in events if e["name"] == "engine.set_active"]
    assert all(e["parent"] == "engine.process_packed" for e in flips)
    # (each read of first tokens holds its blocking read as a span of its
    # own, one a prefill dispatch)
    waits = [e for e in events if e["name"] == "engine.first_tokens.wait"]
    assert len(events) == (4 * len(chunks) + len(admits) + len(steps)
                           + len(flips) + len(firsts) + len(waits))
    # only the dispatch brackets count as busy time
    split = busy_gap_split(events)
    assert split["n_events"] == sum(1 for e in events if e["dispatch"])


@pytest.mark.parametrize("slots,rounds", [(2, 1), (2, 3), (4, 2)])
def test_a_prefill_read_waits_under_a_span_of_its_own(slots, rounds):
    """``engine.first_tokens.wait`` holds the blocking read of one prefill's
    first tokens and nothing else: opened once a prefill dispatch, inside
    ``engine.first_tokens`` (which keeps its name and its ``rows``), and
    closed before the bookkeeping, whose first act a streamed request sees
    is its first token."""
    engine = ContinuousEngine(SPEC, config=_cfg(max_slots=slots), seed=0)
    seen = {}
    for r in range(rounds):
        for i in range(slots):
            req = _req(10 * r + i, 3)
            engine.submit(req, on_tokens=lambda toks, rid=req.request_id:
                          seen.setdefault(rid, time.perf_counter()))
        engine.run_until_idle()
    events = engine.timeline.events()
    firsts = [e for e in events if e["name"] == "engine.first_tokens"]
    waits = [e for e in events if e["name"] == "engine.first_tokens.wait"]
    prefills = [e for e in events if e["name"] == "engine.prefill.dispatch"]
    assert len(waits) == len(prefills) >= rounds
    assert sum(e["args"]["rows"] for e in firsts) == slots * rounds
    assert all(w["parent"] == "engine.first_tokens" for w in waits)
    for w in waits:
        (f,) = [f for f in firsts
                if f["t"] <= w["t"] and w["t"] + w["dur"] <= f["t"] + f["dur"]]
        # the bookkeeping follows the wait, inside the outer span
        assert w["dur"] < f["dur"]
    # every request's first token left after some read had ended
    assert len(seen) == slots * rounds
    first_read_end = min(w["t"] + w["dur"] for w in waits)
    assert all(t >= first_read_end for t in seen.values())


def test_a_familys_prefill_counters_are_read_under_a_wait_span():
    """A per-layer family reads each prefill's counters in the harvest of the
    chunk dispatched behind it: that blocking read is
    ``engine.prefill_counters.wait``, once a prefill dispatch, inside
    ``engine.harvest.book``, so that a reader tells the wait for a prefill
    from the booking around it."""
    from distributed_inference_engine_tpu.config import EngineConfig
    from distributed_inference_engine_tpu.models import ling_spec

    spec = ling_spec("ling-tiny")
    engine = ContinuousEngine(spec, config=EngineConfig(
        max_slots=4, max_seq_len=128, page_size=16, num_pages=40,
        prefill_buckets=[32, 64], decode_steps_per_call=8), seed=0)
    rs = np.random.RandomState(0)
    for r in range(2):
        for i in range(2):
            engine.submit(GenerationRequest(
                prompt=rs.randint(1, spec.vocab_size, size=8).tolist(),
                max_new_tokens=12, temperature=0.0, request_id=f"r{r}-{i}"))
        engine.run_until_idle()
    events = engine.timeline.events()
    waits = [e for e in events
             if e["name"] == "engine.prefill_counters.wait"]
    prefills = [e for e in events if e["name"] == "engine.prefill.dispatch"]
    books = [e for e in events if e["name"] == "engine.harvest.book"]
    assert len(waits) == len(prefills) >= 2
    assert all(w["parent"] == "engine.harvest.book" for w in waits)
    for w in waits:
        assert any(b["t"] <= w["t"] and w["t"] + w["dur"] <= b["t"] + b["dur"]
                   for b in books)
    assert engine.get_metrics()["moe"]["assignments_total"] > 0


def test_compile_flag_follows_the_compilers_counter():
    engine = ContinuousEngine(SPEC, config=_cfg(max_slots=2), seed=0)
    engine.generate([_req(0)])
    before = compile_counters()["backend_compiles"]
    first = [e for e in engine.timeline.events() if e["dispatch"]]
    assert before > 0 and first[0]["args"].get("compile") is True
    mark = len(engine.timeline.events())
    engine.generate([_req(1)])               # same shapes: nothing compiles
    assert compile_counters()["backend_compiles"] == before
    again = [e for e in engine.timeline.events()[mark:] if e["dispatch"]]
    assert again and not any(e["args"].get("compile") for e in again)
    # the compiler reports a compile while a chunk is in flight: that
    # chunk's record, and no other, carries the flag
    fired = []

    def hook():
        if not fired:
            fired.append(1)
            jax.monitoring.record_event_duration_secs(
                "/jax/core/compile/backend_compile_duration", 0.25)

    engine.overlap_hook = hook
    mark = len(engine.timeline.events())
    engine.generate([_req(2)])
    flagged = [e["name"] for e in engine.timeline.events()[mark:]
               if e["args"].get("compile")]
    assert flagged == ["engine.decode.dispatch"]
    after = compile_counters()
    assert after["backend_compiles"] == before + 1
    assert after["backend_compile_s"] >= 0.25


def test_host_span_nests_and_survives_a_disabled_ring():
    tl = StepTimeline(capacity=8)
    with host_span(tl, "outer", rows=2):
        inner = host_span(tl, "inner", dispatch=True, steps=4)
        inner.close(steps=5, compile=True)   # close() args win
    host_span(None, "ringless", a=1).close()   # the annotation alone
    inner_ev, outer_ev = tl.events()
    assert (inner_ev["name"], inner_ev["parent"]) == ("inner", "outer")
    assert inner_ev["args"] == {"steps": 5, "compile": True}
    assert inner_ev["dispatch"] and not outer_ev["dispatch"]
    assert outer_ev["parent"] is None and outer_ev["args"] == {"rows": 2}
    assert outer_ev["t"] <= inner_ev["t"]
    assert outer_ev["dur"] >= inner_ev["dur"]
    anchor = clock_anchor("start")
    tl.add_anchor(anchor)
    meta = tl.to_chrome_trace()["metadata"]
    assert meta["clock_anchors"] == [anchor]
    assert anchor["at"] == "start" and anchor["perf_counter_ns"] > 0
    assert meta["epoch_perf_counter_ns"] <= anchor["perf_counter_ns"]


def test_annotation_values_are_cleaned_of_the_encodings_characters(
        monkeypatch):
    """A caller's request id may hold ``,`` or ``#``: the annotation gets
    it cleaned, the ring record as given."""
    from distributed_inference_engine_tpu.obs import timeline

    seen = []

    class Annotation:
        def __init__(self, name, **kw):
            seen.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(timeline, "_annotation_cls", Annotation)
    tl = StepTimeline(capacity=4)
    host_span(tl, "engine.admit", rows=2, request_ids="a,b#1;c").close()
    assert seen == [("engine.admit", {"rows": 2, "request_ids": "a;b~1;c"})]
    assert tl.events()[0]["args"]["request_ids"] == "a,b#1;c"


def test_obs_imports_and_opens_spans_without_jax():
    code = (
        "import sys\n"
        "from distributed_inference_engine_tpu.obs import timeline\n"
        "tl = timeline.StepTimeline(capacity=4)\n"
        "with timeline.host_span(tl, 'pump.idle_wait'):\n"
        "    pass\n"
        "timeline.clock_anchor('start')\n"
        "assert [e['name'] for e in tl.events()] == ['pump.idle_wait']\n"
        "assert 'jax' not in sys.modules, 'obs imported jax'\n")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert res.returncode == 0, res.stderr


# ------------------------------------------------------------ profile RPC


async def test_profile_rpc_anchors_the_ring_to_the_trace(tmp_path):
    from distributed_inference_engine_tpu.cluster.worker import WorkerClient

    w = WorkerServer(ServerConfig(worker_id="wp", host="127.0.0.1", port=0))
    host, port = await w.start()
    client = WorkerClient(host, port)
    try:
        await client.load_model(_tiny_llama())
        trace_dir = str(tmp_path / "trace")
        started = await client.call("profile", action="start",
                                    trace_dir=trace_dir, python_tracer=False)
        assert started["python_tracer"] is False
        chunks = []
        res = await client.generate_stream(
            "m", GenerationRequest(prompt=[5, 6, 7], max_new_tokens=5,
                                   request_id="prof-1"), chunks.append)
        assert [t for c in chunks for t in c] == res.tokens
        stopped = await client.call("profile", action="stop", timeout=120.0)
        (dump,) = stopped["step_timelines"]
    finally:
        await client.close()
        await w.stop()
    import json

    with open(dump) as f:
        doc = json.load(f)
    anchors = doc["metadata"]["clock_anchors"]
    assert [a["at"] for a in anchors] == ["start", "stop"]
    assert anchors[0]["perf_counter_ns"] < anchors[1]["perf_counter_ns"]
    ring = {e["name"] for e in doc["traceEvents"]}
    assert {"engine.admit", "engine.decode.dispatch",
            "engine.harvest.wait"} <= ring
    # the engine's counters as they stood at the slice's two ends
    with open(f"{trace_dir}/counters.json") as f:
        stamped = json.load(f)
    steps = [stamped[at]["models"]["m"]["decode_steps"]
             for at in ("start", "stop")]
    assert steps[0] == 0 and steps[1] >= 4
    # the same spans and anchors are in the profiler's trace, with no
    # Python-tracer event beside them
    from jax.profiler import ProfileData
    import glob

    (pb,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    host_events = [(ev.name, dict(ev.stats))
                   for plane in ProfileData.from_file(pb).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events]
    names = {n for n, _s in host_events}
    assert {"clock.anchor", "engine.admit", "engine.decode.dispatch",
            "engine.harvest.wait", "pump.drain_inbox"} <= names
    assert not any(n.startswith("$") for n in names)
    in_trace = sorted(s["perf_counter_ns"] for n, s in host_events
                      if n == "clock.anchor")
    assert in_trace == [a["perf_counter_ns"] for a in anchors]
    admit = next(s for n, s in host_events if n == "engine.admit")
    assert admit["request_ids"] == "prof-1"


async def test_profile_stop_writes_the_xplane_alone(tmp_path):
    """``profile stop`` leaves the ``.xplane.pb`` every reader loads and not
    the legacy ``trace.json.gz`` that ``jax.profiler.stop_trace`` converts
    it to (most of a long export's time; nothing reads it)."""
    from distributed_inference_engine_tpu.cluster.worker import WorkerClient

    w = WorkerServer(ServerConfig(worker_id="wr", host="127.0.0.1", port=0))
    host, port = await w.start()
    client = WorkerClient(host, port)
    try:
        started = await client.call("profile", action="start",
                                    trace_dir=str(tmp_path))
        assert started["python_tracer"] is True      # the default, as ever
        await client.call("profile", action="stop", timeout=120.0)
    finally:
        await client.close()
        await w.stop()
    written = [f for _d, _s, fs in os.walk(tmp_path) for f in fs]
    assert [f for f in written if f.endswith(".xplane.pb")]
    assert not [f for f in written if f.endswith(".json.gz")]


def _program_texts(family, spec):
    """The lowered text (with op paths) of a per-layer family's prefill and
    decode-step bodies at a tiny size."""
    import jax
    import jax.numpy as jnp

    params = jax.eval_shape(
        lambda: family.init_params(spec, jax.random.key(0)))
    state = family.init_state(spec, 2)
    lm, w = spec.paged_layers, spec.cache_row_width
    pages = jnp.zeros((lm, 4, 16, w), jnp.bfloat16)
    prefill = jax.jit(
        lambda p, t, n, pg, st, tb, sl: family.forward_prefill_into_pages(
            spec, p, t, n, pg, st, tb, sl)).lower(
        params, jnp.zeros((2, 16), jnp.int32), jnp.ones((2,), jnp.int32),
        pages, state, jnp.zeros((2, 4), jnp.int32),
        jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)
    decode = jax.jit(
        lambda p, t, n, s0, pg, tb, sd, st, ac: family.forward_decode_step(
            spec, p, t, n, s0, family.decode_context(pg, tb, "xla"), sd, st,
            ac)).lower(
        params, jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.int32),
        jnp.ones((2,), jnp.int32), pages, jnp.zeros((2, 4), jnp.int32),
        jnp.zeros((lm, 2, 4, w), jnp.bfloat16), state,
        jnp.ones((2,), bool)).as_text(debug_info=True)
    return prefill, decode


def test_the_residual_scope_is_on_the_mhc_familys_programs_alone():
    """``resid.mhc`` (``perfbench/lib/scopes_mhc.py`` reads it) names ops
    of BOTH programs of the mHC family and of neither of the hybrid
    family's; the scopes the two share keep their names in both."""
    from distributed_inference_engine_tpu.models import ling, xing

    for text in _program_texts(xing, xing.xing_spec("xing-tiny",
                                                    max_seq_len=64)):
        assert "/resid.mhc/" in text
        assert "/attn.mla/" in text and "/moe.experts/" in text
        assert "/attn.kda" not in text and "/state.update/" not in text
    for text in _program_texts(ling, ling.ling_spec("ling-tiny",
                                                    max_seq_len=64)):
        assert "resid.mhc" not in text
        assert "/attn.mla/" in text and "/moe.experts/" in text
        assert "/attn.kda" in text


# ------------------------------------------------ set-up seen from inside


TRACE, LOWER, BACKEND, RETRIEVAL, SAVED = compile_cache.DURATION_EVENTS
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
ASKED = "/jax/compilation_cache/compile_requests_use_cache"


def test_the_five_duration_events_exist_and_carry_the_programs_name():
    """The listener's event names against the installed jax's own, and
    ``fun_name`` as the keyword its timing context sends."""
    import inspect

    from jax._src import compiler, dispatch

    assert (dispatch.JAXPR_TRACE_EVENT, dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
            dispatch.BACKEND_COMPILE_EVENT) == (TRACE, LOWER, BACKEND)
    assert "fun_name=self.fun_name" in inspect.getsource(
        dispatch.LogElapsedTimeContextManager.__exit__)
    # the cache's read is timed INSIDE compile_or_get_cached, which runs
    # under the backend-compile event: a part of it, not a fourth addend
    src = inspect.getsource(compiler.compile_or_get_cached)
    for event in (RETRIEVAL, SAVED, HIT, ASKED):
        assert event in src, event


def test_the_listener_keeps_name_phase_seconds_and_the_caches_answer():
    on_duration, on_event = (compile_cache._on_duration,
                             compile_cache._on_event)
    before = compile_counters()
    mark = compile_cache.log_index()
    on_duration(TRACE, 0.010, fun_name="step")
    on_duration(LOWER, 0.020, fun_name="jit(step)")
    on_event(ASKED)
    on_event(HIT)
    on_duration(SAVED, 3.0)
    on_duration(RETRIEVAL, 0.004)
    on_duration(BACKEND, 0.005, fun_name="jit(step)")
    on_event(ASKED)
    on_event(MISS)
    on_duration(BACKEND, 0.5, fun_name="jit(other)")
    on_event(ASKED)                       # under the cache's thresholds
    on_duration(BACKEND, 0.001)           # and jax sent no name
    on_duration(BACKEND, 0.002, fun_name="jit(uncached)")
    on_duration("/jax/some/other_duration", 9.0, fun_name="x")
    records, nxt = compile_cache.compile_log(mark)
    assert nxt == mark + 6 == compile_cache.log_index()
    assert [(r["fun_name"], r["phase"], r["dur_s"]) for r in records] == [
        ("step", "trace", 0.010), ("jit(step)", "lower", 0.020),
        ("jit(step)", "backend_compile", 0.005),
        ("jit(other)", "backend_compile", 0.5),
        ("?", "backend_compile", 0.001),
        ("jit(uncached)", "backend_compile", 0.002)]
    assert [r.get("cache") for r in records] == [
        None, None, "hit", "miss", "unstored", "off"]
    assert records[2]["cache_retrieval_s"] == 0.004
    assert all(r["t0"] <= time.perf_counter() for r in records)
    after = compile_counters()
    grew = {k: after[k] - before[k] for k in after}
    assert grew == pytest.approx({
        "backend_compiles": 4, "backend_compile_s": 0.508, "cache_hits": 1,
        "cache_misses": 1, "traces": 1, "trace_s": 0.010, "lowerings": 1,
        "lower_s": 0.020, "cache_retrieval_s": 0.004,
        "compile_time_saved_s": 3.0})
    parts = compile_cache.log_summary(records)
    assert parts["programs"] == ["jit(step)", "jit(other)", "?",
                                 "jit(uncached)"]
    assert (parts["cache_hits"], parts["cache_misses"]) == (1, 1)
    assert parts["compile_s"] == pytest.approx(0.508)
    assert compile_cache.compile_log(nxt) == ([], nxt)


def test_the_log_is_bounded_and_a_delta_survives_the_wrap():
    mark = compile_cache.log_index()
    for i in range(compile_cache.LOG_CAPACITY + 10):
        compile_cache._on_duration(LOWER, 1e-6, fun_name=f"f{i}")
    records, nxt = compile_cache.compile_log(mark)
    assert nxt == mark + compile_cache.LOG_CAPACITY + 10
    assert len(records) == compile_cache.LOG_CAPACITY       # the newest
    assert records[-1]["fun_name"] == f"f{compile_cache.LOG_CAPACITY + 9}"
    tail, _ = compile_cache.compile_log(nxt - 3)
    assert [r["fun_name"] for r in tail] == [r["fun_name"]
                                             for r in records[-3:]]


def test_a_real_jit_yields_its_three_records_and_an_inner_jit_counts_once():
    @jax.jit
    def span_test_inner(x):
        return x * 2 + 1

    @jax.jit
    def span_test_outer(x):
        return span_test_inner(x).sum()

    arg = np.ones((3, 5), np.float32)
    before = compile_counters()
    mark = compile_cache.log_index()
    t0 = time.perf_counter()
    span_test_outer(arg)
    wall = time.perf_counter() - t0
    records, _ = compile_cache.compile_log(mark)
    mine = {r["phase"]: r for r in records
            if "span_test_outer" in r["fun_name"]}
    assert set(mine) == {"trace", "lower", "backend_compile"}
    assert mine["backend_compile"]["cache"] == "off"     # the suite's setting
    # the inner jit (and the jitted jnp functions inside both) fired their
    # trace events INSIDE the outer's duration: counted, and neither
    # logged nor added to the seconds
    assert not any(r["fun_name"] == "span_test_inner" for r in records)
    after = compile_counters()
    parts = compile_cache.log_summary(records)
    assert after["traces"] - before["traces"] >= 3 + sum(
        r["phase"] == "trace" for r in records)
    assert after["trace_s"] - before["trace_s"] == pytest.approx(
        parts["trace_s"])
    assert parts["trace_s"] + parts["lower_s"] + parts["compile_s"] <= wall
    # fed by hand, enter and exit in jax's order: the same bookkeeping
    mark = compile_cache.log_index()
    for name in ("outer", "inner"):
        compile_cache._on_scalar(TRACE, 0.0, fun_name=name)
    compile_cache._on_duration(TRACE, 0.25, fun_name="inner")
    compile_cache._on_duration(TRACE, 1.0, fun_name="outer")
    compile_cache._on_duration(TRACE, 0.5, fun_name="next")
    assert [(r["fun_name"], r["dur_s"])
            for r in compile_cache.compile_log(mark)[0]] == [
        ("outer", 1.0), ("next", 0.5)]
    assert compile_counters()["trace_s"] - after["trace_s"] == \
        pytest.approx(1.5)
    assert compile_counters()["traces"] - after["traces"] == 3


def test_a_span_that_did_not_compile_adds_no_key_and_reads_no_counters(
        monkeypatch):
    tl = StepTimeline(capacity=8)
    monkeypatch.setattr(
        compile_cache, "compile_counters",
        lambda: pytest.fail("a span called compile_counters()"))
    monkeypatch.setattr(
        compile_cache, "compile_log",
        lambda since=0: pytest.fail("a quiet span took the log's delta"))
    host_span(tl, "engine.decode.dispatch", dispatch=True, steps=4).close(
        program=("decode", 4))
    (ev,) = tl.events()
    assert ev["args"] == {"steps": 4, "program": ("decode", 4)}
    monkeypatch.undo()
    # one that compiled names the program, its seconds and where it ran
    outer = host_span(tl, "engine.admit")
    sp = host_span(tl, "engine.prefill.dispatch", dispatch=True)
    mark = compile_cache.log_index()
    compile_cache._on_duration(TRACE, 0.25, fun_name="prefill")
    compile_cache._on_duration(LOWER, 0.5, fun_name="jit(prefill)")
    compile_cache._on_duration(BACKEND, 1.5, fun_name="jit(prefill)")
    sp.close()
    compile_cache._on_duration(BACKEND, 0.125, fun_name="jit(install)")
    outer.close()
    _quiet, bracket, admit = tl.events()
    assert bracket["args"] == {
        "compile": True, "trace_s": 0.25, "lower_s": 0.5, "compile_s": 1.5,
        "programs": ["jit(prefill)"], "cache": ["off"]}
    assert admit["args"] == {}           # not a dispatch bracket: no keys
    assert [r["span"] for r in compile_cache.compile_log(mark)[0]] == [
        "engine.prefill.dispatch"] * 3 + ["engine.admit"]


def test_warmup_rounds_are_spans_whose_four_parts_sum_to_their_wall():
    engine = ContinuousEngine(SPEC, config=_cfg(max_slots=2), seed=0)
    grid = [(n, tb) for n in (1, 2) for tb in engine.prefill_buckets]
    engine_thread = threading.get_ident()
    t0 = time.perf_counter()
    assert engine.warmup() == len(grid)
    wall = time.perf_counter() - t0
    warm = engine.get_metrics()["warmup"]
    rounds = warm["rounds"]
    assert [(r["batch"], r["bucket"]) for r in rounds] == grid
    for r in rounds:
        assert r["trace_s"] + r["lower_s"] + r["compile_s"] + r["run_s"] \
            == pytest.approx(r["wall_s"])
        # compiles ran on the calling thread: nothing left over is negative
        assert r["run_s"] > 0 and r["cache_retrieval_s"] <= r["compile_s"]
    first = rounds[0]
    assert first["programs"] and first["compile_s"] > 0 < first["trace_s"]
    assert any("_decode_chunk" in p for p in first["programs"])
    assert warm["wall_s"] == pytest.approx(sum(r["wall_s"] for r in rounds))
    assert 0.9 * wall <= warm["wall_s"] <= wall
    assert warm["compile_s"] == pytest.approx(
        sum(r["compile_s"] for r in rounds))
    spans = [e for e in engine.timeline.events()
             if e["name"] == "engine.warmup.round"]
    assert [(e["args"]["batch"], e["args"]["bucket"]) for e in spans] == grid
    assert spans[0]["args"]["programs"] == first["programs"]
    assert spans[0]["dur"] == pytest.approx(first["wall_s"], abs=1e-3)
    inside = [e for e in engine.timeline.events()
              if e["name"] == "engine.prefill.dispatch"
              and e["args"].get("compile")]
    assert inside and inside[0]["args"]["programs"]
    assert engine.get_metrics()["compiles_after_warmup"] == {
        "count": 0, "seconds": 0.0, "last": []}
    # a repeat finds every program: all of a round is running
    assert engine.warmup() == len(grid)
    again = engine.get_metrics()["warmup"]["rounds"][len(grid):]
    assert len(again) == len(grid)
    assert all(r["programs"] == [] and r["compile_s"] == 0.0
               and r["run_s"] == pytest.approx(r["wall_s"]) for r in again)
    assert threading.get_ident() == engine_thread


def test_a_compile_after_warmup_is_counted_and_named_with_its_span():
    engine = ContinuousEngine(SPEC, config=_cfg(max_slots=2), seed=0)
    engine.warmup()
    # 24 new tokens reach a context bucket the grid's two-token rounds
    # never did: one decode program more
    mark = len(engine.timeline.events())
    engine.generate([_req(0, 24)])
    after = engine.get_metrics()["compiles_after_warmup"]
    assert after["count"] == len(after["last"]) >= 1
    entry = after["last"][0]
    assert "_decode_chunk" in entry["program"]
    assert entry["span"] == "engine.decode.dispatch"
    assert entry["cache"] == "off" and entry["compile_s"] > 0
    assert after["seconds"] == pytest.approx(sum(
        e["trace_s"] + e["lower_s"] + e["compile_s"]
        for e in after["last"]))
    flagged = [e for e in engine.timeline.events()[mark:]
               if e["args"].get("compile")]
    assert flagged[0]["args"]["programs"] == [entry["program"]]
    assert flagged[0]["t"] <= entry["t0"] <= (flagged[0]["t"]
                                              + flagged[0]["dur"])
    # the active-flag update has a span of its own, outside every bracket
    engine._set_active([0], False)
    assert engine.timeline.events()[-1]["name"] == "engine.set_active"


async def test_the_worker_reports_boot_marks_build_and_the_warmups_parts():
    from distributed_inference_engine_tpu.obs import collectors
    from distributed_inference_engine_tpu.obs.registry import MetricsRegistry

    cfg = _tiny_llama()
    cfg.metadata["warmup"] = 1
    w = WorkerServer(ServerConfig(worker_id="w0", host="127.0.0.1", port=0))
    compile_cache.install_compile_counters()          # as cli.worker does
    w.boot.update(main_entered=time.perf_counter())
    try:
        w.mark_boot("load_begin")                     # cli.worker, again
        await w.load_model_async(cfg)
        w.mark_boot("load_end")
        host, port = await w.start()
        m = w.get_metrics()
        boot = m["boot"]
        order = ["main_entered", "load_begin", "load_end", "listening"]
        marks = [boot[k] for k in order]
        assert marks == sorted(marks) and list(boot) == order
        w.mark_boot("load_begin")          # a later pass moves no mark
        assert w.boot_report() == boot
        setup = m["model_setup"]["m"]
        assert set(setup) == {"load_s", "warmup_s", "warmup"}
        assert 0 < setup["warmup_s"] < setup["load_s"]
        assert boot["load_end"] - boot["load_begin"] == pytest.approx(
            setup["load_s"], abs=0.05)
        warm = setup["warmup"]
        assert warm["rounds"] and warm["wall_s"] == pytest.approx(
            setup["warmup_s"], rel=0.01)
        assert warm["trace_s"] + warm["lower_s"] + warm["compile_s"] \
            + warm["run_s"] == pytest.approx(warm["wall_s"])
        assert m["mono"] <= time.perf_counter()
        compiled = m["device"]["compile"]
        assert compiled["trace_s"] > 0 < compiled["lower_s"]
        assert compiled["cache_retrieval_s"] <= compiled["backend_compile_s"]
        reg = MetricsRegistry()
        collectors.apply_worker(reg, m)
        text = reg.render()
        for name in ("worker_backend_trace_seconds",
                     "worker_backend_lower_seconds",
                     "worker_compile_cache_retrieval_seconds",
                     "worker_compiles_after_warmup",
                     "worker_warmup_run_seconds"):
            assert f"\n{name}" in text, name
    finally:
        await w.stop()


def test_deploy_metadata_sizes_the_ring_and_without_it_no_span_is_named():
    """``timeline_capacity`` travels in a served model's metadata like its
    neighbours; with the ring off a compile is still counted and named,
    and says no span."""
    from distributed_inference_engine_tpu.models import engine_from_config

    cfg = _tiny_llama()
    assert engine_from_config(cfg).timeline.capacity == 4096
    cfg.metadata["timeline_capacity"] = 0
    engine = engine_from_config(cfg)
    assert engine.timeline is None
    engine.generate([GenerationRequest(prompt=[5, 6, 7], max_new_tokens=3)])
    after = engine.get_metrics()["compiles_after_warmup"]
    assert after["count"] >= 2 and after["seconds"] > 0
    assert {e["span"] for e in after["last"]} == {None}
