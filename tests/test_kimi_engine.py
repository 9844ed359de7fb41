"""The plain-residual compressed-query MLA family (Kimi-K2.5 at the tiny
size) through ``ContinuousEngine`` with MORE than 8 slots, and behind a real
coordinator and worker whose connection pool follows the slots the worker
reports, plus a look-ahead: twelve streams in flight at once where the ninth
used to wait for a connection, slot reuse and pre-emption by re-prefill with
more than eight rows live, a worker that reports eight slots gets a pool of
ten, and a freed slot finds its successor already queued at the engine.
``tests/test_kimi.py`` holds the logits comparisons against the reference."""

import asyncio
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from distributed_inference_engine_tpu.api.coordinator import (  # noqa: E402
    Coordinator, CoordinatorConfig,
)
from distributed_inference_engine_tpu.cluster.worker import (  # noqa: E402
    WorkerClient, WorkerServer,
)
from distributed_inference_engine_tpu.config import (  # noqa: E402
    EngineConfig, ModelConfig, ServerConfig,
)
from distributed_inference_engine_tpu.engine.continuous import (  # noqa: E402
    ContinuousEngine, resolve_decode_body,
)
from distributed_inference_engine_tpu.engine.types import (  # noqa: E402
    GenerationRequest,
)
from distributed_inference_engine_tpu.models import (  # noqa: E402
    engine_from_config, spec_for_architecture, xing,
)
from distributed_inference_engine_tpu.utils.rpc import (  # noqa: E402
    DEFAULT_POOL, FramedRPCClient, pool_for_slots,
)
from perfbench.lib import families  # noqa: E402

with open(os.path.join(ROOT, "perfbench", "rehearse", "kimi-tiny.json")) as _f:
    CFG = json.load(_f)
REF = families.reference(CFG)
SLOTS = 12


def tiny_engine(dtype="bfloat16", **cfg_kw):
    base = dict(max_slots=SLOTS, max_seq_len=128, page_size=16, num_pages=96,
                prefill_buckets=[32, 64], decode_steps_per_call=4)
    base.update(cfg_kw)
    return ContinuousEngine(
        xing.kimi_spec("kimi-tiny", max_seq_len=128, dtype=dtype),
        config=EngineConfig(**base), seed=11)


def requests(n, seed=1, new=(6, 14)):
    rng = np.random.default_rng(seed)
    return [GenerationRequest(
        prompt=[int(t) for t in rng.integers(1, 256,
                                             int(rng.integers(5, 50)))],
        max_new_tokens=int(rng.integers(*new))) for _ in range(n)]


def judged(engine, reqs, results):
    """Every served token the reference's argmax, or within 8 % of
    max|logit| of it: the bound ``tests/test_kimi.py`` holds the bfloat16
    logits to."""
    for req, res in zip(reqs, results):
        assert len(res.tokens) == req.max_new_tokens
        lg = np.asarray(REF.logits(
            CFG, engine.params, jnp.asarray(req.prompt + res.tokens)))
        for i, tok in enumerate(res.tokens):
            row = lg[len(req.prompt) - 1 + i]
            assert row.max() - row[tok] <= 0.08 * np.abs(row).max(), (i, tok)


@pytest.fixture(scope="module")
def engine():
    return tiny_engine()


def test_sixteen_requests_over_twelve_slots(engine):
    """Batched admission at padded buckets, twelve rows live at once, slots
    reused by the last four; the engine names its slots and its residual;
    a quarter of ONE group is held, so most choices land elsewhere."""
    assert engine.body == "hybrid" and engine.attn_impl == "xla"
    reqs = requests(16)
    results = engine.generate(reqs)
    judged(engine, reqs[:6], results[:6])
    m = engine.get_metrics()
    assert (m["slots"], m["residual"]) == (SLOTS, "plain")
    assert m["total_requests"] == 16 and m["live_slots"] == 0
    moe = m["moe"]
    assert 0 < moe["assignments_held"] < 0.5 * moe["assignments_total"]
    assert 0 < moe["decode_assignments_held"] <= moe["assignments_held"]
    assert 0 < moe["experts_touched"] <= m["decode_steps"] * 3 * 4
    kv = m["kv"]
    assert (kv["paged_layers"], kv["state_layers"]) == (4, 0)
    assert m["prefix_hit_admissions"] == 0


def test_the_kernel_body_emits_the_xla_bodys_tokens():
    """The TPU body through the interpreter (latent rows read in place) at
    twelve rows against ``attention_impl="xla"``, in float32: the same
    greedy tokens."""
    reqs = requests(12, seed=4, new=(4, 8))

    def run(impl):
        eng = tiny_engine(dtype="float32", attention_impl=impl)
        return [r.tokens for r in eng.generate(
            [GenerationRequest(prompt=list(r.prompt),
                               max_new_tokens=r.max_new_tokens)
             for r in reqs])], eng.get_metrics()["mla"]

    want, read_xla = run("xla")
    got, read_kernel = run("pallas-decode_interpret")
    assert got == want
    assert (read_kernel["decode_context_rows"]
            == read_xla["decode_context_rows"]
            <= read_kernel["decode_table_rows"]
            < read_xla["decode_table_rows"])


def test_preemption_by_re_prefill_with_more_than_eight_rows_live():
    """A pool too small for twelve sequences at full length: a sequence is
    pre-empted, re-prefilled as prompt + tokens, and ends as the same
    request served alone does. In float32, as the other families' tests."""
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, 256, n)]
               for n in (30, 28, 26, 31, 29, 27, 30, 28, 25, 24, 29, 30)]

    def make():
        return [GenerationRequest(prompt=list(p), max_new_tokens=30)
                for p in prompts]

    roomy = tiny_engine("float32").generate(make())
    tight = tiny_engine("float32", num_pages=40)
    peak = 0
    for r in make():
        tight.submit(r)
    done = []
    while len(done) < len(prompts):
        tight.step()
        peak = max(peak, tight.get_metrics()["live_slots"])
        done += tight.drain_finished()
    m = tight.get_metrics()
    assert peak > 8 and m["reprefill_preemptions"] >= 1
    assert m["capacity_finishes"] == 0
    by_id = {r.request_id: r for r in done}
    assert len(by_id) == len(prompts)
    assert sorted(tuple(r.tokens) for r in done) == sorted(
        tuple(r.tokens) for r in roomy)


def test_the_body_and_the_architecture_string():
    spec = spec_for_architecture("kimi", size="kimi-k2.5-ep32-pp1",
                                 max_seq_len=7680)
    assert spec.cache_row_width == 640 and spec.kv_row_lanes == 0
    assert resolve_decode_body("auto", "tpu", spec) == ("hybrid",
                                                        "pallas-decode")
    assert resolve_decode_body("auto", "cpu", spec) == ("hybrid", "xla")
    assert spec.max_seq_len == 7680 and not spec.recurrent
    assert spec_for_architecture("kimi").experts_held == (0, 12)
    with pytest.raises(ValueError, match="unknown kimi size"):
        spec_for_architecture("kimi", size="kimi-9b")
    with pytest.raises(ValueError, match="per-layer"):
        tiny_engine(prefill_chunk=32)
    with pytest.raises(ValueError, match="mesh"):
        engine_from_config(ModelConfig(
            name="m", architecture="kimi", max_batch_size=2, max_seq_len=64,
            metadata={"size": "kimi-tiny", "continuous": 1, "page_size": 16,
                      "num_pages": 8, "tp": 2}))


# ------------------------------------------- the pool follows the slots


def test_pool_for_slots():
    """The slots plus a look-ahead of a quarter of them, two at least; the
    default where the worker says nothing or that fills less; and a worker
    that sheds what waits gets only the look-ahead its queue leaves room for
    beyond its slots."""
    assert DEFAULT_POOL == 8 == FramedRPCClient("h", 1).max_connections
    assert [pool_for_slots(n) for n in (None, 0, 4, 6, 7, 8, 12, 32)] == [
        8, 8, 8, 8, 9, 10, 15, 40]
    assert [pool_for_slots(8, q) for q in (None, 0, 4, 8, 9, 10, 64)] == [
        10, 8, 8, 8, 9, 10, 10]
    assert [pool_for_slots(32, q) for q in (0, 36, 64)] == [32, 36, 40]


async def test_a_shrunk_pool_lets_go_of_connections_as_calls_end():
    """``resize_pool`` closes nothing under a call: the connections over a
    smaller bound go as their calls end, and a larger bound wakes waiters."""
    w = WorkerServer(ServerConfig(worker_id="w", host="127.0.0.1", port=0))
    host, port = await w.start()
    client = WorkerClient(host, port)
    try:
        client.resize_pool(3)
        await asyncio.gather(*(client.ping() for _ in range(3)))
        assert client._total == 3 and client.pool_stats()["size"] == 3
        client.resize_pool(1)
        await asyncio.gather(*(client.ping() for _ in range(3)))
        assert client._total == 1
    finally:
        await client.close()
        await w.stop()


def _model(slots):
    return ModelConfig(
        name="m", architecture="kimi", dtype="bfloat16", max_seq_len=128,
        max_batch_size=slots,
        metadata={"size": "kimi-tiny", "page_size": 16,
                  "num_pages": 8 * slots, "prefill_buckets": [32, 64],
                  "decode_steps_per_call": 4, "continuous": 1, "seed": 11})


async def _fleet(model):
    coord = Coordinator(CoordinatorConfig())
    await coord.start()
    w = WorkerServer(ServerConfig(worker_id="w0", host="127.0.0.1", port=0))
    host, port = await w.start()
    coord.add_worker("w0", host, port)
    await coord.deploy_model(model)
    return coord, w


async def test_twelve_streams_are_in_flight_at_once_behind_a_coordinator():
    """A worker whose engine runs 12 slots: the deploy's load receipt sizes
    the coordinator's pool to the 12 and their look-ahead, twelve streams
    hold a connection each and none waits (with the default of 8 the ninth
    waited for one), the engine has more than eight rows live, and every
    stream ends with its tokens."""
    coord, w = await _fleet(_model(SLOTS))
    try:
        assert coord.get_stats()["pool_size"] == pool_for_slots(SLOTS) > SLOTS
        dev = w.device_report()["models"]["m"]
        assert (dev["slots"], dev["residual"]) == (SLOTS, "plain")
        reqs = requests(SLOTS, seed=6, new=(24, 32))
        streams = [asyncio.ensure_future(coord.submit_stream(
            "m", prompt=list(r.prompt), on_tokens=lambda t: None,
            max_new_tokens=r.max_new_tokens, request_id=f"s{i}"))
            for i, r in enumerate(reqs)]
        peak_use = peak_wait = peak_live = 0
        while not all(s.done() for s in streams):
            stats = coord.get_stats()
            peak_use = max(peak_use, stats["pool_in_use"])
            peak_wait = max(peak_wait, stats["pool_waiting"])
            peak_live = max(peak_live,
                            w.engines["m"].get_metrics()["live_slots"])
            await asyncio.sleep(0.01)
        outs = await asyncio.gather(*streams)
        # (a health probe may hold a connection of either client beside
        # the twelve streams: not a stream)
        assert SLOTS <= peak_use <= SLOTS + 2 and peak_wait == 0
        assert peak_live > 8
        for r, o in zip(reqs, outs):
            assert len(o["tokens"]) == r.max_new_tokens
        # no stream waited for a connection: each had one long before the
        # first of them ended
        marks = [o["trace"] for o in outs]
        first_done = min(t["done"] for t in marks)
        assert max(t["conn_acquired"] for t in marks) < first_done
        waits = [t["conn_acquired"] - t["dispatched"] for t in marks]
        assert max(waits) < 0.5 * min(t["done"] - t["dispatched"]
                                      for t in marks), waits
        assert (await coord.router.client_for("w0").ping())["slots"] == SLOTS
    finally:
        await coord.stop()
        await w.stop()


async def test_a_worker_that_reports_eight_slots_gets_its_look_ahead():
    coord, w = await _fleet(_model(8))
    try:
        pool = pool_for_slots(8)
        assert pool > 8 == DEFAULT_POOL
        assert coord.get_stats()["pool_size"] == pool
        assert coord.router.client_for("w0").max_connections == pool
        # the load balancer's own client learns the same from its pings
        lb_client = coord.lb.client_for("w0")
        await lb_client.ping()
        assert lb_client.max_connections == pool
        assert coord.get_stats()["pool_size"] == pool   # one pool a worker
        # an engine that sheds what waits too long is sent no look-ahead
        w.engines["m"].config.queue_deadline_s = 5.0
        assert (await lb_client.ping())["queue"] == 0
        assert lb_client.max_connections == pool_for_slots(8, 0) == 8
    finally:
        await coord.stop()
        await w.stop()


async def _turnovers(pool=None):
    """24 streams through a worker of 8 slots (``pool``: the coordinator's
    pool to it, ``None`` for the rule's). The first eight end two chunks
    apart, so a chunk sees a finish or two and never more than the
    look-ahead. Returns the engine's metrics, the outputs with the lengths
    asked, and what each decode dispatch after the first finish saw:
    (``empty_slot_dispatches`` so far, streams waiting at the coordinator)."""
    slots, chunk = 8, 4
    coord, w = await _fleet(_model(slots))
    try:
        client = coord.router.client_for("w0")
        if pool is not None:
            client.resize_pool(pool)        # the report stays: no resize back
        eng = w.engines["m"]
        reqs = requests(3 * slots, seed=9)
        for i, r in enumerate(reqs):
            r.max_new_tokens = chunk * (3 + 2 * i if i < slots else 8)
        seen = []
        streams = [asyncio.ensure_future(coord.submit_stream(
            "m", prompt=list(r.prompt), on_tokens=lambda t: None,
            max_new_tokens=r.max_new_tokens, request_id=f"s{i}"))
            for i, r in enumerate(reqs)]
        while w._pumps.get("m") is None:
            await asyncio.sleep(0.001)
        after_dispatch = eng.overlap_hook       # the pump's, on its thread

        def hook():
            if eng._admissions > slots:         # a slot has been given twice
                seen.append((eng._empty_slot_dispatches,
                             client.pool_stats()["waiting"]))
            after_dispatch()

        eng.overlap_hook = hook
        outs = await asyncio.gather(*streams)
        return eng.get_metrics(), list(zip(reqs, outs)), seen
    finally:
        await coord.stop()
        await w.stop()


async def test_a_freed_slots_successor_is_already_queued_at_the_engine():
    """With the look-ahead at the worker a freed slot's successor is in the
    engine's queue when the slot frees and joins the very next chunk; on a
    pool of the slots alone (the control, same process) every finish costs
    its slot an empty chunk. Held against the control, not against a count
    of chunks: the coordinator's and the engine's threads race, and a slot
    is handed on one chunk before its result leaves (and its successor's
    successor sets out), so a finish or two can outrun a look-ahead of
    two."""
    slots = 8

    def empty_while_streams_waited(seen):
        """Growth of ``empty_slot_dispatches`` over the decode dispatches
        that saw a stream still waiting at the coordinator."""
        waited = [e for e, n in seen if n > 0]
        assert len(waited) >= 10, seen
        return waited[-1] - waited[0]

    m, pairs, seen = await _turnovers()
    for r, o in pairs:
        assert len(o["tokens"]) == r.max_new_tokens
    assert m["admissions"] == len(pairs)
    ahead = empty_while_streams_waited(seen)
    # every slot but the look-ahead's worth found its successor queued
    successors = len(pairs) - slots
    assert m["admissions_from_queue"] >= successors - (
        pool_for_slots(slots) - slots), m["admissions_from_queue"]

    m, pairs, seen = await _turnovers(pool=slots)
    for r, o in pairs:
        assert len(o["tokens"]) == r.max_new_tokens
    alone = empty_while_streams_waited(seen)
    assert alone >= 6, seen                     # one a finish, nearly
    assert m["admissions_from_queue"] == 0
    assert 3 * ahead <= alone, (ahead, alone)
