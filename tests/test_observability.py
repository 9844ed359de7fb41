"""Unified telemetry (obs/): metrics registry name/label rules, OpenMetrics
exposition format, the engine step timeline's Chrome-trace export, and
cross-process request tracing (coordinator marks + worker-side spans with a
consistent request_id) through the in-process fleet path."""

import asyncio
import json

import numpy as np
import pytest

from distributed_inference_engine_tpu.api import (
    Coordinator,
    CoordinatorClient,
    CoordinatorConfig,
    CoordinatorServer,
)
from distributed_inference_engine_tpu.config import (
    BatcherConfig,
    EngineConfig,
    HealthConfig,
    ModelConfig,
    ServerConfig,
)
from distributed_inference_engine_tpu.cluster.worker import (
    WorkerClient,
    WorkerServer,
)
from distributed_inference_engine_tpu.engine.types import GenerationRequest
from distributed_inference_engine_tpu.models.base import ModelSpec
from distributed_inference_engine_tpu.obs import collectors as obs_collectors
from distributed_inference_engine_tpu.obs.registry import (
    OPENMETRICS_CONTENT_TYPE,
    MetricsRegistry,
    _NAME_RE,
    _RESERVED_SUFFIXES,
)
from distributed_inference_engine_tpu.obs.timeline import StepTimeline
from distributed_inference_engine_tpu.utils.tracing import (
    LATENCY_BUCKETS,
    LatencyStats,
    RequestTrace,
)

pytestmark = pytest.mark.obs


# ---------------------------------------------------------------- registry


def test_registry_name_and_label_rules():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("9bad")
    with pytest.raises(ValueError):
        reg.counter("x-y")
    for sfx in _RESERVED_SUFFIXES:
        with pytest.raises(ValueError):
            reg.counter(f"x{sfx}")
    with pytest.raises(ValueError):
        reg.gauge("g", labelnames=("le",))           # reserved label
    with pytest.raises(ValueError):
        reg.gauge("g", labelnames=("__x",))          # dunder label
    with pytest.raises(ValueError):
        reg.gauge("g", labelnames=("a", "a"))        # duplicate


def test_registry_idempotent_and_kind_mismatch():
    reg = MetricsRegistry()
    c1 = reg.counter("hits", "help", labelnames=("model",))
    c2 = reg.counter("hits", "other help", labelnames=("model",))
    assert c1 is c2
    with pytest.raises(ValueError):
        reg.gauge("hits")                            # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("hits", labelnames=("worker",))  # label mismatch


def test_registry_label_value_set_must_match():
    reg = MetricsRegistry()
    c = reg.counter("hits", labelnames=("model", "worker_id"))
    with pytest.raises(ValueError):
        c.labels(model="m")                          # missing worker_id
    child = c.labels(model="m", worker_id="w0")
    child.inc()
    with pytest.raises(ValueError):
        child.inc(-1)                                # counters only go up


def test_openmetrics_exposition_format():
    reg = MetricsRegistry()
    reg.counter("req", "requests", labelnames=("model",)).labels(
        model="m").set(3)
    reg.gauge("occ", "occupancy").labels().set(0.5)
    h = reg.histogram("lat", "latency seconds", buckets=(0.1, 1.0))
    h.labels().observe(0.05)
    h.labels().observe(0.5)
    h.labels().observe(5.0)
    reg.counter("empty_family", "no samples yet")
    text = reg.render()
    lines = text.splitlines()
    assert lines[-1] == "# EOF"
    assert "# TYPE req counter" in lines
    assert '# HELP req requests' in lines
    assert 'req_total{model="m"} 3' in lines
    assert "occ 0.5" in lines
    # cumulative buckets + count + sum
    assert 'lat_bucket{le="0.1"} 1' in lines
    assert 'lat_bucket{le="1"} 2' in lines
    assert 'lat_bucket{le="+Inf"} 3' in lines
    assert "lat_count 3" in lines
    assert any(ln.startswith("lat_sum ") for ln in lines)
    # empty families still document themselves
    assert "# TYPE empty_family counter" in lines
    assert "version=1.0.0" in OPENMETRICS_CONTENT_TYPE


def test_scrape_text_parses_cleanly():
    """Every non-comment line must be ``name{labels} value`` with a float
    value — the shape a Prometheus scraper requires."""
    reg = MetricsRegistry()
    obs_collectors.ensure_families(reg)
    reg.counter("esc", labelnames=("p",)).labels(p='a"b\\c\nd').inc()
    for line in reg.render().splitlines():
        if not line or line.startswith("#"):
            continue
        name_part, _, value = line.rpartition(" ")
        assert name_part and _NAME_RE.match(name_part.split("{")[0])
        float(value)                                 # must parse


def test_catalog_families_are_valid_and_unique():
    for name, (kind, labels, help_text) in obs_collectors.CATALOG.items():
        assert _NAME_RE.match(name), name
        assert not any(name.endswith(s) for s in _RESERVED_SUFFIXES), name
        assert kind in ("counter", "gauge", "histogram")
        assert help_text, name
        for ln in labels:
            assert ln not in ("le", "quantile"), (name, ln)
    # the ensure pass registers every catalog family
    reg = MetricsRegistry()
    obs_collectors.ensure_families(reg)
    assert set(reg.names) == set(obs_collectors.CATALOG)


def test_a_per_layer_specs_mla_counters_reach_the_scrape():
    """``get_metrics()["mla"]`` (latent rows the decode steps attended to,
    and rows the body read for them) lands in two counter families; an
    engine without the sub-dict sets neither."""
    reg = MetricsRegistry()
    obs_collectors.apply_engine(
        reg, {"decode_steps": 8, "mla": {"decode_context_rows": 196,
                                         "decode_table_rows": 4096}},
        model="m", worker_id="w0")
    obs_collectors.apply_engine(reg, {"total_requests": 3}, model="other")
    text = reg.render()
    assert ('engine_mla_decode_context_rows_total{model="m",worker_id="w0"}'
            ' 196' in text)
    assert ('engine_mla_decode_table_rows_total{model="m",worker_id="w0"}'
            ' 4096' in text)
    assert 'engine_mla_decode_table_rows_total{model="other"' not in text
    assert obs_collectors.CATALOG["engine_mla_decode_table_rows"][0] \
        == "counter"


def test_the_latent_prefills_block_counters_reach_the_scrape():
    reg = MetricsRegistry()
    obs_collectors.apply_engine(
        reg, {"mla": {"decode_context_rows": 1, "decode_table_rows": 2,
                      "prefill_key_blocks_visited": 45,
                      "prefill_key_blocks_bucket": 256}},
        model="m", worker_id="w0")
    obs_collectors.apply_engine(
        reg, {"mla": {"decode_context_rows": 0, "decode_table_rows": 0}},
        model="dense")
    text = reg.render()
    # "_bucket" is a histogram's sample suffix: the exported name avoids it
    for name, v in (("visited", 45), ("square", 256)):
        assert (f'engine_mla_prefill_key_blocks_{name}_total'
                f'{{model="m",worker_id="w0"}} {v}' in text)
        assert f'prefill_key_blocks_{name}_total{{model="dense"' not in text


def test_the_kv_row_families_prefill_block_counters_reach_the_scrape():
    reg = MetricsRegistry()
    obs_collectors.apply_engine(
        reg, {"attn": {"full_context_rows": 9, "full_table_rows": 9,
                       "full_prefill_key_blocks_visited": 45,
                       "full_prefill_key_blocks_bucket": 256,
                       "window_prefill_key_blocks_visited": 24,
                       "window_prefill_key_blocks_bucket": 256}},
        model="m", worker_id="w0")
    # a spec without sliding layers: the full layers' pair alone
    obs_collectors.apply_engine(
        reg, {"attn": {"full_prefill_key_blocks_visited": 3,
                       "full_prefill_key_blocks_bucket": 4}}, model="mha")
    obs_collectors.apply_engine(reg, {"total_requests": 3}, model="dense")
    text = reg.render()
    for kind, name, v in (("full", "visited", 45), ("full", "square", 256),
                          ("window", "visited", 24),
                          ("window", "square", 256)):
        metric = f"engine_attn_{kind}_prefill_key_blocks_{name}"
        assert f'{metric}_total{{model="m",worker_id="w0"}} {v}' in text
        assert f'{metric}_total{{model="dense"' not in text
        assert (f'{metric}_total{{model="mha"' in text) == (kind == "full")
        assert obs_collectors.CATALOG[metric][0] == "counter"


def test_latency_stats_histogram_snapshot():
    ls = LatencyStats()
    ls.add(0.0005)            # below first bound
    ls.add(0.3)               # in (0.25, 0.5]
    ls.add(100.0)             # above every bound -> +Inf only
    snap = ls.snapshot()
    b = snap["buckets"]
    assert b["0.001"] == 1
    assert b["0.25"] == 1     # cumulative: only the 0.0005 sample
    assert b["0.5"] == 2
    assert b["30"] == 2
    assert b["+Inf"] == 3
    assert snap["count"] == 3
    assert abs(snap["sum_s"] - 100.3005) < 1e-9
    assert list(b)[-1] == "+Inf"
    # counts accumulate past the reservoir (never decimated)
    ls2 = LatencyStats(reservoir=4)
    for _ in range(100):
        ls2.add(0.01)
    assert ls2.snapshot()["buckets"]["+Inf"] == 100

    # snapshot buckets feed a registry histogram verbatim
    reg = MetricsRegistry()
    h = reg.histogram("ttft_seconds", buckets=LATENCY_BUCKETS)
    h.labels().set_snapshot(b, snap["sum_s"], snap["count"])
    text = reg.render()
    assert 'ttft_seconds_bucket{le="+Inf"} 3' in text
    assert "ttft_seconds_count 3" in text


# ---------------------------------------------------------------- timeline


def test_step_timeline_chrome_trace():
    tl = StepTimeline(capacity=4, name="eng")
    import time

    t0 = time.perf_counter()
    for i in range(6):                               # overflows capacity 4
        tl.record("decode", t0, 0.002, rows=i)
    tl.instant("swap_out", slot=1)
    assert len(tl) == 4                              # ring buffer dropped 3
    doc = tl.to_chrome_trace()
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M"                       # process_name metadata
    complete = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    assert complete and instants
    for e in complete:
        assert e["dur"] == pytest.approx(2000.0)     # µs
        assert "rows" in e["args"]
    assert doc["metadata"]["dropped_events"] == 3
    json.dumps(doc)                                  # serializable


def test_step_timeline_capture_window():
    tl = StepTimeline(capacity=16)
    import time

    tl.record("before", time.perf_counter(), 0.001)
    tl.start_capture()
    tl.record("inside", time.perf_counter(), 0.001)
    evs = tl.stop_capture()
    assert [e["name"] for e in evs] == ["inside"]
    # no window open -> everything
    assert len(tl.stop_capture()) == 2


def test_continuous_engine_records_timeline():
    from distributed_inference_engine_tpu.engine.continuous import (
        ContinuousEngine,
    )

    # same shape rules as tests/test_continuous.py: n_kv_heads*head_dim
    # must be a multiple of 128 for the paged layout
    spec = ModelSpec(vocab_size=512, d_model=256, n_layers=2, n_heads=4,
                     n_kv_heads=2, d_ff=256, max_seq_len=256,
                     dtype="float32")
    cfg = EngineConfig(max_slots=2, max_seq_len=128, prefill_buckets=[16],
                       page_size=16, num_pages=32, decode_steps_per_call=4,
                       attention_impl="xla", kv_dtype="float32")
    eng = ContinuousEngine(spec, config=cfg, seed=0)
    rs = np.random.RandomState(0)
    reqs = [GenerationRequest(prompt=rs.randint(1, 512, size=8).tolist(),
                              max_new_tokens=6, temperature=0.0,
                              request_id=f"r{i}") for i in range(2)]
    eng.generate(reqs)
    kinds = {e["name"] for e in eng.timeline.events()}
    assert {"engine.admit", "engine.prefill.dispatch",
            "engine.decode.dispatch", "engine.harvest.wait",
            "engine.harvest.book", "engine.process_packed"} <= kinds
    decodes = [e for e in eng.timeline.events()
               if e["name"] == "engine.decode.dispatch"]
    assert decodes[0]["args"].get("compile") is True  # the compiler ran
    assert all(e["args"]["kv_pages_total"] == 32 for e in decodes)
    doc = eng.timeline.to_chrome_trace()
    assert any(e.get("ph") == "X" for e in doc["traceEvents"])
    json.dumps(doc)


def test_timeline_capacity_zero_disables(tmp_path):
    from distributed_inference_engine_tpu.engine.engine import Engine

    spec = ModelSpec(vocab_size=128, d_model=64, n_layers=1, n_heads=2,
                     n_kv_heads=2, d_ff=64, max_seq_len=64, dtype="float32")
    cfg = EngineConfig(max_seq_len=64, prefill_buckets=[16],
                       attention_impl="xla", timeline_capacity=0)
    eng = Engine(spec, config=cfg, seed=0)
    eng.generate([GenerationRequest(prompt=[1, 2, 3], max_new_tokens=2)])
    assert eng.timeline is None


# ------------------------------------------------------------ request trace


def test_request_trace_add_offsets():
    tr = RequestTrace(request_id="abc", marks={"received": 10.0,
                                               "dispatched": 12.0})
    tr.add_offsets("worker.", {"received": 0.0, "first_token": 0.5,
                               "done": 1.25, "junk": "str"})
    assert tr.marks["worker.received"] == pytest.approx(12.0)
    assert tr.marks["worker.first_token"] == pytest.approx(12.5)
    assert tr.marks["worker.done"] == pytest.approx(13.25)
    assert "worker.junk" not in tr.marks
    # first-wins: a second merge must not move existing marks
    tr.add_offsets("worker.", {"done": 99.0})
    assert tr.marks["worker.done"] == pytest.approx(13.25)


# ------------------------------------------------------- fleet round-trips


def fake_cfg(name="echo", **meta):
    return ModelConfig(name=name, architecture="fake", metadata=meta)


async def make_fleet(n_workers=2, model_meta=None):
    workers = []
    coord = Coordinator(CoordinatorConfig(
        batcher=BatcherConfig(max_batch_size=4, max_latency_ms=10.0),
        health=HealthConfig(check_interval=0.1, check_timeout=1.0,
                            max_consecutive_failures=2),
    ))
    await coord.start()
    for i in range(n_workers):
        w = WorkerServer(ServerConfig(worker_id=f"w{i}", port=0))
        host, port = await w.start()
        workers.append(w)
        coord.add_worker(f"w{i}", host, port)
    await coord.deploy_model(fake_cfg(**(model_meta or {})))
    return coord, workers


async def stop_fleet(coord, workers):
    await coord.stop()
    for w in workers:
        await w.stop()


async def test_trace_includes_worker_spans():
    coord, workers = await make_fleet(n_workers=1)
    try:
        out = await coord.submit("echo", prompt=[1, 2, 3], max_new_tokens=4,
                                 request_id="traced-1")
        tr = out["trace"]
        assert tr["request_id"] == "traced-1"
        # coordinator-side AND worker-side phases on one timeline
        for phase in ("received", "routed", "dispatched", "done",
                      "worker.received", "worker.first_token",
                      "worker.done"):
            assert phase in tr, phase
        assert tr["worker.received"] >= tr["dispatched"] - 1e-6
        assert tr["worker.done"] >= tr["worker.received"]
        # retrievable after the fact from the coordinator
        dumped = coord.get_trace("traced-1")
        assert dumped is not None
        assert dumped["request_id"] == "traced-1"
        assert "worker.done" in dumped
        assert coord.get_trace("no-such-request") is None
    finally:
        await stop_fleet(coord, workers)


async def test_stream_trace_includes_worker_spans():
    # streaming needs a pumped continuous engine (FakeEngine has none) —
    # tiny llama on CPU, the tests/test_streaming.py idiom
    coord = Coordinator(CoordinatorConfig())
    await coord.start()
    w = WorkerServer(ServerConfig(worker_id="w0", port=0))
    host, port = await w.start()
    coord.add_worker("w0", host, port)
    try:
        await coord.deploy_model(ModelConfig(
            name="m", architecture="llama", dtype="float32",
            max_seq_len=64, max_batch_size=4,
            metadata={"size": "llama-tiny", "page_size": 16,
                      "num_pages": 64, "attention_impl": "xla",
                      "kv_dtype": "float32", "decode_steps_per_call": 3,
                      "continuous": 1}))
        chunks = []
        out = await coord.submit_stream(
            "m", prompt=[5, 6, 7], on_tokens=chunks.append,
            max_new_tokens=4, request_id="stream-1")
        assert [t for c in chunks for t in c] == out["tokens"]
        tr = out["trace"]
        assert tr["request_id"] == "stream-1"
        for phase in ("received", "routed", "dispatched", "done",
                      "worker.received", "worker.first_token",
                      "worker.done"):
            assert phase in tr, phase
        assert coord.get_trace("stream-1") is not None
    finally:
        await coord.stop()
        await w.stop()


async def test_recent_traces_bounded():
    coord, workers = await make_fleet(n_workers=1)
    try:
        coord._recent_traces_cap = 8
        for i in range(12):
            await coord.submit("echo", prompt=[i + 1], max_new_tokens=2,
                               request_id=f"lru-{i}", no_cache=True)
        assert len(coord._recent_traces) == 8
        assert coord.get_trace("lru-0") is None      # aged out
        assert coord.get_trace("lru-11") is not None
    finally:
        await stop_fleet(coord, workers)


async def test_coordinator_metrics_text_covers_fleet():
    coord, workers = await make_fleet(n_workers=2)
    try:
        await coord.submit("echo", prompt=[1, 2], max_new_tokens=2)
        text = await coord.metrics_text()
        assert text.endswith("# EOF\n")
        # families from every layer render at least their TYPE line
        for family in ("engine_requests", "batcher_requests",
                       "batcher_queue_wait_seconds", "pump_steps",
                       "kv_pages", "offload_hit_pages", "worker_requests",
                       "coordinator_submitted", "router_routes",
                       "lb_picks"):
            assert f"# TYPE {family} " in text, family
        # worker-side samples carry the worker_id label
        assert 'worker_requests_total{worker_id="w0"}' in text
        assert 'worker_requests_total{worker_id="w1"}' in text
        assert "coordinator_submitted_total 1" in text
    finally:
        await stop_fleet(coord, workers)


async def test_unregistered_worker_series_drop_from_scrape():
    """A removed worker's labelled series must vanish at the next scrape:
    the coordinator prunes its cached per-worker metrics against the live
    membership instead of re-applying ghost samples forever."""
    coord, workers = await make_fleet(n_workers=2)
    try:
        await coord.submit("echo", prompt=[1, 2], max_new_tokens=2)
        text = await coord.metrics_text()
        assert 'worker_id="w1"' in text
        coord.remove_worker("w1")
        # refresh_workers=False: nothing repolls, so any w1 line in this
        # render could only come from the stale cache
        text = await coord.metrics_text(refresh_workers=False)
        assert 'worker_id="w1"' not in text
        assert 'worker_id="w0"' in text
    finally:
        await stop_fleet(coord, workers)


async def test_worker_metrics_rpc_and_http():
    w = WorkerServer(ServerConfig(worker_id="wm", port=0))
    host, port = await w.start()
    try:
        client = WorkerClient(host, port)
        try:
            await client.load_model(fake_cfg("m"))
            text = await client.metrics_text()
            assert "# TYPE worker_uptime_seconds gauge" in text
            assert 'worker_requests_total{worker_id="wm"}' in text
            # framed RPC still works on the same port after HTTP requests
            reader, writer = await asyncio.open_connection(host, port)
            writer.write(b"GET /metrics HTTP/1.1\r\nHost: t\r\n\r\n")
            await writer.drain()
            raw = await asyncio.wait_for(reader.read(1 << 20), timeout=5.0)
            writer.close()
            head, _, body = raw.partition(b"\r\n\r\n")
            assert head.startswith(b"HTTP/1.1 200")
            assert OPENMETRICS_CONTENT_TYPE.encode() in head
            assert body.rstrip().endswith(b"# EOF")
            assert (await client.ping())["worker_id"] == "wm"
        finally:
            await client.close()
    finally:
        await w.stop()


async def test_coordinator_http_metrics_and_trace_rpc():
    coord, workers = await make_fleet(n_workers=1)
    server = CoordinatorServer(coord, ServerConfig(worker_id="co", port=0))
    # Coordinator.start is idempotent; the server start path re-enters it
    host, port = await server.start()
    try:
        client = CoordinatorClient(host, port)
        try:
            out = await client.generate("echo", prompt=[1, 2, 3],
                                        max_new_tokens=4,
                                        request_id="rpc-1")
            assert out["tokens"] == [3, 2, 1]
            # trace verb round-trips the stored trace
            tr = await client.get_trace("rpc-1")
            assert tr is not None and tr["request_id"] == "rpc-1"
            assert "worker.done" in tr
            assert await client.get_trace("missing") is None
            # metrics_text verb
            text = await client.metrics_text()
            assert "# TYPE coordinator_submitted counter" in text
            assert 'worker_requests_total{worker_id="w0"}' in text
        finally:
            await client.close()
        # plain HTTP scrape on the same port
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(b"GET /metrics HTTP/1.0\r\n\r\n")
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(1 << 20), timeout=5.0)
        writer.close()
        head, _, body = raw.partition(b"\r\n\r\n")
        assert head.startswith(b"HTTP/1.1 200")
        assert b"# EOF" in body
        # unknown path -> 404
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(b"GET /nope HTTP/1.1\r\nHost: t\r\n\r\n")
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(1 << 20), timeout=5.0)
        writer.close()
        assert raw.startswith(b"HTTP/1.1 404")
    finally:
        await server.stop()
        for w in workers:
            await w.stop()
