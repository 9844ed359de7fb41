"""The host-span reduction on a hand-made trace whose answers are known and
on the small recorded piece of a real chip trace kept beside this file, and
the arithmetic of the span readers on a hand-made run."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib import hostspans, spanreaders  # noqa: E402
from perfbench.lib.loadgen import Record  # noqa: E402
from perfbench.lib.session import RunData  # noqa: E402
from perfbench.lib.traffic import Request  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000.0


def made_trace():
    """One device, busy 0..100, 150..300, 310..400 and 500..600 us: two
    idle gaps that count (50 and 100 us) and one under ``MIN_GAP_NS``. The
    engine thread is in a decode bracket 0..320 (blocked in the harvest
    read 120..300), resolves 330..340 and idles 600..900, after the
    device's last op; the first gap lies under the harvest wait, the
    second under no span. Of the device's 440 us, fusion.2 (150) gathers
    K/V context and fusion.3 (90) writes K/V rows."""
    ops = [["%fusion.1 = f32[8]{0} fusion(%p)", 0, 100 * US],
           ["%fusion.2 = f32[8]{0} fusion(%p)", 150 * US, 150 * US],
           ["%fusion.3 = f32[8]{0} fusion(%p)", 310 * US, 90 * US],
           ["%fusion.4 = f32[8]{0} fusion(%p)", 500 * US, 100 * US]]
    engine = [["$pump.py:212 _run", 0, 900 * US],
              ["engine.decode.dispatch#steps=8,live_slots=3#", 0, 320 * US],
              ["engine.harvest.wait", 120 * US, 180 * US],
              ["pump.resolve", 330 * US, 10 * US],
              ["pump.idle_wait", 600 * US, 300 * US]]
    loop = [["clock.anchor", 5 * US, 1 * US],
            ["$events.py:80 _run", 0, 880 * US]]
    scopes = ["jit(_decode_chunk)/mul:",
              "jit(_decode_chunk)/while/body/attn.kv_gather/dynamic_slice:",
              "jit(_decode_chunk)/while/body/attn.kv_update/scatter:", ""]
    return {"scoped_ops": [[[sc, o[1], o[2]] for sc, o in zip(scopes, ops)]],
            "planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "engine-pump", "events": engine},
                   {"name": "python3", "events": loop}]}]}


def test_engine_thread_share_and_attributed_share():
    red = hostspans.reduce_spans(made_trace())
    assert red["engine_thread_found"] and red["devices"] == 1
    # the window is the device's, first op to last: neither the idle wait
    # after it nor the Python tracer's events past it (0..900) stretch it
    assert red["window_s"] == pytest.approx(600e-6)
    assert red["engine_spans_s"] == pytest.approx(330e-6)   # nested once
    assert red["engine_wait_s"] == pytest.approx(180e-6)
    assert red["engine_busy_s"] == pytest.approx(150e-6)
    assert red["engine_span_s"]["engine.decode.dispatch"] == \
        pytest.approx(320e-6)
    assert red["idle_gap_s"] == pytest.approx(150e-6)
    assert red["idle_attributed_s"] == pytest.approx(50e-6)
    assert red["idle_by_span"] == {
        "engine.harvest.wait": pytest.approx(50e-6),
        hostspans.NO_SPAN: pytest.approx(100e-6)}


def test_scope_times_are_self_times():
    red = hostspans.reduce_spans(made_trace())
    assert red["device_busy_s"] == pytest.approx(440e-6)
    assert red["kv_copy_s"] == pytest.approx(240e-6)
    # an enclosing op (the layer loop) is cut down to what its children
    # leave: 100 us of while, 60 of them a gather inside it
    nested = [[["jit(f)/while:", 0, 100 * US],
               ["jit(f)/while/body/attn.kv_gather/gather:", 20 * US, 60 * US]]]
    assert hostspans.scope_times(nested) == {
        "device_busy_s": pytest.approx(100e-6),
        "kv_copy_s": pytest.approx(60e-6)}
    # a program without the scopes (an earlier commit), or no protobuf
    assert hostspans.scope_times([[["jit(f)/mul:", 0, 10 * US]]])[
        "kv_copy_s"] is None
    trace = made_trace()
    del trace["scoped_ops"]
    assert hostspans.reduce_spans(trace)["kv_copy_s"] is None


def test_scoped_ops_reads_tf_op_from_the_xplane_file(tmp_path):
    """The op's scope path is the ``tf_op`` stat of its event metadata,
    as a string or as a reference to a stat name."""
    space = hostspans._xspace_class()()
    host = space.planes.add(name="/host:CPU")
    host.lines.add(name="XLA Ops").events.add(metadata_id=1, duration_ps=5)
    plane = space.planes.add(name="/device:TPU:0")
    for key, name in ((3, "tf_op"), (4, "jit(f)/attn.kv_update/scatter:")):
        e = plane.stat_metadata.add(key=key)
        e.value.id, e.value.name = key, name
    plane.event_metadata.add(key=7).value.stats.add(
        metadata_id=3, str_value="jit(f)/attn.kv_gather/gather:")
    plane.event_metadata.add(key=8).value.stats.add(metadata_id=3, ref_value=4)
    plane.lines.add(name="XLA Modules").events.add(metadata_id=7)
    line = plane.lines.add(name="XLA Ops", timestamp_ns=1000)
    for mid, off_ns, dur_ns in ((7, 5, 2000), (8, 3000, 1000), (9, 5000, 10)):
        line.events.add(metadata_id=mid, offset_ps=off_ns * 1000,
                        duration_ps=dur_ns * 1000)
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(space.SerializeToString())
    assert hostspans.scoped_ops(str(tmp_path)) == [[
        ["jit(f)/attn.kv_gather/gather:", 1005.0, 2000.0],
        ["jit(f)/attn.kv_update/scatter:", 4000.0, 1000.0],
        ["", 6000.0, 10.0]]]
    assert hostspans.scoped_ops(str(tmp_path / "nothing")) == []


def test_a_trace_without_program_spans_reports_nothing():
    trace = made_trace()
    trace["planes"][1]["lines"] = [
        {"name": "engine-pump",
         "events": [["$continuous.py:2431 _harvest_chunk", 0, 900 * US]]}]
    assert hostspans.reduce_spans(trace) == {"engine_thread_found": False}


def test_recorded_piece():
    """A piece of the steady cell's traced slice on one v5e chip, cut by
    ``tools/record_spans.py`` with the answers the reduction gave then."""
    with open(os.path.join(HERE, "recorded_spans.json")) as f:
        rec = json.load(f)
    red = hostspans.reduce_spans(rec["trace"])
    want = rec["expected"]
    assert red["engine_thread_found"] and red["devices"] == 1
    for key in ("window_s", "engine_busy_s", "engine_wait_s", "idle_gap_s",
                "idle_attributed_s", "device_busy_s", "kv_copy_s"):
        assert red[key] == pytest.approx(want[key]), key
    assert red["idle_by_span"] == pytest.approx(want["idle_by_span"])
    # what the piece shows: the engine thread mostly waits, and the idle
    # gaps of a decode chunk lie under the program's own spans
    assert red["engine_wait_s"] > red["engine_busy_s"] > 0
    assert red["idle_attributed_s"] >= 0.9 * red["idle_gap_s"] > 0
    assert 0 < red["kv_copy_s"] < red["device_busy_s"]


# ----------------------------------------------------------- span readers


def made_run():
    def rec(i, trace):
        r = Record(req=Request(i, "window", 1.0, [1, 2, 3], 2), due=1.0,
                   sent=1.0, frames=[(1.2, 1), (1.3, 1)], done=1.4,
                   tokens=[5, 6], trace=trace)
        return r

    def trace(pool, inbox, queue, prefill, lag, transit=0.001):
        t, out = 0.002, {"received": 0.0, "routed": 0.001}
        out["dispatched"] = t
        for key, d in (("conn_acquired", pool), ("worker.submitted", inbox),
                       ("worker.admitted", queue),
                       ("worker.first_token", prefill),
                       ("worker.first_frame_sent", lag),
                       ("first_frame", transit)):
            t += d
            out[key] = t
            if key == "conn_acquired":
                out["worker.received"] = t
        return out

    compile_before = {"backend_compiles": 40, "backend_compile_s": 31.5}
    compile_after = {"backend_compiles": 41, "backend_compile_s": 31.75}
    return RunData(
        config={"vocab_size": 100}, mix={}, t_open=0.0, t_close=10.0,
        setup={}, device={},
        records=[rec(0, trace(0.000, 0.030, 0.002, 0.100, 0.001)),
                 rec(1, trace(0.010, 0.040, 0.004, 0.120, 0.003)),
                 rec(2, trace(0.500, 0.050, 0.080, 0.140, 0.002)),
                 rec(3, {"received": 0.0, "first_frame": 0.2})],  # old marks
        workers_before={"w0": {"device": {"compile": compile_before}}},
        workers_after={"w0": {"device": {"compile": compile_after}}},
        samples=[{"coord": {"pool_waiting": 2, "streams_in_flight": 10}},
                 {"coord": {"pool_waiting": 4, "streams_in_flight": 12}}])


def test_span_readers():
    run = made_run()
    assert spanreaders.span_p50_ms(run, "dispatched", "conn_acquired") == \
        pytest.approx(10.0)
    assert spanreaders.span_p50_ms(run, "worker.received",
                                   "worker.submitted") == pytest.approx(40.0)
    assert spanreaders.span_p50_ms(run, "no.such", "mark") is None
    cov = spanreaders.tile_coverage(run)
    assert cov["requests"] == 3                       # the fourth has no marks
    assert cov["p50_ms"]["queue_wait"] == pytest.approx(4.0)
    # request 1: 177 ms of spans in 180 ms received -> first_frame
    assert cov["covered_share_p50_pct"] == pytest.approx(100 * 177 / 180)
    assert spanreaders.coord_gauge_mean(run, "pool_waiting") == 3.0
    assert spanreaders.coord_gauge_mean(run, "streams_in_flight") == 11.0
    assert spanreaders.coord_gauge_mean(run, "absent") is None
    assert spanreaders.compile_delta(run, "backend_compiles") == 1.0
    assert spanreaders.compile_at_open(run, "backend_compile_s") == 31.5
    assert spanreaders.host_spans(run) is None        # no traced slice


def test_readers_return_nothing_for_a_program_without_the_marks():
    run = made_run()
    run.records = run.records[3:]
    run.workers_before = {"w0": {"device": {"memory": {}}}}
    run.workers_after = {"w0": {"device": {"memory": {}}}}
    run.samples = [{"coord": {"stream_frames": 3}}]
    assert spanreaders.span_p50_ms(run, "dispatched", "conn_acquired") is None
    assert spanreaders.tile_coverage(run) is None
    assert spanreaders.coord_gauge_mean(run, "pool_waiting") is None
    assert spanreaders.compile_delta(run, "backend_compiles") is None
    assert spanreaders.compile_at_open(run, "backend_compile_s") is None
    assert spanreaders.host_busy_share_pct(run) is None
