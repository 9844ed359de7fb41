"""The slice account (``lib/slicereaders.py``) on the two recorded pieces of
real chip traces kept beside this file, on a hand-made trace whose answers
are known, and its list of the program's scope names against the package's
source."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import hostspans, slicereaders, tracered  # noqa: E402
from perfbench.lib.session import RunData  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000.0


def anchored(trace, start, stop):
    """``trace`` with a ``clock.anchor`` pair on a host line of its own."""
    return dict(trace, planes=trace["planes"] + [{
        "name": "/host:CPU", "lines": [{"name": "event-loop", "events": [
            ["clock.anchor", start, 2 * US],
            ["clock.anchor#at=stop,perf_counter_ns=1#", stop, 2 * US]]}]}])


def recorded(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def test_one_recorded_decode_step_is_busy_throughout():
    """1,665 ops of one ``jit__decode_chunk`` (Mistral, 32 layers): the
    gaps between the ops of the chunk's ``while`` are the loop's own self
    time, not idle time."""
    rec = recorded("recorded_slice.json")
    plane = tracered.device_planes(rec["trace"])[0]
    (_n, start, dur), = tracered.line_events(plane, tracered.MODULE_LINE)
    acc = slicereaders.reduce_slice(
        anchored(rec["trace"], start - 5 * US, start + dur + 5 * US), None,
        rec["config"])
    assert acc["found"] and acc["devices"] == 1
    # the anchors lie outside the step: the window is the device's
    assert acc["window_s"] == pytest.approx(rec["expect"]["busy_s"])
    assert acc["idle_s"] == pytest.approx(0.0, abs=1e-12)
    assert acc["idle_in_programs_s"] == pytest.approx(0.0, abs=1e-12)
    assert acc["idle_in_programs_by_kind"] == {
        "decode": pytest.approx(0.0, abs=1e-12)}
    assert acc["loop_self_s"] == pytest.approx(36.5e-6, abs=0.1e-6)
    assert acc["self_s"] == pytest.approx(acc["busy_s"])
    assert acc["program_calls"] == {"decode": 1}
    # the piece was cut without its scope paths: all of it is unnamed, by
    # the op classes ``tracered`` gives the same step
    assert acc["scoped_self_s"] == 0.0
    assert acc["unscoped_by_class"]["int4_matmul"] == pytest.approx(
        rec["expect"]["int4_matmul_s"])
    assert sum(acc["unscoped_by_class"].values()) == pytest.approx(
        acc["busy_s"])


def test_the_recorded_spans_split_as_hostspans_splits_them():
    """0.4 s of a served run: the account's engine-thread seconds and its
    gaps by span are ``hostspans.reduce_spans``' own, on the same window
    (the anchors lie outside the piece's first and last op)."""
    rec = recorded("recorded_spans.json")
    acc = slicereaders.reduce_slice(anchored(rec["trace"], -1.0, 4.1e8))
    want = rec["expected"]
    assert acc["window_s"] == pytest.approx(want["window_s"])
    assert acc["engine_work_s"] == pytest.approx(want["engine_busy_s"])
    assert acc["engine_wait_s"] == pytest.approx(want["engine_wait_s"])
    assert acc["gaps_s"] == pytest.approx(want["idle_gap_s"])
    assert acc["gaps_by_span"] == {
        k: pytest.approx(v) for k, v in want["idle_by_span"].items()}
    assert acc["gaps_under_wait_s"] == pytest.approx(
        want["idle_by_span"]["engine.harvest.wait"])
    assert acc["gaps_under_wait_s"] + acc["gaps_under_work_s"] == \
        pytest.approx(acc["gaps_s"])
    # no "XLA Modules" line in the piece: every gap is between programs
    assert acc["idle_between_programs_s"] == pytest.approx(acc["idle_s"])


def made_trace():
    """One device between anchors at 100 and 1,100 us, inside 2,000 us of
    host events (the overhang). Two programs: a decode chunk 100..500 whose
    ``while`` (110..480) runs three ops with one 30 us hole before the
    sampling op, and a prefill 800..1,000 of one fused op; an op at 20..60
    ran before the start anchor. Between them the engine thread waits for
    the chunk (500..600: 100 us of the gap) and books it (600..800: 200 us);
    after the prefill it is under no span (1,000..1,100)."""
    ops = [["%early = f32[8]{0} fusion(%p)", 20 * US, 40 * US],
           ["%while.1 = (s32[]) while(%t)", 110 * US, 370 * US],
           ["%fusion.1 = f32[8]{0} fusion(%p)", 110 * US, 190 * US],
           ["%int4_mm.2 = f32[8]{0} custom-call(%p)", 300 * US, 100 * US],
           ["%fusion.3 = s32[8]{0} fusion(%p)", 430 * US, 50 * US],
           ["%fusion.4 = f32[8]{0} fusion(%p)", 800 * US, 200 * US]]
    scopes = ["jit(_decode_chunk)/chunk.begin/slice:",
              "jit(_decode_chunk)/while:",
              "jit(_decode_chunk)/while/body/closed_call/attn.qkv/mul:",
              "jit(_decode_chunk)/while/body/closed_call/mul:",
              "jit(_decode_chunk)/while/body/closed_call/sample/argmax:",
              "jit(_prefill_pages)/attn.core/flash_prefill/dot:"]
    mods = [["jit__decode_chunk(1)", 100 * US, 400 * US],
            ["jit__prefill_pages(2)", 800 * US, 200 * US]]
    engine = [["engine.step", 50 * US, 950 * US],
              ["engine.decode.dispatch#steps=8#", 60 * US, 540 * US],
              ["engine.harvest.wait", 300 * US, 300 * US],
              ["engine.harvest.book", 600 * US, 200 * US],
              ["engine.prefill_counters.wait", 700 * US, 40 * US],
              ["engine.first_tokens", 800 * US, 200 * US],
              ["engine.first_tokens.wait", 810 * US, 180 * US]]
    tracer = [["$pump.py:212 _run", 0, 2000 * US]]
    trace = {"scoped_ops": [[[sc, o[1], o[2]] for sc, o in zip(scopes, ops)]],
             "planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "engine-pump", "events": engine + tracer}]}]}
    return anchored(trace, 100 * US, 1100 * US)


def test_every_part_of_a_made_slice_has_its_name_and_they_sum():
    acc = slicereaders.reduce_slice(
        made_trace(), {"start": {"models": {"m": {"decode_steps": 40}}},
                       "stop": {"models": {"m": {"decode_steps": 48}}}})
    # the window: the anchors, cut to the device's last op (1,000); the
    # op before the start anchor is outside it
    assert acc["window_s"] == pytest.approx(900e-6)
    assert acc["tracered_window_s"] == pytest.approx(2000e-6)
    assert acc["device_outside_window_s"] == pytest.approx(40e-6)
    assert acc["anchor_stop_before_last_op_s"] == pytest.approx(-100e-6)
    # busy 110..480 (the while spans its hole) and 800..1,000
    assert acc["busy_s"] == pytest.approx(570e-6)
    assert acc["idle_s"] == pytest.approx(330e-6)
    # inside programs: 100..110 and 480..500 of the chunk
    assert acc["idle_in_programs_s"] == pytest.approx(30e-6)
    assert acc["idle_in_programs_by_kind"] == {
        "decode": pytest.approx(30e-6), "prefill": pytest.approx(0.0)}
    assert acc["idle_between_programs_s"] == pytest.approx(300e-6)
    assert acc["idle_in_programs_by_following_scope"] == {
        "decode:attn.qkv": pytest.approx(10e-6),
        "decode:(program end)": pytest.approx(20e-6)}
    # the while's own time: the hole before the sampling op, and the loop's
    # first and last 0 us
    assert acc["loop_self_s"] == pytest.approx(30e-6)
    # the gap between the two programs' ops (480..800), by the span over
    # its midpoint (640)
    assert acc["gaps_by_span"] == {
        "engine.harvest.book": pytest.approx(320e-6)}
    assert acc["gaps_under_work_s"] == pytest.approx(320e-6)
    assert acc["gaps_under_wait_s"] == 0.0
    # self time by the innermost scope of the program on the path
    assert acc["self_by_scope"] == {
        "attn.qkv": pytest.approx(190e-6), "sample": pytest.approx(50e-6),
        "flash_prefill": pytest.approx(200e-6),
        slicereaders.UNSCOPED: pytest.approx(130e-6)}
    assert acc["unscoped_by_class"] == {
        "int4_matmul": pytest.approx(100e-6),
        "loop_control": pytest.approx(30e-6)}
    assert acc["scoped_self_s"] == pytest.approx(440e-6)
    # the engine thread: under spans 100..1,000, waiting 300..600,
    # 700..740 (inside the booking) and 810..990
    assert acc["engine_spans_s"] == pytest.approx(900e-6)
    assert acc["engine_wait_s"] == pytest.approx(520e-6)
    assert acc["engine_work_s"] == pytest.approx(380e-6)
    assert acc["decode_steps_by_counters"] == 8.0
    assert acc["decode_steps_by_sample_op"] == 1
    # every second of the window has one name
    parts = (acc["busy_s"] + acc["idle_in_programs_s"]
             + acc["idle_between_programs_s"])
    assert parts == pytest.approx(acc["window_s"])
    assert sum(acc["self_by_scope"].values()) == pytest.approx(acc["busy_s"])


def test_a_gap_under_a_wait_and_one_under_work():
    """The same slice with the booking moved behind the prefill: the gap's
    midpoint then lies under the harvest wait."""
    trace = made_trace()
    engine = trace["planes"][1]["lines"][0]["events"]
    engine[2] = ["engine.harvest.wait", 300 * US, 500 * US]
    engine[3] = ["engine.harvest.book", 1000 * US, 50 * US]
    acc = slicereaders.reduce_slice(trace)
    assert acc["gaps_under_wait_s"] == pytest.approx(320e-6)
    assert acc["gaps_under_work_s"] == 0.0


def test_a_trace_without_anchors_or_device_reports_nothing():
    trace = made_trace()
    trace["planes"] = trace["planes"][:-1]           # no anchor pair
    assert slicereaders.reduce_slice(trace) == {"found": False}
    assert slicereaders.reduce_slice(
        {"planes": made_trace()["planes"][1:]}) == {"found": False}


def test_the_readers_divide_by_the_slices_window(tmp_path):
    """The ten entries' arithmetic on the made account every made run of
    these tests finds (``conftest.py``); the first identity is the one that
    ties ``device.idle_share.*`` to the two shares that replace it."""
    (tmp_path / "trace-w0").mkdir()
    run = RunData(config={}, mix={}, records=[], t_open=0.0, t_close=1.0,
                  setup={}, device={},
                  trace_dirs={"w0": str(tmp_path / "trace-w0")})
    over = slicereaders.trace_overhang_share_pct(run)
    idle = slicereaders.share_pct(run, "idle_s")
    assert over == pytest.approx(20.0) and idle == pytest.approx(12.5)
    # tracered's idle share of the same made slice: 1 - 3.5 / 5.0
    assert over + (100.0 - over) * idle / 100.0 == pytest.approx(30.0)
    assert slicereaders.share_pct(run, "idle_in_programs_s") == \
        pytest.approx(2.5)
    assert slicereaders.share_pct(run, "scoped_self_s", "self_s") == \
        pytest.approx(100.0 * 3.3 / 3.5)
    assert slicereaders.under_span_share_pct(run, "engine_work_s") == \
        pytest.approx(37.5)
    assert slicereaders.under_span_share_pct(run, "gaps_under_work_s") == \
        pytest.approx(6.25)
    assert slicereaders.under_span_share_pct(run, "gaps_under_wait_s") == \
        pytest.approx(4.5)
    # no trace, or a trace whose account found nothing: no value, no raise
    run.trace_dirs = {}
    assert slicereaders.share_pct(run, "idle_s") is None
    (tmp_path / "slice-w0.json").write_text(json.dumps({"found": False}))
    run.trace_dirs = {"w0": str(tmp_path / "trace-w0")}
    assert slicereaders.trace_overhang_share_pct(run) is None
    assert slicereaders.under_span_share_pct(run, "engine_work_s") is None


# --------------------------------------------- the program's scope names

SCOPE = re.compile(r'named_scope\(f?"([^"]+)"\)')


def source_scopes():
    """Every ``named_scope("...")`` literal of the package, read as text
    (this process imports neither jax nor the package)."""
    names = set()
    for d, _sub, files in os.walk(os.path.join(
            ROOT, "distributed_inference_engine_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(d, name)) as f:
                    names.update(SCOPE.findall(f.read()))
    return names


def test_the_scope_list_is_the_packages_own():
    """``PROGRAM_SCOPES`` holds every literal of the source and nothing
    else; the one template (``attn.{kind}``) stands for the names it
    makes."""
    literal = {n for n in source_scopes() if "{" not in n}
    templates = [re.compile(re.sub(r"\\\{\w+\\\}", r"\\w+", re.escape(n)))
                 for n in source_scopes() if "{" in n]
    listed = set(slicereaders.PROGRAM_SCOPES)
    assert len(listed) == len(slicereaders.PROGRAM_SCOPES)
    assert literal <= listed
    made = listed - literal
    assert made and all(any(t.fullmatch(n) for t in templates) for n in made)
    assert all(any(t.fullmatch(n) for n in listed) for t in templates)


@pytest.mark.parametrize("module", ["scopes", "scopes_dsa", "scopes_gdn",
                                    "scopes_mhc", "scopes_mla_share",
                                    "scopes_swa"])
def test_every_scope_a_family_reads_is_on_the_list(module):
    """A name a family's reader matches is a scope of the program: the
    account's ``scoped`` seconds hold every family's."""
    with open(os.path.join(ROOT, "perfbench", "lib", f"{module}.py")) as f:
        text = f.read()
    block = re.search(r"^SCOPES = \((.*?)\)", text, re.S | re.M).group(1)
    names = re.findall(r'"([^"]+)"', block)
    assert names and set(names) <= set(slicereaders.PROGRAM_SCOPES)
    assert not any(("decode" in n or "prefill" in n)
                   for n in set(slicereaders.PROGRAM_SCOPES) - set(names)
                   if n not in ("flash_decode", "flash_prefill",
                                "attn.gdn.prefill", "attn.kda.prefill"))
