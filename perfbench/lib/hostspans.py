"""Reduction of the program's own host spans in a profiler trace: what the
engine thread spends its time on, and how much of the device's idle time
lies under a named span.

The program opens its host spans through one helper
(``obs/timeline.host_span``), which writes each as a
``jax.profiler.TraceAnnotation``: an event named ``engine.*`` or ``pump.*``
on the thread's line of a ``/host:`` plane, on the device planes' clock,
with or without the Python tracer. It also reads the share of device time
under the program's ``jax.named_scope`` names: an op's scope path is the
``tf_op`` stat of its metadata, which ``jax.profiler.ProfileData`` does not
give, so ``scoped_ops`` reads the ``.xplane.pb`` with protobuf's runtime.
Works on ``tracered``'s neutral form of the trace (plus ``scoped_ops``), so
the test keeps a small recorded piece. Run as a script (a child process:
the benchmark process never imports jax)::

    python perfbench/lib/hostspans.py <trace_dir> <out.json>
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib.tracered import (  # noqa: E402
    MIN_GAP_NS, OPS_LINE, Event, device_planes, host_span_at, leaf_ops,
    line_events, load_xplane, union_intervals,
)

PROGRAM_PREFIXES = ("engine.", "pump.")
ENGINE_THREAD_MARK = "engine.decode.dispatch"
# the engine thread is blocked, not working, inside these
WAIT_SPANS = ("engine.harvest.wait", "pump.idle_wait")
NO_SPAN = "no_host_span"
# named scopes of the model programs under which K/V rows are only moved:
# the dense context sliced out of the pool, a step's rows written into it
COPY_SCOPES = ("/attn.kv_gather/", "/attn.kv_update/")
SCOPE_STAT = "tf_op"


def span_name(event_name: str) -> str:
    """An annotation's name without the ``#k=v,...#`` suffix some trace
    forms keep on it."""
    return event_name.split("#", 1)[0]


def program_lines(trace: Dict[str, Any]
                  ) -> List[Tuple[List[float], List[Event]]]:
    """Per host thread that carries any, the program's spans by start, in
    the form ``tracered.host_span_at`` takes."""
    out = []
    for p in trace["planes"]:
        if not p["name"].startswith("/host:"):
            continue
        for ln in p["lines"]:
            evs = sorted(((span_name(e[0]), e[1], e[2]) for e in ln["events"]
                          if span_name(e[0]).startswith(PROGRAM_PREFIXES)),
                         key=lambda e: e[1])
            if evs:
                out.append(([e[1] for e in evs], evs))
    return out


def measure(events: Sequence[Event]) -> float:
    return sum(e - s for s, e in union_intervals(events))


def engine_thread(lines: List[Tuple[List[float], List[Event]]]
                  ) -> List[Event]:
    """The spans of the thread that dispatches decode chunks (the one with
    most such spans, if several engines ran)."""
    best: List[Event] = []
    most = 0
    for _starts, evs in lines:
        n = sum(1 for e in evs if e[0] == ENGINE_THREAD_MARK)
        if n > most:
            best, most = evs, n
    return best


def reduce_spans(trace: Dict[str, Any]) -> Dict[str, Any]:
    """Seconds of the traced window the engine thread spends under any
    program span, and under the waits among them; device idle gaps (as
    ``tracered`` finds them: between device ops, at least ``MIN_GAP_NS``)
    in total, under a program span, and by the deepest span over each
    gap's midpoint. ``engine_thread_found`` is false for a trace of a
    program that opens no spans: nothing else is then reported."""
    lines = program_lines(trace)
    evs = engine_thread(lines)
    if not evs:
        return {"engine_thread_found": False}
    planes = [(p, union_intervals(line_events(p, OPS_LINE)))
              for p in device_planes(trace)]
    # the window is the device's: its first op to its last. (First to last
    # event of any plane would stretch with the Python tracer, whose events
    # run on after the last op.) Without a device plane, the thread's own.
    edges = [t for _p, busy in planes for t in (busy[0][0], busy[-1][1])]
    lo = min(edges) if edges else evs[0][1]
    hi = max(edges) if edges else max(s + d for _n, s, d in evs)
    evs = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
           for n, s, d in evs if s < hi and s + d > lo]
    spans_s = measure(evs) / 1e9
    wait_s = measure([e for e in evs if e[0] in WAIT_SPANS]) / 1e9
    out: Dict[str, Any] = {
        "engine_thread_found": True, "window_s": (hi - lo) / 1e9,
        "engine_spans_s": spans_s, "engine_wait_s": wait_s,
        "engine_busy_s": spans_s - wait_s,
        "engine_span_s": {}, "idle_gap_s": 0.0, "idle_attributed_s": 0.0,
        "idle_by_span": {}, "devices": len(planes),
        **scope_times(trace.get("scoped_ops") or [])}
    for name, _start, dur in evs:
        out["engine_span_s"][name] = \
            out["engine_span_s"].get(name, 0.0) + dur / 1e9
    for _plane, busy in planes:
        for (_s0, e0), (s1, _e1) in zip(busy, busy[1:]):
            if s1 - e0 < MIN_GAP_NS:
                continue
            gap_s = (s1 - e0) / 1e9
            name = host_span_at(lines, (e0 + s1) / 2)
            out["idle_gap_s"] += gap_s
            out["idle_by_span"][name] = \
                out["idle_by_span"].get(name, 0.0) + gap_s
            if name != NO_SPAN:
                out["idle_attributed_s"] += gap_s
    return out


def scope_times(scoped_ops: Sequence[Sequence[Event]]) -> Dict[str, Any]:
    """Device self time (``tracered.leaf_ops``) of all ops and of those
    under ``COPY_SCOPES``, over the device planes' "XLA Ops" events named
    by scope path. ``kv_copy_s`` is ``None`` when no op carries such a
    scope: a program without them, or a trace read without protobuf."""
    busy_ns = copy_ns = 0.0
    found = False
    for ops in scoped_ops:
        for scope, _end, self_ns in leaf_ops([tuple(e) for e in ops]):
            busy_ns += self_ns
            if any(c in scope for c in COPY_SCOPES):
                copy_ns += self_ns
                found = True
    return {"device_busy_s": busy_ns / 1e9,
            "kv_copy_s": copy_ns / 1e9 if found else None}


def _xspace_class() -> Any:
    """A message class for the few fields of the profiler's ``XSpace``
    read here (``tsl/profiler/protobuf/xplane.proto``), made with
    protobuf's runtime: unknown fields are skipped on the wire."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="perfbench_xplane.proto", package="perfbench_xplane",
        syntax="proto3")

    def message(name: str, *fields: Tuple[str, int, Any]) -> None:
        m = fd.message_type.add(name=name)
        for fname, number, kind in fields:
            rep = isinstance(kind, list)
            kind = kind[0] if rep else kind
            f = m.field.add(
                name=fname, number=number,
                label=F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL)
            if isinstance(kind, str):
                f.type, f.type_name = F.TYPE_MESSAGE, \
                    f".perfbench_xplane.{kind}"
            else:
                f.type = kind

    message("XStat", ("metadata_id", 1, F.TYPE_INT64),
            ("str_value", 5, F.TYPE_STRING), ("ref_value", 7, F.TYPE_UINT64))
    message("XStatMetadata", ("id", 1, F.TYPE_INT64),
            ("name", 2, F.TYPE_STRING))
    message("XEventMetadata", ("id", 1, F.TYPE_INT64), ("stats", 5, ["XStat"]))
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64),
            ("offset_ps", 2, F.TYPE_INT64), ("duration_ps", 3, F.TYPE_INT64))
    message("XLine", ("name", 2, F.TYPE_STRING),
            ("timestamp_ns", 3, F.TYPE_INT64), ("events", 4, ["XEvent"]))
    # the two maps, as the repeated key/value entries they are on the wire
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64),
            ("value", 2, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64),
            ("value", 2, "XStatMetadata"))
    message("XPlane", ("name", 2, F.TYPE_STRING), ("lines", 3, ["XLine"]),
            ("event_metadata", 4, ["EventMetadataEntry"]),
            ("stat_metadata", 5, ["StatMetadataEntry"]))
    message("XSpace", ("planes", 1, ["XPlane"]))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("perfbench_xplane.XSpace"))


def scoped_ops(trace_dir: str) -> List[List[List[Any]]]:
    """Per device plane, its "XLA Ops" events as ``[scope path, start_ns,
    dur_ns]``: the op's ``tf_op`` (``jit(fn)/scope/.../primitive:``), ``""``
    where it has none. Empty without protobuf or without the file."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    try:
        space = _xspace_class()()
    except ImportError:
        return []
    if not paths:
        return []
    with open(paths[-1], "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        scope_of: Dict[int, str] = {}
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if stat_names.get(st.metadata_id) == SCOPE_STAT:
                    scope_of[entry.key] = (
                        st.str_value or stat_names.get(st.ref_value, ""))
        for line in plane.lines:
            if line.name == OPS_LINE:
                out.append([[scope_of.get(ev.metadata_id, ""),
                             line.timestamp_ns + ev.offset_ps / 1e3,
                             ev.duration_ps / 1e3] for ev in line.events])
    return out


def main(argv: Sequence[str]) -> int:
    trace_dir, out_path = argv[:2]
    trace = load_xplane(trace_dir)
    trace["scoped_ops"] = scoped_ops(trace_dir)
    with open(out_path, "w") as f:
        json.dump(reduce_spans(trace), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
