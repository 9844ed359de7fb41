"""One account of the traced slice on the device's own clock, from what the
program writes: every second of the slice is busy under a named scope, idle
inside a program, or idle between programs under a span of the engine thread.

The slice's window is ``[start anchor, stop anchor]``, the two
``clock.anchor`` events the worker's ``profile`` RPC stamps where the slice
was asked to begin and to end (``cluster/worker.py``), cut to the device's
first op start and last op end. ``tracered``'s ``window_s`` runs over every
plane's events instead, the host's too, which go on while the profiler starts
and is written out: the part of it outside this window is the OVERHANG, not
the device's idle time. Every share here divides by this window.

Reuses ``tracered``'s and ``hostspans``' functions on their neutral form of
the trace. Run as a script (a child process: the benchmark process never
imports jax), it writes the account the readers below and
``tools/slice_account.py`` take::

    python perfbench/lib/slicereaders.py <trace_dir> <out.json>
"""

from __future__ import annotations

import bisect
import collections
import json
import os
import re
import subprocess
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib import procs  # noqa: E402
from perfbench.lib.scopes import _add as add_up  # noqa: E402
from perfbench.lib.hostspans import (  # noqa: E402
    NO_SPAN, engine_thread, measure, program_lines, scoped_ops, span_name,
)
from perfbench.lib.tracered import (  # noqa: E402
    MIN_GAP_NS, MODULE_LINE, OPS_LINE, Event, device_planes, host_span_at,
    leaf_ops, line_events, load_xplane, op_class, op_classes, program_kind,
    trace_span, union_intervals,
)

ANCHOR = "clock.anchor"
# the engine thread is blocked, not working, inside these
WAIT_SPANS = ("engine.harvest.wait", "engine.first_tokens.wait",
              "engine.prefill_counters.wait", "pump.idle_wait")
# every ``jax.named_scope`` of the package (``tests/test_slicereaders.py``
# holds the list to the literals of its source): an op whose ``tf_op`` path
# has one of them as a segment runs under a name of the program
PROGRAM_SCOPES = (
    "attn.core", "attn.dsa", "attn.full", "attn.gather", "attn.gdn.prefill",
    "attn.gdn.step", "attn.index", "attn.kda.prefill", "attn.kda.step",
    "attn.kv_gather", "attn.kv_index", "attn.kv_side", "attn.kv_update",
    "attn.mla", "attn.out", "attn.qkv", "attn.select", "attn.sparse",
    "attn.swa", "attn.window_keep", "chunk.advance", "chunk.begin",
    "chunk.end", "chunk.pack", "embed", "flash_decode", "flash_prefill",
    "gmm", "head.firsts", "head.unembed", "mlp.dense", "mlp.moe", "mlp.norm",
    "moe.combine", "moe.experts", "moe.route", "moe.shared", "recurrence",
    "resid.add", "resid.mhc", "resid.norm", "sample", "slots.install",
    "state.read", "state.stack", "state.update", "step.counters",
    "step.setup")
UNSCOPED = "(no scope)"
STEP_SCOPE = "sample"          # runs once a decode step, in every family


def anchors(trace: Dict[str, Any]) -> Optional[Tuple[float, float]]:
    """Start of the first and of the last ``clock.anchor`` event on the host
    planes: where the slice was asked to begin and to end. ``None`` unless
    there are two (a program that stamps none)."""
    at = sorted(e[1] for p in trace["planes"] if p["name"].startswith("/host:")
                for ln in p["lines"] for e in ln["events"]
                if span_name(e[0]) == ANCHOR)
    return (at[0], at[-1]) if len(at) >= 2 else None


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(s + d, hi) - max(s, lo))
            for n, s, d in events if s < hi and s + d > lo]


def inner_scope(path: str) -> str:
    """The innermost of the program's scopes on an op's ``tf_op`` path."""
    for seg in reversed(path.split("/")):
        if seg in _SCOPE_SET:
            return seg
    return UNSCOPED


_SCOPE_SET = frozenset(PROGRAM_SCOPES)


def covered(intervals: List[Tuple[float, float]], starts: List[float],
            lo: float, hi: float) -> float:
    """Length of ``[lo, hi)`` that the ordered, disjoint ``intervals``
    cover."""
    total = 0.0
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    while i < len(intervals) and intervals[i][0] < hi:
        total += max(0.0, min(intervals[i][1], hi) - max(intervals[i][0], lo))
        i += 1
    return total


def add(into: Dict[str, float], key: str, amount: float) -> None:
    into[key] = into.get(key, 0.0) + amount


def reduce_device(plane: Dict[str, Any], scoped: Sequence[Sequence[Any]],
                  lo: float, hi: float, thread: List[Event],
                  classes) -> Dict[str, Any]:
    """One device plane inside ``[lo, hi]``. ``scoped`` is the plane's
    "XLA Ops" line by scope path (``hostspans.scoped_ops``), event for event
    what the plane's line holds by name; ``thread`` the engine thread's
    spans."""
    raw = [ln["events"] for ln in plane["lines"] if ln["name"] == OPS_LINE][0]
    paths = ([e[0] for e in scoped] if len(scoped) == len(raw)
             else [""] * len(raw))
    # an op is its place in the line: ``raw[i]`` names it, ``paths[i]`` is
    # its scope path
    ops = clip([(i, e[1], e[2]) for i, e in enumerate(raw)], lo, hi)
    mods = clip(line_events(plane, MODULE_LINE), lo, hi)
    busy = union_intervals(ops)
    busy_starts = [s for s, _e in busy]
    window = hi - lo
    out: Dict[str, Any] = {
        "window_s": window / 1e9,
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "program_s": {}, "program_calls": {}, "idle_in_programs_by_kind": {},
        "idle_in_programs_by_following_scope": {}, "gaps_by_span": {},
        "self_by_scope": {}, "unscoped_by_class": {}, "unscoped_by_path": {},
        "unscoped_by_op": {},
        "loop_self_s": 0.0}
    # what the device ran before the start anchor and after the stop anchor
    out["device_outside_window_s"] = (
        measure(line_events(plane, OPS_LINE)) / 1e9 - out["busy_s"])
    out["idle_s"] = out["window_s"] - out["busy_s"]
    mod_union = union_intervals(mods)
    out["idle_in_programs_s"] = sum(
        e - s - covered(busy, busy_starts, s, e) for s, e in mod_union) / 1e9
    out["idle_between_programs_s"] = \
        out["idle_s"] - out["idle_in_programs_s"]
    for name, start, dur in mods:
        kind = program_kind(name)
        add(out["program_s"], kind, dur / 1e9)
        add(out["program_calls"], kind, 1)
        add(out["idle_in_programs_by_kind"], kind,
            (dur - covered(busy, busy_starts, start, start + dur)) / 1e9)
    # the gaps: inside a program by the scope of the op that follows (the
    # program's end where none does), all of MIN_GAP_NS or more by the
    # engine thread's span over their midpoint
    mod_starts = [m[1] for m in mods]
    first_at = {s: i for i, s, _d in sorted(ops, key=lambda e: (
        e[1], -e[2]))}                     # the innermost op starting there
    lines = [([e[1] for e in thread], thread)] if thread else []
    edges = [(lo, lo)] + busy + [(hi, hi)]
    out["gaps_s"] = out["gaps_under_wait_s"] = out["gaps_under_work_s"] = 0.0
    for (_s0, e0), (s1, _e1) in zip(edges, edges[1:]):
        if s1 <= e0:
            continue
        i = max(0, bisect.bisect_right(mod_starts, e0) - 1)
        while i < len(mods) and mods[i][1] < s1:
            name, start, dur = mods[i]
            inside = min(s1, start + dur) - max(e0, start)
            if inside > 0:
                path = paths[first_at[s1]] if s1 in first_at else ""
                add(out["idle_in_programs_by_following_scope"],
                    f"{program_kind(name)}:" + (
                        inner_scope(path) if s1 <= start + dur
                        else "(program end)"), inside / 1e9)
            i += 1
        if s1 - e0 >= MIN_GAP_NS:
            span = host_span_at(lines, (e0 + s1) / 2)
            out["gaps_s"] += (s1 - e0) / 1e9
            add(out["gaps_by_span"], span, (s1 - e0) / 1e9)
            if span in WAIT_SPANS:
                out["gaps_under_wait_s"] += (s1 - e0) / 1e9
            elif span != NO_SPAN:
                out["gaps_under_work_s"] += (s1 - e0) / 1e9
    # busy time by scope: self times, so that they sum to the busy time
    steps: Dict[str, int] = collections.Counter()
    self_ns = scoped_ns = 0.0
    for i, _end, own in leaf_ops(ops):
        name, path = raw[i][0], paths[i]
        scope = inner_scope(path)
        self_ns += own
        add(out["self_by_scope"], scope, own / 1e9)
        label = op_class(name, classes)
        if label == "loop_control":
            out["loop_self_s"] += own / 1e9
        if scope == UNSCOPED:
            add(out["unscoped_by_class"], label, own / 1e9)
            add(out["unscoped_by_path"], path or "(no tf_op)", own / 1e9)
            add(out["unscoped_by_op"],
                re.sub(r"[.\d]+$", "", name.split(" = ", 1)[0]), own / 1e9)
        else:
            scoped_ns += own
    for i, _s, _d in ops:
        if f"/{STEP_SCOPE}/" in paths[i] \
                and program_kind(paths[i]) == "decode":
            steps[raw[i][0].split(" = ", 1)[0]] += 1
    out["self_s"], out["scoped_self_s"] = self_ns / 1e9, scoped_ns / 1e9
    for key in ("unscoped_by_path", "unscoped_by_op"):        # the largest
        out[key] = dict(sorted(out[key].items(), key=lambda kv: -kv[1])[:24])
    # one op of the sampling scope runs once a step: the commonest count
    counts = collections.Counter(steps.values()).most_common(1)
    out["decode_steps_by_sample_op"] = counts[0][0] if counts else 0
    return out


def reduce_slice(trace: Dict[str, Any], counters: Optional[Dict[str, Any]]
                 = None, cfg: Optional[Dict[str, Any]] = None
                 ) -> Dict[str, Any]:
    """The account of one worker's trace, its device planes added up.
    ``found`` is false where the trace has no device plane or no anchor
    pair: nothing else is then reported. ``counters`` is the worker's
    ``counters.json`` (the engines' counters at the two anchors)."""
    planes = device_planes(trace)
    stamps = anchors(trace)
    if not planes or stamps is None:
        return {"found": False}
    first = min(e[1] for p in planes for e in line_events(p, OPS_LINE)[:1])
    last = max(s + d for p in planes for _n, s, d in line_events(p, OPS_LINE))
    lo, hi = max(stamps[0], first), min(stamps[1], last)
    if hi <= lo:
        return {"found": False}
    t_lo, t_hi = trace_span(trace)
    thread = clip(engine_thread(program_lines(trace)), lo, hi)
    scoped = trace.get("scoped_ops") or []
    classes = op_classes(cfg or {})
    out: Dict[str, Any] = {}
    for i, plane in enumerate(planes):
        add_up(out, reduce_device(
            plane, scoped[i] if i < len(scoped) else [], lo, hi, thread,
            classes))
    spans_s = measure(thread) / 1e9
    wait_s = measure([e for e in thread if e[0] in WAIT_SPANS]) / 1e9
    by_span: Dict[str, float] = {}
    for name, _s, dur in thread:
        add(by_span, name, dur / 1e9)
    out.update(
        found=True, devices=len(planes),
        anchors_ns=list(stamps), device_first_ns=first, device_last_ns=last,
        # how far each anchor lies inside the device's own first and last op
        anchor_start_after_first_op_s=(stamps[0] - first) / 1e9,
        anchor_stop_before_last_op_s=(last - stamps[1]) / 1e9,
        tracered_window_s=(t_hi - t_lo) / 1e9 * len(planes),
        engine_spans_s=spans_s,
        engine_wait_s=wait_s, engine_work_s=spans_s - wait_s,
        engine_span_s=by_span,
        decode_steps_by_counters=_steps_between(counters))
    return out


def _steps_between(counters: Optional[Dict[str, Any]]) -> Optional[float]:
    """The engines' ``decode_steps`` between the two anchors' stamps."""
    try:
        return float(sum(
            counters["stop"]["models"][m]["decode_steps"] - eng["decode_steps"]
            for m, eng in counters["start"]["models"].items()))
    except (KeyError, TypeError):
        return None


# ------------------------------------------------------------- the readers


def slice_account(run) -> Optional[Dict[str, Any]]:
    """``reduce_slice`` of every worker's traced slice, added up over the
    workers. Each trace is reduced once, by a child process, and kept as
    ``slice-<wid>.json`` in the run's work directory. ``None`` without a
    trace, or where one has no device plane or no anchor pair."""
    total: Dict[str, Any] = {}
    for wid, trace_dir in run.trace_dirs.items():
        path = os.path.join(os.path.dirname(trace_dir), f"slice-{wid}.json")
        if not os.path.exists(path):
            res = subprocess.run(
                [sys.executable, os.path.abspath(__file__), trace_dir, path],
                env=procs.child_env("cpu"), cwd=procs.ROOT,
                capture_output=True, text=True, timeout=900)
            if res.returncode != 0:
                raise procs.BenchFailure(
                    f"slice reduction failed:\n{res.stderr[-2000:]}")
        with open(path) as f:
            red = json.load(f)
        if not red.get("found"):
            return None
        add_up(total, {k: v for k, v in red.items()
                       if isinstance(v, (float, dict))})
    return total or None


def share_pct(run, part: str, whole: str = "window_s") -> Optional[float]:
    """``part`` of the account over ``whole`` of it, in per cent."""
    acc = slice_account(run)
    if not acc or not acc.get(whole) or acc.get(part) is None:
        return None
    return 100.0 * acc[part] / acc[whole]


def trace_overhang_share_pct(run) -> Optional[float]:
    """The part of ``tracered``'s window that is not the slice: host planes
    running on while the profiler starts and is written out."""
    inside = share_pct(run, "window_s", "tracered_window_s")
    return None if inside is None else 100.0 - inside


def under_span_share_pct(run, part: str) -> Optional[float]:
    """``part`` of the account over the window, where the engine thread
    opened spans in the slice at all (``engine_work_s``: the thread under a
    span that is no wait; ``gaps_under_work_s`` / ``gaps_under_wait_s``:
    the device's idle gaps of ``MIN_GAP_NS`` or more by the span over their
    midpoint)."""
    acc = slice_account(run)
    if not acc or not acc.get("engine_spans_s"):
        return None
    return share_pct(run, part)


def main(argv: Sequence[str]) -> int:
    trace_dir, out_path = argv[:2]
    trace = load_xplane(trace_dir)
    trace["scoped_ops"] = scoped_ops(trace_dir)
    counters = cfg = None
    try:
        with open(os.path.join(trace_dir, "counters.json")) as f:
            counters = json.load(f)
    except (OSError, ValueError):
        pass
    # the op classes' K/V slice is told by the configuration's head shape
    cfg_path = os.path.join(os.path.dirname(trace_dir), "config.json")
    if os.path.exists(cfg_path):
        with open(cfg_path) as f:
            cfg = json.load(f)
    with open(out_path, "w") as f:
        json.dump(reduce_slice(trace, counters, cfg), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
