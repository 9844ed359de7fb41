"""Reduction of a profiler trace to numbers: device busy and idle time,
program and kernel time, the classes of device operations, and the idle gaps
by what the host was doing in them.

Works on a neutral form of the trace — ``{"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, dur_ns], ...]}]}]}`` — which
``load_xplane`` reads from the ``.xplane.pb`` file the JAX profiler writes
(with ``jax.profiler.ProfileData``, nothing else) and which the test keeps a
small recorded piece of. Run as a script (a child process of ``run.py``, so
the benchmark process itself never imports jax)::

    python perfbench/lib/tracered.py <trace_dir> <config.json> <out.json>
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]            # name, start_ns, dur_ns

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
MIN_GAP_NS = 20_000.0                        # shorter idle is launch latency


def load_xplane(trace_dir: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                      for ev in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [p for p in trace["planes"]
            if p["name"].startswith("/device:") and any(
                ln["name"] == OPS_LINE for ln in p["lines"])]


def line_events(plane: Dict[str, Any], name: str) -> List[Event]:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return sorted((tuple(e) for e in ln["events"]),
                          key=lambda e: e[1])
    return []


def union_intervals(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals of possibly nested or overlapping
    events, ordered."""
    out: List[Tuple[float, float]] = []
    for _n, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def trace_span(trace: Dict[str, Any]) -> Tuple[float, float]:
    """First start and last end over every event of every plane: the
    traced window."""
    lo, hi = float("inf"), 0.0
    for p in trace["planes"]:
        for ln in p["lines"]:
            for _n, start, dur in ln["events"]:
                lo = min(lo, start)
                hi = max(hi, start + dur)
    return lo, hi


def leaf_ops(ops: Sequence[Event]) -> List[Event]:
    """Self time of each op: an enclosing op (a while loop, a call) is cut
    down to the parts no later-starting op inside it covers, so that the
    classes sum to the busy time and nothing is counted twice."""
    out: List[Event] = []
    stack: List[List[Any]] = []              # [name, end, self_ns, cursor]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, self_ns, cursor = stack.pop()
            self_ns += max(0.0, end - cursor)
            out.append((name, end, self_ns))
            if stack:
                stack[-1][3] = max(stack[-1][3], end)

    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            parent = stack[-1]
            parent[2] += max(0.0, start - parent[3])
            parent[3] = max(parent[3], start)
        stack.append([name, start + dur, 0.0, start])
    close(float("inf"))
    return out                               # (name, end_ns, self_ns)


def op_parts(name: str) -> Tuple[str, str]:
    """An "XLA Ops" event is named by its HLO text, ``%lhs = shape
    opcode(operands...)``: returns ``(lhs, shape)``. Only these two are
    matched — the operand list names other ops."""
    lhs, _, rest = name.partition(" = ")
    return lhs.lstrip("%"), rest.split(" ", 1)[0]


def op_classes(cfg: Dict[str, Any]) -> List[Tuple[str, str, str]]:
    """Device-op classes as ``(label, lhs pattern, shape pattern)``, first
    match wins. The int4 matmul is the program's Mosaic kernel; the
    per-layer slice of the dense K/V decode buffer is recognised by the
    configuration's own K/V head count and head size; the rest are XLA's
    names."""
    kv_tail = ""
    if cfg.get("num_key_value_heads") and cfg.get("head_dim"):
        kv_tail = rf",{cfg['num_key_value_heads']},{cfg['head_dim']}\]"
    return [
        ("int4_matmul", r"int4", ""),
        ("kv_context_slice", r"dynamic[-_]slice", kv_tail or r"^$"),
        ("dynamic-update-slice", r"dynamic[-_]update[-_]slice", ""),
        ("dynamic-slice", r"dynamic[-_]slice", ""),
        ("copy_transpose", r"^(copy|transpose)", ""),
        ("pad_concatenate", r"^(pad|concatenate)", ""),
        ("reduce_fusions", r"reduce", ""),
        ("loop_control", r"^(while|conditional|call)", ""),
        ("other_kernels", r"custom[-_]call", ""),
        ("fusions", r"fusion", ""),
    ]


def op_class(name: str, classes: Sequence[Tuple[str, str, str]]) -> str:
    lhs, shape = op_parts(name)
    for label, lhs_rx, shape_rx in classes:
        if re.search(lhs_rx, lhs) and (not shape_rx
                                       or re.search(shape_rx, shape)):
            return label
    return "other"


def program_kind(module_name: str) -> str:
    n = module_name.lower()
    if "decode" in n:
        return "decode"
    if "prefill" in n:
        return "prefill"
    return "other"


def host_span_at(host_lines: List[Tuple[List[float], List[Event]]],
                 t: float) -> str:
    """The deepest host event covering time ``t``, among those the caller
    kept (the program's own Python functions)."""
    best: Optional[Event] = None
    for starts, events in host_lines:
        i = bisect.bisect_right(starts, t) - 1
        for j in range(i, max(-1, i - 256), -1):
            name, start, dur = events[j]
            if start + dur >= t:
                if best is None or start > best[1]:
                    best = events[j]
                break
    if best is None:
        return "no_host_span"
    return re.sub(r"[^A-Za-z0-9_.]+", "_",
                  re.sub(r":\d+", "", best[0])).strip("_")[:60]


def host_python_lines(trace: Dict[str, Any], program_files: Sequence[str]
                      ) -> List[Tuple[List[float], List[Event]]]:
    """Per host thread, the Python-tracer events (``$file.py:line func``)
    from the program's own files (all of them if none are named), by start."""
    out = []
    for p in trace["planes"]:
        if not p["name"].startswith("/host:"):
            continue
        for ln in p["lines"]:
            evs = sorted((tuple(e) for e in ln["events"]
                          if e[0].startswith("$") and (
                              not program_files
                              or e[0][1:].split(":")[0] in program_files)),
                         key=lambda e: e[1])
            if evs:
                out.append(([e[1] for e in evs], evs))
    return out


def reduce_plane(plane: Dict[str, Any], classes_of, host_lines,
                 calls_per_step: int) -> Dict[str, Any]:
    """One device: seconds busy, per op class (self time), per program kind,
    in the int4 kernel per program kind, between programs, and in idle gaps
    by host span."""
    ops = line_events(plane, OPS_LINE)
    mods = line_events(plane, MODULE_LINE)
    busy = union_intervals(ops)
    out: Dict[str, Any] = {
        "devices": 1, "busy_s": sum(e - s for s, e in busy) / 1e9,
        "classes": {}, "idle_gaps": {}, "program_s": {}, "program_calls": {},
        "int4_kernel_s": {}, "int4_kernel_calls": {}}

    def add(key: str, label: str, amount: float) -> None:
        out[key][label] = out[key].get(label, 0.0) + amount

    mod_starts = [m[1] for m in mods]
    for name, end, self_ns in leaf_ops(ops):
        label = op_class(name, classes_of)
        add("classes", label, self_ns / 1e9)
        if label == "int4_matmul":
            i = bisect.bisect_right(mod_starts, end - 1.0) - 1
            kind = "other"
            if i >= 0 and mods[i][1] + mods[i][2] >= end - 1.0:
                kind = program_kind(mods[i][0])
            add("int4_kernel_s", kind, self_ns / 1e9)
            add("int4_kernel_calls", kind, 1)
    for name, _start, dur in mods:
        add("program_s", program_kind(name), dur / 1e9)
        add("program_calls", program_kind(name), 1)
    mod_busy = union_intervals(mods)
    out["between_programs_s"] = sum(
        s1 - e0 for (_s0, e0), (s1, _e1) in zip(mod_busy, mod_busy[1:])) / 1e9
    for (_s0, e0), (s1, _e1) in zip(busy, busy[1:]):
        if s1 - e0 >= MIN_GAP_NS:
            add("idle_gaps", host_span_at(host_lines, (e0 + s1) / 2),
                (s1 - e0) / 1e9)
    out["decode_steps"] = (out["int4_kernel_calls"].get("decode", 0.0)
                           / calls_per_step if calls_per_step else 0.0)
    return out


def average(reductions: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Several devices' (or workers') reductions as one: ``devices`` summed,
    every other number averaged over them (a missing label counts 0)."""
    n = len(reductions)
    out: Dict[str, Any] = {}
    for r in reductions:
        for key, val in r.items():
            if isinstance(val, dict):
                tgt = out.setdefault(key, {})
                for label, v in val.items():
                    tgt[label] = tgt.get(label, 0.0) + v / n
            elif key == "devices":
                out[key] = out.get(key, 0) + val
            else:
                out[key] = out.get(key, 0.0) + val / n
    return out


def reduce_trace(trace: Dict[str, Any], calls_per_step: int = 0,
                 program_files: Sequence[str] = (),
                 cfg: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """All the trace gives, averaged over the device planes that ran ops.

    ``calls_per_step`` is the number of int4 kernel calls in one forward
    pass (from the configuration): decode steps are counted as int4 kernel
    calls inside decode programs over it. ``program_files`` are the base
    names of the program's own source files: idle gaps are named after the
    deepest host function from one of them (all Python events if empty)."""
    planes = device_planes(trace)
    if not planes:
        return {"devices": 0}
    classes_of = op_classes(cfg or {})
    host_lines = host_python_lines(trace, program_files)
    lo, hi = trace_span(trace)
    out = average([reduce_plane(p, classes_of, host_lines, calls_per_step)
                   for p in planes])
    out["window_s"] = (hi - lo) / 1e9
    return out


def top(d: Dict[str, float], n: int = 10) -> List[List[Any]]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def main(argv: Sequence[str]) -> int:
    trace_dir, config_path, out_path = argv[:3]
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from perfbench.lib import families

    with open(config_path) as f:
        cfg = json.load(f)
    trace = load_xplane(trace_dir)
    calls = (int(cfg.get("_int4_calls_per_step", 0))
             or families.int4_calls_per_pass(cfg))
    with open(out_path, "w") as f:
        json.dump(reduce_trace(trace, calls,
                               cfg.get("_program_files", ()), cfg), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
