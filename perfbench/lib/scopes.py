"""Device self time by the program's ``jax.named_scope`` names, the
arithmetic every family's scope readers share (a counter's growth over the
window and over the traced SLICE, the least seconds of a cost), and the scope
readers of the hybrid linear-attention family (``counts/bailing_hybrid.py``
``SCOPE_READERS``; the mHC family's ``lib/scopes_mhc.py`` reads through them
too).

A slice's decode steps are counted ONE way here, from the slice: the
engine's ``decode_steps`` between the two stamps of ``counters.json`` (which
the worker writes into the trace directory: its counters as they stood when
the trace began and when it was asked to end, at most one chunk after the
programs they count). Rows and experts are the same two stamps' growth, so a
share of a peak divides the slice's seconds by the slice's work. The stamps
close when the profiler is asked to end and the trace a chunk or two later:
a step reads up to a few per cent long and a share of a peak as much low,
never high.

An op's scope path is its ``tf_op`` (``jit(_decode_chunk)/.../moe.experts/
gmm/...``): ``hostspans.scoped_ops`` reads it from the ``.xplane.pb`` with
protobuf's runtime (no jax), ``tracered.leaf_ops`` gives each op its self
time. A path names its program, so a scope's seconds split into those of
decode programs and of the rest. Every reader returns ``None`` when what it
reads is not there: a program without these scopes or counters (an earlier
commit, another family) leaves the metric out.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional, Sequence

from . import families, hostspans, peaks, readers
from .procs import MODEL
from .tracered import leaf_ops

# (the last two are around the head of every model's programs: models/base.py
# and ops/sampling.py open them for all families)
SCOPES = ("moe.experts", "moe.route", "moe.shared", "attn.kda.step",
          "attn.kda.prefill", "attn.mla", "state.update", "head.unembed",
          "sample")
KERNEL = "gmm"          # the grouped matmul's own scope, inside moe.experts


def reduce_scopes(scoped_ops: Sequence[Sequence[Sequence[Any]]]
                  ) -> Dict[str, Any]:
    """``{"busy_s", "scopes": {name: {"decode": s, "other": s}}}`` over the
    device planes' ops; the first of ``SCOPES`` on an op's path takes it."""
    busy = 0.0
    out: Dict[str, Dict[str, float]] = {}
    for ops in scoped_ops:
        for path, _end, self_ns in leaf_ops([tuple(e) for e in ops]):
            busy += self_ns
            kind = "decode" if "decode" in path.lower() else "other"
            first = next((s for s in SCOPES if f"/{s}/" in path), None)
            if first is None:
                continue
            kernel = [KERNEL] if f"/{KERNEL}/" in path else []
            for name in [first] + kernel:
                d = out.setdefault(name, {"decode": 0.0, "other": 0.0})
                d[kind] += self_ns / 1e9
    return {"busy_s": busy / 1e9, "scopes": out}


def _add(into: Dict[str, Any], more: Dict[str, Any]) -> Dict[str, Any]:
    """``more`` added into ``into``: numbers add, dicts by key, lists (a
    kernel's ``[runs, seconds]``) by position."""
    for k, v in more.items():
        if isinstance(v, dict):
            _add(into.setdefault(k, {}), v)
        elif isinstance(v, list):
            have = into.setdefault(k, [0] * len(v))
            into[k] = [a + b for a, b in zip(have, v)]
        else:
            into[k] = into.get(k, 0) + v
    return into


def summed_reductions(run, stem: str, reduce) -> Dict[str, Any]:
    """A family's reduction of every worker's traced slice
    (``reduce(hostspans.scoped_ops(trace_dir))``, kept beside the trace as
    ``<stem>-<wid>.json``), added up over the workers. Every family's
    ``scope_seconds`` is this and its own test of whose trace it is."""
    total: Dict[str, Any] = {"busy_s": 0.0, "scopes": {}}
    for wid, trace_dir in run.trace_dirs.items():
        path = os.path.join(os.path.dirname(trace_dir), f"{stem}-{wid}.json")
        if not os.path.exists(path):
            with open(path, "w") as f:
                json.dump(reduce(hostspans.scoped_ops(trace_dir)), f)
        with open(path) as f:
            _add(total, json.load(f))
    return total


def scope_seconds(run) -> Optional[Dict[str, Any]]:
    """``reduce_scopes`` over the workers' traced slices; ``None`` without
    a trace or when no op carries one of the scopes."""
    total = summed_reductions(run, "scopes", reduce_scopes)
    return total if total["scopes"] and total["busy_s"] else None


def share_of(sc: Optional[Dict[str, Any]], names: Sequence[str]
             ) -> Optional[float]:
    """Device self time under ``names`` (scopes that do not nest in one
    another), both kinds of program, over all device self time; ``None``
    where the reduction holds none of them (not this program's scopes)."""
    if not sc or not any(n in sc["scopes"] for n in names):
        return None
    s = sum(sum(sc["scopes"].get(n, {}).values()) for n in names)
    return 100.0 * s / sc["busy_s"]


def step_ms(run, steps: Optional[float]) -> Optional[float]:
    """Device time of the slice's decode programs over its ``steps``."""
    if not steps:
        return None
    return 1e3 * run.trace["program_s"].get("decode", 0.0) / steps


def prefill_share_pct(run, sc: Optional[Dict[str, Any]]) -> Optional[float]:
    """The prefill programs' device time over the device's busy time, where
    the trace is the family's (``sc``)."""
    t = run.trace
    if not t or not t.get("busy_s") or sc is None:
        return None
    return 100.0 * t["program_s"].get("prefill", 0.0) / t["busy_s"]


def counts_with(run, name: str):
    """The run's family's ``counts`` module if it has ``name``."""
    counts = families.counts(run.config)
    return counts if hasattr(counts, name) else None


def share_pct(run, names: Sequence[str]) -> Optional[float]:
    return share_of(scope_seconds(run), names)


def counter(run, *path: str) -> Optional[float]:
    """Growth over the window of a counter of the model's ``get_metrics()``;
    ``None`` where the program has no such counter."""
    try:
        return readers.counter_delta(run, ["models", MODEL, *path])
    except (KeyError, TypeError):
        return None


def per_decode_step(run, *path: str) -> Optional[float]:
    """A counter's growth a decode step, both over the WINDOW."""
    steps = counter(run, "decode_steps")
    value = counter(run, *path)
    if not steps or value is None:
        return None
    return value / steps


def slice_counter(run, *path: str) -> Optional[float]:
    """Growth of a counter of the model's ``get_metrics()`` over the TRACED
    SLICE, summed over the workers: between the two stamps of
    ``counters.json`` in each trace directory. ``None`` without the file
    (an earlier program) or without the counter."""
    total = 0.0
    try:
        for trace_dir in run.trace_dirs.values():
            with open(os.path.join(trace_dir, "counters.json")) as f:
                stamps = json.load(f)
            a, b = stamps["stop"], stamps["start"]
            for k in ("models", MODEL, *path):
                a, b = a[k], b[k]
            total += a - b
    except (OSError, ValueError, KeyError, TypeError):
        return None
    return total if run.trace_dirs else None


def per_slice_step(run, *path: str) -> Optional[float]:
    """``slice_counter`` a decode step between the same two stamps."""
    steps = slice_counter(run, "decode_steps")
    value = slice_counter(run, *path)
    if not steps or value is None:
        return None
    return value / steps


def least_seconds(run, cost: Dict[str, float]) -> float:
    """The least time the run's chip could take for ``cost``'s bytes and
    operations (``lib/peaks.py``)."""
    pk = peaks.peaks_for(run.device["kind"])
    return max(cost["bytes"] / pk["hbm_bytes_per_s"],
               cost["flops"] / pk["bf16_flops_per_s"])


def held_assignment_share_pct(run) -> Optional[float]:
    """Top-k choices that landed on the experts this chip holds over all
    choices (prefill and decode), across the window."""
    held = counter(run, "moe", "assignments_held")
    total = counter(run, "moe", "assignments_total")
    return 100.0 * held / total if held is not None and total else None


def decode_steps_in_slice(run) -> Optional[float]:
    """Decode steps the traced slice ran: the engine's ``decode_steps``
    between the slice's two stamps."""
    return slice_counter(run, "decode_steps") if run.trace else None


def decode_step_ms(run) -> Optional[float]:
    return step_ms(run, decode_steps_in_slice(run))


def expert_stream_roofline_pct(run, scope: str = "moe.experts"
                               ) -> Optional[float]:
    """Least time the chip could take to read the experts that got a row
    (counter ``moe.experts_touched``: touched, never all held) and their
    rows, both the SLICE's own, over the decode programs' self time under
    ``scope`` (``KERNEL``: the grouped matmul alone)."""
    sc = scope_seconds(run)
    touched = slice_counter(run, "moe", "experts_touched")
    rows = slice_counter(run, "moe", "decode_assignments_held")
    if not sc or touched is None or rows is None:
        return None
    seconds = sc["scopes"].get(scope, {}).get("decode")
    if not seconds:
        return None
    counts = families.counts(run.config)
    return 100.0 * least_seconds(run, counts.expert_stream_cost(
        run.config, touched, rows)) / seconds
