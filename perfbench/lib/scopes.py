"""Device self time by the program's ``jax.named_scope`` names, and the
arithmetic the ``*.ling`` readers share (``metrics/*.ling.py``).

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


def scope_seconds(run) -> Optional[Dict[str, Any]]:
    """``reduce_scopes`` summed over the workers' traced slices (kept as
    ``scopes-<wid>.json`` beside the trace); ``None`` without a trace or
    when no op carries one of the scopes."""
    total: Dict[str, Any] = {"busy_s": 0.0, "scopes": {}}
    for wid, trace_dir in run.trace_dirs.items():
        path = os.path.join(os.path.dirname(trace_dir), f"scopes-{wid}.json")
        if not os.path.exists(path):
            with open(path, "w") as f:
                json.dump(reduce_scopes(hostspans.scoped_ops(trace_dir)), f)
        with open(path) as f:
            red = json.load(f)
        total["busy_s"] += red["busy_s"]
        for name, d in red["scopes"].items():
            t = total["scopes"].setdefault(name, {"decode": 0.0, "other": 0.0})
            for kind, s in d.items():
                t[kind] += s
    return total if total["scopes"] and total["busy_s"] else None


def share_pct(run, names: Sequence[str]) -> Optional[float]:
    """Device self time under ``names`` over all device self time."""
    sc = scope_seconds(run)
    if not sc:
        return None
    s = sum(sum(sc["scopes"].get(n, {}).values()) for n in names)
    return 100.0 * s / sc["busy_s"]


def counter(run, *path: str) -> Optional[float]:
    """Growth over the window of a counter of the model's ``get_metrics()``;
    ``None`` where the program has no such counter."""
    try:
        return readers.counter_delta(run, ["models", MODEL, *path])
    except (KeyError, TypeError):
        return None


def per_decode_step(run, *path: str) -> Optional[float]:
    steps = counter(run, "decode_steps")
    value = counter(run, *path)
    if not steps or value is None:
        return None
    return value / steps


def decode_steps_in_slice(run) -> Optional[float]:
    """Decode steps the traced slice ran: its decode programs (device trace)
    times the window's steps a decode chunk (counters)."""
    t = run.trace
    steps, chunks = counter(run, "decode_steps"), counter(run, "decode_chunks")
    if not t or not steps or not chunks:
        return None
    calls = t.get("program_calls", {}).get("decode")
    return calls * steps / chunks if calls else None


def decode_step_ms(run) -> Optional[float]:
    n = decode_steps_in_slice(run)
    if not n:
        return None
    return 1e3 * run.trace["program_s"].get("decode", 0.0) / n


def expert_stream_roofline_pct(run, scope: str = "moe.experts"
                               ) -> Optional[float]:
    """Least time the chip could take to read the experts that got a row
    (counter ``moe.experts_touched``: touched, never all held) and their
    rows, over the decode programs' self time under ``scope``."""
    sc = scope_seconds(run)
    n = decode_steps_in_slice(run)
    touched = per_decode_step(run, "moe", "experts_touched")
    rows = per_decode_step(run, "moe", "decode_assignments_held")
    if not sc or not n or touched is None or rows is None:
        return None
    seconds = sc["scopes"].get(scope, {}).get("decode")
    if not seconds:
        return None
    counts = families.counts(run.config)
    cost = counts.expert_stream_cost(run.config, touched * n, rows * n)
    pk = peaks.peaks_for(run.device["kind"])
    least = max(cost["bytes"] / pk["hbm_bytes_per_s"],
                cost["flops"] / pk["bf16_flops_per_s"])
    return 100.0 * least / seconds
