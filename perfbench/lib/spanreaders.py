"""Shared arithmetic of the readers of the program's own spans, marks and
counters (``metrics/coord.pool_wait*``, ``pump.inbox_wait*``, ``engine.*``,
``kv.copy_time_share.*``,
``setup.backend_*``). Like
``readers.py``: a reader returns ``None`` when what it reads is not there —
a program without these marks (an earlier commit) then simply leaves the
metric out."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Any, Dict, List, Optional

from . import procs, readers
from .stats import mean

HERE = os.path.dirname(os.path.abspath(__file__))

# the marks that tile a streamed request's time to its first frame, in
# order; each span is named for what the request waits for in it
TILE = [("pool_wait", "dispatched", "conn_acquired"),
        ("inbox_wait", "worker.received", "worker.submitted"),
        ("queue_wait", "worker.submitted", "worker.admitted"),
        ("prefill_to_first_token", "worker.admitted", "worker.first_token"),
        ("first_frame_lag", "worker.first_token", "worker.first_frame_sent")]


def span_ms(run, start: str, end: str) -> List[float]:
    """``end - start`` of two RequestTrace marks, over the good requests
    the run judges whose trace has both."""
    out = []
    for r in readers.good(run):
        a, b = r.trace.get(start), r.trace.get(end)
        if a is not None and b is not None:
            out.append((b - a) * 1e3)
    return out


def span_p50_ms(run, start: str, end: str) -> Optional[float]:
    return readers.pct(span_ms(run, start, end), 50)


def tile_coverage(run) -> Optional[Dict[str, Any]]:
    """Per request: the five spans of ``TILE`` summed over its
    ``received -> first_frame``; the median of that share and of each
    span. Written next to the traces (``ttft-split.json``) for the reader
    of a run, not reported as a metric."""
    shares: List[float] = []
    spans: Dict[str, List[float]] = {name: [] for name, _a, _b in TILE}
    for r in readers.good(run):
        t = r.trace
        if "first_frame" not in t or any(
                a not in t or b not in t for _n, a, b in TILE):
            continue
        parts = {name: (t[b] - t[a]) * 1e3 for name, a, b in TILE}
        total = (t["first_frame"] - t.get("received", 0.0)) * 1e3
        if total <= 0:
            continue
        shares.append(100.0 * sum(parts.values()) / total)
        for name, v in parts.items():
            spans[name].append(v)
    if not shares:
        return None
    out = {"requests": len(shares),
           "covered_share_p50_pct": readers.pct(shares, 50),
           "received_to_first_frame_p50_ms": readers.pct(
               span_ms(run, "received", "first_frame"), 50),
           "p50_ms": {n: readers.pct(v, 50) for n, v in spans.items()}}
    work = work_dir(run)
    if work:
        with open(os.path.join(work, "ttft-split.json"), "w") as f:
            json.dump(out, f)
    return out


def coord_gauge_mean(run, key: str) -> Optional[float]:
    """Mean over the window's samples of one gauge of the coordinator's
    ``stats`` RPC."""
    vals = [float(s["coord"][key]) for s in run.samples
            if key in (s.get("coord") or {})]
    return mean(vals) if vals else None


def _compile(metrics: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    return (metrics.get("device") or {}).get("compile")


def compile_delta(run, key: str) -> Optional[float]:
    """Growth across the window of one of the workers' compile counters
    (``metrics`` RPC, ``device.compile``), summed over workers."""
    total = 0.0
    for wid, after in run.workers_after.items():
        a, b = _compile(after), _compile(run.workers_before.get(wid) or {})
        if a is None or b is None or key not in a:
            return None
        total += a[key] - b[key]
    return total if run.workers_after else None


def compile_at_open(run, key: str) -> Optional[float]:
    """A compile counter as the window opened: process start to window
    open is set-up. The largest over the workers (they start together)."""
    vals = [c[key] for c in map(_compile, run.workers_before.values())
            if c is not None and key in c]
    return float(max(vals)) if vals else None


def work_dir(run) -> Optional[str]:
    for d in run.trace_dirs.values():
        return os.path.dirname(d)
    return None


def host_spans(run) -> Optional[Dict[str, Any]]:
    """``hostspans.reduce_spans`` of every worker's traced slice, summed
    over the workers. Each trace is reduced once, by a child process (the
    benchmark process never imports jax), and kept as
    ``hostspans-<wid>.json`` in the run's work directory for the other
    readers. ``None`` without a trace, or when no engine thread opened a
    span in it."""
    total: Dict[str, float] = {}
    for wid, trace_dir in run.trace_dirs.items():
        path = os.path.join(os.path.dirname(trace_dir),
                            f"hostspans-{wid}.json")
        if not os.path.exists(path):
            res = subprocess.run(
                [sys.executable, os.path.join(HERE, "hostspans.py"),
                 trace_dir, path], env=procs.child_env("cpu"),
                cwd=procs.ROOT, capture_output=True, text=True, timeout=600)
            if res.returncode != 0:
                raise procs.BenchFailure(
                    f"host-span reduction failed:\n{res.stderr[-2000:]}")
        with open(path) as f:
            red = json.load(f)
        if not red.get("engine_thread_found"):
            return None
        for key in ("window_s", "engine_busy_s", "idle_gap_s",
                    "idle_attributed_s", "device_busy_s"):
            total[key] = total.get(key, 0.0) + red[key]
        if red["kv_copy_s"] is not None:
            total["kv_copy_s"] = total.get("kv_copy_s", 0.0) + red["kv_copy_s"]
    return total or None


def host_busy_share_pct(run) -> Optional[float]:
    hs = host_spans(run)
    if not hs or not hs["window_s"]:
        return None
    return 100.0 * hs["engine_busy_s"] / hs["window_s"]


def kv_copy_share_pct(run) -> Optional[float]:
    """Device self time of the ops under the model programs'
    ``attn.kv_gather`` and ``attn.kv_update`` scopes over all device self
    time; ``None`` for a program without those scopes."""
    hs = host_spans(run)
    if not hs or "kv_copy_s" not in hs or not hs["device_busy_s"]:
        return None
    return 100.0 * hs["kv_copy_s"] / hs["device_busy_s"]
