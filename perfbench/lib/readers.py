"""Shared arithmetic of the per-layer readers under ``metrics/``. A reader
takes the ``RunData`` of one run and returns a number, or ``None`` when what
it reads is not there (the harness then leaves the metric out)."""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

from . import opcount, peaks
from .stats import mean, percentile, tpot_s


def good(run) -> List[Any]:
    v = int(run.config["vocab_size"])
    return [r for r in run.judged() if not r.failure(v)]


def ttfts_ms(run) -> List[float]:
    return [(r.frames[0][0] - r.due) * 1e3 for r in good(run) if r.frames]


def tpots_ms(run) -> List[float]:
    out = []
    for r in good(run):
        t = tpot_s(r.frames)
        if t is not None:
            out.append(t * 1e3)
    return out


def pct(values: List[float], q: float) -> Optional[float]:
    return percentile(values, q) if values else None


def engine_ttfts_ms(run) -> List[float]:
    """Worker receive -> first token, from the worker's own trace offsets."""
    out = []
    for r in good(run):
        off = r.trace.get("worker.first_token")
        base = r.trace.get("worker.received")
        if off is not None and base is not None:
            out.append((off - base) * 1e3)
    return out


def sampled(run, fn: Callable[[Dict[str, Any]], Optional[float]]
            ) -> Optional[float]:
    """Mean over the window's samples of ``fn(worker metrics)``, itself
    averaged over the workers."""
    vals = []
    for s in run.samples:
        per = [fn(m) for m in s["workers"].values()]
        per = [p for p in per if p is not None]
        if per:
            vals.append(sum(per) / len(per))
    return mean(vals)


def counter_delta(run, path: List[str]) -> Optional[float]:
    """Sum over workers of a counter's growth across the window."""
    total = 0.0
    for wid, after in run.workers_after.items():
        before = run.workers_before.get(wid)
        if before is None:
            return None
        a, b = after, before
        for k in path:
            a, b = a[k], b[k]
        total += a - b
    return total


def hbm_in_use_gb(run) -> Optional[float]:
    """``bytes_in_use`` on the fullest chip when the window closed: what the
    served system holds under the cell's traffic (weights, KV pool, decode
    buffer, live transients). ``peak_bytes_in_use`` also keeps the highest
    transient of loading and warm-up."""
    used = [int((stats or {}).get("bytes_in_use", 0))
            for m in run.workers_after.values()
            for stats in ((m.get("device") or {}).get("memory") or {}).values()]
    return max(used) / 1e9 if used and max(used) else None


def decode_step_ms(run) -> Optional[float]:
    t = run.trace
    if not t or not t.get("decode_steps"):
        return None
    return 1e3 * t["program_s"].get("decode", 0.0) / t["decode_steps"]


def int4_roofline_pct(run) -> Optional[float]:
    """Least possible time of the decode steps' int4 matmuls over their
    measured kernel time. Rows = max_slots: a decode step computes every
    slot."""
    t = run.trace
    if not t or not t.get("decode_steps") or \
            not t["int4_kernel_s"].get("decode"):
        return None
    pk = peaks.peaks_for(run.device["kind"])
    cost = opcount.int4_step_cost(run.config,
                                  int(run.config["serve"]["max_batch_size"]))
    least, _bound = opcount.roofline_seconds(cost, pk)
    return 100.0 * least * t["decode_steps"] / t["int4_kernel_s"]["decode"]


def int4_time_share_pct(run) -> Optional[float]:
    t = run.trace
    if not t or not t.get("busy_s"):
        return None
    return 100.0 * t["classes"].get("int4_matmul", 0.0) / t["busy_s"]


def idle_share_pct(run) -> Optional[float]:
    t = run.trace
    if not t or not t.get("busy_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def between_programs_pct(run) -> Optional[float]:
    t = run.trace
    if not t or not t.get("busy_s"):
        return None
    return 100.0 * t["between_programs_s"] / t["window_s"]
