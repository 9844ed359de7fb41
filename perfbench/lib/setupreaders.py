"""Readers of what the program says of its own set-up (PR 37): the worker's
``boot`` marks, ``model_setup`` (the warm-up grid's rounds and their four
parts) and the engine's ``compiles_after_warmup``, all in the
``metrics`` RPC the harness already keeps at the window's two edges
(``run.workers_before`` / ``workers_after``). Like ``readers.py``: ``None``
where the program has no such key (an earlier commit). No entry of
BENCHMARK.json reads these yet (PERF.md section 7 says why);
``tools/setup_split.py`` does."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from .procs import MODEL

WARMUP_PARTS = ("trace_s", "lower_s", "compile_s", "cache_retrieval_s",
                "run_s", "wall_s")


def _largest(values: List[Optional[float]]) -> Optional[float]:
    """The workers start together: the slowest one is the set-up's."""
    vals = [v for v in values if v is not None]
    return float(max(vals)) if vals and len(vals) == len(values) else None


def _setup(metrics: Dict[str, Any]) -> Dict[str, Any]:
    return (metrics.get("model_setup") or {}).get(MODEL) or {}


def boot_s(run) -> Optional[float]:
    """Process start to ``listening`` without the model's load: the
    interpreter's start, imports (jax among them), the server's start."""
    out = []
    for m in run.workers_before.values():
        at = (m.get("boot") or {}).get("listening")
        load = _setup(m).get("load_s")
        out.append(None if at is None or load is None else at - load)
    return _largest(out)


def build_s(run) -> Optional[float]:
    """``load_s`` less the warm-up: the engine factory (parameters drawn,
    quantised and placed, pools allocated) and, on the worker's first
    load, the backend's start (8-10 s on a v5e chip). The worker times no
    third number: this is the difference of its two."""
    out = []
    for m in run.workers_before.values():
        load, warm = _setup(m).get("load_s"), _setup(m).get("warmup_s")
        out.append(None if load is None or warm is None else load - warm)
    return _largest(out)


def warmup_part_s(run, part: str) -> Optional[float]:
    """One part of the warm-up grid summed over its rounds: ``trace_s``,
    ``lower_s``, ``compile_s`` (``cache_retrieval_s`` is the part of it
    that read the persistent cache), ``run_s``, or their sum ``wall_s``."""
    if part not in WARMUP_PARTS:
        raise ValueError(f"no warm-up part {part!r}")
    return _largest([(_setup(m).get("warmup") or {}).get(part)
                     for m in run.workers_before.values()])


def after_warmup(metrics: Dict[str, Any]) -> Dict[str, Any]:
    """The engine's ``compiles_after_warmup`` in one ``metrics`` reply."""
    return ((metrics.get("models") or {}).get(MODEL) or {}).get(
        "compiles_after_warmup") or {}


def prime_compile_s(run) -> Optional[float]:
    """Seconds tracing, lowering and compiling between the warm-up's end and
    the window's open: the programs the benchmark's ``prime`` (and its
    ramp) reached that the grid did not."""
    return _largest([after_warmup(m).get("seconds")
                     for m in run.workers_before.values()])


def compiles_after_warmup(run) -> Optional[List[Dict[str, Any]]]:
    """The programs the backend was asked for between the window's two
    stamps, each with its phase seconds, the cache's answer and the span
    it ran under: the entries of the engine's bounded list whose start
    lies between the two snapshots' ``mono``."""
    out: List[Dict[str, Any]] = []
    for wid, after in run.workers_after.items():
        before = run.workers_before.get(wid) or {}
        last = after_warmup(after).get("last")
        t0, t1 = before.get("mono"), after.get("mono")
        if last is None or t0 is None or t1 is None:
            return None
        out += [dict(e, worker=wid) for e in last if t0 <= e["t0"] < t1]
    return out if run.workers_after else None


def compiles_in_window(run) -> Optional[float]:
    """How many programs the backend was asked for while the window was
    open, by the engines' own count."""
    total = 0.0
    for wid, after in run.workers_after.items():
        a = after_warmup(after).get("count")
        b = after_warmup(run.workers_before.get(wid) or {}).get("count")
        if a is None or b is None:
            return None
        total += a - b
    return total if run.workers_after else None
