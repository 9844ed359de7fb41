"""Where the persistent XLA compile cache lives — decided from outside.

Every entry point that compiles (``cli.worker``, ``bench.py``, the chip
smoke's children, the example scripts) calls ``configure_compile_cache()``
once before its first jit. The directory is part of the cache key, so it
must not move between runs: no temp name, pid or time goes into it.

- ``JAX_COMPILATION_CACHE_DIR`` exported: jax reads it itself; nothing is
  set in code, and the cache is written there and nowhere else.
- otherwise: one fixed, git-ignored directory at the root of the checkout.

Only the directory is set. Whether the cache is ON stays with whoever owns
the process: ``tests/conftest.py`` turns it off for the CPU suite
(``jax_enable_compilation_cache=False``) and this helper leaves that alone.

The same call counts what the compiler does (``compile_counters``) and keeps
a bounded log of it by program (``compile_log``): jax's own monitoring
events, so a compile under the persistent cache's time threshold, which
never becomes a cache entry, is counted and named too.
"""

from __future__ import annotations

import itertools
import os
import pathlib
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Tuple

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")
LOG_CAPACITY = 1024

# jax's duration events that carry ``fun_name`` (``dispatch.py``'s
# ``LogElapsedTimeContextManager``): event -> (phase, count key, seconds key)
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("trace", "traces", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("lower", "lowerings", "lower_s"),
    "/jax/core/compile/backend_compile_duration":
        ("backend_compile", "backend_compiles", "backend_compile_s"),
}
# the persistent cache's two durations (``compiler.py``), fired on a hit
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "compile_time_saved_s",
}
_EVENT_KEYS = {"/jax/compilation_cache/cache_hits": "cache_hits",
               "/jax/compilation_cache/cache_misses": "cache_misses"}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
DURATION_EVENTS = (*_PHASES, *_CACHE_SECONDS)
# process-wide, like the event stream they count; written only by jax's
# monitoring callbacks (under the GIL, on the thread that compiles), read by
# ``compile_counters`` / ``compile_log``
_COUNTS: Dict[str, Any] = {
    "backend_compiles": 0, "backend_compile_s": 0.0,
    "cache_hits": 0, "cache_misses": 0,
    "traces": 0, "trace_s": 0.0, "lowerings": 0, "lower_s": 0.0,
    "cache_retrieval_s": 0.0, "compile_time_saved_s": 0.0}
_LOG: deque = deque(maxlen=LOG_CAPACITY)
_logged = 0            # records ever appended: the next record's index
# what the cache said since the last backend-compile record
_pending = {"cache": "off", "cache_retrieval_s": 0.0}
_thread = threading.local()      # see ``_nesting``
_installed = False


def _on_event(event: str, **_kw) -> None:
    key = _EVENT_KEYS.get(event)
    if key is not None:
        _COUNTS[key] += 1
        _pending["cache"] = "hit" if key == "cache_hits" else "miss"
    elif event == _CACHE_ASKED:
        _pending["cache"] = "unstored"


def _nesting() -> Dict[str, int]:
    """This thread's depth inside jax's timing contexts, by phase."""
    state = getattr(_thread, "state", None)
    if state is None:
        state = _thread.state = {
            phase: 0 for phase, _n, _s in _PHASES.values()}
    return state


def _on_scalar(event: str, _value: float, **_kw) -> None:
    """jax's timing context ENTERS (it sends its start as a scalar under
    the duration's own event name): one level deeper in that phase."""
    found = _PHASES.get(event)
    if found is not None:
        _nesting()[found[0]] += 1


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    global _logged
    cache_key = _CACHE_SECONDS.get(event)
    if cache_key is not None:
        _COUNTS[cache_key] += duration_secs
        if cache_key == "cache_retrieval_s":
            _pending["cache_retrieval_s"] += duration_secs
        return
    found = _PHASES.get(event)
    if found is None:
        return
    phase, n_key, s_key = found
    _COUNTS[n_key] += 1
    depth = _nesting()
    # (an event fed without its enter, as a test does, lies in nothing)
    depth[phase] = max(0, depth[phase] - 1)
    if depth[phase]:
        # an inner jit, traced inside an outer one's duration: the outer's
        # record will hold these seconds
        return
    rec: Dict[str, Any] = {
        "fun_name": str(kw.get("fun_name", "?")), "phase": phase,
        "t0": time.perf_counter() - duration_secs, "dur_s": duration_secs}
    if phase == "backend_compile":
        rec.update(_pending)
        _pending.update(cache="off", cache_retrieval_s=0.0)
    _COUNTS[s_key] += duration_secs
    _LOG.append(rec)
    _logged += 1


def install_compile_counters() -> None:
    """Register the ``jax.monitoring`` listeners once per process."""
    global _installed
    if _installed:
        return
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _installed = True


def compile_counters() -> dict:
    """What the compiler has done in this process since the listeners went
    in. ``backend_compiles`` / ``backend_compile_s`` count every program
    jax asked the backend for — one per new program shape, whether XLA
    compiled it or the persistent cache supplied it (``cache_hits``);
    ``cache_misses`` are the ones XLA compiled and the cache then stored.
    ``traces`` / ``trace_s`` and ``lowerings`` / ``lower_s`` are the two
    phases before it (Python tracing to a jaxpr; jaxpr to an MLIR module,
    Mosaic kernels included). The counts take every event; the seconds
    and the log leave out an event fired inside another of its phase (an
    inner ``jax.jit``, and every jitted ``jnp`` function, traced inside an
    outer one: thousands a program; told by the depth of jax's timing
    contexts on the thread), so they add up to wall time on the thread
    that compiled and the log holds programs, not their parts.
    ``cache_retrieval_s`` is the read of the persistent cache on a hit:
    jax 0.9.0 times it INSIDE the backend-compile event
    (``compile_or_get_cached`` runs under it), so it is a part of
    ``backend_compile_s``, not a fourth addend; ``compile_time_saved_s`` is
    what the cache's entries say their compiles had cost, less the reads."""
    install_compile_counters()
    return dict(_COUNTS)


def log_index() -> int:
    """The index the next log record will get (the integer a span reads
    when it opens: it grew if and only if something was traced, lowered or
    compiled since)."""
    return _logged


def compile_log(since: int = 0) -> Tuple[List[Dict[str, Any]], int]:
    """The log's records from index ``since`` on (those the bounded log
    still holds) and the next index: how a reader takes a delta. One
    record per outermost duration event: ``fun_name`` (``"?"`` where jax
    sent none), ``phase`` (``trace | lower | backend_compile``), ``t0`` and
    ``dur_s`` on ``time.perf_counter`` (stamped in the callback: start =
    now - duration), and for a backend compile ``cache``: ``hit`` (the persistent
    cache supplied it; ``cache_retrieval_s`` is the read), ``miss``
    (compiled and stored), ``unstored`` (compiled with the cache on, and
    under its thresholds: never an entry) or ``off`` (no cache was asked).
    A span that closes over a record writes its own name in as ``span``
    (``obs.timeline.HostSpan``)."""
    skip = max(0, since - (_logged - len(_LOG)))
    return list(itertools.islice(_LOG, skip, None)), _logged


def log_summary(records: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Seconds by phase over ``records``, the cache's answers and the
    programs the backend was asked for."""
    out: Dict[str, Any] = {
        "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
        "cache_retrieval_s": 0.0, "cache_hits": 0, "cache_misses": 0,
        "programs": []}
    for r in records:
        if r["phase"] != "backend_compile":
            out[r["phase"] + "_s"] += r["dur_s"]        # trace_s, lower_s
            continue
        out["compile_s"] += r["dur_s"]
        out["cache_retrieval_s"] += r["cache_retrieval_s"]
        out["cache_hits"] += r["cache"] == "hit"
        out["cache_misses"] += r["cache"] == "miss"
        if r["fun_name"] not in out["programs"]:
            out["programs"].append(r["fun_name"])
    return out


def configure_compile_cache() -> str:
    """Place the compile cache (see module docstring) and start the
    compile counters; returns the directory in use."""
    install_compile_counters()
    exported = os.environ.get(ENV_VAR)
    if exported:
        return exported
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
