"""StepTimeline: a ring-buffer recorder for engine dispatches, exported as
Chrome trace-event JSON (loadable in Perfetto / ``chrome://tracing``).

``jax.profiler`` captures the XLA/TPU device timeline; what it cannot show
is the ENGINE's view — which step was a mixed ragged dispatch vs a pure
decode chunk, how many prefill tokens rode along, what the KV pool and
host tier looked like at that moment, and which dispatches paid a first
-execution (compile) cost. This recorder captures exactly that, cheaply
(one small dict appended to a bounded deque per dispatch — against step
times in the tens of milliseconds), and brackets cleanly around the
worker's ``jax.profiler`` start/stop hooks so the two timelines cover the
same window.

Trace-event mapping: each step is a complete event (``"ph": "X"``) with
microsecond ``ts``/``dur`` relative to the timeline's epoch; markers are
instant events (``"ph": "i"``). Event ``args`` carry the per-step payload
(rows, prefill tokens, pool occupancy, ``compile``) and show up in the
Perfetto slice-details pane.

Host spans (``host_span``) are the one way the program opens a span on
the engine thread: each is a ``jax.profiler.TraceAnnotation`` — so while a
profile runs it sits in the ``.xplane.pb`` on the device planes' clock —
and, when the engine keeps a ring, one record of that ring. ``clock_anchor``
ties the ring's ``perf_counter`` clock to the profiler's.
"""

from __future__ import annotations

import json
import sys
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..utils import compile_cache

_annotation_cls: Any = None
# a TraceAnnotation's args travel as ``#k=v,k=v#`` text behind its name
_ANNOTATION_UNSAFE = str.maketrans({",": ";", "#": "~"})


def _trace_annotation() -> Any:
    """``jax.profiler.TraceAnnotation`` once this process has imported jax
    (``obs`` never does: a process without jax has no profiler to write
    to), else ``None``."""
    global _annotation_cls
    if _annotation_cls is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        _annotation_cls = jax.profiler.TraceAnnotation
    return _annotation_cls


def compile_keys(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """What a span's ring record says of the compile-log records that
    closed inside it: ``compile_s`` / ``trace_s`` / ``lower_s``, the
    ``programs`` the backend was asked for and ``cache`` (each one's
    answer); ``compile=True`` when there was such a program."""
    parts = compile_cache.log_summary(records)
    keys: Dict[str, Any] = {"trace_s": parts["trace_s"],
                            "lower_s": parts["lower_s"]}
    if parts["programs"]:
        keys.update(compile=True, compile_s=parts["compile_s"],
                    programs=parts["programs"],
                    cache=[r["cache"] for r in records
                           if r["phase"] == "backend_compile"])
    return keys


class StepTimeline:
    """Bounded per-engine step recorder with Chrome trace export."""

    def __init__(self, capacity: int = 4096, name: str = "engine") -> None:
        self.name = name
        self.capacity = int(capacity)
        self._events: deque = deque(maxlen=max(1, self.capacity))
        self._epoch = time.perf_counter()
        if "jax" in sys.modules:
            # the spans of this ring say what compiled inside them
            compile_cache.install_compile_counters()
        self._capture_from: Optional[float] = None
        self._dropped = 0
        self._open: List[str] = []       # names of the spans open now
        self._anchors: List[Dict[str, Any]] = []

    def __len__(self) -> int:
        return len(self._events)

    # -- recording ---------------------------------------------------------

    def record(self, kind: str, t_start: float, dur_s: float,
               parent: Optional[str] = None, dispatch: bool = True,
               **args: Any) -> None:
        """One complete span: ``t_start`` is a ``time.perf_counter()``
        stamp, ``dur_s`` its wall duration, ``parent`` the span it was
        opened inside. ``dispatch`` marks a device-dispatch bracket: the
        records ``busy_gap_split`` reads."""
        if len(self._events) == self._events.maxlen:
            self._dropped += 1
        self._events.append({"name": kind, "t": float(t_start),
                             "dur": float(dur_s), "parent": parent,
                             "dispatch": dispatch, "args": args})

    def instant(self, kind: str, **args: Any) -> None:
        if len(self._events) == self._events.maxlen:
            self._dropped += 1
        self._events.append({"name": kind, "t": time.perf_counter(),
                             "dur": None, "args": args})

    # -- capture window (brackets jax.profiler start/stop) -----------------

    def start_capture(self) -> None:
        self._capture_from = time.perf_counter()
        self._anchors = []

    def add_anchor(self, anchor: Dict[str, Any]) -> None:
        """Keep a ``clock_anchor`` for the next dump's metadata."""
        self._anchors.append(dict(anchor))

    def stop_capture(self) -> List[Dict[str, Any]]:
        """Events recorded since ``start_capture()`` (all events if the
        window was never opened). Leaves the ring intact."""
        since, self._capture_from = self._capture_from, None
        return self.events(since=since)

    def events(self, since: Optional[float] = None) -> List[Dict[str, Any]]:
        evs = list(self._events)
        if since is not None:
            evs = [e for e in evs if e["t"] >= since]
        return evs

    # -- export ------------------------------------------------------------

    def to_chrome_trace(self, events: Optional[List[Dict[str, Any]]] = None,
                        pid: int = 0, tid: int = 0) -> Dict[str, Any]:
        """Chrome trace-event JSON object (the ``traceEvents`` container
        format Perfetto ingests directly)."""
        if events is None:
            events = self.events()
        out: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": self.name},
        }]
        for e in events:
            ts = (e["t"] - self._epoch) * 1e6
            if e["dur"] is None:
                out.append({"name": e["name"], "ph": "i", "s": "t",
                            "ts": ts, "pid": pid, "tid": tid,
                            "args": dict(e["args"])})
            else:
                args = dict(e["args"])
                if e.get("parent"):
                    args["parent"] = e["parent"]
                out.append({"name": e["name"], "ph": "X", "ts": ts,
                            "dur": e["dur"] * 1e6, "pid": pid, "tid": tid,
                            "args": args})
        return {
            "traceEvents": out,
            "displayTimeUnit": "ms",
            # ts 0 is ``epoch_perf_counter_ns`` on this process's
            # perf_counter; each anchor is the same instant on that clock
            # and, as a ``clock.anchor`` event, in the profiler's trace
            "metadata": {"timeline": self.name,
                         "dropped_events": self._dropped,
                         "epoch_perf_counter_ns": int(self._epoch * 1e9),
                         "clock_anchors": list(self._anchors)},
        }

    def dump(self, path: str,
             events: Optional[List[Dict[str, Any]]] = None) -> str:
        # tmp+rename so a crash mid-dump never leaves Perfetto a half-JSON
        from ..utils.files import atomic_write

        trace = self.to_chrome_trace(events)
        return atomic_write(path, lambda f: json.dump(trace, f))


class HostSpan:
    """One open host span; ``close()`` (or leaving the ``with``) ends it.
    Open at construction, so a dispatch site can open it where its bracket
    starts and close it where it ends without re-indenting what lies
    between. ``close(**more)`` adds what is only known at the end to the
    ring record (the annotation keeps what it was opened with). A span
    with a ring reads one integer when it opens
    (``compile_cache.log_index``); only if that grew by its close does it
    take the log's delta: the records no inner span claimed get its name
    (``span``: where a compile ran), and a dispatch bracket's ring record
    gets ``compile_keys``: that dispatch paid an XLA compile or a
    compile-cache load, of these programs, for this long. The compile ran
    inside the open annotation, so in a profile it lies under this span."""

    __slots__ = ("name", "t0", "args", "_tl", "_ann", "_parent", "_dispatch",
                 "_logged")

    def __init__(self, timeline: Optional[StepTimeline], name: str,
                 dispatch: bool, args: Dict[str, Any]) -> None:
        self.name = name
        self.args = args
        self._tl = timeline
        self._dispatch = dispatch
        self._parent: Optional[str] = None
        if timeline is not None:
            self._logged = compile_cache.log_index()
            if timeline._open:
                self._parent = timeline._open[-1]
            timeline._open.append(name)
        cls = _trace_annotation()
        self._ann = None
        if cls is not None:
            self._ann = cls(name, **{
                k: v.translate(_ANNOTATION_UNSAFE) if isinstance(v, str)
                else v for k, v in args.items()})
            self._ann.__enter__()
        self.t0 = time.perf_counter()

    def close(self, **more: Any) -> float:
        """End the span; returns the ``perf_counter`` stamp of its end."""
        now = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        tl, self._tl = self._tl, None
        if tl is not None:
            # tolerate a span an exception skipped: pop down to this one
            while tl._open and tl._open.pop() != self.name:
                pass
            if compile_cache.log_index() != self._logged:
                records, _ = compile_cache.compile_log(self._logged)
                for r in records:
                    r.setdefault("span", self.name)
                if self._dispatch:
                    more.update(compile_keys(records))
            tl.record(self.name, self.t0, now - self.t0,
                      parent=self._parent, dispatch=self._dispatch,
                      **{**self.args, **more})
        return now

    def __enter__(self) -> "HostSpan":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


def host_span(timeline: Optional[StepTimeline], name: str,
              dispatch: bool = False, **args: Any) -> HostSpan:
    """Open a host span on the calling thread (see the module docstring).
    ``timeline`` may be ``None`` (ring disabled): the annotation alone.
    Annotation values travel as ``k=v`` text: ``,`` and ``#`` in a string
    value (a caller's request id) are replaced there; the ring record
    keeps the value as given."""
    return HostSpan(timeline, name, dispatch, args)


def clock_anchor(at: str) -> Dict[str, Any]:
    """One ``clock.anchor`` annotation carrying this process's
    ``perf_counter_ns``: the same instant on the profiler's clock (the
    event's start) and on the clock of ``RequestTrace`` offsets and ring
    records (its stat). Returns the anchor for the ring dump."""
    ns = time.perf_counter_ns()
    host_span(None, "clock.anchor", perf_counter_ns=ns, at=at).close()
    return {"at": at, "perf_counter_ns": ns}


def busy_gap_split(events: List[Dict[str, Any]]) -> Dict[str, float]:
    """Decompose a window of dispatch events into busy (inside a dispatch
    bracket) vs gap (host time BETWEEN consecutive brackets) seconds —
    the roofline split (ISSUE 5): ``hbm_util`` regressions attribute to
    the kernel side when busy grew, to the scheduler/host side when gap
    grew. Instant markers (``dur is None``) and spans that are not
    dispatch brackets are skipped; overlapping brackets clamp the gap at
    zero rather than going negative.

    Returns busy_s, gap_s, bubble_frac = gap / (busy + gap), and the
    event count the split was computed over."""
    spans = sorted((e["t"], e["t"] + e["dur"]) for e in events
                   if e.get("dur") is not None and e.get("dispatch", True))
    busy = 0.0
    gap = 0.0
    prev_end: Optional[float] = None
    for t0, t1 in spans:
        busy += t1 - t0
        if prev_end is not None and t0 > prev_end:
            gap += t0 - prev_end
        prev_end = max(prev_end, t1) if prev_end is not None else t1
    total = busy + gap
    return {
        "busy_s": busy,
        "gap_s": gap,
        "bubble_frac": (gap / total) if total > 0 else 0.0,
        "n_events": len(spans),
    }
