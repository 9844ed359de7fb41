"""Request tracing: real request IDs propagated end-to-end with per-phase
timestamps.

The reference README promises "request tracing" (``README.md:18``) but only
``FakeModel`` fabricates a request_id that never leaves the mock
(``src/mock_models/fake_model.py:56``); the worker logs per-connection
durations (``src/worker.py:126-133``) with no correlation id. Here a
``RequestTrace`` travels with each request and records queue/prefill/decode
phase boundaries — the timestamps that produce TTFT and tok/s, the
BASELINE.json metrics.
"""

from __future__ import annotations

import bisect
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, Optional


def new_request_id() -> str:
    return uuid.uuid4().hex[:16]


# fixed histogram bucket bounds (seconds) shared with the metrics registry
# (obs/registry.py imports these as its default): LatencyStats snapshots
# carry cumulative counts over EXACTLY these bounds, so they export as
# OpenMetrics histograms without translation
LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0,
)


def format_bucket_bound(bound: float) -> str:
    """Canonical ``le`` label for a bucket bound (shortest float form)."""
    f = float(bound)
    if f == float("inf"):
        return "+Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


@dataclass
class RequestTrace:
    """Monotonic per-phase marks for one request's lifetime.

    Coordinator marks, in order: received, routed, dispatched,
    conn_acquired (streams), first_frame (streams), done; a batched
    request has queued / batched on its way to routed. The
    worker's marks arrive as offsets and land as ``worker.*``
    (``add_offsets``): received, submitted, admitted, first_token,
    first_frame_sent, done. ``docs/observability.md`` has the glossary.
    """

    request_id: str = field(default_factory=new_request_id)
    marks: Dict[str, float] = field(default_factory=dict)

    def __post_init__(self) -> None:
        if "received" not in self.marks:
            self.mark("received")

    def mark(self, phase: str) -> float:
        t = time.monotonic()
        self.marks.setdefault(phase, t)   # first mark wins (first_token semantics)
        return t

    def span(self, start: str, end: str) -> Optional[float]:
        if start in self.marks and end in self.marks:
            return self.marks[end] - self.marks[start]
        return None

    def add_offsets(self, prefix: str, offsets: Dict[str, float],
                    anchor: Optional[float] = None) -> None:
        """Merge REMOTE phase marks recorded as offsets on another clock.

        A worker cannot share this trace's ``time.monotonic`` epoch, so it
        reports phases as offsets from its own receive time; anchoring
        them at the moment this side had a connection to it (network
        transit folds into the remote ``received``≈0 offset) lands them
        on the local timeline. ``mark()``'s first-wins semantics are
        preserved via ``setdefault``. ``anchor`` is an absolute local
        monotonic stamp; defaults to the ``conn_acquired`` mark, else
        ``dispatched``, else ``received``."""
        if anchor is None:
            anchor = self.marks.get(
                "conn_acquired",
                self.marks.get("dispatched",
                               self.marks.get("received", 0.0)))
        for phase, off in offsets.items():
            if isinstance(off, (int, float)):
                self.marks.setdefault(f"{prefix}{phase}",
                                      anchor + float(off))

    def to_dict(self) -> Dict[str, float]:
        base = self.marks.get("received", 0.0)
        d = {k: v - base for k, v in self.marks.items()}
        d["request_id"] = self.request_id  # type: ignore[assignment]
        return d


class LatencyStats:
    """Streaming latency accumulator with percentile snapshots.

    Keeps a bounded reservoir so long-running workers don't grow
    unboundedly. Fixed-bucket counts (over ``LATENCY_BUCKETS``) accumulate
    over EVERY observation — unlike the percentiles, they never decimate —
    so ``snapshot()`` exports as a proper OpenMetrics histogram
    (cumulative buckets + sum + count).
    """

    def __init__(self, reservoir: int = 4096,
                 buckets: tuple = LATENCY_BUCKETS) -> None:
        self._samples: list[float] = []
        self._reservoir = reservoir
        self._buckets = tuple(sorted(float(b) for b in buckets))
        self._bucket_counts = [0] * (len(self._buckets) + 1)  # +Inf tail
        self.count = 0
        self.total = 0.0

    def add(self, latency_s: float) -> None:
        self.count += 1
        self.total += latency_s
        self._bucket_counts[
            bisect.bisect_left(self._buckets, latency_s)] += 1
        if len(self._samples) < self._reservoir:
            self._samples.append(latency_s)
        else:
            # deterministic decimation: overwrite round-robin
            self._samples[self.count % self._reservoir] = latency_s

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def percentile(self, p: float) -> float:
        if not self._samples:
            return 0.0
        s = sorted(self._samples)
        idx = min(len(s) - 1, int(round(p / 100.0 * (len(s) - 1))))
        return s[idx]

    def bucket_counts(self) -> Dict[str, int]:
        """CUMULATIVE counts keyed by their ``le`` label (+Inf last)."""
        out: Dict[str, int] = {}
        cum = 0
        for bound, n in zip(self._buckets, self._bucket_counts):
            cum += n
            out[format_bucket_bound(bound)] = cum
        out["+Inf"] = self.count
        return out

    def snapshot(self) -> Dict[str, Any]:
        return {
            "count": self.count,
            "mean_s": self.mean,
            "p50_s": self.percentile(50),
            "p95_s": self.percentile(95),
            "p99_s": self.percentile(99),
            "sum_s": self.total,
            "buckets": self.bucket_counts(),
        }
