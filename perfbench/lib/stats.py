"""Metric arithmetic: percentiles, per-request TPOT, tokens in the window,
and the spread the bounds are set from."""

from __future__ import annotations

import statistics
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile, q in [0, 100]; raises on no data."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def tpot_s(frames: Sequence[Tuple[float, int]]) -> Optional[float]:
    """Per-request time per output token: (t_last - t_first) / (n_out - 1),
    from the arrival times of the stream frames ``(t, n_tokens)``. ``None``
    for a reply of fewer than two tokens or one frame (no gap to measure)."""
    n_out = sum(n for _, n in frames)
    if n_out < 2 or len(frames) < 2:
        return None
    return (frames[-1][0] - frames[0][0]) / (n_out - 1)


def tokens_in_window(frames: Iterable[Tuple[float, int]], t0: float,
                     t1: float) -> int:
    """Tokens whose stream frame reached the client inside [t0, t1)."""
    return sum(n for t, n in frames if t0 <= t < t1)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median — the driver's measure of a metric's run-to-run spread."""
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else float("inf")


def mean(values: Sequence[float]) -> Optional[float]:
    xs: List[float] = list(values)
    return sum(xs) / len(xs) if xs else None
