"""Cut the small recorded piece the host-span reduction's test keeps.

    python3 perfbench/tools/record_spans.py <trace_dir> <out.json> [ms] [skip_ms]

Takes ``ms`` milliseconds of the trace (default 400) starting ``skip_ms``
(default 1000) after the first device op, and writes it in ``tracered``'s
neutral form with only what ``hostspans.reduce_spans`` reads: the program's
host spans (``engine.*`` / ``pump.*``, clipped to the piece) on their
threads' lines, and each device plane's "XLA Ops" line with every run of ops
less than ``MIN_GAP_NS`` apart merged into one ``ops_run`` event — the
reduction sees the same idle gaps, the file stays a few kilobytes. The ops
named by scope path (``hostspans.scoped_ops``) are kept one by one, each
path cut down to the scope ``hostspans`` looks for in it, and only for the
piece's first ``SCOPED_MS`` milliseconds, about one decode step. The
expected answers go next to the piece, computed by the reduction as it is
now: the test then pins them.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib import hostspans, tracered  # noqa: E402


SCOPED_MS = 12.0


def cut(trace, ms: float, skip_ms: float):
    t0 = min((e[1] for p in tracered.device_planes(trace)
              for e in tracered.line_events(p, tracered.OPS_LINE)),
             default=0.0) + skip_ms * 1e6
    t1 = t0 + ms * 1e6
    planes = []
    for p in trace["planes"]:
        lines = []
        if p["name"].startswith("/device:"):
            ops = [e for e in tracered.line_events(p, tracered.OPS_LINE)
                   if t0 <= e[1] and e[1] + e[2] <= t1]
            runs = []
            for s, e in tracered.union_intervals(ops):
                if runs and s - runs[-1][1] < tracered.MIN_GAP_NS:
                    runs[-1][1] = e
                else:
                    runs.append([s, e])
            if runs:
                lines.append({"name": tracered.OPS_LINE, "events": [
                    ["ops_run", s - t0, e - s] for s, e in runs]})
        elif p["name"].startswith("/host:"):
            for ln in p["lines"]:
                evs = []
                for n, s, d in ln["events"]:
                    n = hostspans.span_name(n)
                    if not n.startswith(hostspans.PROGRAM_PREFIXES):
                        continue
                    s, e = max(s, t0), min(s + d, t1)
                    if e > s:
                        evs.append([n, s - t0, e - s])
                if evs:
                    lines.append({"name": ln["name"], "events": evs})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    t2 = t0 + SCOPED_MS * 1e6
    scoped = [[[next((c for c in hostspans.COPY_SCOPES if c in sc), ""),
                s - t0, d] for sc, s, d in ops if t0 <= s and s + d <= t2]
              for ops in trace.get("scoped_ops") or []]
    return {"planes": planes, "scoped_ops": [ops for ops in scoped if ops]}


def main(argv) -> int:
    trace_dir, out_path = argv[:2]
    ms = float(argv[2]) if len(argv) > 2 else 400.0
    skip_ms = float(argv[3]) if len(argv) > 3 else 1000.0
    trace = tracered.load_xplane(trace_dir)
    trace["scoped_ops"] = hostspans.scoped_ops(trace_dir)
    piece = cut(trace, ms, skip_ms)
    with open(out_path, "w") as f:
        json.dump({"trace": piece,
                   "expected": hostspans.reduce_spans(piece)}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
