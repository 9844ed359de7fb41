"""Look at a trace by hand, and cut the small recorded piece the reduction's
test keeps.

    python3 perfbench/tools/record_slice.py <trace_dir> <out_dir> [ms]

Writes ``summary.txt`` (planes, lines, the heaviest event names of each),
``slice.json`` (every event of the device planes and of the host's Python
lines that starts within ``ms`` milliseconds, default 60, of the first device
op) and ``step.json`` (one whole decode step — the first complete inner
``while`` of a decode program — with the module and host events clipped to
it and names cut to 96 characters: small enough to keep as
``tests/recorded_slice.json``'s ``trace``) into ``out_dir``.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib import tracered  # noqa: E402


def main(argv) -> int:
    trace_dir, out_dir = argv[:2]
    ms = float(argv[2]) if len(argv) > 2 else 60.0
    os.makedirs(out_dir, exist_ok=True)
    trace = tracered.load_xplane(trace_dir)
    with open(os.path.join(out_dir, "summary.txt"), "w") as f:
        for p in trace["planes"]:
            f.write(f"PLANE {p['name']}\n")
            for ln in p["lines"]:
                tot = {}
                for n, _s, d in ln["events"]:
                    a = tot.setdefault(n, [0.0, 0])
                    a[0] += d
                    a[1] += 1
                f.write(f"  LINE {ln['name']} events={len(ln['events'])}\n")
                for n, (d, c) in sorted(tot.items(),
                                        key=lambda kv: -kv[1][0])[:40]:
                    f.write(f"    {d / 1e6:10.3f} ms x{c:<7d} {n[:140]}\n")
    t0 = min((e[1] for p in tracered.device_planes(trace)
              for ln in p["lines"] if ln["name"] == tracered.OPS_LINE
              for e in ln["events"]), default=0.0)
    t1 = t0 + ms * 1e6
    planes = []
    for p in trace["planes"]:
        dev = p["name"].startswith("/device:")
        lines = []
        for ln in p["lines"]:
            evs = [e for e in ln["events"] if t0 <= e[1] < t1
                   and (dev or ".py" in e[0] or e[0].startswith("$"))]
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    with open(os.path.join(out_dir, "slice.json"), "w") as f:
        json.dump({"planes": planes}, f)
    step = one_decode_step(trace)
    if step is not None:
        with open(os.path.join(out_dir, "step.json"), "w") as f:
            json.dump(step, f)
    return 0


def one_decode_step(trace):
    """The first inner ``while`` (one decode step over all layers) that lies
    inside another ``while`` on a device, with everything inside it."""
    for p in tracered.device_planes(trace):
        ops = tracered.line_events(p, tracered.OPS_LINE)
        whiles = [e for e in ops
                  if tracered.op_parts(e[0])[0].startswith("while")]
        inner = [w for w in whiles if any(
            o is not w and o[1] <= w[1] and o[1] + o[2] >= w[1] + w[2]
            for o in whiles)]
        if not inner:
            continue
        _n, s0, d0 = inner[0]
        e0 = s0 + d0

        def clip(events, whole):
            out = []
            for n, s, d in events:
                if whole and s >= s0 and s + d <= e0:
                    out.append([n[:96], s, d])
                elif not whole and s < e0 and s + d > s0:
                    a, b = max(s, s0), min(s + d, e0)
                    out.append([n[:96], a, b - a])
            return out

        planes = [{"name": p["name"], "lines": [
            {"name": tracered.MODULE_LINE, "events": clip(
                tracered.line_events(p, tracered.MODULE_LINE), False)},
            {"name": tracered.OPS_LINE, "events": clip(ops, True)}]}]
        for hp in trace["planes"]:
            if not hp["name"].startswith("/host:"):
                continue
            lines = [{"name": ln["name"], "events": clip(
                [e for e in ln["events"] if e[0].startswith("$")], False)}
                for ln in hp["lines"]]
            planes.append({"name": hp["name"],
                           "lines": [ln for ln in lines if ln["events"]]})
        return {"planes": planes}
    return None


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
