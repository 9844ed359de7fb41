"""One traced run's slice, every second of it under a name.

    python3 perfbench/tools/slice_account.py <cell> [--work DIR]
    python3 perfbench/tools/slice_account.py <cell> --run --seed N
        [--seconds 51] [--python-tracer 0|1]

Prints the account ``lib/slicereaders.py`` keeps of the run ``run.py --trace
1`` left in ``.work/<cell>/`` (``slice-<wid>.json``, reduced now if it is not
there): the window by the program's two anchors beside ``tracered``'s and the
overhang between them; the device's busy time by the program's scopes, what
runs under none by op class and by path; its idle time between programs by the
engine thread's span and inside programs by program kind and by the scope of
the op that follows; the engine thread's own seconds; the slice's decode steps
counted both ways. With ``--run`` it first makes that run, as ``run.py`` does;
``--python-tracer 0`` asks the worker's profiler for the program's spans
alone (the worker's ``profile`` RPC has the option; ``run.py``'s traced run
always takes the Python tracer too).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import session, slicereaders  # noqa: E402

WORK = os.path.join(ROOT, "perfbench", ".work")


def rows(title: str, parts: Dict[str, float], whole: float, unit: str = "s",
         most: int = 16) -> List[str]:
    out = [f"  {title}"]
    for name, v in sorted(parts.items(), key=lambda kv: -kv[1])[:most]:
        out.append(f"    {v:10.6f} {unit} {100.0 * v / whole:6.2f} %  {name}")
    return out


def account(acc: Dict[str, Any]) -> str:
    """The account as text; every share is of the slice's window."""
    w = acc["window_s"]
    a0, a1 = acc["anchors_ns"]

    def pct(v: float) -> str:
        return f"{v:10.6f} s {100.0 * v / w:6.2f} %"

    named = (acc["busy_s"] + acc["idle_in_programs_s"]
             + acc["idle_between_programs_s"])
    over = 1.0 - w / acc["tracered_window_s"]
    idle_t = 1.0 - (acc["busy_s"] + acc["device_outside_window_s"]) \
        / acc["tracered_window_s"]
    lines = [
        f"window        {w:10.6f} s  = [start anchor, stop anchor] "
        f"{(a1 - a0) / 1e9:.6f} s cut to the device's first and last op",
        f"  start anchor {acc['anchor_start_after_first_op_s']:+.6f} s after "
        f"the first op, stop anchor "
        f"{acc['anchor_stop_before_last_op_s']:+.6f} s before the last op "
        f"(device busy outside the anchors "
        f"{acc['device_outside_window_s']:.6f} s)",
        f"tracered's    {acc['tracered_window_s']:10.6f} s  overhang "
        f"{100.0 * over:.2f} % of it; its idle share {100.0 * idle_t:.2f} % "
        f"= overhang + (1 - overhang) x idle in window "
        f"{100.0 * (over + (1 - over) * acc['idle_s'] / w):.2f} %",
        f"busy          {pct(acc['busy_s'])}",
        f"idle          {pct(acc['idle_s'])}",
        f"  between programs {pct(acc['idle_between_programs_s'])}",
        f"  inside programs  {pct(acc['idle_in_programs_s'])}",
        f"parts sum to  {100.0 * named / w:.4f} % of the window",
        f"scoped        {100.0 * acc['scoped_self_s'] / acc['self_s']:.2f} % "
        f"of the device's self time runs under a scope of the program; "
        f"while / call self time {acc['loop_self_s']:.6f} s",
        *rows("busy by scope", acc["self_by_scope"], w, most=48),
        *rows("under no scope, by op class", acc["unscoped_by_class"], w),
        *rows("under no scope, by tf_op", acc["unscoped_by_path"], w, most=12),
        *rows("under no scope, by HLO op", acc.get("unscoped_by_op", {}), w,
              most=12),
        *rows("idle gaps >= 20 us, by the engine thread's span",
              acc["gaps_by_span"], w),
        f"    under a wait {pct(acc['gaps_under_wait_s'])}, under work "
        f"{pct(acc['gaps_under_work_s'])}, under no span "
        f"{pct(acc['gaps_s'] - acc['gaps_under_wait_s'] - acc['gaps_under_work_s'])}",
        *rows("idle inside programs, by program kind",
              acc["idle_in_programs_by_kind"], w),
        *rows("idle inside programs, by the scope of the op that follows",
              acc["idle_in_programs_by_following_scope"], w),
        f"engine thread under spans {pct(acc['engine_spans_s'])}, waits "
        f"{pct(acc['engine_wait_s'])}, work {pct(acc['engine_work_s'])}",
        *rows("engine thread by span (nested spans each in full)",
              acc["engine_span_s"], w, most=24),
        f"programs      {json.dumps(acc['program_s'])} s, calls "
        f"{json.dumps(acc['program_calls'])}",
        f"decode steps  {acc['decode_steps_by_counters']} between the "
        f"anchors' counter stamps, {acc['decode_steps_by_sample_op']} by one "
        f"sampling op's runs in decode programs",
    ]
    return "\n".join(lines)


def make_run(args: argparse.Namespace) -> int:
    from perfbench import run as bench

    if not args.python_tracer:
        async def spans_alone(self, t_open, window_s, dirs):
            """``Session._trace_slice`` with the Python tracer off."""
            start = t_open + max(0.0, window_s - session.TRACE_S)
            await asyncio.sleep(max(0.0, start - session.time.monotonic()))
            for wid, wc in self.worker_clients.items():
                d = os.path.join(self.work_dir, f"trace-{wid}")
                await wc.call("profile", action="start", trace_dir=d,
                              python_tracer=False)
                dirs[wid] = d
            await asyncio.sleep(min(session.TRACE_S, window_s))
            for wc in self.worker_clients.values():
                await wc.call("profile", action="stop", timeout=120.0)

        session.Session._trace_slice = spans_alone
    return bench.main(["--workload", args.cell, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "1"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--work", default=WORK)
    ap.add_argument("--run", action="store_true")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=1)
    args = ap.parse_args()
    if args.run:
        rc = make_run(args)
        if rc != 0:
            return rc
    work = os.path.join(args.work, args.cell)
    traces = sorted(d for d in os.listdir(work) if d.startswith("trace-"))
    if not traces:
        print(f"no trace under {work}", file=sys.stderr)
        return 1
    for d in traces:
        wid = d[len("trace-"):]
        path = os.path.join(work, f"slice-{wid}.json")
        if not os.path.exists(path):
            slicereaders.main([os.path.join(work, d), path])
        with open(path) as f:
            acc = json.load(f)
        print(f"== {args.cell} {wid}")
        if not acc.get("found"):
            print("no device plane or no anchor pair in the trace")
            return 1
        print(account(acc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
