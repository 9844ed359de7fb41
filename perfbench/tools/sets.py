"""Run a cell's sets the way the bounds are set from: ``--sets`` sets of
runs, the same ``--seeds`` in each, every run a new process; then each
metric's median and spread (interquartile distance over the median) per set.

    python3 perfbench/tools/sets.py --workload mistral7b-chat-steady \\
        --seeds 3000000019,2147483659,... --sets 2 --seconds 51 --out DIR

Writes every run's result line to ``DIR/<workload>.jsonl``. ``--trace-seed``
adds one traced run at the end.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib.stats import spread  # noqa: E402


def one_run(workload: str, seed: int, seconds: float, trace: int) -> dict:
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, cwd=ROOT)
    wall = time.monotonic() - t0
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        return {"error": proc.stderr[-2000:], "rc": proc.returncode,
                "wall_s": wall, "seed": seed}
    res = json.loads(lines[-1])
    res["wall_s"] = wall
    res["stderr_tail"] = proc.stderr.strip().splitlines()[-3:]
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--trace-seed", type=int, default=0)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.workload}.jsonl")
    sets = []
    with open(path, "a") as f:
        for s in range(args.sets):
            runs = []
            for seed in args.seeds:
                res = one_run(args.workload, seed, args.seconds, 0)
                res["set"] = s
                f.write(json.dumps(res) + "\n")
                f.flush()
                print(f"set {s} seed {seed}: " + json.dumps(
                    {k: v["value"] for k, v in res.get(
                        "metrics", {}).items()} or res) + " " + " ".join(
                    ln for ln in res.get("stderr_tail", [])
                    if ln.startswith("window:")), flush=True)
                if "metrics" in res:
                    runs.append(res)
            sets.append(runs)
        if args.trace_seed:
            res = one_run(args.workload, args.trace_seed, args.seconds, 1)
            res["set"] = "trace"
            f.write(json.dumps(res) + "\n")
            print("traced: " + json.dumps(res)[:6000], flush=True)
    names = sorted({m for runs in sets for r in runs for m in r["metrics"]})
    for name in names:
        for s, runs in enumerate(sets):
            vals = [r["metrics"][name]["value"] for r in runs]
            if len(vals) >= 2:
                print(f"{name} set {s}: n={len(vals)} median="
                      f"{statistics.median(vals):.5g} spread="
                      f"{100 * spread(vals):.2f}% values="
                      f"{[round(v, 4) for v in vals]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
