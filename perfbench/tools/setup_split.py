"""Where a cell's ``setup_s`` goes, from what the program says of itself.

    python3 perfbench/tools/setup_split.py <cell> --seed N [--seconds 51]
        [--trace 0|1] [--out DIR]
    python3 perfbench/tools/setup_split.py DIR/<cell>-<seed>.json [more ...]

With a cell it makes one run as ``run.py`` does (same session, prime, ramp,
window and, with ``--trace 1``, traced slice and per-layer readers), keeps one
more ``metrics`` snapshot and the step ring's compile records right after
prime, writes what it read to
``DIR/<cell>-<seed>.json`` (default ``chiprun_out/setup_split``) and prints
the split. With such files it prints the table alone, one row a file.

Columns, seconds: ``spawn`` (benchmark start -> the worker process exists, and
the lag of reading its port line: the benchmark's clock against the worker's
``boot`` marks), ``boot`` (process start -> listening, less the load; the
``boot marks`` line under the table has its parts),
``build`` (``load_s`` less ``warmup_s``: the engine factory and, in it, the
backend's start), the warm-up
grid's ``trace`` / ``lower`` / ``compile`` (of which ``cache``
read the persistent cache) / ``run``, ``install`` (what is left of
``load_s``: the warm-up's wall against the worker's own timing of it),
``connect`` (coordinator start, connect, device check: the
benchmark's clock), ``prime`` (of which ``compiling``), ``ramp``, and
``unplaced`` = ``setup_s`` less all of them.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import run as bench  # noqa: E402  (its T_START: this start)

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

from perfbench.lib import procs, session, setupreaders, traffic  # noqa: E402

COLUMNS = ["spawn", "boot", "build", "trace", "lower", "compile", "cache",
           "run", "install", "connect", "prime", "compiling", "ramp",
           "unplaced"]
# ``cache`` is inside ``compile`` and ``compiling`` inside ``prime``
PARTS = [c for c in COLUMNS if c not in ("cache", "compiling", "unplaced")]


def split(saved: Dict[str, Any]) -> Dict[str, Optional[float]]:
    """The table's row from one saved run. A part the program did not
    report (an earlier commit) is ``None`` and counts as unplaced."""
    s, own = saved["setup"], saved["program"]
    boot = own.get("boot") or {}
    listening = boot.get("listening")
    row: Dict[str, Optional[float]] = {
        "spawn": (None if listening is None
                  else s["workers_ready_s"] - listening),
        "boot": own.get("boot_s"), "build": own.get("build_s"),
        **{name: own["warmup"].get(f"{name}_s") for name in (
            "trace", "lower", "compile", "run")},
        "cache": own["warmup"].get("cache_retrieval_s"),
        "connect": s["prime_begin_s"] - s["workers_ready_s"],
        "prime": s["prime_s"], "compiling": own.get("prime_compile_s"),
        "ramp": s["setup_s"] - s["prime_begin_s"] - s["prime_s"]}
    named = [row["build"], own["warmup"].get("wall_s")]
    row["install"] = (None if None in named
                      else s["load_s"] + s["warmup_s"] - sum(named))
    row["unplaced"] = s["setup_s"] - sum(row[p] or 0.0 for p in PARTS)
    return row


def table(saved: List[Dict[str, Any]]) -> str:
    head = ["cell", "seed", "setup_s", *COLUMNS, "named %"]
    lines = ["| " + " | ".join(head) + " |",
             "| " + " | ".join("---" for _ in head) + " |"]
    for one in saved:
        row = split(one)
        base = one["setup"]["setup_s"] - (row["ramp"] or 0.0)
        named = 100.0 * (base - row["unplaced"]) / base
        cells = [one["cell"], str(one["seed"]),
                 f"{one['setup']['setup_s']:.1f}"]
        cells += ["-" if row[c] is None else f"{row[c]:.2f}"
                  for c in COLUMNS]
        lines.append("| " + " | ".join(cells + [f"{named:.1f}"]) + " |")
    return "\n".join(lines)


def program_side(run: session.RunData) -> Dict[str, Any]:
    """What the worker and its engine reported as the window opened."""
    first = next(iter(run.workers_before.values()), {})
    setup = (first.get("model_setup") or {}).get(procs.MODEL) or {}
    return {
        "boot": first.get("boot"),
        "boot_s": setupreaders.boot_s(run),
        "build_s": setupreaders.build_s(run),
        "warmup": {part: setupreaders.warmup_part_s(run, part)
                   for part in setupreaders.WARMUP_PARTS},
        "rounds": (setup.get("warmup") or {}).get("rounds"),
        "compile_at_open": (first.get("device") or {}).get("compile"),
        "compiles_in_window": setupreaders.compiles_in_window(run),
        "in_window": setupreaders.compiles_after_warmup(run),
        "after_warmup_at_close": {
            wid: setupreaders.after_warmup(m)
            for wid, m in run.workers_after.items()}}


async def drive(sess: session.Session, mix: Dict[str, Any],
                args: argparse.Namespace) -> Dict[str, Any]:
    """``run.drive`` with the stamps and the one snapshot it does not
    keep: when prime began, and the workers' metrics when it ended."""
    await sess.connect()
    sess.setup["prime_begin_s"] = time.monotonic() - sess.t_start
    await sess.prime()
    primed = {wid: await wc.metrics()
              for wid, wc in sess.worker_clients.items()}
    rings = {wid: ((await wc.call("events")).get("timelines") or {}).get(
        procs.MODEL) or [] for wid, wc in sess.worker_clients.items()}
    run = await sess.measure(mix, args.seed, float(args.seconds),
                             bool(args.trace))
    cases = await bench.parity_chains(sess, args.seed) if args.trace else []
    run.device["memory_peak_bytes"] = await sess.peak_memory_bytes()
    await sess.disconnect()
    return {"run": run, "cases": cases, "primed": primed, "rings": rings}


def primed_side(run: session.RunData, primed: Dict[str, Dict[str, Any]],
                rings: Dict[str, List[Dict[str, Any]]]) -> Dict[str, Any]:
    """The run as prime ended (the snapshot ``drive`` keeps): what compiled
    under prime alone, by the reader that on ``run`` itself spans prime
    and the ramp. ``run`` is left as it is: the per-layer readers take
    its ``workers_before`` for the window's open."""
    at_prime = dataclasses.replace(run, workers_before=primed)
    return {
        "prime_compile_s": setupreaders.prime_compile_s(at_prime),
        "compiled_spans": {wid: compiled_spans(ring)
                           for wid, ring in rings.items()},
        "after_warmup_at_primed": {wid: setupreaders.after_warmup(m)
                                   for wid, m in primed.items()}}


def compiled_spans(ring: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The ring's records that say what compiled inside them: the warm-up
    grid's rounds and every dispatch bracket flagged ``compile``."""
    return [{"name": e["name"], "t": e["t"], "dur": e["dur"], **{
        k: v for k, v in e["args"].items() if k in (
            "batch", "bucket", "program", "programs", "cache", "trace_s",
            "lower_s", "compile_s", "cache_retrieval_s", "run_s")}}
            for e in ring if e["name"] == "engine.warmup.round"
            or e.get("args", {}).get("compile")]


def one_run(args: argparse.Namespace) -> Dict[str, Any]:
    man = bench.manifest()
    cell = bench.find_cell(man, args.target)
    config = session.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    sess = session.Session(
        config, os.path.join(ROOT, "perfbench", ".work", cell["name"]),
        args.seed, bench.T_START)
    try:
        sess.start()
        out = asyncio.run(drive(sess, mix, args))
        sess.stop()
        run: session.RunData = out["run"]
        correct = not run.failures()
        if args.trace:
            correct = bench.after_servers(sess, out, args.seed) and correct
    finally:
        sess.stop()
    saved: Dict[str, Any] = {
        "cell": cell["name"], "seed": args.seed, "trace": args.trace,
        "correct": correct, "setup": run.setup, "device": run.device,
        "end_to_end": bench.end_to_end(run), "program": program_side(run)}
    saved["program"].update(primed_side(run, out["primed"], out["rings"]))
    if args.trace:
        saved["per_layer"] = {}
        for m in bench.metric_names(man, "per_layer", cell):
            value = bench.load_reader(m["name"]).read(out["run"])
            if value is not None:
                saved["per_layer"][m["name"]] = value
    return saved


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("target", nargs="+",
                    help="one cell of BENCHMARK.json, or saved files")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "setup_split"))
    args = ap.parse_args(argv)
    if all(os.path.isfile(t) for t in args.target):
        saved = []
        for path in args.target:
            with open(path) as f:
                saved.append(json.load(f))
        print(table(saved))
        return 0
    if len(args.target) != 1:
        raise SystemExit("one cell, or files that exist")
    args.target = args.target[0]
    saved = one_run(args)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{saved['cell']}-{args.seed}.json")
    with open(path, "w") as f:
        json.dump(saved, f, indent=1)
    print(f"wrote {path}", file=sys.stderr)
    print(json.dumps({k: saved[k] for k in (
        "cell", "seed", "correct", "setup", "end_to_end")}))
    for e in saved["program"]["in_window"] or []:
        print(f"in the window: {json.dumps(e)}")
    print(table([saved]))
    print(f"boot marks: {json.dumps(saved['program']['boot'])}")
    return 0 if saved["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
