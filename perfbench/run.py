"""The benchmark's command: one cell, one seed, one measured window.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Benchmark process (never imports jax) -> framed RPC -> ``cli.coordinator``
child -> ``cli.worker`` child(ren) holding the chip(s) -> ContinuousEngine.
The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
``compared``: every number ``correct`` was decided by beside its limit (the
same lines end standard error).
Any failure to start, to find the device the cell asks for, or to finish
exits non-zero with no result line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import asyncio
import importlib.util
import json
import os
import pickle
import re
import sys
import traceback
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from perfbench.lib import (  # noqa: E402
    procs, readers, session, tracered, traffic,
)
from perfbench.lib.loadgen import in_flight_at  # noqa: E402
from perfbench.lib.stats import tokens_in_window  # noqa: E402

CHAIN_PROMPT, CHAIN_OUT = 48, 24     # the two chains the reference judges


def manifest() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(man: Dict[str, Any], name: str) -> Dict[str, Any]:
    """A cell of BENCHMARK.json, or a rehearsal cell (``rehearsal.json``:
    run off-chip, never reported as a cell)."""
    for w in man["workloads"]:
        if w["name"] == name:
            return dict(w, rehearsal=False)
    with open(os.path.join(HERE, "rehearsal.json")) as f:
        for w in json.load(f)["workloads"]:
            if w["name"] == name:
                return dict(w, rehearsal=True)
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metric_names(man: Dict[str, Any], kind: str, cell: Dict[str, Any]
                 ) -> List[Dict[str, Any]]:
    """The metrics of ``kind`` this cell reports: those with no
    ``workloads`` key, or that list it. A rehearsal cell tries them all."""
    return [m for m in man[kind]
            if cell["rehearsal"] or "workloads" not in m
            or cell["name"] in m["workloads"]]


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def end_to_end(run: session.RunData) -> Dict[str, Optional[float]]:
    """Every end-to-end number the benchmark knows how to take; the cell's
    manifest entries choose among them. Latencies are over the requests DUE
    in the window; the rate counts tokens whose frame reached the client in
    it."""
    return {
        "tpot_p50_ms": readers.pct(readers.tpots_ms(run), 50),
        "out_tok_s": tokens_in_window(run.frames(), run.t_open, run.t_close)
        / run.window_s / max(1, run.device["count"]),
        "setup_s": run.setup["setup_s"],
    }


async def parity_chains(sess: session.Session, seed: int
                        ) -> List[Dict[str, Any]]:
    """Two short prompts served alone, for the reference to judge."""
    rng = traffic.token_rng("parity", seed)
    vocab = int(sess.config["vocab_size"])
    cases = []
    for i in range(2):
        req = traffic.Request(i, "parity", 0.0, [
            rng.randrange(1, vocab) for _ in range(CHAIN_PROMPT)], CHAIN_OUT)
        (rec,) = await sess.ask([req], "parity")
        cases.append({"label": f"chain-{i}", "prompt": req.prompt,
                      "tokens": rec.tokens})
    return cases


async def drive(sess: session.Session, mix: Dict[str, Any],
                args: argparse.Namespace) -> Dict[str, Any]:
    await sess.connect()
    await sess.prime()
    run = await sess.measure(mix, args.seed, float(args.seconds),
                             bool(args.trace))
    cases = await parity_chains(sess, args.seed) if args.trace else []
    run.device["memory_peak_bytes"] = await sess.peak_memory_bytes()
    await sess.disconnect()
    return {"run": run, "cases": cases}


def reference_numbers(log: str, ended_well: bool) -> Dict[str, Any]:
    """Every number ``reference/check.py`` compared, beside its limit, from
    the child's own lines (``reference: ... min_strict_share=S`` and one
    ``<label>: {json}`` a chain)."""
    out: Dict[str, Any] = {
        "reference_ran_to_its_end": {"value": 0, "at_least": 1}}
    share = re.search(r"min_strict_share=([0-9.]+)", log)
    for label, doc in re.findall(r"^\s+(\S+): (\{.*\})\s*$", log, re.M):
        res = json.loads(doc)
        out[f"{label}.outside_tie_band"] = {"value": res["outside"],
                                            "at_most": 0}
        out[f"{label}.strict_share"] = {
            "value": res["strict"] / max(1, res["n"]),
            "at_least": float(share.group(1)) if share else None}
        out[f"{label}.worst_tie"] = {"value": res["worst_tie"], "below": 1.0}
    ran = "chains verified" in log
    out["reference_ran_to_its_end"]["value"] = int(ran)
    out["reference_verdict"] = {"value": int(ended_well), "at_least": 1}
    return out


def after_servers(sess: session.Session, out: Dict[str, Any], seed: int
                  ) -> Dict[str, Any]:
    """Traced run, servers stopped, chip free: reduce the traces and judge
    the served chains against the plain reference. Returns every number it
    compared beside its limit; ``reference_verdict`` is its verdict."""
    run: session.RunData = out["run"]
    cfg = dict(sess.config)
    # the worker's count of int4 tensors, fused or not as it runs them; a
    # tree that reports none is asked no int4 question (the reduction then
    # takes the family's count, 0 for a family that stores none)
    place = next(iter(run.workers_after.values()))["device"]["models"][
        procs.MODEL]
    n_int4 = sum((place.get("int4_paths") or {}).values())
    if n_int4 > 1:
        # stacked tensors run once per layer, the head once
        cfg["_int4_calls_per_step"] = \
            int(cfg["num_hidden_layers"]) * (n_int4 - 1) + 1
    cfg["_program_files"] = sorted({
        f for _d, _s, files in os.walk(os.path.join(ROOT, procs.PKG))
        for f in files if f.endswith(".py")})
    cfg_path = os.path.join(sess.work_dir, "config.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    reduced = []
    for wid, d in run.trace_dirs.items():
        res = os.path.join(sess.work_dir, f"reduced-{wid}.json")
        child = sess.children.run_to_end(
            f"reduce-{wid}", [sys.executable,
                              os.path.join(HERE, "lib", "tracered.py"),
                              d, cfg_path, res],
            procs.child_env("cpu"), timeout=300.0)
        if child.proc.returncode != 0:
            raise procs.BenchFailure(f"trace reduction failed:\n"
                                     f"{child.tail()}")
        with open(res) as f:
            reduced.append(json.load(f))
    reduced = [r for r in reduced if r.get("devices")]
    if reduced:
        run.trace = tracered.average(reduced)
    # what the readers take, kept beside the traces: ``tools/reread.py``
    # reads a finished run again (another tree's readers on the same run)
    with open(os.path.join(sess.work_dir, "run.pkl"), "wb") as f:
        pickle.dump(run, f)
    job = os.path.join(sess.work_dir, "reference_job.json")
    with open(job, "w") as f:
        json.dump({"config": sess.config,
                   "weight_seed": seed % (2 ** 31 - 1),
                   "cases": out["cases"]}, f)
    child = sess.children.run_to_end(
        "reference", [sys.executable,
                      os.path.join(HERE, "reference", "check.py"), job],
        procs.child_env(sess.platform), timeout=300.0)
    sys.stderr.write(child.tail(6))
    with open(child.log_path, errors="replace") as f:
        return reference_numbers(f.read(), child.proc.returncode == 0)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    man = manifest()
    cell = find_cell(man, args.workload)
    config = session.load_config(cell["config"])
    if int(cell["chips"]) != 1:
        raise SystemExit(f"{cell['name']}: the harness starts one worker on "
                         f"one chip; a cell across chips brings its own way")
    mix = traffic.load_mix(cell["traffic"])
    work = os.path.join(HERE, ".work", cell["name"])
    sess = session.Session(config, work, args.seed, T_START)
    try:
        sess.start()
        out = asyncio.run(drive(sess, mix, args))
        sess.stop()                       # frees the chip(s)
        run: session.RunData = out["run"]
        compared: Dict[str, Any] = {}
        if args.trace:
            compared = after_servers(sess, out, args.seed)
    except BaseException as e:
        sess.stop()
        if not isinstance(e, (procs.BenchFailure, SystemExit)):
            traceback.print_exc()
        print(f"perfbench FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    failures = run.failures()
    hits = readers.counter_delta(run, ["models", procs.MODEL,
                                       "prefix_hit_admissions"])
    if hits:
        # every prompt of a mix is unique: a prefix-cache hit means the
        # traffic shared what it must not, and work was removed
        failures.append(f"{int(hits)} prefix-cache hits on unique prompts")
    drained = max((r.done for r in run.records
                   if r.req.phase != "tail" and r.done), default=run.t_close)
    print(f"window: in flight {in_flight_at(run.records, run.t_open)} at "
          f"the open, {in_flight_at(run.records, run.t_close)} at the "
          f"close; drained {max(0.0, drained - run.t_close):.1f}s after it",
          file=sys.stderr)
    print(f"set-up: {json.dumps(run.setup)} hbm_in_use_gb "
          f"{readers.hbm_in_use_gb(run)} total "
          f"{time.monotonic() - T_START:.1f}s", file=sys.stderr)
    for f in failures[:5]:
        print(f"failed request: {f}", file=sys.stderr)
    metrics: Dict[str, Dict[str, Any]] = {}
    if args.trace:
        for m in metric_names(man, "per_layer", cell):
            value = load_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = end_to_end(run)
        for m in metric_names(man, "end_to_end", cell):
            if values.get(m["name"]) is None:
                print(f"perfbench FAILED: no value for {m['name']}",
                      file=sys.stderr)
                return 1
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    compared = {"failed_requests": {"value": len(failures) - bool(hits),
                                    "at_most": 0},
                "prefix_cache_hits": {"value": int(hits or 0), "at_most": 0},
                **compared}
    device = dict(run.device)
    result: Dict[str, Any] = {
        "correct": not failures and compared.get(
            "reference_verdict", {"value": 1})["value"] == 1,
        "attempted": len(run.judged()), "failed": len(failures),
        "metrics": metrics, "device": device,
        "workload": cell["name"], "seed": args.seed,
    }
    if args.trace and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": tracered.top(run.trace["classes"]),
            "idle_gaps": tracered.top(run.trace["idle_gaps"])}
    result["compared"] = compared          # last in the line, and on stderr
    for name, doc in compared.items():
        print(f"compared {name}: {json.dumps(doc)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
