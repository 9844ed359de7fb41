"""An overload mix's rate from the plateau of THIS tree, and the cell's sets of
runs at that rate, on one set-up.

    python3 perfbench/tools/plateau.py --config xing4.0-29b-a4b-pp1 \\
        --traffic longdoc-overload-xing --rates 2.2,2.9,3.8 --seeds 11,12 \\
        --sets 21,22,23,24,25,26/31,32,33,34,35,36 --out chiprun_out/pr49

Three stages through ``sweep.py``'s own session and row:

1. every rate of ``--rates`` on every seed of ``--seeds`` (``--seconds``
   windows; ``0`` is the mix's own rate: a cell that is backlogged today is
   its own plateau). A row is SATURATED with ``LIVE_PCT`` of the slots live
   and more requests in flight at the close than at the open. The plateau is
   the mean tok/s of the saturated rows, and it is AGREED when every row of
   the two highest rates is saturated and the two rates' means lie within
   ``AGREE`` of each other (one rate: its rows within ``AGREE``); where they
   do not agree the highest rate's saturated rows stand in, marked so.
2. ``rate_rps = FACTOR x plateau / the mix's mean output``, two decimals;
   ``--write-rate`` puts it into the mix file (so that ``run.py`` can follow
   in the same chip call; the builder copies it into the repo by hand).
3. each set of ``--sets`` (seeds by comma, sets by ``/``) at that rate with
   ``--set-seconds`` windows: min, max, median, (max - min) / median and
   ``lib/stats.py`` ``spread`` of ``out_tok_s``, and whether the set's median
   bears the plateau out (within ``AGREE``).

Every row goes to ``<out>/<config>-sweep.txt`` as sweep.py prints it, the
summary to ``<out>/<config>-plateau.json``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import asyncio
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))

from perfbench import sweep  # noqa: E402
from perfbench.lib import session, traffic  # noqa: E402
from perfbench.lib.stats import spread  # noqa: E402

LIVE_PCT, AGREE, FACTOR = 97.0, 0.03, 1.5


def mean_output(mix: dict) -> float:
    """Mean of the mix's output lengths as the generator draws them."""
    q = traffic.lognormal_quantiles(mix["output"], 20000)
    return sum(q) / len(q)


def saturated(row: dict) -> bool:
    return (row["occupancy_pct"] or 0.0) >= LIVE_PCT and \
        row["in_flight_end"] > row["in_flight_start"] and not row["failed"]


def plateau(rows: list) -> dict:
    """The plateau of stage 1's rows, and whether they agree on it."""
    by_rate: dict = {}
    for r in rows:
        by_rate.setdefault(r["rate_rps"], []).append(r)
    sat = [r["out_tok_s"] for r in rows if saturated(r)]
    top = sorted(by_rate)[-2:]
    means = [statistics.mean(r["out_tok_s"] for r in by_rate[k]) for k in top]
    flat = all(saturated(r) for k in top for r in by_rate[k]) and (
        max(means) / min(means) - 1.0 <= AGREE if len(top) > 1 else
        max(sat) / min(sat) - 1.0 <= AGREE)
    if sat and not flat:        # no plateau shown: the highest rate's rows
        sat = [r["out_tok_s"] for r in by_rate[top[-1]] if saturated(r)] or sat
    return {"tok_s": statistics.mean(sat) if sat else None,
            "saturated_rows": len(sat), "rows": len(rows),
            "top_rates": top, "top_means": means, "agreed": bool(sat and flat)}


def summarise(rows: list, tok_s: float) -> dict:
    vals = [r["out_tok_s"] for r in rows]
    med = statistics.median(vals)
    return {"seeds": [r["seed"] for r in rows], "out_tok_s": vals,
            "min": min(vals), "max": max(vals), "median": med,
            "range_over_median": (max(vals) - min(vals)) / med,
            "spread": spread(vals) if len(vals) > 1 else None,
            "median_over_plateau": med / tok_s,
            "bears_out": abs(med / tok_s - 1.0) <= AGREE,
            "all_saturated": all(saturated(r) for r in rows),
            "failed": sum(r["failed"] for r in rows),
            "shed": sum(r["shed"] or 0 for r in rows)}


async def drive(sess: session.Session, mix: dict, args, log) -> dict:
    await sess.connect()
    await sess.prime()
    log(f"set-up: {json.dumps(sess.setup)} device {sess.device}")
    n = 0

    async def one(rate: float, seed: int, seconds: float) -> dict:
        nonlocal n
        n += 1
        run = await sess.measure(mix, seed, seconds, False, rate_rps=rate,
                                 tag=f"p{n}", sample=True)
        r = sweep.row(run, rate, seed)
        log("ROW " + json.dumps(r))
        await sess.wait_idle()
        return r

    first = [await one(rate or float(mix["rate_rps"]), seed, args.seconds)
             for rate in args.rates for seed in args.seeds]
    out = {"config": args.config, "traffic": args.traffic,
           "mean_output": mean_output(mix), "plateau": plateau(first)}
    tok_s = out["plateau"]["tok_s"]
    if tok_s is None:
        log("PLATEAU none: no row was saturated")
        return out
    out["rate_rps"] = round(FACTOR * tok_s / out["mean_output"], 2)
    log("PLATEAU " + json.dumps(dict(out["plateau"], rate_rps=out["rate_rps"],
                                     mean_output=out["mean_output"])))
    if args.write_rate:
        path = os.path.join(HERE, "traffic", f"{args.traffic}.json")
        with open(path) as f:
            doc = json.load(f)
        doc["rate_rps"] = out["rate_rps"]
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, ensure_ascii=False)
    out["sets"] = []
    for seeds in args.sets:
        rows = [await one(out["rate_rps"], s, args.set_seconds) for s in seeds]
        out["sets"].append(summarise(rows, tok_s))
        log("SET " + json.dumps(out["sets"][-1]))
    out["peak_bytes"] = await sess.peak_memory_bytes()
    await sess.disconnect()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(x) for x in s.split(",")])
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--sets", default=[],
                    type=lambda s: [[int(x) for x in part.split(",")]
                                    for part in s.split("/") if part])
    ap.add_argument("--seconds", type=float, default=38.0)
    ap.add_argument("--set-seconds", type=float, default=51.0)
    ap.add_argument("--write-rate", action="store_true")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    config = session.load_config(args.config)
    mix = traffic.load_mix(args.traffic)
    os.makedirs(args.out, exist_ok=True)
    rows_path = os.path.join(args.out, f"{args.config}-sweep.txt")

    def log(line: str) -> None:
        print(line, flush=True)
        with open(rows_path, "a") as f:
            f.write(line + "\n")

    log(f"# plateau.py {' '.join(sys.argv[1:])} (mix rate "
        f"{mix['rate_rps']}, strata {mix.get('strata')})")
    work = os.path.join(HERE, ".work", f"sweep-{args.config}")
    sess = session.Session(config, work, args.seeds[0], T_START)
    try:
        sess.start()
        out = asyncio.run(drive(sess, mix, args, log))
    finally:
        sess.stop()
    with open(os.path.join(args.out, f"{args.config}-plateau.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
