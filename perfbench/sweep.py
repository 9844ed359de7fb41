"""Find a configuration's knee: one set-up, several fixed rates, the same
generator as the cells.

    python3 perfbench/sweep.py --config mistral-7b-int4 --traffic chat-steady \\
        --rates 0.8,1.2,1.6,2.0,2.6 --seconds 30 --seed 11

``--seeds a,b,...`` runs every rate on every one of those seeds (rate by
rate) in place of ``--seed`` + the row's number: two seeds a rate tell a
plateau from a seed, and six seeds at one rate are a cell's set of runs for
one set-up (``--rates 0`` is the mix's own rate; ``tools/plateau.py`` reads
the rows).

One row per rate and seed: the share of requests due in the window that
met both limits (TTFT <= 1000 ms, TPOT <= 50 ms; a failed request misses), TTFT
p50/p90, TPOT p50, tokens per second, requests in flight at the window's
start and end, the sampled engine occupancy, KV pool share and pump
in-flight, and the longest request (a mix's ``ramp_s`` is twice that). The knee is the highest rate with attained >= 90 % and no more in
flight at the end than at the start (two more are let pass as noise).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import asyncio
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from perfbench.lib import (  # noqa: E402
    procs, readers, session, spanreaders, traffic,
)
from perfbench.lib.loadgen import in_flight_at  # noqa: E402
from perfbench.lib.stats import tokens_in_window, tpot_s  # noqa: E402

TTFT_LIMIT_MS, TPOT_LIMIT_MS = 1000.0, 50.0


def row(run: session.RunData, rate: float, seed: int) -> dict:
    judged = run.judged()
    vocab = int(run.config["vocab_size"])
    met = 0
    for r in judged:
        if r.failure(vocab) or not r.frames:
            continue
        tp = tpot_s(r.frames)
        met += ((r.frames[0][0] - r.due) * 1e3 <= TTFT_LIMIT_MS
                and (tp is None or tp * 1e3 <= TPOT_LIMIT_MS))
    slots = float(run.config["serve"]["max_batch_size"])
    M = procs.MODEL
    return {
        "rate_rps": rate, "seed": seed, "window_s": run.window_s,
        "due": len(judged), "failed": len(run.failures()),
        "attained_pct": 100.0 * met / max(1, len(judged)),
        "ttft_p50_ms": readers.pct(readers.ttfts_ms(run), 50),
        "ttft_p90_ms": readers.pct(readers.ttfts_ms(run), 90),
        "tpot_p50_ms": readers.pct(readers.tpots_ms(run), 50),
        "out_tok_s": tokens_in_window(run.frames(), run.t_open, run.t_close)
        / run.window_s,
        "in_flight_start": in_flight_at(run.records, run.t_open),
        "in_flight_end": in_flight_at(run.records, run.t_close),
        "occupancy_pct": readers.sampled(
            run, lambda m: 100.0 * m["models"][M]["live_slots"] / slots),
        "kv_used_pct": readers.sampled(
            run, lambda m: 100.0 * m["models"][M]["kv"]["utilization"]),
        "pump_in_flight": readers.sampled(
            run, lambda m: float(m["pumps"][M]["in_flight"])),
        "engine_waiting": readers.sampled(
            run, lambda m: float(m["models"][M]["waiting"])),
        "shed": sum(readers.counter_delta(run, [k]) for k in (
            "overloaded_count", "deadline_expired_count", "error_count")),
        "pool_waiting": spanreaders.coord_gauge_mean(run, "pool_waiting"),
        "drained_s": max((r.done for r in run.records
                          if r.req.phase != "tail" and r.done),
                         default=run.t_close) - run.t_close,
        "latency_max_s": max((r.done - r.due for r in judged if r.done),
                             default=0.0),
        "lateness_p99_ms": readers.pct(
            [(r.sent - r.due) * 1e3 for r in judged if r.sent], 99),
    }


def plan(args, mix: dict) -> list:
    """(rate, seed) of every row, in the order they run."""
    rates = [r or float(mix["rate_rps"]) for r in args.rates]
    if args.seeds:
        return [(r, s) for r in rates for s in args.seeds]
    return [(r, args.seed + i) for i, r in enumerate(rates)]


async def drive(sess: session.Session, mix: dict, args) -> None:
    await sess.connect()
    await sess.prime()
    print(f"set-up: {json.dumps(sess.setup)} device {sess.device}",
          flush=True)
    for i, (rate, seed) in enumerate(plan(args, mix)):
        run = await sess.measure(mix, seed, args.seconds, False,
                                 rate_rps=rate, tag=f"s{i}", sample=True)
        print("ROW " + json.dumps(row(run, rate, seed)), flush=True)
        await sess.wait_idle()
    print(f"peak_bytes {await sess.peak_memory_bytes()}", flush=True)
    await sess.disconnect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seeds", default=[],
                    type=lambda s: [int(x) for x in s.split(",")])
    args = ap.parse_args()
    config = session.load_config(args.config)
    mix = traffic.load_mix(args.traffic)
    work = os.path.join(HERE, ".work", f"sweep-{args.config}")
    sess = session.Session(config, work, args.seed, T_START)
    try:
        sess.start()
        asyncio.run(drive(sess, mix, args))
    finally:
        sess.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
