"""Latency-throughput sweep: Poisson load against one continuous engine at
several offered rates (VERDICT r2 item 2's measurement half).

Builds the engine ONCE (8B-scale init and compile are the long part of a run),
then for each offered rate runs an independent Poisson arrival trial and
reports goodput, TTFT p50/p99, ITL p99, occupancy, and rejections. With
overload handling on (queue cap + deadline shed), past-saturation rates
show a knee — bounded p99 with explicit rejections — instead of unbounded
queue growth.

Each rate runs ``SWEEP_TRIALS`` independent trials (default 3, distinct
arrival seeds) and reports the MEDIAN trial by goodput with the min–max
band across trials — the headline estimator for a noisy serving metric
is the median, not the best trial (repeated saturation trials on the
same engine land in a ~6% band, and best-of-N only ever ratchets up).

Usage (defaults mirror bench.py serving mode at the 8B rung):
    python examples/serving_sweep.py
    SWEEP_RATES=4,8,12 SWEEP_REQUESTS=96 SWEEP_TRIALS=5 \
        python examples/serving_sweep.py
    SWEEP_SHAPE=long python examples/serving_sweep.py   # 2k-prompt rung
    SWEEP_SHAPE=mixed python examples/serving_sweep.py  # long prompts mixed in
Prints one JSON line per rate (the median trial, annotated with the
band) and a final markdown table on stderr.
"""

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_inference_engine_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()
# serving stays at bs64: the r5 bs128 decode default assumes the batch
# bench's memory shape — serving adds per-bucket compiled programs and
# admission-prefill workspace on top, and bs128 OOMs the 16 GB chip
os.environ.setdefault("BENCH_BATCH", "64")
# SWEEP_SHAPE=long: the long-prompt rung (2048-token prompts, 128 new).
# At 8B/bs64 the KV footprint is 2176 tokens/slot — fp16 KV would blow the
# 16 GB chip, so this shape forces fp8 KV and chunked prefill, and turns
# the host KV tier on so evicted long prefixes restage over PCIe instead
# of recomputing a 2k prefill. All setdefault: any knob can still be
# overridden from the environment.
if os.environ.get("SWEEP_SHAPE", "") == "long":
    os.environ.setdefault("BENCH_PROMPT", "2048")
    os.environ.setdefault("BENCH_NEW_TOKENS", "128")
    os.environ.setdefault("BENCH_PREFILL_CHUNK", "512")
    os.environ.setdefault("BENCH_KV_DTYPE", "float8_e4m3fn")
    os.environ.setdefault("BENCH_KV_OFFLOAD", "1")
# SWEEP_SHAPE=mixed (ISSUE 3): a steady 128-token decode stream with every
# 8th request admitting a 2k-token prompt — the workload whose decode ITL
# p99 long-prompt admissions cliff (watch for steps past ~2x the
# steady-state ITL median). Runs chunked prefill, one chunk alternating
# with each decode chunk. fp8 KV for the same capacity reason as the long
# rung.
# SWEEP_SHAPE=moe (ISSUE 14 / VERDICT.md "Next" #8): the capacity-bound
# MoE rung — mixtral-16g (12.9B params, 8 experts, top-2) is the largest
# Mixtral shape whose int4 weights (~6.0 GiB) leave a 16 GB chip room
# for KV + activations at bs64. BENCH_QUANT=4 is EXPLICIT here: the
# Mosaic kernel disengages on the 4-D expert mats (resolve_quant's
# honored-but-logged path), so expert matmuls ride XLA int4 — the
# capacity-vs-expert-throughput trade this rung exists to measure. On
# CPU this shrinks to a parity check; the hardware capture protocol is
# in docs/decode_profile.md ("Capacity-bound MoE rung").
if os.environ.get("SWEEP_SHAPE", "") == "moe":
    os.environ.setdefault("BENCH_MODEL", "mixtral-16g")
    os.environ.setdefault("BENCH_QUANT", "4")
    os.environ.setdefault("BENCH_PROMPT", "128")
    os.environ.setdefault("BENCH_NEW_TOKENS", "128")
    os.environ.setdefault("BENCH_KV_DTYPE", "float8_e4m3fn")
if os.environ.get("SWEEP_SHAPE", "") == "mixed":
    os.environ.setdefault("BENCH_PROMPT", "128")
    os.environ.setdefault("BENCH_NEW_TOKENS", "128")
    os.environ.setdefault("BENCH_MIX_EVERY", "8")
    os.environ.setdefault("BENCH_MIX_PROMPT", "2048")
    os.environ.setdefault("BENCH_PREFILL_CHUNK", "512")
    os.environ.setdefault("BENCH_KV_DTYPE", "float8_e4m3fn")

import numpy as np  # noqa: E402

import bench  # noqa: E402  (repo-root bench.py: engine/request builders)
from bench import log, pct  # noqa: E402
from distributed_inference_engine_tpu.engine.types import (  # noqa: E402
    EngineOverloadedError,
)
from distributed_inference_engine_tpu.serving.pump import EnginePump  # noqa: E402


async def run_rate(pump, spec, rate, n_requests, seed, trace_sink=None):
    engine = pump.engine
    ttfts, itls = [], []
    rejected = [0]
    reqs = bench._requests(spec, seed, n_requests)
    m0 = engine.get_metrics()
    steps0 = m0["engine_steps"]
    occ0 = m0["batch_occupancy"] * steps0 * engine.max_slots
    dispatch0 = m0.get("dispatch_s_total", 0.0)
    gap0 = m0.get("host_gap_s_total", 0.0)

    async def client(req):
        marks = []

        def on_tokens(toks):
            marks.append((time.perf_counter(), len(toks)))

        try:
            res = await pump.generate_streaming(req, on_tokens)
        except EngineOverloadedError:
            rejected[0] += 1
            return 0
        if trace_sink is not None:
            row = bench._result_row(res)
            row["rate"] = rate
            trace_sink.append(row)
        ttfts.append(res.ttft_s)
        prev = None
        for t, k in marks:
            if prev is not None:
                itls.append(t - prev)
                itls.extend([0.0] * (k - 1))
            prev = t
        return len(res.tokens)

    rs = np.random.RandomState(seed)
    tasks = []
    t_start = time.perf_counter()
    for req in reqs:
        tasks.append(asyncio.create_task(client(req)))
        await asyncio.sleep(float(rs.exponential(1.0 / rate)))
    counts = await asyncio.gather(*tasks)
    wall = time.perf_counter() - t_start
    m = engine.get_metrics()
    d_steps = m["engine_steps"] - steps0
    occ = ((m["batch_occupancy"] * m["engine_steps"] * engine.max_slots
            - occ0) / (d_steps * engine.max_slots)) if d_steps else 0.0
    # host-gap split over this trial's window (same delta idiom as
    # occupancy): dispatch seconds inside device brackets vs host gap
    # between them — same decomposition bench.py decode mode reports
    d_dispatch = m.get("dispatch_s_total", 0.0) - dispatch0
    d_gap = m.get("host_gap_s_total", 0.0) - gap0
    bubble = d_gap / (d_dispatch + d_gap) if (d_dispatch + d_gap) > 0 else 0.0
    return {
        "rate": rate,
        "goodput_toks": round(sum(counts) / wall, 1),
        "served": len(reqs) - rejected[0],
        "rejected": rejected[0],
        "rejection_rate": round(rejected[0] / len(reqs), 3),
        "ttft_p50_ms": round(pct(ttfts, 0.5) * 1e3, 1),
        "ttft_p99_ms": round(pct(ttfts, 0.99) * 1e3, 1),
        "itl_p50_ms": round(pct(itls, 0.5) * 1e3, 2),
        "itl_p99_ms": round(pct(itls, 0.99) * 1e3, 2),
        "occupancy": round(occ, 3),
        "dispatch_s": round(d_dispatch, 2),
        "host_gap_s": round(d_gap, 2),
        "host_bubble_frac": round(bubble, 3),
        "wall_s": round(wall, 1),
    }


def main():
    spec = bench._spec()
    rates = [float(r) for r in os.environ.get(
        "SWEEP_RATES", "4,8,12,16,24").split(",")]
    n_requests = int(os.environ.get("SWEEP_REQUESTS", "96"))
    steps = int(os.environ.get("BENCH_STEPS", "16"))

    t0 = time.perf_counter()
    params = bench._build_params(spec, bench.QUANT)
    engine = bench._engine(spec, params, "continuous", bench.BATCH, steps)
    engine.config.max_waiting = int(
        os.environ.get("BENCH_MAX_WAITING", str(bench.BATCH)))
    engine.config.queue_deadline_s = float(
        os.environ.get("BENCH_DEADLINE_S", "8"))
    log(f"engine init ({bench.MODEL}, bs{bench.BATCH}, "
        f"prompt={bench.PROMPT_LEN}+{bench.NEW_TOKENS}, "
        f"quant={bench.QUANT_BITS if bench.QUANT else 0}, "
        f"max_waiting={engine.config.max_waiting}, "
        f"deadline={engine.config.queue_deadline_s}s): "
        f"{time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    engine.warmup(max_new_tokens=2)
    log(f"warmup (all buckets): {time.perf_counter() - t0:.1f}s")

    # BENCH_OVERLAP=0 disables batch-formation overlap (engine.overlap_hook)
    # for A/B against the top-of-loop-only inbox drain
    pump = EnginePump(engine, idle_wait_s=0.01,
                      overlap_forms=os.environ.get(
                          "BENCH_OVERLAP", "1") not in ("0", ""))
    bench.prime_pump(pump, spec, bench.BATCH)
    trials = max(1, int(os.environ.get("SWEEP_TRIALS", "3")))
    rows = []
    trace_sink: list = []
    for i, rate in enumerate(rates):
        trial_rows = []
        for t in range(trials):
            r = asyncio.run(run_rate(pump, spec, rate, n_requests,
                                     100 + trials * i + t,
                                     trace_sink=trace_sink))
            trial_rows.append(r)
            log(f"  rate {rate:g} trial {t + 1}/{trials}: "
                f"{r['goodput_toks']} tok/s")
        # median trial BY GOODPUT is the reported row (upper median for
        # even N); the band is the min-max spread across trials — the
        # honest run-to-run noise a single number would hide
        trial_rows.sort(key=lambda r: r["goodput_toks"])
        row = trial_rows[len(trial_rows) // 2]
        row["trials"] = trials
        row["goodput_band"] = [trial_rows[0]["goodput_toks"],
                               trial_rows[-1]["goodput_toks"]]
        rows.append(row)
        print(json.dumps(row), flush=True)
    asyncio.run(pump.stop())
    # registry snapshot + per-request traces + step timeline next to the
    # sweep output (BENCH_OBS_DIR, default bench_obs; "0" disables)
    bench.dump_obs(engine, trace_sink, "sweep", pump=pump)

    log("\n| offered req/s | goodput tok/s (median) | band | served | "
        "rejected | TTFT p50 | TTFT p99 | ITL p50 | ITL p99 | occupancy | "
        "host bubble |")
    log("|---|---|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        lo, hi = r["goodput_band"]
        log(f"| {r['rate']:g} | {r['goodput_toks']} | {lo:g}–{hi:g} | "
            f"{r['served']} | "
            f"{r['rejected']} ({r['rejection_rate']:.0%}) | "
            f"{r['ttft_p50_ms']:.0f} ms | {r['ttft_p99_ms']:.0f} ms | "
            f"{r['itl_p50_ms']:.1f} ms | {r['itl_p99_ms']:.1f} ms | "
            f"{r['occupancy']:.2f} | {r['host_bubble_frac']:.1%} |")


if __name__ == "__main__":
    main()
