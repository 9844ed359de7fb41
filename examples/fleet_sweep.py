"""Fleet sweep: goodput scaling of a coordinator-fronted worker fleet
(ISSUE 10's measurement half), over real framed RPC on localhost.

Four fake-fleet legs plus one real-engine leg, every one driving Poisson
offered load through ``Coordinator.submit`` and checking token-exactness
against the crc32-chain reference (the fake's next token is a pure
function of the full context, so any worker — or any sequence of workers,
after a failover — must produce the same stream):

  replicated  N ∈ {1,2,4} decode workers as a pure replica set
              (``deploy_model(register_shards=False)`` — LB spreading, not
              registry sharding), offered load scaled with N and ~20% past
              per-worker capacity, so the rows measure SUSTAINED goodput.
              Acceptance: N=4 goodput ≥ 3.2x the N=1 row.
  disagg      prefill pool + N decode workers via
              ``deploy_model_disaggregated``: prefill handoffs cross the
              wire as real ``PrefillHandoff`` frames; rows add handoff
              bytes/s. Every result token-exact vs the single-engine
              reference chain.
  affinity    N=4 replicas with the fake's prefix-cache TTFT model on
              (cold admission costs admit_latency_per_token_s per uncached
              prompt token), same high-reuse workload twice: lb_strategy
              least_connections (off) vs prefix_affinity (on). Rows carry
              the LB's hit/miss/rebind counters and the measured TTFT
              delta. Acceptance: hit-rate ≥ 90% and TTFT improves.
  kill        N=4 under load, one worker hard-killed mid-run, supervisor
              auto-respawns it (restart hook), retries+failover carry the
              in-flight work. Acceptance: ≥ 99% of requests token-exact.
  kvfabric    N=3 with the KV fabric on: a shared 256-token system prompt
              is cold-prefilled by exactly ONE worker; the coordinator
              pre-warms the other replicas over kv_export/kv_import, and a
              spread workload (distinct routing keys) proves every worker
              serves the prefix warm (fleet admit-sleep budget fits one
              cold prefill). Then the bound worker is hard-killed
              mid-stream: failover imports the cached wire into the
              alternate and hands the binding over. Acceptance: 100%
              token-exact, resumed TTFT ≤ 2x the affinity-hit TTFT, and
              two same-seed runs produce identical token receipts.
  stream      sub-chunk streaming at the SLO knee (ISSUE 13): N=2 replicas
              driven through ``Coordinator.submit_stream`` at ~50% of
              fleet capacity, once with whole-chunk emission (the fake's
              8-token megastep: ITL is chunk-quantized at 8x the per-step
              decode time) and once with 1-token sub-chunks through the
              device->host token ring. Acceptance: streaming ITL p99 <=
              1.5x per-step decode time, goodput within 10% of the
              non-streaming run, every stream token-exact (streamed concat
              == final result == crc chain), and two same-seed streaming
              runs produce identical token receipts.
  autoscale   the SLO loop closed (cluster/autoscaler.py): fleet starts at
              BENCH_FLEET_MIN under easy load, offered load jumps to
              BENCH_FLEET_BURST× one worker's capacity mid-run — the
              autoscaler must grow the fleet to BENCH_FLEET_MAX (spawn →
              artifact cold-start → half-open rejoin), then drain back
              down once the burst passes. Runs TWICE with the same seed.
              Acceptance: ≥ 99% token-exact through all the churn, fleet
              reaches max within 10 s of the burst, shrinks back to min,
              and the two runs' decision ledgers are identical.
  upgrade     N=3 replicas under live load, rolling upgrade to a new
              (token-identical) artifact: drain → swap → golden-probe →
              half-open rejoin, one worker at a time. Then a second
              rollout to a BAD artifact (different vocab — the probe's
              greedy tokens diverge) which must roll back on worker one
              and abort. Acceptance: 100% token-exact during the good
              rollout (zero dropped tokens), rollback proven, fleet still
              token-exact after the abort.
  multimodel  2 fake models (distinct vocab → distinct crc chains) on a
              2-worker fleet: model B stages in the BACKGROUND under live
              model-A load (goodput must hold within 10% — staging rides a
              side thread, never the dispatch executor), hot-swaps in
              behind the golden-token probe, then both models serve
              concurrently under interleaved model+prefix affinity load.
              Acceptance: per-model token-exact, staged swap >= 5x faster
              than a cold ``load_model``, per-model affinity hit rate >=
              90%, two same-seed runs emit identical receipts.
  long        long-context rung: 2048-token prompts (default policy;
              SWEEP_SHAPE=long raises to 8192) through the coordinator
              with per-token admission cost. Every result token-exact vs
              the analytic chain; the row carries TTFT/ITL percentiles.
  tiny        llama-tiny (real jax engines, CPU-friendly): 1 prefill + 1
              decode worker disaggregated vs a plain continuous reference
              worker, same seeded random-init weights (init key 0), same
              prompts — the disagg path must be token-exact against the
              single-engine answer THROUGH the coordinator. A CPU leg:
              its three in-process workers all build on JAX's default
              device (one worker per chip needs ``cli.worker`` processes,
              README "One worker per chip").

Knobs: BENCH_FLEET_* (read by bench.py — see its docstring) size the
fleet and load; SWEEP_LEGS=replicated,disagg,... runs a subset. One JSON
row per (leg, N) on stdout; per-leg BENCH_FLEET_<leg>.json files land in
BENCH_FLEET_DIR (default bench_obs, "0" disables); a markdown table on
stderr closes the run.

    python examples/fleet_sweep.py
    SWEEP_LEGS=replicated,affinity BENCH_FLEET_REQUESTS=80 \
        python examples/fleet_sweep.py
"""

import asyncio
import json
import os
import sys
import time
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

import bench  # noqa: E402  (repo-root bench.py: knobs + pct/log helpers)
from bench import log, pct  # noqa: E402
from distributed_inference_engine_tpu.api.coordinator import (  # noqa: E402
    Coordinator, CoordinatorConfig,
)
from distributed_inference_engine_tpu.cluster.autoscaler import (  # noqa: E402
    FleetAutoscaler, RollingUpgrade,
)
from distributed_inference_engine_tpu.cluster.worker import (  # noqa: E402
    WorkerServer,
)
from distributed_inference_engine_tpu.config import (  # noqa: E402
    AutoscalerConfig, HealthConfig, ModelConfig, ServerConfig,
)
from distributed_inference_engine_tpu.models.fake import _chain  # noqa: E402

VOCAB = 997
STEP_S = bench.FLEET_STEP_MS / 1e3


def expected_tokens(prompt, n, vocab=VOCAB):
    st = 0
    for t in prompt:
        st = _chain(st, t)
    out = []
    for _ in range(n):
        nxt = st % vocab
        st = _chain(st, nxt)
        out.append(nxt)
    return out


def fake_cfg(name="m", **meta) -> ModelConfig:
    md = {"continuous": 1, "max_slots": bench.FLEET_SLOTS,
          "step_latency_s": STEP_S}
    md.update(meta)
    return ModelConfig(name=name, architecture="fake", metadata=md)


async def start_fleet(n_workers, *, coord_cfg=None, prefix="w"):
    coord = Coordinator(coord_cfg or CoordinatorConfig(
        retry_seed=bench.FLEET_SEED, retry_backoff_base_s=0.01))
    await coord.start()
    workers = {}
    for i in range(n_workers):
        wid = f"{prefix}{i}"
        w = WorkerServer(ServerConfig(host="127.0.0.1", port=0,
                                      worker_id=wid))
        host, port = await w.start()
        workers[wid] = w
        coord.add_worker(wid, host, port)
    return coord, workers


async def stop_fleet(coord, workers):
    await coord.stop()
    for w in workers.values():
        try:
            await w.stop()
        except Exception:
            pass


async def worker_generated(coord, model="m"):
    """Per-worker generated-token counters (worker metrics RPC)."""
    out = {}
    for wid in list(coord.router.workers):
        try:
            m = await coord.router.client_for(wid).metrics()
        except Exception:
            continue
        eng = m.get("models", {}).get(model, {})
        out[wid] = {
            "generated": int(eng.get("total_generated_tokens", 0)),
            "handoff_bytes": int(m.get("handoff_bytes_shipped", 0)),
        }
    return out


async def drive(coord, prompts, rate, new_tokens, seed, model="m",
                mid_load_hook=None, tag="r"):
    """Poisson arrivals at ``rate`` req/s; returns (results, wall_s,
    ttfts, itls) with results aligned to ``prompts``. ``mid_load_hook``
    (an async callable) fires once ~a third of the way into the arrival
    schedule — the kill leg's sabotage slot. ``tag`` prefixes request
    ids so concurrent drives (the multimodel leg) don't collide."""
    rs = np.random.RandomState(seed)
    tasks = []
    fire_at = len(prompts) // 3
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        tasks.append(asyncio.ensure_future(coord.submit(
            model, prompt=p, max_new_tokens=new_tokens,
            request_id=f"{tag}{i}", no_cache=True)))
        if mid_load_hook is not None and i == fire_at:
            await mid_load_hook()
            mid_load_hook = None
        await asyncio.sleep(float(rs.exponential(1.0 / rate)))
    results = await asyncio.gather(*tasks, return_exceptions=True)
    wall = time.perf_counter() - t0
    ttfts, itls = [], []
    for r in results:
        if isinstance(r, dict):
            ttfts.append(float(r.get("ttft_s", 0.0)))
            n = len(r.get("tokens", ()))
            if n > 1:
                itls.append(float(r.get("decode_s", 0.0)) / n)
    return results, wall, ttfts, itls


def score(prompts, results, new_tokens, vocab=VOCAB):
    ok, toks = 0, 0
    for p, r in zip(prompts, results):
        if isinstance(r, dict):
            toks += len(r.get("tokens", ()))
            if r.get("tokens") == expected_tokens(p, new_tokens, vocab):
                ok += 1
    return ok, toks


def row_base(leg, n, wall, prompts, results, ttfts, itls, new_tokens,
             rate, gen0, gen1):
    ok, toks = score(prompts, results, new_tokens)
    per_worker = {
        wid: round((gen1[wid]["generated"]
                    - gen0.get(wid, {"generated": 0})["generated"]) / wall, 1)
        for wid in gen1}
    return {
        "leg": leg, "workers": n, "requests": len(prompts),
        "offered_req_s": round(rate, 1),
        "goodput_toks": round(toks / wall, 1),
        "token_exact": ok,
        "token_exact_frac": round(ok / max(1, len(prompts)), 4),
        "ttft_p50_ms": round(pct(ttfts, 0.5) * 1e3, 1),
        "ttft_p99_ms": round(pct(ttfts, 0.99) * 1e3, 1),
        "ttft_mean_ms": round(1e3 * sum(ttfts) / max(1, len(ttfts)), 1),
        "itl_p50_ms": round(pct(itls, 0.5) * 1e3, 2),
        "itl_p99_ms": round(pct(itls, 0.99) * 1e3, 2),
        "per_worker_goodput": per_worker,
        "wall_s": round(wall, 2),
    }


def emit(row):
    print(json.dumps(row), flush=True)
    return row


def dump_leg(leg, rows):
    if bench.FLEET_DIR in ("0", ""):
        return
    os.makedirs(bench.FLEET_DIR, exist_ok=True)
    path = os.path.join(bench.FLEET_DIR, f"BENCH_FLEET_{leg}.json")
    with open(path, "w") as f:
        json.dump({"leg": leg, "rows": rows}, f, indent=1)
    log(f"  wrote {path}")


def prompts_unique(n, seed, length=3):
    rs = np.random.RandomState(seed)
    return [[int(rs.randint(1, VOCAB)) for _ in range(length - 1)] + [i]
            for i in range(n)]


async def leg_replicated():
    rows = []
    for n in bench.FLEET_NS:
        coord, workers = await start_fleet(n)
        await coord.deploy_model(fake_cfg(), register_shards=False)
        n_req = bench.FLEET_REQUESTS * n
        rate = bench.FLEET_RATE * n
        prompts = prompts_unique(n_req, bench.FLEET_SEED + n)
        gen0 = await worker_generated(coord)
        results, wall, ttfts, itls = await drive(
            coord, prompts, rate, bench.FLEET_NEW_TOKENS,
            bench.FLEET_SEED + n)
        gen1 = await worker_generated(coord)
        rows.append(emit(row_base("replicated", n, wall, prompts, results,
                                  ttfts, itls, bench.FLEET_NEW_TOKENS,
                                  rate, gen0, gen1)))
        await stop_fleet(coord, workers)
    by_n = {r["workers"]: r["goodput_toks"] for r in rows}
    if 1 in by_n and 4 in by_n and by_n[1]:
        scaling = by_n[4] / by_n[1]
        log(f"  replicated scaling N=4 vs N=1: {scaling:.2f}x "
            f"(acceptance >= 3.2x)")
        rows.append(emit({"leg": "replicated", "summary": True,
                          "scaling_n4_vs_n1": round(scaling, 2)}))
    dump_leg("replicated", rows)
    return rows


async def leg_disagg():
    rows = []
    for n in bench.FLEET_NS:
        n_prefill = 1 if n < 4 else 2
        coord, workers = await start_fleet(0)
        for i in range(n_prefill):
            wid = f"p{i}"
            w = WorkerServer(ServerConfig(host="127.0.0.1", port=0,
                                          worker_id=wid))
            host, port = await w.start()
            workers[wid] = w
            coord.add_worker(wid, host, port)
        for i in range(n):
            wid = f"d{i}"
            w = WorkerServer(ServerConfig(host="127.0.0.1", port=0,
                                          worker_id=wid))
            host, port = await w.start()
            workers[wid] = w
            coord.add_worker(wid, host, port)
        await coord.deploy_model_disaggregated(
            fake_cfg(), [f"p{i}" for i in range(n_prefill)],
            [f"d{i}" for i in range(n)])
        n_req = bench.FLEET_REQUESTS * n
        rate = bench.FLEET_RATE * n
        # longer prompts than the replicated leg so the handoff KV is a
        # real payload (64 B/token on the fake's placeholder KV)
        prompts = prompts_unique(n_req, bench.FLEET_SEED + 10 * n, length=16)
        gen0 = await worker_generated(coord)
        results, wall, ttfts, itls = await drive(
            coord, prompts, rate, bench.FLEET_NEW_TOKENS,
            bench.FLEET_SEED + 10 * n)
        gen1 = await worker_generated(coord)
        row = row_base("disagg", n, wall, prompts, results, ttfts, itls,
                       bench.FLEET_NEW_TOKENS, rate, gen0, gen1)
        hb = sum(gen1[w]["handoff_bytes"]
                 - gen0.get(w, {"handoff_bytes": 0})["handoff_bytes"]
                 for w in gen1 if w.startswith("p"))
        row["prefill_workers"] = n_prefill
        row["handoff_bytes"] = hb
        row["handoff_bytes_per_s"] = round(hb / wall, 1)
        rows.append(emit(row))
        await stop_fleet(coord, workers)
    dump_leg("disagg", rows)
    return rows


def _affinity_prompts(n_prefixes, per_prefix, prefix_len, seed):
    rs = np.random.RandomState(seed)
    prefixes = [[int(rs.randint(1, VOCAB)) for _ in range(prefix_len)]
                for _ in range(n_prefixes)]
    prompts = [prefixes[i] + [i, j]
               for i in range(n_prefixes) for j in range(per_prefix)]
    rs.shuffle(prompts)
    return prompts


async def leg_affinity():
    n = 4
    page = 64
    cfg = fake_cfg(prefix_cache=1, prefix_page_size=page,
                   admit_latency_per_token_s=5e-4)
    prompts = _affinity_prompts(12, 20, 2 * page, bench.FLEET_SEED)
    # moderate utilisation (~40%) so TTFT reflects admission cost, not
    # queueing noise — the cold/warm admission delta is what this leg is
    # isolating
    rate = 0.4 * bench.FLEET_SLOTS / STEP_S / bench.FLEET_NEW_TOKENS * n
    rows = []
    for mode, strategy in (("off", "least_connections"),
                           ("on", "prefix_affinity")):
        coord, workers = await start_fleet(n, coord_cfg=CoordinatorConfig(
            lb_strategy=strategy, affinity_page_size=page, affinity_pages=2,
            retry_seed=bench.FLEET_SEED, retry_backoff_base_s=0.01))
        await coord.deploy_model(cfg, register_shards=False)
        gen0 = await worker_generated(coord)
        results, wall, ttfts, itls = await drive(
            coord, prompts, rate, bench.FLEET_NEW_TOKENS, bench.FLEET_SEED)
        gen1 = await worker_generated(coord)
        row = row_base(f"affinity_{mode}", n, wall, prompts, results,
                       ttfts, itls, bench.FLEET_NEW_TOKENS, rate,
                       gen0, gen1)
        lb = coord.lb.get_all_stats()
        hits = lb.get("affinity_hits", 0)
        misses = lb.get("affinity_misses", 0)
        row["affinity_hits"] = hits
        row["affinity_misses"] = misses
        row["affinity_rebinds"] = lb.get("affinity_rebinds", 0)
        row["affinity_hit_rate"] = round(
            hits / max(1, hits + misses), 4)
        rows.append(emit(row))
        await stop_fleet(coord, workers)
    off, on = rows
    delta = off["ttft_mean_ms"] - on["ttft_mean_ms"]
    log(f"  affinity: hit-rate {on['affinity_hit_rate']:.1%} "
        f"(acceptance >= 90%), TTFT mean {off['ttft_mean_ms']:.1f} -> "
        f"{on['ttft_mean_ms']:.1f} ms ({delta:+.1f} ms improvement)")
    rows.append(emit({"leg": "affinity", "summary": True,
                      "hit_rate": on["affinity_hit_rate"],
                      "ttft_mean_improvement_ms": round(delta, 1)}))
    dump_leg("affinity", rows)
    return rows


async def leg_kill():
    n = 4
    coord_cfg = CoordinatorConfig(
        retry_seed=bench.FLEET_SEED, retry_backoff_base_s=0.01,
        health=HealthConfig(check_interval=0.05, check_timeout=0.5,
                            max_consecutive_failures=2),
        supervisor_interval_s=0.05, supervisor_backoff_base_s=0.02,
        supervisor_backoff_max_s=0.1)
    coord, workers = await start_fleet(n, coord_cfg=coord_cfg)
    cfg = fake_cfg()
    spawned = []

    async def restart_hook(worker_id, info):
        w = WorkerServer(ServerConfig(host="127.0.0.1", port=0,
                                      worker_id=worker_id))
        host, port = await w.start()
        spawned.append(w)
        return host, port

    coord.start_supervisor(restart_hook)
    await coord.deploy_model(cfg)

    async def sabotage():
        victim = f"w{n - 1}"
        log(f"  !! hard-killing {victim} mid-load (supervisor respawns)")
        await workers.pop(victim).stop()

    n_req = bench.FLEET_REQUESTS * n
    rate = 0.8 * bench.FLEET_RATE * n
    prompts = prompts_unique(n_req, bench.FLEET_SEED + 77)
    gen0 = await worker_generated(coord)
    results, wall, ttfts, itls = await drive(
        coord, prompts, rate, bench.FLEET_NEW_TOKENS,
        bench.FLEET_SEED + 77, mid_load_hook=sabotage)
    for _ in range(100):
        if coord.get_stats()["supervisor_respawns"] >= 1:
            break
        await asyncio.sleep(0.05)
    gen1 = await worker_generated(coord)
    stats = coord.get_stats()
    row = row_base("kill", n, wall, prompts, results, ttfts, itls,
                   bench.FLEET_NEW_TOKENS, rate, gen0, gen1)
    row["supervisor_respawns"] = stats["supervisor_respawns"]
    row["dispatch_retries"] = stats["dispatch_retries"]
    log(f"  kill leg: {row['token_exact']}/{n_req} token-exact "
        f"({row['token_exact_frac']:.1%}, acceptance >= 99%), "
        f"respawns={row['supervisor_respawns']}")
    rows = [emit(row)]
    await stop_fleet(coord, workers)
    for w in spawned:
        try:
            await w.stop()
        except Exception:
            pass
    dump_leg("kill", rows)
    return rows


def _spawner(spawned):
    """Spawn-hook factory shared by the autoscale/upgrade legs: bring up a
    fresh local WorkerServer and hand back its address (the same contract
    as the kill leg's supervisor restart hook)."""
    async def hook(worker_id, info):
        w = WorkerServer(ServerConfig(host="127.0.0.1", port=0,
                                      worker_id=worker_id))
        host, port = await w.start()
        spawned.append(w)
        return host, port
    return hook


async def _autoscale_once(tag):
    """One seeded autoscale run: easy load → burst → easy load → settle.
    Returns (row, canonical ledger). Seeds are run-independent so a second
    call replays the same offered load."""
    cap = bench.FLEET_SLOTS / STEP_S / bench.FLEET_NEW_TOKENS  # req/s/worker
    base_rate = 0.5 * cap
    burst_rate = bench.FLEET_BURST * cap
    as_cfg = AutoscalerConfig(
        ttft_p95_target_s=0.3, itl_p95_target_s=0.0,
        queue_depth_target=4.0,
        min_workers=bench.FLEET_MIN, max_workers=bench.FLEET_MAX,
        breach_ticks=2, clear_ticks=4,
        cooldown_up_ticks=2, cooldown_down_ticks=4,
        # the shed path is unit-tested; this leg sizes the burst so max
        # fleet CAN absorb it, making the decision sequence replay-stable
        shed_ticks=10_000,
        interval_s=0.1, seed=bench.FLEET_SEED)
    # fast health probes (as in the kill leg) so a half-open rejoin gets
    # its trial within one tick instead of a default probe period
    coord_cfg = CoordinatorConfig(
        retry_seed=bench.FLEET_SEED, retry_backoff_base_s=0.01,
        health=HealthConfig(check_interval=0.05, check_timeout=1.0,
                            max_consecutive_failures=3))
    coord, workers = await start_fleet(bench.FLEET_MIN, prefix=f"{tag}w",
                                       coord_cfg=coord_cfg)
    await coord.deploy_model(fake_cfg(), register_shards=False)
    spawned = []
    scaler = FleetAutoscaler(coord, "m", spawn_hook=_spawner(spawned),
                             cfg=as_cfg, worker_prefix=f"{tag}as")
    await scaler.start()

    n1 = bench.FLEET_REQUESTS
    n2 = 5 * bench.FLEET_REQUESTS
    p1 = prompts_unique(n1, bench.FLEET_SEED + 201)
    p2 = prompts_unique(n2, bench.FLEET_SEED + 202)
    p3 = prompts_unique(n1, bench.FLEET_SEED + 203)

    peak = {"fleet": bench.FLEET_MIN, "t_max": None}

    async def monitor(t_burst):
        while peak["t_max"] is None:
            size = scaler.get_stats()["fleet_size"]
            peak["fleet"] = max(peak["fleet"], size)
            if size >= as_cfg.max_workers:
                peak["t_max"] = time.perf_counter() - t_burst
            await asyncio.sleep(0.05)

    gen0 = await worker_generated(coord)
    r1, w1, t1, i1 = await drive(coord, p1, base_rate,
                                 bench.FLEET_NEW_TOKENS,
                                 bench.FLEET_SEED + 201)
    mon = asyncio.ensure_future(monitor(time.perf_counter()))
    r2, w2, t2, i2 = await drive(coord, p2, burst_rate,
                                 bench.FLEET_NEW_TOKENS,
                                 bench.FLEET_SEED + 202)
    r3, w3, t3, i3 = await drive(coord, p3, base_rate,
                                 bench.FLEET_NEW_TOKENS,
                                 bench.FLEET_SEED + 203)
    # settle: no offered load — the controller must drain back to min
    for _ in range(150):
        if scaler.get_stats()["fleet_size"] <= as_cfg.min_workers:
            break
        await asyncio.sleep(0.1)
    mon.cancel()
    await scaler.stop()
    gen1 = await worker_generated(coord)
    stats = scaler.get_stats()

    prompts = p1 + p2 + p3
    results = list(r1) + list(r2) + list(r3)
    wall = w1 + w2 + w3
    ttfts, itls = t1 + t2 + t3, i1 + i2 + i3
    row = row_base(f"autoscale_{tag}", bench.FLEET_MAX, wall, prompts,
                   results, ttfts, itls, bench.FLEET_NEW_TOKENS,
                   burst_rate, gen0, gen1)
    ok2, toks2 = score(p2, r2, bench.FLEET_NEW_TOKENS)
    row["burst_goodput_toks"] = round(toks2 / w2, 1)
    row["peak_fleet"] = peak["fleet"]
    row["final_fleet"] = stats["fleet_size"]
    row["time_to_max_fleet_s"] = (round(peak["t_max"], 2)
                                  if peak["t_max"] is not None else None)
    row["scale_ups"] = stats["scale_ups"]
    row["scale_downs"] = stats["scale_downs"]
    row["guard_holds"] = stats["guard_holds"]
    row["ledger"] = stats["ledger"]
    # canonical replay form: the action/fleet-size sequence (the reason
    # string names whichever SLO dimension crossed first — informational)
    ledger = [(e["action"], e["fleet_from"], e["fleet_to"])
              for e in stats["ledger"]]
    await stop_fleet(coord, workers)
    for w in spawned:
        try:
            await w.stop()
        except Exception:
            pass
    return row, ledger


async def leg_autoscale():
    rows = []
    ledgers = []
    for tag in ("a", "b"):
        row, ledger = await _autoscale_once(tag)
        rows.append(emit(row))
        ledgers.append(ledger)
        log(f"  autoscale run {tag}: token-exact "
            f"{row['token_exact_frac']:.1%} (acceptance >= 99%), fleet "
            f"{bench.FLEET_MIN} -> {row['peak_fleet']} -> "
            f"{row['final_fleet']}, max reached in "
            f"{row['time_to_max_fleet_s']}s (acceptance <= 10s), "
            f"ledger {ledger}")
    replay_ok = ledgers[0] == ledgers[1] and len(ledgers[0]) > 0
    log(f"  autoscale replay: same-seed ledgers "
        f"{'IDENTICAL' if replay_ok else 'DIVERGED'} (acceptance: "
        f"identical)")
    rows.append(emit({"leg": "autoscale", "summary": True,
                      "ledgers_identical": replay_ok,
                      "ledger": ledgers[0]}))
    dump_leg("autoscale", rows)
    return rows


async def leg_upgrade():
    n = 3
    # fast health probes so each upgraded worker's half-open trial closes
    # promptly and the fleet is fully healthy between rollouts
    coord_cfg = CoordinatorConfig(
        retry_seed=bench.FLEET_SEED, retry_backoff_base_s=0.01,
        health=HealthConfig(check_interval=0.05, check_timeout=1.0,
                            max_consecutive_failures=3))
    coord, workers = await start_fleet(n, coord_cfg=coord_cfg)
    await coord.deploy_model(fake_cfg(), register_shards=False)
    spawned = []
    hook = _spawner(spawned)

    # -- good rollout under live load: new artifact rev, same token chain
    good_cfg = fake_cfg(artifact_rev=2)
    upg = RollingUpgrade(coord, "m", good_cfg, swap_hook=hook,
                         probe_prompt=[5, 3, 2], probe_new_tokens=8)
    rate = 0.4 * bench.FLEET_RATE * n
    prompts = prompts_unique(2 * bench.FLEET_REQUESTS,
                             bench.FLEET_SEED + 301)
    gen0 = await worker_generated(coord)
    drive_task = asyncio.ensure_future(drive(
        coord, prompts, rate, bench.FLEET_NEW_TOKENS,
        bench.FLEET_SEED + 301))
    await asyncio.sleep(0.2)   # streams in flight before the first drain
    summary = await upg.run([f"w{i}" for i in range(n)])
    results, wall, ttfts, itls = await drive_task
    gen1 = await worker_generated(coord)
    row = row_base("upgrade", n, wall, prompts, results, ttfts, itls,
                   bench.FLEET_NEW_TOKENS, rate, gen0, gen1)
    row["upgrade_completed"] = summary["completed"]
    row["upgraded"] = summary["upgraded"]
    dropped = row["requests"] - row["token_exact"]
    log(f"  upgrade: rolled {summary['upgraded']}/{n} workers under load, "
        f"{row['token_exact']}/{row['requests']} token-exact "
        f"({dropped} dropped/diverged, acceptance 0)")
    rows = [emit(row)]

    # -- bad rollout: vocab changes the chain, the golden probe must catch
    # it on worker one, roll back, and abort
    bad_cfg = fake_cfg(vocab_size=991)
    upg2 = RollingUpgrade(coord, "m", bad_cfg, swap_hook=hook,
                          probe_prompt=[5, 3, 2], probe_new_tokens=8)
    summary2 = await upg2.run([f"w{i}" for i in range(n)])
    probe = prompts_unique(8, bench.FLEET_SEED + 302)
    exact = 0
    for i, p in enumerate(probe):
        r = await coord.submit("m", prompt=p,
                               max_new_tokens=bench.FLEET_NEW_TOKENS,
                               request_id=f"pb{i}", no_cache=True)
        if r["tokens"] == expected_tokens(p, bench.FLEET_NEW_TOKENS):
            exact += 1
    row2 = {"leg": "upgrade_rollback", "workers": n,
            "requests": len(probe), "token_exact": exact,
            "token_exact_frac": round(exact / len(probe), 4),
            "upgrade_completed": summary2["completed"],
            "aborted_at": summary2.get("aborted_at"),
            "rolled_back": summary2.get("rolled_back"),
            "probe_failures": upg2.get_stats()["probe_failures"],
            "rollbacks": upg2.get_stats()["rollbacks"]}
    log(f"  upgrade rollback: bad artifact caught at "
        f"{summary2.get('aborted_at')} (completed={summary2['completed']},"
        f" rolled_back={summary2.get('rolled_back')}), post-abort fleet "
        f"{exact}/{len(probe)} token-exact")
    rows.append(emit(row2))
    await stop_fleet(coord, workers)
    for w in spawned:
        try:
            await w.stop()
        except Exception:
            pass
    dump_leg("upgrade", rows)
    return rows


async def leg_tiny():
    """Real-engine leg: llama-tiny disaggregated through the coordinator
    must match a plain single-engine worker token-for-token (both engines
    random-init from the same fixed key, so their logits agree)."""
    base = dict(architecture="llama-tiny", max_seq_len=128,
                max_batch_size=4)
    cfg = ModelConfig(name="tiny", metadata={"continuous": 1,
                                             "max_slots": 2}, **base)
    ref_cfg = ModelConfig(name="tiny_ref", metadata={"continuous": 1,
                                                     "max_slots": 2}, **base)
    coord, workers = await start_fleet(0)
    for wid in ("tp0", "td0", "ref0"):
        w = WorkerServer(ServerConfig(host="127.0.0.1", port=0,
                                      worker_id=wid))
        host, port = await w.start()
        workers[wid] = w
        coord.add_worker(wid, host, port)
    t0 = time.perf_counter()
    await coord.deploy_model_disaggregated(cfg, ["tp0"], ["td0"])
    await coord.deploy_model(ref_cfg, worker_ids=["ref0"])
    log(f"  tiny: engines up in {time.perf_counter() - t0:.1f}s")
    rs = np.random.RandomState(bench.FLEET_SEED)
    prompts = [[int(rs.randint(1, 96)) for _ in range(16)]
               for _ in range(4)]
    exact = 0
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        got = await coord.submit("tiny", prompt=p, max_new_tokens=8,
                                 request_id=f"t{i}", no_cache=True)
        ref = await coord.submit("tiny_ref", prompt=p, max_new_tokens=8,
                                 request_id=f"tr{i}", no_cache=True)
        if got["tokens"] == ref["tokens"]:
            exact += 1
        else:
            log(f"  tiny MISMATCH req {i}: disagg={got['tokens']} "
                f"ref={ref['tokens']}")
    wall = time.perf_counter() - t0
    m = await coord.router.client_for("tp0").metrics()
    row = {"leg": "tiny", "workers": 2, "requests": len(prompts),
           "token_exact": exact,
           "token_exact_frac": round(exact / len(prompts), 4),
           "handoff_bytes": int(m.get("handoff_bytes_shipped", 0)),
           "wall_s": round(wall, 2)}
    log(f"  tiny: {exact}/{len(prompts)} token-exact vs single-engine "
        f"reference, {row['handoff_bytes']} handoff bytes")
    rows = [emit(row)]
    await stop_fleet(coord, workers)
    dump_leg("tiny", rows)
    return rows


async def _fabric_worker_metrics(coord, model="m"):
    """Per-worker engine + kv_fabric_* counters (worker metrics RPC)."""
    out = {}
    for wid in list(coord.router.workers):
        try:
            m = await coord.router.client_for(wid).metrics()
        except Exception:
            continue
        eng = dict(m.get("models", {}).get(model, {}))
        eng.update({k: v for k, v in m.items()
                    if k.startswith("kv_fabric_")})
        out[wid] = eng
    return out


async def _kvfabric_once(seed, run_tag):
    """One seeded pass of the kvfabric leg. Returns (rows, receipt) where
    the receipt is the canonical (tag, tokens) ledger — two same-seed
    passes must produce identical receipts."""
    n = 3
    page = 64
    lat = 2e-3  # cold admission: 2 ms per uncached prompt token
    sys_prefix = [int(t) for t in
                  np.random.RandomState(seed).randint(1, VOCAB, 4 * page)]
    nt = bench.FLEET_NEW_TOKENS
    cfg = fake_cfg(prefix_cache=1, prefix_page_size=page,
                   admit_latency_per_token_s=lat)
    coord_cfg = CoordinatorConfig(
        # affinity_pages covers the FULL system prompt: the fabric
        # migrates the prefix the affinity router tracks, so the wire
        # must span all four pages for the one-cold-prefill budget
        lb_strategy="prefix_affinity", affinity_page_size=page,
        affinity_pages=4, retry_seed=seed, retry_backoff_base_s=0.01,
        health=HealthConfig(check_interval=0.05, check_timeout=0.5,
                            max_consecutive_failures=2),
        supervisor_interval_s=0.05, supervisor_backoff_base_s=0.02,
        supervisor_backoff_max_s=0.1)
    coord, workers = await start_fleet(n, coord_cfg=coord_cfg)
    spawned = []
    coord.start_supervisor(_spawner(spawned))
    await coord.deploy_model(cfg, register_shards=False)
    receipt, rows = [], []
    try:
        # -- phase 1: ONE cold prefill fleet-wide, then fabric pre-warm.
        # The warm-up request binds the shared system prompt to one worker
        # and pays the only cold admission of the whole leg; every other
        # worker receives the pages over the fabric instead.
        p0 = sys_prefix + [1, 0]
        r = await coord.submit("m", prompt=p0, max_new_tokens=nt,
                               no_cache=True)
        assert r["tokens"] == expected_tokens(p0, nt), "warm-up diverged"
        ttft_cold = float(r["ttft_s"])
        receipt.append(("warmup", tuple(r["tokens"])))
        origin = next(iter(coord.lb._affinity.values()))
        for _ in range(200):  # background snapshot → coordinator wire cache
            if coord.get_stats()["kv_fabric_cached_wires"] >= 1:
                break
            await asyncio.sleep(0.02)
        assert coord.get_stats()["kv_fabric_cached_wires"] >= 1, \
            "fabric snapshot never landed"
        prewarmed = 0
        for wid in workers:
            if wid != origin:
                prewarmed += await coord.prewarm_worker(wid)
        assert prewarmed == n - 1, \
            f"pre-warm landed on {prewarmed}/{n - 1} workers"

        # -- phase 2: shared-system-prompt spread. Distinct routing keys
        # force the requests across ALL workers; each must admit the
        # shared prefix warm off its imported copy.
        sleep0 = sum(m.get("admit_sleep_s", 0.0) for m in
                     (await _fabric_worker_metrics(coord)).values())
        gen0 = await worker_generated(coord)
        spread = [sys_prefix + [2, j] for j in range(4 * n)]
        t0 = time.perf_counter()
        s_res = await asyncio.gather(*[
            coord.submit("m", prompt=p, max_new_tokens=nt, key=f"s{j}",
                         no_cache=True)
            for j, p in enumerate(spread)], return_exceptions=True)
        wall = time.perf_counter() - t0
        ok, toks = score(spread, s_res, nt)
        assert ok == len(spread), f"spread phase: {ok}/{len(spread)} exact"
        receipt += [(f"spread{j}", tuple(r["tokens"]))
                    for j, r in enumerate(s_res)]
        gen1 = await worker_generated(coord)
        wm = await _fabric_worker_metrics(coord)
        served = {wid: gen1[wid]["generated"]
                  - gen0.get(wid, {"generated": 0})["generated"]
                  for wid in gen1}
        assert all(v > 0 for v in served.values()), \
            f"a worker served nothing: {served}"
        for wid, m in wm.items():
            if wid != origin:
                assert m.get("fabric_imports", 0) >= 1, \
                    f"{wid} never imported over the fabric"
        # the fleet-wide cold-admission bill must fit ONE prefix prefill
        # plus the per-request uncached tails — a second cold prefill
        # anywhere would blow the budget by ~prefix_len * lat
        sleep1 = sum(m.get("admit_sleep_s", 0.0) for m in wm.values())
        uncached_budget = lat * (len(sys_prefix) + 2 * (len(spread) + 1))
        assert sleep1 - 0.0 <= uncached_budget * 1.25 + 0.05, \
            f"prefix cold-prefilled more than once fleet-wide " \
            f"(admit sleep {sleep1:.3f}s > budget {uncached_budget:.3f}s)"
        ttfts = [float(r["ttft_s"]) for r in s_res if isinstance(r, dict)]
        rows.append(emit({
            "leg": "kvfabric_prewarm", "run": run_tag, "workers": n,
            "requests": len(spread), "token_exact": ok,
            "token_exact_frac": round(ok / len(spread), 4),
            "goodput_toks": round(toks / wall, 1),
            "ttft_cold_ms": round(ttft_cold * 1e3, 1),
            "ttft_p50_ms": round(pct(ttfts, 0.5) * 1e3, 1),
            "ttft_p99_ms": round(pct(ttfts, 0.99) * 1e3, 1),
            "prewarm_pushes": prewarmed,
            "fleet_admit_sleep_s": round(sleep1, 3),
            "served_per_worker": served, "wall_s": round(wall, 2)}))

        # -- phase 3: mid-stream kill of the bound worker. The failover
        # path imports the dead stream's cached wire into the alternate
        # and hands the binding over, so resumed TTFT stays warm.
        kill_prompts = [sys_prefix + [3, j] for j in range(18)]
        rate = 30.0

        async def sabotage():
            log(f"  !! hard-killing bound worker {origin} mid-stream")
            await workers.pop(origin).stop()

        k_res, k_wall, _, _ = await drive(
            coord, kill_prompts, rate, nt, seed + 1,
            mid_load_hook=sabotage)
        ok_k, toks_k = score(kill_prompts, k_res, nt)
        assert ok_k == len(kill_prompts), \
            f"kill phase: {ok_k}/{len(kill_prompts)} exact"
        receipt += [(f"kill{j}", tuple(r["tokens"]))
                    for j, r in enumerate(k_res)]
        fire_at = len(kill_prompts) // 3
        warm = [float(r["ttft_s"]) for r in k_res[:fire_at]
                if isinstance(r, dict)]
        resumed = [float(r["ttft_s"]) for r in k_res[fire_at:]
                   if isinstance(r, dict)]
        ratio = pct(resumed, 0.5) / max(pct(warm, 0.5), 1e-9)
        for _ in range(100):
            if coord.get_stats()["supervisor_respawns"] >= 1:
                break
            await asyncio.sleep(0.05)
        st = coord.get_stats()
        rows.append(emit({
            "leg": "kvfabric_kill", "run": run_tag, "workers": n,
            "requests": len(kill_prompts), "token_exact": ok_k,
            "token_exact_frac": round(ok_k / len(kill_prompts), 4),
            "goodput_toks": round(toks_k / k_wall, 1),
            "ttft_warm_p50_ms": round(pct(warm, 0.5) * 1e3, 1),
            "ttft_resumed_p50_ms": round(pct(resumed, 0.5) * 1e3, 1),
            "resumed_over_warm": round(ratio, 2),
            "failover_imports": st["kv_fabric_failover_imports"],
            "prewarm_pushes_total": st["kv_fabric_prewarm_pushes"],
            "supervisor_respawns": st["supervisor_respawns"],
            "wall_s": round(k_wall, 2)}))
        assert ratio <= 2.0, \
            f"resumed TTFT {ratio:.2f}x warm (acceptance <= 2x)"
    finally:
        await stop_fleet(coord, workers)
        for w in spawned:
            try:
                await w.stop()
            except Exception:
                pass
    return rows, receipt


async def leg_kvfabric():
    """KV fabric leg: shared-system-prompt fleet where the prefix is
    prefilled locally at most once fleet-wide (everyone else imports it),
    plus a mid-stream kill whose resumed TTFT must stay within 2x the
    affinity-hit TTFT. Runs TWICE with the same seed — the token receipts
    must be identical."""
    rows_a, receipt_a = await _kvfabric_once(bench.FLEET_SEED, "a")
    rows_b, receipt_b = await _kvfabric_once(bench.FLEET_SEED, "b")
    assert receipt_a == receipt_b, \
        "same-seed kvfabric runs produced different token receipts"
    h = zlib.crc32(repr(receipt_a).encode()) & 0xFFFFFFFF
    log(f"  kvfabric: receipts identical across same-seed runs "
        f"(crc32 {h:#010x}), resumed TTFT "
        f"{rows_a[1]['resumed_over_warm']}x warm (acceptance <= 2x)")
    rows = rows_a + rows_b
    rows.append(emit({"leg": "kvfabric", "summary": True,
                      "receipt_crc32": h, "receipts_identical": True,
                      "resumed_over_warm": rows_a[1]["resumed_over_warm"]}))
    dump_leg("kvfabric", rows)
    return rows


async def _stream_run(meta, n, prompts, rate, nt, seed):
    """One seeded streaming pass: every request rides submit_stream, each
    delivered frame is stamped at the coordinator hand-off (the consumer
    side of the relay — engine ring, worker RPC and coordinator hop are
    all inside the gap). Returns per-token ITLs built the serving_main
    way: one inter-frame gap per frame, zero-cost co-arrivals for the
    rest of the frame's tokens."""
    coord, workers = await start_fleet(n)
    await coord.deploy_model(fake_cfg(**meta), register_shards=False)
    rs = np.random.RandomState(seed)
    marks = [[] for _ in prompts]

    def mk_cb(rec):
        def cb(toks):
            rec.append((time.perf_counter(), list(toks)))
        return cb

    tasks = []
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        tasks.append(asyncio.ensure_future(coord.submit_stream(
            "m", prompt=p, max_new_tokens=nt, on_tokens=mk_cb(marks[i]),
            request_id=f"s{i}")))
        await asyncio.sleep(float(rs.exponential(1.0 / rate)))
    results = await asyncio.gather(*tasks, return_exceptions=True)
    wall = time.perf_counter() - t0
    itls, frames, spliced = [], 0, 0
    for ms, r in zip(marks, results):
        frames += len(ms)
        streamed = [t for _, toks in ms for t in toks]
        if isinstance(r, dict) and streamed == r.get("tokens"):
            spliced += 1
        prev = None
        for t, toks in ms:
            if prev is not None:
                itls.append(t - prev)
            itls.extend([0.0] * (len(toks) - 1))
            prev = t
    st = coord.get_stats()
    receipt = [tuple(r["tokens"]) if isinstance(r, dict) else ("ERR",)
               for r in results]
    await stop_fleet(coord, workers)
    return results, wall, itls, frames, spliced, st, receipt


async def leg_stream():
    """Sub-chunk streaming vs whole-chunk emission at the SLO knee
    (ISSUE 13's measurement half). Calibration: 8 tokens per 80 ms fake
    step = 10 ms per-step decode time, so whole-chunk ITL is quantized at
    8x (one 8-token frame per step) while 1-token sub-chunks should land
    each token within 1.5x."""
    n = 2
    nt = bench.FLEET_NEW_TOKENS
    tps, step_s = 8, 0.08
    per_token = step_s / tps              # the per-step decode analog
    base_meta = dict(step_latency_s=step_s, tokens_per_step=tps)
    sub_meta = dict(base_meta, stream_chunk_tokens=1,
                    stream_dispatch_overhead_s=1e-4)
    # the knee: ~50% of fleet token capacity — past it queueing noise
    # drowns the emission cadence this leg is isolating
    cap = bench.FLEET_SLOTS * tps / step_s / nt   # req/s per worker
    rate = 0.5 * cap * n
    n_req = bench.FLEET_REQUESTS * n
    prompts = prompts_unique(n_req, bench.FLEET_SEED + 401)
    rows, receipts = [], {}
    runs = (("base", base_meta), ("sub", sub_meta), ("sub_replay", sub_meta))
    for mode, meta in runs:
        results, wall, itls, frames, spliced, st, receipt = \
            await _stream_run(meta, n, prompts, rate, nt,
                              bench.FLEET_SEED + 401)
        receipts[mode] = receipt
        ok, toks = score(prompts, results, nt)
        itl_stats = st.get("stream_itl", {})
        row = {
            "leg": f"stream_{mode}", "workers": n, "requests": n_req,
            "offered_req_s": round(rate, 1),
            "goodput_toks": round(toks / wall, 1),
            "token_exact": ok,
            "token_exact_frac": round(ok / max(1, n_req), 4),
            "stream_spliced_exact": spliced,
            "frames": frames,
            "frames_per_req": round(frames / max(1, n_req), 2),
            "itl_p50_ms": round(pct(itls, 0.5) * 1e3, 2),
            "itl_p99_ms": round(pct(itls, 0.99) * 1e3, 2),
            "per_step_ms": round(per_token * 1e3, 2),
            "coord_stream_frames": st.get("stream_frames", 0),
            "coord_itl_count": int(itl_stats.get("count", 0)),
            "wall_s": round(wall, 2),
        }
        rows.append(emit(row))
        assert ok == n_req, f"stream_{mode}: {ok}/{n_req} token-exact"
        assert spliced == n_req, \
            f"stream_{mode}: {spliced}/{n_req} streams spliced exact"
    base, sub = rows[0], rows[1]
    itl_ratio = sub["itl_p99_ms"] / (per_token * 1e3)
    base_ratio = base["itl_p99_ms"] / (per_token * 1e3)
    goodput_frac = sub["goodput_toks"] / max(base["goodput_toks"], 1e-9)
    replay_ok = receipts["sub"] == receipts["sub_replay"]
    log(f"  stream: ITL p99 {base['itl_p99_ms']:.2f} ms "
        f"({base_ratio:.1f}x per-step, chunk-quantized) -> "
        f"{sub['itl_p99_ms']:.2f} ms ({itl_ratio:.2f}x per-step, "
        f"acceptance <= 1.5x); goodput {base['goodput_toks']} -> "
        f"{sub['goodput_toks']} tok/s ({goodput_frac:.1%}, acceptance "
        f">= 90%); same-seed receipts "
        f"{'IDENTICAL' if replay_ok else 'DIVERGED'}")
    assert base_ratio >= 0.95 * tps, \
        f"baseline ITL p99 {base_ratio:.2f}x not chunk-quantized"
    assert itl_ratio <= 1.5, \
        f"streaming ITL p99 {itl_ratio:.2f}x per-step (acceptance <= 1.5x)"
    assert goodput_frac >= 0.9, \
        f"streaming goodput {goodput_frac:.1%} of baseline (floor 90%)"
    assert replay_ok, "same-seed streaming runs diverged"
    rows.append(emit({"leg": "stream", "summary": True,
                      "itl_p99_over_per_step": round(itl_ratio, 2),
                      "baseline_itl_p99_over_per_step": round(base_ratio, 2),
                      "goodput_vs_base": round(goodput_frac, 4),
                      "receipts_identical": replay_ok}))
    dump_leg("stream", rows)
    return rows


async def _multimodel_once(run_tag):
    """One seeded pass of the multimodel leg. Returns (rows, receipt)
    where the receipt is the canonical (tag, tokens) ledger — two
    same-seed passes must produce identical receipts."""
    from distributed_inference_engine_tpu.engine.artifact import (
        GOLDEN_PROMPT,
    )
    n = 2
    page = 64
    nt = bench.FLEET_NEW_TOKENS
    lat = 5e-4
    load_sleep = 0.5    # the fake's cold checkpoint-read cost
    vocab_b = 1009      # distinct vocab -> distinct crc token chain
    ma = fake_cfg(name="ma", prefix_cache=1, prefix_page_size=page,
                  admit_latency_per_token_s=lat, load_sleep_s=load_sleep)
    mb = fake_cfg(name="mb", vocab_size=vocab_b, prefix_cache=1,
                  prefix_page_size=page, admit_latency_per_token_s=lat,
                  load_sleep_s=load_sleep)
    coord_cfg = CoordinatorConfig(
        lb_strategy="prefix_affinity", affinity_page_size=page,
        affinity_pages=2, retry_seed=bench.FLEET_SEED,
        retry_backoff_base_s=0.01)
    coord, workers = await start_fleet(n, coord_cfg=coord_cfg,
                                       prefix=f"{run_tag}w")
    rate = 0.4 * bench.FLEET_SLOTS / STEP_S / nt * n
    receipt, rows = [], []
    try:
        await coord.deploy_model(ma, register_shards=False)

        # -- phase 1: single-model baseline goodput for ma
        p1 = _affinity_prompts(8, 8, 2 * page, bench.FLEET_SEED + 501)
        r1, w1, t1, _ = await drive(coord, p1, rate, nt,
                                    bench.FLEET_SEED + 501, model="ma",
                                    tag="ma1_")
        ok1, toks1 = score(p1, r1, nt)
        assert ok1 == len(p1), f"baseline: {ok1}/{len(p1)} exact"
        receipt += [("base", tuple(r["tokens"])) for r in r1]
        goodput_base = toks1 / w1

        # -- phase 2: stage mb in the BACKGROUND and immediately re-drive
        # ma — staging must not displace dispatch, so goodput holds
        staged = await coord.stage_model(mb)
        assert staged == n, f"staging started on {staged}/{n} workers"
        p2 = _affinity_prompts(8, 8, 2 * page, bench.FLEET_SEED + 502)
        r2, w2, t2, _ = await drive(coord, p2, rate, nt,
                                    bench.FLEET_SEED + 502, model="ma",
                                    tag="ma2_")
        ok2, toks2 = score(p2, r2, nt)
        assert ok2 == len(p2), f"staged drive: {ok2}/{len(p2)} exact"
        receipt += [("staged", tuple(r["tokens"])) for r in r2]
        goodput_staged = toks2 / w2
        goodput_frac = goodput_staged / max(goodput_base, 1e-9)
        assert goodput_frac >= 0.9, \
            f"goodput fell to {goodput_frac:.1%} of baseline while a " \
            f"stage was in flight (floor 90%)"

        # -- phase 3: probe-gated hot swap-in on every worker, then a cold
        # load_model of the same-shaped model for the latency receipt
        probe = expected_tokens(list(GOLDEN_PROMPT), 8, vocab=vocab_b)
        swaps = await coord.swap_model("mb", probe=probe)
        assert all(not s["already_resident"] for s in swaps)
        swap_s = max(s["swap_s"] for s in swaps)
        overlap = 0
        for wid in list(coord.router.workers):
            m = await coord.router.client_for(wid).metrics()
            overlap += int(m.get("stage_overlap_steps", 0))
            assert set(m.get("models", {})) == {"ma", "mb"}, \
                f"{wid} resident set {set(m.get('models', {}))}"
        assert overlap > 0, "stage overlapped zero serving steps"
        wid0 = next(iter(workers))
        cold = await coord.router.client_for(wid0).load_model(
            fake_cfg(name="mcold", vocab_size=vocab_b,
                     load_sleep_s=load_sleep))
        cold_s = float(cold["load_s"])
        speedup = cold_s / max(swap_s, 1e-9)
        assert speedup >= 5.0, \
            f"staged swap only {speedup:.1f}x faster than cold load " \
            f"(acceptance >= 5x)"

        # -- phase 4: both models serving CONCURRENTLY under interleaved
        # affinity load; per-model token-exactness and per-model+prefix
        # affinity hit rate
        pa = _affinity_prompts(6, 10, 2 * page, bench.FLEET_SEED + 503)
        pb = _affinity_prompts(6, 10, 2 * page, bench.FLEET_SEED + 504)
        # snapshot per-model counters so the hit rate scores THIS phase's
        # interleaved load, not the earlier phases' first-touch misses
        before = {m: dict(rec) for m, rec in
                  coord.lb.get_all_stats()["affinity_models"].items()}
        (ra, wa, ta, _), (rb, wb, tb, _) = await asyncio.gather(
            drive(coord, pa, rate / 2, nt, bench.FLEET_SEED + 503,
                  model="ma", tag="mma_"),
            drive(coord, pb, rate / 2, nt, bench.FLEET_SEED + 504,
                  model="mb", tag="mmb_"))
        ok_a, toks_a = score(pa, ra, nt)
        ok_b, toks_b = score(pb, rb, nt, vocab=vocab_b)
        assert ok_a == len(pa), f"model ma: {ok_a}/{len(pa)} exact"
        assert ok_b == len(pb), f"model mb: {ok_b}/{len(pb)} exact"
        receipt += [("ma", tuple(r["tokens"])) for r in ra]
        receipt += [("mb", tuple(r["tokens"])) for r in rb]
        per_model = coord.lb.get_all_stats()["affinity_models"]
        hit_rates = {}
        for mname in ("ma", "mb"):
            rec = per_model.get(mname, {"hits": 0, "misses": 0})
            b = before.get(mname, {"hits": 0, "misses": 0})
            hits = rec["hits"] - b.get("hits", 0)
            misses = rec["misses"] - b.get("misses", 0)
            hit_rates[mname] = hits / max(1, hits + misses)
        rows.append(emit({
            "leg": "multimodel", "run": run_tag, "workers": n,
            "models": 2, "requests": len(p1) + len(p2) + len(pa) + len(pb),
            "token_exact": ok1 + ok2 + ok_a + ok_b,
            "token_exact_frac": 1.0,
            "goodput_base_toks": round(goodput_base, 1),
            "goodput_while_staging_toks": round(goodput_staged, 1),
            "staging_goodput_frac": round(goodput_frac, 4),
            "stage_overlap_steps": overlap,
            "swap_s": round(swap_s, 4),
            "cold_load_s": round(cold_s, 4),
            "swap_speedup": round(speedup, 1),
            "affinity_hit_rate_ma": round(hit_rates["ma"], 4),
            "affinity_hit_rate_mb": round(hit_rates["mb"], 4),
        }))
        for mname, hr in hit_rates.items():
            assert hr >= 0.9, \
                f"model {mname} affinity hit rate {hr:.1%} (floor 90%)"
    finally:
        await stop_fleet(coord, workers)
    return rows, receipt


async def leg_multimodel():
    """Multi-model worker leg (ISSUE 14): two fake models with distinct
    crc token chains share a 2-worker fleet. Background-stages the second
    model under live load (goodput must hold within 10%), hot-swaps it in
    behind the golden-token probe (staged swap >= 5x faster than a cold
    ``load_model``), then serves BOTH models concurrently — per-model
    token-exact, per-model+prefix affinity hit rate >= 90%. Runs TWICE
    with the same seed; the token receipts must be identical."""
    rows_a, receipt_a = await _multimodel_once("a")
    rows_b, receipt_b = await _multimodel_once("b")
    assert receipt_a == receipt_b, \
        "same-seed multimodel runs produced different token receipts"
    h = zlib.crc32(repr(receipt_a).encode()) & 0xFFFFFFFF
    ra = rows_a[0]
    log(f"  multimodel: both models token-exact, staged swap "
        f"{ra['swap_s'] * 1e3:.0f} ms vs cold load "
        f"{ra['cold_load_s'] * 1e3:.0f} ms ({ra['swap_speedup']}x, "
        f"acceptance >= 5x); goodput while staging "
        f"{ra['staging_goodput_frac']:.1%} of baseline (floor 90%); "
        f"hit rates ma {ra['affinity_hit_rate_ma']:.1%} / mb "
        f"{ra['affinity_hit_rate_mb']:.1%} (floor 90%); receipts "
        f"identical (crc32 {h:#010x})")
    rows = rows_a + rows_b
    rows.append(emit({"leg": "multimodel", "summary": True,
                      "receipt_crc32": h, "receipts_identical": True,
                      "swap_speedup": ra["swap_speedup"],
                      "staging_goodput_frac": ra["staging_goodput_frac"]}))
    dump_leg("multimodel", rows)
    return rows


async def leg_long():
    """Long-context rung: 2k-token prompts (the DEFAULT policy; set
    SWEEP_SHAPE=long for the full 8k row) flow through the coordinator
    to a 2-worker fleet with per-token admission cost — the framed RPC
    path, affinity keys and crc reference chain all exercised at depth.
    Every result must be token-exact against the analytic chain."""
    n = 2
    nt = 32
    plen = 8192 if os.environ.get("SWEEP_SHAPE", "") == "long" else 2048
    lat = 2e-5   # admission cost per uncached prompt token
    page = 64
    cfg = fake_cfg(prefix_cache=1, prefix_page_size=page,
                   admit_latency_per_token_s=lat)
    coord, workers = await start_fleet(n, coord_cfg=CoordinatorConfig(
        lb_strategy="prefix_affinity", affinity_page_size=page,
        affinity_pages=2, retry_seed=bench.FLEET_SEED,
        retry_backoff_base_s=0.01))
    await coord.deploy_model(cfg, register_shards=False)
    rs = np.random.RandomState(bench.FLEET_SEED + 601)
    prompts = [[int(t) for t in rs.randint(1, VOCAB, plen - 1)] + [i]
               for i in range(24)]
    rate = 0.4 * bench.FLEET_SLOTS / STEP_S / nt * n
    gen0 = await worker_generated(coord)
    results, wall, ttfts, itls = await drive(
        coord, prompts, rate, nt, bench.FLEET_SEED + 601, tag="lg")
    gen1 = await worker_generated(coord)
    row = row_base("long", n, wall, prompts, results, ttfts, itls,
                   nt, rate, gen0, gen1)
    row["prompt_len"] = plen
    log(f"  long: {row['token_exact']}/{row['requests']} token-exact at "
        f"prompt_len={plen} (default policy 2048; SWEEP_SHAPE=long for "
        f"8192), TTFT p50 {row['ttft_p50_ms']} ms")
    assert row["token_exact"] == len(prompts), \
        f"long-context: {row['token_exact']}/{len(prompts)} exact"
    rows = [emit(row)]
    await stop_fleet(coord, workers)
    dump_leg("long", rows)
    return rows


LEGS = {"replicated": leg_replicated, "disagg": leg_disagg,
        "affinity": leg_affinity, "kill": leg_kill,
        "kvfabric": leg_kvfabric, "stream": leg_stream,
        "autoscale": leg_autoscale, "upgrade": leg_upgrade,
        "multimodel": leg_multimodel, "long": leg_long}


async def main_async():
    want = [s for s in os.environ.get(
        "SWEEP_LEGS",
        "replicated,disagg,affinity,kill,kvfabric,stream,autoscale,"
        "upgrade,multimodel,long,tiny"
    ).split(",") if s]
    all_rows = []
    for name in want:
        if name == "tiny":
            if not bench.FLEET_TINY:
                continue
            log("=== leg: tiny (real llama-tiny engines) ===")
            all_rows += await leg_tiny()
            continue
        fn = LEGS.get(name)
        if fn is None:
            log(f"unknown leg {name!r} — skipping")
            continue
        log(f"=== leg: {name} ===")
        all_rows += await fn()
    data_rows = [r for r in all_rows if not r.get("summary")]
    log("\n| leg | N | goodput tok/s | token-exact | TTFT p50 | "
        "TTFT p99 | ITL p50 | hit-rate | handoff B/s |")
    log("|---|---|---|---|---|---|---|---|---|")
    for r in data_rows:
        log(f"| {r['leg']} | {r.get('workers', '-')} | "
            f"{r.get('goodput_toks', '-')} | "
            f"{r['token_exact']}/{r['requests']} | "
            f"{r.get('ttft_p50_ms', '-')} | {r.get('ttft_p99_ms', '-')} | "
            f"{r.get('itl_p50_ms', '-')} | "
            f"{r.get('affinity_hit_rate', '-')} | "
            f"{r.get('handoff_bytes_per_s', '-')} |")


if __name__ == "__main__":
    asyncio.run(main_async())
