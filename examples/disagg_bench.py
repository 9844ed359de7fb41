"""Disaggregated prefill/decode: measured on real hardware (VERDICT r2 item 3).

Two pools in ONE process — a prefill WorkerServer and a continuous-decode
WorkerServer on loopback framed RPC, sharing one set of int8 weights
(both pools build on JAX's default device, so ONE chip executes both
pools' programs whatever the host has; the wire format, framing, batching
and handoff path are exactly the two-host deployment's).
Measures:

- handoff bytes per request (the dense [L, T, Hkv, Dh] KV payload),
- prefill + handoff serialization/transfer time (client-observed),
- decode-pool admission cost for handed-off KV,
- relay end-to-end (prefill pool -> decode peer -> results) vs the SAME
  decode engine serving the same requests single-pool.

Loopback measures serialization + copy + framing; a real DCN hop adds
bytes/bandwidth on top — the printed bytes-per-request is the number to
divide by your DCN bandwidth (docs/design.md's estimate, now measured).

Usage:  python examples/disagg_bench.py
Knobs:  BENCH_MODEL/BENCH_QUANT/BENCH_BATCH (default 16),
        BENCH_PROMPT (default 512), BENCH_NEW_TOKENS (default 128)

``--coordinator`` runs the COORDINATOR-path mode instead (ISSUE 10): the
same two pools, but deployed via ``deploy_model_disaggregated`` and driven
through ``Coordinator.submit`` — requests cross the real framed-RPC control
plane (coordinator -> prefill worker -> KV handoff -> decode worker),
against a single-pool reference worker deployed on the same coordinator.
The JSON row records handoff bytes (serialize/transfer, from the prefill
worker's ``handoff_bytes_shipped`` counter), handoff bytes/s, end-to-end
latency percentiles, and the coordinator-path overhead vs single-pool.

    BENCH_MODEL=llama-tiny BENCH_PROMPT=32 BENCH_NEW_TOKENS=8 \
        BENCH_BATCH=4 python examples/disagg_bench.py --coordinator
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
os.environ.setdefault("BENCH_BATCH", "16")
os.environ.setdefault("BENCH_PROMPT", "512")

import bench  # noqa: E402
from distributed_inference_engine_tpu.config import (  # noqa: E402
    ModelConfig,
    ServerConfig,
)
from distributed_inference_engine_tpu.cluster.worker import (  # noqa: E402
    WorkerClient,
    WorkerServer,
)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


async def main():
    spec = bench._spec()
    n = bench.BATCH
    t0 = time.perf_counter()
    params = bench._build_params(spec, bench.QUANT)
    from distributed_inference_engine_tpu.config import EngineConfig
    from distributed_inference_engine_tpu.engine.continuous import (
        ContinuousEngine,
    )
    from distributed_inference_engine_tpu.engine.disagg import PrefillEngine

    max_seq = min(spec.max_seq_len, bench.PROMPT_LEN + bench.NEW_TOKENS)
    # 2x page backing: the delta-handoff phase needs the PREVIOUS batch's
    # registered prefix pages still resident — an exactly-sized pool
    # reclaims them for the next batch's allocations (measured: 14/16
    # probes missed with 1x backing)
    ecfg = EngineConfig(
        max_slots=n, max_seq_len=max_seq,
        prefill_buckets=[bench.PROMPT_LEN], decode_steps_per_call=64,
        page_size=128, num_pages=2 * n * (-(-max_seq // 128)) + 8,
    )

    def factory(cfg: ModelConfig):
        if cfg.metadata.get("role") == "prefill":
            return PrefillEngine(spec, params=params, config=ecfg)
        return ContinuousEngine(spec, params=params, config=ecfg)

    pre = WorkerServer(ServerConfig(worker_id="pool-prefill", port=0,
                                    max_frame_bytes=2 * 1024 * 1024 * 1024),
                       engine_factory=factory)
    dec = WorkerServer(ServerConfig(worker_id="pool-decode", port=0,
                                    max_frame_bytes=2 * 1024 * 1024 * 1024),
                       engine_factory=factory)
    ph, pp = await pre.start()
    dh, dp = await dec.start()
    await pre.load_model_async(ModelConfig(
        name="m", architecture=bench.MODEL, max_seq_len=max_seq,
        metadata={"role": "prefill"}))
    await dec.load_model_async(ModelConfig(
        name="m", architecture=bench.MODEL, max_seq_len=max_seq,
        metadata={"continuous": 1}))
    # the first call compiles the 512-token prefill bucket at 8B scale —
    # the default RPC timeout is for serving, not warmup
    ca = WorkerClient(ph, pp, max_frame=2 * 1024 * 1024 * 1024, timeout=600.0)
    cb = WorkerClient(dh, dp, max_frame=2 * 1024 * 1024 * 1024, timeout=600.0)
    log(f"pools up ({bench.MODEL}, int8={bench.QUANT}, bs{n}, prompt "
        f"{bench.PROMPT_LEN} + {bench.NEW_TOKENS} new): "
        f"{time.perf_counter() - t0:.1f}s")

    from distributed_inference_engine_tpu.cluster.worker import (
        request_to_dict,
    )
    from distributed_inference_engine_tpu.engine.disagg import (
        handoff_to_wire,
    )

    def reqs(seed):
        return bench._requests(spec, seed, n)

    # ---- warmup/compile both paths, including the per-group batch
    # buckets the pipelined relay admits (group prefills run at n/4)
    t0 = time.perf_counter()
    warm = await ca.prefill("m", reqs(1))
    await cb.call("generate_prefilled", model="m",
                  requests=[request_to_dict(r) for r in reqs(1)],
                  handoffs=[handoff_to_wire(h) for h in warm],
                  timeout=600.0)
    await cb.generate("m", reqs(2), timeout=600.0)
    for pg in (1, 4):
        short = reqs(3)
        for r in short:
            r.max_new_tokens = 2
        await ca.call("prefill_generate", model="m",
                      requests=[request_to_dict(r) for r in short],
                      decode_host=dh, decode_port=dp, peer_timeout=600.0,
                      pipeline_groups=pg, timeout=600.0)
    log(f"warmup (compile both pools): {time.perf_counter() - t0:.1f}s")

    # ---- 1) prefill + handoff transfer (client-observed, loopback frame)
    t0 = time.perf_counter()
    handoffs = await ca.prefill("m", reqs(10))
    t_prefill_ship = time.perf_counter() - t0
    kv_bytes = sum(h.k.nbytes + h.v.nbytes for h in handoffs)

    # ---- 2) decode-pool admission of handed-off KV (2 tokens)
    short = reqs(10)
    for r in short:
        r.max_new_tokens = 2
    t0 = time.perf_counter()
    await cb.call("generate_prefilled", model="m",
                  requests=[request_to_dict(r) for r in short],
                  handoffs=[handoff_to_wire(h) for h in handoffs],
                  timeout=600.0)
    t_admit = time.perf_counter() - t0

    # ---- 3) relay end-to-end vs single-pool, same engine, same requests.
    # pipeline_groups=1: monolithic (prefill all -> ship all -> decode);
    # =4: group g+1 prefills while group g's KV is in flight and decoding
    t0 = time.perf_counter()
    out = await ca.call("prefill_generate", model="m",
                        requests=[request_to_dict(r) for r in reqs(20)],
                        decode_host=dh, decode_port=dp, peer_timeout=600.0,
                        pipeline_groups=1, timeout=600.0)
    t_mono = time.perf_counter() - t0
    toks_mono = sum(len(r["tokens"]) for r in out["results"])

    t0 = time.perf_counter()
    out = await ca.call("prefill_generate", model="m",
                        requests=[request_to_dict(r) for r in reqs(21)],
                        decode_host=dh, decode_port=dp, peer_timeout=600.0,
                        pipeline_groups=4, timeout=600.0)
    t_disagg = time.perf_counter() - t0
    toks_disagg = sum(len(r["tokens"]) for r in out["results"])

    t0 = time.perf_counter()
    res_single = await cb.generate("m", reqs(30), timeout=600.0)
    t_single = time.perf_counter() - t0
    toks_single = sum(len(r.tokens) for r in res_single)

    # ---- 4) prefix-aware delta handoff: repeat the SAME prompts — the
    # decode pool's prefix cache holds their full pages, so the relay
    # ships only each prompt's final partial page
    shipped0 = (await ca.call("metrics"))["handoff_bytes_shipped"]
    t0 = time.perf_counter()
    out = await ca.call("prefill_generate", model="m",
                        requests=[request_to_dict(r) for r in reqs(21)],
                        decode_host=dh, decode_port=dp, peer_timeout=600.0,
                        timeout=600.0)
    t_delta = time.perf_counter() - t0
    toks_delta = sum(len(r["tokens"]) for r in out["results"])
    shipped_delta = ((await ca.call("metrics"))["handoff_bytes_shipped"]
                     - shipped0)

    row = {
        "metric": f"disagg_{bench.MODEL}{'_int8' if bench.QUANT else ''}"
                  f"_bs{n}_p{bench.PROMPT_LEN}",
        "kv_handoff_mb_per_req": round(kv_bytes / n / 1e6, 2),
        "prefill_ship_s": round(t_prefill_ship, 2),
        "admit_s": round(t_admit, 2),
        "disagg_mono_e2e_s": round(t_mono, 2),
        "disagg_pipe4_e2e_s": round(t_disagg, 2),
        "single_e2e_s": round(t_single, 2),
        "disagg_tok_s": round(toks_disagg / t_disagg, 1),
        "single_tok_s": round(toks_single / t_single, 1),
        "pipeline_gain_pct": round(100 * (t_mono - t_disagg) / t_mono, 1),
        "overhead_vs_single_pct": round(
            100 * (t_disagg - t_single) / t_single, 1),
        "delta_repeat_e2e_s": round(t_delta, 2),
        "delta_shipped_mb_per_req": round(shipped_delta / n / 1e6, 2),
        "delta_bytes_saved_pct": round(
            100 * (1 - shipped_delta / max(kv_bytes, 1)), 1),
    }
    assert (toks_mono > 0 and toks_disagg > 0 and toks_single > 0
            and toks_delta > 0)
    print(json.dumps(row), flush=True)
    await ca.close()
    await cb.close()
    await pre.stop()
    await dec.stop()


async def main_coordinator():
    """Coordinator-path mode: prefill + decode + single-pool reference
    workers on one coordinator; both paths driven through
    ``Coordinator.submit`` over the framed control plane. Workers build
    their own engines from the ModelConfig (random-init, fixed key), so
    the disagg path and the reference share weights and must agree
    token-for-token at temperature 0."""
    from distributed_inference_engine_tpu.api.coordinator import (
        Coordinator, CoordinatorConfig,
    )

    n = bench.BATCH
    max_seq = bench.PROMPT_LEN + bench.NEW_TOKENS
    big = 2 * 1024 * 1024 * 1024
    coord = Coordinator(CoordinatorConfig(dispatch_timeout_s=600.0))
    await coord.start()
    servers = {}
    for wid in ("p0", "d0", "ref0"):
        w = WorkerServer(ServerConfig(host="127.0.0.1", port=0,
                                      worker_id=wid, max_frame_bytes=big))
        host, port = await w.start()
        servers[wid] = w
        coord.add_worker(wid, host, port)
    t0 = time.perf_counter()
    cfg = ModelConfig(name="m", architecture=bench.MODEL,
                      max_seq_len=max_seq, max_batch_size=n,
                      metadata={"continuous": 1, "max_slots": n})
    ref = ModelConfig(name="m_ref", architecture=bench.MODEL,
                      max_seq_len=max_seq, max_batch_size=n,
                      metadata={"continuous": 1, "max_slots": n})
    await coord.deploy_model_disaggregated(cfg, ["p0"], ["d0"])
    await coord.deploy_model(ref, worker_ids=["ref0"])
    log(f"coordinator fleet up ({bench.MODEL}, prompt {bench.PROMPT_LEN} "
        f"+ {bench.NEW_TOKENS} new): {time.perf_counter() - t0:.1f}s")

    import numpy as np
    rs = np.random.RandomState(17)
    prompts = [[int(rs.randint(1, 96)) for _ in range(bench.PROMPT_LEN)]
               for _ in range(n)]

    async def run(model, seed_tag):
        lats = []
        t0 = time.perf_counter()
        outs = []
        for i, p in enumerate(prompts):
            t1 = time.perf_counter()
            r = await coord.submit(model, prompt=p,
                                   max_new_tokens=bench.NEW_TOKENS,
                                   request_id=f"{seed_tag}{i}",
                                   no_cache=True)
            lats.append(time.perf_counter() - t1)
            outs.append(r)
        return outs, time.perf_counter() - t0, lats

    # warmup/compile both paths, then the timed passes
    await run("m", "warm")
    await run("m_ref", "warmref")
    m0 = await coord.router.client_for("p0").metrics()
    outs, t_disagg, lats = await run("m", "c")
    m1 = await coord.router.client_for("p0").metrics()
    refs, t_single, ref_lats = await run("m_ref", "s")
    shipped = (m1["handoff_bytes_shipped"] - m0["handoff_bytes_shipped"])
    exact = sum(1 for a, b in zip(outs, refs)
                if a["tokens"] == b["tokens"])
    toks = sum(len(r["tokens"]) for r in outs)
    row = {
        "metric": f"disagg_coord_{bench.MODEL}_bs{n}_p{bench.PROMPT_LEN}",
        "mode": "coordinator",
        "requests": n,
        "token_exact_vs_single": exact,
        "handoff_mb_per_req": round(shipped / n / 1e6, 3),
        "handoff_bytes_per_s": round(shipped / t_disagg, 1),
        "disagg_e2e_s": round(t_disagg, 2),
        "single_e2e_s": round(t_single, 2),
        "disagg_tok_s": round(toks / t_disagg, 1),
        "lat_p50_s": round(bench.pct(lats, 0.5), 3),
        "lat_p99_s": round(bench.pct(lats, 0.99), 3),
        "single_lat_p50_s": round(bench.pct(ref_lats, 0.5), 3),
        "overhead_vs_single_pct": round(
            100 * (t_disagg - t_single) / max(t_single, 1e-9), 1),
    }
    assert exact == n, f"coordinator disagg path diverged: {exact}/{n}"
    print(json.dumps(row), flush=True)
    await coord.stop()
    for w in servers.values():
        await w.stop()


if __name__ == "__main__":
    if "--coordinator" in sys.argv[1:]:
        asyncio.run(main_coordinator())
    else:
        asyncio.run(main())
