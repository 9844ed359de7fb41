"""Fleet demo: multi-worker serving with mid-run fault injection.

Heir of the reference's ``examples/load_balancer_demo.py`` (its closest thing
to a system test) with its gap closed: the reference never actually sent
requests to the balanced worker — it slept instead
(``examples/load_balancer_demo.py:145-146``). Here every request goes through
the coordinator's full path (cache -> batcher -> router/LB -> framed RPC ->
real JAX engine) and a worker is killed mid-run to show failover.

CPU demo: every in-process worker builds its engine on JAX's default
device, so on a multi-chip host all replicas would share chip 0. For one
replica per chip start ``cli.worker`` processes confined by libtpu's
chip-visibility variables (README "One worker per chip").

    JAX_PLATFORMS=cpu python examples/fleet_demo.py --workers 3 --requests 24
"""

import argparse
import asyncio
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from distributed_inference_engine_tpu.api.coordinator import (  # noqa: E402
    Coordinator, CoordinatorConfig,
)
from distributed_inference_engine_tpu.cluster.worker import WorkerServer  # noqa: E402
from distributed_inference_engine_tpu.config import (  # noqa: E402
    HealthConfig, ModelConfig, ServerConfig,
)


async def run(n_workers: int, n_requests: int, strategy: str, kill: bool,
              trace_out: str = "") -> None:
    print(f"=== fleet demo: {n_workers} workers, {n_requests} requests, "
          f"strategy={strategy} ===")
    workers = []
    for i in range(n_workers):
        w = WorkerServer(ServerConfig(worker_id=f"w{i}", host="127.0.0.1", port=0))
        await w.start()
        workers.append(w)
        print(f"  worker w{i} on port {w.address[1]}")

    coord = Coordinator(CoordinatorConfig(
        lb_strategy=strategy,
        health=HealthConfig(check_interval=0.5, max_consecutive_failures=2),
    ))
    await coord.start()
    for w in workers:
        h, p = w.address
        coord.add_worker(w.worker_id, h, p)

    # every worker shares one serving-artifact dir: the first slow-path
    # load commits it, every later load (the respawn below included) is
    # an artifact cold-start
    art_dir = tempfile.mkdtemp(prefix="fleet_artifact_")
    model = ModelConfig(
        name="tiny", architecture="llama", max_seq_len=64, dtype="float32",
        metadata={"size": "llama-tiny",
                  "artifact": os.path.join(art_dir, "tiny")},
    )
    n = await coord.deploy_model(model)
    print(f"  deployed {model.name} across {n} workers "
          f"(serving artifact at {art_dir})")

    served = {w.worker_id: 0 for w in workers}
    errors = 0
    t0 = time.perf_counter()

    async def one(i: int) -> None:
        nonlocal errors
        try:
            out = await coord.submit(
                model="tiny", prompt=[1 + i, 2, 3], max_new_tokens=4,
                key=f"user-{i}", no_cache=True,
            )
            wid = out["metadata"].get("worker_id")
            if wid in served:
                served[wid] += 1
        except Exception as e:
            errors += 1
            print(f"  request {i} FAILED: {e}")

    half = n_requests // 2
    q3 = half + (n_requests - half) // 2
    await asyncio.gather(*(one(i) for i in range(half)))
    if kill and workers:
        victim = workers[0]
        print(f"  !! killing worker {victim.worker_id} mid-run")
        await victim.stop()
    await asyncio.gather(*(one(half + i) for i in range(q3 - half)))
    if kill:
        # elastic respawn: a fresh worker joins mid-run and deploy_model's
        # idempotent scale-out loads the model onto it only — from the
        # committed artifact, so the join is seconds, not a re-derivation
        respawn = WorkerServer(ServerConfig(worker_id=f"w{n_workers}",
                                            host="127.0.0.1", port=0))
        await respawn.start()
        h, p = respawn.address
        coord.add_worker(respawn.worker_id, h, p)
        await coord.deploy_model(model)
        served[respawn.worker_id] = 0
        workers.append(respawn)
        load_s = respawn._last_load_s.get(model.name, 0.0)
        hit = getattr(respawn.engines.get(model.name),
                      "artifact_manifest", None) is not None
        print(f"  ++ respawned capacity as {respawn.worker_id} on port {p} "
              f"— load_model took {load_s:.2f}s"
              f"{' [artifact cold-start]' if hit else ' [slow path]'}")
    await asyncio.gather(*(one(q3 + i) for i in range(n_requests - q3)))
    wall = time.perf_counter() - t0

    print(f"  {n_requests} requests in {wall:.2f}s "
          f"({n_requests / wall:.1f} req/s), {errors} errors")
    stats = coord.get_stats()
    print("  router:", {k: stats["router"][k]
                        for k in ("workers_by_health", "failover_count",
                                  "routing_errors")})
    print("  per-worker latency/requests:")
    for wid, s in stats["load_balancer"]["workers"].items():
        print(f"    {wid}: reqs={s['request_count']} errs={s['error_count']} "
              f"avg_latency={s['avg_latency_s'] * 1e3:.1f}ms healthy={s['healthy']}")
    if trace_out:
        # flight recorder: clock-sync the survivors, pull their event
        # rings + step timelines, and merge with the coordinator's own
        # request spans into one Perfetto-loadable trace
        from distributed_inference_engine_tpu.obs import clocksync

        trace = await coord.fleet_trace(label="fleet_demo")
        clocksync.dump_trace(trace_out, trace)
        tracks = sum(1 for e in trace["traceEvents"]
                     if e.get("name") == "process_name")
        print(f"  fleet trace -> {trace_out} ({tracks} process tracks, "
              f"{len(trace['traceEvents'])} events)")
    await coord.stop()
    for w in workers[1 if kill else 0:]:
        await w.stop()
    print("=== done ===")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--strategy", default="round_robin",
                    choices=["round_robin", "least_connections", "random",
                             "least_latency"])
    ap.add_argument("--no-kill", action="store_true",
                    help="skip the mid-run worker kill")
    ap.add_argument("--trace-out", default="",
                    help="dump a merged Perfetto fleet trace to this path")
    args = ap.parse_args()
    asyncio.run(run(args.workers, args.requests, args.strategy,
                    kill=not args.no_kill, trace_out=args.trace_out))


if __name__ == "__main__":
    main()
