"""Advanced serving demo: the techniques layered on the core engine —
streaming, prefix caching, quantization, speculative decoding, and a
disaggregated prefill/decode pair — each exercised end-to-end in process.

Scripted like the reference's ``examples/batcher_demo.py`` (assertions in
prose, printed outcomes), but every section drives the real serving path.

CPU demo: every in-process worker builds its engine on JAX's default
device, so on a multi-chip host all replicas would share chip 0. For one
replica per chip start ``cli.worker`` processes confined by libtpu's
chip-visibility variables (README "One worker per chip").

    JAX_PLATFORMS=cpu python examples/advanced_demo.py
"""

import asyncio
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from distributed_inference_engine_tpu.api.coordinator import (  # noqa: E402
    Coordinator,
    CoordinatorConfig,
)
from distributed_inference_engine_tpu.cluster.worker import (  # noqa: E402
    WorkerServer,
)
from distributed_inference_engine_tpu.config import (  # noqa: E402
    ModelConfig,
    ServerConfig,
)

TINY = {"size": "llama-tiny", "page_size": 16, "num_pages": 64,
        "attention_impl": "xla", "kv_dtype": "float32",
        "decode_steps_per_call": 4}


def cfg(name, **extra):
    meta = dict(TINY, **extra)
    return ModelConfig(name=name, architecture="llama", dtype="float32",
                       max_seq_len=64, max_batch_size=4, metadata=meta,
                       quantized=bool(meta.pop("quantized", False)))


async def main() -> None:
    coord = Coordinator(CoordinatorConfig())
    await coord.start()
    workers = []
    for i in range(3):
        w = WorkerServer(ServerConfig(worker_id=f"w{i}", port=0))
        host, port = await w.start()
        workers.append(w)
        coord.add_worker(f"w{i}", host, port)

    try:
        print("=== 1. streaming (continuous engine, chunk frames) ===")
        await coord.deploy_model(cfg("stream", continuous=1),
                                 worker_ids=["w0"])
        chunks = []
        out = await coord.submit_stream(
            "stream", prompt=[1, 2, 3, 4], max_new_tokens=12,
            on_tokens=lambda t: (chunks.append(t),
                                 print(f"  chunk: {t}"))[0])
        print(f"  final ({len(out['tokens'])} tokens) matches stream: "
              f"{[t for c in chunks for t in c] == out['tokens']}")

        print("=== 2. prefix KV cache (shared system prompt) ===")
        system = list(range(1, 33))          # 32 tokens = 2 full pages
        t0 = time.perf_counter()
        await coord.submit("stream", prompt=system + [40],
                           max_new_tokens=4, no_cache=True)
        cold = time.perf_counter() - t0
        # first hit compiles the suffix-prefill program — time the second
        await coord.submit("stream", prompt=system + [50],
                           max_new_tokens=4, no_cache=True)
        t0 = time.perf_counter()
        await coord.submit("stream", prompt=system + [60],
                           max_new_tokens=4, no_cache=True)
        warm = time.perf_counter() - t0
        kv = (await coord.router.client_for("w0").metrics()
              )["models"]["stream"]["kv"]
        print(f"  cold {cold*1e3:.0f} ms -> warm hit {warm*1e3:.0f} ms; "
              f"prefix hits: {kv['prefix_hit_tokens']} tokens")

        print("=== 3. int8 quantized weights ===")
        await coord.deploy_model(cfg("q8", quantized=True),
                                 worker_ids=["w1"])
        out = await coord.submit("q8", prompt=[5, 6, 7], max_new_tokens=6)
        print(f"  quantized generate: {out['tokens']}")

        print("=== 4. speculative decoding (draft k=4) ===")
        await coord.deploy_model(cfg("spec", speculative=4,
                                     draft_size="llama-tiny"),
                                 worker_ids=["w1"])
        out = await coord.submit("spec", prompt=[5, 6, 7], max_new_tokens=8)
        m = (await coord.router.client_for("w1").metrics()
             )["models"]["spec"]
        print(f"  tokens: {out['tokens']}")
        print(f"  rounds: {m['rounds']}, acceptance: "
              f"{m['draft_acceptance_rate']:.2f} "
              "(random-init draft disagrees with target — a trained "
              "draft accepts most)")

        print("=== 5. disaggregated prefill/decode (w2 prefill -> w0 decode) ===")
        # w0 already hosts the continuous engine; w2 becomes the prefill pool
        await coord.deploy_model_disaggregated(
            cfg("stream", continuous=1), ["w2"], ["w0"])
        out = await coord.submit("stream", prompt=[9, 8, 7],
                                 max_new_tokens=6, no_cache=True)
        print(f"  tokens: {out['tokens']}")
        print(f"  prefill worker: {out['metadata']['prefill_worker']}, "
              f"decode worker: {out['metadata']['decode_worker']}")

        print("=== stats ===")
        s = coord.get_stats()
        print(f"  submitted: {s['submitted']}, "
              f"disaggregated pools: {s['disaggregated']}")
    finally:
        await coord.stop()
        for w in workers:
            await w.stop()


if __name__ == "__main__":
    asyncio.run(main())
