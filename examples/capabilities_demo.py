"""Capabilities demo: the features added on top of the core serving stack —
model families (Qwen2 / Mistral / Gemma), stop conditions + min-p sampling,
chunked prefill, config-driven tensor/sequence parallelism on a virtual
mesh, pipeline-parallel training, and engine warmup.

Scripted like the reference's ``examples/batcher_demo.py`` (printed
outcomes), but every section drives the real engines. Run on CPU with a
virtual 8-device mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/capabilities_demo.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

from distributed_inference_engine_tpu.config import (  # noqa: E402
    EngineConfig,
    MeshConfig,
    ModelConfig,
)
from distributed_inference_engine_tpu.engine.continuous import (  # noqa: E402
    ContinuousEngine,
)
from distributed_inference_engine_tpu.engine.engine import Engine  # noqa: E402
from distributed_inference_engine_tpu.engine.types import (  # noqa: E402
    GenerationRequest,
)
from distributed_inference_engine_tpu.models import (  # noqa: E402
    engine_from_config,
    gemma_spec,
    mistral_spec,
    qwen_spec,
)


def banner(title: str) -> None:
    print(f"\n=== {title} " + "=" * max(0, 60 - len(title)))


def demo_families() -> None:
    banner("Model families: Qwen2 (qkv bias), Mistral (SWA), Gemma (GeGLU)")
    for fac, size, quirk in (
        (qwen_spec, "qwen-tiny", "q/k/v biases"),
        (mistral_spec, "mistral-tiny", "sliding window 64"),
        (gemma_spec, "gemma-tiny", "head_dim 32 != d_model/heads"),
    ):
        spec = fac(size, max_seq_len=128)
        eng = Engine(spec, config=EngineConfig(
            max_slots=2, max_seq_len=128, prefill_buckets=[16],
            decode_steps_per_call=4))
        out = eng.generate([GenerationRequest(prompt=[1, 2, 3, 4],
                                              max_new_tokens=8)])[0]
        print(f"  {size:13s} ({quirk}): {out.tokens}")


def demo_stops_minp() -> None:
    banner("Stop sequences + min-p")
    spec = mistral_spec("mistral-tiny", max_seq_len=128).replace(
        dtype="float32")
    eng = Engine(spec, config=EngineConfig(
        max_slots=2, max_seq_len=128, prefill_buckets=[16],
        decode_steps_per_call=4))
    base = eng.generate([GenerationRequest(prompt=[1, 2, 3],
                                           max_new_tokens=12)])[0].tokens
    stop = base[4]
    stopped = eng.generate([GenerationRequest(
        prompt=[1, 2, 3], max_new_tokens=12, stop_ids=[stop])])[0]
    print(f"  greedy:   {base}")
    print(f"  stop@{stop}: {stopped.tokens} ({stopped.finish_reason})")
    minp = eng.generate([GenerationRequest(
        prompt=[1, 2, 3], max_new_tokens=12, temperature=0.9,
        min_p=1.0)])[0].tokens
    print(f"  min_p=1.0 @ temp 0.9 == greedy: {minp == base}")


def demo_chunked_prefill() -> None:
    banner("Chunked prefill (prefill_chunk=32, 96-token prompt)")
    from distributed_inference_engine_tpu.models.llama import llama_spec

    spec = llama_spec("llama-tiny", max_seq_len=256).replace(dtype="float32")
    eng = ContinuousEngine(spec, config=EngineConfig(
        max_slots=4, max_seq_len=256, prefill_buckets=[32, 128],
        page_size=16, num_pages=64, decode_steps_per_call=4,
        prefill_chunk=32))
    out = eng.generate([GenerationRequest(prompt=list(range(1, 97)),
                                          max_new_tokens=6)])[0]
    m = eng.get_metrics()
    print(f"  tokens {out.tokens}; chunked_admissions="
          f"{m['chunked_admissions']}, prefill dispatches="
          f"{m['prefill_calls']} (3 chunks of 32)")


def demo_config_parallel() -> None:
    banner("Config-driven parallelism (virtual 8-device mesh)")
    tp_eng = engine_from_config(ModelConfig(
        name="tp", architecture="llama-tiny", dtype="float32",
        max_batch_size=2, max_seq_len=128,
        metadata={"continuous": 1, "page_size": 16, "tp": 4}))
    print(f"  tp=4 deploy: wq sharding "
          f"{tp_eng.params['blocks']['wq'].sharding.spec}")
    out = tp_eng.generate([GenerationRequest(prompt=[1, 2, 3],
                                             max_new_tokens=4)])[0]
    print(f"  tp serve: {out.tokens}")
    sp_eng = engine_from_config(ModelConfig(
        name="sp", architecture="llama-tiny", dtype="float32",
        max_batch_size=2, max_seq_len=128,
        metadata={"sp": 4, "dp": 2, "prefill_buckets": [64]}))
    out = sp_eng.generate([GenerationRequest(prompt=list(range(1, 50)),
                                             max_new_tokens=4)])[0]
    print(f"  sp=4 ring-attention prefill serve: {out.tokens}")


def demo_pipeline() -> None:
    banner("Pipeline parallelism (pp=4, 4 microbatches)")
    import jax.numpy as jnp
    import numpy as np

    from distributed_inference_engine_tpu.models.llama import llama_spec
    from distributed_inference_engine_tpu.parallel.mesh import make_mesh
    from distributed_inference_engine_tpu.parallel.pipeline import (
        make_pp_train_step,
    )

    spec = llama_spec("llama-tiny", max_seq_len=64).replace(dtype="float32")
    mesh = make_mesh(MeshConfig(dp=2, pp=4))
    init_state, step = make_pp_train_step(spec, mesh, n_micro=4,
                                          learning_rate=1e-2)
    state = init_state(jax.random.key(0))
    rs = np.random.RandomState(0)
    tokens = jnp.asarray(rs.randint(1, 1000, (8, 24)), jnp.int32)
    lens = jnp.full((8,), 24, jnp.int32)
    losses = []
    for _ in range(4):
        state, loss = step(state, tokens, lens)
        losses.append(float(loss))
    print(f"  losses over 4 steps: {[round(l, 3) for l in losses]}")


def demo_warmup() -> None:
    banner("Engine warmup (pre-compile all bucketed programs)")
    from distributed_inference_engine_tpu.models.llama import llama_spec

    spec = llama_spec("llama-tiny", max_seq_len=128).replace(dtype="float32")
    eng = Engine(spec, config=EngineConfig(
        max_slots=2, max_seq_len=128, prefill_buckets=[16],
        decode_steps_per_call=4))
    t0 = time.perf_counter()
    rounds = eng.warmup()
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.generate([GenerationRequest(prompt=[1, 2, 3], max_new_tokens=4)])
    t_req = time.perf_counter() - t0
    print(f"  {rounds} warmup rounds in {t_warm:.1f}s; "
          f"first real request {t_req*1e3:.0f}ms")


def demo_round2_compositions() -> None:
    banner("Round 2: int8 x tp, speculative knobs, sp decode, persistence")
    # int8 weight-only composed with tensor parallelism via plain config
    cfg = ModelConfig(name="q8", architecture="llama-tiny", dtype="float32",
                      max_batch_size=2, max_seq_len=128,
                      metadata={"continuous": 1, "page_size": 16, "tp": 2})
    cfg.quantized = True
    eng = engine_from_config(cfg)
    out = eng.generate([GenerationRequest(prompt=[1, 2, 3, 4],
                                          max_new_tokens=6)])[0]
    print(f"  int8 tp=2 continuous serve: {out.tokens} "
          f"(wq sharding {eng.params['blocks']['wq'].q.sharding.spec})")

    # speculative decoding honoring top-k (one-hot => target's exact chain)
    sp_cfg = ModelConfig(name="s", architecture="llama-tiny",
                         dtype="float32", max_batch_size=2, max_seq_len=64,
                         metadata={"speculative": 2,
                                   "draft_size": "llama-tiny"})
    sp_eng = engine_from_config(sp_cfg)
    out = sp_eng.generate([GenerationRequest(prompt=[5, 6, 7],
                                             max_new_tokens=6,
                                             temperature=0.8, top_k=1)])[0]
    m = sp_eng.get_metrics()
    print(f"  speculative top_k=1 @ temp 0.8: {out.tokens} "
          f"(acceptance {m['draft_acceptance_rate']:.2f})")

    # context-parallel decode: sequence-sharded dense KV cache
    cp = engine_from_config(ModelConfig(
        name="cp", architecture="llama-tiny", dtype="float32",
        max_batch_size=2, max_seq_len=128,
        metadata={"sp": 4, "dp": 2, "prefill_buckets": [64]}))
    out = cp.generate([GenerationRequest(prompt=list(range(1, 50)),
                                         max_new_tokens=6)])[0]
    print(f"  sp=4 decode (cache spec {cp._cache_sharding.spec}): "
          f"{out.tokens}")

    # response-cache persistence round-trip
    import tempfile

    from distributed_inference_engine_tpu.serving.cache import ResponseCache

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "cache.pkl")
        c = ResponseCache(max_size=8)
        c.set(("m", (1, 2, 3)), {"tokens": [9, 8]}, ttl=60.0)
        c.save(path)
        c2 = ResponseCache(max_size=8)
        c2.load(path)
        print(f"  cache persisted + restored: {c2.get(('m', (1, 2, 3)))} "
              f"(remaining ttl {c2._entries[('m', (1, 2, 3))].ttl:.0f}s)")




def demo_round3_serving() -> None:
    """Round-3 serving features: overload shedding (typed per-request
    outcomes) and the prefix-aware delta KV handoff between disaggregated
    pools."""
    banner("round 3: overload shedding / delta handoff")
    from distributed_inference_engine_tpu.engine.disagg import (
        PrefillEngine,
        trim_handoff,
    )
    from distributed_inference_engine_tpu.models.base import init_params
    from distributed_inference_engine_tpu.models.llama import llama_spec

    spec = llama_spec("llama-tiny", max_seq_len=128).replace(dtype="float32")
    params = init_params(spec, jax.random.key(0))
    def cfg(**kw):
        base = dict(max_slots=2, max_seq_len=64, prefill_buckets=[32],
                    page_size=16, num_pages=16, decode_steps_per_call=4,
                    kv_dtype="float32")
        base.update(kw)
        return EngineConfig(**base)

    # ---- overload: bounded queue, per-request typed outcomes
    eng = ContinuousEngine(spec, params=params, config=cfg(max_waiting=2))
    reqs = [GenerationRequest(prompt=[1 + i, 2, 3], max_new_tokens=6,
                              request_id=f"o{i}") for i in range(6)]
    out = eng.generate(reqs)
    served = sum(r.finish_reason == "length" for r in out)
    shed = [r for r in out if r.finish_reason == "overloaded"]
    print(f"  burst of 6 at queue cap 2 (no drain between submits): "
          f"{served} accepted+served, {len(shed)} refused "
          f"({shed[0].metadata['overload_reason']}) — per-request "
          "outcomes, accepted siblings keep their generations")

    # ---- prefix-aware delta handoff (disaggregated pools, in-process)
    pe = PrefillEngine(spec, params=params, config=cfg())
    de = ContinuousEngine(spec, params=params, config=cfg(num_pages=32))
    head = list(range(1, 33))                    # two shared full pages
    r1 = GenerationRequest(prompt=head + [40], max_new_tokens=4,
                           temperature=0.0, request_id="full")
    r2 = GenerationRequest(prompt=head + [50], max_new_tokens=4,
                           temperature=0.0, request_id="delta")
    h1, h2 = pe.prefill([r1, r2])
    de.submit_prefilled(r1, h1)
    de.run_until_idle()
    cached = de.kv.probe_prefix(de.kv._page_hashes(r2.prompt, 2))
    delta = trim_handoff(h2, cached * de.kv.page_size)
    de.submit_prefilled(r2, delta)
    (res,) = de.run_until_idle()
    print(f"  delta handoff: decode pool held {cached} prefix pages; "
          f"shipped {delta.nbytes()} B instead of {h2.nbytes()} B "
          f"({100 * (1 - delta.nbytes() / h2.nbytes()):.0f}% saved); "
          f"decoded {res.tokens}")


def main() -> None:
    if jax.default_backend() != "cpu" or len(jax.devices()) < 8:
        sys.exit(
            "this demo needs the virtual 8-device CPU mesh — run as:\n"
            "  JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "python examples/capabilities_demo.py")
    print(f"devices: {len(jax.devices())} x {jax.devices()[0].platform}")
    demo_families()
    demo_stops_minp()
    demo_chunked_prefill()
    demo_config_parallel()
    demo_pipeline()
    demo_warmup()
    demo_round2_compositions()
    demo_round3_serving()
    print("\nAll capability demos completed.")


if __name__ == "__main__":
    main()
