"""fp8 (e4m3) KV cache: half the KV HBM of bf16, so double the live
sequences per chip — ``EngineConfig.kv_dtype="float8_e4m3fn"`` flows
through the contiguous cache, the paged pools (XLA and Pallas paths), the
prefix cache, and the disaggregated handoff. The attention ops upcast at
the boundary (fp8 has no implicit promotion path in jax)."""

import numpy as np
import pytest

from distributed_inference_engine_tpu.config import EngineConfig
from distributed_inference_engine_tpu.engine.continuous import ContinuousEngine
from distributed_inference_engine_tpu.engine.engine import Engine
from distributed_inference_engine_tpu.engine.types import GenerationRequest
from distributed_inference_engine_tpu.models.llama import llama_spec

SPEC = llama_spec("llama-tiny", max_seq_len=128).replace(dtype="float32")
CFG = dict(max_slots=2, max_seq_len=128, prefill_buckets=[16],
           decode_steps_per_call=4)


def _req(n=10):
    return GenerationRequest(prompt=[1, 2, 3, 4], max_new_tokens=n)


def test_static_engine_fp8_kv_matches_bf16_greedy():
    ref = Engine(SPEC, config=EngineConfig(**CFG), seed=0)
    base = ref.generate([_req()])[0].tokens
    e8 = Engine(SPEC, params=ref.params,
                config=EngineConfig(**CFG, kv_dtype="float8_e4m3fn"))
    assert e8.generate([_req()])[0].tokens == base


def test_continuous_fp8_pages_half_the_bytes():
    ref = ContinuousEngine(SPEC, config=EngineConfig(
        **CFG, page_size=16, num_pages=24), seed=0)
    base = ref.generate([_req()])[0].tokens
    c8 = ContinuousEngine(SPEC, params=ref.params, config=EngineConfig(
        **CFG, page_size=16, num_pages=24, kv_dtype="float8_e4m3fn"))
    assert c8.generate([_req()])[0].tokens == base
    assert c8.kv.k_pages.dtype.itemsize == 1
    assert (c8.kv.get_stats()["hbm_bytes"]
            == ref.kv.get_stats()["hbm_bytes"] // 2)


def test_disagg_handoff_fp8_roundtrip():
    from distributed_inference_engine_tpu.engine.disagg import (
        PrefillEngine,
        handoff_from_wire,
        handoff_to_wire,
    )

    eng = PrefillEngine(SPEC, config=EngineConfig(
        **CFG, kv_dtype="float8_e4m3fn"), seed=0)
    h = eng.prefill([GenerationRequest(prompt=[1, 2, 3, 4],
                                       max_new_tokens=2,
                                       request_id="r")])[0]
    assert h.k.dtype.itemsize == 1
    h2 = handoff_from_wire(handoff_to_wire(h))
    np.testing.assert_array_equal(
        h.k.view(np.uint8), h2.k.view(np.uint8))

    # and the decode side admits it
    dec = ContinuousEngine(SPEC, params=eng.params, config=EngineConfig(
        **CFG, page_size=16, num_pages=24, kv_dtype="float8_e4m3fn"))
    dec.submit_prefilled(GenerationRequest(prompt=[1, 2, 3, 4],
                                           max_new_tokens=4,
                                           request_id="r"), h2)
    out = dec.run_until_idle()[0]
    assert len(out.tokens) == 4
