"""Chunked prefill: long prompts prefill in page-aligned chunks interleaved
with decode rounds (``EngineConfig.prefill_chunk``), so admissions stop
stalling live decodes for a whole prompt (SURVEY.md §7 hard-part #3 —
prefill/decode interference inside one pool).

Correctness bar: chunking is an execution schedule, not a model change —
greedy output must be token-identical with and without it.
"""

import numpy as np

from distributed_inference_engine_tpu.config import EngineConfig
from distributed_inference_engine_tpu.engine.continuous import ContinuousEngine
from distributed_inference_engine_tpu.engine.engine import Engine
from distributed_inference_engine_tpu.engine.types import GenerationRequest
from distributed_inference_engine_tpu.models.llama import llama_spec

SPEC = llama_spec("llama-tiny", max_seq_len=256).replace(dtype="float32")


def _cfg(**kw):
    base = dict(max_slots=4, max_seq_len=256, prefill_buckets=[16, 64, 256],
                page_size=16, num_pages=80, decode_steps_per_call=4)
    base.update(kw)
    return EngineConfig(**base)


def test_chunked_greedy_matches_unchunked():
    static = Engine(SPEC, config=_cfg(), seed=0)
    plain = ContinuousEngine(SPEC, params=static.params, config=_cfg())
    chunked = ContinuousEngine(SPEC, params=static.params,
                               config=_cfg(prefill_chunk=32))
    prompt = list(range(1, 161))            # 160 tokens -> 5 chunks of 32
    req = lambda: GenerationRequest(prompt=list(prompt), max_new_tokens=12)
    a = plain.generate([req()])[0]
    b = chunked.generate([req()])[0]
    assert a.tokens == b.tokens
    assert chunked.get_metrics()["chunked_admissions"] == 1
    # the chunk schedule really ran: 5 prefill dispatches, not 1
    assert chunked.get_metrics()["prefill_calls"] == 5


def test_chunk_size_rounds_to_page_multiple():
    eng = ContinuousEngine(SPEC, config=_cfg(prefill_chunk=40))  # page 16
    assert eng._chunk == 32
    eng2 = ContinuousEngine(SPEC, config=_cfg(prefill_chunk=7))
    assert eng2._chunk == 16                # at least one page


def test_short_prompts_bypass_chunking():
    eng = ContinuousEngine(SPEC, config=_cfg(prefill_chunk=64))
    out = eng.generate([GenerationRequest(prompt=[1, 2, 3],
                                          max_new_tokens=4)])[0]
    assert len(out.tokens) == 4
    m = eng.get_metrics()
    assert m["chunked_admissions"] == 0 and m["prefill_calls"] == 1


def test_decode_interleaves_with_chunked_prefill():
    """A short request admitted alongside a long one must finish while the
    long prompt is still prefilling — the scheduling property chunking
    buys."""
    eng = ContinuousEngine(SPEC, config=_cfg(prefill_chunk=16))
    long_id = eng.submit(GenerationRequest(prompt=list(range(1, 129)),
                                           max_new_tokens=4))
    short_id = eng.submit(GenerationRequest(prompt=[5, 6, 7],
                                            max_new_tokens=4))
    short_done_while_prefilling = False
    for _ in range(200):
        n = eng.step()
        done_ids = {r.request_id for r in eng._finished}
        if short_id in done_ids and eng._prefilling:
            short_done_while_prefilling = True
        if n == 0 and not eng.n_waiting:
            break
    results = {r.request_id: r for r in eng.drain_finished()}
    assert set(results) == {long_id, short_id}
    assert len(results[long_id].tokens) == 4
    assert short_done_while_prefilling, \
        "short request should finish mid-prefill of the long prompt"


def test_burst_of_long_prompts_prefills_in_parallel():
    """VERDICT r1 item 7: every in-flight chunked prefill advances per
    step in ONE batched suffix dispatch, so a burst of N long prompts
    finishes prefill in ~1/N the steps of the round-1 serial schedule
    (which advanced one prompt per step: 4×8 chunks = 32 steps)."""
    cfg = _cfg(prefill_chunk=16, num_pages=200, max_slots=8)
    eng = ContinuousEngine(SPEC, config=cfg, seed=0)
    for i in range(4):
        eng.submit(GenerationRequest(prompt=list(range(1 + i, 129 + i)),
                                     max_new_tokens=2))   # 8 chunks each
    steps = 0
    while eng._prefilling or eng.n_waiting:
        eng.step()
        steps += 1
        assert steps < 40, "prefill burst did not converge"
    # parallel schedule: 1 admission (first chunks batched) + 7 batched
    # advances ≈ 8 steps; the serial schedule needed 32
    assert steps <= 10, f"burst took {steps} steps — chunk advance serialized?"
    out = eng.run_until_idle()
    assert len(out) == 4 and all(len(r.tokens) == 2 for r in out)


def test_parallel_chunked_parity_with_unchunked():
    """Batched multi-prompt chunk advance is still only a schedule: greedy
    output for a burst of different-length long prompts must match the
    unchunked engine token-for-token."""
    big = dict(max_slots=8, num_pages=200)
    plain = ContinuousEngine(SPEC, config=_cfg(**big), seed=0)
    chunked = ContinuousEngine(SPEC, params=plain.params,
                               config=_cfg(prefill_chunk=32, **big))
    mk = lambda: [GenerationRequest(prompt=list(range(1 + i, 100 + i * 7)),
                                    max_new_tokens=8, request_id=f"r{i}")
                  for i in range(4)]
    a = {r.request_id: r.tokens for r in plain.generate(mk())}
    b = {r.request_id: r.tokens for r in chunked.generate(mk())}
    assert a == b
    assert chunked.get_metrics()["chunked_admissions"] == 4


def test_chunked_streaming_and_eos():
    eng = ContinuousEngine(SPEC, config=_cfg(prefill_chunk=32), seed=1)
    got = []
    req = GenerationRequest(prompt=list(range(1, 81)), max_new_tokens=16)
    eng.submit(req, on_tokens=got.extend)
    res = eng.run_until_idle()[0]
    assert got == res.tokens


def test_abort_frees_prefilling_pages():
    eng = ContinuousEngine(SPEC, config=_cfg(prefill_chunk=16))
    eng.submit(GenerationRequest(prompt=list(range(1, 129)),
                                 max_new_tokens=4))
    eng.step()                               # admit + first chunk only
    assert eng._prefilling
    used_before = eng.kv.get_stats()["pages_used"]
    n = eng.abort_all()
    assert n == 1 and not eng._prefilling
    assert eng.kv.get_stats()["pages_used"] < used_before


def test_pump_completes_chunked_prefill_without_other_traffic():
    """Regression: mid-chunked-prefill sequences must count as live, or the
    pump's idle gate stops stepping the engine after the first chunk and
    the request hangs forever."""
    import asyncio

    from distributed_inference_engine_tpu.serving.pump import EnginePump

    async def main():
        eng = ContinuousEngine(SPEC, config=_cfg(prefill_chunk=16), seed=0)
        pump = EnginePump(eng, idle_wait_s=0.05)
        req = GenerationRequest(prompt=list(range(1, 129)), max_new_tokens=4)
        out = await asyncio.wait_for(pump.generate([req]), timeout=60)
        assert len(out[0].tokens) == 4
        assert eng.get_metrics()["chunked_admissions"] == 1
        await pump.stop()

    asyncio.run(main())


def test_prefix_hit_with_long_tail_chunks_the_tail():
    """A prefix-cache hit whose uncached tail exceeds the chunk must chunk
    the tail (a long unique tail stalls decode exactly like a miss)."""
    cfg = _cfg(prefill_chunk=32, prefix_cache=True)
    eng = ContinuousEngine(SPEC, config=cfg, seed=0)
    shared = list(range(1, 49))              # 3 pages, page-aligned prefix
    r1 = GenerationRequest(prompt=list(shared), max_new_tokens=2)
    eng.generate([r1])                       # registers the prefix pages
    long_tail = list(shared) + list(range(60, 180))   # 120-token unique tail
    r2 = GenerationRequest(prompt=list(long_tail), max_new_tokens=4)
    out = eng.generate([r2])[0]
    assert len(out.tokens) == 4
    m = eng.get_metrics()
    assert m["chunked_admissions"] >= 1      # the tail went through chunking
    assert m["prefix_hit_admissions"] >= 1   # counted as a prefix hit too
    # parity: same request on a fresh engine without chunking/prefix cache
    ref = ContinuousEngine(SPEC, params=eng.params,
                           config=_cfg(prefix_cache=False))
    assert ref.generate([GenerationRequest(prompt=list(long_tail),
                                           max_new_tokens=4)])[0].tokens \
        == out.tokens


# ------------------------------------------------- config compose validation


def test_validate_prefill_compose():
    import pytest

    from distributed_inference_engine_tpu.config import (
        validate_prefill_compose,
    )

    validate_prefill_compose(0, sp=4)        # no chunking: any sp is fine
    validate_prefill_compose(512, sp=1)      # chunking without sp is fine
    with pytest.raises(ValueError, match="prefill_chunk"):
        validate_prefill_compose(512, sp=2)
    # the message must be actionable: name both escape hatches
    with pytest.raises(ValueError, match="prefill_chunk=0"):
        validate_prefill_compose(512, sp=2)
    with pytest.raises(ValueError, match="sp=1"):
        validate_prefill_compose(512, sp=2)


def test_metadata_loader_rejects_sp_plus_chunk():
    """The deploy-config path fails BEFORE the checkpoint load."""
    import pytest

    from distributed_inference_engine_tpu.config import ModelConfig
    from distributed_inference_engine_tpu.models import engine_from_config

    cfg = ModelConfig(
        name="m", architecture="gpt2", metadata={
            "sp": 2, "prefill_chunk": 512})
    with pytest.raises(ValueError, match="prefill_chunk"):
        engine_from_config(cfg)
