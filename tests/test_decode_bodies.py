"""The continuous engine's decode bodies against the static ``Engine``.

A ``ContinuousEngine`` runs ONE decode body, chosen at construction from what
it observes (``engine.continuous.resolve_attention_impl``): ``dense`` (XLA,
the context gathered once a chunk), ``window`` (``ops/flash_decode.py`` in
place from the page pool; on a CPU through ``pallas-decode_interpret``) or
``inline`` (per-step page scatter, the only path a sliding-window spec has).
Every case here serves the same six greedy requests over four slots through
one body and holds the tokens to the static engine's, request by request:
unequal prompt lengths, slot reuse, admission into a decoding batch (deferred
first tokens), rows that stay dead, one request stopped by a stop id and one
whose ``max_new_tokens`` ends in the middle of a chunk. Families cover GQA
groups, qkv bias, the plus-one norm with a tied head and routed experts; the
kv dtypes the model's own and fp8 pools; the quantized cases int8 and int4
trees."""

import jax
import pytest

from distributed_inference_engine_tpu.config import EngineConfig
from distributed_inference_engine_tpu.engine.continuous import ContinuousEngine
from distributed_inference_engine_tpu.engine.engine import Engine
from distributed_inference_engine_tpu.engine.types import GenerationRequest
from distributed_inference_engine_tpu.models import (
    gemma_spec,
    gpt2_spec,
    llama_spec,
    mistral_spec,
    mixtral_spec,
    qwen_spec,
)
from distributed_inference_engine_tpu.models.base import init_params
from distributed_inference_engine_tpu.ops.quant import random_quantized_params

_SMALL = dict(n_layers=2, vocab_size=128, max_seq_len=128, dtype="float32")

# every spec keeps Hkv * Dh = 128 lanes: what the in-place kernel needs
_SPECS = {
    "gpt2": lambda: gpt2_spec("gpt2", d_model=128, n_heads=4, n_kv_heads=4,
                              d_ff=256, **_SMALL),
    "llama": lambda: llama_spec("llama-tiny", **_SMALL),       # GQA 8:4
    "qwen": lambda: qwen_spec("qwen-tiny", **_SMALL),          # qkv bias
    "gemma": lambda: gemma_spec("gemma-tiny", n_heads=4, n_kv_heads=1,
                                head_dim_override=128, **_SMALL),  # MQA
    "moe": lambda: mixtral_spec("mixtral-tiny", **_SMALL),
    # a window the contexts outgrow, and one they never reach
    "swa-short": lambda: mistral_spec("mistral-tiny", sliding_window=8,
                                      **_SMALL),
    "swa-long": lambda: mistral_spec("mistral-tiny", sliding_window=256,
                                     **_SMALL),
}

_IMPL = {"dense": "xla", "window": "pallas-decode_interpret",
         "inline": "auto"}
_KV = {"model": "float32", "fp8": "float8_e4m3fn"}

# (prompt length, max_new_tokens): 7 and 6 end inside a chunk of 4; the
# first request outlives the rest, so later admissions meet a live batch
_SHAPES = [(5, 17), (19, 7), (3, 12), (33, 6), (11, 10), (26, 8)]
_STOPPED = 2            # this request gets one of its own tokens as stop id


def _requests(stops=None):
    """``stops`` = (the id that ends request ``_STOPPED``, an id no chain
    holds): every request carries a stop id, so each engine keeps to the
    one decode program that checks them."""
    reqs = []
    for i, (n, m) in enumerate(_SHAPES):
        reqs.append(GenerationRequest(
            prompt=[3 + i] + [(7 * j + i) % 97 + 1 for j in range(n - 1)],
            max_new_tokens=m, temperature=0.0, request_id=f"r{i}",
            stop_ids=[] if stops is None else [stops[i != _STOPPED]]))
    return reqs


def _static_tokens(spec, params, kv_dtype):
    """The static engine's chains, and the stop id the case uses: a token
    request ``_STOPPED`` emits after its first chunk has begun."""
    eng = Engine(spec, params=params, config=EngineConfig(
        max_slots=8, max_seq_len=128, prefill_buckets=[64],
        kv_dtype=kv_dtype))
    free = {r.request_id: r.tokens for r in eng.generate(_requests())}
    seen = {t for toks in free.values() for t in toks}
    stops = (free[f"r{_STOPPED}"][5],
             next(t for t in range(spec.vocab_size) if t not in seen))
    want = {r.request_id: (r.tokens, r.finish_reason)
            for r in eng.generate(_requests(stops))}
    for i, (_n, m) in enumerate(_SHAPES):
        if i == _STOPPED:
            assert want[f"r{i}"][1] == "stop" and len(want[f"r{i}"][0]) < m
        else:
            assert len(want[f"r{i}"][0]) == m
    return want, stops


def _serve(spec, params, body, kv_dtype):
    # one row an admission: one prefill program a case (batched admission
    # is tests/test_continuous.py's), every later admission deferred
    eng = ContinuousEngine(spec, params=params, config=EngineConfig(
        max_slots=4, max_seq_len=128, prefill_buckets=[64],
        page_size=32, num_pages=20, decode_steps_per_call=4,
        prefix_cache=False, kv_dtype=kv_dtype, admission_max_rows=1,
        attention_impl=_IMPL[body]))
    return eng


def _check_body(eng, body):
    m = eng.get_metrics()
    in_place, dense = m["decode_chunks_in_place"], m["decode_chunks_dense"]
    if body == "dense":
        assert m["attn_impl"] == "xla" and dense > 0 and in_place == 0
    elif body == "window":
        assert m["attn_impl"] == "pallas-decode_interpret"
        assert in_place > 0 and dense == 0
    else:
        assert m["attn_impl"] == "xla" and dense == 0 and in_place == 0
        assert m["decode_chunks"] > 0
    # six requests over four slots: some successor was prefilled behind
    # the chunk its predecessor ended in
    assert m["admissions_ahead"] > 0
    assert m["total_requests"] == len(_SHAPES)


@pytest.fixture(scope="module")
def trees():
    """One parameter tree a (family, weight bits), and the static engine's
    chains a (family, bits, kv dtype), built the first time a case asks."""
    cache = {}

    def get(family, kv, bits=0):
        if (family, bits) not in cache:
            spec = _SPECS[family]()
            cache[family, bits] = (spec, random_quantized_params(
                spec, jax.random.key(5), bits=bits) if bits
                else init_params(spec, jax.random.key(11)))
        spec, params = cache[family, bits]
        if (family, bits, kv) not in cache:
            cache[family, bits, kv] = _static_tokens(spec, params, _KV[kv])
        return (spec, params) + cache[family, bits, kv]

    return get


_UNIFORM = [(f, b) for f in ("gpt2", "llama", "qwen", "gemma", "moe")
            for b in ("dense", "window")]
_WINDOWED = [("swa-short", "inline"), ("swa-long", "inline")]


@pytest.mark.parametrize("kv", ["model", "fp8"])
@pytest.mark.parametrize("family,body", _UNIFORM + _WINDOWED)
def test_body_tokens_match_static_engine(trees, family, body, kv):
    spec, params, want, stops = trees(family, kv)
    eng = _serve(spec, params, body, _KV[kv])
    got = {r.request_id: (r.tokens, r.finish_reason)
           for r in eng.generate(_requests(stops))}
    assert got == want
    _check_body(eng, body)


@pytest.mark.parametrize("body", ["dense", "window"])
@pytest.mark.parametrize("bits", [8, 4])
def test_body_quantized_trees(trees, bits, body):
    """int8 and packed-int4 trees (the int4 product through its kernel,
    interpreted here) under both uniform bodies."""
    spec, params, want, stops = trees("llama", "model", bits)
    eng = _serve(spec, params, body, _KV["model"])
    got = {r.request_id: (r.tokens, r.finish_reason)
           for r in eng.generate(_requests(stops))}
    assert got == want
    _check_body(eng, body)


@pytest.mark.parametrize("family,body", [
    ("llama", "dense"), ("llama", "window"), ("moe", "dense"),
    ("swa-short", "inline")])
def test_body_streamed_matches_static_engine(trees, family, body):
    """Every request streamed: a chunk's tokens go to the callbacks under
    the NEXT chunk's dispatch (a finishing slot's at once), on every body.
    Tokens and reasons stay the static engine's, logprobs those of the
    same engine unstreamed; each stream splices to its result, and every
    decode chunk is counted once, as carried or as flushed."""
    spec, params, want, stops = trees(family, "model")
    plain = {r.request_id: r for r in _serve(
        spec, params, body, _KV["model"]).generate(_requests(stops))}
    eng = _serve(spec, params, body, _KV["model"])
    frames = {}
    for r in _requests(stops):
        frames[r.request_id] = []
        eng.submit(r, on_tokens=frames[r.request_id].append)
    got = {r.request_id: r for r in eng.run_until_idle()}
    assert {i: (r.tokens, r.finish_reason) for i, r in got.items()} == want
    for rid, res in got.items():
        assert res.logprobs == plain[rid].logprobs
        assert [t for f in frames[rid] for t in f] == res.tokens
    _check_body(eng, body)
    m = eng.get_metrics()
    assert m["emit_carried_chunks"] > 0
    assert (m["emit_carried_chunks"] + m["emit_flushed_chunks"]
            == m["decode_chunks"])
