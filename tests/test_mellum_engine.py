"""The sliding-window family (``models/mellum.py``) through
``ContinuousEngine`` and the worker's factory on the CPU: slots, the two page
pools under real admission (the window's pages released on the host path of
``_step`` and taken again), pre-emption by re-prefill, slot reuse after a
long request, the spans and counters, and every combination a per-layer spec
cannot serve, which must raise as for the other per-layer families.
``tests/test_mellum.py`` holds the logits comparisons."""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from distributed_inference_engine_tpu.config import (  # noqa: E402
    EngineConfig, ModelConfig,
)
from distributed_inference_engine_tpu.engine.continuous import (  # noqa: E402
    ContinuousEngine, resolve_decode_body,
)
from distributed_inference_engine_tpu.engine.paged_kv import (  # noqa: E402
    PagedKVCache,
)
from distributed_inference_engine_tpu.engine.types import (  # noqa: E402
    GenerationRequest,
)
from distributed_inference_engine_tpu.models import (  # noqa: E402
    engine_from_config, mellum, spec_for_architecture,
)
from perfbench.lib import families  # noqa: E402
from conftest import grown  # noqa: E402  (this directory)

with open(os.path.join(ROOT, "perfbench", "rehearse",
                       "mellum-tiny.json")) as _f:
    CFG = json.load(_f)
REF = families.reference(CFG)
WINDOW, PAGE = 32, 8
BOUND = WINDOW // PAGE + 2       # window pages a slot, at most


def tiny_spec(**kw):
    return mellum.mellum_spec("mellum-tiny", max_seq_len=256, **kw)


def tiny_engine(dtype="bfloat16", **cfg_kw):
    base = dict(max_slots=4, max_seq_len=256, page_size=PAGE, num_pages=128,
                prefill_buckets=[32, 64, 128], decode_steps_per_call=4)
    base.update(cfg_kw)
    return ContinuousEngine(tiny_spec(dtype=dtype),
                            config=EngineConfig(**base), seed=11)


def judged(engine, requests, results):
    """Every served token the reference's argmax, or within 8 % of
    max|logit| of it: the bound ``tests/test_mellum.py`` holds the bfloat16
    logits to."""
    for req, res in zip(requests, results):
        assert len(res.tokens) == req.max_new_tokens
        lg = np.asarray(REF.logits(
            CFG, engine.params, jnp.asarray(req.prompt + res.tokens)))
        for i, tok in enumerate(res.tokens):
            row = lg[len(req.prompt) - 1 + i]
            assert row.max() - row[tok] <= 0.08 * np.abs(row).max(), (i, tok)


@pytest.mark.parametrize("impl", ["xla", "pallas-decode_interpret"])
def test_engine_serves_eight_rows_of_unequal_length(impl):
    """Ten requests over eight slots (eight live at once, two waiting for a
    freed slot and the window pages it handed back), contexts from inside
    the window of 32 to four of them, across pages of 8 and many chunks of
    4, on the XLA body and on the interpreted kernel."""
    engine = tiny_engine(max_slots=8, num_pages=160, prefix_cache=True,
                         attention_impl=impl)
    assert (engine.body, engine.attn_impl) == ("hybrid", impl)
    rng = np.random.default_rng(1)
    reqs = [GenerationRequest(
        prompt=[int(t) for t in rng.integers(1, 256, n)], max_new_tokens=m)
        for n, m in ((20, 10), (37, 40), (5, 12), (100, 30), (33, 7), (12, 5),
                     (61, 14), (9, 45), (31, 6), (32, 11))]
    results = engine.generate(reqs)
    judged(engine, reqs, results)
    m = engine.get_metrics()
    assert m["prefix_disabled_per_layer"] == 1
    assert m["prefix_hit_admissions"] == 0 and m["kv"]["prefix_queries"] == 0
    assert m["decode_steps"] >= 44 and m["decode_chunks"] >= 11
    # top-2 of 8 in every one of 8 layers, prefill and decode
    tokens = sum(len(r.prompt) + r.max_new_tokens - 1 for r in reqs)
    assert m["moe"]["assignments_total"] == tokens * 2 * 8
    assert m["moe"]["assignments_held"] == m["moe"]["assignments_total"]
    assert 0 < m["moe"]["experts_touched"] <= m["decode_steps"] * 8 * 8
    kv = m["kv"]
    assert (kv["paged_layers"], kv["window_layers"], kv["state_layers"]) == (
        2, 6, 0)
    assert kv["latent_bytes_per_token"] == 2 * 256 * 2
    assert kv["hbm_bytes"] == 2 * 160 * PAGE * 256 * 2
    assert kv["window_hbm_bytes"] == 6 * 8 * BOUND * PAGE * 256 * 2
    assert kv["window_num_pages"] == 8 * BOUND
    assert kv["window_pages_per_slot"] == BOUND
    assert BOUND <= kv["peak_window_pages_used"] <= 8 * BOUND
    # behind the window as the host knows it, a chunk behind the device:
    # what the last chunk of a request passes goes back with its slot
    assert kv["window_pages_released"] >= 8
    # every slot was freed: both pools are whole again
    assert kv["window_pages_used"] == 0 and kv["pages_used"] == 0
    assert 0 < kv["window_pages_held_sum"] < kv["window_pages_uncut_sum"]


def test_counters_follow_lengths_and_steps(shared):
    """One request alone: a prompt of 60 (past the window of 32) and 9
    tokens. The first comes from the prefill; the 8 decode steps attend to
    61 ... 68 rows in a full layer and to 32 each in a sliding one. What the
    attention READ is the program's own count: the XLA body the whole table
    (4 slots x 32 pages x 8) a full layer and the window's pages (4 x 5 x 8)
    a sliding one, the kernel the pages it started a copy of, both plus the
    side window (4 slots x 4 rows)."""
    for impl in ("xla", "pallas-decode_interpret"):
        engine = shared(attention_impl=impl)
        m0 = engine.get_metrics()
        engine.generate([GenerationRequest(prompt=list(range(1, 61)),
                                           max_new_tokens=9)])
        m = engine.get_metrics()
        attn = grown(m0["attn"], m["attn"])
        assert m["decode_steps"] - m0["decode_steps"] == 8
        assert attn["full_context_rows"] == sum(range(61, 69))
        assert attn["window_context_rows"] == 8 * WINDOW
        side = 4 * 4
        if impl == "xla":
            assert attn["full_table_rows"] == 8 * (4 * 32 * PAGE + side)
            assert attn["window_table_rows"] == 8 * (
                4 * (WINDOW // PAGE + 1) * PAGE + side)
        else:
            # chunk 1 from 60 cached rows (8 pages), chunk 2 from 64 (8);
            # a sliding layer's steps from rows 29, 30, 31 (page 3 on: 5
            # pages), 32 (page 4 on: 4) and 33 ... 36 (4 each)
            assert attn["full_table_rows"] == 8 * (8 * PAGE + side)
            assert attn["window_table_rows"] == (
                (3 * 5 + 5 * 4) * PAGE + 8 * side)
        assert m["mla"] == {"decode_context_rows": 0, "decode_table_rows": 0}
        assert "state" not in m


@pytest.mark.parametrize("length,full,band,square", [
    (20, 1 + 2, 1 + 2, 4),                   # bucket 32: both blocks live
    (50, 1 + 2 + 3 + 4, 1 + 2 + 3 + 3, 16),  # bucket 64: block 3 sees 1-3
    (100, 28, 1 + 2 + 5 * 3, 64)])           # bucket 128: 7 of 8 live
def test_prefill_key_block_counters_by_hand(monkeypatch, shared, length,
                                            full, band, square):
    """Blocks of 16 for the count and the window of 32: what the prefill
    kernel would visit for an admitted prompt, a layer of each kind (a full
    layer: at or under the diagonal; a sliding one: from the block holding
    row ``q0 - 31`` on; both below the prompt's length), over the blocks of
    its bucket's whole square; the counters add from one admission to the
    next."""
    from distributed_inference_engine_tpu.ops import flash_prefill

    monkeypatch.setattr(flash_prefill, "Q_BLOCK", 16)
    monkeypatch.setattr(flash_prefill, "K_BLOCK", 16)
    names = [f"{kind}_prefill_key_blocks_{what}"
             for kind in ("full", "window") for what in ("visited", "bucket")]
    engine = shared(attention_impl="xla")
    before = engine.get_metrics()["attn"]
    engine.generate([GenerationRequest(
        prompt=list(range(1, length + 1)), max_new_tokens=2)])
    got = grown(before, engine.get_metrics()["attn"])
    assert [got[n] for n in names] == [full, square, band, square]
    assert not any(k.startswith("prefill_key_blocks")
                   for k in engine.get_metrics()["mla"])


def test_a_dense_tree_reports_no_prefill_blocks_of_either_kind():
    from distributed_inference_engine_tpu.models.llama import llama_spec

    eng = ContinuousEngine(
        llama_spec("llama-tiny", max_seq_len=64), config=EngineConfig(
            max_slots=2, page_size=16, num_pages=16, max_seq_len=64))
    assert "attn" not in eng.get_metrics()


def test_the_spans_are_in_the_programs(shared):
    """Every scope the per-layer metrics read is on some operation of the
    lowered decode and prefill programs."""
    from distributed_inference_engine_tpu.ops.sampling import SamplingParams

    eng = shared(attention_impl="xla")
    kv, n = eng.kv, eng.max_slots
    sampling = SamplingParams(eng._temps, eng._top_k, eng._top_p, eng._min_p)
    dec = eng._decode_chunk.lower(
        eng.params, *kv.pools, eng._lengths, eng._last, eng._active,
        eng._produced, kv.page_table, jnp.zeros((n,), jnp.int32),
        eng._max_new, sampling, eng._eos, eng._stops_dev,
        jax.random.key(0), n_steps=4).as_text(debug_info=True)
    for scope in ("attn.swa", "attn.full", "flash_decode", "attn.kv_update",
                  "attn.kv_gather", "moe.route", "moe.experts",
                  "head.unembed", "sample"):
        assert re.search(rf'["/]{re.escape(scope)}/', dec), scope
    assert not re.search(r'["/]moe\.shared/', dec)
    pre = eng._prefill_pages.lower(
        eng.params, jnp.zeros((1, 32), jnp.int32), jnp.ones((1,), jnp.int32),
        *kv.pools, jnp.zeros((1, kv.max_pages_per_seq), jnp.int32),
        SamplingParams(jnp.zeros((1,)), jnp.zeros((1,), jnp.int32),
                       jnp.ones((1,)), jnp.zeros((1,))), jax.random.key(0),
        jnp.zeros((1,), jnp.int32)).as_text(debug_info=True)
    for scope in ("attn.swa", "attn.full", "attn.kv_update", "moe.route",
                  "moe.experts", "head.unembed", "sample"):
        assert re.search(rf'["/]{re.escape(scope)}/', pre), scope


def test_the_same_prompt_twice_is_no_prefix_hit_and_the_same_tokens():
    engine = tiny_engine(prefix_cache=True)
    prompt = [int(t) for t in np.random.default_rng(2).integers(1, 256, 40)]
    first = engine.generate([GenerationRequest(prompt=list(prompt),
                                               max_new_tokens=8)])
    second = engine.generate([GenerationRequest(prompt=list(prompt),
                                                max_new_tokens=8)])
    assert first[0].tokens == second[0].tokens
    m = engine.get_metrics()
    assert m["prefix_hit_admissions"] == 0 and m["kv"]["prefix_hit_pages"] == 0


def test_a_preempted_sequence_is_re_prefilled_and_resumes(shared):
    """A full-layer pool too small for both requests at full length: the
    victim is re-queued as prompt + tokens and re-prefilled (its window
    pages went back with its slot and come anew); the result equals the same
    request served alone. In float32, as the other families' tests."""
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, 256, n)] for n in (30, 28)]

    def make():
        return [GenerationRequest(prompt=list(p), max_new_tokens=60)
                for p in prompts]

    alone = [shared("float32").generate([r])[0] for r in make()]
    tight = tiny_engine("float32", num_pages=16)
    together = tight.generate(make())
    m = tight.get_metrics()
    assert m["reprefill_preemptions"] >= 1 and m["capacity_finishes"] == 0
    for a, b in zip(alone, together):
        assert a.tokens == b.tokens and len(b.tokens) == 60
        assert b.finish_reason == a.finish_reason
    assert m["kv"]["window_pages_used"] == 0


def test_a_slot_is_reused_after_a_long_request_and_its_window_pages_too():
    """One slot: a request four windows long, then a short one in the same
    slot over the pages the first gave back; both serve the reference's
    tokens (float32, greedy), and the slot never held more than its bound."""
    rng = np.random.default_rng(5)
    one = tiny_engine("float32", max_slots=1)
    for n, m in ((100, 40), (10, 12)):
        reqs = [GenerationRequest(
            prompt=[int(t) for t in rng.integers(1, 256, n)],
            max_new_tokens=m)]
        judged(one, reqs, one.generate(reqs))
    kv = one.get_metrics()["kv"]
    assert kv["peak_window_pages_used"] <= BOUND
    # 40 rows pass five pages; the host is a chunk behind, so the last of
    # them goes back with the slot and not from behind the window
    assert kv["window_pages_released"] >= 40 // PAGE - 1
    assert kv["window_pages_used"] == 0


def test_a_chunk_as_long_as_a_page_is_served_from_what_the_window_pool_spares(
        shared):
    """A chunk of 8 on pages of 8 (``perfbench/rehearse/mellum-tiny.json``):
    a window and TWO chunks no longer fit a slot's six window pages, so a
    grant a chunk ahead of the one in flight comes back short, the engine
    reads that chunk first and decides on current lengths; nothing is
    refused at load and the tokens are the one-chunk-a-page engine's."""
    rng = np.random.default_rng(9)
    prompts = [[int(t) for t in rng.integers(1, 256, n)]
               for n in (60, 37, 90, 20, 45, 33)]

    def make():
        return [GenerationRequest(prompt=list(p), max_new_tokens=44)
                for p in prompts]

    ref = shared("float32").generate(make())
    engine = tiny_engine("float32", decode_steps_per_call=PAGE)
    got = engine.generate(make())
    assert [r.tokens for r in got] == [r.tokens for r in ref]
    m = engine.get_metrics()
    assert m["sync_fallback_iterations"] > 0
    assert m["capacity_finishes"] == 0
    assert m["kv"]["peak_window_pages_used"] <= 4 * BOUND
    assert m["kv"]["window_pages_used"] == 0


def test_streamed_matches_unstreamed(shared):
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, 256, n)] for n in (30, 45)]
    eng = shared("float32")

    def run(stream):
        frames = [[] for _ in prompts]
        for i, p in enumerate(prompts):
            eng.submit(GenerationRequest(prompt=list(p), max_new_tokens=24,
                                         request_id=f"x{i}"),
                       on_tokens=frames[i].append if stream else None)
        res = {r.request_id: r for r in eng.run_until_idle()}
        return [res[f"x{i}"] for i in range(len(prompts))], frames

    got, frames = run(True)
    want, _none = run(False)
    assert len(got) == len(want) == 2
    for g, w, fr in zip(got, want, frames):
        assert (g.tokens, g.finish_reason) == (w.tokens, w.finish_reason)
        assert [t for f in fr for t in f] == g.tokens and len(g.tokens) == 24


# ------------------------------------------------------- what it cannot do


def test_the_body_is_chosen_from_what_the_spec_states():
    """K|V rows of whole lane tiles: the kernel on a TPU, XLA elsewhere and
    over a mesh; the window does not send a per-layer spec to ``inline``
    (a uniform spec's still does)."""
    spec = spec_for_architecture("mellum", size="mellum2-12b-a2.5b-pp1",
                                 max_seq_len=16896)
    assert spec.max_seq_len == 16896 and not spec.recurrent
    assert (spec.kv_row_lanes, spec.cache_row_width) == (512, 1024)
    assert (spec.paged_layers, spec.window_layers) == (3, 9)
    assert resolve_decode_body("auto", "tpu", spec) == ("hybrid",
                                                        "pallas-decode")
    assert resolve_decode_body("auto", "cpu", spec) == ("hybrid", "xla")
    assert resolve_decode_body("auto", "tpu", spec, sharded=True) == (
        "hybrid", "xla")
    uniform = spec_for_architecture("mistral", size="mistral-tiny")
    if uniform.sliding_window:
        assert resolve_decode_body("auto", "tpu", uniform) == ("inline",
                                                               "xla")
    narrow = tiny_spec(n_kv_heads=1)
    assert narrow.kv_row_lanes == 64
    assert resolve_decode_body("auto", "tpu", narrow) == ("hybrid", "xla")
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        resolve_decode_body("pallas-decode", "tpu", narrow)
    with pytest.raises(ValueError, match="unknown mellum size"):
        spec_for_architecture("mellum", size="mellum-9b")


def test_a_chunk_longer_than_the_window_is_refused_when_traced():
    eng = ContinuousEngine(
        tiny_spec(sliding_window=2), seed=1,
        config=EngineConfig(max_slots=2, max_seq_len=64, page_size=PAGE,
                            num_pages=16, prefill_buckets=[32],
                            decode_steps_per_call=4))
    with pytest.raises(ValueError, match="longer than the window"):
        eng.generate([GenerationRequest(prompt=[1, 2, 3], max_new_tokens=6)])


@pytest.mark.parametrize("kw", [{"kv_offload": True}, {"prefill_chunk": 32}])
def test_engine_options_a_per_layer_spec_cannot_honour_raise(kw):
    with pytest.raises(ValueError, match="per-layer"):
        tiny_engine(**kw)


def test_sharding_an_artifact_and_a_quantized_tree_raise():
    cfg = EngineConfig(max_slots=2, max_seq_len=64, page_size=PAGE,
                       num_pages=16)
    for kw in ({"shard_fn": lambda p: p}, {"kv_sharding": object()},
               {"sp_mesh": object()}, {"artifact_path": "/nonexistent"}):
        with pytest.raises(ValueError, match="per-layer"):
            ContinuousEngine(tiny_spec(), config=cfg, **kw)
    from distributed_inference_engine_tpu.ops.quant import quantize_weight

    params = mellum.init_params(tiny_spec(), jax.random.key(7))
    bad = dict(params, lm_head=quantize_weight(
        params["lm_head"].astype(jnp.float32), reduce_axes=(0,)))
    with pytest.raises(ValueError, match="unquantized"):
        ContinuousEngine(tiny_spec(), params=bad, config=cfg)


@pytest.mark.parametrize("change,match", [
    ({"quantized": True}, "quantized"),
    ({"path": "/tmp"}, "checkpoint"),
    ({"metadata": {"tp": 2}}, "mesh"),
    ({"metadata": {"speculative": 2}}, "speculative"),
    ({"metadata": {"role": "prefill"}}, "prefill"),
    ({"metadata": {"artifact": "/tmp/a"}}, "artifact"),
    ({"metadata": {"continuous": 0}}, "static engine"),
    ({"metadata": {"kv_offload": True}}, "kv_offload"),
    ({"metadata": {"prefill_chunk": 32}}, "prefill_chunk"),
])
def test_deploys_this_architecture_cannot_serve_raise(change, match):
    meta = {"size": "mellum-tiny", "continuous": 1, "page_size": PAGE,
            "num_pages": 16}
    meta.update(change.get("metadata", {}))
    cfg = ModelConfig(name="m", architecture="mellum", max_batch_size=2,
                      max_seq_len=64, metadata=meta,
                      **{k: v for k, v in change.items() if k != "metadata"})
    with pytest.raises(ValueError, match=match):
        engine_from_config(cfg)


def test_calls_a_per_layer_spec_cannot_answer_raise(shared):
    engine = shared(attention_impl="xla")
    with pytest.raises(ValueError, match="per-layer spec has no prefill"):
        engine.kv_export([1, 2, 3])
    with pytest.raises(ValueError, match="per-layer"):
        engine.submit_prefilled(GenerationRequest(prompt=[1, 2]), None)
    with pytest.raises(ValueError, match="ONE K|V pool"):
        PagedKVCache(tiny_spec(), max_slots=2, page_size=PAGE, num_pages=8,
                     offload=object())


def test_the_worker_seeds_the_tree_from_metadata():
    def build(seed):
        return engine_from_config(ModelConfig(
            name="m", architecture="mellum", max_batch_size=2,
            max_seq_len=64, dtype="bfloat16", metadata={
                "size": "mellum-tiny", "continuous": 1, "page_size": PAGE,
                "num_pages": 16, "seed": seed, "admission_max_rows": 1}))

    a, b, c = build(5), build(5), build(6)
    assert a.config.admission_max_rows == 1
    la, lb, lc = (e.params["period"][1]["w_gate_up"] for e in (a, b, c))
    assert bool((la == lb).all()) and not bool((la == lc).all())
    assert la.dtype == jnp.bfloat16
    assert a.params["period"][0]["w_router"].dtype == jnp.float32
    assert a.kv.num_window_pages == 2 * BOUND
