"""The mHC family (``models/xing.py``) through ``ContinuousEngine`` and the
worker's factory on the CPU: slots and latent pages under real admission, a
zero-layer state through every program, pre-emption by re-prefill, streamed
and unstreamed, and every combination a per-layer spec WITHOUT recurrent
layers cannot serve, which must raise at load or at the call under a message
that says why (no prefill continues from cached pages) and not "recurrent".
``tests/test_xing.py`` holds the logits comparisons against the reference."""

import json
import os
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
    engine_from_config, spec_for_architecture, xing,
)
from perfbench.lib import families  # noqa: E402
from conftest import grown  # noqa: E402  (this directory)

with open(os.path.join(ROOT, "perfbench", "rehearse", "xing-tiny.json")) as _f:
    CFG = json.load(_f)
REF = families.reference(CFG)


def tiny_spec(**kw):
    return xing.xing_spec("xing-tiny", max_seq_len=128, **kw)


def tiny_engine(dtype="bfloat16", **cfg_kw):
    base = dict(max_slots=4, max_seq_len=128, page_size=16, num_pages=32,
                prefill_buckets=[32, 64], decode_steps_per_call=4)
    base.update(cfg_kw)
    return ContinuousEngine(tiny_spec(dtype=dtype),
                            config=EngineConfig(**base), seed=11)


def judged(engine, requests, results):
    """Every served token the reference's argmax, or within 8 % of
    max|logit| of it: the bound ``tests/test_xing.py`` holds the bfloat16
    logits to."""
    for req, res in zip(requests, results):
        assert len(res.tokens) == req.max_new_tokens
        lg = np.asarray(REF.logits(
            CFG, engine.params, jnp.asarray(req.prompt + res.tokens)))
        for i, tok in enumerate(res.tokens):
            row = lg[len(req.prompt) - 1 + i]
            assert row.max() - row[tok] <= 0.08 * np.abs(row).max(), (i, tok)


def test_engine_serves_the_family_through_slots_and_latent_pages(shared):
    """Six requests of unequal length over four slots: batched admission at
    padded buckets, deferred first tokens, slot reuse, counters."""
    engine = shared(prefix_cache=True)
    m0 = engine.get_metrics()
    assert engine.body == "hybrid" and engine.attn_impl == "xla"
    assert engine.spec.cache_row_width == 128     # 32 + 8 values, one tile
    rng = np.random.default_rng(1)
    reqs = [GenerationRequest(
        prompt=[int(t) for t in rng.integers(1, 256, n)], max_new_tokens=m)
        for n, m in ((20, 10), (37, 6), (5, 12), (50, 9), (33, 7), (12, 5))]
    results = engine.generate(reqs)
    judged(engine, reqs, results)
    m = engine.get_metrics()
    # off from the spec, and the counter says why: per-layer, not recurrent
    assert m["prefix_disabled_per_layer"] == 1
    assert m["prefix_hit_admissions"] == 0 and m["kv"]["prefix_queries"] == 0
    steps = m["decode_steps"] - m0["decode_steps"]
    assert steps >= 12 and m["decode_chunks"] - m0["decode_chunks"] >= 3
    moe = grown(m0["moe"], m["moe"])
    assert 0 < moe["assignments_held"] == moe["assignments_total"]
    assert 0 < moe["experts_touched"] <= steps * 3 * 8
    kv = m["kv"]
    assert (kv["paged_layers"], kv["state_layers"]) == (4, 0)
    assert kv["latent_bytes_per_token"] == 4 * 128 * 2
    assert kv["state_bytes"] == 0
    assert kv["hbm_bytes"] == 4 * 32 * 16 * 128 * 2
    assert {a.shape for a in engine.kv.state.values()} == {(0, 4)}


def test_mla_counters_follow_lengths_and_steps(shared):
    """One request alone: a prompt of 20 and 9 tokens. The first comes from
    the prefill; the 8 decode steps attend to 21, 22, ... 28 rows each (the
    cached rows and the step's own). What the attention READ is the
    program's own count, a layer: the XLA body a layer's whole gathered
    table (4 slots x 8 pages x 16) every step, the kernel the pages it
    started a copy of (the live row's 2 pages of 16 below its frozen
    prefix), both plus the side window (4 slots x 4 rows)."""
    for impl, pages in (("xla", 4 * 8), ("pallas-decode_interpret", 2)):
        engine = shared(attention_impl=impl)
        m0 = engine.get_metrics()
        engine.generate([GenerationRequest(prompt=list(range(1, 21)),
                                           max_new_tokens=9)])
        m = engine.get_metrics()
        mla = grown(m0["mla"], m["mla"])
        assert mla["decode_context_rows"] == sum(range(21, 29))
        assert (m["decode_steps"] - m0["decode_steps"] == 8
                == 4 * (m["decode_chunks"] - m0["decode_chunks"]))
        assert mla["decode_table_rows"] == 8 * (pages * 16 + 4 * 4)
        # a second request grows both: its 2 decode steps attend to 6 and 7
        # rows; the kernel copies its 1 page at those two steps of the
        # chunk's 4 and none once the row has gone inactive
        engine.generate([GenerationRequest(prompt=list(range(1, 6)),
                                           max_new_tokens=3)])
        m2 = engine.get_metrics()
        assert (m2["mla"]["decode_context_rows"]
                == m["mla"]["decode_context_rows"] + 6 + 7)
        assert (m2["mla"]["decode_table_rows"]
                == m["mla"]["decode_table_rows"]
                + (4 * pages if impl == "xla" else 2) * 16 + 4 * 4 * 4)


def test_the_kernel_body_emits_the_xla_bodys_tokens(shared):
    """The TPU body through the interpreter (latent rows read in place from
    the family's one pool) against ``attention_impl="xla"`` (a layer's pages
    gathered a step), in float32: the same greedy tokens for six requests of
    unequal length over four slots, through admission, slot reuse and chunks
    that cross pages; and the kernel read the live pages' rows. (bfloat16
    rows: ``tests/test_flash_decode.py`` holds the kernel to the XLA body.)"""
    rng = np.random.default_rng(2)
    shapes = ((20, 10), (37, 6), (5, 12), (50, 9), (33, 7), (12, 5))
    prompts = [[int(t) for t in rng.integers(1, 256, n)] for n, _m in shapes]

    def run(impl):
        engine = shared("float32", attention_impl=impl)
        assert (engine.body, engine.attn_impl) == ("hybrid", impl)
        before = engine.get_metrics()["mla"]
        reqs = [GenerationRequest(prompt=p, max_new_tokens=m)
                for p, (_n, m) in zip(prompts, shapes)]
        res = engine.generate(reqs)
        judged(engine, reqs, res)
        return ([r.tokens for r in res],
                grown(before, engine.get_metrics()["mla"]))

    want, read_xla = run("xla")
    got, read_kernel = run("pallas-decode_interpret")
    assert got == want
    assert read_kernel["decode_context_rows"] == read_xla[
        "decode_context_rows"]
    # every page read holds a live row: fewer rows than one more page a
    # (row, step) on top of what was attended to, and far under the table
    assert (read_kernel["decode_context_rows"]
            <= read_kernel["decode_table_rows"]
            < read_xla["decode_table_rows"] // 2)


@pytest.mark.parametrize("length,visited,square", [
    (20, 1 + 2, 4),              # bucket 32: both query blocks live
    (37, 1 + 2 + 3, 16),         # bucket 64: three of four
    (50, 1 + 2 + 3 + 4, 16)])    # bucket 64: all four
def test_prefill_key_block_counters_by_hand(monkeypatch, shared, length,
                                            visited, square):
    """Blocks of 16 for the count: what the kernel would visit for an
    admitted prompt (at or under the diagonal, below its length) over the
    blocks of its bucket's whole square, per paged layer; the counters add
    from one admission to the next."""
    from distributed_inference_engine_tpu.ops import mla

    monkeypatch.setattr(mla, "Q_BLOCK", 16)
    monkeypatch.setattr(mla, "K_BLOCK", 16)
    engine = shared(attention_impl="xla")
    before = engine.get_metrics()["mla"]
    engine.generate([GenerationRequest(
        prompt=list(range(1, length + 1)), max_new_tokens=2)])
    got = grown(before, engine.get_metrics()["mla"])
    assert (got["prefill_key_blocks_visited"],
            got["prefill_key_blocks_bucket"]) == (visited, square)


def test_a_tree_without_latent_layers_reports_no_prefill_blocks():
    from distributed_inference_engine_tpu.models.llama import llama_spec

    eng = ContinuousEngine(
        llama_spec("llama-tiny", max_seq_len=64), config=EngineConfig(
            max_slots=2, page_size=16, num_pages=16, max_seq_len=64))
    assert not any(k.startswith("prefill_key_blocks")
                   for k in eng.get_metrics().get("mla", {}))


def test_the_same_prompt_twice_is_no_prefix_hit_and_the_same_tokens(shared):
    engine = shared(prefix_cache=True)
    prompt = [int(t) for t in np.random.default_rng(2).integers(1, 256, 40)]
    first = engine.generate([GenerationRequest(prompt=list(prompt),
                                               max_new_tokens=8)])
    second = engine.generate([GenerationRequest(prompt=list(prompt),
                                                max_new_tokens=8)])
    assert first[0].tokens == second[0].tokens
    m = engine.get_metrics()
    assert m["prefix_hit_admissions"] == 0 and m["kv"]["prefix_hit_pages"] == 0


def test_a_preempted_sequence_is_re_prefilled_and_resumes(shared):
    """A pool too small for both requests at full length: with no recurrent
    layer the pre-emption is still a re-prefill (the host tier is refused
    and no prefill continues from pages); the result equals the same
    request served alone. In float32, as the Ling test."""
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, 256, n)] for n in (30, 28)]

    def make():
        return [GenerationRequest(prompt=list(p), max_new_tokens=40)
                for p in prompts]

    alone = [shared("float32", attention_impl="xla").generate([r])[0]
             for r in make()]
    tight = shared("float32", num_pages=7)
    m0 = tight.get_metrics()
    together = tight.generate(make())
    m = grown(m0, tight.get_metrics())
    assert m["reprefill_preemptions"] >= 1 and m["capacity_finishes"] == 0
    for a, b in zip(alone, together):
        assert a.tokens == b.tokens and len(b.tokens) == 40
        assert b.finish_reason == a.finish_reason


@pytest.mark.parametrize("pages", [32, 7])
def test_streamed_matches_unstreamed(shared, pages):
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, 256, n)] for n in (30, 28)]
    eng = (shared("float32", num_pages=7) if pages == 7
           else shared("float32", attention_impl="xla"))

    def run(stream):
        m0 = eng.get_metrics()
        frames = [[] for _ in prompts]
        for i, p in enumerate(prompts):
            eng.submit(GenerationRequest(prompt=list(p), max_new_tokens=40,
                                         request_id=f"x{i}"),
                       on_tokens=frames[i].append if stream else None)
        res = {r.request_id: r for r in eng.run_until_idle()}
        return (grown(m0, eng.get_metrics()),
                [res[f"x{i}"] for i in range(len(prompts))], frames)

    m, got, frames = run(True)
    _plain, want, _none = run(False)
    assert len(got) == len(want) == 2
    for g, w, fr in zip(got, want, frames):
        assert (g.tokens, g.logprobs, g.finish_reason) == (
            w.tokens, w.logprobs, w.finish_reason)
        assert [t for f in fr for t in f] == g.tokens and len(g.tokens) == 40
    assert (m["reprefill_preemptions"] >= 1) == (pages == 7)
    assert (m["emit_carried_chunks"] + m["emit_flushed_chunks"]
            == m["decode_chunks"])


# ------------------------------------------------------- what it cannot do


def test_the_body_is_chosen_from_the_spec():
    spec = spec_for_architecture("xing", size="xing4.0-pp1",
                                 max_seq_len=8704)
    # latent rows held at whole lane tiles: the kernel under exactly the
    # conditions a K|V spec takes it
    assert spec.cache_row_width == 640 and spec.kv_row_lanes == 0
    assert resolve_decode_body("auto", "tpu", spec) == ("hybrid",
                                                        "pallas-decode")
    assert resolve_decode_body("auto", "cpu", spec) == ("hybrid", "xla")
    assert resolve_decode_body("auto", "tpu", spec, sharded=True) == (
        "hybrid", "xla")
    assert resolve_decode_body("xla", "tpu", spec) == ("hybrid", "xla")
    assert resolve_decode_body("pallas-decode_interpret", "cpu", spec) == (
        "hybrid", "pallas-decode_interpret")
    assert spec.max_seq_len == 8704 and not spec.recurrent
    with pytest.raises(ValueError, match="unknown xing size"):
        spec_for_architecture("xing", size="xing-9b")


@pytest.mark.parametrize("kw", [
    {"kv_offload": True}, {"prefill_chunk": 32},
    {"kv_offload": True, "prefill_chunk": 32},
    {"kv_offload": True, "attention_impl": "pallas-decode_interpret"}])
def test_engine_options_a_per_layer_spec_cannot_honour_raise(kw):
    with pytest.raises(ValueError, match="per-layer") as e:
        tiny_engine(**kw)
    assert "recurrent" not in str(e.value)


def test_sharding_an_artifact_and_a_quantized_tree_raise():
    cfg = EngineConfig(max_slots=2, max_seq_len=64, page_size=16,
                       num_pages=8)
    for kw in ({"shard_fn": lambda p: p}, {"kv_sharding": object()},
               {"sp_mesh": object()}, {"artifact_path": "/nonexistent"}):
        with pytest.raises(ValueError, match="per-layer"):
            ContinuousEngine(tiny_spec(), config=cfg, **kw)
    from distributed_inference_engine_tpu.ops.quant import quantize_weight

    params = xing.init_params(tiny_spec(), jax.random.key(7))
    bad = dict(params, lm_head=quantize_weight(
        params["lm_head"].astype(jnp.float32), reduce_axes=(0,)))
    with pytest.raises(ValueError, match="unquantized"):
        ContinuousEngine(tiny_spec(), params=bad, config=cfg)


@pytest.mark.parametrize("change,match", [
    ({"quantized": True}, "quantized"),
    ({"path": "/tmp"}, "checkpoint"),
    ({"metadata": {"tp": 2}}, "mesh"),
    ({"metadata": {"sp": 2}}, "mesh"),
    ({"metadata": {"speculative": 2}}, "speculative"),
    ({"metadata": {"role": "prefill"}}, "prefill"),
    ({"metadata": {"artifact": "/tmp/a"}}, "artifact"),
    ({"metadata": {"continuous": 0}}, "static engine"),
    ({"metadata": {"kv_offload": True}}, "kv_offload"),
    ({"metadata": {"prefill_chunk": 32}}, "prefill_chunk"),
])
def test_deploys_this_architecture_cannot_serve_raise(change, match):
    meta = {"size": "xing-tiny", "continuous": 1, "page_size": 16,
            "num_pages": 8}
    meta.update(change.get("metadata", {}))
    cfg = ModelConfig(name="m", architecture="xing", max_batch_size=2,
                      max_seq_len=64, metadata=meta,
                      **{k: v for k, v in change.items() if k != "metadata"})
    with pytest.raises(ValueError, match=match):
        engine_from_config(cfg)


def test_calls_a_per_layer_spec_cannot_answer_raise(shared):
    engine = shared(attention_impl="xla")
    with pytest.raises(ValueError,
                       match="continues from cached pages") as e:
        engine.kv_export([1, 2, 3])
    assert "recurrent" not in str(e.value)
    with pytest.raises(ValueError, match="per-layer"):
        engine.submit_prefilled(GenerationRequest(prompt=[1, 2]), None)
    from distributed_inference_engine_tpu.engine.kv_fabric import (
        FabricRejected,
    )

    with pytest.raises(FabricRejected):
        engine.kv_import({"pages": []})
    with pytest.raises(ValueError, match="ONE latent pool"):
        PagedKVCache(tiny_spec(), max_slots=2, page_size=16, num_pages=8,
                     offload=object())


def test_the_worker_seeds_the_tree_from_metadata():
    def build(seed):
        return engine_from_config(ModelConfig(
            name="m", architecture="xing", max_batch_size=2, max_seq_len=64,
            dtype="bfloat16", metadata={
                "size": "xing-tiny", "continuous": 1, "page_size": 16,
                "num_pages": 8, "seed": seed, "admission_max_rows": 1}))

    a, b, c = build(5), build(5), build(6)
    assert a.config.admission_max_rows == 1
    la, lb, lc = (e.params["layers"][1]["hc_mlp"]["phi"] for e in (a, b, c))
    assert bool((la == lb).all()) and not bool((la == lc).all())
    assert la.dtype == jnp.float32
    assert a.params["layers"][1]["w_gate_up"].dtype == jnp.bfloat16
    assert (np.asarray(a.params["layers"][1]["router_bias"])
            == np.asarray(c.params["layers"][1]["router_bias"])).all()
