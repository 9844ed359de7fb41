"""The hybrid family (``models/ling.py``) through ``ContinuousEngine`` and the
worker's factory on the CPU: slots, pages and state under real admission,
pre-emption by re-prefill, and every combination it cannot serve yet, which
must raise at load or at the call. ``tests/test_ling.py`` holds the layer
and logits comparisons against the reference."""

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
    engine_from_config, ling, mistral_spec, spec_for_architecture,
)
from distributed_inference_engine_tpu.ops import kda  # noqa: E402
from perfbench.lib import families  # noqa: E402
from conftest import grown  # noqa: E402  (this directory)

with open(os.path.join(ROOT, "perfbench", "rehearse", "ling-tiny.json")) as _f:
    CFG = json.load(_f)
REF = families.reference(CFG)


def tiny_spec(**kw):
    return ling.ling_spec("ling-tiny", max_seq_len=128, **kw)


@pytest.fixture(scope="module")
def served_bf16():
    return ling.init_params(tiny_spec(), jax.random.key(7))


# ---------------------------------------------------------- engine, served


def tiny_engine(dtype="bfloat16", **cfg_kw):
    base = dict(max_slots=4, max_seq_len=128, page_size=16, num_pages=32,
                prefill_buckets=[32, 64], decode_steps_per_call=4)
    base.update(cfg_kw)
    return ContinuousEngine(tiny_spec(dtype=dtype),
                            config=EngineConfig(**base), seed=11)


def judged(engine, requests, results):
    """Every served token the reference's argmax, or within 6 % of
    max|logit| of it: the bound ``tests/test_ling.py`` holds the bfloat16
    logits to (activations re-rounded through six layers at width 64, on
    flat random-init logits)."""
    for req, res in zip(requests, results):
        assert len(res.tokens) == req.max_new_tokens
        lg = np.asarray(REF.logits(
            CFG, engine.params, jnp.asarray(req.prompt + res.tokens)))
        for i, tok in enumerate(res.tokens):
            row = lg[len(req.prompt) - 1 + i]
            assert row.max() - row[tok] <= 0.06 * np.abs(row).max(), (i, tok)


def test_engine_serves_the_hybrid_through_slots_pages_and_state(shared):
    """Six requests of unequal length over four slots: batched admission at
    padded buckets, deferred first tokens, slot reuse, counters."""
    engine = shared(prefix_cache=True)
    m0 = engine.get_metrics()
    rng = np.random.default_rng(1)
    reqs = [GenerationRequest(
        prompt=[int(t) for t in rng.integers(1, 256, n)], max_new_tokens=m)
        for n, m in ((20, 10), (37, 6), (5, 12), (50, 9), (33, 7), (12, 5))]
    results = engine.generate(reqs)
    judged(engine, reqs, results)
    m = engine.get_metrics()
    assert m["prefix_disabled_per_layer"] == 1
    assert m["prefix_hit_admissions"] == 0 and m["kv"]["prefix_queries"] == 0
    assert m["attn_impl"] == "xla"
    steps = m["decode_steps"] - m0["decode_steps"]
    assert steps >= 12 and m["decode_chunks"] - m0["decode_chunks"] >= 3
    moe = grown(m0["moe"], m["moe"])
    assert 0 < moe["assignments_held"] < moe["assignments_total"]
    assert 0 < moe["experts_touched"] <= steps * 5 * 4
    kv = m["kv"]
    assert (kv["paged_layers"], kv["state_layers"]) == (2, 4)
    assert kv["latent_bytes_per_token"] == 2 * 128 * 2    # 32 + 8: one tile
    assert kv["state_bytes"] == 4 * ling.state_bytes_per_slot(tiny_spec())
    assert kv["hbm_bytes"] == 2 * 32 * 16 * 128 * 2
    # every slot is free again, and free means zero
    assert all(float(jnp.abs(a).max()) == 0 for a in engine.kv.state.values())


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


def test_a_preempted_sequence_resumes_where_it_stopped(shared):
    """A pool too small for both requests at full length: the one that
    cannot grow is re-queued with its tokens so far and re-prefilled (its
    state rebuilt from the tokens, never resumed on a zero state); the
    result equals the same request served alone. In float32: the chunked
    prefill and the one-step form round differently, and in bfloat16 a
    flat random-init logit row may flip its argmax on that."""
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, 256, n)] for n in (30, 28)]

    def make():
        return [GenerationRequest(prompt=list(p), max_new_tokens=40)
                for p in prompts]

    alone = [shared("float32", attention_impl="xla").generate([r])[0]
             for r in make()]
    tight = shared("float32", num_pages=7)  # 2 x 2 pages at admission, 7 all
    m0 = tight.get_metrics()
    together = tight.generate(make())
    m = grown(m0, tight.get_metrics())
    assert m["reprefill_preemptions"] >= 1 and m["capacity_finishes"] == 0
    for a, b in zip(alone, together):
        assert a.tokens == b.tokens and len(b.tokens) == 40
        assert b.finish_reason == a.finish_reason


def test_a_slot_handed_on_leaks_no_state_to_its_successor(shared):
    """One slot, three requests that end by ``max_new_tokens``: each
    successor is prefilled into its predecessor's slot, pages and
    recurrent state row BEHIND the chunk the predecessor ends in
    (``admissions_ahead``). Device order zeroes the row after that chunk
    and the prefill overwrites it: every successor's tokens are the
    reference's, and those of the same request served alone (float32)."""
    rng = np.random.default_rng(5)
    shapes = ((30, 9), (21, 11), (44, 6))
    prompts = [[int(t) for t in rng.integers(1, 256, n)] for n, _ in shapes]

    def make():
        return [GenerationRequest(prompt=list(p), max_new_tokens=m)
                for p, (_, m) in zip(prompts, shapes)]

    engine = shared("float32", max_slots=1)
    m0 = engine.get_metrics()
    reqs = make()
    together = engine.generate(reqs)
    m = grown(m0, engine.get_metrics())
    assert m["admissions"] == 3 and m["admissions_ahead"] == 2
    assert m["empty_slot_dispatches"] == 0 and m["finishes_learned_late"] == 0
    judged(engine, reqs, together)
    for req, res in zip(make(), together):
        assert engine.generate([req])[0].tokens == res.tokens
    # every slot is free again, and free means zero
    assert all(float(jnp.abs(a).max()) == 0
               for k, a in engine.kv.state.items() if k != "window_table")


def test_both_state_step_bodies_serve_the_same_tokens(monkeypatch, shared):
    """The in-place kernel (through the interpreter) against the XLA body
    the CPU picks, under everything that touches a slot's state: five
    requests of unequal length over 4 slots and a pool too small for them,
    so slots finish, are zeroed (``zero_state_slot``) and taken again, and
    one sequence is pre-empted and re-prefilled. Greedy tokens equal, and
    the engine says which body ran."""
    rng = np.random.default_rng(9)
    prompts = [[int(t) for t in rng.integers(1, 256, n)]
               for n in (30, 28, 9, 17, 24)]
    new = (40, 36, 7, 12, 21)

    def serve(engine):
        m0 = engine.get_metrics()
        results = engine.generate([
            GenerationRequest(prompt=list(p), max_new_tokens=n)
            for p, n in zip(prompts, new)])
        m = engine.get_metrics()
        return [r.tokens for r in results], m["state"]["step_body"], (
            m["reprefill_preemptions"] - m0["reprefill_preemptions"],
            m["state"]["rows_updated"] - m0["state"]["rows_updated"])

    tokens_xla, body_xla, grew_xla = serve(
        shared("float32", num_pages=9, attention_impl="xla"))
    # its own engine: the body is picked when the programs are traced
    monkeypatch.setattr(kda, "step_impl", lambda: "inplace_interpret")
    tokens_kernel, body_kernel, grew_kernel = serve(
        tiny_engine("float32", num_pages=9))
    assert tokens_kernel == tokens_xla
    assert [len(t) for t in tokens_xla] == list(new)
    assert (body_xla, body_kernel) == ("xla", "inplace_interpret")
    assert grew_kernel[0] >= 1 and grew_xla[0] >= 1
    assert grew_kernel[1] == grew_xla[1] > 0


@pytest.mark.parametrize("pages", [32, 9])
def test_the_latent_kernel_body_emits_the_xla_bodys_tokens(shared, pages):
    """The MLA layers' rows read in place from the pool by the interpreted
    kernel against ``attention_impl="xla"`` (a layer's pages gathered a
    step): the same greedy tokens in float32 for five requests over four
    slots, with 9 pages through a re-prefilled pre-emption too; attended
    rows equal, and the kernel's own count of what it read stays between
    them and half of what the XLA body gathered."""
    rng = np.random.default_rng(4)
    prompts = [[int(t) for t in rng.integers(1, 256, n)]
               for n in (30, 28, 9, 17, 24)]
    new = (40, 36, 7, 12, 21)

    def serve(impl):
        engine = (shared("float32", attention_impl=impl) if pages == 32
                  else shared("float32", num_pages=pages,
                              attention_impl=impl))
        assert (engine.body, engine.attn_impl) == ("hybrid", impl)
        m0 = engine.get_metrics()
        results = engine.generate([
            GenerationRequest(prompt=list(p), max_new_tokens=n)
            for p, n in zip(prompts, new)])
        m = engine.get_metrics()
        return [r.tokens for r in results], {
            "mla": grown(m0["mla"], m["mla"]),
            "decode_steps": m["decode_steps"] - m0["decode_steps"]}

    tokens_xla, m_xla = serve("xla")
    tokens_kernel, m_kernel = serve("pallas-decode_interpret")
    assert tokens_kernel == tokens_xla
    assert [len(t) for t in tokens_xla] == list(new)
    read_xla, read_kernel = m_xla["mla"], m_kernel["mla"]
    assert (read_kernel["decode_context_rows"]
            == read_xla["decode_context_rows"] > 0)
    assert (read_kernel["decode_context_rows"]
            <= read_kernel["decode_table_rows"]
            < read_xla["decode_table_rows"] // 2)
    # a layer's whole gathered table (4 slots x 8 pages x 16) every step
    assert read_xla["decode_table_rows"] >= m_xla["decode_steps"] * 4 * 8 * 16


@pytest.mark.parametrize("pages", [32, 7])
def test_streamed_hybrid_matches_unstreamed(shared, pages):
    """The hybrid family streamed: a chunk's tokens go out under the next
    dispatch; tokens, logprobs and reasons are those of the same engine
    unstreamed, each stream splices to its result. With 7 pages one
    sequence is pre-empted and re-prefilled: what is carried for it is
    flushed before it is re-queued, and its stream goes on after."""
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, 256, n)] for n in (30, 28)]

    eng = (shared("float32", num_pages=7) if pages == 7
           else shared("float32", attention_impl="xla"))

    def run(stream):
        m0 = eng.get_metrics()
        frames = [[] for _ in prompts]
        for i, p in enumerate(prompts):
            eng.submit(GenerationRequest(prompt=list(p), max_new_tokens=40,
                                         request_id=f"h{i}"),
                       on_tokens=frames[i].append if stream else None)
        res = {r.request_id: r for r in eng.run_until_idle()}
        return (grown(m0, eng.get_metrics()),
                [res[f"h{i}"] for i in range(len(prompts))], frames)

    m, got, frames = run(True)
    _plain, want, _none = run(False)
    for g, w, fr in zip(got, want, frames):
        assert (g.tokens, g.logprobs, g.finish_reason) == (
            w.tokens, w.logprobs, w.finish_reason)
        assert [t for f in fr for t in f] == g.tokens and len(g.tokens) == 40
    assert (m["reprefill_preemptions"] >= 1) == (pages == 7)
    assert m["emit_carried_chunks"] >= 1
    assert (m["emit_carried_chunks"] + m["emit_flushed_chunks"]
            == m["decode_chunks"])


# ------------------------------------------------------- what it cannot do


def test_existing_families_keep_their_specs():
    spec = mistral_spec("mistral-tiny")
    assert spec.layer_kinds == () and not spec.recurrent
    assert spec.layer_plan == tuple(("attn", "dense", i) for i in range(4))
    assert spec.cache_row_width == spec.n_kv_heads * spec.head_dim
    assert spec.paged_layers == spec.n_layers and spec.state_layers == 0
    cut = spec_for_architecture("ling", size="ling-3.0-flash-ep4",
                                max_seq_len=3072)
    assert cut.layer_ids == [0, 2, 3, 4, 5, 6, 7]
    assert cut.layer_kinds == ["kda"] * 4 + ["mla"] + ["kda"] * 2
    # 512 | 64 latent values held at whole 128-lane tiles
    assert cut.experts_held == (0, 128) and cut.cache_row_width == 640
    assert resolve_decode_body("auto", "tpu", cut) == ("hybrid",
                                                       "pallas-decode")
    assert resolve_decode_body("auto", "cpu", cut) == ("hybrid", "xla")
    assert resolve_decode_body("xla", "tpu", cut) == ("hybrid", "xla")
    assert hash(cut) == hash(type(cut).from_dict(
        json.loads(json.dumps(cut.to_dict()))))


@pytest.mark.parametrize("kw", [
    {"kv_offload": True}, {"prefill_chunk": 32},
    {"kv_offload": True, "prefill_chunk": 32},
    {"kv_offload": True, "attention_impl": "pallas-decode_interpret"}])
def test_engine_options_a_recurrent_spec_cannot_honour_raise(kw):
    with pytest.raises(ValueError, match="hybrid"):
        tiny_engine(**kw)


def test_sharding_an_artifact_and_a_quantized_tree_raise(served_bf16):
    cfg = EngineConfig(max_slots=2, max_seq_len=64, page_size=16,
                       num_pages=8)
    for kw in ({"shard_fn": lambda p: p}, {"kv_sharding": object()},
               {"sp_mesh": object()}, {"artifact_path": "/nonexistent"}):
        with pytest.raises(ValueError, match="hybrid"):
            ContinuousEngine(tiny_spec(), config=cfg, **kw)
    from distributed_inference_engine_tpu.ops.quant import quantize_weight

    bad = dict(served_bf16, lm_head=quantize_weight(
        served_bf16["lm_head"].astype(jnp.float32), reduce_axes=(0,)))
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
])
def test_deploys_a_hybrid_architecture_cannot_serve_raise(change, match):
    meta = {"size": "ling-tiny", "continuous": 1, "page_size": 16,
            "num_pages": 8}
    meta.update(change.get("metadata", {}))
    cfg = ModelConfig(name="m", architecture="ling", max_batch_size=2,
                      max_seq_len=64, metadata=meta,
                      **{k: v for k, v in change.items() if k != "metadata"})
    with pytest.raises(ValueError, match=match):
        engine_from_config(cfg)


def test_calls_a_recurrent_spec_cannot_answer_raise(shared):
    engine = shared(prefix_cache=True)
    with pytest.raises(ValueError, match="recurrent state"):
        engine.kv_export([1, 2, 3])
    with pytest.raises(ValueError, match="hybrid"):
        engine.submit_prefilled(GenerationRequest(prompt=[1, 2]), None)
    from distributed_inference_engine_tpu.engine.kv_fabric import (
        FabricRejected,
    )

    with pytest.raises(FabricRejected):
        engine.kv_import({"pages": []})
    with pytest.raises(ValueError, match="recurrent"):
        PagedKVCache(tiny_spec(), max_slots=2, page_size=16, num_pages=8,
                     offload=object())


def test_the_worker_seeds_the_tree_from_metadata():
    def build(seed):
        return engine_from_config(ModelConfig(
            name="m", architecture="ling", max_batch_size=2, max_seq_len=64,
            dtype="bfloat16", metadata={
                "size": "ling-tiny", "continuous": 1, "page_size": 16,
                "num_pages": 8, "seed": seed, "admission_max_rows": 1}))

    a, b, c = build(5), build(5), build(6)
    assert a.config.admission_max_rows == 1
    la, lb, lc = (e.params["layers"][1]["w_router"] for e in (a, b, c))
    assert bool((la == lb).all()) and not bool((la == lc).all())
    assert la.dtype == jnp.float32
    assert a.params["layers"][1]["w_gate_up"].dtype == jnp.bfloat16
    # the expert bias: uneven across groups, and the same for every seed
    bias = np.asarray(a.params["layers"][1]["router_bias"]).reshape(4, 4)
    assert bias.mean(axis=1).std() > 0 and bias.std(axis=1).min() > 0
    assert (bias.reshape(-1)
            == np.asarray(c.params["layers"][1]["router_bias"])).all()
