"""The Gated-DeltaNet family (``models/olmo_hybrid.py``) through
``ContinuousEngine`` and the worker's factory on the CPU: slots, K|V pages
read in place and the per-slot state under real admission, pre-emption by
re-prefill, the spans and counters, and every combination a per-layer spec
cannot serve, which must raise as for the other per-layer families.
``tests/test_olmo_hybrid.py`` holds the logits comparisons."""

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
    engine_from_config, olmo_hybrid, spec_for_architecture,
)
from distributed_inference_engine_tpu.ops import kda  # noqa: E402
from perfbench.lib import families  # noqa: E402
from conftest import grown  # noqa: E402  (this directory)

with open(os.path.join(ROOT, "perfbench", "rehearse", "olmo-tiny.json")) as _f:
    CFG = json.load(_f)
REF = families.reference(CFG)


def tiny_spec(**kw):
    return olmo_hybrid.olmo_hybrid_spec("olmo-hybrid-tiny", max_seq_len=128,
                                        **kw)


def tiny_engine(dtype="bfloat16", **cfg_kw):
    base = dict(max_slots=4, max_seq_len=128, page_size=16, num_pages=32,
                prefill_buckets=[32, 64], decode_steps_per_call=4)
    base.update(cfg_kw)
    return ContinuousEngine(tiny_spec(dtype=dtype),
                            config=EngineConfig(**base), seed=11)


def judged(engine, requests, results):
    """Every served token the reference's argmax, or within 8 % of
    max|logit| of it: the bound ``tests/test_olmo_hybrid.py`` holds the
    bfloat16 logits to."""
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
    freed slot whose state was zeroed), contexts that cross pages of 16 and
    several chunks of 4, on the XLA body and on the interpreted kernel."""
    engine = tiny_engine(max_slots=8, num_pages=64, prefix_cache=True,
                         attention_impl=impl)
    assert (engine.body, engine.attn_impl) == ("hybrid", impl)
    rng = np.random.default_rng(1)
    reqs = [GenerationRequest(
        prompt=[int(t) for t in rng.integers(1, 256, n)], max_new_tokens=m)
        for n, m in ((20, 10), (37, 22), (5, 12), (50, 9), (33, 7), (12, 5),
                     (61, 14), (9, 30), (28, 6), (44, 11))]
    results = engine.generate(reqs)
    judged(engine, reqs, results)
    m = engine.get_metrics()
    assert m["prefix_disabled_per_layer"] == 1
    assert m["prefix_hit_admissions"] == 0 and m["kv"]["prefix_queries"] == 0
    assert m["decode_steps"] >= 29 and m["decode_chunks"] >= 8
    assert m["moe"]["assignments_total"] == 0
    kv = m["kv"]
    assert (kv["paged_layers"], kv["state_layers"]) == (2, 6)
    assert kv["latent_bytes_per_token"] == 2 * 256 * 2
    assert kv["state_bytes"] == 8 * olmo_hybrid.state_bytes_per_slot(
        engine.spec)
    assert kv["hbm_bytes"] == 2 * 64 * 16 * 256 * 2
    # every slot was freed: every state is zero again
    assert all(float(jnp.abs(a).max()) == 0 for a in engine.kv.state.values())


def test_counters_follow_lengths_and_steps(shared):
    """One request alone: a prompt of 20 and 9 tokens. The first comes from
    the prefill; the 8 decode steps attend to 21 ... 28 rows each (cached
    and the chunk's own) and move one state each. What the attention READ
    is the program's own count: the XLA body the whole table (4 slots x 8
    pages x 16) every step, the kernel the pages it started a copy of (the
    live row's 2 pages of 16 below its frozen prefix, counted in the
    kernel), both plus the side window (4 slots x 4 rows)."""
    for impl, pages in (("xla", 4 * 8), ("pallas-decode_interpret", 2)):
        engine = shared(attention_impl=impl)
        m0 = engine.get_metrics()
        engine.generate([GenerationRequest(prompt=list(range(1, 21)),
                                           max_new_tokens=9)])
        m = engine.get_metrics()
        attn = grown(m0["attn"], m["attn"])
        assert m["decode_steps"] - m0["decode_steps"] == 8
        assert attn["full_context_rows"] == sum(range(21, 29))
        assert attn["full_table_rows"] == 8 * (pages * 16 + 4 * 4)
        assert grown(m0["state"], m["state"]) == {"rows_updated": 8}
        assert m["mla"] == {"decode_context_rows": 0, "decode_table_rows": 0}


@pytest.mark.parametrize("length,visited,square", [
    (20, 1 + 2, 4),              # bucket 32: both query blocks live
    (37, 1 + 2 + 3, 16)])        # bucket 64: three of four
def test_prefill_key_block_counters_by_hand(monkeypatch, shared, length,
                                            visited, square):
    """Blocks of 16 for the count: what the prefill kernel would visit for
    an admitted prompt in a full-attention layer (at or under the diagonal,
    below its length) over the blocks of its bucket's whole square; a spec
    without sliding layers reports no window pair."""
    from distributed_inference_engine_tpu.ops import flash_prefill

    monkeypatch.setattr(flash_prefill, "Q_BLOCK", 16)
    monkeypatch.setattr(flash_prefill, "K_BLOCK", 16)
    engine = shared(attention_impl="xla")
    before = engine.get_metrics()["attn"]
    engine.generate([GenerationRequest(prompt=list(range(1, length + 1)),
                                       max_new_tokens=2)])
    got = grown(before, engine.get_metrics()["attn"])
    assert (got["full_prefill_key_blocks_visited"],
            got["full_prefill_key_blocks_bucket"]) == (visited, square)
    assert not any(k.startswith("window") for k in got)


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
    for scope in ("attn.gdn.step", "recurrence", "attn.full", "flash_decode",
                  "attn.kv_update", "attn.kv_gather", "state.update",
                  "mlp.dense", "head.unembed", "sample"):
        assert re.search(rf'["/]{re.escape(scope)}/', dec), scope
    pre = eng._prefill_pages.lower(
        eng.params, jnp.zeros((1, 32), jnp.int32), jnp.ones((1,), jnp.int32),
        *kv.pools, jnp.zeros((1, kv.max_pages_per_seq), jnp.int32),
        SamplingParams(jnp.zeros((1,)), jnp.zeros((1,), jnp.int32),
                       jnp.ones((1,)), jnp.zeros((1,))), jax.random.key(0),
        jnp.zeros((1,), jnp.int32)).as_text(debug_info=True)
    for scope in ("attn.gdn.prefill", "recurrence", "attn.full",
                  "attn.kv_update", "state.update", "mlp.dense",
                  "head.unembed", "sample"):
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
    """A pool too small for both requests at full length: the victim is
    re-queued as prompt + tokens and re-prefilled (its state rebuilt from
    nothing, the host tier refused); the result equals the same request
    served alone. In float32, as the Ling test."""
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, 256, n)] for n in (30, 28)]

    def make():
        return [GenerationRequest(prompt=list(p), max_new_tokens=40)
                for p in prompts]

    alone = [shared("float32").generate([r])[0] for r in make()]
    tight = shared("float32", num_pages=7)
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
        results = engine.generate([
            GenerationRequest(prompt=list(p), max_new_tokens=n)
            for p, n in zip(prompts, new)])
        return [r.tokens for r in results], engine.get_metrics()

    # an engine each: the body is picked when the programs are traced
    tokens_xla, m_xla = serve(tiny_engine("float32", num_pages=9))
    monkeypatch.setattr(kda, "step_impl", lambda: "inplace_interpret")
    tokens_kernel, m_kernel = serve(tiny_engine("float32", num_pages=9))
    assert tokens_kernel == tokens_xla
    assert [len(t) for t in tokens_xla] == list(new)
    assert m_xla["state"]["step_body"] == "xla"
    assert m_kernel["state"]["step_body"] == "inplace_interpret"
    for m in (m_xla, m_kernel):
        assert m["reprefill_preemptions"] >= 1
        assert m["state"]["rows_updated"] == m_xla["state"]["rows_updated"] > 0


def test_streamed_matches_unstreamed(shared):
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, 256, n)] for n in (30, 28)]
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
    over a mesh; latent rows stay on XLA; rows of no whole tiles refuse the
    kernel by name."""
    spec = spec_for_architecture("olmo_hybrid", size="olmo-hybrid-7b-pp2",
                                 max_seq_len=6144)
    assert spec.max_seq_len == 6144 and spec.recurrent
    assert spec.kv_row_lanes == 3840
    assert resolve_decode_body("auto", "tpu", spec) == ("hybrid",
                                                        "pallas-decode")
    assert resolve_decode_body("auto", "cpu", spec) == ("hybrid", "xla")
    assert resolve_decode_body("auto", "tpu", spec, sharded=True) == (
        "hybrid", "xla")
    assert resolve_decode_body("xla", "tpu", spec) == ("hybrid", "xla")
    narrow = tiny_spec(n_heads=2, n_kv_heads=2, d_model=64)
    assert narrow.kv_row_lanes == 64
    assert resolve_decode_body("auto", "tpu", narrow) == ("hybrid", "xla")
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        resolve_decode_body("pallas-decode", "tpu", narrow)
    with pytest.raises(ValueError, match="unknown olmo_hybrid size"):
        spec_for_architecture("olmo_hybrid", size="olmo-9b")


@pytest.mark.parametrize("kw", [{"kv_offload": True}, {"prefill_chunk": 32}])
def test_engine_options_a_per_layer_spec_cannot_honour_raise(kw):
    with pytest.raises(ValueError, match="per-layer"):
        tiny_engine(**kw)


def test_sharding_an_artifact_and_a_quantized_tree_raise():
    cfg = EngineConfig(max_slots=2, max_seq_len=64, page_size=16,
                       num_pages=8)
    for kw in ({"shard_fn": lambda p: p}, {"kv_sharding": object()},
               {"sp_mesh": object()}, {"artifact_path": "/nonexistent"}):
        with pytest.raises(ValueError, match="per-layer"):
            ContinuousEngine(tiny_spec(), config=cfg, **kw)
    from distributed_inference_engine_tpu.ops.quant import quantize_weight

    params = olmo_hybrid.init_params(tiny_spec(), jax.random.key(7))
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
    meta = {"size": "olmo-hybrid-tiny", "continuous": 1, "page_size": 16,
            "num_pages": 8}
    meta.update(change.get("metadata", {}))
    cfg = ModelConfig(name="m", architecture="olmo_hybrid", max_batch_size=2,
                      max_seq_len=64, metadata=meta,
                      **{k: v for k, v in change.items() if k != "metadata"})
    with pytest.raises(ValueError, match=match):
        engine_from_config(cfg)


def test_calls_a_per_layer_spec_cannot_answer_raise(shared):
    engine = shared(attention_impl="xla")
    with pytest.raises(ValueError, match="without the recurrent state"):
        engine.kv_export([1, 2, 3])
    with pytest.raises(ValueError, match="per-layer"):
        engine.submit_prefilled(GenerationRequest(prompt=[1, 2]), None)
    from distributed_inference_engine_tpu.engine.kv_fabric import (
        FabricRejected,
    )

    with pytest.raises(FabricRejected):
        engine.kv_import({"pages": []})
    with pytest.raises(ValueError, match="ONE K|V pool"):
        PagedKVCache(tiny_spec(), max_slots=2, page_size=16, num_pages=8,
                     offload=object())


def test_the_worker_seeds_the_tree_from_metadata():
    def build(seed):
        return engine_from_config(ModelConfig(
            name="m", architecture="olmo_hybrid", max_batch_size=2,
            max_seq_len=64, dtype="bfloat16", metadata={
                "size": "olmo-hybrid-tiny", "continuous": 1, "page_size": 16,
                "num_pages": 8, "seed": seed, "admission_max_rows": 1}))

    a, b, c = build(5), build(5), build(6)
    assert a.config.admission_max_rows == 1
    la, lb, lc = (e.params["period"][1]["w_gate_up"] for e in (a, b, c))
    assert bool((la == lb).all()) and not bool((la == lc).all())
    assert la.dtype == jnp.bfloat16
    assert a.params["period"][0]["dt_bias"].dtype == jnp.float32
