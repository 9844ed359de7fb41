"""The learned-sparse-attention family (``models/keye.py``) through
``ContinuousEngine``, the worker's factory and a real coordinator and worker
on the CPU: slots, the index keys' pool on the K|V pages' table under real
admission, pre-emption by re-prefill, slot reuse after a long request (stale
index keys must not be selectable), the spans and counters, and every
combination a per-layer spec cannot serve, which must raise as for the other
per-layer families. ``tests/test_keye.py`` holds the logits comparisons."""

import asyncio
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

from distributed_inference_engine_tpu.api.coordinator import (  # noqa: E402
    Coordinator, CoordinatorConfig,
)
from distributed_inference_engine_tpu.cluster.worker import (  # noqa: E402
    WorkerServer,
)
from distributed_inference_engine_tpu.config import (  # noqa: E402
    EngineConfig, ModelConfig, ServerConfig,
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
    engine_from_config, keye, spec_for_architecture,
)
from perfbench.lib import families  # noqa: E402
from conftest import grown  # noqa: E402  (this directory)

with open(os.path.join(ROOT, "perfbench", "rehearse",
                       "keye-tiny.json")) as _f:
    CFG = json.load(_f)
REF = families.reference(CFG)
TOPK, PAGE = 16, 8
KERNELS = "pallas-decode_interpret"     # the TPU's decode body, interpreted


def tiny_spec(**kw):
    return keye.keye_spec("keye-tiny", max_seq_len=256, **kw)


def tiny_engine(dtype="bfloat16", **cfg_kw):
    base = dict(max_slots=4, max_seq_len=256, page_size=PAGE, num_pages=128,
                prefill_buckets=[32, 64, 128], decode_steps_per_call=4)
    base.update(cfg_kw, kv_dtype=dtype)     # a float32 engine's pages too
    return ContinuousEngine(tiny_spec(dtype=dtype),
                            config=EngineConfig(**base), seed=11)


def judged(engine, requests, results):
    """Every served token of a FLOAT32 engine is the reference's argmax, or
    within 1e-4 of max|logit| of it (a tie the two break differently). The
    bfloat16 band of this size is too wide to judge a token by
    (``tests/test_keye.py``)."""
    with jax.default_matmul_precision("highest"):
        for req, res in zip(requests, results):
            assert len(res.tokens) == req.max_new_tokens
            lg = np.asarray(REF.logits(
                CFG, engine.params, jnp.asarray(req.prompt + res.tokens)))
            for i, tok in enumerate(res.tokens):
                row = lg[len(req.prompt) - 1 + i]
                assert row.max() - row[tok] <= 1e-4 * np.abs(row).max(), (
                    i, tok)


def test_engine_serves_eight_rows_of_unequal_length():
    """Ten requests over eight slots (eight live at once, two waiting for a
    freed slot and its pages, whose stale index keys the successor must not
    select), contexts from below the top-k of 16 to eight times it, across
    pages of 8 and many chunks of 4."""
    with jax.default_matmul_precision("highest"):
        engine = tiny_engine("float32", max_slots=8, num_pages=160,
                             prefix_cache=True)
        assert (engine.body, engine.attn_impl) == ("hybrid", "xla")
        rng = np.random.default_rng(1)
        reqs = [GenerationRequest(
            prompt=[int(t) for t in rng.integers(1, 256, n)],
            max_new_tokens=m)
            for n, m in ((20, 10), (37, 40), (5, 12), (100, 30), (33, 7),
                         (12, 5), (61, 14), (9, 45), (31, 6), (32, 11))]
        results = engine.generate(reqs)
    judged(engine, reqs, results)
    m = engine.get_metrics()
    assert m["prefix_disabled_per_layer"] == 1
    assert m["prefix_hit_admissions"] == 0 and m["kv"]["prefix_queries"] == 0
    assert m["decode_steps"] >= 44 and m["decode_chunks"] >= 11
    # top-2 of 8 in every one of 4 layers, prefill and decode
    tokens = sum(len(r.prompt) + r.max_new_tokens - 1 for r in reqs)
    assert m["moe"]["assignments_total"] == tokens * 2 * 4
    assert m["moe"]["assignments_held"] == m["moe"]["assignments_total"]
    assert 0 < m["moe"]["experts_touched"] <= m["decode_steps"] * 4 * 8
    kv = m["kv"]
    assert (kv["paged_layers"], kv["state_layers"]) == (4, 0)
    assert "window_layers" not in kv
    assert kv["latent_bytes_per_token"] == 4 * 256 * 4
    assert kv["index_bytes_per_token"] == 4 * 32 * 4
    assert kv["hbm_bytes"] == 4 * 160 * PAGE * 256 * 4
    assert kv["state_bytes"] == 4 * 160 * PAGE * 32 * 4
    assert kv["pages_used"] == 0            # every slot was freed


def test_counters_follow_lengths_and_steps(shared):
    """One request alone: a prompt of 60 (far above the top-k of 16) and 9
    tokens. The first comes from the prefill; the 8 decode steps score 61
    ... 68 index keys a layer and select 16 rows each. What the indexer
    READ is the program's own count: the whole table (4 slots x 32 pages x
    8) plus the side window (4 slots x 4 rows) a step."""
    engine = shared()
    m0 = engine.get_metrics()
    engine.generate([GenerationRequest(prompt=list(range(1, 61)),
                                       max_new_tokens=9)])
    m = engine.get_metrics()
    attn = grown(m0["attn"], m["attn"])
    assert m["decode_steps"] - m0["decode_steps"] == 8
    assert attn["full_context_rows"] == sum(range(61, 69))
    assert attn["index_rows_scored"] == sum(range(61, 69))
    assert attn["rows_selected"] == 8 * TOPK
    assert attn["index_table_rows"] == 8 * (4 * 32 * PAGE + 4 * 4)
    assert attn["index_prefill_pairs"] == 60 * 61 // 2
    assert attn["full_prefill_key_blocks_visited"] >= 1
    assert "state" not in m
    # the XLA body gathered the top-16 and the side window, every slot's
    assert attn["kv_rows_read"] == 8 * (4 * TOPK + 4 * 4)
    # below the top-k every row is selected
    m0 = engine.get_metrics()
    engine.generate([GenerationRequest(prompt=[5, 6, 7], max_new_tokens=5)])
    attn = grown(m0["attn"], engine.get_metrics()["attn"])
    assert attn["rows_selected"] == attn["full_context_rows"] == sum(
        range(4, 8))


def test_the_kernel_bodys_counters_follow_the_live_pages(shared):
    """The same request on the kernel body: what the indexer and the
    attention READ is the row's live pages whole (8 pages of 8 as the two
    chunks of 4 steps began at 60 and 64 rows) and the side window (4 slots x
    4 rows) a step, not the table; the selection and the context are the
    XLA body's."""
    engine = shared(attention_impl=KERNELS)
    assert (engine.body, engine.attn_impl) == ("hybrid", KERNELS)
    m0 = engine.get_metrics()
    engine.generate([GenerationRequest(prompt=list(range(1, 61)),
                                       max_new_tokens=9)])
    m = engine.get_metrics()
    attn = grown(m0["attn"], m["attn"])
    assert m["decode_steps"] - m0["decode_steps"] == 8
    assert attn["index_rows_scored"] == sum(range(61, 69))
    assert attn["rows_selected"] == 8 * TOPK
    assert attn["index_table_rows"] == 8 * (8 * PAGE + 4 * 4)
    assert attn["kv_rows_read"] == attn["index_table_rows"]
    assert attn["rows_selected"] <= attn["kv_rows_read"]
    assert attn["index_rows_scored"] <= attn["index_table_rows"]


def _lowered_decode_chunk(eng):
    from distributed_inference_engine_tpu.ops.sampling import SamplingParams

    kv, n = eng.kv, eng.max_slots
    sampling = SamplingParams(eng._temps, eng._top_k, eng._top_p, eng._min_p)
    return eng._decode_chunk.lower(
        eng.params, *kv.pools, eng._lengths, eng._last, eng._active,
        eng._produced, kv.page_table, jnp.zeros((n,), jnp.int32),
        eng._max_new, sampling, eng._eos, eng._stops_dev,
        jax.random.key(0), n_steps=4).as_text(debug_info=True)


def test_the_kernel_bodys_ops_carry_the_selections_scopes(shared):
    """The TPU body's decode chunk: the three kernels under ``attn.index``,
    ``attn.select`` and ``attn.sparse`` inside ``attn.dsa`` (the scopes the
    decode roofline's denominator sums; ``attn.gather`` has no op), under
    names the prefill kernels' reader does not match, and nothing of the
    XLA body (no sort, no ``top_k``)."""
    dec = _lowered_decode_chunk(shared(attention_impl=KERNELS))
    for scope, launcher, kernel in (
            ("attn.index", "index_scores_decode", "index_scores_decode"),
            ("attn.select", "select_mask_decode", "select_mask_decode"),
            ("attn.sparse", "sparse_decode_attention",
             "sparse_decode_flash")):
        assert f"attn.dsa/{scope}/jit({launcher})" in dec, launcher
        assert f'"{kernel}/' in dec, kernel
    assert "attn.gather" not in dec
    assert not re.search(
        r"(index_scores_flash|sparse_prefill_flash)_b\d+q\d+k\d+", dec)
    dsa = [line for line in dec.splitlines() if "attn.dsa" in line]
    assert dsa and not [line for line in dsa
                        if re.search(r"top_k|chlo\.top_k|stablehlo\.sort",
                                     line)]
    for scope in ("attn.kv_update", "moe.route", "moe.experts",
                  "head.unembed", "sample"):
        assert re.search(rf'["/]{re.escape(scope)}/', dec), scope


def test_the_spans_are_in_the_programs(shared):
    """Every scope the per-layer metrics read is on some operation of the
    lowered decode and prefill programs."""
    from distributed_inference_engine_tpu.ops.sampling import SamplingParams

    eng = shared()
    kv = eng.kv
    dec = _lowered_decode_chunk(eng)
    for scope in ("attn.dsa", "attn.index", "attn.select", "attn.gather",
                  "attn.sparse", "attn.kv_update", "moe.route",
                  "moe.experts", "head.unembed", "sample"):
        assert re.search(rf'["/]{re.escape(scope)}/', dec), scope
    pre = eng._prefill_pages.lower(
        eng.params, jnp.zeros((1, 32), jnp.int32), jnp.ones((1,), jnp.int32),
        *kv.pools, jnp.zeros((1, kv.max_pages_per_seq), jnp.int32),
        SamplingParams(jnp.zeros((1,)), jnp.zeros((1,), jnp.int32),
                       jnp.ones((1,)), jnp.zeros((1,))), jax.random.key(0),
        jnp.zeros((1,), jnp.int32)).as_text(debug_info=True)
    for scope in ("attn.dsa", "attn.index", "attn.select", "attn.sparse",
                  "attn.kv_update", "moe.route", "moe.experts",
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
    re-queued as prompt + tokens and re-prefilled (K|V rows and index keys
    anew, through the selection: its context is above the top-k); the
    result equals the same request served alone. In float32, as the other
    families' tests."""
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, 256, n)] for n in (30, 28)]

    def make():
        return [GenerationRequest(prompt=list(p), max_new_tokens=60)
                for p in prompts]

    with jax.default_matmul_precision("highest"):
        alone = [shared("float32").generate([r])[0] for r in make()]
        tight = tiny_engine("float32", num_pages=16)
        together = tight.generate(make())
    m = tight.get_metrics()
    assert m["reprefill_preemptions"] >= 1 and m["capacity_finishes"] == 0
    for a, b in zip(alone, together):
        assert a.tokens == b.tokens and len(b.tokens) == 60
        assert b.finish_reason == a.finish_reason
    assert m["kv"]["pages_used"] == 0


def test_a_slot_is_reused_after_a_long_request():
    """One slot: a request six top-ks long, then a short one in the same
    slot over the pages the first gave back, full of its index keys; both
    serve the reference's tokens (float32, greedy)."""
    rng = np.random.default_rng(5)
    with jax.default_matmul_precision("highest"):
        one = tiny_engine("float32", max_slots=1, num_pages=20)
        for n, m in ((100, 40), (10, 30)):
            reqs = [GenerationRequest(
                prompt=[int(t) for t in rng.integers(1, 256, n)],
                max_new_tokens=m)]
            judged(one, reqs, one.generate(reqs))
    assert one.get_metrics()["kv"]["pages_used"] == 0


def test_a_sixteen_step_chunk_crosses_the_topk(shared):
    """``decode_steps_per_call`` 16, the cell's cadence: a chunk that
    begins at 10 rows ends at 26, its selection moving from every row to 16
    of them with the chunk's own rows in the side window; the tokens are the
    4-step engine's and the reference's."""
    rng = np.random.default_rng(9)
    prompts = [[int(t) for t in rng.integers(1, 256, n)]
               for n in (10, 37, 3, 20)]

    def make():
        return [GenerationRequest(prompt=list(p), max_new_tokens=40)
                for p in prompts]

    with jax.default_matmul_precision("highest"):
        ref = shared("float32").generate(make())
        engine = shared("float32", decode_steps_per_call=16)
        got = engine.generate(make())
    assert [r.tokens for r in got] == [r.tokens for r in ref]
    judged(engine, make(), got)


def test_a_sixteen_step_chunk_crosses_the_topk_through_the_kernels(shared):
    """The same four requests through the TPU's decode body (the three
    kernels, interpreted) at the cell's cadence, over slots that are reused
    (ten requests over four slots: a successor's pages hold its
    predecessor's index keys): every token is the reference's."""
    rng = np.random.default_rng(9)
    reqs = [GenerationRequest(
        prompt=[int(t) for t in rng.integers(1, 256, n)], max_new_tokens=m)
        for n, m in ((10, 40), (37, 40), (3, 40), (20, 40), (100, 30),
                     (33, 7), (12, 5), (61, 14), (9, 45), (31, 6))]
    with jax.default_matmul_precision("highest"):
        engine = shared("float32", decode_steps_per_call=16,
                        attention_impl=KERNELS)
        got = engine.generate(reqs)
        xla = shared("float32", decode_steps_per_call=16).generate(reqs[:4])
    judged(engine, reqs, got)
    assert [r.tokens for r in got[:4]] == [r.tokens for r in xla]
    attn = engine.get_metrics()["attn"]
    assert attn["rows_selected"] < attn["kv_rows_read"] < attn[
        "full_context_rows"] + 16 * 4 * engine.get_metrics()["decode_steps"]


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
    """A learned selection takes its kernels under exactly a K|V-row spec's
    conditions (a TPU, the pool on one device, K|V rows of whole 128-lane
    tiles): ``auto`` resolves from the spec and the backend, ``xla`` keeps
    the gathers, the kernel's names ask for it anywhere."""
    spec = spec_for_architecture("keye", size="keye-vl-2.0-30b-a3b-pp1",
                                 max_seq_len=33792)
    assert spec.max_seq_len == 33792 and not spec.recurrent
    assert (spec.kv_row_lanes, spec.cache_row_width) == (512, 1024)
    assert (spec.paged_layers, spec.window_layers) == (6, 0)
    assert (spec.index_heads, spec.index_head_dim, spec.index_topk) == (
        16, 64, 2048)
    assert resolve_decode_body("auto", "tpu", spec) == (
        "hybrid", "pallas-decode")
    assert resolve_decode_body("auto", "cpu", spec) == ("hybrid", "xla")
    assert resolve_decode_body("auto", "tpu", spec, sharded=True) == (
        "hybrid", "xla")
    assert resolve_decode_body("xla", "tpu", spec) == ("hybrid", "xla")
    for name in ("pallas-decode", KERNELS):
        assert resolve_decode_body(name, "cpu", spec) == ("hybrid", name)
    assert resolve_decode_body("auto", "tpu", tiny_spec()) == (
        "hybrid", "pallas-decode")
    with pytest.raises(ValueError, match="not one of"):
        resolve_decode_body("sparse-decode", "tpu", spec)
    with pytest.raises(ValueError, match="unknown keye size"):
        spec_for_architecture("keye", size="keye-9b")


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

    params = keye.init_params(tiny_spec(), jax.random.key(7))
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
    meta = {"size": "keye-tiny", "continuous": 1, "page_size": PAGE,
            "num_pages": 16}
    meta.update(change.get("metadata", {}))
    cfg = ModelConfig(name="m", architecture="keye", max_batch_size=2,
                      max_seq_len=64, metadata=meta,
                      **{k: v for k, v in change.items() if k != "metadata"})
    with pytest.raises(ValueError, match=match):
        engine_from_config(cfg)


def test_calls_a_per_layer_spec_cannot_answer_raise(shared):
    engine = shared()
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
            name="m", architecture="keye", max_batch_size=2,
            max_seq_len=64, dtype="bfloat16", metadata={
                "size": "keye-tiny", "continuous": 1, "page_size": PAGE,
                "num_pages": 16, "seed": seed, "admission_max_rows": 1}))

    a, b, c = build(5), build(5), build(6)
    assert a.config.admission_max_rows == 1
    la, lb, lc = (e.params["period"][0]["w_iq"] for e in (a, b, c))
    assert bool((la == lb).all()) and not bool((la == lc).all())
    assert la.dtype == jnp.bfloat16
    assert a.params["period"][0]["w_router"].dtype == jnp.float32
    assert a.kv.state["index_pages"].shape == (4, 16, PAGE, 32)


# ------------------------------------------- behind a coordinator and worker


async def test_streams_behind_a_real_coordinator_and_worker():
    """``architecture: "keye"`` deployed through a coordinator onto a
    worker: eight streams over four slots, contexts on both sides of the
    top-k, every stream ends with its tokens and they are what the same
    engine configuration serves directly; the worker's device report names
    the body."""
    model = ModelConfig(
        name="m", architecture="keye", dtype="bfloat16", max_seq_len=128,
        max_batch_size=4,
        metadata={"size": "keye-tiny", "page_size": PAGE, "num_pages": 64,
                  "prefill_buckets": [32, 64], "decode_steps_per_call": 4,
                  "continuous": 1, "seed": 11})
    coord = Coordinator(CoordinatorConfig())
    await coord.start()
    w = WorkerServer(ServerConfig(worker_id="w0", host="127.0.0.1", port=0))
    host, port = await w.start()
    try:
        coord.add_worker("w0", host, port)
        await coord.deploy_model(model)
        dev = w.device_report()["models"]["m"]
        assert dev["slots"] == 4 and dev["decode_attention"] == "xla"
        rng = np.random.default_rng(4)
        reqs = [([int(t) for t in rng.integers(1, 256, n)], m)
                for n, m in ((5, 20), (40, 12), (12, 9), (60, 6), (3, 30),
                             (33, 8), (20, 20), (50, 10))]
        outs = await asyncio.gather(*[coord.submit_stream(
            "m", prompt=list(p), on_tokens=lambda t: None,
            max_new_tokens=m, request_id=f"s{i}")
            for i, (p, m) in enumerate(reqs)])
        direct = engine_from_config(model).generate([
            GenerationRequest(prompt=list(p), max_new_tokens=m)
            for p, m in reqs])
        for (p, m), o, d in zip(reqs, outs, direct):
            assert len(o["tokens"]) == m and o["tokens"] == d.tokens
        attn = w.engines["m"].get_metrics()["attn"]
        assert attn["rows_selected"] < attn["full_context_rows"]
    finally:
        await coord.stop()
        await w.stop()
