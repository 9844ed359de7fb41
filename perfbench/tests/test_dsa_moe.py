"""The ``dsa_moe`` family's benchmark files: its counts at the cut against
the hand count of ISSUE 45 and against the tree (the published one by shapes
only), every matrix of a pass named once, the byte functions the rooflines
read against hand-reckoned numbers, the family's scopes on a recorded list of
op paths, the ``*.keye`` readers on a made run and on a run of another
program (they read nothing and do not raise), the wrong models of the
reference against ``check.py``'s judge at the tiny size, and the tiny
rehearsal through the seam's own questions."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import test_families as seam  # noqa: E402  (this directory: pytest puts it first)
from perfbench.lib import (  # noqa: E402
    families, procs, scopes, scopes_dsa, scopes_swa, session,
)
from perfbench.lib.session import RunData  # noqa: E402
from perfbench.tools import rehearse, rehearse_keye  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
US = 1000.0
CELL = "keyevl2-longctx-overload"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def cut():
    return session.load_config("keye-vl-2.0-30b-a3b-pp1")


@pytest.fixture
def tiny(monkeypatch):
    """``keye-tiny`` lies in ``perfbench/rehearse/`` (``tools/rehearse.py``
    says why): the seam's questions are asked of it here, by name."""
    cfg = rehearse.load("keye-tiny")
    real = session.load_config
    monkeypatch.setattr(session, "load_config",
                        lambda n: dict(cfg) if n == "keye-tiny" else real(n))
    return cfg


@pytest.mark.parametrize("question", [
    seam.test_family_resolves_to_counts_and_a_reference,
    seam.test_param_bytes_are_the_bytes_of_the_tree,
    seam.test_serve_reaches_the_worker_and_the_coordinator_whole],
    ids=lambda q: q.__name__[5:])
def test_the_seams_questions_of_the_tiny_rehearsal(question, tiny):
    assert tiny["platform"] == "cpu"
    question("keye-tiny")


@pytest.fixture(scope="module")
def tiny_chains():
    return seam.served_chains(rehearse.load("keye-tiny"))


def test_the_tiny_chains_pass_its_reference_alone(tiny, tiny_chains,
                                                  tmp_path):
    def chains(_name):
        return tiny_chains
    seam.test_served_chains_pass_their_own_reference("keye-tiny", chains,
                                                     tmp_path)
    seam.test_another_familys_chains_fail_the_dense_reference(
        "keye-tiny", chains, tmp_path)


def test_the_wrong_models_at_the_tiny_size(tiny, tiny_chains):
    """``check.py``'s judge with the family's own limits, the same served
    chains against the reference with ONE named term wrong. The chains are
    48 + 24 tokens, four times the tiny top-k of 16: every judged token's
    attention reads a selection, and the control without one (``dense``)
    refuses a chain, as does a halved top-k."""
    import jax.numpy as jnp
    import numpy as np

    from perfbench.reference import check

    ref = families.reference(tiny)
    params = ref.build_params(tiny, seam.program_spec(tiny), 7)

    def verdicts(**kw):
        out = []
        for case in tiny_chains:
            seq = jnp.asarray(case["prompt"] + case["tokens"], jnp.int32)
            lg = np.asarray(ref.logits(tiny, params, seq, **kw), np.float32)
            out.append(check.judge(lg, len(case["prompt"]), case["tokens"],
                                   ref.TIE_FRACTION,
                                   ref.MIN_STRICT_SHARE)["ok"])
        return out

    assert len(ref.CONTROLS) == 6
    refused = {c for c in ref.CONTROLS if not all(verdicts(control=c))}
    assert refused >= {"dense", "topk_halved"}, refused


def test_the_rehearsal_has_its_two_files_outside_the_benchmarks():
    (config, mix), = rehearse_keye.REHEARSALS.values()
    assert rehearse.load(config)["serve"] and rehearse.load(mix)["prompt"]
    assert not os.path.exists(os.path.join(HERE, "configs", f"{config}.json"))
    assert not os.path.exists(os.path.join(HERE, "traffic", f"{mix}.json"))
    assert set(rehearse_keye.REHEARSALS).isdisjoint(rehearse.REHEARSALS)
    with open(os.path.join(HERE, "rehearsal.json")) as f:
        assert "keye" not in f.read()


def test_the_family_answers_both_apis():
    cfg = cut()
    counts, ref = families.counts(cfg), families.reference(cfg)
    assert all(hasattr(counts, a) for a in families.COUNTS_API)
    assert all(hasattr(ref, a) for a in families.REFERENCE_API)
    assert families.int4_calls_per_pass(cfg) == 0
    assert "K|V row" in counts.CACHE and "index key" in counts.CACHE
    assert "ONE page table" in counts.CACHE and "13,056 B" in counts.CACHE
    keys = {k for k, _f in ref.SPEC_PAIRS}
    assert keys >= {"hidden_size", "vocab_size", "num_attention_heads",
                    "num_key_value_heads", "head_dim", "num_hidden_layers",
                    "num_experts", "num_experts_per_tok",
                    "moe_intermediate_size", "rms_norm_eps", "rope_theta"}
    spec = seam.program_spec(cfg)
    for key, field in ref.SPEC_PAIRS:
        assert cfg[key] == getattr(spec, field), key
    for key, field in ref.SA_PAIRS:
        assert cfg["sa_config"][key] == getattr(spec, field), key
    # the reference imports nothing from ops/
    with open(os.path.join(HERE, "reference", "dsa_moe.py")) as f:
        text = f.read()
    assert "ops" not in [line.split()[1].split(".")[-1]
                         for line in text.splitlines()
                         if line.startswith(("import ", "from "))]
    assert "..ops" not in text and ".ops " not in text


NEW_HERE = ["model.decode_step_ms.overload", "model.prefill_time_share.overload",
            "attn.index_time_share.keye", "attn.select_time_share.keye",
            "attn.sparse_time_share.keye", "attn.selected_share.keye",
            "attn.index_table_live_share.keye",
            "moe.experts_time_share.overload", "moe.route_time_share.overload",
            "moe.experts_touched_per_step.overload", "head.time_share.overload",
            "model.decode_stream_roofline.overload",
            "moe.expert_stream_roofline.overload", "moe_gmm_roofline.overload",
            "attn.sparse_decode_roofline.keye",
            "attn.index_prefill_roofline.keye",
            "attn.sparse_prefill_roofline.keye"]
# of these, the shares of a peak that divide the slice's own counters
FROM_COUNTERS = NEW_HERE[-6:-2]


def test_the_configuration_is_the_catalogs_with_one_cut():
    cfg = cut()
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Keye-VL-2.0-30B-A3B")
    assert cfg["source"] == entry["source_url"]
    changed = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers"}
    assert cfg["sa_config"] == entry["config"]["sa_config"]
    assert cfg["sa_config"]["topk"] == 2048
    assert families.counts(cfg).widths(cfg)["L"] == 6
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["kept_layers"] == list(range(6)) and cfg["family"] == "dsa_moe"
    assert "stage 1 of an 8-stage pipeline" in cfg["deployment"]
    assert {"q/k normalisation", "m-rope", "indexer", "selection",
            "q_chunk_size / kv_chunk_size", "moe", "weights", "cache",
            "sizing"} <= set(cfg["assumed"])
    assert {"vision tower", "m-rope's unequal ids",
            "fp8 / hadamard indexer"} <= set(cfg["departures"])
    serve = cfg["serve"]
    pages = -(-serve["max_seq_len"] // serve["page_size"])
    assert serve["num_pages"] == serve["max_batch_size"] * pages == 2112
    assert max(serve["prefill_buckets"]) == 32768 < serve["max_seq_len"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    (cell,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert cell["config"] == "keye-vl-2.0-30b-a3b-pp1" and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    mix = json.load(open(os.path.join(HERE, "traffic",
                                      f"{cell['traffic']}.json")))
    assert (mix["prompt"], mix["output"]) == (
        {"median": 8192, "sigma": 0.8, "min": 2048, "max": 32768},
        {"median": 256, "sigma": 0.6, "min": 64, "max": 768})
    assert mix["prompt"]["max"] + mix["output"]["max"] <= serve["max_seq_len"]
    assert mix["rate_rps"] > 0 and (mix["ramp_s"], mix["tail_s"]) == (10, 10)
    assert mix["strata"] == 6
    # every prompt is above the top-k: every decoded token selects
    assert mix["prompt"]["min"] >= cfg["sa_config"]["topk"]
    # appended to the one end-to-end metric, to the 16 shared layers'
    # readers and to the readings it has in common with other families
    assert [m for m in man["end_to_end"] if CELL in m.get("workloads", [])
            ][0]["name"] == "out_tok_s"
    shared = [m["name"] for m in man["per_layer"]
              if m["name"].endswith(".overload")
              and CELL in m.get("workloads", [])]
    assert len(shared) >= 16
    assert "device.idle_attributed_share.overload" not in shared
    by = {m["name"]: m for m in man["per_layer"]}
    assert all(CELL in by[n]["workloads"] and by[n]["moves"] == "out_tok_s"
               for n in NEW_HERE)
    # what no other family has keeps the family's suffix; nothing else does
    own = sorted(n for n, m in by.items() if m.get("workloads") == [CELL])
    assert own == sorted(n for n in NEW_HERE if n.endswith(".keye"))


def test_the_hand_count_of_the_cut():
    """ISSUE 45's arithmetic, in millions of parameters, and its bytes."""
    cfg = cut()
    c = families.counts(cfg)
    w = c.widths(cfg)
    assert (w["L"], w["Dh"], w["E"], w["k"], w["Hi"], w["Di"], w["topk"]
            ) == (6, 128, 128, 8, 16, 64, 2048)
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    index = 2048 * (1024 + 64 + 16)
    norms = 2 * 2048 + 2 * 128 + 2 * 64
    expert = 3 * 2048 * 768
    router = 2048 * 128
    assert round(attn / 1e6, 2) == 18.87 and round(index / 1e6, 2) == 2.26
    assert round(128 * expert / 1e6, 2) == 603.98
    layer = attn + index + norms + router + 128 * expert
    assert round(layer / 1e6, 1) == 625.4
    ends = 2 * 151936 * 2048 + 2048
    assert round(ends / 1e6, 1) == 622.3
    # the whole model, for the record: 30.64 B
    assert round((48 * layer + ends) / 1e9, 2) == 30.64
    hand = 6 * layer + ends
    # every tensor bf16 but the router (float32: + 2 B each)
    assert c.param_bytes(cfg) == 2 * hand + 6 * 2 * router
    assert round(c.param_bytes(cfg) / 1e9, 2) == 8.75
    assert c.expert_bytes(cfg) == 2 * expert == 9437184
    assert (c.kv_row_bytes(cfg), c.index_key_bytes(cfg)) == (2048, 128)
    assert c.kv_bytes_per_token(cfg) == 13056
    serve = cfg["serve"]
    rows = serve["num_pages"] * serve["page_size"]
    assert round(rows * 6 * 2048 / 1e9, 2) == 3.32
    assert round(rows * 6 * 128 / 1e9, 2) == 0.21
    assert round((c.param_bytes(cfg) + rows * 13056) / 1e9, 2) == 12.28


def test_counts_are_the_published_trees_bytes_by_shapes():
    import jax

    from distributed_inference_engine_tpu.models import keye

    cfg = cut()
    spec = seam.program_spec(cfg)
    tree = jax.eval_shape(lambda: keye.init_params(spec, jax.random.key(0)))
    have = sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))
    assert have == families.counts(cfg).param_bytes(cfg)
    serve = cfg["serve"]
    state = jax.eval_shape(lambda: keye.init_state(
        spec, 8, serve["page_size"], serve["num_pages"]))
    assert state["index_pages"].size * 2 == (
        serve["num_pages"] * serve["page_size"] * 6 * 128)


def test_weight_matmuls_names_every_matrix_once():
    import jax

    from distributed_inference_engine_tpu.models import keye

    cfg = cut()
    c = families.counts(cfg)
    mats = c.weight_matmuls(cfg)
    names = [m[0] for m in mats]
    assert len(names) == len(set(names)) == 11
    by = {m[0]: m for m in mats}
    assert by["attn_q"][1:4] == (2048, 4096, 6)
    assert by["attn_k"][1:4] == by["attn_v"][1:4] == (2048, 512, 6)
    assert by["attn_out"][1:4] == (4096, 2048, 6)
    assert by["index_q"][1:4] == (2048, 1024, 6)
    assert by["index_k"][1:4] == (2048, 64, 6)
    assert by["index_w"][1:4] == (2048, 16, 6)
    assert by["router"][1:] == (2048, 128, 6, "float32")
    # a token multiplies by 8 experts a layer, never 128
    assert by["expert_gate_up"][1:4] == (2048, 1536, 48)
    assert by["expert_down"][1:4] == (768, 2048, 48)
    assert by["lm_head"][1:4] == (2048, 151936, 1)
    spec = seam.program_spec(cfg)
    stack = jax.eval_shape(lambda: keye._init_stack(spec, jax.random.key(0)))
    for mine, theirs in (("wq", "attn_q"), ("wk", "attn_k"),
                         ("wv", "attn_v"), ("wo", "attn_out"),
                         ("w_iq", "index_q"), ("w_ik", "index_k"),
                         ("w_iw", "index_w"), ("w_router", "router")):
        assert stack[mine].shape == (6,) + by[theirs][1:3], mine
    assert stack["w_gate_up"].shape == (6, 128, 2048, 1536)
    assert stack["w_down"].shape == (6, 128, 768, 2048)


def test_the_byte_functions_by_hand():
    cfg = cut()
    c = families.counts(cfg)
    # 8 rows at 11,000 tokens of context, one step: 88,000 index keys and
    # 8 x 2,048 K|V rows in each of 6 layers
    kv = c.sparse_decode_cost(cfg, index_rows=88000, selected_rows=16384)
    assert kv["bytes"] == 6 * (88000 * 128 + 16384 * 2048)
    assert kv["flops"] == 6 * (88000 * 2 * 16 * 64 + 16384 * 4 * 32 * 128)
    # against a dense layer reading the context: a quarter of the bytes
    assert 4 * kv["bytes"] < 6 * 88000 * 2048 * 1.01
    # a layer's index scores of a 32,768 prompt's causal pairs
    ix = c.index_kernel_cost(cfg, pairs=32768 * 32769 // 2)
    assert round(ix["flops"] / 1e12, 1) == 1.1         # ISSUE 45: 1.1 TFLOP
    # the index-score kernel scores a block's whole row: twice that
    assert c.index_kernel_cost(cfg, 32768 * 32768)["flops"] == \
        32768 * 32768 * 2 * 16 * 64
    # the masked kernel's floor: 6 layers of 8 runs at the 32,768 bucket,
    # the shortest prompt it holds 16,385 = 33 blocks of 512 (561 at or
    # under the diagonal; a prompt of 32,768 has 2,080: the floor lies
    # between 1 x and 4 x under what ran), 7 runs more fill no layer
    assert c.sparse_prefill_least_pairs(cfg, 1, 4096, 32768, 48 + 7) == \
        6 * 561 * 512 * 512
    assert c.sparse_prefill_least_pairs(cfg, 1, 4096, 4096, 3) == \
        3 * 512 * 512
    # the last bucket is ``max_seq_len`` (11 runs of 3,072 a layer)
    assert c.sparse_prefill_least_pairs(cfg, 1, 3072, 33792, 11) == \
        65 * 66 // 2 * 512 * 512
    assert c.sparse_prefill_kernel_cost(cfg, 10)["flops"] == 10 * 4 * 32 * 128
    # 50 experts a layer touched by 8 rows x 8 choices
    ex = c.expert_stream_cost(cfg, experts_touched=6 * 50, rows=6 * 64)
    assert ex["bytes"] == 6 * 50 * 9437184 + 6 * 64 * (6 * 2048 + 6 * 768)
    assert ex["flops"] == 2 * 6 * 64 * 3 * 2048 * 768
    whole = c.decode_stream_cost(cfg, 1, 6 * 50, 6 * 64, 88000, 16384, 8)
    fixed = 6 * c.attention_weight_bytes(cfg) + 2 * 151936 * 2048 + 2 * 2048
    assert whole["bytes"] == fixed + ex["bytes"] + kv["bytes"]
    # HBM-bound by far
    assert whole["bytes"] / 819e9 > 20 * whole["flops"] / 197e12
    assert round(1e3 * whole["bytes"] / 819e9, 1) == 4.9


# ------------------------------------------------------------------ scopes

# op paths as a v5e trace of the cell's two programs names them (prefixes
# as XLA writes them); [path, start ns, duration ns]
D = "jit(_decode_chunk)/jit(main)/while/body/while/body/"
E = "jit(_decode_chunk)/jit(main)/while/body/"
P = "jit(_prefill_pages)/jit(main)/while/body/"
RECORDED = [[
    [D + "attn.dsa/dot_general:", 0, 20 * US],
    [D + "attn.dsa/attn.kv_update/select_n:", 20 * US, 5 * US],
    [D + "attn.dsa/attn.index/gather:", 25 * US, 25 * US],
    [D + "attn.dsa/attn.index/dot_general:", 50 * US, 15 * US],
    [D + "attn.dsa/attn.select/top_k:", 65 * US, 40 * US],
    [D + "attn.dsa/attn.select/gt:", 105 * US, 1 * US],
    [D + "attn.dsa/attn.gather/gather:", 106 * US, 50 * US],
    [D + "attn.dsa/attn.sparse/dot_general:", 156 * US, 14 * US],
    [D + "moe.route/top_k:", 170 * US, 20 * US],
    [D + "moe.experts/gather:", 190 * US, 10 * US],
    [D + "moe.experts/gmm/pallas_call:", 200 * US, 100 * US],
    [E + "head.unembed/dot_general:", 300 * US, 30 * US],
    [E + "sample/argmax:", 330 * US, 10 * US],
    # one layer of an 8,192 bucket: a block of 512 queries scored, the two
    # chunks of 4,096 queries of the masked kernel
    [P + "attn.dsa/attn.index/jit(_index_scores_flash)/"
     "index_scores_flash_b1q512k8192/pallas_call:", 340 * US, 20 * US],
    [P + "attn.dsa/attn.select/reduce:", 360 * US, 30 * US],
    [P + "attn.dsa/attn.sparse/jit(_masked_flash_prefill)/"
     "sparse_prefill_flash_b1q4096k8192/pallas_call:", 390 * US, 25 * US],
    [P + "attn.dsa/attn.sparse/jit(_masked_flash_prefill)/"
     "sparse_prefill_flash_b1q4096k8192/pallas_call:", 415 * US, 35 * US],
    [P + "moe.experts/gmm/pallas_call:", 450 * US, 50 * US]]]


def test_the_familys_scopes_on_recorded_op_paths():
    red = scopes_dsa.reduce_scopes(RECORDED)
    assert red["busy_s"] == pytest.approx(500e-6)
    sc = red["scopes"]
    assert sc["attn.dsa"] == {"decode": pytest.approx(170e-6),
                              "other": pytest.approx(110e-6)}
    # a nested scope is counted under both names, and by its kind
    assert sc["attn.index"] == {"decode": pytest.approx(40e-6),
                                "other": pytest.approx(20e-6)}
    assert sc["attn.select"] == {"decode": pytest.approx(41e-6),
                                 "other": pytest.approx(30e-6)}
    assert sc["attn.gather"]["decode"] == pytest.approx(50e-6)
    assert sc["attn.sparse"] == {"decode": pytest.approx(14e-6),
                                 "other": pytest.approx(60e-6)}
    assert sc["moe.experts"] == {"decode": pytest.approx(110e-6),
                                 "other": pytest.approx(50e-6)}
    assert sc["gmm"]["decode"] == pytest.approx(100e-6)
    assert sc["head.unembed"]["decode"] == pytest.approx(30e-6)
    # the operation counted: the head's product in decode programs, once a
    # step (top_k's longest, the gather's and a concatenate run 2-3 x a
    # layer a step on a v5e)
    assert red["steps"] == 1
    two = scopes_dsa.reduce_scopes([RECORDED[0] + [
        [D + "attn.dsa/attn.select/top_k:", 600 * US, 38 * US],
        [D + "attn.dsa/attn.select/top_k:", 640 * US, 38 * US],
        [D + "attn.dsa/attn.gather/gather:", 680 * US, 50 * US],
        [E + "head.unembed/dot_general:", 731 * US, 31 * US],
        [E + "head.unembed/convert:", 770 * US, 1 * US],
        [P + "head.unembed/dot_general:", 780 * US, 500 * US]]])
    assert two["steps"] == 2
    # the prefill's kernels by the shape their names give: runs, seconds
    assert red["kernels"] == {
        "index_scores_flash 1 512 8192": [1, pytest.approx(20e-6)],
        "sparse_prefill_flash 1 4096 8192": [2, pytest.approx(60e-6)]}
    assert scopes_dsa.reduce_scopes([[["jit(f)/mul:", 0, 5.0]]])["scopes"] \
        == {}
    # Mellum's reader finds its own scope in none of these paths
    assert scopes_swa.OWN not in scopes_swa.reduce_scopes(RECORDED)["scopes"]


def made_run(tmp_path):
    """A traced run of the cell: 10 decode programs of 16 steps in the
    slice, the first cut by the slice's start so that the head ran 150
    steps there; over the window 1,600 steps in 100 chunks, 8
    rows live at a context of 12,000; between the worker's two stamps of
    the traced slice (``counters.json``) the rows' contexts are 11,000, 50
    experts a layer got a row and one prompt of 8,192 was admitted: what
    the rooflines divide by the slice's seconds is the slice's own."""
    trace_dir = tmp_path / "trace-w0"
    trace_dir.mkdir()
    (tmp_path / "scopes-w0.json").write_text(json.dumps(
        scopes.reduce_scopes(RECORDED)))
    (tmp_path / "scopes-dsa-w0.json").write_text(json.dumps(
        dict(scopes_dsa.reduce_scopes(RECORDED), steps=150)))

    def worker(steps, chunks, context, selected, table, touched, pairs,
               prefill_pairs=0):
        return {"models": {procs.MODEL: {
            "decode_steps": steps, "decode_chunks": chunks,
            "attn": {"full_context_rows": context,
                     "index_rows_scored": context,
                     "rows_selected": selected, "index_table_rows": table,
                     "index_prefill_pairs": prefill_pairs},
            "moe": {"experts_touched": touched,
                    "decode_assignments_held": pairs}}}}

    table = 8 * 264 * 128 + 8 * 16
    (trace_dir / "counters.json").write_text(json.dumps({
        "start": worker(2300, 180, 10 ** 8, 10 ** 7, 10 ** 9, 10 ** 6,
                        10 ** 6, 10 ** 9),
        "stop": worker(2460, 190, 10 ** 8 + 160 * 88000,
                       10 ** 7 + 160 * 16384, 10 ** 9 + 160 * table,
                       10 ** 6 + 160 * 6 * 50, 10 ** 6 + 160 * 6 * 64,
                       10 ** 9 + 8192 * 8193 // 2)}))
    return RunData(
        config=cut(), mix={}, records=[], t_open=0.0, t_close=51.0, setup={},
        device={"kind": "TPU v5 lite"},
        workers_before={"w0": worker(1000, 100, 10 ** 6, 10 ** 6, 10 ** 6,
                                     5000, 5000)},
        workers_after={"w0": worker(
            2600, 200, 10 ** 6 + 1600 * 96000, 10 ** 6 + 1600 * 16384,
            10 ** 6 + 1600 * table, 5000 + 1600 * 6 * 51,
            5000 + 1600 * 6 * 64)},
        samples=[],
        trace_dirs={"w0": str(trace_dir)},
        trace={"program_s": {"decode": 1.8, "prefill": 0.9},
               "program_calls": {"decode": 10, "prefill": 3},
               "busy_s": 3.0, "window_s": 4.0, "between_programs_s": 0.2})


def reader(name):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("r_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_readers_on_a_made_run(tmp_path):
    run = made_run(tmp_path)
    # the steps are the head's runs in the slice, not whole programs
    assert scopes.decode_steps_in_slice(run) == pytest.approx(160.0)
    assert scopes_dsa.steps_in_slice(run) == pytest.approx(150.0)
    assert reader("model.decode_step_ms.overload")(run) == pytest.approx(12.0)
    assert reader("model.prefill_time_share.overload")(run) == pytest.approx(30.)
    assert reader("attn.index_time_share.keye")(run) == pytest.approx(12.0)
    assert reader("attn.select_time_share.keye")(run) == pytest.approx(14.2)
    assert reader("attn.sparse_time_share.keye")(run) == pytest.approx(24.8)
    assert reader("moe.experts_time_share.overload")(run) == pytest.approx(32.0)
    assert reader("moe.route_time_share.overload")(run) == pytest.approx(4.0)
    assert reader("head.time_share.overload")(run) == pytest.approx(8.0)
    assert reader("moe.experts_touched_per_step.overload")(run) == \
        pytest.approx(6 * 51)
    assert reader("attn.selected_share.keye")(run) == \
        pytest.approx(100.0 * 16384 / 96000)
    assert reader("attn.index_table_live_share.keye")(run) == \
        pytest.approx(100.0 * 96000 / (8 * 264 * 128 + 128))
    counts = families.counts(run.config)
    # the slice's own rows a step (88,000), not the window's (96,000)
    assert scopes_dsa.per_slice_step(run, "attn", "index_rows_scored") == \
        pytest.approx(88000.0)
    whole = counts.decode_stream_cost(
        run.config, 150, 6 * 50 * 150, 6 * 64 * 150, 88000 * 150,
        16384 * 150, 8 * 150)
    assert reader("model.decode_stream_roofline.overload")(run) == \
        pytest.approx(100 * whole["bytes"] / 819e9 / 1.8)
    assert 35 < reader("model.decode_stream_roofline.overload")(run) < 45
    kv = counts.sparse_decode_cost(
        run.config, (8 * 264 * 128 + 128) * 150, 16384 * 150)
    assert reader("attn.sparse_decode_roofline.keye")(run) == \
        pytest.approx(100 * kv["bytes"] / 819e9 / 145e-6)
    # the two kernels from what RAN in the slice (their names), whatever
    # the host admitted between the stamps: a block of 512 queries against
    # 8,192 keys; one whole layer of the 8,192 bucket at the shortest
    # prompt it holds (4,097: 9 blocks of 512, 45 at or under the diagonal)
    assert reader("attn.index_prefill_roofline.keye")(run) == \
        pytest.approx(100 * 512 * 8192 * 2 * 16 * 64 / 197e12 / 20e-6)
    assert reader("attn.sparse_prefill_roofline.keye")(run) == \
        pytest.approx(100 * 45 * 512 * 512 * 4 * 32 * 128 / 197e12 / 60e-6)
    ex = counts.expert_stream_cost(run.config, 6 * 50 * 150, 6 * 64 * 150)
    assert reader("moe.expert_stream_roofline.overload")(run) == \
        pytest.approx(100 * ex["bytes"] / 819e9 / 110e-6)
    assert reader("moe_gmm_roofline.overload")(run) == \
        pytest.approx(100 * ex["bytes"] / 819e9 / 100e-6)
    assert reader("device.idle_share.overload")(run) == pytest.approx(25.0)
    # without the worker's stamps (an earlier program): no share of a peak
    os.remove(os.path.join(run.trace_dirs["w0"], "counters.json"))
    for name in FROM_COUNTERS:
        assert reader(name)(run) is None
    # a slice of this family's programs in which no prompt above the top-k
    # was prefilled: the kernels took none of it
    (tmp_path / "scopes-dsa-w0.json").write_text(json.dumps(
        dict(scopes_dsa.reduce_scopes(RECORDED), kernels={})))
    for name in NEW_HERE[-2:]:
        assert reader(name)(run) == 0.0


def test_the_readers_read_nothing_from_another_program(tmp_path):
    """Traced runs of the PARENT's programs (Mellum's scopes and counters;
    Olmo's; Mistral's): the readers this PR brings return None and none
    raises."""
    trace_dir = tmp_path / "trace-w0"
    trace_dir.mkdir()
    old = {"models": {procs.MODEL: {
        "decode_steps": 10, "decode_chunks": 1, "live_slots": 3,
        "attn": {"full_context_rows": 5, "full_table_rows": 9},
        "mla": {"decode_context_rows": 5, "decode_table_rows": 9},
        "kv": {"utilization": 0.4}}}}
    (trace_dir / "counters.json").write_text(json.dumps(
        {"start": old, "stop": old}))
    L = "jit(_decode_chunk)/jit(main)/while/body/"
    for cfg_name, ops in (
            ("mellum2-12b-a2.5b-pp1",
             [[[L + "attn.full/flash_decode/pallas_call:", 0, 9.0],
               [L + "moe.experts/gmm/pallas_call:", 10.0, 5.0]]]),
            ("olmo-hybrid-7b-pp2",
             [[[L + "attn.full/flash_decode/pallas_call:", 0, 9.0],
               [L + "mlp.dense/dot_general:", 10.0, 5.0]]]),
            ("mistral-7b-int4",
             [[[L + "attn.kv_update/scatter:", 0, 9.0], ["", 10.0, 5.0]]])):
        for f in os.listdir(tmp_path):
            if f.startswith("scopes-"):
                os.remove(tmp_path / f)
        (tmp_path / "scopes-dsa-w0.json").write_text(json.dumps(
            scopes_dsa.reduce_scopes(ops)))
        (tmp_path / "scopes-w0.json").write_text(json.dumps(
            scopes.reduce_scopes(ops)))
        run = RunData(
            config=cut(), mix={}, records=[],
            t_open=0.0, t_close=51.0, setup={},
            device={"kind": "TPU v5 lite"},
            workers_before={"w0": old}, workers_after={"w0": old},
            trace_dirs={"w0": str(trace_dir)},
            trace={"program_s": {"decode": 1.0, "prefill": 0.5},
                   "program_calls": {"decode": 9}, "busy_s": 2.0,
                   "decode_steps": 72.0})
        for name in NEW_HERE:
            assert reader(name)(run) is None, (cfg_name, name)
    # and without a trace at all (an untraced or a CPU run)
    run = RunData(config=cut(), mix={}, records=[], t_open=0.0, t_close=51.0,
                  setup={}, device={"kind": "cpu"},
                  workers_before={"w0": old}, workers_after={"w0": old},
                  trace_dirs={}, trace=None)
    for name in NEW_HERE:
        assert reader(name)(run) is None, name
