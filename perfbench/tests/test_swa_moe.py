"""The ``swa_moe`` family's benchmark files: its counts at the cut against
the hand count of ISSUE 39 and against the tree (the published one by shapes
only), every matrix of a pass named once, the byte functions the rooflines
read against hand-reckoned numbers, the family's scopes on a recorded list of
op paths, the ``*.mellum`` readers on a made run and on a run of another
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
    families, procs, scopes, scopes_gdn, scopes_swa, session,
)
from perfbench.lib.session import RunData  # noqa: E402
from perfbench.tools import rehearse, rehearse_mellum  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
US = 1000.0
CELL = "mellum2-repo-overload"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def cut():
    return session.load_config("mellum2-12b-a2.5b-pp1")


@pytest.fixture
def tiny(monkeypatch):
    """``mellum-tiny`` lies in ``perfbench/rehearse/`` (``tools/rehearse.py``
    says why): the seam's questions are asked of it here, by name."""
    cfg = rehearse.load("mellum-tiny")
    real = session.load_config
    monkeypatch.setattr(session, "load_config",
                        lambda n: dict(cfg) if n == "mellum-tiny" else real(n))
    return cfg


@pytest.mark.parametrize("question", [
    seam.test_family_resolves_to_counts_and_a_reference,
    seam.test_param_bytes_are_the_bytes_of_the_tree,
    seam.test_serve_reaches_the_worker_and_the_coordinator_whole],
    ids=lambda q: q.__name__[5:])
def test_the_seams_questions_of_the_tiny_rehearsal(question, tiny):
    assert tiny["platform"] == "cpu"
    question("mellum-tiny")


@pytest.fixture(scope="module")
def tiny_chains():
    return seam.served_chains(rehearse.load("mellum-tiny"))


def test_the_tiny_chains_pass_its_reference_alone(tiny, tiny_chains,
                                                  tmp_path):
    def chains(_name):
        return tiny_chains
    seam.test_served_chains_pass_their_own_reference("mellum-tiny", chains,
                                                     tmp_path)
    seam.test_another_familys_chains_fail_the_dense_reference(
        "mellum-tiny", chains, tmp_path)


def test_the_wrong_models_at_the_tiny_size(tiny, tiny_chains):
    """``check.py``'s judge with the family's own limits, the same served
    chains against the reference with ONE named term wrong. The chains are
    48 + 24 tokens, so they pass the tiny window of 32: a window one row
    off and the two wrong rotary tables refuse a chain; what the router's
    two controls move at this width (1e-3 of a logit,
    ``tests/test_mellum.py``) no token shows, and the float32 tests hold
    them."""
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

    assert all(verdicts())
    assert len(ref.CONTROLS) == 6
    refused = {c for c in ref.CONTROLS if not all(verdicts(control=c))}
    assert refused >= {"window_minus_1", "plain_rope_on_full"}, refused


def test_the_rehearsal_has_its_two_files_outside_the_benchmarks():
    (config, mix), = rehearse_mellum.REHEARSALS.values()
    assert rehearse.load(config)["serve"] and rehearse.load(mix)["prompt"]
    assert not os.path.exists(os.path.join(HERE, "configs", f"{config}.json"))
    assert not os.path.exists(os.path.join(HERE, "traffic", f"{mix}.json"))
    assert set(rehearse_mellum.REHEARSALS).isdisjoint(rehearse.REHEARSALS)
    with open(os.path.join(HERE, "rehearsal.json")) as f:
        assert "mellum" not in f.read()


def test_the_family_answers_both_apis():
    cfg = cut()
    counts, ref = families.counts(cfg), families.reference(cfg)
    assert all(hasattr(counts, a) for a in families.COUNTS_API)
    assert all(hasattr(ref, a) for a in families.REFERENCE_API)
    assert families.int4_calls_per_pass(cfg) == 0
    assert "stop growing at 1,024 rows" in counts.CACHE
    assert "9 SLIDING" in counts.CACHE and "3 FULL" in counts.CACHE
    keys = {k for k, _f in ref.SPEC_PAIRS}
    assert keys >= {"hidden_size", "vocab_size", "num_attention_heads",
                    "num_key_value_heads", "head_dim", "num_hidden_layers",
                    "num_experts", "num_experts_per_tok",
                    "moe_intermediate_size", "sliding_window",
                    "rms_norm_eps"}
    spec = seam.program_spec(cfg)
    for key, field in ref.SPEC_PAIRS:
        assert cfg[key] == getattr(spec, field), key
    # the reference imports nothing from ops/
    with open(os.path.join(HERE, "reference", "swa_moe.py")) as f:
        text = f.read()
    assert "ops" not in [line.split()[1].split(".")[-1]
                         for line in text.splitlines()
                         if line.startswith(("import ", "from "))]
    assert "..ops" not in text and ".ops " not in text


def test_the_configuration_is_the_catalogs_with_one_cut():
    cfg = cut()
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert cfg["source"] == entry["source_url"]
    changed = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    # layer_types stays the published list: the stage runs its first 12
    assert changed == {"num_hidden_layers"}
    assert families.counts(cfg).widths(cfg)["L"] == 12
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 28}
    assert cfg["kept_layers"] == list(range(12)) and cfg["family"] == \
        "swa_moe"
    assert "stage 1 of a pipeline over the 28 layers" in cfg["deployment"]
    assert {"q/k normalisation", "mtp", "rope_parameters", "moe", "weights",
            "cache", "sizing"} <= set(cfg["assumed"])
    serve = cfg["serve"]
    pages = -(-serve["max_seq_len"] // serve["page_size"])
    assert serve["num_pages"] == serve["max_batch_size"] * pages == 1056
    assert max(serve["prefill_buckets"]) == 16384 < serve["max_seq_len"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    (cell,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert cell["config"] == "mellum2-12b-a2.5b-pp1" and cell["chips"] == 1
    assert cell in man["workloads"] and len(cell["why"]) <= 200
    mix = json.load(open(os.path.join(HERE, "traffic",
                                      f"{cell['traffic']}.json")))
    assert (mix["prompt"], mix["output"]) == (
        {"median": 4096, "sigma": 1.0, "min": 256, "max": 16384},
        {"median": 192, "sigma": 0.6, "min": 32, "max": 512})
    assert mix["prompt"]["max"] + mix["output"]["max"] <= serve["max_seq_len"]
    assert mix["rate_rps"] > 0 and (mix["ramp_s"], mix["tail_s"]) == (10, 10)
    assert mix["strata"] == 6
    # appended to the one end-to-end metric, the shared layers' readers and
    # the readings it has in common with other families
    assert [m for m in man["end_to_end"] if CELL in m.get("workloads", [])
            ][0]["name"] == "out_tok_s"
    shared = [m["name"] for m in man["per_layer"]
              if m["name"].endswith(".overload")
              and CELL in m.get("workloads", [])]
    assert len(shared) >= 16
    by = {m["name"]: m for m in man["per_layer"]}
    assert all(CELL in by[n]["workloads"] and by[n]["moves"] == "out_tok_s"
               for n in NEW_HERE)
    # what no other family has keeps the family's suffix; nothing else does
    own = sorted(n for n, m in by.items() if m.get("workloads") == [CELL])
    assert own == sorted(n for n in NEW_HERE if n.endswith(".mellum"))


def test_the_hand_count_of_the_cut():
    """ISSUE 39's arithmetic, in millions of parameters, and its bytes."""
    cfg = cut()
    c = families.counts(cfg)
    w = c.widths(cfg)
    assert (w["L"], w["L_swa"], w["L_full"], w["Dh"], w["E"], w["k"]) == (
        12, 9, 3, 128, 64, 8)
    attn = 2 * 2304 * 4096 + 2 * 2304 * 512 + 2 * 2304
    expert = 3 * 2304 * 896
    router = 2304 * 64
    assert round(attn / 1e6, 1) == 21.2 and round(expert / 1e6, 2) == 6.19
    layer = attn + router + 64 * expert
    assert round(layer / 1e6, 1) == 417.7       # the issue rounds up: 417.8
    ends = 2 * 98304 * 2304 + 2304
    assert round(ends / 1e6, 1) == 453.0
    # the whole model, for the record: 12.15 B
    assert round((28 * layer + ends) / 1e9, 2) == 12.15
    hand = 12 * layer + ends
    # every tensor bf16 but the router (float32: + 2 B each)
    assert c.param_bytes(cfg) == 2 * hand + 12 * 2 * router
    assert round(c.param_bytes(cfg) / 1e9, 2) == 10.94
    assert c.expert_bytes(cfg) == 2 * expert == 12386304
    assert c.kv_row_bytes(cfg) == 2048 and c.kv_bytes_per_token(cfg) == 6144
    serve = cfg["serve"]
    full = serve["num_pages"] * serve["page_size"] * c.kv_bytes_per_token(cfg)
    assert round(full / 1e9, 2) == 0.83
    assert c.window_pages_per_slot(cfg) == 10
    window = serve["max_batch_size"] * c.window_bytes_per_slot(cfg)
    assert window == 8 * 10 * 128 * 2048 * 9 and round(window / 1e9, 2) == 0.19
    # the same cache with every layer keeping every row
    uncut = serve["num_pages"] * serve["page_size"] * 12 * 2048
    assert round(uncut / 1e9, 2) == 3.32


def test_counts_are_the_published_trees_bytes_by_shapes():
    import jax

    from distributed_inference_engine_tpu.models import mellum

    cfg = cut()
    spec = seam.program_spec(cfg)
    tree = jax.eval_shape(lambda: mellum.init_params(
        spec, jax.random.key(0)))
    have = sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))
    assert have == families.counts(cfg).param_bytes(cfg)
    serve = cfg["serve"]
    per_slot = mellum.window_pages_per_slot(spec, serve["page_size"])
    assert per_slot == families.counts(cfg).window_pages_per_slot(cfg)
    state = jax.eval_shape(lambda: mellum.init_state(
        spec, 8, serve["page_size"], 8 * per_slot, 132))
    assert state["window_pages"].size * 2 == \
        8 * families.counts(cfg).window_bytes_per_slot(cfg)


def test_weight_matmuls_names_every_matrix_once():
    import jax

    from distributed_inference_engine_tpu.models import mellum

    cfg = cut()
    c = families.counts(cfg)
    mats = c.weight_matmuls(cfg)
    names = [m[0] for m in mats]
    assert len(names) == len(set(names)) == 8
    by = {m[0]: m for m in mats}
    assert by["attn_q"][1:4] == (2304, 4096, 12)
    assert by["attn_k"][1:4] == by["attn_v"][1:4] == (2304, 512, 12)
    assert by["attn_out"][1:4] == (4096, 2304, 12)
    assert by["router"][1:] == (2304, 64, 12, "float32")
    # a token multiplies by 8 experts a layer, never 64
    assert by["expert_gate_up"][1:4] == (2304, 1792, 96)
    assert by["expert_down"][1:4] == (896, 2304, 96)
    assert by["lm_head"][1:4] == (2304, 98304, 1)
    spec = seam.program_spec(cfg)
    stack = jax.eval_shape(
        lambda: mellum._init_stack(spec, jax.random.key(0)))
    for mine, theirs in (("wq", "attn_q"), ("wk", "attn_k"),
                         ("wv", "attn_v"), ("wo", "attn_out"),
                         ("w_router", "router")):
        assert stack[mine].shape == (3,) + by[theirs][1:3], mine
    assert stack["w_gate_up"].shape == (3, 64, 2304, 1792)
    assert stack["w_down"].shape == (3, 64, 896, 2304)


def test_the_byte_functions_by_hand():
    cfg = cut()
    c = families.counts(cfg)
    # 8 rows at 5,000 tokens of context, one step: 40,000 rows in each of 3
    # full layers, 8 x 1,024 in each of 9 sliding ones, 2,048 B a row
    kv = c.attn_decode_cost(cfg, full_rows=40000, window_rows=8192)
    assert kv["bytes"] == (3 * 40000 + 9 * 8192) * 2048
    assert kv["flops"] == (3 * 40000 + 9 * 8192) * 4 * 32 * 128
    # against every layer reading the context: 2.5x the rows
    assert 12 * 40000 > 2.4 * (3 * 40000 + 9 * 8192)
    # 41 experts a layer touched by 8 rows x 8 choices
    ex = c.expert_stream_cost(cfg, experts_touched=12 * 41, rows=12 * 64)
    assert ex["bytes"] == 12 * 41 * 12386304 + 12 * 64 * (
        6 * 2304 + 6 * 896)
    assert ex["flops"] == 2 * 12 * 64 * 3 * 2304 * 896
    whole = c.decode_stream_cost(cfg, 1, 12 * 41, 12 * 64, 40000, 8192, 8)
    fixed = 12 * c.attention_weight_bytes(cfg) + 2 * 98304 * 2304 + 2 * 2304
    assert round(12 * c.attention_weight_bytes(cfg) / 1e9, 2) == 0.52
    assert whole["bytes"] == fixed + ex["bytes"] + kv["bytes"]
    # HBM-bound by far: the bytes' time is 50x the operations'
    assert whole["bytes"] / 819e9 > 20 * whole["flops"] / 197e12
    assert round(1e3 * whole["bytes"] / 819e9, 1) == 9.1


# ------------------------------------------------------------------ scopes

# op paths as a v5e trace of the cell's two programs names them (prefixes
# as XLA writes them); [path, start ns, duration ns]
D = "jit(_decode_chunk)/jit(main)/while/body/while/body/"
E = "jit(_decode_chunk)/jit(main)/while/body/"
P = "jit(_prefill_pages)/jit(main)/while/body/"
RECORDED = [[
    [D + "attn.swa/dot_general:", 0, 30 * US],
    [D + "attn.swa/attn.kv_update/select_n:", 30 * US, 5 * US],
    [D + "attn.swa/flash_decode/pallas_call:", 35 * US, 15 * US],
    [D + "moe.route/top_k:", 50 * US, 20 * US],
    [D + "moe.experts/gather:", 70 * US, 10 * US],
    [D + "moe.experts/gmm/pallas_call:", 80 * US, 100 * US],
    [D + "attn.full/dot_general:", 180 * US, 20 * US],
    [D + "attn.full/flash_decode/pallas_call:", 200 * US, 25 * US],
    [D + "attn.full/flash_decode/pad:", 225 * US, 1 * US],
    [E + "head.unembed/dot_general:", 226 * US, 30 * US],
    [E + "sample/argmax:", 256 * US, 10 * US],
    [E + "add:", 266 * US, 4 * US],
    [P + "attn.swa/dot_general:", 270 * US, 50 * US],
    [P + "attn.full/dot_general:", 320 * US, 40 * US],
    [P + "moe.route/top_k:", 360 * US, 10 * US],
    [P + "moe.experts/gmm/pallas_call:", 370 * US, 130 * US]]]


def test_the_familys_scopes_on_recorded_op_paths():
    red = scopes_swa.reduce_scopes(RECORDED)
    assert red["busy_s"] == pytest.approx(500e-6)
    sc = red["scopes"]
    assert sc["attn.swa"] == {"decode": pytest.approx(50e-6),
                              "other": pytest.approx(50e-6)}
    assert sc["attn.full"] == {"decode": pytest.approx(46e-6),
                               "other": pytest.approx(40e-6)}
    # a nested scope is counted under both names, and by its kind
    assert sc["flash_decode"] == {"decode": pytest.approx(41e-6),
                                  "other": 0.0}
    assert sc["attn.swa/flash_decode"]["decode"] == pytest.approx(15e-6)
    assert sc["attn.full/flash_decode"]["decode"] == pytest.approx(26e-6)
    assert sc["attn.kv_update"]["decode"] == pytest.approx(5e-6)
    assert sc["moe.route"] == {"decode": pytest.approx(20e-6),
                               "other": pytest.approx(10e-6)}
    assert sc["moe.experts"] == {"decode": pytest.approx(110e-6),
                                 "other": pytest.approx(130e-6)}
    assert sc["gmm"]["decode"] == pytest.approx(100e-6)
    assert sc["head.unembed"]["decode"] == pytest.approx(30e-6)
    assert sc["sample"]["decode"] == pytest.approx(10e-6)
    # the kernel: the FULL layers' operation that takes most of its scope
    assert red["kernel_calls"] == 1
    assert red["kernel_s"] == pytest.approx(25e-6)
    two = scopes_swa.reduce_scopes([RECORDED[0] + [
        [D + "attn.full/flash_decode/pallas_call:", 600 * US, 20 * US],
        [D + "attn.swa/flash_decode/pallas_call:", 620 * US, 9 * US]]])
    assert (two["kernel_calls"], two["kernel_s"]) == (2, pytest.approx(45e-6))
    assert scopes_swa.reduce_scopes([[["jit(f)/mul:", 0, 5.0]]])["scopes"] \
        == {}
    # Olmo's reader finds its own scopes in none of these paths
    assert not set(scopes_gdn.reduce_scopes(RECORDED)["scopes"]) & set(
        scopes_gdn.OWN)


def made_run(tmp_path):
    """A traced run of the cell: 10 decode programs of 16 steps in the
    slice, the first cut by the slice's start so that the full layers'
    kernel ran 150 steps x 3 layers there; over the window 1,600 steps in
    100 chunks, 8 rows live at a context of 5,000; between the worker's two
    stamps of the traced slice (``counters.json``) the rows' contexts are
    4,000 and 40 experts a layer got a row: what the rooflines divide by
    the slice's seconds is the slice's own."""
    trace_dir = tmp_path / "trace-w0"
    trace_dir.mkdir()
    (tmp_path / "scopes-w0.json").write_text(json.dumps(
        scopes.reduce_scopes(RECORDED)))
    (tmp_path / "scopes-swa-w0.json").write_text(json.dumps(
        dict(scopes_swa.reduce_scopes(RECORDED), kernel_calls=150 * 3)))

    def worker(steps, chunks, full, window, touched, pairs, held=0, uncut=0,
               tables=(0, 0)):
        return {"models": {procs.MODEL: {
            "decode_steps": steps, "decode_chunks": chunks,
            "attn": {"full_context_rows": full, "full_table_rows": tables[0],
                     "window_context_rows": window,
                     "window_table_rows": tables[1]},
            "moe": {"experts_touched": touched,
                    "decode_assignments_held": pairs},
            "kv": {"window_pages_held_sum": held,
                   "window_pages_uncut_sum": uncut,
                   "window_pages_released": 0}}}}

    (trace_dir / "counters.json").write_text(json.dumps({
        "start": worker(2300, 180, 2 * 10 ** 7, 10 ** 7, 10 ** 6, 10 ** 6),
        "stop": worker(2460, 190, 2 * 10 ** 7 + 160 * 32000,
                       10 ** 7 + 160 * 8192, 10 ** 6 + 160 * 12 * 40,
                       10 ** 6 + 160 * 12 * 64)}))
    return RunData(
        config=cut(), mix={}, records=[], t_open=0.0, t_close=51.0, setup={},
        device={"kind": "TPU v5 lite"},
        workers_before={"w0": worker(1000, 100, 10 ** 6, 10 ** 6, 5000, 5000,
                                     1000, 5000, (10 ** 7, 10 ** 7))},
        workers_after={"w0": worker(
            2600, 200, 10 ** 6 + 1600 * 40000, 10 ** 6 + 1600 * 8192,
            5000 + 1600 * 12 * 41, 5000 + 1600 * 12 * 64,
            1000 + 100 * 72, 5000 + 100 * 320,
            (10 ** 7 + 1600 * (8 * 40 * 128 + 128),
             10 ** 7 + 1600 * (8 * 9 * 128 + 128)))},
        samples=[
            {"t": 46.5, "workers": {"w0": worker(2000, 150, 0, 0, 0, 0)}},
            {"t": 50.75, "workers": {"w0": worker(2450, 190, 0, 0, 0, 0)}}],
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


NEW_HERE = ["model.decode_step_ms.overload", "model.prefill_time_share.overload",
            "attn.window_time_share.mellum", "attn.full_time_share.overload",
            "attn.window_table_live_share.mellum",
            "attn.full_table_live_share.overload",
            "attn.window_pages_held_share.mellum",
            "moe.experts_time_share.overload", "moe.route_time_share.overload",
            "moe.experts_touched_per_step.overload", "head.time_share.overload",
            "model.decode_stream_roofline.overload",
            "attn.decode_roofline.mellum",
            "moe.expert_stream_roofline.overload", "moe_gmm_roofline.overload"]


def test_the_readers_on_a_made_run(tmp_path):
    run = made_run(tmp_path)
    # the steps are the kernel's calls in the slice, not whole programs
    assert scopes.decode_steps_in_slice(run) == pytest.approx(160.0)
    assert scopes_swa.steps_in_slice(run) == pytest.approx(150.0)
    assert reader("model.decode_step_ms.overload")(run) == pytest.approx(12.0)
    assert reader("model.prefill_time_share.overload")(run) == \
        pytest.approx(30.0)
    assert reader("attn.window_time_share.mellum")(run) == pytest.approx(20.)
    assert reader("attn.full_time_share.overload")(run) == pytest.approx(17.2)
    assert reader("moe.experts_time_share.overload")(run) == pytest.approx(48.)
    assert reader("moe.route_time_share.overload")(run) == pytest.approx(6.0)
    assert reader("head.time_share.overload")(run) == pytest.approx(8.0)
    assert reader("moe.experts_touched_per_step.overload")(run) == \
        pytest.approx(12 * 41)
    assert reader("attn.full_table_live_share.overload")(run) == \
        pytest.approx(100.0 * 40000 / (8 * 40 * 128 + 128))
    assert reader("attn.window_table_live_share.mellum")(run) == \
        pytest.approx(100.0 * 8192 / (8 * 9 * 128 + 128))
    assert reader("attn.window_pages_held_share.mellum")(run) == \
        pytest.approx(100.0 * 72 / 320)
    counts = families.counts(run.config)
    # the slice's own rows a step (32,000), not the window's (40,000)
    assert scopes_swa.per_slice_step(run, "attn", "full_context_rows") == \
        pytest.approx(32000.0)
    whole = counts.decode_stream_cost(
        run.config, 150, 12 * 40 * 150, 12 * 64 * 150, 32000 * 150,
        8192 * 150, 8 * 150)
    assert reader("model.decode_stream_roofline.overload")(run) == \
        pytest.approx(100 * whole["bytes"] / 819e9 / 1.8)
    assert 70 < reader("model.decode_stream_roofline.overload")(run) < 80
    kv = counts.attn_decode_cost(run.config, 32000 * 150, 8192 * 150)
    assert reader("attn.decode_roofline.mellum")(run) == \
        pytest.approx(100 * kv["bytes"] / 819e9 / 41e-6)
    ex = counts.expert_stream_cost(run.config, 12 * 40 * 150, 12 * 64 * 150)
    assert reader("moe.expert_stream_roofline.overload")(run) == \
        pytest.approx(100 * ex["bytes"] / 819e9 / 110e-6)
    assert reader("moe_gmm_roofline.overload")(run) == \
        pytest.approx(100 * ex["bytes"] / 819e9 / 100e-6)
    assert reader("device.idle_share.overload")(run) == pytest.approx(25.0)
    # without the worker's stamps (an earlier program): no share of a peak
    os.remove(os.path.join(run.trace_dirs["w0"], "counters.json"))
    for name in NEW_HERE[-4:]:
        assert reader(name)(run) is None


def test_the_readers_read_nothing_from_another_program(tmp_path):
    """Traced runs of the PARENT's programs (Olmo's scopes and counters;
    Xing's; Mistral's): the readers this PR brings return None and none
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
            ("olmo-hybrid-7b-pp2",
             [[[L + "attn.full/flash_decode/pallas_call:", 0, 9.0],
               [L + "mlp.dense/dot_general:", 10.0, 5.0]]]),
            ("xing4.0-29b-a4b-pp1",
             [[[L + "moe.experts/gmm/pallas_call:", 0, 9.0],
               [L + "moe.route/top_k:", 10.0, 5.0]]]),
            ("mistral-7b-int4",
             [[[L + "attn.kv_update/scatter:", 0, 9.0], ["", 10.0, 5.0]]])):
        for f in os.listdir(tmp_path):
            if f.startswith("scopes-"):
                os.remove(tmp_path / f)
        (tmp_path / "scopes-swa-w0.json").write_text(json.dumps(
            scopes_swa.reduce_scopes(ops)))
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
    # and without a trace at all (an untraced or a CPU run): only the two
    # counters' ratios could be read, and only from this family's program
    run = RunData(config=cut(), mix={}, records=[], t_open=0.0, t_close=51.0,
                  setup={}, device={"kind": "cpu"},
                  workers_before={"w0": old}, workers_after={"w0": old},
                  trace_dirs={}, trace=None)
    for name in NEW_HERE:
        assert reader(name)(run) is None, name
