"""The ``xing4_mhc`` family's benchmark files: its counts at the cut against
the hand count of ISSUE 31, every matrix of a pass named once, the expert
stream and the latent cache against hand-reckoned numbers, the residual
scope's helper on a recorded list of op paths, the ``*.xing`` readers on a
made run and on a run of another program (they read nothing and do not
raise), and the tiny rehearsal through the seam's own questions."""

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
    families, procs, scopes, scopes_mhc, session,
)
from perfbench.lib.session import RunData  # noqa: E402
from perfbench.tools import rehearse, rehearse_xing  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
US = 1000.0
CELL = "xing4-longdoc-overload"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def cut():
    return session.load_config("xing4.0-29b-a4b-pp1")


@pytest.fixture
def tiny(monkeypatch):
    """``xing-tiny`` lies in ``perfbench/rehearse/`` (``tools/rehearse.py``
    says why): the seam's questions are asked of it here, by name."""
    cfg = rehearse.load("xing-tiny")
    real = session.load_config
    monkeypatch.setattr(session, "load_config",
                        lambda n: dict(cfg) if n == "xing-tiny" else real(n))
    return cfg


@pytest.mark.parametrize("question", [
    seam.test_family_resolves_to_counts_and_a_reference,
    seam.test_param_bytes_are_the_bytes_of_the_tree,
    seam.test_serve_reaches_the_worker_and_the_coordinator_whole],
    ids=lambda q: q.__name__[5:])
def test_the_seams_questions_of_the_tiny_rehearsal(question, tiny):
    assert tiny["platform"] == "cpu"
    question("xing-tiny")


def test_the_tiny_chains_pass_its_reference_alone(tiny, tmp_path):
    made = {}

    def chains(name):
        return made.setdefault(name, seam.served_chains(tiny))
    seam.test_served_chains_pass_their_own_reference("xing-tiny", chains,
                                                     tmp_path)
    seam.test_another_familys_chains_fail_the_dense_reference(
        "xing-tiny", chains, tmp_path)


def test_the_rehearsal_has_its_two_files_outside_the_benchmarks():
    (config, mix), = rehearse_xing.REHEARSALS.values()
    assert rehearse.load(config)["serve"] and rehearse.load(mix)["prompt"]
    assert not os.path.exists(os.path.join(HERE, "configs", f"{config}.json"))
    assert not os.path.exists(os.path.join(HERE, "traffic", f"{mix}.json"))
    assert set(rehearse_xing.REHEARSALS).isdisjoint(rehearse.REHEARSALS)


def test_the_family_answers_both_apis():
    cfg = cut()
    counts, ref = families.counts(cfg), families.reference(cfg)
    assert all(hasattr(counts, a) for a in families.COUNTS_API)
    assert all(hasattr(ref, a) for a in families.REFERENCE_API)
    assert families.int4_calls_per_pass(cfg) == 0
    assert "576 latent values" in counts.CACHE and "EVERY" in counts.CACHE
    keys = {k for k, _f in ref.SPEC_PAIRS}
    # every width, the stream count, the rounds, the query rank, YaRN (the
    # whole rope_scaling group), the kept layers
    assert keys >= {"hidden_size", "intermediate_size",
                    "moe_intermediate_size", "num_attention_heads",
                    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                    "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
                    "num_experts_per_tok", "routed_scaling_factor",
                    "hc_mult", "hc_sinkhorn_iters", "hc_eps",
                    "mhc_h_res_clamp_min", "mhc_h_res_clamp_max",
                    "rope_theta", "rope_scaling", "vocab_size",
                    "kept_layers", "layer_mlps", "num_hidden_layers"}
    assert ref.layer_mlps(cfg) == cfg["layer_mlps"]
    assert len(ref.CONTROLS) == 8


def test_the_familys_limits_lie_between_their_two_chip_readings():
    """Each limit between the served chains' reading and the nearest
    control's, as the reference file gives them (one v5e chip, PR 31 review
    round, 16 + 2 short chains and the long one), with room on both sides;
    the short chains' limits are what ``correct`` judges by and are TIGHTER
    than ``check.py``'s, the ``LONG_*`` ones the builder's long chain's."""
    from perfbench.reference import check

    ref = families.reference(cut())
    # gaps: served 0.0062 at most, H_res transposed 0.0424 at least
    assert 3 * 0.0062 < ref.TIE_FRACTION < 0.0424 / 1.5
    assert ref.TIE_FRACTION < check.TIE_FRACTION
    # strict of 24: served 22 at least, H_res transposed 19 at most
    need = ref.MIN_STRICT_SHARE * 24
    assert 19 < need <= 22 - 2 and ref.MIN_STRICT_SHARE > check.MIN_STRICT_SHARE
    assert 0.0062 < ref.LONG_TIE_FRACTION < 0.0156
    assert 58 < ref.LONG_MIN_STRICT_SHARE * 64 <= 62 - 1
    # the chip's readings through the harness's own judge: every served
    # chain inside, every chain of a wrong model refused
    import numpy as np

    def chain(strict, gap):
        """24 reference rows whose judged tokens read ``strict`` exact
        matches and a worst gap of ``gap`` x max|logit|."""
        lg = np.zeros((24, 8), np.float32)
        lg[:, 0] = 1.0                       # the argmax, max|logit| = 1
        lg[:, 1] = 1.0 - gap
        toks = [0] * strict + [1] * (24 - strict)
        return check.judge(lg, 1, toks, ref.TIE_FRACTION,
                           ref.MIN_STRICT_SHARE)["ok"]

    assert chain(22, 0.0062) and chain(24, 0.0)
    for strict, gap in ((19, 0.0424), (12, 0.1304), (17, 0.0984)):
        assert not chain(strict, gap) and not chain(24 - 1, gap)
        assert not chain(strict, 0.001)
    assert set(ref.NOT_SEPARATED) == {"bfloat16", "mhc_bfloat16"}
    assert "mhc_bfloat16" in ref.CONTROLS


def test_the_configuration_file_keeps_every_published_number():
    cfg = cut()
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Xing4.0-29B-A4B")
    assert cfg["source"] == entry["source_url"]
    changed = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["published"] == {"num_hidden_layers": 40}
    assert cfg["kept_layers"][0] == 0 and cfg["kept_layers"][1] == 2
    assert len(cfg["kept_layers"]) == cfg["num_hidden_layers"] >= 5
    assert cfg["experts_held"] == [0, 64] and cfg["vocab_size"] == 131072
    assert "mtp" in cfg["departures"] and "ep_size 1" in cfg["deployment"]
    assert {"hc_mult / residual", "mHC maps", "rope_scaling",
            "weights"} <= set(cfg["assumed"])
    serve = cfg["serve"]
    pages = -(-serve["max_seq_len"] // serve["page_size"])
    assert serve["num_pages"] == serve["max_batch_size"] * pages == 544
    assert max(serve["prefill_buckets"]) == 8192 < serve["max_seq_len"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    (cell,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert cell["config"] == "xing4.0-29b-a4b-pp1" and cell["chips"] == 1
    mix = json.load(open(os.path.join(HERE, "traffic",
                                      f"{cell['traffic']}.json")))
    assert (mix["prompt"], mix["output"]) == (
        {"median": 3072, "sigma": 0.7, "min": 512, "max": 8192},
        {"median": 256, "sigma": 0.6, "min": 64, "max": 512})
    assert mix["prompt"]["max"] + mix["output"]["max"] <= serve["max_seq_len"]


def test_the_hand_count_of_the_cut():
    """ISSUE 31's arithmetic, in millions of parameters, and its bytes."""
    cfg = cut()
    c = families.counts(cfg)
    w = c.widths(cfg)
    mla = (3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256
           + 32 * 128 * 3584)
    expert = 3 * 3584 * 1024
    mhc = 2 * (24 * 4 * 3584 + 27)
    dense = 3 * 3584 * 9216
    assert round(mla / 1e6, 2) == 28.41
    assert sum(k * n for _n, k, n in c.mla_matrices(cfg)) == mla
    assert round(expert / 1e6, 2) == 11.01 and c.expert_bytes(cfg) == 2 * expert
    assert round(mhc / 1e6, 2) == 0.69 and round(dense / 1e6, 2) == 99.09
    expert_layer = mla + 65 * expert + 3584 * 64 + mhc
    dense_layer = mla + dense + mhc
    assert round(expert_layer / 1e6, 1) == 745.0
    assert round(dense_layer / 1e6, 1) == 128.2
    assert (w["L"], w["L_dense"], w["L_moe"]) == (
        len(cfg["kept_layers"]), 1, len(cfg["kept_layers"]) - 1)
    hand = (w["L_moe"] * expert_layer + dense_layer + 2 * 131072 * 3584)
    got = c.param_bytes(cfg)
    # + the float32 halves of router and mHC, norms, expert bias
    assert 2 * hand < got < 2 * hand * 1.002
    if w["L_moe"] == 6:
        assert round(hand / 1e6) == 5538 and round(got / 1e9, 2) == 11.09
    assert c.kv_bytes_per_token(cfg) == w["L"] * 1152
    serve = cfg["serve"]
    pool = serve["num_pages"] * serve["page_size"] * c.kv_bytes_per_token(cfg)
    assert round(pool / 1e9, 2) == round(0.0802 * w["L"], 2)


def test_weight_matmuls_names_every_matrix_once():
    cfg = cut()
    c = families.counts(cfg)
    w = c.widths(cfg)
    mats = c.weight_matmuls(cfg)
    names = [m[0] for m in mats]
    assert len(names) == len(set(names)) == 14
    by = {m[0]: m for m in mats}
    for name in ("mla_q_a", "mla_q_b", "mla_kva", "mla_kvb", "mla_out"):
        assert by[name][3] == w["L"]
    assert by["mla_q_a"][1:3] == (3584, 768)
    assert by["mla_q_b"][1:3] == (768, 32 * 192)
    assert by["mla_kva"][1:3] == (3584, 576)
    assert by["mla_kvb"][1:3] == (512, 32 * 256)
    assert by["mla_out"][1:3] == (32 * 128, 3584)
    # two maps a layer, float32, 4 x 3584 -> 24
    assert by["mhc_phi"] == ("mhc_phi", 4 * 3584, 24, 2 * w["L"], "float32")
    assert by["dense_gate_up"][3] == by["dense_down"][3] == 1
    assert by["router"] == ("router", 3584, 64, w["L_moe"], "float32")
    assert by["shared_gate_up"][2] == 2048 and by["shared_down"][1] == 1024
    # expert matrices by ROUTED tokens: 4 choices a token a layer, never 64
    assert by["expert_gate_up"][3] == by["expert_down"][3] == 4 * w["L_moe"]
    assert by["lm_head"][1:4] == (3584, 131072, 1)
    # the tree's own names, for what is a matrix there
    import jax

    from distributed_inference_engine_tpu.models import xing

    spec = xing.xing_spec("xing4.0-pp1")
    dense_l, moe_l = (jax.eval_shape(
        lambda m=m, i=i: xing._init_layer(spec, m, i, jax.random.key(0)))
        for m, i in (("dense", 0), ("moe", 2)))
    assert dense_l["w_qa"].shape == by["mla_q_a"][1:3]
    assert dense_l["w_qb"].shape == by["mla_q_b"][1:3]
    assert dense_l["hc_attn"]["phi"].shape == by["mhc_phi"][1:3]
    assert dense_l["w_gate_up"].shape == by["dense_gate_up"][1:3]
    assert moe_l["w_gate_up"].shape == (64,) + by["expert_gate_up"][1:3]
    assert moe_l["w_down"].shape == (64,) + by["expert_down"][1:3]
    assert moe_l["w_router"].shape == by["router"][1:3]


def test_expert_stream_and_latent_cache_costs_by_hand():
    cfg = cut()
    c = families.counts(cfg)
    w = c.widths(cfg)
    expert = 2 * 3 * 3584 * 1024                       # 22.0 MB
    # touched experts, never all held: 25 of 64 in each of 6 layers
    cost = c.expert_stream_cost(cfg, experts_touched=150, rows=192)
    assert 150 * expert <= cost["bytes"] < 151 * expert
    assert cost["flops"] == 2.0 * 192 * 3 * 3584 * 1024
    # 8 rows at 3,000 tokens of context, one step: 24,000 rows x 1152 B a
    # layer; live rows, not the table, and not the layer's matrices (their
    # read is not all inside the scope whose seconds divide these bytes)
    mla = c.mla_decode_cost(cfg, context_rows=24000)
    per_layer = 24000 * 1152
    assert mla["bytes"] == w["L"] * per_layer
    assert mla["flops"] == w["L"] * 24000 * 32 * (2 * 576 + 2 * 512)
    table = 8 * 8704 * 1152
    assert per_layer < 0.35 * table


# ------------------------------------------------------------------ scopes

# op paths as a v5e trace of the cell's two programs names them (recorded
# from the CPU lowering's metadata of xing-tiny, prefixes as XLA writes
# them); [path, start ns, duration ns]
D = "jit(_decode_chunk)/jit(main)/while/body/"
P = "jit(_prefill_pages)/jit(main)/"
RECORDED = [[
    [D + "resid.mhc/dot_general:", 0, 10 * US],
    [D + "resid.mhc/div:", 10 * US, 30 * US],
    [D + "attn.mla/dot_general:", 40 * US, 50 * US],
    [D + "attn.mla/attn.kv_update/select_n:", 90 * US, 10 * US],
    [D + "resid.mhc/add:", 100 * US, 20 * US],
    [D + "moe.route/sort:", 120 * US, 20 * US],
    [D + "moe.experts/gmm/pallas_call:", 140 * US, 100 * US],
    [D + "moe.shared/dot_general:", 240 * US, 20 * US],
    [D + "head.unembed/dot_general:", 260 * US, 30 * US],
    [D + "sample/reduce_max:", 290 * US, 10 * US],
    [P + "resid.mhc/mul:", 300 * US, 60 * US],
    [P + "attn.mla/dot_general:", 360 * US, 100 * US],
    [P + "moe.experts/gmm/pallas_call:", 460 * US, 40 * US]]]


def test_the_residual_scope_on_recorded_op_paths():
    red = scopes_mhc.reduce_scopes(RECORDED)
    assert red["busy_s"] == pytest.approx(500e-6)
    assert red["scopes"] == {"resid.mhc": {
        "decode": pytest.approx(60e-6), "other": pytest.approx(60e-6)}}
    # the scopes both per-layer families carry, and the two around every
    # model's head, are lib/scopes.py's
    shared = scopes.reduce_scopes(RECORDED)["scopes"]
    assert shared["head.unembed"] == {"decode": pytest.approx(30e-6),
                                      "other": 0.0}
    assert shared["sample"] == {"decode": pytest.approx(10e-6), "other": 0.0}
    assert shared["attn.mla"] == {"decode": pytest.approx(60e-6),
                                  "other": pytest.approx(100e-6)}
    assert shared["gmm"]["decode"] == pytest.approx(100e-6)
    assert "resid.mhc" not in shared
    assert scopes_mhc.reduce_scopes([[["jit(f)/mul:", 0, 5.0]]])["scopes"] \
        == {}


def test_the_programs_name_the_scope_the_helper_reads():
    """``resid.mhc`` is on the op paths of both programs of this family
    (``head.unembed`` and ``sample`` are opened by ``models/base.py`` and
    ``ops/sampling.py`` in the engine's programs, for every family)."""
    import jax
    import jax.numpy as jnp

    from distributed_inference_engine_tpu.models import xing

    spec = xing.xing_spec("xing-tiny", max_seq_len=64)
    params = jax.eval_shape(lambda: xing.init_params(spec, jax.random.key(0)))
    text = jax.jit(lambda p, t, n, pg, st, tb, sl:
                   xing.forward_prefill_into_pages(
                       spec, p, t, n, pg, st, tb, sl)).lower(
        params, jnp.zeros((1, 16), jnp.int32), jnp.ones((1,), jnp.int32),
        jnp.zeros((4, 4, 16, 40), jnp.bfloat16), xing.init_state(spec, 1),
        jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32)
    ).as_text(debug_info=True)
    for scope in scopes_mhc.SCOPES + ("attn.mla", "moe.route",
                                      "moe.experts", "moe.shared"):
        assert f"/{scope}/" in text, scope


def made_run(tmp_path):
    """A traced run of the cell: 160 decode steps between the slice's two
    stamps (150 experts, 192 held rows and 28,000 latent rows a step); over
    the window 1,600 steps in 100 chunks."""
    trace_dir = tmp_path / "trace-w0"
    trace_dir.mkdir()

    def stamp(steps, touched, rows, ctx):
        return {"models": {procs.MODEL: {
            "decode_steps": steps,
            "moe": {"experts_touched": touched,
                    "decode_assignments_held": rows},
            "mla": {"decode_context_rows": ctx}}}}

    (trace_dir / "counters.json").write_text(json.dumps({
        "start": stamp(2000, 5000, 7000, 10 ** 6),
        "stop": stamp(2160, 5000 + 150 * 160, 7000 + 192 * 160,
                      10 ** 6 + 28000 * 160)}))
    (tmp_path / "scopes-w0.json").write_text(json.dumps(
        scopes.reduce_scopes(RECORDED)))
    (tmp_path / "scopes-mhc-w0.json").write_text(json.dumps(
        scopes_mhc.reduce_scopes(RECORDED)))

    def worker(steps, chunks, touched, rows, ctx, table):
        return {"models": {procs.MODEL: {
            "decode_steps": steps, "decode_chunks": chunks,
            "moe": {"experts_touched": touched,
                    "decode_assignments_held": rows,
                    "assignments_held": rows, "assignments_total": rows},
            "mla": {"decode_context_rows": ctx,
                    "decode_table_rows": table}}}}

    return RunData(
        config=cut(), mix={}, records=[], t_open=0.0, t_close=51.0, setup={},
        device={"kind": "TPU v5 lite"},
        workers_before={"w0": worker(1000, 100, 1000, 900, 10 ** 6,
                                     10 ** 7)},
        workers_after={"w0": worker(2600, 200, 241000, 307300 + 900,
                                    10 ** 6 + 1600 * 28000,
                                    10 ** 7 + 1600 * 8 * 8704)},
        trace_dirs={"w0": str(trace_dir)},
        trace={"program_s": {"decode": 1.6e-3, "prefill": 0.3},
               "program_calls": {"decode": 10, "prefill": 2},
               "busy_s": 1.0, "window_s": 2.0, "between_programs_s": 0.2})


def reader(name):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("r_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_readers_on_a_made_run(tmp_path):
    run = made_run(tmp_path)
    assert scopes.decode_steps_in_slice(run) == pytest.approx(160.0)
    assert reader("model.decode_step_ms.overload")(run) == pytest.approx(1e-2)
    assert reader("model.prefill_time_share.overload")(run) == pytest.approx(30.)
    assert reader("mhc.time_share.xing")(run) == pytest.approx(24.0)
    assert reader("head.time_share.overload")(run) == pytest.approx(8.0)
    assert reader("mhc.decode_step_ms.xing")(run) == \
        pytest.approx(1e3 * 60e-6 / 160)
    assert reader("mla.time_share.overload")(run) == pytest.approx(32.0)
    assert reader("moe.experts_time_share.overload")(run) == pytest.approx(28.0)
    assert reader("moe.route_time_share.overload")(run) == pytest.approx(4.0)
    assert reader("moe.experts_touched_per_step.overload")(run) == \
        pytest.approx(150.0)
    assert reader("mla.table_live_share.overload")(run) == \
        pytest.approx(100.0 * 28000 / (8 * 8704))
    counts = families.counts(run.config)
    # 28,000 live rows a step x 160 steps in the slice, over the 60 us the
    # recorded paths give attn.mla in decode programs
    mla = counts.mla_decode_cost(run.config, 28000 * 160)
    assert reader("mla.decode_roofline.overload")(run) == \
        pytest.approx(100 * mla["bytes"] / 819e9 / 60e-6)
    cost = counts.expert_stream_cost(run.config, 150 * 160, 192 * 160)
    assert reader("moe_gmm_roofline.overload")(run) == \
        pytest.approx(100 * cost["bytes"] / 819e9 / 100e-6)
    assert reader("moe.expert_stream_roofline.overload")(run) == \
        reader("moe_gmm_roofline.overload")(run)
    assert reader("device.idle_share.overload")(run) == pytest.approx(50.0)


def test_the_readers_read_nothing_from_another_program(tmp_path):
    """Another program's trace under this family's configuration (the Ling
    program's scopes, then the dense decoder's: no ``resid.mhc``, no ``mla``
    counters, no stamps): the family's own readers return None and none
    raises."""
    trace_dir = tmp_path / "trace-w0"
    trace_dir.mkdir()
    old = {"models": {procs.MODEL: {
        "decode_steps": 10, "decode_chunks": 1, "live_slots": 3,
        "kv": {"utilization": 0.4}}}}
    new_here = ["mhc.time_share.xing", "mhc.decode_step_ms.xing",
                "model.prefill_time_share.overload",
                "mla.decode_roofline.overload",
                "mla.table_live_share.overload"]
    for cfg_name, ops in (
            ("xing4.0-29b-a4b-pp1",
             [[[D + "attn.mla/dot_general:", 0, 9.0],
               [D + "moe.experts/gmm/pallas_call:", 10.0, 5.0]]]),
            ("xing4.0-29b-a4b-pp1",
             [[[D + "attn.kv_update/scatter:", 0, 9.0], ["", 10.0, 5.0]]])):
        for f in os.listdir(tmp_path):
            if f.startswith("scopes-"):
                os.remove(tmp_path / f)
        (tmp_path / "scopes-w0.json").write_text(json.dumps(
            scopes.reduce_scopes(ops)))
        (tmp_path / "scopes-mhc-w0.json").write_text(json.dumps(
            scopes_mhc.reduce_scopes(ops)))
        run = RunData(
            config=session.load_config(cfg_name), mix={}, records=[],
            t_open=0.0, t_close=51.0, setup={},
            device={"kind": "TPU v5 lite"},
            workers_before={"w0": old}, workers_after={"w0": old},
            trace_dirs={"w0": str(trace_dir)},
            trace={"program_s": {"decode": 1.0, "prefill": 0.5},
                   "program_calls": {"decode": 9}, "busy_s": 2.0,
                   "decode_steps": 72.0})
        for name in new_here:
            assert reader(name)(run) is None, (cfg_name, name)
    # (the last run: no op under any of lib/scopes.py's scopes, the head's
    # two among them, so nothing to take a share of)
    assert reader("head.time_share.overload")(run) is None
