"""The ``bailing_hybrid`` family's benchmark files: its counts at the cut
against the hand count of ISSUE 27, the scope reduction on a hand-made trace
whose answers are known, the ``*.ling`` readers on a run of another program
(they read nothing and do not raise), and the dense family's worker JSON and
``--deploy`` string, which the new family must not move."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import test_families as seam  # noqa: E402  (this directory: pytest puts it first)
from perfbench.lib import families, procs, scopes, session  # noqa: E402
from perfbench.lib.session import RunData  # noqa: E402
from perfbench.tools import rehearse  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
US = 1000.0
CELL = "ling3flash-reason-overload"


def cut():
    return session.load_config("ling-3.0-flash-ep4")


@pytest.fixture
def tiny(monkeypatch):
    """``ling-tiny`` lies in ``perfbench/rehearse/``, outside the
    ``configs/`` that ``test_families.py`` walks (``tools/rehearse.py`` says
    why): its questions are asked of it here, by name as there."""
    cfg = rehearse.load("ling-tiny")
    real = session.load_config
    monkeypatch.setattr(session, "load_config",
                        lambda n: dict(cfg) if n == "ling-tiny" else real(n))
    return cfg


@pytest.mark.parametrize("question", [
    seam.test_family_resolves_to_counts_and_a_reference,
    seam.test_param_bytes_are_the_bytes_of_the_tree,
    seam.test_serve_reaches_the_worker_and_the_coordinator_whole],
    ids=lambda q: q.__name__[5:])
def test_the_seams_questions_of_the_tiny_hybrid(question, tiny):
    assert tiny["platform"] == "cpu"
    question("ling-tiny")


def test_the_tiny_hybrids_chains_pass_its_reference_alone(tiny, tmp_path):
    made = {}

    def chains(name):
        return made.setdefault(name, seam.served_chains(tiny))
    seam.test_served_chains_pass_their_own_reference("ling-tiny", chains,
                                                     tmp_path)
    seam.test_another_familys_chains_fail_the_dense_reference(
        "ling-tiny", chains, tmp_path)


def test_every_rehearsal_kept_here_has_its_two_files():
    for config, mix in rehearse.REHEARSALS.values():
        assert rehearse.load(config)["serve"] and rehearse.load(mix)["prompt"]
        # not also in the accepted benchmark's directories
        assert not os.path.exists(
            os.path.join(HERE, "configs", f"{config}.json"))
        assert not os.path.exists(os.path.join(HERE, "traffic", f"{mix}.json"))


def test_the_family_answers_both_apis():
    cfg = cut()
    counts, ref = families.counts(cfg), families.reference(cfg)
    assert all(hasattr(counts, a) for a in families.COUNTS_API)
    assert all(hasattr(ref, a) for a in families.REFERENCE_API)
    assert families.int4_calls_per_pass(cfg) == 0
    assert "576 latent values" in counts.CACHE
    keys = {k for k, _f in ref.SPEC_PAIRS}
    assert keys >= {"kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                    "v_head_dim", "num_experts_published", "experts_held",
                    "n_group", "topk_group", "routed_scaling_factor",
                    "short_conv_kernel_size", "kda_lower_bound",
                    "kept_layers", "layer_kinds", "layer_mlps"}
    # layer_group_size and first_k_dense_replace reach the program through
    # the two lists they give, which the reference derives and checks
    assert list(ref.layer_kinds(cfg)) == [cfg["layer_kinds"],
                                          cfg["layer_mlps"]]


def test_the_familys_tie_limit_lies_between_its_two_chip_readings():
    """0.080 of max|logit|: the worst gap a served chain read on the chip;
    0.48: the smallest with the shared expert dropped (PERF.md section 6).
    The strict share is ``check.py``'s."""
    from perfbench.reference import check

    ref = families.reference(cut())
    assert 2 * 0.080 < ref.TIE_FRACTION < 0.48 / 2
    assert not hasattr(ref, "MIN_STRICT_SHARE")
    assert check.MIN_STRICT_SHARE == 0.5


def test_the_hand_count_of_the_cut():
    """ISSUE 27's arithmetic, in millions of parameters, and its bytes."""
    cfg = cut()
    c = families.counts(cfg)
    w = c.widths(cfg)
    expert = 3 * 2560 * 768
    kda = 6 * 2560 * 4096 + 2560 * 32 + 4 * 3 * 4096
    mla = 2560 * 6144 + 2560 * 576 + 512 * 8192 + 4096 * 2560 + 2560 * 32
    assert expert == 5_898_240 and c.expert_bytes(cfg) == 2 * expert
    assert round(kda / 1e6, 1) == 63.0 and round(mla / 1e6, 1) == 32.0
    assert (w["L_kda"], w["L_mla"], w["L_dense"], w["L_moe"]) == (6, 1, 1, 6)
    hand = (128 * 6 * expert + 6 * kda + mla + 3 * 2560 * 6144
            + 6 * (expert + 2560 * 512) + 2 * 39296 * 2560)
    assert round(hand / 1e9, 2) == 5.23
    got = c.param_bytes(cfg)
    assert abs(got - 2 * hand) < 0.005 * got        # + float32 router, norms
    assert round(got / 1e9, 1) == 10.5
    assert c.kv_bytes_per_token(cfg) == 1152
    assert c.state_bytes_per_slot(cfg) == 6 * (32 * 128 * 128 * 4
                                               + 3 * 12288 * 2)
    # expert matrices by ROUTED tokens: 6 layers x 8 choices x a quarter
    mats = {m[0]: m for m in c.weight_matmuls(cfg)}
    assert mats["expert_gate_up"][3] == 6 * 8 * 0.25
    assert mats["router"][2] == 512 and mats["router"][4] == "float32"
    # touched experts, never all held
    cost = c.expert_stream_cost(cfg, experts_touched=16, rows=16)
    assert 16 * 2 * expert <= cost["bytes"] < 17 * 2 * expert


def test_the_configuration_file_states_its_cut():
    cfg = cut()
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 42, "num_experts": 512,
                                "vocab_size": 157184}
    assert "mtp" in cfg["departures"] and "4 chips" in cfg["deployment"]
    assert cfg["serve"] == {
        "architecture": "ling", "size": "ling-3.0-flash-ep4",
        "quantized": False, "dtype": "bfloat16", "max_batch_size": 8,
        "max_seq_len": 3072, "page_size": 128, "num_pages": 192,
        "prefill_buckets": [512, 1024, 2048],
        "metadata": {"admission_max_rows": 1, "decode_steps_per_call": 16}}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ling-3.0-flash-ep4", "reason-overload-ling", 1)


def made_scoped_ops():
    """One device: a decode program (400 us) and a prefill (100 us)."""
    d = "jit(_decode_chunk)/jit(main)/while/body/"
    p = "jit(_prefill_pages)/jit(main)/"
    return [[[d + "moe.experts/gmm/pallas_call:", 0, 100 * US],
             [d + "moe.experts/gather:", 100 * US, 50 * US],
             [d + "moe.route/sort:", 150 * US, 30 * US],
             [d + "attn.kda.step/mul:", 180 * US, 120 * US],
             [d + "state.update/select_n:", 300 * US, 20 * US],
             [d + "attn.mla/attn.kv_update/select_n:", 320 * US, 10 * US],
             [d + "head.unembed/dot_general:", 330 * US, 20 * US],
             [d + "dot_general:", 350 * US, 50 * US],
             [p + "attn.kda.prefill/dot_general:", 500 * US, 60 * US],
             [p + "moe.experts/gmm/pallas_call:", 560 * US, 40 * US]]]


def test_scope_reduction_on_a_made_trace():
    red = scopes.reduce_scopes(made_scoped_ops())
    assert red["busy_s"] == pytest.approx(500e-6)
    sc = red["scopes"]
    assert sc["moe.experts"] == {"decode": pytest.approx(150e-6),
                                 "other": pytest.approx(40e-6)}
    assert sc["gmm"] == {"decode": pytest.approx(100e-6),
                         "other": pytest.approx(40e-6)}
    assert sc["attn.mla"]["decode"] == pytest.approx(10e-6)   # outer scope
    assert sc["attn.kda.prefill"]["other"] == pytest.approx(60e-6)
    assert scopes.reduce_scopes([[["jit(f)/mul:", 0, 5.0]]])["scopes"] == {}


def made_run(tmp_path):
    """A traced run of the cell: 80 decode steps between the slice's two
    stamps, which touched 6,400 experts for 1,280 held rows; over the window
    800 steps in 100 chunks touched 64,000 experts."""
    trace_dir = tmp_path / "trace-w0"
    trace_dir.mkdir()

    def stamp(steps, touched, rows):
        return {"models": {procs.MODEL: {
            "decode_steps": steps, "moe": {
                "experts_touched": touched,
                "decode_assignments_held": rows}}}}

    (trace_dir / "counters.json").write_text(json.dumps({
        "start": stamp(1500, 40000, 9000),
        "stop": stamp(1580, 46400, 10280)}))
    (tmp_path / "scopes-w0.json").write_text(json.dumps(
        scopes.reduce_scopes(made_scoped_ops())))

    def worker(steps, chunks, touched, rows, held, total):
        return {"models": {procs.MODEL: {
            "decode_steps": steps, "decode_chunks": chunks,
            "moe": {"experts_touched": touched,
                    "decode_assignments_held": rows,
                    "assignments_held": held, "assignments_total": total}}}}

    return RunData(
        config=cut(), mix={}, records=[], t_open=0.0, t_close=51.0, setup={},
        device={"kind": "TPU v5 lite"},
        workers_before={"w0": worker(1000, 125, 1000, 900, 500, 2000)},
        workers_after={"w0": worker(1800, 225, 65000, 13700, 3000, 12000)},
        trace_dirs={"w0": str(trace_dir)},
        trace={"program_s": {"decode": 0.4e-3}, "program_calls": {"decode": 10},
               "busy_s": 1.0, "window_s": 2.0, "between_programs_s": 0.2})


def reader(name):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("r_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def ling_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [m["name"] for m in json.load(f)["per_layer"]
                if CELL in m.get("workloads", [])]


def test_the_readers_on_a_made_run(tmp_path):
    run = made_run(tmp_path)
    assert scopes.decode_steps_in_slice(run) == pytest.approx(80.0)
    assert reader("model.decode_step_ms.overload")(run) == pytest.approx(5e-3)
    assert reader("moe.experts_time_share.overload")(run) == pytest.approx(38.0)
    assert reader("kda.time_share.ling")(run) == pytest.approx(36.0)
    assert reader("state.update_time_share.overload")(run) == pytest.approx(4.0)
    assert reader("moe.held_assignment_share.overload")(run) == pytest.approx(25.)
    assert reader("moe.experts_touched_per_step.overload")(run) == \
        pytest.approx(80.0)
    # 80 experts a step x 80 steps in the slice x 11.8 MB at 819 GB/s, over
    # the 150 us (scope) or 100 us (kernel) the made trace gives them
    counts = families.counts(run.config)
    cost = counts.expert_stream_cost(run.config, 80 * 80, 16 * 80)
    least = cost["bytes"] / 819e9
    assert reader("moe.expert_stream_roofline.overload")(run) == \
        pytest.approx(100 * least / 150e-6)
    assert reader("moe_gmm_roofline.overload")(run) == \
        pytest.approx(100 * least / 100e-6)
    assert reader("head.time_share.overload")(run) == pytest.approx(4.0)
    assert reader("device.idle_share.overload")(run) == pytest.approx(50.0)


def test_the_readers_read_nothing_from_another_program(tmp_path):
    """A traced run of another program (the dense decoder's: no such scope,
    no such counter, no stamps): every reader of the cell's own mechanism
    returns None and none raises."""
    trace_dir = tmp_path / "trace-w0"
    trace_dir.mkdir()
    (tmp_path / "scopes-w0.json").write_text(json.dumps(scopes.reduce_scopes(
        [[["jit(_decode_chunk)/while/body/attn.kv_update/scatter:", 0, 9.0],
          ["", 10.0, 5.0]]])))
    old = {"models": {procs.MODEL: {"live_slots": 3,
                                    "kv": {"utilization": 0.4}}}}
    run = RunData(
        config=cut(), mix={}, records=[],
        t_open=0.0, t_close=51.0, setup={}, device={"kind": "TPU v5 lite"},
        workers_before={"w0": old}, workers_after={"w0": old},
        trace_dirs={"w0": str(trace_dir)},
        trace={"program_s": {"decode": 1.0}, "program_calls": {"decode": 9},
               "decode_steps": 72.0})
    new = [n for n in ling_metrics() if n.split(".")[0] in (
        "model", "moe", "moe_gmm_roofline", "kda", "mla", "state")]
    assert len(new) == 10
    for name in new:
        assert reader(name)(run) is None, name


def test_the_dense_family_is_served_as_before():
    """The worker's model JSON and the coordinator's --deploy string for
    mistral-7b-int4, byte for byte what the parent commit wrote."""
    serve = session.load_config("mistral-7b-int4")["serve"]
    assert json.dumps(procs.model_dict(serve, 7), sort_keys=True) == (
        '{"architecture": "mistral", "max_batch_size": 8, "max_seq_len": '
        '1024, "metadata": {"continuous": 1, "num_pages": 64, "page_size": '
        '128, "prefill_buckets": [256, 512, 768], "seed": 7, "size": '
        '"mistral-7b", "warmup": 1, "weight_bits": 4}, "name": "bench", '
        '"quantized": true}')
    assert procs.deploy_spec(serve) == (
        "name=bench,architecture=mistral,size=mistral-7b,quantized=1,"
        "continuous=1,weight_bits=4,max_batch_size=8,max_seq_len=1024")
