"""The ``mla_moe_share`` family's benchmark files (Kimi-K2.5 as one chip of
a DP-attention + EP32 deployment holds it): its counts at the cut against
the hand count of ISSUE 41 and against the tree (by shapes only), every
matrix of a pass named once, the byte functions the rooflines read against
hand-reckoned numbers, the family's scopes and the latent kernel on a
recorded list of op paths, the ``*.kimi`` readers on a made run and on a run
of another program (they read nothing and do not raise), the wrong models of
the reference against ``check.py``'s judge at the tiny size, and the tiny
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
    families, procs, scopes, scopes_mla_share, session,
)
from perfbench.lib.session import RunData  # noqa: E402
from perfbench.tools import rehearse, rehearse_kimi  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
US = 1000.0
CELL = "kimik25-agent-overload"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def cut():
    return session.load_config("kimi-k2.5-ep32-pp1")


@pytest.fixture
def tiny(monkeypatch):
    """``kimi-tiny`` lies in ``perfbench/rehearse/`` (``tools/rehearse.py``
    says why): the seam's questions are asked of it here, by name."""
    cfg = rehearse.load("kimi-tiny")
    real = session.load_config
    monkeypatch.setattr(session, "load_config",
                        lambda n: dict(cfg) if n == "kimi-tiny" else real(n))
    return cfg


@pytest.mark.parametrize("question", [
    seam.test_family_resolves_to_counts_and_a_reference,
    seam.test_param_bytes_are_the_bytes_of_the_tree,
    seam.test_serve_reaches_the_worker_and_the_coordinator_whole],
    ids=lambda q: q.__name__[5:])
def test_the_seams_questions_of_the_tiny_rehearsal(question, tiny):
    assert tiny["platform"] == "cpu"
    question("kimi-tiny")


@pytest.fixture(scope="module")
def tiny_chains():
    return seam.served_chains(rehearse.load("kimi-tiny"))


def test_the_tiny_chains_pass_its_reference_alone(tiny, tiny_chains,
                                                  tmp_path):
    def chains(_name):
        return tiny_chains
    seam.test_served_chains_pass_their_own_reference("kimi-tiny", chains,
                                                     tmp_path)
    seam.test_another_familys_chains_fail_the_dense_reference(
        "kimi-tiny", chains, tmp_path)


def test_the_wrong_models_at_the_tiny_size(tiny, tiny_chains):
    """``check.py``'s judge with the family's own limits, the same served
    chains against the reference with ONE named term wrong: at width 64 a
    wrong term moves a logit by 1e-3 to 4e-2 (``tests/test_kimi.py`` holds
    each at that level in float32) and no token need show it: the served
    chains must pass, and a control must be computable by the judge's
    path; the chip's long chain is where the controls are refused."""
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
    # the judge takes a control's logits as it takes the reference's; what
    # it says of them at this width binds nothing
    assert len(verdicts(control="no_shared_expert")) == len(tiny_chains)


def test_the_rehearsal_has_its_two_files_outside_the_benchmarks():
    (config, mix), = rehearse_kimi.REHEARSALS.values()
    assert rehearse.load(config)["serve"] and rehearse.load(mix)["prompt"]
    assert not os.path.exists(os.path.join(HERE, "configs", f"{config}.json"))
    assert not os.path.exists(os.path.join(HERE, "traffic", f"{mix}.json"))
    assert set(rehearse_kimi.REHEARSALS).isdisjoint(rehearse.REHEARSALS)
    with open(os.path.join(HERE, "rehearsal.json")) as f:
        assert "kimi" not in f.read()


def test_the_family_answers_both_apis():
    cfg = cut()
    counts, ref = families.counts(cfg), families.reference(cfg)
    assert all(hasattr(counts, a) for a in families.COUNTS_API)
    assert all(hasattr(ref, a) for a in families.REFERENCE_API)
    assert families.int4_calls_per_pass(cfg) == 0
    assert "576 latent values" in counts.CACHE and "64" in counts.CACHE
    spec = seam.program_spec(cfg)
    for key, field in ref.SPEC_PAIRS:
        assert cfg[key] == getattr(spec, field), key
    assert spec.hc_mult == 0 and spec.n_experts == 384
    # the reference imports nothing from ops/ or from another reference
    with open(os.path.join(HERE, "reference", "mla_moe_share.py")) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert not [i for i in imports if "ops" in i or "xing4_mhc" in i
                or "reference" in i], imports


def test_the_configuration_is_the_catalogs_with_three_cuts():
    cfg = cut()
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Kimi-K2.5")
    assert cfg["source"] == entry["source_url"]
    differs = sorted(k for k, v in entry["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {k: entry["config"][k] for k in cfg["reduced"]}
    # the floors of a model_config cut: four expert layers after the dense
    # one, 8 routed experts, an eighth of the vocabulary; no width touched
    assert cfg["layer_mlps"].count("moe") >= 4
    assert cfg["n_routed_experts"] >= 8 == cfg["num_experts_per_tok"]
    assert cfg["vocab_size"] * 8 == entry["config"]["vocab_size"]
    assert cfg["num_experts_published"] == 384
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]]
    assert "vision_tower" in cfg["departures"]
    serve = cfg["serve"]
    assert serve["max_batch_size"] == 32 >= 16
    assert len(serve["prefill_buckets"]) <= 5
    assert all(b % 512 == 0 for b in serve["prefill_buckets"])
    assert serve["num_pages"] * serve["page_size"] == 32 * serve["max_seq_len"]
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry_,) = [c for c in man["configs"] if c["name"] == cfg["name"]]
    assert entry_["reduced"] == cfg["reduced"]
    (cell,) = [w for w in man["workloads"] if w["config"] == cfg["name"]]
    assert (cell["name"], cell["chips"]) == (CELL, 1)
    mix = json.load(open(os.path.join(HERE, "traffic",
                                      f"{cell['traffic']}.json")))
    assert mix["prompt"] == {"median": 1536, "sigma": 0.8, "min": 256,
                             "max": 6144}
    assert mix["output"] == {"median": 512, "sigma": 0.6, "min": 128,
                             "max": 1536}
    assert (mix["strata"], mix["ramp_s"], mix["tail_s"]) == (6, 10.0, 10.0)
    # the longest request fits a slot
    assert 6144 + 1536 <= serve["max_seq_len"]


def test_the_hand_count_of_the_cut():
    """ISSUE 41's bytes, in millions of parameters (bf16 = 2 B each)."""
    cfg = cut()
    c = families.counts(cfg)
    mla = dict((n, k * m) for n, k, m in c.mla_matrices(cfg))
    assert [round(v / 1e6, 2) for v in mla.values()] == [
        11.01, 18.87, 4.13, 8.39, 58.72]
    assert round(sum(mla.values()) / 1e6, 1) == 101.1
    assert c.expert_bytes(cfg) == 3 * 7168 * 2048 * 2 == 88080384
    w = c.widths(cfg)
    assert (w["L"], w["L_dense"], w["L_moe"], w["held"], w["E"], w["k"]) == (
        7, 1, 6, 12, 384, 8)
    assert round(c.param_bytes(cfg) / 1e9, 2) == 9.73
    assert c.kv_bytes_per_token(cfg) == 7 * 1152
    # outside the routed experts and the embedding: layer 0 1.00 GB, six
    # layers of MLA 202 + shared 88 + router 11 MB, the head 0.29 GB
    assert round(c.step_weight_bytes(cfg) / 1e9, 2) == 3.10


def test_counts_are_the_cut_trees_bytes_by_shapes():
    import jax

    from distributed_inference_engine_tpu.models import xing

    cfg = cut()
    spec = seam.program_spec(cfg)
    tree = jax.eval_shape(lambda: xing.init_params(spec, jax.random.key(0)))
    have = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert have == families.counts(cfg).param_bytes(cfg)


def test_weight_matmuls_names_every_matrix_once():
    cfg = cut()
    mats = families.counts(cfg).weight_matmuls(cfg)
    names = [m[0] for m in mats]
    assert len(names) == len(set(names)) == 13
    by = {m[0]: m for m in mats}
    assert by["router"][1:] == (7168, 384, 6, "float32")
    assert by["mla_q_b"][1:4] == (1536, 64 * 192, 7)
    assert by["lm_head"][1:4] == (7168, 20480, 1)
    # a token sends this chip 8 x 12 / 384 = 0.25 assignments a layer
    assert by["expert_down"][3] == pytest.approx(6 * 0.25)


def test_the_byte_functions_by_hand():
    cfg = cut()
    c = families.counts(cfg)
    ex = c.expert_stream_cost(cfg, 35, 48)
    assert ex["bytes"] == 35 * 88080384 + 48 * (6 * 7168 + 6 * 2048)
    assert ex["flops"] == 2.0 * 48 * 3 * 7168 * 2048
    kv = c.mla_decode_cost(cfg, 1000)
    assert kv["bytes"] == 7 * 1000 * 1152
    assert kv["flops"] == 7 * 1000 * 64 * (2 * 576 + 2 * 512)
    # 139 kFLOP for each 1,152 B row: 121 FLOP/B, under the v5e's ridge of
    # 240, so the HBM peak binds
    assert 120 < kv["flops"] / kv["bytes"] < 122 < 197e12 / 819e9
    whole = c.decode_stream_cost(cfg, 10, 350, 80, 10 * 60000, 320)
    assert whole["bytes"] == (10 * c.step_weight_bytes(cfg)
                              + c.expert_stream_cost(cfg, 350, 80)["bytes"]
                              + c.mla_decode_cost(cfg, 600000)["bytes"])
    # ISSUE 41's reckoning of a step at 32 rows: ~6.9 GB
    step = c.decode_stream_cost(cfg, 1, 35.4, 8, 32 * 2400, 32)
    assert 6.5e9 < step["bytes"] < 7.3e9
    assert step["flops"] / 197e12 < step["bytes"] / 819e9


# ------------------------------------------------------------------ scopes

# op paths as a v5e trace of the cell's two programs names them (prefixes
# as XLA writes them); [path, start ns, duration ns]
D = "jit(_decode_chunk)/jit(main)/while/body/"
P = "jit(_prefill_pages)/jit(main)/"
K = "attn.mla/jit(latent_decode_attention_pallas)/"
RECORDED = [[
    [D + "attn.mla/dot_general:", 0, 60 * US],
    [D + "attn.mla/attn.kv_update/select_n:", 60 * US, 5 * US],
    [D + K + "pallas_call:", 65 * US, 30 * US],
    [D + K + "pad:", 95 * US, 1 * US],
    [D + "moe.route/top_k:", 96 * US, 14 * US],
    [D + "moe.experts/while/body/gather:", 110 * US, 10 * US],
    [D + "moe.experts/while/body/gmm/pallas_call:", 120 * US, 80 * US],
    [D + "moe.shared/dot_general:", 200 * US, 40 * US],
    [D + "head.unembed/dot_general:", 240 * US, 30 * US],
    [D + "sample/argmax:", 270 * US, 10 * US],
    [D + "add:", 280 * US, 20 * US],
    [P + "attn.mla/mla_prefill_flash/pallas_call:", 300 * US, 90 * US],
    [P + "moe.route/top_k:", 390 * US, 10 * US],
    [P + "moe.experts/while/body/gmm/pallas_call:", 400 * US, 100 * US]]]


def test_the_familys_scopes_on_recorded_op_paths():
    red = scopes_mla_share.reduce_scopes(RECORDED)
    assert red["busy_s"] == pytest.approx(500e-6)
    sc = red["scopes"]
    assert sc["attn.mla"] == {"decode": pytest.approx(96e-6),
                              "other": pytest.approx(90e-6)}
    assert sc["attn.kv_update"]["decode"] == pytest.approx(5e-6)
    assert sc["moe.route"] == {"decode": pytest.approx(14e-6),
                               "other": pytest.approx(10e-6)}
    # a nested scope is counted under both names, inside the held-rows loop
    assert sc["moe.experts"] == {"decode": pytest.approx(90e-6),
                                 "other": pytest.approx(100e-6)}
    assert sc["gmm"] == {"decode": pytest.approx(80e-6),
                         "other": pytest.approx(100e-6)}
    assert sc["moe.shared"]["decode"] == pytest.approx(40e-6)
    assert sc["head.unembed"]["decode"] == pytest.approx(30e-6)
    assert sc["sample"]["decode"] == pytest.approx(10e-6)
    # the kernel: the ONE path of its jit that takes most of its time
    assert (red["kernel_calls"], red["kernel_s"]) == (1, pytest.approx(30e-6))
    two = scopes_mla_share.reduce_scopes([RECORDED[0] + [
        [D + K + "pallas_call:", 600 * US, 20 * US]]])
    assert (two["kernel_calls"], two["kernel_s"]) == (2, pytest.approx(50e-6))
    assert scopes_mla_share.reduce_scopes(
        [[["jit(f)/mul:", 0, 5.0]]])["scopes"] == {}


def made_run(tmp_path):
    """A traced run of the cell: 10 decode programs of 16 steps in the
    slice, the first cut by the slice's start so that the latent kernel ran
    150 steps x 7 layers there; over the window 1,600 steps in 100 chunks,
    32 rows live at a context of 2,500; between the worker's two stamps of
    the traced slice (``counters.json``) the rows' contexts are 2,000, 36
    experts a step got a row and 8 assignments a layer were held: what the
    rooflines divide by the slice's seconds is the slice's own."""
    trace_dir = tmp_path / "trace-w0"
    trace_dir.mkdir()
    (tmp_path / "scopes-w0.json").write_text(json.dumps(
        scopes.reduce_scopes(RECORDED)))
    (tmp_path / "scopes-mla-share-w0.json").write_text(json.dumps(
        dict(scopes_mla_share.reduce_scopes(RECORDED),
             kernel_calls=150 * 7, kernel_s=0.2)))

    def worker(steps, chunks, context, table, touched, pairs, held, total,
               tokens):
        return {"models": {procs.MODEL: {
            "decode_steps": steps, "decode_chunks": chunks,
            "total_generated_tokens": tokens,
            "mla": {"decode_context_rows": context,
                    "decode_table_rows": table},
            "moe": {"experts_touched": touched,
                    "decode_assignments_held": pairs,
                    "assignments_held": held, "assignments_total": total}}}}

    (trace_dir / "counters.json").write_text(json.dumps({
        "start": worker(2300, 180, 10 ** 8, 0, 10 ** 5, 10 ** 5, 0, 0,
                        10 ** 6),
        "stop": worker(2460, 190, 10 ** 8 + 160 * 64000, 0,
                       10 ** 5 + 160 * 36, 10 ** 5 + 160 * 48, 0, 0,
                       10 ** 6 + 160 * 32)}))
    return RunData(
        config=cut(), mix={}, records=[], t_open=0.0, t_close=51.0, setup={},
        device={"kind": "TPU v5 lite"},
        workers_before={"w0": worker(1000, 100, 10 ** 6, 10 ** 6, 5000, 5000,
                                     10 ** 4, 10 ** 6, 10 ** 5)},
        workers_after={"w0": worker(
            2600, 200, 10 ** 6 + 1600 * 80000,
            10 ** 6 + 1600 * (32 * 21 * 128 + 32 * 16),
            5000 + 1600 * 35, 5000 + 1600 * 48,
            10 ** 4 + 3125 * 100, 10 ** 6 + 10 ** 5 * 100,
            10 ** 5 + 1600 * 32)},
        samples=[
            {"t": 46.5, "workers": {"w0": worker(2000, 150, 0, 0, 0, 0, 0, 0,
                                                 0)}},
            {"t": 50.75, "workers": {"w0": worker(2450, 190, 0, 0, 0, 0, 0,
                                                  0, 0)}}],
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
            "mla.time_share.overload", "mla.table_live_share.overload",
            "moe.experts_time_share.overload", "moe.route_time_share.overload",
            "moe.experts_touched_per_step.overload",
            "moe.held_assignment_share.overload", "head.time_share.overload",
            "model.decode_stream_roofline.overload", "mla.decode_roofline.overload",
            "moe.expert_stream_roofline.overload", "moe_gmm_roofline.overload"]


def test_the_cells_readings_stand_under_the_shared_names():
    """Every reading of the family is an entry WITHOUT the family's suffix
    that lists the cell (PR 49: one reader a reading); none is left under
    ``.kimi``."""
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by = {m["name"]: m for m in man["per_layer"]}
    assert all(CELL in by[n]["workloads"] and by[n]["moves"] == "out_tok_s"
               for n in NEW_HERE)
    assert not [n for n in by if n.endswith(".kimi")]


def test_the_readers_on_a_made_run(tmp_path):
    run = made_run(tmp_path)
    # the steps are the kernel's calls in the slice, not whole programs
    assert scopes.decode_steps_in_slice(run) == pytest.approx(160.0)
    assert scopes_mla_share.steps_in_slice(run) == pytest.approx(150.0)
    assert reader("model.decode_step_ms.overload")(run) == pytest.approx(12.0)
    assert reader("model.prefill_time_share.overload")(run) == pytest.approx(30.0)
    assert reader("mla.time_share.overload")(run) == pytest.approx(37.2)
    assert reader("moe.experts_time_share.overload")(run) == pytest.approx(38.0)
    assert reader("moe.route_time_share.overload")(run) == pytest.approx(4.8)
    assert reader("head.time_share.overload")(run) == pytest.approx(8.0)
    assert reader("moe.experts_touched_per_step.overload")(run) == \
        pytest.approx(35.0)
    assert reader("moe.held_assignment_share.overload")(run) == \
        pytest.approx(3.125)
    assert reader("mla.table_live_share.overload")(run) == \
        pytest.approx(100.0 * 80000 / (32 * 21 * 128 + 32 * 16))
    counts = families.counts(run.config)
    # the slice's own rows a step (64,000), not the window's (80,000)
    assert scopes_mla_share.per_slice_step(
        run, "mla", "decode_context_rows") == pytest.approx(64000.0)
    whole = counts.decode_stream_cost(
        run.config, 150, 36 * 150, 48 * 150, 64000 * 150, 32 * 150)
    assert reader("model.decode_stream_roofline.overload")(run) == \
        pytest.approx(100 * whole["bytes"] / 819e9 / 1.8)
    assert 60 < reader("model.decode_stream_roofline.overload")(run) < 75
    kv = counts.mla_decode_cost(run.config, 64000 * 150)
    assert reader("mla.decode_roofline.overload")(run) == \
        pytest.approx(100 * kv["bytes"] / 819e9 / 0.2)
    ex = counts.expert_stream_cost(run.config, 36 * 150, 48 * 150)
    assert reader("moe.expert_stream_roofline.overload")(run) == \
        pytest.approx(100 * ex["bytes"] / 819e9 / 90e-6)
    assert reader("moe_gmm_roofline.overload")(run) == \
        pytest.approx(100 * ex["bytes"] / 819e9 / 80e-6)
    # without the worker's stamps (an earlier program): no share of a peak
    os.remove(os.path.join(run.trace_dirs["w0"], "counters.json"))
    for name in NEW_HERE[-4:]:
        assert reader(name)(run) is None


def test_the_readers_read_nothing_from_another_program(tmp_path):
    """Traced runs of other programs (Olmo's scopes; Mistral's) and an
    untraced run: the trace's readers this PR brings return None and none
    raises."""
    trace_dir = tmp_path / "trace-w0"
    trace_dir.mkdir()
    old = {"models": {procs.MODEL: {
        "decode_steps": 10, "decode_chunks": 1, "live_slots": 3,
        "attn": {"full_context_rows": 5, "full_table_rows": 9},
        "kv": {"utilization": 0.4}}}}
    (trace_dir / "counters.json").write_text(json.dumps(
        {"start": old, "stop": old}))
    L = "jit(_decode_chunk)/jit(main)/while/body/"
    for cfg_name, ops in (
            ("olmo-hybrid-7b-pp2",
             [[[L + "attn.full/flash_decode/pallas_call:", 0, 9.0],
               [L + "mlp.dense/dot_general:", 10.0, 5.0]]]),
            ("mistral-7b-int4",
             [[[L + "attn.kv_update/scatter:", 0, 9.0], ["", 10.0, 5.0]]])):
        for f in os.listdir(tmp_path):
            if f.startswith("scopes-"):
                os.remove(tmp_path / f)
        (tmp_path / "scopes-mla-share-w0.json").write_text(json.dumps(
            scopes_mla_share.reduce_scopes(ops)))
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
    run = RunData(config=cut(), mix={}, records=[], t_open=0.0, t_close=51.0,
                  setup={}, device={"kind": "cpu"},
                  workers_before={"w0": old}, workers_after={"w0": old},
                  trace_dirs={}, trace=None)
    for name in NEW_HERE:
        assert reader(name)(run) is None, name
