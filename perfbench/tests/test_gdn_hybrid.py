"""The ``gdn_hybrid`` family's benchmark files: its counts at the cut against
the hand count of ISSUE 33 and against the tree (the published one by shapes
only), every matrix of a pass named once, the three byte functions the
rooflines read against hand-reckoned numbers, the family's scopes on a
recorded list of op paths, the ``*.olmo`` readers on a made run and on a run
of another program (they read nothing and do not raise), the wrong models of
the reference refused by ``check.py``'s judge at the tiny size, and the tiny
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
    families, procs, scopes, scopes_gdn, session,
)
from perfbench.lib.session import RunData  # noqa: E402
from perfbench.tools import rehearse, rehearse_olmo  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
US = 1000.0
CELL = "olmohybrid-longgen-overload"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def cut():
    return session.load_config("olmo-hybrid-7b-pp2")


@pytest.fixture
def tiny(monkeypatch):
    """``olmo-tiny`` lies in ``perfbench/rehearse/`` (``tools/rehearse.py``
    says why): the seam's questions are asked of it here, by name."""
    cfg = rehearse.load("olmo-tiny")
    real = session.load_config
    monkeypatch.setattr(session, "load_config",
                        lambda n: dict(cfg) if n == "olmo-tiny" else real(n))
    return cfg


@pytest.mark.parametrize("question", [
    seam.test_family_resolves_to_counts_and_a_reference,
    seam.test_param_bytes_are_the_bytes_of_the_tree,
    seam.test_serve_reaches_the_worker_and_the_coordinator_whole],
    ids=lambda q: q.__name__[5:])
def test_the_seams_questions_of_the_tiny_rehearsal(question, tiny):
    assert tiny["platform"] == "cpu"
    question("olmo-tiny")


@pytest.fixture(scope="module")
def tiny_chains():
    return seam.served_chains(rehearse.load("olmo-tiny"))


def test_the_tiny_chains_pass_its_reference_alone(tiny, tiny_chains,
                                                  tmp_path):
    def chains(_name):
        return tiny_chains
    seam.test_served_chains_pass_their_own_reference("olmo-tiny", chains,
                                                     tmp_path)
    seam.test_another_familys_chains_fail_the_dense_reference(
        "olmo-tiny", chains, tmp_path)


def test_the_wrong_models_are_refused_at_the_tiny_size(tiny, tiny_chains):
    """``check.py``'s judge with the family's own limits, the same served
    chains against the reference with ONE named term wrong: every control
    refuses a chain (at this width all five can)."""
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
    assert len(ref.CONTROLS) == 5
    for control in ref.CONTROLS:
        assert not all(verdicts(control=control)), control


def test_the_rehearsal_has_its_two_files_outside_the_benchmarks():
    (config, mix), = rehearse_olmo.REHEARSALS.values()
    assert rehearse.load(config)["serve"] and rehearse.load(mix)["prompt"]
    assert not os.path.exists(os.path.join(HERE, "configs", f"{config}.json"))
    assert not os.path.exists(os.path.join(HERE, "traffic", f"{mix}.json"))
    assert set(rehearse_olmo.REHEARSALS).isdisjoint(rehearse.REHEARSALS)


def test_the_family_answers_both_apis():
    cfg = cut()
    counts, ref = families.counts(cfg), families.reference(cfg)
    assert all(hasattr(counts, a) for a in families.COUNTS_API)
    assert all(hasattr(ref, a) for a in families.REFERENCE_API)
    assert families.int4_calls_per_pass(cfg) == 0
    assert "FULL-attention" in counts.CACHE and "per SLOT" in counts.CACHE
    keys = {k for k, _f in ref.SPEC_PAIRS}
    assert keys >= {"hidden_size", "intermediate_size", "vocab_size",
                    "num_attention_heads", "num_key_value_heads",
                    "num_hidden_layers", "linear_num_key_heads",
                    "linear_num_value_heads", "linear_key_head_dim",
                    "linear_value_head_dim", "linear_conv_kernel_dim",
                    "rms_norm_eps"}
    spec = seam.program_spec(cfg)
    for key, field in ref.SPEC_PAIRS:
        assert cfg[key] == getattr(spec, field), key
    # the reference imports nothing from ops/
    with open(os.path.join(HERE, "reference", "gdn_hybrid.py")) as f:
        text = f.read()
    assert "ops" not in [line.split()[1].split(".")[-1]
                         for line in text.splitlines()
                         if line.startswith(("import ", "from "))]
    assert "..ops" not in text and ".ops " not in text


def test_the_configuration_is_the_catalogs_with_one_cut():
    cfg = cut()
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Olmo-Hybrid-7B")
    assert cfg["source"] == entry["source_url"]
    changed = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    # layer_types stays the published list: the stage runs its first 16
    assert changed == {"num_hidden_layers"}
    assert families.counts(cfg).widths(cfg)["L"] == 16
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 32}
    assert cfg["kept_layers"] == list(range(16)) and cfg["family"] == \
        "gdn_hybrid"
    assert "two pipeline stages" in cfg["deployment"]
    assert {"rope_parameters", "norms", "linear layers", "weights", "cache",
            "sizing"} <= set(cfg["assumed"])
    serve = cfg["serve"]
    pages = -(-serve["max_seq_len"] // serve["page_size"])
    assert serve["num_pages"] == serve["max_batch_size"] * pages == 384
    assert max(serve["prefill_buckets"]) == 4096 < serve["max_seq_len"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    (cell,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert cell["config"] == "olmo-hybrid-7b-pp2" and cell["chips"] == 1
    mix = json.load(open(os.path.join(HERE, "traffic",
                                      f"{cell['traffic']}.json")))
    assert (mix["prompt"], mix["output"]) == (
        {"median": 1024, "sigma": 0.8, "min": 128, "max": 4096},
        {"median": 640, "sigma": 0.6, "min": 192, "max": 2048})
    assert mix["prompt"]["max"] + mix["output"]["max"] <= serve["max_seq_len"]
    assert mix["rate_rps"] > 0 and (mix["ramp_s"], mix["tail_s"]) == (10, 10)


def test_the_hand_count_of_the_cut():
    """ISSUE 33's arithmetic, in millions of parameters, and its bytes."""
    cfg = cut()
    c = families.counts(cfg)
    w = c.widths(cfg)
    assert (w["L"], w["L_gdn"], w["L_full"], w["Dh"], w["C"]) == (
        16, 12, 4, 128, 11520)
    gdn = (2 * 3840 * 2880 + 2 * 3840 * 5760 + 5760 * 3840 + 2 * 3840 * 30
           + 4 * 11520 + 2 * 30 + 192)
    full = 4 * 3840 * 3840 + 2 * 3840
    mlp = 3 * 3840 * 11008
    assert round(gdn / 1e6, 2) == 88.75 and round(full / 1e6, 2) == 58.99
    assert round(mlp / 1e6, 2) == 126.81
    linear_layer, full_layer = gdn + mlp + 2 * 3840, full + mlp + 2 * 3840
    assert round(linear_layer / 1e6, 2) == 215.57
    assert round(full_layer / 1e6, 2) == 185.81
    ends = 2 * 100352 * 3840 + 3840
    assert round(ends / 1e6, 1) == 770.7
    # the whole model, for the record: 7.43 B
    assert round((24 * linear_layer + 8 * full_layer + ends) / 1e9, 3) == 7.431
    hand = 12 * linear_layer + 4 * full_layer + ends
    assert round(hand / 1e9, 3) == 4.101
    # every tensor bf16 but A_log and dt_bias (float32: + 2 B each)
    assert c.param_bytes(cfg) == 2 * hand + 12 * 2 * 30 * 2
    assert round(c.param_bytes(cfg) / 1e9, 2) == 8.20
    assert c.kv_row_bytes(cfg) == 15360 and c.kv_bytes_per_token(cfg) == 61440
    serve = cfg["serve"]
    pool = serve["num_pages"] * serve["page_size"] * c.kv_bytes_per_token(cfg)
    assert round(pool / 1e9, 2) == 3.02
    state = 12 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    assert c.state_bytes_per_slot(cfg) == state
    assert round(state / 1e6, 1) == 27.4


def test_counts_are_the_published_trees_bytes_by_shapes():
    import jax

    from distributed_inference_engine_tpu.models import olmo_hybrid

    cfg = cut()
    spec = seam.program_spec(cfg)
    tree = jax.eval_shape(lambda: olmo_hybrid.init_params(
        spec, jax.random.key(0)))
    have = sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))
    assert have == families.counts(cfg).param_bytes(cfg)
    state = jax.eval_shape(lambda: olmo_hybrid.init_state(spec, 8))
    assert sum(a.size * a.dtype.itemsize for a in state.values()) == \
        8 * families.counts(cfg).state_bytes_per_slot(cfg)
    assert olmo_hybrid.state_bytes_per_slot(spec) == \
        families.counts(cfg).state_bytes_per_slot(cfg)


def test_weight_matmuls_names_every_matrix_once():
    import jax

    from distributed_inference_engine_tpu.models import olmo_hybrid

    cfg = cut()
    c = families.counts(cfg)
    mats = c.weight_matmuls(cfg)
    names = [m[0] for m in mats]
    assert len(names) == len(set(names)) == 14
    by = {m[0]: m for m in mats}
    assert by["gdn_q"][1:4] == by["gdn_k"][1:4] == (3840, 2880, 12)
    assert by["gdn_v"][1:4] == by["gdn_gate"][1:4] == (3840, 5760, 12)
    assert by["gdn_a"][1:4] == by["gdn_b"][1:4] == (3840, 30, 12)
    assert by["gdn_out"][1:4] == (5760, 3840, 12)
    for name in ("full_q", "full_k", "full_v", "full_out"):
        assert by[name][1:4] == (3840, 3840, 4)
    assert by["mlp_gate_up"][1:4] == (3840, 22016, 16)
    assert by["mlp_down"][1:4] == (11008, 3840, 16)
    assert by["lm_head"][1:4] == (3840, 100352, 1)
    # the tree's own names, for what is a matrix there
    spec = seam.program_spec(cfg)
    gdn, full = (jax.eval_shape(
        lambda k=k: olmo_hybrid._init_stack(spec, k, jax.random.key(0)))
        for k in ("gdn", "full"))
    for mine, theirs in (("wq", "gdn_q"), ("wk", "gdn_k"), ("wv", "gdn_v"),
                         ("w_g", "gdn_gate"), ("w_a", "gdn_a"),
                         ("w_b", "gdn_b"), ("wo", "gdn_out"),
                         ("w_gate_up", "mlp_gate_up"),
                         ("w_down", "mlp_down")):
        assert gdn[mine].shape == (4,) + by[theirs][1:3], mine
    for mine, theirs in (("wq", "full_q"), ("wk", "full_k"),
                         ("wv", "full_v"), ("wo", "full_out")):
        assert full[mine].shape == (4,) + by[theirs][1:3], mine


def test_the_three_byte_functions_by_hand():
    cfg = cut()
    c = families.counts(cfg)
    # 8 rows at 2,000 tokens of context, one step: 16,000 rows x 15,360 B in
    # each of 4 full layers; live rows, never the table
    kv = c.full_decode_cost(cfg, context_rows=16000)
    assert kv["bytes"] == 4 * 16000 * 15360
    assert kv["flops"] == 4 * 16000 * 4 * 30 * 128
    assert kv["bytes"] < 0.35 * 4 * 8 * 6144 * 15360
    # 8 live rows, one step: 12 layers x (2.21 MB + the tail), read + written
    st = c.state_cost(cfg, rows_updated=8)
    assert st["bytes"] == 2 * 8 * c.state_bytes_per_slot(cfg)
    assert st["flops"] == 12 * 8 * 8 * 30 * 96 * 192
    # a whole step: the tree without the embedding table, once, + both
    whole = c.decode_stream_cost(cfg, 1, 16000, 8)
    weights = c.param_bytes(cfg) - 2 * 100352 * 3840
    assert round(weights / 1e9, 2) == 7.43
    assert whole["bytes"] == weights + kv["bytes"] + st["bytes"]
    # HBM-bound by far: the bytes' time is 9x the operations'
    assert whole["bytes"] / 819e9 > 5 * whole["flops"] / 197e12
    assert round(1e3 * whole["bytes"] / 819e9, 1) == 10.8


# ------------------------------------------------------------------ scopes

# op paths as a v5e trace of the cell's two programs names them (prefixes
# as XLA writes them); [path, start ns, duration ns]
D = "jit(_decode_chunk)/jit(main)/while/body/while/body/"
E = "jit(_decode_chunk)/jit(main)/while/body/"
P = "jit(_prefill_pages)/jit(main)/while/body/"
RECORDED = [[
    [D + "attn.gdn.step/dot_general:", 0, 60 * US],
    [D + "attn.gdn.step/recurrence/mul:", 60 * US, 30 * US],
    [D + "state.update/select_n:", 90 * US, 10 * US],
    [D + "mlp.dense/dot_general:", 100 * US, 120 * US],
    [D + "attn.full/dot_general:", 220 * US, 20 * US],
    [D + "attn.full/attn.kv_update/select_n:", 240 * US, 5 * US],
    [D + "attn.full/flash_decode/pallas_call:", 245 * US, 25 * US],
    [E + "head.unembed/dot_general:", 270 * US, 30 * US],
    [E + "sample/argmax:", 300 * US, 10 * US],
    [E + "add:", 310 * US, 10 * US],
    [P + "attn.gdn.prefill/dot_general:", 320 * US, 50 * US],
    [P + "attn.gdn.prefill/recurrence/while:", 370 * US, 30 * US],
    [P + "attn.full/dot_general:", 400 * US, 40 * US],
    [P + "mlp.dense/dot_general:", 440 * US, 60 * US]]]


def test_the_familys_scopes_on_recorded_op_paths():
    red = scopes_gdn.reduce_scopes(RECORDED)
    assert red["busy_s"] == pytest.approx(500e-6)
    sc = red["scopes"]
    assert sc["attn.gdn.step"] == {"decode": pytest.approx(90e-6),
                                   "other": 0.0}
    # a nested scope is counted under both names
    assert sc["recurrence"] == {"decode": pytest.approx(30e-6),
                                "other": pytest.approx(30e-6)}
    assert sc["attn.gdn.prefill"]["other"] == pytest.approx(80e-6)
    assert sc["attn.full"] == {"decode": pytest.approx(50e-6),
                               "other": pytest.approx(40e-6)}
    assert sc["flash_decode"] == {"decode": pytest.approx(25e-6),
                                  "other": 0.0}
    assert sc["mlp.dense"] == {"decode": pytest.approx(120e-6),
                               "other": pytest.approx(60e-6)}
    assert sc["head.unembed"]["decode"] == pytest.approx(30e-6)
    assert sc["sample"]["decode"] == pytest.approx(10e-6)
    # the kernel: the operation that takes most of ``flash_decode``'s time
    assert red["kernel_calls"] == 1
    assert red["kernel_s"] == pytest.approx(25e-6)
    two = scopes_gdn.reduce_scopes([RECORDED[0] + [
        [D + "attn.full/flash_decode/pallas_call:", 600 * US, 20 * US],
        [D + "attn.full/flash_decode/pad:", 620 * US, 1 * US]]])
    assert (two["kernel_calls"], two["kernel_s"]) == (2, pytest.approx(45e-6))
    # lib/scopes.py names none of them but state.update (Ling's too) and
    # the two around every model's head
    assert set(scopes.reduce_scopes(RECORDED)["scopes"]) == {
        "state.update", "head.unembed", "sample"}
    assert scopes_gdn.reduce_scopes([[["jit(f)/mul:", 0, 5.0]]])["scopes"] \
        == {}


def made_run(tmp_path):
    """A traced run of the cell: 10 decode programs of 16 steps in the
    slice, the first cut by the slice's start so that the kernel ran 150
    steps x 4 full layers there; over the window 1,600 steps in 100 chunks,
    8 rows live at a context of 2,000; between the worker's two stamps of
    the traced slice (``counters.json``) the rows' contexts are 1,500: what
    the rooflines divide by the slice's seconds is the slice's own."""
    trace_dir = tmp_path / "trace-w0"
    trace_dir.mkdir()
    (tmp_path / "scopes-w0.json").write_text(json.dumps(
        scopes.reduce_scopes(RECORDED)))
    (tmp_path / "scopes-gdn-w0.json").write_text(json.dumps(
        dict(scopes_gdn.reduce_scopes(RECORDED), kernel_calls=150 * 4)))

    def worker(steps, chunks, ctx, table, moved):
        return {"models": {procs.MODEL: {
            "decode_steps": steps, "decode_chunks": chunks,
            "attn": {"full_context_rows": ctx, "full_table_rows": table},
            "state": {"rows_updated": moved}}}}

    (trace_dir / "counters.json").write_text(json.dumps({
        "start": worker(2300, 180, 2 * 10 ** 7, 0, 9000),
        "stop": worker(2460, 190, 2 * 10 ** 7 + 160 * 12000, 0,
                       9000 + 160 * 8)}))
    return RunData(
        config=cut(), mix={}, records=[], t_open=0.0, t_close=51.0, setup={},
        device={"kind": "TPU v5 lite"},
        workers_before={"w0": worker(1000, 100, 10 ** 6, 10 ** 7, 5000)},
        workers_after={"w0": worker(2600, 200, 10 ** 6 + 1600 * 16000,
                                    10 ** 7 + 1600 * 8 * 2064,
                                    5000 + 1600 * 8)},
        samples=[
            {"t": 46.5, "workers": {"w0": worker(2000, 150, 0, 0, 0)}},
            {"t": 50.75, "workers": {"w0": worker(2450, 190, 0, 0, 0)}}],
        trace_dirs={"w0": str(trace_dir)},
        trace={"program_s": {"decode": 2.4, "prefill": 0.3},
               "program_calls": {"decode": 10, "prefill": 2},
               "busy_s": 3.0, "window_s": 4.0, "between_programs_s": 0.2})


def reader(name):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("r_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


NEW_HERE = ["model.decode_step_ms.overload", "model.prefill_time_share.overload",
            "gdn.time_share.olmo", "gdn.prefill_time_share.olmo",
            "attn.full_time_share.overload", "state.update_time_share.overload",
            "mlp.time_share.olmo", "head.time_share.overload",
            "attn.full_table_live_share.overload",
            "model.decode_stream_roofline.overload",
            "attn.full_decode_roofline.olmo", "gdn.state_roofline.olmo"]


def test_the_readers_on_a_made_run(tmp_path):
    run = made_run(tmp_path)
    # the steps are the kernel's calls in the slice, not whole programs
    assert scopes.decode_steps_in_slice(run) == pytest.approx(160.0)
    assert scopes_gdn.steps_in_slice(run) == pytest.approx(150.0)
    assert reader("model.decode_step_ms.overload")(run) == pytest.approx(16.0)
    assert reader("model.prefill_time_share.overload")(run) == pytest.approx(10.)
    assert reader("gdn.time_share.olmo")(run) == pytest.approx(34.0)
    assert reader("gdn.prefill_time_share.olmo")(run) == pytest.approx(16.0)
    assert reader("attn.full_time_share.overload")(run) == pytest.approx(18.0)
    assert reader("state.update_time_share.overload")(run) == pytest.approx(2.0)
    assert reader("mlp.time_share.olmo")(run) == pytest.approx(36.0)
    assert reader("head.time_share.overload")(run) == pytest.approx(8.0)
    assert reader("attn.full_table_live_share.overload")(run) == \
        pytest.approx(100.0 * 16000 / (8 * 2064))
    counts = families.counts(run.config)
    # the slice's own rows a step (12,000), not the window's (16,000)
    assert scopes_gdn.per_slice_step(run, "attn", "full_context_rows") == \
        pytest.approx(12000.0)
    whole = counts.decode_stream_cost(run.config, 150, 12000 * 150, 8 * 150)
    assert reader("model.decode_stream_roofline.overload")(run) == \
        pytest.approx(100 * whole["bytes"] / 819e9 / 2.4)
    assert 60 < reader("model.decode_stream_roofline.overload")(run) < 70
    kv = counts.full_decode_cost(run.config, 12000 * 150)
    assert reader("attn.full_decode_roofline.olmo")(run) == \
        pytest.approx(100 * kv["bytes"] / 819e9 / 25e-6)
    st = counts.state_cost(run.config, 8 * 150)
    assert reader("gdn.state_roofline.olmo")(run) == \
        pytest.approx(100 * st["bytes"] / 819e9 / 40e-6)
    assert reader("device.idle_share.overload")(run) == pytest.approx(25.0)
    # without the worker's stamps (an earlier program): no share of a peak
    os.remove(os.path.join(run.trace_dirs["w0"], "counters.json"))
    for name in ("model.decode_stream_roofline.overload",
                 "attn.full_decode_roofline.olmo", "gdn.state_roofline.olmo"):
        assert reader(name)(run) is None


def test_the_readers_read_nothing_from_another_program(tmp_path):
    """Traced runs of the PARENT's programs (Ling's scopes and counters;
    Mistral's): the readers this PR brings return None and none raises."""
    trace_dir = tmp_path / "trace-w0"
    trace_dir.mkdir()
    old = {"models": {procs.MODEL: {
        "decode_steps": 10, "decode_chunks": 1, "live_slots": 3,
        "mla": {"decode_context_rows": 5, "decode_table_rows": 9},
        "kv": {"utilization": 0.4}}}}
    L = "jit(_decode_chunk)/jit(main)/while/body/"
    for cfg_name, ops in (
            ("ling-3.0-flash-ep4",
             [[[L + "attn.kda.step/dot_general:", 0, 9.0],
               [L + "state.update/select_n:", 10.0, 5.0]]]),
            ("mistral-7b-int4",
             [[[L + "attn.kv_update/scatter:", 0, 9.0], ["", 10.0, 5.0]]])):
        for f in os.listdir(tmp_path):
            if f.startswith("scopes-"):
                os.remove(tmp_path / f)
        (tmp_path / "scopes-gdn-w0.json").write_text(json.dumps(
            scopes_gdn.reduce_scopes(ops)))
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
