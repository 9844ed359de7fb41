"""The trace reduction on a hand-made trace whose answers are known, and on
the small recorded piece of a real chip trace kept beside this file."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib import tracered  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000.0


def made_trace():
    """One device: two programs (a decode chunk of two steps, a prefill),
    100 us apart, inside a 1000 us window that the host spans mark."""
    kern = ("%_int4_matmul_stacked.3 = bf16[32,6144]{1,0} custom-call("
            "bf16[32,2048]{1,0} %get-tuple-element.7)")
    ops = [
        # decode program 100..500: a while loop enclosing its body ops
        ["%while.1 = (s32[], bf16[32,32,1024,8,128]) while(%tuple.2)",
         100 * US, 400 * US],
        [kern, 110 * US, 100 * US],
        ["%bitcast_dynamic-update-slice_fusion.7 = bf16[32,32,1024,8,128]"
         "{4,3,2,1,0} fusion(%p.1)", 220 * US, 50 * US],
        [kern, 300 * US, 100 * US],
        # a fusion that only CONSUMES the kernel's output is not the kernel
        ["%fusion.9 = bf16[32,1,4096]{2,0,1} fusion(bf16[32,6144]{1,0} "
         "%_int4_matmul_stacked.3)", 420 * US, 70 * US],
        # prefill program 600..900
        [kern.replace(".3 =", ".5 ="), 600 * US, 200 * US],
        ["%copy.2 = bf16[131072,8,8,128]{3,2,1,0} copy(%bitcast.1)",
         800 * US, 100 * US],
    ]
    mods = [["jit__decode_chunk(123)", 100 * US, 400 * US],
            ["jit__prefill_pages(77)", 600 * US, 300 * US]]
    host = [["$pump.py:10 run", 0.0, 1000 * US],
            ["$continuous.py:2185 step", 50 * US, 500 * US],
            ["$continuous.py:1476 _admit_batch", 520 * US, 100 * US],
            ["$threading.py:323 wait", 530 * US, 50 * US]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": host}]}]}


def test_busy_idle_and_programs():
    r = tracered.reduce_trace(made_trace(), calls_per_step=1,
                              program_files=["continuous.py", "pump.py"])
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["busy_s"] == pytest.approx(700e-6)     # 400 + 300, nesting once
    assert r["program_s"]["decode"] == pytest.approx(400e-6)
    assert r["program_s"]["prefill"] == pytest.approx(300e-6)
    assert r["between_programs_s"] == pytest.approx(100e-6)
    # two int4 calls inside the decode program, one per step
    assert r["decode_steps"] == 2
    assert r["int4_kernel_s"]["decode"] == pytest.approx(200e-6)
    assert r["int4_kernel_s"]["prefill"] == pytest.approx(200e-6)


def test_classes_are_self_time_and_sum_to_busy():
    r = tracered.reduce_trace(made_trace(), calls_per_step=1)
    c = r["classes"]
    assert c["int4_matmul"] == pytest.approx(400e-6)
    assert c["dynamic-update-slice"] == pytest.approx(50e-6)
    assert c["copy_transpose"] == pytest.approx(100e-6)
    assert c["fusions"] == pytest.approx(70e-6)
    # the loop's own time is what its body leaves uncovered: 400 - 320
    assert c["loop_control"] == pytest.approx(80e-6)
    assert sum(c.values()) == pytest.approx(r["busy_s"])


def test_idle_gap_goes_to_the_programs_own_deepest_function():
    r = tracered.reduce_trace(made_trace(), calls_per_step=1,
                              program_files=["continuous.py", "pump.py"])
    # the one gap (500..600 us) has its middle inside _admit_batch; the
    # deeper threading.py wait is not the program's file
    assert r["idle_gaps"] == {"continuous.py__admit_batch":
                              pytest.approx(100e-6)}


def test_kv_slice_is_found_by_the_configurations_widths():
    cfg = {"num_key_value_heads": 8, "head_dim": 128}
    classes = tracered.op_classes(cfg)
    kv = ("%constant_dynamic-slice_fusion.62 = bf16[1,32,1024,8,128]"
          "{4,3,2,1,0} fusion(bf16[32,32,1024,8,128] %gte.3)")
    other = "%dynamic-slice.4 = s32[1]{0} dynamic-slice(s32[32]{0} %p.3)"
    assert tracered.op_class(kv, classes) == "kv_context_slice"
    assert tracered.op_class(other, classes) == "dynamic-slice"
    # another configuration's widths do not match this buffer
    assert tracered.op_class(kv, tracered.op_classes(
        {"num_key_value_heads": 4, "head_dim": 128})) == "dynamic-slice"


def test_union_and_leaf_ops():
    ev = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 30.0, 5.0)]
    assert tracered.union_intervals(ev) == [(0.0, 15.0), (30.0, 35.0)]
    leaves = {n: s for n, _e, s in tracered.leaf_ops(
        [("outer", 0.0, 100.0), ("in1", 10.0, 20.0), ("in2", 50.0, 30.0)])}
    assert leaves == {"outer": 50.0, "in1": 20.0, "in2": 30.0}


def test_no_device_plane_gives_no_device_numbers():
    t = made_trace()
    t["planes"] = t["planes"][1:]
    assert tracered.reduce_trace(t) == {"devices": 0}


def test_recorded_chip_slice():
    """One real decode step: 4 int4 kernel calls per layer x 32 layers, the
    per-layer K/V slices found by the configuration's widths, every class
    self-timed so that they sum to the busy time."""
    with open(os.path.join(HERE, "recorded_slice.json")) as f:
        rec = json.load(f)
    r = tracered.reduce_trace(rec["trace"], rec["calls_per_step"],
                              rec["program_files"], rec["config"])
    want = rec["expect"]
    assert r["devices"] == want["devices"]
    for key in ("busy_s", "window_s", "decode_steps"):
        assert r[key] == pytest.approx(want[key], rel=1e-9)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert sum(r["classes"].values()) == pytest.approx(r["busy_s"], rel=1e-9)
    assert r["classes"]["int4_matmul"] == pytest.approx(
        want["int4_matmul_s"], rel=1e-9)
    assert r["classes"]["kv_context_slice"] == pytest.approx(
        want["kv_context_slice_s"], rel=1e-9)
    assert r["int4_kernel_calls"]["decode"] == 4 * 32
    assert r["program_calls"]["decode"] == 1
    # the int4 kernel cannot beat the HBM roofline of its own weight bytes
    from perfbench.lib import opcount, peaks
    cfg = {"hidden_size": 4096, "intermediate_size": 14336,
           "num_hidden_layers": 32, "num_attention_heads": 32,
           "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 32768}
    layers_only = sum(t * (k * n / 2 + 4 * n) for name, k, n, t
                      in opcount.int4_matmuls(cfg) if name != "lm_head")
    least = layers_only / peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert 0.5 < least / r["int4_kernel_s"]["decode"] < 1.0
