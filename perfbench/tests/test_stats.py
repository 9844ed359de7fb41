"""Percentile, TPOT, tokens-in-window and spread arithmetic."""

import os
import statistics
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib import opcount, peaks, stats  # noqa: E402


def test_percentile():
    xs = list(range(1, 102))                     # 1..101
    assert stats.percentile(xs, 50) == 51
    assert stats.percentile(xs, 90) == 91
    assert stats.percentile(xs, 0) == 1 and stats.percentile(xs, 100) == 101
    assert stats.percentile([3.0], 90) == 3.0
    assert stats.percentile([1.0, 2.0], 50) == 1.5
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_tpot():
    # first frame at t=1 with 1 token, then 8 tokens every 0.2 s
    frames = [(1.0, 1), (1.2, 8), (1.4, 8)]
    assert stats.tpot_s(frames) == pytest.approx(0.4 / 16)
    assert stats.tpot_s([(1.0, 5)]) is None      # one frame: no gap
    assert stats.tpot_s([(1.0, 1)]) is None


def test_tokens_in_window():
    frames = [(0.9, 8), (1.0, 8), (1.5, 3), (2.0, 8)]
    assert stats.tokens_in_window(frames, 1.0, 2.0) == 11


def test_spread_is_the_drivers():
    vals = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx(
        (q[2] - q[0]) / statistics.median(vals))


def test_param_bytes_match_the_published_widths():
    mistral = {"hidden_size": 4096, "intermediate_size": 14336,
               "num_hidden_layers": 32, "num_attention_heads": 32,
               "num_key_value_heads": 8, "head_dim": 128,
               "vocab_size": 32768}
    # 7.25e9 parameters; 6.98e9 of them int4 (3.49 GB) + bf16 embedding
    assert 3.7e9 < opcount.param_bytes(mistral) < 3.9e9
    assert opcount.kv_bytes_per_token(mistral) == 131072
    qwen = {"hidden_size": 3584, "intermediate_size": 18944,
            "num_hidden_layers": 28, "num_attention_heads": 28,
            "num_key_value_heads": 4, "head_dim": 128,
            "vocab_size": 152064, "qkv_bias": True}
    assert opcount.kv_bytes_per_token(qwen) == 57344
    # the q/k/v biases are stored (bf16) and counted: (28 + 2 * 4) * 128
    # columns in each of 28 layers
    assert opcount.param_bytes(qwen) - opcount.param_bytes(
        dict(qwen, qkv_bias=False)) == 28 * 36 * 128 * 2
    assert opcount.widths(qwen)["Vpad"] == 153600


def test_decode_int4_is_hbm_bound_and_roofline_is_sane():
    mistral = {"hidden_size": 4096, "intermediate_size": 14336,
               "num_hidden_layers": 32, "num_attention_heads": 32,
               "num_key_value_heads": 8, "head_dim": 128,
               "vocab_size": 32768}
    pk = peaks.peaks_for("TPU v5 lite")
    cost = opcount.int4_step_cost(mistral, 32)
    t, bound = opcount.roofline_seconds(cost, pk)
    assert bound == "hbm"
    assert 0.004 < t < 0.005          # ~3.5 GB of int4 weights at 819 GB/s
    t, bound = opcount.roofline_seconds(
        opcount.int4_step_cost(mistral, 4096), pk)
    assert bound == "flops"
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
