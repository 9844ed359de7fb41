"""The plain-residual compressed-query MLA family (Kimi-K2.5:
``models/xing.py`` ``kimi_spec``, ``hc_mult`` 0; 4 of the 16 experts of ONE
routing group held at the tiny size) against the plain float32 reference the
benchmark judges it by (``perfbench/reference/mla_moe_share.py``), on the
CPU; the held-rows expert layer (``ops/moe_routed.py`` ``moe_block_held``)
against ``moe_block``; and the test that ties the chip's share to the model:
the expert layer's outputs of ALL the shares add up to the uncut reference's.

Tolerances. With the served tree widened to float32 and matmuls at highest
precision the two implementations differ by rounding order alone: logits of
magnitude ~0.7 agree to 5e-5 (seen: 2.1e-7 after decoding through the pages).
Every control, the same served logits against a reference with ONE named term
wrong, moves them by at least five times the bound (seen, the smallest: plain
RoPE frequencies and the YaRN softmax factor 6.3e-4 each; the routed scaling
factor left at 1 2.4e-1, the shared expert dropped 3.2e-1, gates not
renormalised 4.2e-1, ``experts_held`` shifted 5.0e-1). Served in bfloat16
the same comparison reads 0.4-0.7 % of max|logit| at nine positions in ten
(the rounding; 1 % bounds it) and, at 0 to 4 positions of 131, up to 32 %:
there bfloat16 activations swapped a token's 4th best expert for its 5th
against the float32 reference and one of the two is held, at the share's
draw of 16 x the shared expert's (``xing.ROUTED_DOWN_SCALE_SHARE``; six tree
/ token seeds read 0.6-32 %). 70 % bounds a swap, twice the worst seen, and
at most 8 positions of 131 (6 %) may lie over 5 %, twice the most seen. The two
expert-layer bodies add a token's held choices in another order (by expert
against by choice): float32 sums of at most ``k`` terms, 1e-6 of the
output's size.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from distributed_inference_engine_tpu.models import xing  # noqa: E402
from distributed_inference_engine_tpu.models.base import (  # noqa: E402
    layered_family,
)
from distributed_inference_engine_tpu.ops import moe_routed  # noqa: E402
from perfbench.lib import families  # noqa: E402
# the mHC family's driver over ``models/xing.py``'s programs (prefill at a
# padded bucket, teacher-forced decode through the latent pages): the same
# module serves this family
from test_xing import served_logits  # noqa: E402  (this directory)

F32_TOL = 5e-5
BF16_TOL = 0.01          # of max|logit|, nine positions in ten
BF16_SWAP_TOL = 0.7      # of max|logit|, every position
BF16_SWAPPED_SHARE = 0.06   # of the positions may lie over 5 % of it

with open(os.path.join(ROOT, "perfbench", "rehearse", "kimi-tiny.json")) as _f:
    CFG = json.load(_f)
REF = families.reference(CFG)

def tiny_spec(**kw):
    return xing.kimi_spec("kimi-tiny", max_seq_len=128, **kw)


@pytest.fixture(scope="module")
def served_bf16():
    return xing.init_params(tiny_spec(), jax.random.key(7))


@pytest.fixture(scope="module")
def served_f32(served_bf16):
    return jax.tree.map(lambda a: a.astype(jnp.float32), served_bf16)


def sequences(seed=0, lens=(45, 77, 9)):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, CFG["vocab_size"], n)]
            for n in lens]


def max_diff(got, params, seqs, **kw):
    worst, scale = 0.0, 0.0
    for lg, seq in zip(got, seqs):
        ref = np.asarray(REF.logits(CFG, params, jnp.asarray(seq), **kw))
        worst = max(worst, float(np.abs(lg - ref).max()))
        scale = max(scale, float(np.abs(ref).max()))
    return worst, scale


# ---------------------------------------------- served path vs the reference

# prompts of unequal length; the 77-token row decodes from 37 across the
# page boundaries at 48 and 64 (pages of 16), past YaRN's original context
# of 32 (factor 4)
PROMPTS = (20, 37, 5)


@pytest.fixture(scope="module")
def float32_run(served_f32):
    seqs = sequences()
    with jax.default_matmul_precision("highest"):
        got, sv = served_logits(tiny_spec(dtype="float32"), served_f32, seqs,
                                PROMPTS)
    return seqs, got, sv


def test_served_float32_logits_are_the_references(served_f32, float32_run):
    seqs, got, sv = float32_run
    with jax.default_matmul_precision("highest"):
        worst, scale = max_diff(got, served_f32, seqs)
    assert worst < F32_TOL and scale > 0.3, (worst, scale)
    # the logits are over the chip's slice of the vocabulary and nothing else
    assert {lg.shape[1] for lg in got} == {CFG["vocab_size"]}
    # a quarter of ONE group is held: some choices land here, most do not
    held, total, _touched = sv.moe
    assert 0 < held < 0.5 * total


@pytest.mark.parametrize("control", REF.CONTROLS)
def test_a_wrong_term_fails(served_f32, float32_run, control):
    """The tolerance is tight enough to see each control: the same served
    logits against the reference with one named term wrong."""
    seqs, got, _ = float32_run
    with jax.default_matmul_precision("highest"):
        worst, _ = max_diff(got, served_f32, seqs, control=control)
    assert worst > 5 * F32_TOL, (control, worst)


def test_an_unknown_control_is_an_error(served_f32):
    with pytest.raises(ValueError, match="unknown control"):
        REF.logits(CFG, served_f32, jnp.asarray([1, 2, 3]), control="nope")


def test_served_bfloat16_logits_are_near_the_references(served_bf16):
    seqs = sequences(1)
    got, _ = served_logits(tiny_spec(), served_bf16, seqs, PROMPTS)
    refs = [np.asarray(REF.logits(CFG, served_bf16, jnp.asarray(seq)))
            for seq in seqs]
    scale = max(float(np.abs(ref).max()) for ref in refs)
    diffs = np.concatenate([np.abs(lg - ref).max(-1)
                            for lg, ref in zip(got, refs)]) / scale
    assert np.quantile(diffs, 0.9) < BF16_TOL, np.quantile(diffs, 0.9)
    assert np.mean(diffs > 0.05) < BF16_SWAPPED_SHARE, np.mean(diffs > 0.05)
    assert diffs.max() < BF16_SWAP_TOL, diffs.max()


def test_the_reference_in_bfloat16_is_a_control_not_the_reference(
        served_bf16):
    """One precision below what the configuration's reference states fails
    the float32 bound: the comparison would see a bfloat16 computation."""
    seq = jnp.asarray(sequences(2)[1])
    ref = np.asarray(REF.logits(CFG, served_bf16, seq))
    low = REF.logits(CFG, served_bf16, seq, dtype=jnp.bfloat16)
    assert low.dtype == jnp.bfloat16 and ref.dtype == np.float32
    worst = float(np.abs(np.asarray(low, np.float32) - ref).max())
    assert 5 * F32_TOL < worst < BF16_SWAP_TOL * float(np.abs(ref).max())
    # only the last positions: what a long chain's judge reads
    tail = np.asarray(REF.logits(CFG, served_bf16, seq, last=5))
    assert tail.shape[0] == 5
    assert float(np.abs(tail - ref[-5:]).max()) < 1e-6


# ------------------------------------------------- the held-rows expert layer


def _layer_inputs(n, seed=3, dtype=jnp.float32):
    spec = tiny_spec(dtype="float32")
    x = jax.random.normal(jax.random.key(seed), (n, spec.d_model),
                          jnp.float32).astype(dtype)
    return spec, x


def _route_to(monkeypatch, experts):
    """Every token chooses ``experts`` (k ids), at equal gates."""
    def fixed(spec, x, w, bias=None):
        n = x.shape[0]
        idx = jnp.broadcast_to(jnp.asarray(experts, jnp.int32),
                               (n, len(experts)))
        return idx, jnp.full(idx.shape, 0.7, jnp.float32)

    monkeypatch.setattr(moe_routed, "route", fixed)


@pytest.mark.parametrize("case,impl", [
    ("fraction", "xla"), ("none_held", "xla"), ("all_held", "xla"),
    ("fraction", "gmm_interpret")])
def test_held_rows_body_equals_moe_block(monkeypatch, served_f32, case, impl):
    """``moe_block_held`` (gather, grouped products and the sum back over
    the HELD assignments, in row blocks) against ``moe_block`` (all N x k
    rows, the ones not held masked): equal outputs and equal counters for a
    share that holds a fraction of one group, for a batch in which no
    token is held (the loop runs no block) and for one in which every
    choice of every token is (N k / R blocks: more than one)."""
    spec, x = _layer_inputs(40)
    blk = served_f32["layers"][1]
    valid = jnp.arange(40) < 37           # pad rows route nowhere
    if case == "none_held":
        _route_to(monkeypatch, (9, 10, 12, 15))
    if case == "all_held":
        _route_to(monkeypatch, (0, 1, 2, 3))
    assert moe_routed.held_fraction_of_one_group(spec)
    r = moe_routed.held_block_rows(spec, 40)
    assert r == 128 and 40 * spec.experts_per_token > r  # 160 rows: 2 blocks
    with jax.default_matmul_precision("highest"):
        want, cw = jax.jit(lambda b, x, v: moe_routed.moe_block(
            spec, b, x, v, impl))(blk, x, valid)
        got, cg = jax.jit(lambda b, x, v: moe_routed.moe_block_held(
            spec, b, x, v, impl))(blk, x, valid)
    assert np.asarray(cg).tolist() == np.asarray(cw).tolist()
    held, total, touched = np.asarray(cg).tolist()
    assert total == 37 * 4
    assert (held, touched) == {"none_held": (0, 0),
                               "all_held": (total, 4)}.get(
        case, (held, touched))
    if case == "fraction":
        assert 0 < held < total and 1 <= touched <= 4
    scale = float(np.abs(np.asarray(want)).max())
    assert float(np.abs(np.asarray(got) - np.asarray(want)).max()) \
        < 1e-6 * max(scale, 1.0), case


def test_the_family_picks_the_body_from_the_spec():
    assert moe_routed.moe_body(tiny_spec()) is moe_routed.moe_block_held
    # every expert held (the mHC family's cell), or whole groups (the
    # hybrid family's): the parent's body, and its jaxpr
    assert moe_routed.moe_body(xing.xing_spec("xing-tiny")) is moe_routed.moe_block
    from distributed_inference_engine_tpu.models.ling import ling_spec

    assert not moe_routed.held_fraction_of_one_group(ling_spec("ling-tiny"))
    cut = xing.kimi_spec("kimi-k2.5-ep32-pp1", max_seq_len=7680)
    # decode at 32 rows: 256 assignments, ~8 held; the 6,144 prefill:
    # 49,152 assignments, ~1,536 held
    assert moe_routed.held_block_rows(cut, 32) == 128
    assert moe_routed.held_block_rows(cut, 6144) == 2048
    assert moe_routed.held_block_rows(cut, 512) == 256


# ------------------------------------------------------- the share test


def test_all_the_shares_add_up_to_the_uncut_layer(served_f32):
    """The guide's share test: the expert layer of every ``experts_held``
    slice (4 chips x 4 of the 16 experts), the shared expert counted once,
    adds up to what the uncut reference gives for the whole layer; and a
    token none of whose choices is held gets the shared expert's output
    alone. The program's shares and the reference's, both."""
    whole = tiny_spec(dtype="float32", experts_held=(0, 16))
    full = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        xing.init_params(whole.replace(dtype="bfloat16"),
                         jax.random.key(5)))["layers"][1]
    assert full["w_gate_up"].shape[0] == 16
    _, x = _layer_inputs(24, seed=9)
    valid = jnp.ones((24,), bool)
    cfg_whole = dict(CFG, experts_held=[0, 16])
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(REF.experts(cfg_whole, full, x))
        shared = np.asarray(REF.shared(full, x))
        prog = np.zeros_like(uncut)
        ref = np.zeros_like(uncut)
        lonely = 0
        for first in range(0, 16, 4):
            spec = tiny_spec(dtype="float32", experts_held=(first, 4))
            blk = dict(full, w_gate_up=full["w_gate_up"][first:first + 4],
                       w_down=full["w_down"][first:first + 4])
            out, counters = moe_routed.moe_block_held(spec, blk, x, valid,
                                                      "xla")
            out = np.asarray(out)
            prog += out - shared
            ref += np.asarray(REF.routed(CFG, blk, x, (first, 4)))
            # the tokens that sent this share nothing
            idx, _g = moe_routed.route(spec, x, blk["w_router"],
                                       blk["router_bias"])
            none = np.asarray(((idx >= first) & (idx < first + 4)).sum(1) == 0)
            lonely += int(none.sum())
            assert (out[none] == shared[none]).all()
    assert lonely > 0                      # some token was held nowhere here
    for parts in (prog, ref):
        assert float(np.abs(parts + shared - uncut).max()) < F32_TOL


# ------------------------------------------------------------------ the spec


def test_sizes_the_cut_and_the_spec_round_trip():
    cut = xing.kimi_spec("kimi-k2.5-ep32-pp1", max_seq_len=7680)
    assert cut.layer_ids == list(range(7))
    assert cut.layer_mlps == ["dense"] + ["moe"] * 6
    assert cut.layer_kinds == ["mla"] * 7 and cut.experts_held == (0, 12)
    assert (cut.n_experts, cut.experts_per_token, cut.n_group) == (384, 8, 1)
    assert cut.vocab_size == 163840 // 8 and cut.n_heads == 64
    assert cut.cache_row_width == 640 and cut.paged_layers == 7
    assert (cut.hc_mult, cut.q_lora_rank, cut.d_model) == (0, 1536, 7168)
    assert cut.routed_scaling_factor == 2.827 and cut.rope_theta == 50000.0
    again = type(cut).from_dict(json.loads(json.dumps(cut.to_dict())))
    assert again == cut and hash(again) == hash(cut)
    # the family is told by the compressed query, the residual by hc_mult
    assert layered_family(cut) is xing
    assert layered_family(xing.xing_spec("xing-tiny")) is xing
    with pytest.raises(ValueError, match="unknown kimi size"):
        xing.kimi_spec("kimi-huge")
    with pytest.raises(ValueError, match="q_lora_rank"):
        tiny_spec(layer_kinds=("kda", "mla", "mla", "mla"))
    with pytest.raises(ValueError, match="hc_mult"):
        tiny_spec(hc_mult=4)               # streams without Sinkhorn rounds


def test_a_plain_residual_tree_holds_no_mhc_tensor_and_no_mhc_scope(
        served_bf16):
    from test_span_tracing import _program_texts

    blk = served_bf16["layers"][1]
    assert "hc_attn" not in blk and "hc_mlp" not in blk
    assert blk["w_router"].shape == (64, 16)          # all published experts
    assert blk["w_gate_up"].shape[0] == 4             # the held ones
    assert blk["w_router"].dtype == jnp.float32
    other = xing.init_params(tiny_spec(), jax.random.key(8))["layers"][1]
    assert bool((blk["router_bias"] == other["router_bias"]).all())
    assert not bool((blk["w_qa"] == other["w_qa"]).all())
    for text in _program_texts(xing, xing.kimi_spec("kimi-tiny",
                                                    max_seq_len=64)):
        assert "resid.mhc" not in text
        assert "/attn.mla/" in text and "/moe.experts/" in text
        assert "/moe.route/" in text
