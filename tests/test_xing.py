"""The mHC family (``models/xing.py``: four residual streams mixed by
Sinkhorn-normalised hyper-connections around every sublayer, query-compressed
YaRN MLA whose latent rows are every layer's only cache, sigmoid-routed
experts all held) against the plain float32 reference the benchmark judges it
by (``perfbench/reference/xing4_mhc.py``), at the tiny size, on the CPU.

Tolerances. With the served tree widened to float32 and matmuls at highest
precision the two implementations differ by rounding order alone: logits of
magnitude ~0.8 agree to 5e-5 (seen: 2e-7 after decoding through the pages).
Every control below, the same served logits against a reference with ONE
named term wrong, moves them by at least five times the bound (seen, the
smallest: the YaRN softmax factor 7e-4, gates not renormalised 9e-4 (the
routed experts are drawn an eighth of the shared one), plain RoPE
frequencies 2e-3, mHC in bfloat16 4e-3; one Sinkhorn round 2e-2, H_res
transposed 6e-2, the rest 4e-2 to 0.2). Served in bfloat16 the same
comparison reads 0.3 % of max|logit|; 8 % bounds it.
"""

import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from distributed_inference_engine_tpu.engine.paged_kv import (  # noqa: E402
    PagedKVCache,
)
from distributed_inference_engine_tpu.models import (  # noqa: E402
    ling_spec, xing,
)
from distributed_inference_engine_tpu.models.base import (  # noqa: E402
    LAYERED_FAMILY, decode_sums, layered_family, prefill_sums,
)
from distributed_inference_engine_tpu.models.mellum import (  # noqa: E402
    mellum_spec,
)
from distributed_inference_engine_tpu.models.olmo_hybrid import (  # noqa: E402
    olmo_hybrid_spec,
)
from distributed_inference_engine_tpu.ops import mhc, mla  # noqa: E402
from perfbench.lib import families  # noqa: E402
from test_ling import Served as _Served  # noqa: E402  (this directory)

F32_TOL = 5e-5
BF16_TOL = 0.08          # of max|logit|

with open(os.path.join(ROOT, "perfbench", "rehearse", "xing-tiny.json")) as _f:
    CFG = json.load(_f)
REF = families.reference(CFG)


def tiny_spec(**kw):
    return xing.xing_spec("xing-tiny", max_seq_len=128, **kw)


@pytest.fixture(scope="module")
def served_bf16():
    return xing.init_params(tiny_spec(), jax.random.key(7))


@pytest.fixture(scope="module")
def served_f32(served_bf16):
    return jax.tree.map(lambda a: a.astype(jnp.float32), served_bf16)


# the serving programs driven by hand through ``PagedKVCache`` (prefill at a
# padded bucket, teacher-forced decode chunks through the latent pages, every
# position's logits; slots not fed are dead rows of every step): the hybrid
# family's driver, over this family's programs
Served = partial(_Served, family=xing)


def served_logits(spec, params, seqs, n_prompt, bucket=48, **kw):
    """Full-position logits of each sequence: its first ``n_prompt[i]``
    tokens prefilled together at a padded bucket, the rest decoded."""
    sv = Served(spec, params, **kw)
    prompts = [s[:n] for s, n in zip(seqs, n_prompt)]
    slots, pre = sv.prefill(prompts, bucket)
    dec, _ = sv.decode({sl: s[n:] for sl, s, n in zip(slots, seqs, n_prompt)},
                       {sl: n for sl, n in zip(slots, n_prompt)})
    return [np.concatenate([p, np.stack(dec[sl])]) if len(dec[sl]) else p
            for sl, p in zip(slots, pre)], sv


def sequences(seed=0, lens=(45, 77, 9)):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, CFG["vocab_size"], n)]
            for n in lens]


def max_diff(got, cfg, params, seqs, **kw):
    worst, scale = 0.0, 0.0
    for lg, seq in zip(got, seqs):
        ref = np.asarray(REF.logits(cfg, params, jnp.asarray(seq), **kw))
        worst = max(worst, float(np.abs(lg - ref).max()))
        scale = max(scale, float(np.abs(ref).max()))
    return worst, scale


# ---------------------------------------------- served path vs the reference

# prompts of unequal length; the 77-token row decodes from 37 across the
# page boundaries at 48 and 64 (pages of 16) and ten 4-step chunks, past the
# YaRN ramp's original context of 32
PROMPTS = (20, 37, 5)


@pytest.fixture(scope="module")
def float32_run(served_f32):
    """Three rows of unequal length and a pad row prefilled at a padded
    bucket, then decoded through the latent pages (the fourth slot a dead
    row of every step): once, for the tests that hold it against the
    reference and against each control."""
    seqs = sequences()
    with jax.default_matmul_precision("highest"):
        got, sv = served_logits(tiny_spec(dtype="float32"), served_f32, seqs,
                                PROMPTS)
    return seqs, got, sv


def test_served_float32_logits_are_the_references(served_f32, float32_run):
    seqs, got, sv = float32_run
    with jax.default_matmul_precision("highest"):
        worst, scale = max_diff(got, CFG, served_f32, seqs)
    assert worst < F32_TOL and scale > 0.3, (worst, scale)
    # every expert is held: all choices land
    assert sv.moe[1] > 0 and sv.moe[0] == sv.moe[1]


@pytest.mark.parametrize("control", REF.CONTROLS)
def test_a_wrong_term_fails(served_f32, float32_run, control):
    """The tolerance is tight enough to see each control: the same served
    logits against the reference with one named term wrong."""
    seqs, got, _ = float32_run
    with jax.default_matmul_precision("highest"):
        worst, _ = max_diff(got, CFG, served_f32, seqs, control=control)
    assert worst > 5 * F32_TOL, (control, worst)


def test_an_unknown_control_is_an_error(served_f32):
    with pytest.raises(ValueError, match="unknown control"):
        REF.logits(CFG, served_f32, jnp.asarray([1, 2, 3]), control="nope")


def test_served_bfloat16_logits_are_near_the_references(served_bf16):
    seqs = sequences(1)
    got, _ = served_logits(tiny_spec(), served_bf16, seqs, PROMPTS)
    worst, scale = max_diff(got, CFG, served_bf16, seqs)
    assert worst < BF16_TOL * scale, (worst, scale)


def test_the_reference_in_bfloat16_is_a_control_not_the_reference(
        served_bf16):
    seq = jnp.asarray(sequences(2)[1])
    ref = np.asarray(REF.logits(CFG, served_bf16, seq))
    low = REF.logits(CFG, served_bf16, seq, dtype=jnp.bfloat16)
    assert low.dtype == jnp.bfloat16 and ref.dtype == np.float32
    worst = float(np.abs(np.asarray(low, np.float32) - ref).max())
    assert 5 * F32_TOL < worst < 2 * BF16_TOL * float(np.abs(ref).max())
    # only the last positions: what a long chain's judge reads
    tail = np.asarray(REF.logits(CFG, served_bf16, seq, last=5))
    assert tail.shape[0] == 5
    assert float(np.abs(tail - ref[-5:]).max()) < 1e-6


def test_a_reused_slot_serves_a_fresh_sequence(served_f32):
    """Free a slot after a long sequence, serve a fresh one in it: the
    pages are re-issued and nothing of the first sequence is read."""
    spec = tiny_spec(dtype="float32")
    first, second = sequences(2, (60, 28))
    with jax.default_matmul_precision("highest"):
        sv = Served(spec, served_f32, slots=1)
        (slot,), _ = sv.prefill([first[:30]], 48)
        sv.decode({slot: first[30:]}, {slot: 30})
        sv.kv.free_slot(slot)
        (slot2,), pre = sv.prefill([second[:17]], 48)
        assert slot2 == slot
        dec, _ = sv.decode({slot2: second[17:]}, {slot2: 17})
        got = np.concatenate([pre[0], np.stack(dec[slot2])])
        worst, _ = max_diff([got], CFG, served_f32, [second])
    assert worst < F32_TOL, worst


_PER_LAYER = {
    "ling-tiny": lambda: ling_spec("ling-tiny"),
    "xing-tiny": lambda: xing.xing_spec("xing-tiny", max_seq_len=128),
    "kimi-tiny": lambda: xing.kimi_spec("kimi-tiny", max_seq_len=128),
    "olmo-hybrid-tiny": lambda: olmo_hybrid_spec("olmo-hybrid-tiny"),
    "mellum-tiny": lambda: mellum_spec("mellum-tiny", max_seq_len=256),
}


@pytest.mark.parametrize("size", sorted(_PER_LAYER))
def test_a_family_module_defines_the_whole_interface(size):
    """What ``layered_family``'s docstring says a family module holds: every
    name, counters named once each as ``<group>.<key>``, and as many names
    as the vectors its two programs' bodies return (traced, not run)."""
    spec = _PER_LAYER[size]()
    fam = layered_family(spec)
    assert [n for n in LAYERED_FAMILY if not hasattr(fam, n)] == []
    for names in (fam.DECODE_COUNTERS, fam.PREFILL_COUNTERS):
        named = [n for n in names if n]
        assert len(set(named)) == len(named)
        assert all(n.count(".") == 1 for n in named)
    kv = PagedKVCache(spec, max_slots=2, page_size=8, num_pages=32,
                      max_seq_len=64)
    pages, state = kv.pools
    params = jax.eval_shape(lambda: fam.init_params(spec, jax.random.key(0)))
    z = jnp.zeros((2,), jnp.int32)
    side = jnp.zeros((spec.window_layers + spec.paged_layers, 2, 4,
                      pages.shape[-1]),
                     pages.dtype)
    step = jax.eval_shape(
        lambda p: fam.forward_decode_step(
            spec, p, z, z, z, fam.decode_context(pages, kv.page_table, "xla"),
            side, state, z > 0), params)
    assert step[3].shape == (len(fam.DECODE_COUNTERS),)
    back = jax.eval_shape(
        lambda: fam.write_side(pages, state, side, kv.page_table, z, z))
    assert back[0].shape == pages.shape and set(back[1]) == set(state)
    table_rows = jnp.zeros((2, kv.max_pages_per_seq), jnp.int32)
    prefill = jax.eval_shape(
        lambda p: fam.forward_prefill_into_pages(
            spec, p, jnp.zeros((2, 16), jnp.int32), z + 1, pages, state,
            table_rows, z), params)
    assert prefill[3].shape == (len(fam.PREFILL_COUNTERS),)
    sums = {**decode_sums(spec, np.array([3, 0]), np.array([10, 5])),
            **prefill_sums(spec, 10, 16)}
    assert all(n.count(".") == 1 and type(v) is int and v > 0
               for n, v in sums.items()), sums
    assert not set(sums) & set(fam.DECODE_COUNTERS + fam.PREFILL_COUNTERS)


def test_the_state_is_zero_layers_wide_and_rides_every_program(served_f32):
    spec = tiny_spec(dtype="float32")
    assert (spec.paged_layers, spec.state_layers, spec.recurrent) == (4, 0,
                                                                      False)
    assert layered_family(spec) is xing
    sv = Served(spec, served_f32, slots=2)
    assert {a.shape for a in sv.kv.state.values()} == {(0, 2)}
    (slot,), _ = sv.prefill([[5, 6, 7]], 48)
    assert {a.shape for a in sv.kv.state.values()} == {(0, 2)}
    sv.kv.free_slot(slot)
    assert sv.kv.get_stats()["state_bytes"] == 0


def test_one_swapped_expert_moves_an_mlp_output_by_little(monkeypatch,
                                                          served_bf16):
    """The routed experts' down projections are drawn an eighth of the
    shared expert's: served in bfloat16, top-4 of 64 scores now and then
    swaps the 4th best expert for the 5th against a float32 reference (their
    scores closer than the rounding), and that must move the layer's output
    by the rounding's own size, not by a fifth (``ROUTED_DOWN_SCALE``)."""
    from distributed_inference_engine_tpu.ops import moe_routed

    spec = tiny_spec()
    blk = served_bf16["layers"][1]
    ratio = float(jnp.std(blk["w_down"].astype(jnp.float32))
                  / jnp.std(blk["ws_down"].astype(jnp.float32)))
    assert abs(ratio / xing.ROUTED_DOWN_SCALE - 1) < 0.05, ratio
    x = jax.random.normal(jax.random.key(3), (16, spec.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    valid = jnp.ones((16,), bool)
    out, _ = moe_routed.moe_block(spec, blk, x, valid, "xla")
    # the same rows with every token's LAST choice replaced by its next best
    real = moe_routed.route

    def swapped(spec_, x_, w, bias):
        k = spec_.experts_per_token
        idx, g = real(spec_.replace(experts_per_token=k + 1), x_, w, bias)
        idx = jnp.concatenate([idx[:, :k - 1], idx[:, k:]], 1)
        return idx, real(spec_, x_, w, bias)[1]

    monkeypatch.setattr(moe_routed, "route", swapped)
    other, _ = moe_routed.moe_block(spec, blk, x, valid, "xla")
    moved = float(jnp.linalg.norm((out - other).astype(jnp.float32))
                  / jnp.linalg.norm(out.astype(jnp.float32)))
    assert 0.0 < moved < 0.2, moved


# ------------------------------------------------------------------- mHC


def _hc_inputs(n_tok=6, seed=0):
    spec = tiny_spec(dtype="float32")
    hc = mhc.init_hc(spec, jax.random.key(seed))
    x = jax.random.normal(jax.random.key(seed + 1),
                          (n_tok, spec.hc_mult, spec.d_model))
    return spec, hc, x


def test_sinkhorn_gives_an_uneven_doubly_stochastic_matrix():
    """20 rounds from ``init_hc``'s biases (spread 3 and a shift towards
    the next stream: far from doubly stochastic, so the rounds have work to
    do: the rows are within 2e-2 of 1 after 20, the columns exact): the
    matrix is neither uniform nor symmetric, and it depends on the input."""
    spec, hc, x = _hc_inputs()
    pre, post, res = mhc.hc_maps(spec, hc, x)
    res = np.asarray(res)                               # [n, n, N]
    assert np.abs(res.sum(0) - 1).max() < 1e-5
    assert np.abs(res.sum(1) - 1).max() < 2e-2
    assert np.abs(res - np.swapaxes(res, 0, 1)).max() > 0.05
    assert res.std(axis=(0, 1)).min() > 0.05
    assert np.ptp(res, axis=-1).max() > 0.002     # over the six tokens
    assert 0 < float(pre.min()) and float(pre.max()) < 1
    assert 0 < float(post.min()) and float(post.max()) < 2
    assert float(pre.std()) > 0.1 and float(post.std()) > 0.2


def test_mhc_ops_are_the_references_sublayer():
    """maps, read and write-back against the reference's sublayer with F =
    a fixed linear map; one round and a transposed H_res are seen."""
    spec, hc, x = _hc_inputs(9, 3)
    scale = jnp.linspace(0.5, 1.5, spec.d_model)
    w = 0.1 * jax.random.normal(jax.random.key(9),
                                (spec.d_model, spec.d_model))

    def ours(x):
        pre, post, res = mhc.hc_maps(spec, hc, x)
        h = REF.rms_norm(mhc.hc_read(x, pre), scale, spec.norm_eps)
        return mhc.hc_write(x, h @ w, post, res)

    with jax.default_matmul_precision("highest"):
        got = ours(x)
        for control, same in (("", True), ("one_sinkhorn_round", False),
                              ("res_transposed", False),
                              ("pre_post_swapped", False)):
            want = REF.sublayer(CFG, hc, scale, x, lambda h: h @ w, control)
            diff = float(jnp.abs(got - want).max())
            assert (diff < 1e-5) == same, (control, diff)


def test_the_clamp_holds_an_extreme_bias():
    spec, hc, x = _hc_inputs()
    hc = dict(hc, bias=hc["bias"].at[2 * spec.hc_mult].set(500.0))
    _pre, _post, res = mhc.hc_maps(spec, hc, x)
    assert bool(jnp.isfinite(res).all())
    assert np.abs(np.asarray(res).sum(0) - 1).max() < 1e-4


# ------------------------------------------------------------------ YaRN


def test_yarn_frequencies_at_the_published_numbers():
    """factor 64 over 4,096 at theta 10,000, 32 pairs: the ramp runs from
    pair 10 to pair 23 and the softmax scale gains mscale^2 = 2.005."""
    sc = xing.xing_spec("xing4.0-pp1").rope_scaling
    inv, amp = mla.yarn_inv_freq(64, 10000.0, sc)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    assert np.allclose(inv[:11], plain[:11], rtol=1e-6)
    assert np.allclose(inv[23:], plain[23:] / 64, rtol=1e-6)
    mid = inv[11:23] / plain[11:23]
    assert (np.diff(mid) < 0).all() and 1 / 64 < mid.min() and mid.max() < 1
    assert amp == 1.0
    scale = mla.yarn_softmax_scale(192, sc)
    assert abs(scale * 192 ** 0.5 - (0.1 * np.log(64) + 1) ** 2) < 1e-6
    assert abs(scale * 192 ** 0.5 - 2.005) < 1e-3


def test_the_tiny_ramp_is_crossed_inside_a_test_sequence():
    sc = tiny_spec().rope_scaling
    inv, _ = mla.yarn_inv_freq(8, 10000.0, sc)
    ratio = inv / 10000.0 ** (-np.arange(4) / 4.0)
    assert ratio[0] == 1.0 and abs(ratio[-1] - 0.25) < 1e-6
    # the reference computes its own and agrees
    freq, amp, soft = REF.yarn(CFG, "")
    assert np.allclose(np.asarray(freq), inv, rtol=1e-6) and amp == 1.0
    assert abs(soft - mla.yarn_softmax_scale(24, sc)) < 1e-7


def _attention_inputs(b, t, h, dims, dtype=jnp.float32):
    dn, dr, dv = dims
    ks = jax.random.split(jax.random.key(t + h), 4)
    qn, qr, kv = (jax.random.normal(k, (b, t, h, d), dtype)
                  for k, d in zip(ks, (dn, dr, dn + dv)))
    # values a quarter as large: outputs of magnitude <= 1, as the layers'
    kv = kv.at[..., dn:].multiply(0.25)
    return qn, qr, kv, jax.random.normal(ks[3], (b, t, dr), dtype)


def test_skipping_masked_key_blocks_gives_the_same_attention():
    """The XLA body reads, for a block of queries, only the keys up to its
    last row: the sums of the one-block form to rounding order, rows past a
    sequence's end aside."""
    b, t, h = 2, 64, 3
    qn, qr, kv, kr = _attention_inputs(b, t, h, (16, 8, 16))
    lens = jnp.asarray([64, 37])
    args = (qn, qr, kv[..., :16], kr, kv[..., 16:], lens, 24 ** -0.5)
    whole = mla.mla_causal_attention_xla(*args, q_block=t)
    blocked = mla.mla_causal_attention_xla(*args, q_block=16)
    live = (jnp.arange(t)[None, :] < lens[:, None])[..., None, None]
    assert float(jnp.abs(jnp.where(live, blocked - whole, 0)).max()) < 2e-6
    # fewer scores: the unrolled blocks' key lengths are 16, 32, 48, 64
    text = str(jax.make_jaxpr(lambda *a: mla.mla_causal_attention_xla(
        *a, q_block=16))(*args))
    assert "f32[2,3,16,16]" in text and "f32[2,3,16,48]" in text
    assert "f32[2,3,64,64]" not in text
    # a T of no whole blocks is one block, and the default picks it here
    assert str(jax.make_jaxpr(lambda *a: mla.mla_causal_attention(
        *a))(qn, qr, kv, kr, lens)).count("f32[2,3,64,64]")


# (T, prompt length, heads, softmax scale, dtype): blocks of 512 at the
# published head widths. Lengths at a block's edge, inside a block, a whole
# bucket, one token, a pad row; the hybrid family's plain scale (None) and
# this family's YaRN scale; 8 heads = two head groups, 3 = a group of three
KERNEL_CASES = [
    (1024, 1024, 2, None, "float32"), (1024, 512, 2, "yarn", "float32"),
    (1024, 700, 8, None, "float32"), (1024, 1, 2, "yarn", "float32"),
    (1024, 0, 2, None, "float32"), (2048, 2048, 3, "yarn", "float32"),
    (2048, 1024, 2, None, "float32"), (2048, 1100, 2, "yarn", "float32"),
    (2048, 1537, 2, None, "float32"), (1024, 700, 2, "yarn", "bfloat16"),
    (2048, 2048, 2, None, "bfloat16"), (2048, 1100, 3, "yarn", "bfloat16"),
]


@pytest.mark.parametrize("t,n,h,scale,dtype", KERNEL_CASES)
def test_the_prefill_kernel_is_the_one_block_xla_body(t, n, h, scale, dtype):
    """The interpreted kernel against the whole-``T`` einsum / softmax: rows
    below ``seq_lens`` to rounding order (float32) or to the family's
    bfloat16 tolerance; query blocks wholly past the prompt are zeros."""
    if scale == "yarn":
        scale = mla.yarn_softmax_scale(192, {"factor": 64.0,
                                             "mscale_all_dim": 1.0})
    dn = 128
    qn, qr, kv, kr = _attention_inputs(1, t, h, (dn, 64, 128),
                                       jnp.dtype(dtype))
    lens = jnp.asarray([n])
    ref = mla.mla_causal_attention_xla(
        qn, qr, kv[..., :dn], kr, kv[..., dn:], lens,
        scale or 192 ** -0.5, q_block=t).astype(jnp.float32)
    got = jax.jit(lambda *a: mla.mla_causal_attention(
        *a, scale=scale, impl="flash_interpret"))(qn, qr, kv, kr, lens)
    assert got.shape == ref.shape and got.dtype == qn.dtype
    got = got.astype(jnp.float32)
    tol = 2e-6 if dtype == "float32" else BF16_TOL * float(jnp.abs(ref).max())
    if n:
        assert float(jnp.abs(got[:, :n] - ref[:, :n]).max()) < tol
    assert not bool(jnp.any(got[:, -(-n // mla.Q_BLOCK) * mla.Q_BLOCK:]))
    assert bool(jnp.isfinite(got).all())


@pytest.mark.parametrize("bq,bk", [(16, 32), (32, 16), (64, 16)])
def test_unequal_blocks_visit_the_same_keys(monkeypatch, bq, bk):
    """Query and key blocks of different heights: the pair tables, the
    diagonal and the finishing pair follow the rows, not the block index."""
    monkeypatch.setattr(mla, "Q_BLOCK", bq)
    monkeypatch.setattr(mla, "K_BLOCK", bk)
    qn, qr, kv, kr = _attention_inputs(3, 128, 2, (16, 8, 16))
    lens = jnp.asarray([128, 45, 64])
    ref = mla.mla_causal_attention_xla(qn, qr, kv[..., :16], kr,
                                       kv[..., 16:], lens, 0.2, q_block=128)
    got = mla.mla_causal_attention(qn, qr, kv, kr, lens, scale=0.2,
                                   impl="flash_interpret")
    live = (jnp.arange(128)[None, :] < lens[:, None])[..., None, None]
    assert float(jnp.abs(jnp.where(live, got - ref, 0)).max()) < 2e-6
    qi, ki = mla._pairs(128, bq, bk)
    assert len(qi) == mla.prefill_key_blocks(128, 128)[0]
    assert all(k * bk <= q * bq + bq - 1 for q, k in zip(qi, ki))


@pytest.mark.parametrize("backend,t,impl", [
    ("tpu", 1024, "flash"), ("tpu", 8704, "flash"), ("tpu", 512, "flash"),
    ("tpu", 48, "xla"), ("tpu", 1000, "xla"), ("cpu", 1024, "xla"),
    ("gpu", 8192, "xla")])
def test_the_kernel_is_chosen_on_a_tpu_at_whole_blocks(monkeypatch, backend,
                                                       t, impl):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert mla.prefill_impl(t) == impl


@pytest.mark.parametrize("length,t,visited,square", [
    (4100, 8192, 45, 256),       # 9 query blocks: 1 + 2 + ... + 9
    (8192, 8192, 136, 256), (512, 1024, 1, 4), (513, 1024, 3, 4),
    (0, 1024, 0, 4), (37, 48, 1, 1)])
def test_key_blocks_visited_by_hand(length, t, visited, square):
    assert mla.prefill_key_blocks(length, t) == (visited, square)


def test_prefill_into_pages_through_the_kernel_is_the_reference(monkeypatch,
                                                                served_f32):
    """``xing-tiny``'s prefill with the interpreted kernel forced (blocks of
    16 in the bucket of 48: three rows of unequal length and a pad row)
    against the float32 reference, at the limit the XLA body is held to."""
    monkeypatch.setattr(mla, "Q_BLOCK", 16)
    monkeypatch.setattr(mla, "K_BLOCK", 16)
    monkeypatch.setattr(mla, "prefill_impl", lambda t: "flash_interpret")
    seqs = [s[:n] for s, n in zip(sequences(), PROMPTS)]
    with jax.default_matmul_precision("highest"):
        sv = Served(tiny_spec(dtype="float32"), served_f32)
        _, got = sv.prefill(seqs, 48)
        worst, scale = max_diff(got, CFG, served_f32, seqs)
    assert worst < F32_TOL, (worst, scale)


def test_plain_rope_and_ling_trace_what_they_did():
    """No ``rope_scaling``: the default arguments are the old function."""
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 8))
    pos = jnp.arange(5)[None]
    a = mla.rope_interleaved(x, pos, 10000.0)
    b = mla.rope_interleaved(x, pos, 10000.0, None, 1.0)
    assert str(jax.make_jaxpr(lambda x: mla.rope_interleaved(
        x, pos, 10000.0))(x)) == str(jax.make_jaxpr(
            lambda x: mla.rope_interleaved(x, pos, 10000.0, None, 1.0))(x))
    assert bool((a == b).all())


# ------------------------------------------------------------------ specs


def test_sizes_and_the_spec_round_trip():
    cut = xing.xing_spec("xing4.0-pp1", max_seq_len=8704)
    assert cut.layer_ids == [0, 2, 3, 4, 5, 6, 7]
    assert cut.layer_mlps == ["dense"] + ["moe"] * 6
    assert cut.layer_kinds == ["mla"] * 7 and cut.experts_held == (0, 64)
    assert cut.cache_row_width == 640 and cut.paged_layers == 7
    assert (cut.hc_mult, cut.hc_sinkhorn_iters, cut.q_lora_rank) == (4, 20,
                                                                     768)
    assert cut.rope_scaling == {
        "type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096}
    again = type(cut).from_dict(json.loads(json.dumps(cut.to_dict())))
    assert again == cut and hash(again) == hash(cut)
    whole = xing.xing_spec("xing4.0-29b-a4b")
    assert whole.n_layers == 40 and list(whole.layer_mlps[:3]) == [
        "dense", "dense", "moe"]
    with pytest.raises(ValueError, match="unknown xing size"):
        xing.xing_spec("xing-huge")
    with pytest.raises(ValueError, match="hc_mult"):
        tiny_spec(q_lora_rank=0)


def test_the_mhc_draw_follows_the_seed_and_the_expert_bias_does_not():
    a = xing.init_params(tiny_spec(), jax.random.key(5))
    b = xing.init_params(tiny_spec(), jax.random.key(6))
    la, lb = a["layers"][1], b["layers"][1]
    assert la["hc_attn"]["phi"].dtype == jnp.float32
    assert la["hc_attn"]["phi"].shape == (4 * 64, 24)
    assert not bool((la["hc_attn"]["bias"] == lb["hc_attn"]["bias"]).all())
    assert not bool((la["hc_attn"]["bias"] == la["hc_mlp"]["bias"]).all())
    assert bool((la["router_bias"] == lb["router_bias"]).all())
    assert la["w_router"].dtype == jnp.float32
    assert la["w_gate_up"].dtype == jnp.bfloat16
    assert "router_bias" not in a["layers"][0]
