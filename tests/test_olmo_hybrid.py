"""The Gated-DeltaNet family (``models/olmo_hybrid.py``: linear-attention
layers with a per-slot state three to one with full-attention layers whose
K|V rows are read in place from the family's one pool, a dense MLP in every
layer, the tree one period stacked over the periods) against the plain
float32 reference the benchmark judges it by
(``perfbench/reference/gdn_hybrid.py``), at the tiny size, on the CPU.

Tolerances. With the served tree widened to float32 and matmuls at highest
precision the two implementations differ by rounding order alone (chunked WY
form against token by token; online softmax over pages and a side window
against one masked softmax): logits of magnitude ~0.9 agree to 5e-5 (seen:
7e-7). Every control, the same served logits against the reference with ONE
named term wrong, moves them by thousands of times the bound (seen: rotary
embedding on the full layers 0.27, no decay 0.33, beta without its factor 2
0.35, no convolution 0.74, the state's axes swapped 0.83). Served in
bfloat16 the comparison reads 1.2 % of max|logit|; 8 % bounds it, and a
state kept in bfloat16 between decode steps (float32 everything else) moves
the float32 comparison to 4e-3, eighty times its bound.
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

from distributed_inference_engine_tpu.models import olmo_hybrid  # noqa: E402
from distributed_inference_engine_tpu.models.base import (  # noqa: E402
    layered_family,
)
from distributed_inference_engine_tpu.ops import flash_prefill  # noqa: E402
from perfbench.lib import families  # noqa: E402
from test_ling import Served as _Served  # noqa: E402  (this directory)

F32_TOL = 5e-5
BF16_TOL = 0.08          # of max|logit|

with open(os.path.join(ROOT, "perfbench", "rehearse", "olmo-tiny.json")) as _f:
    CFG = json.load(_f)
REF = families.reference(CFG)

Served = partial(_Served, family=olmo_hybrid)


def tiny_spec(**kw):
    return olmo_hybrid.olmo_hybrid_spec("olmo-hybrid-tiny", max_seq_len=128,
                                        **kw)


@pytest.fixture(scope="module")
def served_bf16():
    return olmo_hybrid.init_params(tiny_spec(), jax.random.key(7))


@pytest.fixture(scope="module")
def served_f32(served_bf16):
    return jax.tree.map(lambda a: a.astype(jnp.float32), served_bf16)


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


# prompts of unequal length (none a multiple of the recurrence's chunk); the
# 77-token row decodes from 37 across the page boundaries at 48 and 64 (pages
# of 16) and ten 4-step chunks
PROMPTS = (20, 37, 5)


@pytest.fixture(scope="module")
def float32_run(served_f32):
    """Three rows of unequal length and a pad row prefilled at a padded
    bucket, then decoded through the pages and the state (the fourth slot a
    dead row of every step): once, for the tests that hold it against the
    reference and against each control."""
    seqs = sequences()
    with jax.default_matmul_precision("highest"):
        got, sv = served_logits(tiny_spec(dtype="float32"), served_f32, seqs,
                                PROMPTS)
    return seqs, got, sv


def test_served_float32_logits_are_the_references(served_f32, float32_run):
    seqs, got, _ = float32_run
    with jax.default_matmul_precision("highest"):
        worst, scale = max_diff(got, CFG, served_f32, seqs)
    assert worst < F32_TOL and scale > 0.3, (worst, scale)


@pytest.mark.parametrize("control", REF.CONTROLS)
def test_a_wrong_term_fails(served_f32, float32_run, control):
    """The tolerance is tight enough to see each control: the same served
    logits against the reference with one named term wrong."""
    seqs, got, _ = float32_run
    with jax.default_matmul_precision("highest"):
        worst, _ = max_diff(got, CFG, served_f32, seqs, control=control)
    assert worst > 100 * F32_TOL, (control, worst)


def test_an_unknown_control_is_an_error(served_f32):
    with pytest.raises(ValueError, match="unknown control"):
        REF.logits(CFG, served_f32, jnp.asarray([1, 2, 3]), control="nope")


def test_the_interpreted_kernel_reads_the_pool_as_the_xla_body(served_f32):
    """The TPU body (``ops/flash_decode.py`` with ``kv_fused``: the pool's
    K|V rows copied where they lie) through the interpreter, against the
    XLA body on the same rows, and both against the reference."""
    seqs = sequences(3)
    spec = tiny_spec(dtype="float32")
    with jax.default_matmul_precision("highest"):
        xla, _ = served_logits(spec, served_f32, seqs, PROMPTS)
        ker, _ = served_logits(spec, served_f32, seqs, PROMPTS,
                               attn_impl="pallas-decode_interpret")
        worst, _ = max_diff(ker, CFG, served_f32, seqs)
    assert worst < F32_TOL, worst
    assert max(float(np.abs(a - b).max()) for a, b in zip(xla, ker)) < F32_TOL


def test_prefill_through_the_interpreted_kernel_is_the_xla_path(monkeypatch,
                                                               served_f32):
    """``olmo-hybrid-tiny``'s prefill with the flash kernel forced through
    the interpreter (blocks of 16 in a bucket of 96; MHA: several K/V heads
    a step, no window; rows of 77, 45 and 9 tokens and a pad row) against
    the XLA body this process resolves by itself, at the family's float32
    limit; and against the reference."""
    seqs = sequences()
    with jax.default_matmul_precision("highest"):
        spec = tiny_spec(dtype="float32")
        _, xla = Served(spec, served_f32).prefill(seqs, 96)
        monkeypatch.setattr(flash_prefill, "Q_BLOCK", 16)
        monkeypatch.setattr(flash_prefill, "K_BLOCK", 16)
        monkeypatch.setattr(flash_prefill, "prefill_impl",
                            lambda t, dh: "flash_interpret")
        real, windows = flash_prefill._flash_prefill, []
        monkeypatch.setattr(
            flash_prefill, "_flash_prefill",
            lambda *a, **kw: windows.append(kw["window"]) or real(*a, **kw))
        _, got = Served(spec, served_f32).prefill(seqs, 96)
        worst, scale = max_diff(got, CFG, served_f32, seqs)
    assert windows == [0]                 # the period's one full layer
    assert max(float(np.abs(a - b).max()) for a, b in zip(got, xla)) < F32_TOL
    assert worst < F32_TOL, (worst, scale)


@pytest.mark.parametrize("lens,pages_per_block", [
    ((40, 0, 0, 0), 1), ((40, 0, 0, 0), 0), ((0, 0, 17, 0), 2),
    ((0, 0, 0, 0), 0)])
def test_the_kernel_counts_the_pages_it_copies(lens, pages_per_block):
    """``count_pages``: one more output, the kernel's own count of the page
    copies it started: a live row's pages below its prefix, rounded up to
    whole pages of 8, nothing of a dead row; the attention is the same.
    (One live row: under the interpreter a later row's first block would be
    issued, and counted, twice: ``ops/flash_decode.py``.)"""
    from distributed_inference_engine_tpu.ops.flash_decode import (
        flash_decode_attention_pallas,
    )

    b, h, dh, p, mp, w = 4, 2, 64, 8, 6, 4
    n = b * mp + 2
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (b, h, dh), jnp.float32)
    pool = jax.random.normal(ks[1], (n, p, 2 * h * dh), jnp.float32)
    table = jax.random.permutation(ks[2], n)[:b * mp].reshape(b, mp)
    side = jax.random.normal(ks[3], (b, w, h, dh), jnp.float32)
    lens = jnp.asarray(lens, jnp.int32)
    args = (q, pool, pool, table.astype(jnp.int32), lens, side, side,
            (lens > 0).astype(jnp.int32))
    kw = dict(n_kv_heads=h, interpret=True, kv_fused=True,
              pages_per_block=pages_per_block)
    plain = flash_decode_attention_pallas(*args, **kw)
    out, pages = flash_decode_attention_pallas(*args, count_pages=True, **kw)
    assert pages.dtype == jnp.int32 and pages.shape == ()
    assert int(pages) == int(jnp.sum(-(-lens // p)))
    assert float(jnp.abs(out - plain).max()) == 0.0


def test_served_bfloat16_logits_are_near_the_references(served_bf16):
    seqs = sequences(1)
    got, _ = served_logits(tiny_spec(), served_bf16, seqs, PROMPTS)
    worst, scale = max_diff(got, CFG, served_bf16, seqs)
    assert worst < BF16_TOL * scale, (worst, scale)


def test_a_bfloat16_state_fails_the_float32_tolerance(served_f32):
    """The state is float32 between decode steps: rounded to bfloat16 there
    (everything else as the float32 run) the logits leave the bound."""
    seqs = sequences()
    with jax.default_matmul_precision("highest"):
        got, _ = served_logits(tiny_spec(dtype="float32"), served_f32, seqs,
                               PROMPTS, state_dtype=jnp.bfloat16)
        worst, _ = max_diff(got, CFG, served_f32, seqs)
    assert worst > 10 * F32_TOL, worst


def test_the_reference_in_bfloat16_is_a_control_not_the_reference(
        served_bf16):
    seq = jnp.asarray(sequences(2)[1])
    ref = np.asarray(REF.logits(CFG, served_bf16, seq))
    low = REF.logits(CFG, served_bf16, seq, dtype=jnp.bfloat16)
    assert low.dtype == jnp.bfloat16 and ref.dtype == np.float32
    worst = float(np.abs(np.asarray(low, np.float32) - ref).max())
    assert 10 * F32_TOL < worst < 2 * BF16_TOL * float(np.abs(ref).max())
    # only the last positions: what a long chain's judge reads
    tail = np.asarray(REF.logits(CFG, served_bf16, seq, last=5))
    assert tail.shape[0] == 5
    assert float(np.abs(tail - ref[-5:]).max()) < 1e-5


def test_a_reused_slot_serves_a_fresh_sequence(served_f32):
    """Free a slot after a long sequence, serve a fresh one in it: the
    state is zeroed, the pages are re-issued and nothing of the first
    sequence is read."""
    spec = tiny_spec(dtype="float32")
    first, second = sequences(2, (60, 28))
    with jax.default_matmul_precision("highest"):
        sv = Served(spec, served_f32, slots=1)
        (slot,), _ = sv.prefill([first[:30]], 48)
        sv.decode({slot: first[30:]}, {slot: 30})
        assert float(jnp.abs(sv.kv.state["S"]).max()) > 0
        sv.kv.free_slot(slot)
        assert float(jnp.abs(sv.kv.state["S"]).max()) == 0
        (slot2,), pre = sv.prefill([second[:17]], 48)
        assert slot2 == slot
        dec, _ = sv.decode({slot2: second[17:]}, {slot2: 17})
        got = np.concatenate([pre[0], np.stack(dec[slot2])])
        worst, _ = max_diff([got], CFG, served_f32, [second])
    assert worst < F32_TOL, worst


def test_the_spec_says_what_the_family_holds(served_bf16):
    spec = tiny_spec()
    assert (spec.paged_layers, spec.state_layers, spec.recurrent) == (2, 6,
                                                                      True)
    assert layered_family(spec) is olmo_hybrid
    assert spec.kv_row_lanes == 128 and spec.cache_row_width == 256
    # one period's four layer dicts, each stacked over the two periods
    assert len(served_bf16["period"]) == 4
    assert served_bf16["period"][0]["wq"].shape == (2, 128, 4 * 16)
    assert served_bf16["period"][3]["wq"].shape == (2, 128, 128)
    assert served_bf16["period"][0]["a_log"].dtype == jnp.float32
    sv = Served(spec, served_bf16, slots=2)
    assert sv.kv.k_pages.shape == (2, 32, 16, 256)
    # four heads of 16 x 32 side by side: one row of 128 lanes
    assert sv.kv.state["S"].shape == (2, 3, 2, 1, 16, 128)
    assert sv.kv.state["S"].dtype == jnp.float32
    assert sv.kv.get_stats()["state_bytes"] == \
        2 * olmo_hybrid.state_bytes_per_slot(spec)
    # the published size: 16 layers, 12 states, 4 pools of 7,680-value rows
    pub = olmo_hybrid.olmo_hybrid_spec()
    assert (pub.n_layers, pub.state_layers, pub.paged_layers,
            pub.cache_row_width, pub.head_dim) == (16, 12, 4, 7680, 128)
    assert (pub.gdn_key_head_dim, pub.gdn_value_head_dim) == (96, 192)


def test_the_drawn_gates_spread(served_f32):
    """How the weights are drawn decides whether the controls judge
    anything: over random tokens a layer's decay spreads over most of
    0.5-0.999 and beta over most of (0, 2)."""
    from distributed_inference_engine_tpu.ops import kda

    blk = jax.tree.map(lambda a: a[0], served_f32["period"][1])
    x = served_f32["tok_emb"][jnp.arange(1, 200)]
    decay = np.exp(np.asarray(kda.gdn_gate(
        x @ blk["w_a"], blk["a_log"], blk["dt_bias"])))
    beta = 2 * np.asarray(jax.nn.sigmoid(x @ blk["w_b"]))
    assert decay.min() < 0.8 and decay.max() > 0.99 and decay.min() > 0.05
    assert beta.min() < 0.5 and beta.max() > 1.5


@pytest.mark.parametrize("kinds", [("gdn", "full", "gdn", "full", "gdn"),
                                   ("gdn", "gdn", "gdn"),
                                   ("gdn", "mla", "gdn", "mla")])
def test_a_spec_of_no_whole_periods_is_refused(kinds):
    with pytest.raises(ValueError, match="whole periods|not"):
        tiny_spec(n_layers=len(kinds), layer_kinds=kinds,
                  layer_mlps=("dense",) * len(kinds),
                  layer_ids=tuple(range(len(kinds))))
