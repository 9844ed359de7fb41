"""The sliding-window family (``models/mellum.py``: sliding-window layers
three to one with full-attention layers, two pools of K|V pages of unlike
lifetimes, softmax-routed experts in every layer) against the plain float32
reference the benchmark judges it by (``perfbench/reference/swa_moe.py``), at
the tiny size (window 32, pages of 8), on the CPU.

Tolerances. With the served tree widened to float32 and matmuls at highest
precision the two implementations differ by rounding order alone (the band
in query blocks and an online softmax over pages and a side window against
one masked softmax; grouped experts against every expert weighted by its
gate): logits of magnitude ~0.8 agree to 5e-5 (seen: 1e-6). Every control,
the same served logits against the reference with ONE named term wrong, moves
them by tens to thousands of times the bound (seen: a window of 31 or of 33
rows 0.40 / 0.25, plain RoPE on the full layers 0.32, a missing attention
factor 0.097: the attention is drawn sharp, ``models/mellum.py``
``_layer_shapes``; gates not renormalised 8.9e-3 and a sigmoid router, which
picks the same experts and weighs them a little differently, 5.1e-3: the
routed experts are drawn at half the attention's scale, ``ROUTED_DOWN_SCALE``). Served in bfloat16
the comparison reads about 1 % of max|logit|; 8 % bounds it, and a cache
kept in bfloat16 (float32 everything else) or a router computed in bfloat16
(over 150 tokens a token swaps its 2nd and 3rd expert) moves the float32
comparison past its bound.
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

from distributed_inference_engine_tpu.engine.paged_kv import (  # noqa: E402
    PagedKVCache,
)
from distributed_inference_engine_tpu.models import mellum  # noqa: E402
from distributed_inference_engine_tpu.models.base import (  # noqa: E402
    ModelSpec,
    layered_family,
    unembed,
)
from distributed_inference_engine_tpu.ops import flash_decode  # noqa: E402
from distributed_inference_engine_tpu.ops import flash_prefill  # noqa: E402
from distributed_inference_engine_tpu.ops import moe_routed  # noqa: E402
from perfbench.lib import families  # noqa: E402

F32_TOL = 5e-5
BF16_TOL = 0.08          # of max|logit|
WINDOW, PAGE = 32, 8

with open(os.path.join(ROOT, "perfbench", "rehearse",
                       "mellum-tiny.json")) as _f:
    CFG = json.load(_f)
REF = families.reference(CFG)


def tiny_spec(**kw):
    return mellum.mellum_spec("mellum-tiny", max_seq_len=256, **kw)


class Served:
    """The serving programs driven by hand through ``PagedKVCache``: prefill
    at a padded bucket, then teacher-forced decode chunks through both page
    pools (the window's pages released and taken as the engine's ``_step``
    does), collecting every position's logits."""

    def __init__(self, spec, params, slots=4, pages=128, attn_impl="xla",
                 cache_dtype=None):
        self.spec, self.params = spec, params
        self.attn_impl = attn_impl
        self.kv = PagedKVCache(spec, max_slots=slots, page_size=PAGE,
                               num_pages=pages, max_seq_len=256,
                               dtype=cache_dtype or spec.dtype)
        if cache_dtype:
            self.kv.state = dict(
                self.kv.state, window_pages=self.kv.state[
                    "window_pages"].astype(cache_dtype))
        self.moe = np.zeros(3, np.int64)
        self.rows_read = np.zeros(2, np.int64)     # full, window: a layer
        self.held = []         # (slot, position, window pages, full pages)

    def prefill(self, prompts, bucket):
        n = len(prompts)
        bb = 1 << (n - 1).bit_length()
        slots = [self.kv.alloc_slot(len(p)) for p in prompts]
        toks = np.zeros((bb, bucket), np.int32)
        lens = np.zeros((bb,), np.int32)
        table = np.zeros((bb, self.kv.max_pages_per_seq), np.int32)
        ids = np.full((bb,), self.kv.max_slots, np.int32)
        for i, (p, s) in enumerate(zip(prompts, slots)):
            toks[i, :len(p)], lens[i], ids[i] = p, len(p), s
            table[i] = self.kv._table[s]
        hidden, kp, st, moe = jax.jit(
            lambda *a: mellum.forward_prefill_into_pages(
                self.spec, self.params, *a))(
            jnp.asarray(toks), jnp.asarray(lens), *self.kv.pools,
            jnp.asarray(table), jnp.asarray(ids))
        self.kv.swap(kp, st)
        self.moe += np.asarray(moe)
        logits = unembed(self.spec, self.params, hidden)
        return slots, [np.asarray(logits[i, :len(p)])
                       for i, p in enumerate(prompts)]

    def decode(self, feeds, lengths, n_steps=4):
        """``feeds[slot]`` = the tokens to feed next; returns per slot the
        logits after each fed token."""
        b = self.kv.max_slots
        out = {s: [] for s in feeds}
        step = jax.jit(lambda kp, table, tok, cur, start, *a:
                       mellum.forward_decode_step(
            self.spec, self.params, tok, cur, start,
            mellum.decode_context(kp, table, self.attn_impl), *a))
        pos = dict(lengths)
        fed = {s: 0 for s in feeds}
        while any(fed[s] < len(feeds[s]) for s in feeds):
            for s in feeds:
                if fed[s] < len(feeds[s]):
                    self.kv.release_behind_window(s, pos[s])
                    self.kv.ensure_capacity(s, pos[s] + n_steps)
                    self.held.append((s, pos[s], self.kv.window_pages_held(s),
                                      len(self.kv._slot_pages[s])))
            start = np.zeros((b,), np.int32)
            for s in feeds:
                start[s] = pos[s]
            side = jnp.zeros((self.spec.n_layers, b, n_steps,
                              self.spec.cache_row_width), self.kv.dtype)
            kp, state = self.kv.pools
            cur = start.copy()
            for _ in range(n_steps):
                tok = np.zeros((b,), np.int32)
                act = np.zeros((b,), bool)
                for s in feeds:
                    if fed[s] < len(feeds[s]):
                        tok[s], act[s] = feeds[s][fed[s]], True
                hidden, side, state, c = step(
                    kp, self.kv.page_table, jnp.asarray(tok),
                    jnp.asarray(cur), jnp.asarray(start), side, state,
                    jnp.asarray(act))
                c = np.asarray(c)
                self.moe += c[1:4]
                self.rows_read += c[[0, 4]]
                logits = np.asarray(unembed(self.spec, self.params, hidden))
                for s in feeds:
                    if act[s]:
                        out[s].append(logits[s])
                        fed[s] += 1
                        cur[s] += 1
            kp, state = mellum.write_side(
                kp, state, side, self.kv.page_table,
                jnp.asarray(cur - start), jnp.asarray(start))
            self.kv.swap(kp, state)
            pos = {s: int(cur[s]) for s in feeds}
        return out, pos


def served_logits(spec, params, seqs, n_prompt, bucket=64, **kw):
    """Full-position logits of each sequence: its first ``n_prompt[i]``
    tokens prefilled together at a padded bucket, the rest decoded."""
    sv = Served(spec, params, **kw)
    prompts = [s[:n] for s, n in zip(seqs, n_prompt)]
    slots, pre = sv.prefill(prompts, bucket)
    dec, _ = sv.decode({sl: s[n:] for sl, s, n in zip(slots, seqs, n_prompt)},
                       {sl: n for sl, n in zip(slots, n_prompt)})
    return [np.concatenate([p, np.stack(dec[sl])]) if len(dec[sl]) else p
            for sl, p in zip(slots, pre)], sv


def sequences(seed=0, lens=(150, 77, 40)):
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


@pytest.fixture(scope="module")
def served_bf16():
    return mellum.init_params(tiny_spec(), jax.random.key(7))


@pytest.fixture(scope="module")
def served_f32(served_bf16):
    return jax.tree.map(lambda a: a.astype(jnp.float32), served_bf16)


# the 150-token row decodes from 37: past the window's edge at 32 at once,
# across fourteen page boundaries and four whole windows, in 29 chunks of
# 4 steps; the 77-token row starts INSIDE the window (20) and crosses it;
# the third never leaves it until its last rows
PROMPTS = (37, 20, 5)


@pytest.fixture(scope="module")
def float32_run(served_f32):
    """Three rows of unequal length and a pad row prefilled at a padded
    bucket, then decoded through both pools (the fourth slot a dead row of
    every step): once, for the tests that hold it against the reference and
    against each control."""
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
    seqs, got, _ = float32_run
    with jax.default_matmul_precision("highest"):
        worst, _ = max_diff(got, CFG, served_f32, seqs, control=control)
    assert worst > 10 * F32_TOL, (control, worst)


def test_window_pool_stays_at_its_bound_while_the_full_pool_grows(
        float32_run):
    """The allocator's point: whatever the context, a slot holds no more
    window pages than ``ceil(window / page) + 2``; the full layers' pages
    grow with it; and what the sliding layers' kernel-side count read a row
    stays under the window's pages + the side window."""
    _seqs, _got, sv = float32_run
    bound = WINDOW // PAGE + 2
    assert sv.kv.window_pages_per_slot == bound
    long_row = [(pos, w, f) for s, pos, w, f in sv.held if s == 0]
    assert max(w for _p, w, _f in long_row) == bound
    # at 1x, 2x and 4x the window the window pages are the same few, the
    # full pages as many as the context needs
    for pos, w, f in long_row:
        assert w <= bound and f == -(-(pos + 4) // PAGE), (pos, w, f)
        if pos >= WINDOW:
            assert w >= WINDOW // PAGE
    assert long_row[-1][2] >= 4 * WINDOW // PAGE
    stats = sv.kv.get_stats()
    assert stats["window_pages_released"] > 10
    assert stats["peak_window_pages_used"] <= 3 * bound
    assert stats["window_num_pages"] == 4 * bound


def test_served_bfloat16_is_close_and_a_bfloat16_cache_or_router_is_not_float32(
        served_bf16, served_f32):
    seqs = sequences(3, lens=(90, 50))
    got, _ = served_logits(tiny_spec(), served_bf16, seqs, (40, 12))
    with jax.default_matmul_precision("highest"):
        worst, scale = max_diff(got, CFG, served_f32, seqs)
        assert F32_TOL < worst < BF16_TOL * scale, (worst, scale)
        # float32 everything, the K|V pages of both pools in bfloat16
        got, _ = served_logits(tiny_spec(dtype="float32"), served_f32, seqs,
                               (40, 12), cache_dtype="bfloat16")
        worst, _ = max_diff(got, CFG, served_f32, seqs)
        assert worst > 4 * F32_TOL, worst


def test_a_bfloat16_router_fails_the_float32_tolerance(served_f32,
                                                       monkeypatch):
    seqs = sequences(4, lens=(150,))
    real = moe_routed.route

    def bf16_route(spec, x, w, bias=None):
        return real(spec, x.astype(jnp.bfloat16).astype(jnp.float32),
                    w.astype(jnp.bfloat16), bias)

    monkeypatch.setattr(moe_routed, "route", bf16_route)
    with jax.default_matmul_precision("highest"):
        got, _ = served_logits(tiny_spec(dtype="float32"), served_f32, seqs,
                               (30,))
        worst, _ = max_diff(got, CFG, served_f32, seqs)
    assert worst > 4 * F32_TOL, worst


@pytest.mark.parametrize("n_prompt", [1, WINDOW - 1, WINDOW, WINDOW + 1,
                                      2 * WINDOW + 3])
def test_prompt_lengths_around_the_window(served_f32, n_prompt):
    """The off-by-one of "the window counts the token itself": no history at
    position 0, a prompt shorter than the window, of exactly the window and
    of one more, each then decoded across the edge."""
    seq = sequences(5, lens=(n_prompt + 9,))
    with jax.default_matmul_precision("highest"):
        got, sv = served_logits(tiny_spec(dtype="float32"), served_f32, seq,
                                (n_prompt,), bucket=96)
        worst, _ = max_diff(got, CFG, served_f32, seq)
        wrong = min(max_diff(got, CFG, served_f32, seq, control=c)[0]
                    for c in ("window_minus_1", "window_plus_1"))
    assert worst < F32_TOL, worst
    if n_prompt + 9 > WINDOW + 1:
        assert wrong > 20 * F32_TOL, wrong
    # what the prefill kept of the sliding layers' rows: the pages of the
    # last window - 1 rows and no page before them
    kept = range(max(n_prompt - WINDOW + 1, 0) // PAGE,
                 -(-n_prompt // PAGE))
    assert sv.held[0][2] >= len(kept) - 1


def test_a_released_page_is_reused_by_another_slot_and_no_logit_moves(
        served_f32):
    """Row A decodes far past its window, so pages it held go back; row B,
    admitted after, takes them (the pool has exactly two slots' bound).
    Both rows' logits are the reference's, and A's are what A alone
    gives."""
    a, b = sequences(6, lens=(120, 70))
    spec = tiny_spec(dtype="float32")
    with jax.default_matmul_precision("highest"):
        sv = Served(spec, served_f32, slots=2, pages=64)
        (sa,), pre_a = sv.prefill([a[:40]], 64)
        first_pages = set(sv.kv._slot_wpages[sa].values())
        dec_a, pos = sv.decode({sa: a[40:90]}, {sa: 40})
        released = first_pages - set(sv.kv._slot_wpages[sa].values())
        assert released
        (sb,), pre_b = sv.prefill([b[:50]], 64)
        assert released & set(sv.kv._slot_wpages[sb].values())
        dec, _ = sv.decode({sa: a[90:], sb: b[50:]}, {sa: pos[sa], sb: 50})
        got = [np.concatenate([pre_a[0], np.stack(dec_a[sa] + dec[sa])]),
               np.concatenate([pre_b[0], np.stack(dec[sb])])]
        worst, _ = max_diff(got, CFG, served_f32, [a, b])
        alone, _ = served_logits(spec, served_f32, [a], (40,))
    assert worst < F32_TOL, worst
    assert float(np.abs(alone[0] - got[0]).max()) < F32_TOL


def test_window_pool_bound_at_1x_4x_16x_the_window():
    """The allocator alone: a slot at contexts of 1, 4 and 16 windows holds
    the same few window pages, and admission reckons the two kinds apart."""
    spec = mellum.mellum_spec("mellum-tiny", max_seq_len=1024)
    kv = PagedKVCache(spec, max_slots=2, page_size=PAGE, num_pages=100,
                      max_seq_len=1024)
    bound = WINDOW // PAGE + 2
    held = {}
    for mult in (1, 4, 16):
        slot = kv.alloc_slot(mult * WINDOW)
        cur = mult * WINDOW
        for _ in range(6):              # six decode chunks of a page each
            kv.release_behind_window(slot, cur)
            kv.ensure_capacity(slot, cur + PAGE)
            assert kv.window_pages_held(slot) <= bound
            cur += PAGE
        held[mult] = (kv.window_pages_held(slot), len(kv._slot_pages[slot]))
        kv.free_slot(slot)
    assert held[1][0] == held[4][0] == held[16][0] == bound - 1
    assert held[16][1] > 4 * held[1][1] // 2 and held[16][1] >= 16 * 4
    assert len(kv._wfree) == kv.num_window_pages == 2 * bound
    # the full pool dry, the window pool not: no slot
    assert kv.alloc_slot(96 * PAGE) is not None
    assert kv.alloc_slot(5 * PAGE) is None and len(kv._wfree) > bound


# ------------------------------------------------------------- the kernel


def _kernel_case(seed=0, first=(13, 0, 40, 0), prefix=(37, 20, 48, 0)):
    """4 rows over ONE pool of K|V rows, 2 K/V heads of 64, pages of 8: a
    first live row inside a page, 0 (everything), exactly on a page's edge,
    and a dead row."""
    rng = np.random.default_rng(seed)
    b, h, hkv, dh, page, mp, n = 4, 8, 2, 64, 8, 8, 40
    lanes = hkv * dh
    pool = jnp.asarray(rng.standard_normal((2 * n, page, 2 * lanes)),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(n)[:b * mp].reshape(b, mp), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, h, dh)), jnp.float32)
    side = jnp.asarray(rng.standard_normal((b, 4, 2 * lanes)), jnp.float32)
    sk = side[..., :lanes].reshape(b, 4, hkv, dh)
    sv = side[..., lanes:].reshape(b, 4, hkv, dh)
    return dict(q=q, pool=pool, table=table,
                prefix=jnp.asarray(prefix, jnp.int32), sk=sk, sv=sv,
                n_side=jnp.asarray([2, 1, 4, 0], jnp.int32),
                first=jnp.asarray(first, jnp.int32), n=n, lanes=lanes)


def _kernel(c, first, layer=1):
    return flash_decode.flash_decode_attention_pallas(
        c["q"], c["pool"], c["pool"], c["table"], c["prefix"], c["sk"],
        c["sv"], c["n_side"], n_kv_heads=2, interpret=True, layer=layer,
        n_pages_per_layer=c["n"], kv_fused=True, count_pages=True,
        first_rows=first, pages_per_block=2)


def _xla(c, first, layer=1):
    own = c["pool"][layer * c["n"]:(layer + 1) * c["n"]]
    return flash_decode.flash_decode_attention_xla(
        c["q"], own[..., :c["lanes"]], own[..., c["lanes"]:], c["table"],
        c["prefix"], c["sk"], c["sv"], c["n_side"], n_kv_heads=2,
        first_rows=first)


def test_kernel_with_a_first_live_row_is_the_xla_form_and_copies_no_page_before_it():
    c = _kernel_case()
    with jax.default_matmul_precision("highest"):
        out, copied = _kernel(c, c["first"])
        ref = _xla(c, c["first"])
        everything = _xla(c, None)
    live = np.asarray(c["prefix"]) > 0
    assert float(jnp.abs(out - ref)[live].max()) < 2e-6
    assert float(jnp.abs(ref - everything)[0].max()) > 1e-3   # the bound bites
    # pages that hold a row in [first, prefix): row 0 pages 1..4, row 1
    # pages 0..2, row 2 page 5; the interpreter counts a later row's first
    # block again on its own turn (``_prefix_loop``)
    want = sum(-(-p // 8) - f // 8
               for p, f in zip((37, 20, 48), (13, 0, 40)))
    assert want <= int(copied) <= want + 2 * 2


def test_kernel_and_xla_form_with_first_row_0_are_bit_equal_to_none():
    """Olmo's full layers pass nothing: the same bits as a first row of 0
    handed in, through the kernel and through the XLA form."""
    c = _kernel_case(1)
    zeros = jnp.zeros((4,), jnp.int32)
    with jax.default_matmul_precision("highest"):
        a, na = _kernel(c, None)
        b, nb = _kernel(c, zeros)
        assert np.array_equal(np.asarray(a), np.asarray(b)) and na == nb
        assert np.array_equal(np.asarray(_xla(c, None)),
                              np.asarray(_xla(c, zeros)))


def test_interpreted_kernel_body_gives_the_xla_bodys_logits(served_f32):
    seqs = sequences(8, lens=(73, 30))
    spec = tiny_spec(dtype="float32")
    with jax.default_matmul_precision("highest"):
        x, sx = served_logits(spec, served_f32, seqs, (37, 9))
        k, sk = served_logits(spec, served_f32, seqs, (37, 9),
                              attn_impl="pallas-decode_interpret")
    for a, b in zip(x, k):
        assert float(np.abs(a - b).max()) < F32_TOL
    # what the kernel says it read a sliding layer: never more than the
    # window's pages + the side window a row a step
    steps = 73 - 37
    per_row_step = WINDOW // PAGE + 1
    assert sk.rows_read[1] <= (steps * 2 * (per_row_step + 1) * PAGE
                               + (steps + 3) * 4 * 4)
    assert sk.rows_read[1] < sx.rows_read[1] <= sk.rows_read[0] * 4


# ------------------------------------------------------------- the router


def test_prefill_through_the_interpreted_kernel_is_the_xla_path(monkeypatch,
                                                               served_f32):
    """``mellum-tiny``'s prefill with the flash kernel forced through the
    interpreter (blocks of 16 in a bucket of 160, the window 32: a query
    block's first key block is masked whole for its last rows; rows of 150,
    77 and 40 tokens and a pad row) against the XLA band body this process
    resolves by itself, at the family's float32 limit; and against the
    reference."""
    seqs = sequences()
    with jax.default_matmul_precision("highest"):
        spec = tiny_spec(dtype="float32")
        _, xla = Served(spec, served_f32).prefill(seqs, 160)
        monkeypatch.setattr(flash_prefill, "Q_BLOCK", 16)
        monkeypatch.setattr(flash_prefill, "K_BLOCK", 16)
        monkeypatch.setattr(flash_prefill, "prefill_impl",
                            lambda t, dh: "flash_interpret")
        real, windows = flash_prefill._flash_prefill, []
        monkeypatch.setattr(
            flash_prefill, "_flash_prefill",
            lambda *a, **kw: windows.append(kw["window"]) or real(*a, **kw))
        _, got = Served(spec, served_f32).prefill(seqs, 160)
        worst, scale = max_diff(got, CFG, served_f32, seqs)
    assert sorted(windows) == [0, WINDOW, WINDOW, WINDOW]   # one a layer
    assert max(float(np.abs(a - b).max()) for a, b in zip(got, xla)) < F32_TOL
    assert worst < F32_TOL and scale > 0.3, (worst, scale)


def _route_inputs(seed, n, d, e):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((n, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((d, e)) * d ** -0.5, jnp.float32),
            jnp.asarray(rng.standard_normal((e,)) * 0.01, jnp.float32))


def test_softmax_route_is_a_hand_written_top_k():
    spec = tiny_spec()
    x, w, _ = _route_inputs(0, 33, 64, 8)
    idx, g = moe_routed.route(spec, x, w)
    logits = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.argsort(-p, axis=-1)[:, :2]
    assert np.array_equal(np.asarray(idx), want)
    top = np.take_along_axis(p, want, -1)
    np.testing.assert_allclose(np.asarray(g), top / top.sum(-1, keepdims=True),
                               rtol=2e-6)
    np.testing.assert_allclose(np.asarray(g).sum(-1), 1.0, rtol=1e-6)


def _parent_route(spec, x, w_router, bias):
    """``ops/moe_routed.py`` ``route`` as the parent commit (PR 38) had it,
    word for word: sigmoid + bias + groups."""
    from jax import lax

    e, ng, k = spec.n_experts, spec.n_group, spec.experts_per_token
    s = jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    c = s + bias.astype(jnp.float32)
    top2, _ = lax.top_k(c.reshape(-1, ng, e // ng), 2)
    _, groups = lax.top_k(top2.sum(-1), spec.topk_group)
    keep = jnp.zeros((c.shape[0], ng), bool).at[
        jnp.arange(c.shape[0])[:, None], groups].set(True)
    c = jnp.where(jnp.repeat(keep, e // ng, axis=1), c, -jnp.inf)
    _, idx = lax.top_k(c, k)
    g = jnp.take_along_axis(s, idx, axis=1)
    g = g / jnp.sum(g, axis=-1, keepdims=True) * spec.routed_scaling_factor
    return idx.astype(jnp.int32), g


@pytest.mark.parametrize("family", ["ling", "xing"])
def test_sigmoid_route_is_bit_equal_to_the_parents(family):
    from distributed_inference_engine_tpu.models import ling, xing

    spec = (ling.ling_spec("ling-tiny") if family == "ling"
            else xing.xing_spec("xing-tiny"))
    assert spec.moe_scoring == "sigmoid"
    x, w, bias = _route_inputs(2, 57, spec.d_model, spec.n_experts)
    for fn in (lambda f: f, jax.jit):
        idx, g = fn(lambda *a: moe_routed.route(spec, *a))(x, w, bias)
        idx0, g0 = fn(lambda *a: _parent_route(spec, *a))(x, w, bias)
        assert np.array_equal(np.asarray(idx), np.asarray(idx0))
        assert np.array_equal(np.asarray(g), np.asarray(g0))


def test_moe_block_adds_a_shared_expert_only_where_the_spec_has_one(
        served_bf16):
    spec = tiny_spec()
    assert spec.shared_d_ff == 0
    blk = jax.tree.map(lambda a: a[0], served_bf16["period"][0])
    assert "ws_gate_up" not in blk and "router_bias" not in blk
    x = jnp.asarray(np.random.default_rng(0).standard_normal((16, 64)),
                    jnp.bfloat16)
    out, counters = moe_routed.moe_block(spec, blk, x,
                                         jnp.ones((16,), bool), "xla")
    assert out.shape == x.shape and np.asarray(counters).tolist()[:2] == [
        32, 32]


# ------------------------------------------------------------ load errors


def test_the_family_is_found_from_the_spec_and_mixed_kinds_are_refused():
    spec = tiny_spec()
    assert layered_family(spec) is mellum
    assert (spec.paged_layers, spec.window_layers, spec.state_layers) == (
        2, 6, 0)
    base = spec.to_dict()
    for kinds in (("swa", "swa", "swa", "mla") * 2,
                  ("swa", "gdn", "swa", "full") * 2,
                  ("kda", "swa", "swa", "full") * 2):
        with pytest.raises(ValueError, match="no family runs that mix"):
            ModelSpec(**dict(base, layer_kinds=kinds)).validate()
    with pytest.raises(ValueError, match="whole periods of sliding-window"):
        ModelSpec(**dict(base, sliding_window=0)).validate()
    with pytest.raises(ValueError, match="whole periods of sliding-window"):
        ModelSpec(**dict(base, layer_kinds=("swa",) * 8)).validate()
    with pytest.raises(ValueError, match="moe_scoring"):
        ModelSpec(**dict(base, moe_scoring="tanh")).validate()


def test_published_size_is_the_configs():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "mellum2-12b-a2.5b-pp1.json")) as f:
        cfg = json.load(f)
    spec = mellum.mellum_spec(cfg["serve"]["size"])
    for key, field in REF.SPEC_PAIRS:
        assert cfg[key] == getattr(spec, field), (key, field)
    assert spec.layer_kinds == tuple(
        {"sliding_attention": "swa", "full_attention": "full"}[t]
        for t in cfg["layer_types"][:cfg["num_hidden_layers"]])
    yarn = dict(spec.rope_scaling)
    for key, val in cfg["rope_parameters"]["full_attention"].items():
        if key != "rope_theta":
            assert yarn[key] == val, key
    assert spec.rope_theta == cfg["rope_parameters"]["sliding_attention"][
        "rope_theta"]
    assert mellum.window_pages_per_slot(spec, 128) == 10
    assert mellum.window_read_pages(spec, 128) == 9
