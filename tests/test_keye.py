"""The learned-sparse-attention family (``models/keye.py``: grouped-query
attention over the rows an indexer scored highest, index keys in a second
paged cache on the K|V pages' table, softmax-routed experts in every layer)
against the plain float32 reference the benchmark judges it by
(``perfbench/reference/dsa_moe.py``), at the tiny size (a top-k of 16 rows,
pages of 8), on the CPU.

Tolerances. With the served tree widened to float32 and matmuls at highest
precision the two implementations differ by rounding order alone (index
scores over gathered pages and a side window against one score matrix;
``lax.top_k`` or a counted threshold against ``lax.top_k``; a softmax over
gathered rows against one masked softmax; grouped experts against every
expert weighted by its gate): logits of magnitude ~1 agree to 5e-5 (seen:
2e-6). Every control, the same served logits against the reference with ONE
named term wrong, moves them by hundreds of times the bound (seen: no
selection 0.5, a halved top-k 0.4, the others 0.2-0.5: at a top-k of 16 of
up to 150 rows nearly every query's set changes). Served in bfloat16 the
band is wide AT THIS SIZE and stated in two parts: index scores computed
from bfloat16 activations of width 64 swap rows at the selection's edge,
and at a top-k of 16 a swapped row is a sixteenth of the softmax's rows: a
different attention output, not a rounding. The median position reads
0.01-0.11 of max|logit| and the worst 0.41-0.57 (the REFERENCE computed in
bfloat16 reads the same: median 0.01-0.07, worst 0.52-0.69): 0.2 bounds the
median and 0.9 the worst, and a position's argmax is not asked. At the
published widths a swapped row is one of 2,048 (PERF.md section 6, PR 45,
has the chip's readings). The reference computed in bfloat16 fails the
float32 bound, and so does a cache of bfloat16 index keys under float32
everything else.
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
from distributed_inference_engine_tpu.models import keye  # noqa: E402
from distributed_inference_engine_tpu.models.base import (  # noqa: E402
    LAYERED_FAMILY,
    decode_sums,
    layered_family,
    prefill_sums,
    unembed,
)
from distributed_inference_engine_tpu.ops import sparse_index  # noqa: E402
from perfbench.lib import families  # noqa: E402

F32_TOL = 5e-5
BF16_MEDIAN, BF16_WORST = 0.2, 0.9      # of max|logit|, see above
TOPK, PAGE = 16, 8

with open(os.path.join(ROOT, "perfbench", "rehearse",
                       "keye-tiny.json")) as _f:
    CFG = json.load(_f)
REF = families.reference(CFG)


def tiny_spec(**kw):
    return keye.keye_spec("keye-tiny", max_seq_len=256, **kw)


class Served:
    """The serving programs driven by hand through ``PagedKVCache``: prefill
    at a padded bucket, then teacher-forced decode chunks through both
    pools, collecting every position's logits."""

    def __init__(self, spec, params, slots=4, pages=128, cache_dtype=None):
        self.spec, self.params = spec, params
        self.kv = PagedKVCache(spec, max_slots=slots, page_size=PAGE,
                               num_pages=pages, max_seq_len=256)
        if cache_dtype:
            self.kv.state = {k: v.astype(cache_dtype)
                             for k, v in self.kv.state.items()}
        self.counters = np.zeros(5, np.int64)
        self.sums = {}

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
        hidden, kp, st, _moe = jax.jit(
            lambda *a: keye.forward_prefill_into_pages(
                self.spec, self.params, *a))(
            jnp.asarray(toks), jnp.asarray(lens), *self.kv.pools,
            jnp.asarray(table), jnp.asarray(ids))
        self.kv.swap(kp, st)
        logits = unembed(self.spec, self.params, hidden)
        return slots, [np.asarray(logits[i, :len(p)])
                       for i, p in enumerate(prompts)]

    def decode(self, feeds, lengths, n_steps=4):
        """``feeds[slot]`` = the tokens to feed next; returns per slot the
        logits after each fed token."""
        b = self.kv.max_slots
        out = {s: [] for s in feeds}
        step = jax.jit(lambda kp, table, tok, cur, start, *a:
                       keye.forward_decode_step(
            self.spec, self.params, tok, cur, start,
            keye.decode_context(kp, table, "xla"), *a))
        pos = dict(lengths)
        fed = {s: 0 for s in feeds}
        while any(fed[s] < len(feeds[s]) for s in feeds):
            for s in feeds:
                if fed[s] < len(feeds[s]):
                    self.kv.ensure_capacity(s, pos[s] + n_steps)
            start = np.zeros((b,), np.int32)
            for s in feeds:
                start[s] = pos[s]
            side = jnp.zeros(
                (self.spec.n_layers, b, n_steps,
                 self.spec.cache_row_width + self.spec.index_head_dim),
                self.kv.dtype)
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
                self.counters += np.asarray(c)
                logits = np.asarray(unembed(self.spec, self.params, hidden))
                for s in feeds:
                    if act[s]:
                        out[s].append(logits[s])
                        fed[s] += 1
                        cur[s] += 1
            for name, v in decode_sums(self.spec, cur - start, cur).items():
                self.sums[name] = self.sums.get(name, 0) + v
            kp, state = keye.write_side(
                kp, state, side, self.kv.page_table,
                jnp.asarray(cur - start), jnp.asarray(start))
            self.kv.swap(kp, state)
            pos = {s: int(cur[s]) for s in feeds}
        return out, pos


def served_logits(spec, params, seqs, n_prompt, bucket=64, n_steps=4, **kw):
    """Full-position logits of each sequence: its first ``n_prompt[i]``
    tokens prefilled together at a padded bucket, the rest decoded."""
    sv = Served(spec, params, **kw)
    prompts = [s[:n] for s, n in zip(seqs, n_prompt)]
    slots, pre = sv.prefill(prompts, bucket)
    dec, _ = sv.decode({sl: s[n:] for sl, s, n in zip(slots, seqs, n_prompt)},
                       {sl: n for sl, n in zip(slots, n_prompt)},
                       n_steps=n_steps)
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
    return keye.init_params(tiny_spec(), jax.random.key(7))


@pytest.fixture(scope="module")
def served_f32(served_bf16):
    return jax.tree.map(lambda a: a.astype(jnp.float32), served_bf16)


# the 150-token row's prompt (37) is far above the top-k of 16: its prefill
# selects, and it decodes across fourteen page boundaries in 29 chunks of 4
# steps; the 77-token row starts just above the top-k (20); the third's
# prompt (5) lies below it and its decode CROSSES it (a chunk begins at 13
# rows and ends at 17: rows 14-16 select everything, row 17 drops one)
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


def test_a_bfloat16_reference_fails_the_float32_bound(served_f32,
                                                      float32_run):
    seqs, got, _ = float32_run
    worst, _ = max_diff(got, CFG, served_f32, seqs, dtype=jnp.bfloat16)
    assert worst > 10 * F32_TOL, worst


def test_counters_hold_the_selection(float32_run):
    """What the program counted of the rows it selected is what the host
    reckons (min(context, top-k) a token a layer), the indexer read the
    whole table and the side window a step, and scored the context."""
    seqs, _got, sv = float32_run
    names = dict(zip(keye.DECODE_COUNTERS, sv.counters))
    selected = sum(min(p + 1, TOPK) for s, n in zip(seqs, PROMPTS)
                   for p in range(n, len(s)))
    context = sum(p + 1 for s, n in zip(seqs, PROMPTS)
                  for p in range(n, len(s)))
    assert names["attn.rows_selected"] == selected
    assert sv.sums["attn.full_context_rows"] == context
    assert sv.sums["attn.index_rows_scored"] == context
    steps = (150 - 37 + 3) // 4 * 4
    assert names["attn.index_table_rows"] == steps * 4 * (256 + 4)
    assert prefill_sums(tiny_spec(), 37, 64)["attn.index_prefill_pairs"] \
        == 37 * 38 // 2


def test_served_bfloat16_is_close_and_bfloat16_index_keys_are_not_float32(
        served_bf16, served_f32):
    seqs = sequences(3, lens=(90, 50))
    got, _ = served_logits(tiny_spec(), served_bf16, seqs, (40, 12))
    with jax.default_matmul_precision("highest"):
        for lg, seq in zip(got, seqs):
            ref = np.asarray(REF.logits(CFG, served_f32, jnp.asarray(seq)))
            gap = np.abs(lg - ref).max(-1) / np.abs(ref).max()
            assert F32_TOL < np.median(gap) < BF16_MEDIAN, np.median(gap)
            assert gap.max() < BF16_WORST, gap.max()
        # float32 everything, the index keys' pool alone in bfloat16
        got, _ = served_logits(tiny_spec(dtype="float32"), served_f32, seqs,
                               (40, 12), cache_dtype="bfloat16")
        worst, _ = max_diff(got, CFG, served_f32, seqs)
        assert worst > 4 * F32_TOL, worst


@pytest.mark.parametrize("n_prompt", [1, TOPK - 1, TOPK, TOPK + 1,
                                      6 * TOPK + 3])
def test_prompt_lengths_around_the_topk(served_f32, n_prompt):
    """Contexts below, at and far above the top-k: no history at position
    0, a prompt one short of the top-k, of exactly the top-k and of one
    more, each then decoded across the edge (16-step chunks: a chunk that
    begins below the top-k ends above it); while the context is no longer
    than the top-k the dense control IS the model."""
    seq = sequences(5, lens=(n_prompt + 20,))
    with jax.default_matmul_precision("highest"):
        got, _ = served_logits(tiny_spec(dtype="float32"), served_f32, seq,
                               (n_prompt,), bucket=128, n_steps=16)
        worst, _ = max_diff(got, CFG, served_f32, seq)
        ref = np.asarray(REF.logits(CFG, served_f32, jnp.asarray(seq[0])))
        dense = np.asarray(REF.logits(CFG, served_f32, jnp.asarray(seq[0]),
                                      control="dense"))
    assert worst < F32_TOL, worst
    # sparse equals dense while the context <= the top-k, and not after
    assert float(np.abs(got[0][:TOPK] - dense[:TOPK]).max()) < F32_TOL
    assert float(np.abs(ref[:TOPK] - dense[:TOPK]).max()) < F32_TOL
    assert float(np.abs(got[0][TOPK:] - dense[TOPK:]).max()) > 20 * F32_TOL


def test_a_selection_takes_side_rows_and_cached_rows_together(served_f32):
    """A 16-step chunk far above the top-k: by its last steps the chunk's
    own rows (the side window) are most of what a top-16 may pick, and
    cached rows the rest; the logits are the reference's, and would not be
    were the side rows left out of the selection or always in it."""
    seq = sequences(8, lens=(60,))
    spec = tiny_spec(dtype="float32")
    with jax.default_matmul_precision("highest"):
        got, _ = served_logits(spec, served_f32, seq, (44,), n_steps=16)
        worst, _ = max_diff(got, CFG, served_f32, seq)
    assert worst < F32_TOL, worst


def test_eight_rows_of_unlike_lengths_equal_each_served_alone(served_f32):
    lens = (150, 33, 90, 17, 61, 120, 48, 75)
    prompts = (100, 9, 40, 3, 30, 64, 16, 50)
    seqs = sequences(11, lens=lens)
    spec = tiny_spec(dtype="float32")
    with jax.default_matmul_precision("highest"):
        together, _ = served_logits(spec, served_f32, seqs, prompts,
                                    bucket=128, slots=8, pages=256,
                                    n_steps=8)
        worst, _ = max_diff(together, CFG, served_f32, seqs)
        assert worst < F32_TOL, worst
        for i in (0, 3, 6):
            alone, _ = served_logits(spec, served_f32, [seqs[i]],
                                     (prompts[i],), bucket=128, n_steps=8)
            assert float(np.abs(alone[0] - together[i]).max()) < F32_TOL


def test_a_freed_slots_stale_index_keys_are_not_selectable(served_f32):
    """Row A fills pages with index keys and is freed; row B, shorter, takes
    the same pages: A's stale keys lie past B's length in B's pages and in
    the table's padding, and no logit of B moves."""
    a, b = sequences(6, lens=(120, 70))
    spec = tiny_spec(dtype="float32")
    with jax.default_matmul_precision("highest"):
        sv = Served(spec, served_f32, slots=1, pages=16)
        (sa,), _pre = sv.prefill([a[:100]], 128)
        sv.decode({sa: a[100:]}, {sa: 100})
        held = set(sv.kv._slot_pages[sa])
        sv.kv.free_slot(sa)
        (sb,), pre_b = sv.prefill([b[:30]], 128)
        assert set(sv.kv._slot_pages[sb]) & held
        dec, _ = sv.decode({sb: b[30:]}, {sb: 30})
        got = [np.concatenate([pre_b[0], np.stack(dec[sb])])]
        worst, _ = max_diff(got, CFG, served_f32, [b])
    assert worst < F32_TOL, worst


def test_the_sectioned_rotary_form_with_equal_ids_is_plain_rope():
    """M-RoPE as published against what is computed: on text the three
    position ids are equal and the sectioned table is plain RoPE's."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((40, 3, 64)), jnp.float32)
    ids = np.broadcast_to(np.arange(40)[None, :], (3, 40))
    plain = REF.rotate(x, 1e7)
    sectioned = REF.rotate_sectioned(x, 1e7, [8, 12, 12], ids)
    assert float(jnp.abs(plain - sectioned).max()) == 0.0
    moved = REF.rotate_sectioned(x, 1e7, [8, 12, 12],
                                 ids + 5 * np.arange(3)[:, None])
    assert float(jnp.abs(plain - moved).max()) > 0.1


# ------------------------------------------------------ ops/sparse_index.py


def test_select_mask_is_top_k_with_ties_to_the_lower_position():
    rng = np.random.default_rng(1)
    s = rng.standard_normal((6, 40)).astype(np.float32)
    s[0, 5:30] = 0.25                    # a plateau across the threshold
    s[1, :] = -1.0                       # every score equal
    s[2, 3] = -np.inf
    visible = np.ones((6, 40), bool)
    visible[3, 9:] = False               # fewer visible than k
    visible[4, :] = False                # none
    got = np.asarray(sparse_index.select_mask(jnp.asarray(s),
                                              jnp.asarray(visible), 12))
    for r in range(6):
        masked = np.where(visible[r], s[r], -np.inf)
        vals, idx = jax.lax.top_k(jnp.asarray(masked), 12)
        want = np.zeros(40, bool)
        want[np.asarray(idx)[np.asarray(vals) > -np.inf]] = True
        if r == 2:
            want &= visible[r]
        assert (got[r] == (want & visible[r])).all(), r
    assert got[1].sum() == 12 and got[1][:12].all()
    assert got[3].sum() == 9 and got[4].sum() == 0


def _kernel_and_oracle(scores, lens, first, k, bk, interpret=True):
    """(the selection kernel's int8 mask, ``select_mask``'s under the same
    diagonal and lengths) for the queries from ``first`` on."""
    b, bq, s = scores.shape
    scores = jnp.asarray(scores)
    lens = jnp.asarray(lens, jnp.int32)
    cols = jnp.arange(s)[None, None, :]
    rows = first + jnp.arange(bq)[None, :, None]
    visible = (cols <= rows) & (cols < lens[:, None, None])
    got = sparse_index._select_mask_flash(
        scores, lens, jnp.asarray([first], jnp.int32), topk=k, bk=bk,
        interpret=interpret)
    assert got.dtype == jnp.int8 and got.shape == scores.shape
    return (np.asarray(got),
            np.asarray(sparse_index.select_mask(scores, visible, k), np.int8))


def _plateau(s):
    s[:, :, 5:60] = 0.25                 # equal scores across the threshold


def _all_equal(s):
    s[...] = -1.0


def _inf_and_signed_zeros(s):
    s[0, :, 3] = -np.inf
    s[0, :, 70:90] = -np.inf
    s[1, :, ::2] = -0.0                  # ordered BELOW +0.0, as top_k does
    s[1, :, 1::2] = 0.0


def _relu_zeros(s):
    s[...] = np.where(s > 0.4, s, 0.0)   # the k-th is one of many +0.0


# scores' edit, lengths of the two rows, first query, keys
SELECT_CASES = {
    "plateau": (_plateau, (128, 100), 64, 128),
    "all_equal": (_all_equal, (128, 128), 64, 128),
    "inf_and_signed_zeros": (_inf_and_signed_zeros, (128, 128), 64, 128),
    "relu_zeros": (_relu_zeros, (128, 90), 64, 128),
    "fewer_visible_than_k": (None, (7, 3), 64, 128),
    "none_visible": (None, (0, 0), 0, 128),
    "length_inside_a_tile": (None, (85, 41), 32, 128),
    "length_on_a_tiles_edge": (None, (96, 48), 32, 128),
    "first_chunk": (None, (128, 50), 0, 128),
    "a_later_chunk": (_plateau, (128, 120), 64, 128),
    "five_key_tiles": (_relu_zeros, (80, 77), 16, 80),
    "a_tile_of_lanes": (_plateau, (256, 130), 192, 256),
}


@pytest.mark.parametrize("case", sorted(SELECT_CASES))
def test_the_selection_kernel_is_select_mask_bit_for_bit(case):
    """``_select_mask_kernel`` through the interpreter: two rows, 64 queries
    (two tiles of 32 a row) against key tiles of 16 (of 128 lanes in the
    last case), a top-k of 12."""
    edit, lens, first, keys = SELECT_CASES[case]
    scores = np.random.default_rng(3).standard_normal(
        (2, 64, keys)).astype(np.float32)
    if edit:
        edit(scores)
    got, want = _kernel_and_oracle(scores, lens, first, 12,
                                   128 if keys == 256 else 16)
    assert (got == want).all(), np.argwhere(got != want)[:5]
    # the rows' own sums say the case is what its name says
    assert want.sum(-1).max() == min(12, max(min(first + 64, n)
                                             for n in lens))


@pytest.mark.slow
@pytest.mark.parametrize("keys,first,lens", [
    (32768, 0, 32768), (32768, 32256, 32700), (32768, 16384, 20000),
    (33792, 33280, 33792), (33792, 30720, 31000)])
def test_the_compiled_selection_kernel_is_select_mask(keys, first, lens):
    """The Mosaic kernel at the served shapes (a block of 512 queries
    against 32,768 keys; the 33,792 bucket's last chunk), on a TPU only
    (``python -m pytest --noconftest tests/test_keye.py -m slow -k
    compiled`` there: conftest.py pins the CPU): scores with ReLU's zeros
    and a plateau, the top-2,048."""
    if jax.default_backend() != "tpu":
        pytest.skip("the compiled kernel needs a TPU")
    from distributed_inference_engine_tpu.ops import flash_prefill

    scores = np.random.default_rng(keys + first).standard_normal(
        (1, flash_prefill.Q_BLOCK, keys)).astype(np.float32)
    scores[:, 100:200] = np.where(scores[:, 100:200] > 1.5,
                                  scores[:, 100:200], 0.0)
    scores[:, 300:400, 1000:9000] = 0.25
    got, want = _kernel_and_oracle(scores, (lens,), first, 2048,
                                   flash_prefill.K_BLOCK, interpret=False)
    assert (got == want).all(), int((got != want).sum())
    assert want.sum() > 0


def test_select_blocks_count_the_kernels_engagement(monkeypatch):
    """``attn.prefill_select_blocks``: a bucket's query blocks x layers
    where the selection kernel runs (a bucket above the top-k on the kernel
    body), 0 on the XLA body and at a bucket that selects every row."""
    from distributed_inference_engine_tpu.ops import flash_prefill

    spec = tiny_spec()
    assert prefill_sums(spec, 37, 64)["attn.prefill_select_blocks"] == 0
    monkeypatch.setattr(flash_prefill, "prefill_impl",
                        lambda t, dh: "flash_interpret")
    monkeypatch.setattr(flash_prefill, "Q_BLOCK", 16)
    assert prefill_sums(spec, 37, 64)["attn.prefill_select_blocks"] \
        == 64 // 16 * spec.n_layers
    assert prefill_sums(spec, 9, TOPK)["attn.prefill_select_blocks"] == 0


def test_family_module_is_whole_and_the_spec_tells_it():
    spec = tiny_spec()
    fam = layered_family(spec)
    assert fam is keye
    assert all(hasattr(fam, name) for name in LAYERED_FAMILY)
    assert spec.paged_layers == 4 and spec.window_layers == 0
    assert spec.cache_row_width == 256 and spec.kv_row_lanes == 128
    with pytest.raises(ValueError, match="index_topk"):
        keye.keye_spec("keye-tiny", layer_mlps=("dense",) * 4)
    kv = PagedKVCache(spec, max_slots=2, page_size=PAGE, num_pages=16)
    assert kv.state["index_pages"].shape == (4, 16, PAGE, 32)
    assert kv.get_stats()["index_bytes_per_token"] == 4 * 32 * 2


def test_the_prefill_kernel_is_the_xla_body(served_f32, monkeypatch):
    """The TPU's prefill (the selection as an int8 mask a chunk of queries,
    the masked flash kernel a chunk) through the interpreter, at blocks of
    16 and chunks of 32: a bucket of 128 is four chunks of two query blocks
    each, the prompts end inside a block, on a block's edge and in the
    first chunk; the logits are the reference's. A bucket of 80 blocks has
    no chunk of 32 (the served 33,792 has none of 4,096): its chunk is the
    largest run of whole blocks that divides it."""
    from distributed_inference_engine_tpu.ops import flash_prefill

    monkeypatch.setattr(flash_prefill, "prefill_impl",
                        lambda t, dh: "flash_interpret")
    monkeypatch.setattr(flash_prefill, "Q_BLOCK", 16)
    monkeypatch.setattr(flash_prefill, "K_BLOCK", 16)
    monkeypatch.setattr(sparse_index, "Q_CHUNK", 32)
    seqs = sequences(12, lens=(125, 96, 23))
    with jax.default_matmul_precision("highest"):
        got, _ = served_logits(tiny_spec(dtype="float32"), served_f32, seqs,
                               (121, 96, 20), bucket=128)
        worst, _ = max_diff(got, CFG, served_f32, seqs)
        assert worst < F32_TOL, worst
        got, _ = served_logits(tiny_spec(dtype="float32"), served_f32,
                               seqs[1:], (77, 20), bucket=80)
        worst, _ = max_diff(got, CFG, served_f32, seqs[1:])
    assert worst < F32_TOL, worst
