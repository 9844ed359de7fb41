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

    def __init__(self, spec, params, slots=4, pages=128, cache_dtype=None,
                 impl="xla"):
        self.spec, self.params, self.impl = spec, params, impl
        self.kv = PagedKVCache(spec, max_slots=slots, page_size=PAGE,
                               num_pages=pages, max_seq_len=256)
        if cache_dtype:
            self.kv.state = {k: v.astype(cache_dtype)
                             for k, v in self.kv.state.items()}
        self.counters = np.zeros(len(keye.DECODE_COUNTERS), np.int64)
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
            keye.decode_context(kp, table, self.impl), *a))
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


@pytest.fixture(scope="module")
def float32_kernel_run(served_f32):
    """``float32_run`` on the kernel body: the three rows, the pad row and
    the dead slot through the TPU's three kernels (interpreted)."""
    seqs = sequences()
    with jax.default_matmul_precision("highest"):
        got, sv = served_logits(tiny_spec(dtype="float32"), served_f32, seqs,
                                PROMPTS, impl=KERNELS)
    return seqs, got, sv


def test_the_kernel_body_is_the_xla_body_and_the_reference(
        served_f32, float32_run, float32_kernel_run):
    """The masked read of the live pages sums its softmax in another order
    than the gathered rows': inside the float32 bound of this file, against
    the XLA body and against the reference."""
    seqs, got, _ = float32_kernel_run
    for a, b in zip(got, float32_run[1]):
        assert float(np.abs(a - b).max()) < F32_TOL
    with jax.default_matmul_precision("highest"):
        worst, scale = max_diff(got, CFG, served_f32, seqs)
    assert worst < F32_TOL and scale > 0.3, (worst, scale)


def test_the_kernel_bodys_counters_hold_what_it_read(float32_run,
                                                     float32_kernel_run):
    """On the kernel body the indexer and the attention read a row's LIVE
    pages whole and the side window, no table-wide array: what the program
    counted is the host's reckoning from the lengths; the selection is the
    XLA body's to the row."""
    seqs, _got, sv = float32_kernel_run
    names = dict(zip(keye.DECODE_COUNTERS, sv.counters))
    xla = dict(zip(keye.DECODE_COUNTERS, float32_run[2].counters))
    assert names["attn.rows_selected"] == xla["attn.rows_selected"]
    # chunks of 4 steps: a live row's pages as the chunk began, a step
    read = 0
    for s, n in zip(seqs, PROMPTS):
        for start in range(n, len(s), 4):
            steps = min(4, len(s) - start)
            read += steps * -(-start // PAGE) * PAGE
    steps = (150 - 37 + 3) // 4 * 4
    read += steps * 4 * 4                # the side window, every slot's
    assert names["attn.kv_rows_read"] == read
    assert names["attn.index_table_rows"] == read
    assert names["attn.rows_selected"] <= names["attn.kv_rows_read"]
    # the XLA body read every slot's whole table, and gathered the top-k
    assert xla["attn.index_table_rows"] == steps * 4 * (256 + 4)
    assert xla["attn.kv_rows_read"] == steps * 4 * (TOPK + 4)
    assert sv.sums == float32_run[2].sums


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
def test_prompt_lengths_around_the_topk_through_the_kernels(served_f32,
                                                            n_prompt):
    """``test_prompt_lengths_around_the_topk`` on the kernel body: a
    16-step chunk that begins below the top-k and ends above it."""
    seq = sequences(5, lens=(n_prompt + 20,))
    with jax.default_matmul_precision("highest"):
        got, _ = served_logits(tiny_spec(dtype="float32"), served_f32, seq,
                               (n_prompt,), bucket=128, n_steps=16,
                               impl=KERNELS)
        worst, _ = max_diff(got, CFG, served_f32, seq)
    assert worst < F32_TOL, worst


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


# the decode bodies: "xla" (index keys gathered through the table,
# ``lax.top_k``, the picked rows gathered) and the TPU's three kernels
# through the interpreter
KERNELS = "pallas-decode_interpret"
BODIES = pytest.mark.parametrize("impl", ["xla", KERNELS])


@BODIES
def test_a_selection_takes_side_rows_and_cached_rows_together(served_f32,
                                                              impl):
    """A 16-step chunk far above the top-k: by its last steps the chunk's
    own rows (the side window) are most of what a top-16 may pick, and
    cached rows the rest; the logits are the reference's, and would not be
    were the side rows left out of the selection or always in it."""
    seq = sequences(8, lens=(60,))
    spec = tiny_spec(dtype="float32")
    with jax.default_matmul_precision("highest"):
        got, _ = served_logits(spec, served_f32, seq, (44,), n_steps=16,
                               impl=impl)
        worst, _ = max_diff(got, CFG, served_f32, seq)
    assert worst < F32_TOL, worst


@BODIES
def test_eight_rows_of_unlike_lengths_equal_each_served_alone(served_f32,
                                                              impl):
    lens = (150, 33, 90, 17, 61, 120, 48, 75)
    prompts = (100, 9, 40, 3, 30, 64, 16, 50)
    seqs = sequences(11, lens=lens)
    spec = tiny_spec(dtype="float32")
    with jax.default_matmul_precision("highest"):
        together, _ = served_logits(spec, served_f32, seqs, prompts,
                                    bucket=128, slots=8, pages=256,
                                    n_steps=8, impl=impl)
        worst, _ = max_diff(together, CFG, served_f32, seqs)
        assert worst < F32_TOL, worst
        for i in (0, 3, 6):
            alone, _ = served_logits(spec, served_f32, [seqs[i]],
                                     (prompts[i],), bucket=128, n_steps=8,
                                     impl=impl)
            assert float(np.abs(alone[0] - together[i]).max()) < F32_TOL


@BODIES
def test_a_freed_slots_stale_index_keys_are_not_selectable(served_f32, impl):
    """Row A fills pages with index keys and is freed; row B, shorter, takes
    the same pages: A's stale keys lie past B's length in B's pages and in
    the table's padding, and no logit of B moves."""
    a, b = sequences(6, lens=(120, 70))
    spec = tiny_spec(dtype="float32")
    with jax.default_matmul_precision("highest"):
        sv = Served(spec, served_f32, slots=1, pages=16, impl=impl)
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


# ----------------------------------------- the decode step's three kernels


def _decode_layout(mp):
    return sparse_index.decode_layout(mp, PAGE)


def _decode_scores(rng, b, mp):
    """Scores as ``index_scores_decode`` lays them: [B, s_pad]."""
    _g, s_side, s_pad, _tile = _decode_layout(mp)
    return rng.standard_normal((b, s_pad)).astype(np.float32), s_side


def _decode_oracle(scores, lens, n_side, s_side, k):
    cols = np.arange(scores.shape[1])[None, :]
    visible = np.where(cols < s_side, cols < np.asarray(lens)[:, None],
                       cols - s_side < np.asarray(n_side)[:, None])
    return np.asarray(sparse_index.select_mask(
        jnp.asarray(scores), jnp.asarray(visible), k), np.int32)


def _d_plateau(s, s_side):
    s[:, 5:60] = 0.25


def _d_all_equal(s, s_side):
    s[...] = -1.0


def _d_side_wins(s, s_side):
    s[:, s_side:s_side + 4] = 9.0        # the chunk's own rows score highest


def _d_side_ties(s, s_side):
    s[...] = 0.5                         # equal: the cached rows go first


def _d_stale(s, s_side):
    # a freed slot's keys past a row's length, and the table's padding,
    # score above everything (and NaN): never visible
    s[0, 40:] = np.inf
    s[1, 90:s_side] = np.nan
    s[:, s_side + 2:] = np.inf


def _d_relu_zeros(s, s_side):
    s[...] = np.where(s > 0.4, s, 0.0)
    s[2, ::2] = -0.0


# scores' edit, cached rows valid a row, side rows valid a row
DECODE_SELECT_CASES = {
    "below_the_topk": (None, (5, 0, 9, 3), (1, 0, 2, 4)),
    "at_the_topk": (None, (8, 12, 11, 10), (4, 0, 1, 2)),
    "far_above": (None, (256, 200, 131, 77), (4, 1, 2, 3)),
    "plateau": (_d_plateau, (256, 100, 64, 30), (1, 1, 1, 1)),
    "all_equal": (_d_all_equal, (256, 255, 17, 12), (4, 4, 4, 4)),
    "a_dead_row": (None, (200, 0, 90, 0), (3, 0, 1, 0)),
    "side_rows_win": (_d_side_wins, (256, 100, 40, 20), (4, 4, 4, 4)),
    "side_rows_tie": (_d_side_ties, (256, 100, 11, 20), (4, 4, 4, 4)),
    "stale_keys": (_d_stale, (40, 90, 128, 33), (2, 2, 1, 2)),
    "relu_zeros": (_d_relu_zeros, (250, 129, 128, 127), (4, 3, 2, 1)),
}


@pytest.mark.parametrize("case", sorted(DECODE_SELECT_CASES))
def test_the_decode_threshold_kernel_is_select_mask_bit_for_bit(case):
    """``_select_decode_kernel`` through the interpreter: one query a row,
    four rows on the sublanes, a table of 32 pages of 8 and a side window of
    4, a top-k of 12; the mask is ``select_mask``'s to the bit, zeros past
    every length and in the padding."""
    edit, lens, n_side = DECODE_SELECT_CASES[case]
    scores, s_side = _decode_scores(np.random.default_rng(4), 4, 32)
    if edit:
        edit(scores, s_side)
    got = np.asarray(sparse_index.select_mask_decode(
        jnp.asarray(scores), jnp.asarray(lens, jnp.int32),
        jnp.asarray(n_side, jnp.int32), topk=12, mp=32, page_size=PAGE,
        interpret=True))
    want = _decode_oracle(scores, lens, n_side, s_side, 12)
    assert got.dtype == np.int32 and got.shape == scores.shape
    assert (got == want).all(), np.argwhere(got != want)[:5]
    assert [int(x) for x in want.sum(-1)] == [
        min(12, a + c) for a, c in zip(lens, n_side)]
    if case == "side_rows_win":
        assert want[:, s_side:s_side + 4].all()
    if case == "side_rows_tie":
        # equal scores go to the lower position: the cached rows first
        assert not want[[0, 1, 3], s_side:].any()
        assert want[2, :11].all() and want[2, s_side] and not want[
            2, s_side + 1:].any()


def _index_case(dtype, layers=3, n=40, b=4, mp=32, wc=4, hi=2, di=32):
    rng = np.random.default_rng(8)
    pool = jnp.asarray(rng.standard_normal((layers * n, PAGE, di)), dtype)
    table = jnp.asarray(rng.permutation(n)[:b * mp].reshape(b, -1)
                        if n >= b * mp else rng.integers(0, n, (b, mp)),
                        jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, hi, di)), dtype)
    w = jnp.asarray(rng.standard_normal((b, hi)), jnp.float32)
    side = jnp.asarray(rng.standard_normal((b, wc, di)), dtype)
    return pool, table, q, w, side


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 0.0)])
def test_the_decode_index_kernel_is_index_scores_through_the_table(dtype,
                                                                   tol):
    """``index_scores_decode`` (the kernel through the interpreter, the
    middle layer of a stacked pool, a page's keys handed in transposed)
    against ``index_scores`` over that layer's pages gathered through the
    table: equal on every LIVE position of the table and of the side window
    (in bfloat16 the products are exact and two heads sum in one order: to
    the bit), ``-inf`` everywhere else, a dead row all ``-inf``."""
    pool, table, q, w, side = _index_case(dtype)
    n, layer = 40, 1
    lens = jnp.asarray([37, 0, 256, 8], jnp.int32)
    n_side = jnp.asarray([1, 0, 4, 2], jnp.int32)
    _g, s_side, s_pad, _tile = _decode_layout(32)
    got = np.asarray(sparse_index.index_scores_decode(
        q, w, pool.swapaxes(1, 2), table, lens, side, n_side, layer,
        interpret=True, n_pages_per_layer=n))
    assert got.shape == (4, s_pad)
    with jax.default_matmul_precision("highest"):
        cached = pool[layer * n + table].reshape(4, -1, pool.shape[-1])
        want = np.asarray(sparse_index.index_scores(
            q[:, None], jnp.concatenate([cached, side], 1), w[:, None])[:, 0])
    for r in range(4):
        a, c = int(lens[r]), int(n_side[r])
        np.testing.assert_allclose(got[r, :a], want[r, :a], rtol=tol,
                                   atol=tol)
        np.testing.assert_allclose(got[r, s_side:s_side + c],
                                   want[r, 256:256 + c], rtol=tol, atol=tol)
        assert np.isneginf(got[r, a:s_side]).all()
        assert np.isneginf(got[r, s_side + c:]).all()


def test_the_masked_read_is_the_softmax_over_the_selected_rows():
    """``sparse_decode_attention`` (the ``kv_fused`` loop under a mask,
    interpreted) against the masked softmax over the same rows gathered
    through the table: four rows of a stacked pool's last layer, one dead,
    one whose mask keeps a single side row."""
    rng = np.random.default_rng(2)
    b, mp, wc, h, hkv, dh, n, layers = 4, 32, 4, 8, 2, 64, 140, 2
    _g, s_side, s_pad, _tile = _decode_layout(mp)
    pool = jnp.asarray(rng.standard_normal((layers * n, PAGE, 2 * hkv * dh)),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(n)[:b * mp].reshape(b, mp),
                        jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, h, dh)), jnp.float32)
    side = jnp.asarray(rng.standard_normal((b, wc, 2 * hkv * dh)),
                       jnp.float32)
    lens = np.asarray([256, 0, 77, 9], np.int32)
    n_side = np.asarray([4, 0, 2, 1], np.int32)
    keep = np.zeros((b, s_pad), np.int32)
    for r in range(b):
        keep[r, :lens[r]] = rng.random(lens[r]) < 0.3
        keep[r, s_side:s_side + n_side[r]] = 1
    keep[3, :] = 0
    keep[3, s_side] = 1                  # one side row, no cached row
    # garbage where nothing is visible must not be read as kept
    keep[0, 256:s_side] = 1
    keep[2, 77:256] = 1
    keep[2, s_side + 2:s_side + 4] = 1
    lanes = hkv * dh
    split = lambda a: (a[..., :lanes].reshape(*a.shape[:-1], hkv, dh),
                       a[..., lanes:].reshape(*a.shape[:-1], hkv, dh))
    side_k, side_v = split(side)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(sparse_index.sparse_decode_attention(
            q, pool, table, jnp.asarray(lens), side_k, side_v,
            jnp.asarray(n_side), jnp.asarray(keep), 1, n_kv_heads=hkv,
            interpret=True, n_pages_per_layer=n))
        rows = jnp.concatenate(
            [pool[n + table].reshape(b, mp * PAGE, -1), side], 1)
        k, v = split(rows)
        cols = np.arange(mp * PAGE + wc)[None, :]
        visible = np.where(cols < mp * PAGE, cols < lens[:, None],
                           cols - mp * PAGE < n_side[:, None])
        kept = np.concatenate([keep[:, :mp * PAGE],
                               keep[:, s_side:s_side + wc]], 1) != 0
        s = jnp.einsum("bkgd,bskd->bkgs", q.reshape(b, hkv, h // hkv, dh), k
                       ) * dh ** -0.5
        p = sparse_index.masked_softmax(
            s, jnp.asarray(kept & visible)[:, None, None])
        want = np.asarray(jnp.einsum("bkgs,bskd->bkgd", p, v)).reshape(
            b, h, dh)
    assert not got[1].any()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("lens", [
    (33000, 2048, 0, 11000, 2049, 20000, 128, 5000),
    (2048, 2047, 2049, 4096, 0, 0, 33792, 33776),
    (16, 100, 1000, 0, 0, 0, 0, 0)])
def test_the_compiled_decode_kernels_are_their_oracles(lens):
    """The three Mosaic kernels at the served shape (8 rows of 264 pages of
    128, 6 layers' pools, a side window of 16; the top-2,048), on a TPU only
    (``python -m pytest --noconftest tests/test_keye.py -m slow -k
    compiled`` there): the scores against ``index_scores`` through the
    table, the mask against ``select_mask`` to the bit, the masked read
    against the masked softmax over the gathered rows."""
    if jax.default_backend() != "tpu":
        pytest.skip("the compiled kernels need a TPU")
    rng = np.random.default_rng(sum(lens))
    b, mp, page, wc, n, layers, layer = 8, 264, 128, 16, 2112, 2, 1
    hi, di, h, hkv, dh, k = 16, 64, 32, 4, 128, 2048
    _g, s_side, s_pad, _tile = sparse_index.decode_layout(mp, page)
    bf = jnp.bfloat16
    index_pool = jnp.asarray(rng.standard_normal((layers * n, page, di)), bf)
    pool = jnp.asarray(rng.standard_normal((layers * n, page, 2 * hkv * dh)),
                       bf)
    table = jnp.asarray(rng.permutation(n).reshape(b, mp), jnp.int32)
    q_idx = jnp.asarray(rng.standard_normal((b, hi, di)), bf)
    w = jnp.asarray(rng.standard_normal((b, hi)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, h, dh)), bf)
    side = jnp.asarray(rng.standard_normal((b, wc, 2 * hkv * dh + di)), bf)
    lens = jnp.asarray(lens, jnp.int32)
    n_side = jnp.where(lens > 0, jnp.asarray(rng.integers(1, wc + 1, b)), 0
                       ).astype(jnp.int32)
    scores = sparse_index.index_scores_decode(
        q_idx, w, index_pool.swapaxes(1, 2), table, lens,
        side[..., 2 * hkv * dh:], n_side, layer, n_pages_per_layer=n)
    cached = index_pool[layer * n + table].reshape(b, mp * page, di)
    want = sparse_index.index_scores(
        q_idx[:, None], jnp.concatenate([cached, side[..., 2 * hkv * dh:]],
                                        1), w[:, None])[:, 0]
    got = np.asarray(scores)
    for r in range(b):
        a, c = int(lens[r]), int(n_side[r])
        np.testing.assert_allclose(got[r, :a], np.asarray(want[r, :a]),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(
            got[r, s_side:s_side + c],
            np.asarray(want[r, mp * page:mp * page + c]), rtol=1e-5,
            atol=1e-4)
        assert np.isneginf(got[r, a:s_side]).all()
        assert np.isneginf(got[r, s_side + c:]).all()
    keep = np.asarray(sparse_index.select_mask_decode(
        scores, lens, n_side, topk=k, mp=mp, page_size=page))
    oracle = _decode_oracle(got, np.asarray(lens), np.asarray(n_side),
                            s_side, k)
    assert (keep == oracle).all(), int((keep != oracle).sum())
    assert [int(x) for x in keep.sum(-1)] == [
        min(k, int(a) + int(c)) for a, c in zip(lens, n_side)]
    lanes = hkv * dh
    split = lambda a: (a[..., :lanes].reshape(*a.shape[:-1], hkv, dh),
                       a[..., lanes:2 * lanes].reshape(*a.shape[:-1], hkv,
                                                       dh))
    side_k, side_v = split(side)
    out = np.asarray(sparse_index.sparse_decode_attention(
        q, pool, table, lens, side_k, side_v, n_side, jnp.asarray(keep),
        layer, n_kv_heads=hkv, n_pages_per_layer=n), np.float32)
    rows = jnp.concatenate(
        [pool[layer * n + table].reshape(b, mp * page, -1),
         side[..., :2 * lanes]], 1)
    kk, vv = split(rows)
    kept = np.concatenate([keep[:, :mp * page],
                           keep[:, s_side:s_side + wc]], 1) != 0
    s = jnp.einsum("bkgd,bskd->bkgs", q.reshape(b, hkv, h // hkv, dh), kk,
                   preferred_element_type=jnp.float32) * dh ** -0.5
    p = sparse_index.masked_softmax(s, jnp.asarray(kept)[:, None, None])
    ref = np.asarray(jnp.einsum("bkgs,bskd->bkgd", p.astype(bf), vv),
                     np.float32).reshape(b, h, dh)
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)
    assert not out[np.asarray(lens) == 0].any()


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
