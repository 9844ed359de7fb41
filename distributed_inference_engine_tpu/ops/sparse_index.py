"""Learned sparse attention (a DeepSeek-Sparse-Attention style indexer,
``models/keye.py``): index scores, the exact top-k of a query's context, and
the attention over the rows it selected.

A query t scores every position s <= t it may see,

    I[t, s] = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s])

(``index_scores``: float32 from the activations' dtype; the XLA body is one
batched product, the TPU prefill's a tile kernel, and the two may differ in
a sum's last bits), and its attention reads the ``topk`` positions of
largest I, equal scores going to the lower position as ``lax.top_k`` does;
while the context is no longer than ``topk`` that is every position.

- *Decode*, one query a row (``models/keye.py`` ``attn_layer_step`` calls
  either form). On a TPU THREE kernels that read pages where they lie, a
  row of scores and a row of mask a sequence between them
  (``decode_layout``): ``index_scores_decode`` (``_index_decode_kernel``: a
  row's LIVE index-key pages through its table, a page's keys transposed,
  which is how a TPU holds rows of 64 lanes), ``select_mask_decode``
  (``_select_decode_kernel``: the rows on the sublanes, every row's scores
  held in VMEM once, ``_counted_threshold`` as the prefill's, cached and
  side rows competing in position order, an int32 mask out) and
  ``sparse_decode_attention`` (``_sparse_decode_kernel``:
  ``ops/flash_decode.py``'s ``kv_fused`` loop over the live K|V pages, then
  the side window, every key under the mask; a page that holds no selected
  row is still read: at the served contexts every page holds some). The
  XLA form (``"xla"``; the CPU): the scores of a row's cached index keys,
  gathered through the whole table, and of the chunk's own side by side in
  position order, ``decode_select`` = ``lax.top_k`` (0.44 ms for 8 rows of
  33,808 on one v5e chip, PR 45) and the family gathers the K|V rows
  picked.
- *Prefill* (``prefill_attention``): masked-dense, in blocks of queries. The
  k-th largest score of a query is found by bisection over the float's bits
  (``kth_key``: 32 counting passes over the block's scores, exact, where
  ``lax.top_k`` sorts for 17.8 ms a block of 512 queries of 32,768 keys),
  the selection is the mask ``select_mask`` and the attention the blocked
  softmax under it. On a TPU, at a ``T`` of whole blocks and heads of whole
  128-lane tiles (``flash_prefill.prefill_impl``, the K|V-row families'
  rule), a block of ``Q_BLOCK`` queries goes through THREE kernels, HBM
  between them: ``_index_score_kernel`` (a tile's heads summed in VMEM, the
  block's float32 scores written once), ``_select_mask_kernel`` (a tile of
  32 queries' whole score rows held in VMEM: the ordered keys made once,
  the 32 counting passes and the tie rule there (``_counted_threshold``,
  shared with the decode step's kernel), up to the tile's last
  visible key, the scores read once and the int8 mask written once: 0.28
  ms a block of 512 queries over a 32,768 prompt's 64 blocks in a served
  trace on one v5e chip, 0.60 ms the last block alone, where the passes as
  XLA ops, each over the block in HBM, took 2.06 and 2.39, PR 46) and, a
  chunk of ``Q_CHUNK`` queries at a time, ONE blocked flash kernel
  (``_masked_flash_kernel``: ``ops/flash_prefill.py``'s grid, online softmax
  and layout, with the selection handed in as an int8 mask tile a (query
  block, key block) pair, which also carries the diagonal and the row's
  length: the kernel compares no position). A chunk's mask is ``[Q_CHUNK,
  T]`` int8 (128 MB at 32,768); the chunks run one after another under ONE
  ``lax.scan`` (every chunk the same shapes and the same kernels, told its
  first query, writing its query blocks of the result where they lie:
  unrolled, each chunk's temporaries were given memory of their own, 3.3
  GiB at 32,768). Elsewhere (the CPU, the tiny specs) ``select_mask`` and
  the einsum / softmax body: the kernels' oracle.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_decode, flash_prefill
from .attention import NEG_INF
from .mla import _VMEM_LIMIT, LANES, _dot_nt, _lanes

# queries a kernel call of the prefill: its selection mask is [Q_CHUNK, T]
# int8 in HBM
Q_CHUNK = 4096
# the decode step's index-score kernel: pages a double-buffered block of its
# copies, pages a product (a page of index keys is 16 KB); on one v5e chip
# at the cell's shape 8 / 16 / 32 pages a block read 62 / 60 / 61 us a layer,
# 2 / 4 / 8 pages a product 83 / 60 / 50 (PERF.md section 6, PR 47)
INDEX_PAGES_PER_BLOCK = 16
INDEX_PAGES_PER_GROUP = 4
# the masked read's pages a double-buffered block (a K|V page is 256 KB; 4 /
# 8 / 16 read 311 / 310 / 309 us); a group of the index kernel's pages is
# one softmax update (one page an update: 382 us)
SPARSE_PAGES_PER_BLOCK = 4

__all__ = ["index_scores", "kth_key", "select_mask", "decode_select",
           "masked_softmax", "prefill_attention", "query_block",
           "decode_layout", "index_scores_decode", "select_mask_decode",
           "sparse_decode_attention"]


def index_scores(q_idx, k_idx, w):
    """q_idx [..., Tq, Hi, Di], k_idx [..., S, Di], w [..., Tq, Hi] float32
    -> I [..., Tq, S] float32: ONE product of all the heads against the
    keys, ReLU, the weighted sum over the heads. ReLU is written as a
    select, so a head that is not positive adds +0.0 whatever the sign of
    its weight: a negative weight times ReLU's 0 is -0.0, which a top-k may
    order BELOW +0.0, and with few heads such ties are many (every score of
    a query whose weights are all negative is <= 0, and its top-k is the
    zeros of lowest position: only if they are equal)."""
    s = jnp.einsum("...thd,...sd->...ths", q_idx, k_idx,
                   preferred_element_type=jnp.float32)
    return jnp.where(s > 0.0, w[..., None].astype(jnp.float32) * s,
                     0.0).sum(-2)


def _ordered_signed(x):
    """float32 -> int32 whose signed order is the floats' order."""
    i = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


def _ordered(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    i = _ordered_signed(x.astype(jnp.float32))
    return lax.bitcast_convert_type(i, jnp.uint32) ^ jnp.uint32(0x80000000)


def kth_key(keys, k: int):
    """keys uint32 [..., S] -> the k-th largest of each row (the smallest
    where the row has fewer than k): its bits from the top down, a counting
    pass a bit."""
    def body(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        count = (keys >= cand[..., None]).sum(-1, dtype=jnp.int32)
        return jnp.where(count >= k, cand, prefix)

    return lax.fori_loop(0, 32, body,
                         jnp.zeros(keys.shape[:-1], jnp.uint32))


def select_mask(scores, visible, k: int):
    """scores float32 [..., S], visible bool [..., S] -> bool [..., S]: the
    min(k, visible) visible positions of largest score, equal scores to the
    lower position."""
    keys = jnp.where(visible, _ordered(scores), jnp.uint32(0))
    kth = kth_key(keys, k)[..., None]
    above = keys > kth
    ties = (keys == kth) & visible
    room = k - above.sum(-1, dtype=jnp.int32, keepdims=True)
    return (above | (ties & (jnp.cumsum(ties, -1, dtype=jnp.int32) <= room))
            ) & visible


def decode_select(scores, k: int):
    """scores float32 [B, S], ``-inf`` where a position is not visible ->
    (positions int32 [B, k'], valid bool [B, k']), k' = min(k, S): the
    visible positions of largest score, equal scores to the lower one."""
    vals, idx = lax.top_k(scores, min(k, scores.shape[-1]))
    return idx, vals > -jnp.inf


def query_block(t: int, n_heads: int, limit_bytes: int = 1 << 28) -> int:
    """Queries a block of the XLA prefill body: the largest power of two
    that divides ``t``, is at most 512 and keeps the block's float32
    attention scores ``[heads, block, t]`` under ``limit_bytes``."""
    bq = 1
    while (bq < 512 and t % (2 * bq) == 0
           and n_heads * 2 * bq * t * 4 <= limit_bytes):
        bq *= 2
    return bq


def _block_selection(q_idx, k_idx, w, seq_lens, topk: int, first,
                     score_impl: str = "xla"):
    """The selection of ONE block of queries (``q_idx`` [B, bq, Hi, Di], the
    queries from ``first`` on) over the keys ``k_idx`` [B, S, Di]: [B, bq,
    S], the diagonal and the row's length in it; bool from the XLA body,
    int8 from the kernels (``score_impl`` "flash" / "flash_interpret")."""
    b, bq, hi, di = q_idx.shape
    if score_impl == "xla":
        cols = jnp.arange(k_idx.shape[1])[None, None, :]
        qi = first + jnp.arange(bq)[None, :, None]
        visible = (cols <= qi) & (cols < seq_lens[:, None, None])
        with jax.named_scope("attn.index"):
            scores = index_scores(q_idx, k_idx, w)
        with jax.named_scope("attn.select"):
            return select_mask(scores, visible, topk)
    interpret = score_impl == "flash_interpret"
    with jax.named_scope("attn.index"):
        scores = _index_scores_flash(
            q_idx.reshape(b, bq, hi * di), k_idx, w.astype(jnp.float32),
            bk=flash_prefill.K_BLOCK, interpret=interpret)
    with jax.named_scope("attn.select"):
        return _select_mask_flash(
            scores, seq_lens.astype(jnp.int32),
            jnp.asarray(first, jnp.int32)[None], topk=topk,
            bk=flash_prefill.K_BLOCK, interpret=interpret)


def masked_softmax(s, keep):
    """softmax of ``s`` over its last axis among the entries ``keep`` (bool,
    broadcast against ``s``); a row that keeps nothing is all zeros."""
    s = jnp.where(keep, s, NEG_INF)
    p = jnp.where(keep, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    den = p.sum(-1, keepdims=True)
    return p / jnp.where(den == 0.0, 1.0, den)


def _selection(q_idx, k_idx, w, seq_lens, topk: int, q0, bq: int,
               score_impl: str):
    """The selection of the queries ``[q0, q0 + Tq)`` (``q_idx`` [B, Tq, Hi,
    Di], ``w`` [B, Tq, Hi]) over the keys ``k_idx`` [B, S, Di], a block of
    ``bq`` queries at a time through the two kernels (``score_impl`` "flash"
    / "flash_interpret"): int8 [B, Tq, S] (1 = selected), the diagonal and
    the row's length in it."""
    b, tq = q_idx.shape[:2]
    s = k_idx.shape[1]

    def block(i0):
        take = lambda a: lax.dynamic_slice_in_dim(a, i0, bq, axis=1)
        return _block_selection(take(q_idx), k_idx, take(w), seq_lens, topk,
                                q0 + i0, score_impl)

    keep = lax.map(block, jnp.arange(0, tq, bq))       # [nb, B, bq, S]
    return jnp.moveaxis(keep, 0, 1).reshape(b, tq, s)


def _index_score_kernel(q_ref, k_ref, w_ref, o_ref, *, heads: int, di: int):
    """One (query block, key block) tile of ``index_scores``: the heads one
    after another, the sum in VMEM, ONE write of the tile (XLA's form holds
    every head's [512, keys] float32 product before it sums them)."""
    k = k_ref[0]
    acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
    for j in range(heads):
        s = _dot_nt(q_ref[0, :, j * di:(j + 1) * di], k)       # [bq, bk]
        acc = acc + jnp.where(s > 0.0, w_ref[0, :, j:j + 1] * s, 0.0)
    o_ref[0] = acc


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def _index_scores_flash(q_idx, k_idx, w, *, bk: int, interpret: bool):
    """q_idx [B, bq, Hi * Di], k_idx [B, S, Di], w float32 [B, bq, Hi] ->
    index scores float32 [B, bq, S]."""
    b, bq, width = q_idx.shape
    s, di = k_idx.shape[1], k_idx.shape[2]
    heads = width // di
    return pl.pallas_call(
        functools.partial(_index_score_kernel, heads=heads, di=di),
        grid=(b, s // bk),
        in_specs=[pl.BlockSpec((1, bq, width), lambda r, j: (r, 0, 0)),
                  pl.BlockSpec((1, bk, di), lambda r, j: (r, j, 0)),
                  pl.BlockSpec((1, bq, heads), lambda r, j: (r, 0, 0))],
        out_specs=pl.BlockSpec((1, bq, bk), lambda r, j: (r, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, bq, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        # the name says the work of a call, which has no other trace in a
        # profile: rows x queries x keys, every pair scored
        name=f"index_scores_flash_b{b}q{bq}k{s}",
    )(q_idx, k_idx, w)


_NO_KEY = -2 ** 31      # the signed form of ``_ordered``'s 0: not visible


def _counted_threshold(keys_of, fold, write, *, tq: int, bk: int, s: int,
                       topk: int, seen):
    """``select_mask`` over ordered keys held in VMEM (``keys_of(j)`` [tq,
    bk]: tile j's ``_ordered_signed`` keys, ``_NO_KEY`` where a position is
    hidden; ``fold(fn, init)`` folds ``fn(j, acc)`` over the tiles that may
    hold a visible key): ``kth_key``'s 32 counting passes, then the ties: of
    the keys equal to the k-th the lowest positions fill what room is left,
    their bound found by the same bisection over a position's bits (only
    where a row has more ties than room). ``write(j, keep)`` takes each
    folded tile's selection, bool [tq, bk]. ``seen``: the most positions a
    row sees; at no more than ``topk`` every row keeps them all, no pass."""
    lanes = min(bk, LANES)
    lane = lax.broadcasted_iota(jnp.int32, (tq, lanes), 1)

    def cols(j):
        return j * bk + lax.broadcasted_iota(jnp.int32, (tq, bk), 1)

    def count(hit):
        """hit(keys [tq, lanes], their first column) -> bool; int32 [tq, 1]
        of the hits over the folded tiles."""
        def tile(j, acc):
            keys = keys_of(j)
            for l in range(0, bk, lanes):
                acc = acc + hit(keys[:, l:l + lanes],
                                j * bk + l).astype(jnp.int32)
            return acc

        return fold(tile, jnp.zeros((tq, lanes), jnp.int32)).sum(
            -1, keepdims=True)

    def wide(x):                               # [tq, 1] -> [tq, lanes]
        return jnp.broadcast_to(x, (tq, lanes))

    def key_bit(i, prefix):
        cand = prefix | (jnp.int32(1) << (31 - i))
        least = wide(cand ^ _NO_KEY)
        return jnp.where(count(lambda keys, _: keys >= least) >= topk,
                         cand, prefix)

    # rows that see no more than topk positions keep them all: no pass, the
    # k-th is _NO_KEY
    kth = lax.fori_loop(0, jnp.where(seen > topk, 32, 0), key_bit,
                        jnp.zeros((tq, 1), jnp.int32)) ^ _NO_KEY
    kth_w = wide(kth)
    room = topk - count(lambda keys, _: keys > kth_w)
    ties = count(lambda keys, _: keys == kth_w)
    # fewer visible than topk: the k-th is _NO_KEY, which is no visible
    # position's key, and nothing ties
    none = kth == _NO_KEY
    more = (ties > room) & ~none               # more ties than room

    def position_bit(i, bound):
        cand = bound | (jnp.int32(1) << (s.bit_length() - 1 - i))
        cand_w = wide(cand)
        below = count(lambda keys, c0: (keys == kth_w) & (c0 + lane < cand_w))
        return jnp.where(below <= room, cand, bound)

    # the largest bound with no more than ``room`` ties below it
    bound = lax.fori_loop(
        0, jnp.where(more.astype(jnp.int32).max() > 0, s.bit_length(), 0),
        position_bit, jnp.zeros((tq, 1), jnp.int32))
    bound = jnp.where(none, 0, jnp.where(more, bound, s))

    def select(j, _):
        keys = keys_of(j)
        write(j, (keys > kth) | ((keys == kth) & (cols(j) < bound)))

    fold(select, None)


def _select_mask_kernel(lens_ref, first_ref, s_ref, o_ref, keys_ref, *,
                        topk: int, tq: int, bk: int):
    """``select_mask`` of ``tq`` queries' whole rows of scores, held in VMEM:
    the ordered keys written once (``_ordered_signed``, ``_NO_KEY``
    where the diagonal or the length hides a position), then
    ``_counted_threshold`` over them. Every loop over key tiles ends at the
    tile's last visible position; the mask past it is zeros."""
    s = s_ref.shape[-1]
    n = lens_ref[pl.program_id(0)]
    q_first = first_ref[0] + pl.program_id(1) * tq
    seen = jnp.minimum(q_first + tq, n)        # positions the last query sees
    live = jnp.minimum((seen - 1 + bk) // bk, s // bk)
    rows = q_first + lax.broadcasted_iota(jnp.int32, (tq, bk), 0)

    def at(j):
        return pl.ds(pl.multiple_of(j * bk, bk), bk)

    def order(j, _):
        c = j * bk + lax.broadcasted_iota(jnp.int32, (tq, bk), 1)
        keys_ref[:, at(j)] = jnp.where(
            (c <= rows) & (c < n), _ordered_signed(s_ref[0, :, at(j)]),
            _NO_KEY)

    lax.fori_loop(0, live, order, None)

    def dead(j, _):
        o_ref[0, :, at(j)] = jnp.zeros((tq, bk), jnp.int8)

    lax.fori_loop(live, s // bk, dead, None)

    def write(j, keep):
        o_ref[0, :, at(j)] = keep.astype(jnp.int8)

    _counted_threshold(
        lambda j: keys_ref[:, at(j)],
        lambda fn, init: lax.fori_loop(0, live, fn, init), write,
        tq=tq, bk=bk, s=s, topk=topk, seen=seen)


@functools.partial(jax.jit, static_argnames=("topk", "bk", "interpret"))
def _select_mask_flash(scores, seq_lens, first, *, topk: int, bk: int,
                       interpret: bool):
    """scores float32 [B, bq, S] of the queries from ``first`` (int32 [1])
    on, seq_lens int32 [B] -> ``select_mask`` under the diagonal and the
    lengths, int8 [B, bq, S]: the scores read once, the mask written once."""
    b, bq, s = scores.shape
    tq = min(bq, 32)                           # int8's sublane tile
    return pl.pallas_call(
        functools.partial(_select_mask_kernel, topk=topk, tq=tq, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, bq // tq),
            in_specs=[pl.BlockSpec((1, tq, s), lambda r, i, *_: (r, i, 0))],
            out_specs=pl.BlockSpec((1, tq, s), lambda r, i, *_: (r, i, 0)),
            scratch_shapes=[pltpu.VMEM((tq, s), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, bq, s), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=f"select_mask_flash_b{b}q{bq}k{s}",
    )(seq_lens, first, scores)


def prefill_attention(q, rows, q_idx, k_idx, w, seq_lens, n_kv_heads: int,
                      topk: int, impl: str = "") -> jnp.ndarray:
    """q [B, T, H, Dh]; rows [B, T, 2 * Hkv * Dh], a token's ``k | v``;
    q_idx [B, T, Hi, Di], k_idx [B, T, Di], w [B, T, Hi]. Query i attends to
    the ``topk`` positions j <= i (below ``seq_lens``) of largest index
    score, all of them while i < topk; scores at ``Dh^-1/2``. Returns
    [B, T, H, Dh]; rows past ``seq_lens`` are not specified. ``impl``: see
    ``flash_prefill.prefill_impl``, which chooses when it is empty."""
    b, t, h, dh = q.shape
    impl = impl or flash_prefill.prefill_impl(t, dh)
    if impl != "xla":
        bq, bk = flash_prefill.Q_BLOCK, flash_prefill.K_BLOCK
        # whole query blocks that divide the bucket (33,792 = 11 x 3,072)
        chunk = max(c for c in range(bq, min(Q_CHUNK, t) + 1, bq)
                    if t % c == 0)
        lens = seq_lens.astype(jnp.int32)

        q_flat = q.reshape(b, t, h * dh)

        def one(out, q0):
            take = lambda a: lax.dynamic_slice_in_dim(a, q0, chunk, axis=1)
            keep = _selection(take(q_idx), k_idx, take(w), seq_lens, topk,
                              q0, bq, score_impl=impl)
            with jax.named_scope("attn.sparse"):
                return _masked_flash_prefill(
                    q_flat, rows, keep, lens, q0[None], out,
                    n_kv_heads=n_kv_heads, bq=bq, bk=bk,
                    heads_per_step=flash_prefill.HEADS_PER_STEP,
                    interpret=impl == "flash_interpret"), None

        out, _ = lax.scan(one, jnp.zeros_like(q_flat),
                          jnp.arange(0, t, chunk, dtype=jnp.int32))
        return out.reshape(b, t, h, dh)
    g = h // n_kv_heads
    lanes = n_kv_heads * dh
    k, v = (rows[..., at:at + lanes].reshape(b, t, n_kv_heads, dh)
            for at in (0, lanes))
    bq = query_block(t, h)

    def block(i0):
        take = lambda a: lax.dynamic_slice_in_dim(a, i0, bq, axis=1)
        keep = _block_selection(take(q_idx), k_idx, take(w), seq_lens, topk,
                                i0)                            # [B, bq, T]
        with jax.named_scope("attn.sparse"):
            qb = take(q).reshape(b, bq, n_kv_heads, g, dh)
            s = jnp.einsum("bikgd,bjkd->bkgij", qb, k,
                           preferred_element_type=jnp.float32) * dh ** -0.5
            p = masked_softmax(s, keep[:, None, None])
            o = jnp.einsum("bkgij,bjkd->bikgd", p.astype(v.dtype), v)
        return o.reshape(b, bq, h, dh)

    out = lax.map(block, jnp.arange(0, t, bq))               # [nb, B, bq, ..]
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, dh)


def _masked_flash_kernel(lens_ref, q0_ref, qi_ref, ki_ref, q_ref, k_ref,
                         v_ref, keep_ref, _o_in, o_ref, m_ref, l_ref,
                         acc_ref, *,
                         scale: float, heads: int, group: int, dh: int,
                         bq: int, bk: int, last: int):
    """``flash_prefill._flash_kernel`` for the queries from ``q0_ref[0]`` on,
    every pair masked by the tile of ``keep`` handed in: the diagonal and
    the length are in the mask. The pair list is every (query block, key
    block) of the chunk against the whole row; a pair past the diagonal or
    the length computes nothing."""
    n = lens_ref[pl.program_id(0)]
    pair = pl.program_id(2)
    q_at, k_at = q0_ref[0] + qi_ref[pair] * bq, ki_ref[pair] * bk

    @pl.when(k_at == 0)                        # the query block's first pair
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((q_at < n) & (k_at < n) & (k_at < q_at + bq))
    def _():
        keep = keep_ref[0].astype(jnp.int32) != 0              # [bq, bk]
        for j in range(heads):
            at = j // group * dh               # the head's K/V head's lanes
            s = _dot_nt(q_ref[0, :, j * dh:(j + 1) * dh],
                        k_ref[0, :, at:at + dh]) * scale
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_ref[j]                                  # [bq, LANES]
            m_next = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
            # a row with no key in this block and none before it: its m is
            # still NEG_INF and exp(s - m) is 1
            p = jnp.where(keep, jnp.exp(s - _lanes(m_next, bk)), 0.0)
            alpha = jnp.exp(m_prev - m_next)
            l_ref[j] = alpha * l_ref[j] + p.sum(axis=-1)[:, None]
            m_ref[j] = m_next
            v = v_ref[0, :, at:at + dh]
            acc_ref[j] = _lanes(alpha, dh) * acc_ref[j] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(ki_ref[pair] == last)             # the query block's last pair
    def _():
        for j in range(heads):
            l = l_ref[j]                       # 0: a row that saw no key
            o = acc_ref[j] / _lanes(jnp.where(l == 0.0, 1.0, l), dh)
            o_ref[0, :, j * dh:(j + 1) * dh] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_kv_heads", "bq", "bk", "heads_per_step", "interpret"))
def _masked_flash_prefill(q, rows, keep, seq_lens, q0, out, *,
                          n_kv_heads: int, bq: int, bk: int,
                          heads_per_step: int, interpret: bool):
    """q [B, T, H * Dh], rows [B, T, 2 * Hkv * Dh], keep int8 [B, Tq, T] the
    selection of the queries ``[q0, q0 + Tq)`` (``q0`` int32 [1], whole
    query blocks), seq_lens int32 [B], out [B, T, H * Dh] -> out with those
    queries' blocks written (the buffer is the argument's: aliased)."""
    b, t, width = q.shape
    tq = keep.shape[1]
    dh = rows.shape[-1] // (2 * n_kv_heads)
    h = width // dh
    g = h // n_kv_heads
    # query heads a step: whole K/V heads' groups, or a part of one group
    hb = max(d for d in range(1, heads_per_step + 1)
             if h % d == 0 and (d % g == 0 or g % d == 0))
    kb = max(hb // g, 1)                       # K/V heads a step
    qi, ki = (np.repeat(np.arange(tq // bq, dtype=np.int32), t // bk),
              np.tile(np.arange(t // bk, dtype=np.int32), tq // bq))

    def needed(row, pair, lens, q0, qi, ki):
        # the last key block this pair's query block reads of this row:
        # past it nothing is computed, and the block held is named again
        diag = (q0[0] + qi[pair] * bq + bq - 1) // bk
        return jnp.minimum(ki[pair], jnp.minimum(
            diag, jnp.maximum(lens[row] - 1, 0) // bk))

    def q_at(row, grp, pair, lens, q0, qi, ki):
        return row, q0[0] // bq + qi[pair], grp

    def k_at(row, grp, pair, lens, q0, qi, ki):
        return (row, needed(row, pair, lens, q0, qi, ki),
                grp * hb // g // kb)

    def v_at(*a):
        row, blk, lane_blk = k_at(*a)
        return row, blk, n_kv_heads // kb + lane_blk

    def keep_at(row, grp, pair, lens, q0, qi, ki):
        return row, qi[pair], needed(row, pair, lens, q0, qi, ki)

    kernel = functools.partial(
        _masked_flash_kernel, scale=dh ** -0.5, heads=hb, group=g, dh=dh,
        bq=bq, bk=bk, last=t // bk - 1)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, h // hb, len(qi)),
            in_specs=[
                pl.BlockSpec((1, bq, hb * dh), q_at),
                pl.BlockSpec((1, bk, kb * dh), k_at),
                pl.BlockSpec((1, bk, kb * dh), v_at),
                pl.BlockSpec((1, bq, bk), keep_at),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, bq, hb * dh), q_at),
            scratch_shapes=[pltpu.VMEM((hb, bq, LANES), jnp.float32),
                            pltpu.VMEM((hb, bq, LANES), jnp.float32),
                            pltpu.VMEM((hb, bq, dh), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, width), q.dtype),
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        # the call's grid: rows x the chunk's queries x the bucket's keys
        # (what of it is live the diagonal and the lengths decide)
        name=f"sparse_prefill_flash_b{b}q{tq}k{t}",
    )(seq_lens, q0, qi, ki, q, rows, rows, keep, out)


# ------------------------------------------------------ the decode step
#
# One query a row. Three kernels, each reading pages where they lie through
# the row's table, and between them only a row of scores and a row of mask
# a sequence (``decode_layout``: the table's positions, then the chunk's
# side window at ``s_side``, padding to ``s_pad``). Their names must not
# read as the prefill's (``perfbench/lib/scopes_dsa.py`` counts
# ``index_scores_flash`` / ``sparse_prefill_flash`` runs as prefill work).


def decode_layout(mp: int, page_size: int):
    """``(pages a product of the index kernel, s_side, s_pad, tile)`` of a
    decode step's rows of scores and of mask over a table of ``mp`` pages:
    the table's positions in whole products, then a tile whose first lanes
    are the side window's, all in whole tiles of ``tile`` lanes."""
    ap = min(INDEX_PAGES_PER_GROUP, mp)
    unit = ap * page_size
    s_side = -(-mp // ap) * unit
    tile = math.gcd(unit, 512)
    return ap, s_side, s_side + tile, tile


def _index_decode_kernel(
    # scalar prefetch: as ``flash_decode._latent_decode_kernel``
    page_table_ref, prefix_lens_ref, next_live_ref, n_side_ref, layer_ref,
    buffer_index_ref, step_ref,
    # inputs
    q_ref,                     # [B, Hi, Di] VMEM, resident
    w_ref,                     # [B, Hi, 1] float32
    side_ref,                  # [B, Di, Wc] the chunk's own keys, transposed
    pages_hbm,                 # [L*N, Di, P] ANY (stays in HBM)
    # outputs
    out_ref,                   # [B, S] float32, resident: row b written here
    # scratch
    page_vmem,                 # [2, Di, bp * P] double-buffered blocks
    sem,
    *,
    page_size: int,
    pages_per_block: int,
    pages_per_group: int,
    n_pages_per_layer: int,
    s_side: int,
):
    """Row ``b``'s index scores over its LIVE pages' keys
    (``flash_decode._prefix_loop``: own double-buffered page copies, the
    next row's first block in flight across grid steps) and the side
    window's, ``-inf`` wherever the row has no key: a group of pages, side
    by side on the lanes, a product against the row's heads, ReLU, the
    weighted sum over the heads, in float32."""
    b = pl.program_id(0)
    q, w = q_ref[b], w_ref[b]                  # [Hi, Di], [Hi, 1]
    precision = flash_decode._precision(q.dtype)

    @pl.when(b == 0)
    def _first():
        # a group's dead pages are scored too, under the length's mask
        page_vmem[...] = jnp.zeros_like(page_vmem)
        out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, jnp.float32)

    def put(keys, first, n, at):
        """Row ``b``'s scores of ``keys`` [Di, S] at positions ``first`` on
        (``-inf`` from ``n`` on) into the block at lane ``at``: a select
        over the rows' sublanes (Mosaic stores no single sublane at a
        dynamic index)."""
        s = jnp.dot(q, keys.astype(q.dtype),
                    preferred_element_type=jnp.float32,
                    precision=precision)                       # [Hi, S]
        s = jnp.where(s > 0.0, w * s, 0.0).sum(axis=0, keepdims=True)
        pos = first + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < n, s, -jnp.inf)
        shape = (out_ref.shape[0], s.shape[1])
        at = pl.ds(at, s.shape[1])
        mine = lax.broadcasted_iota(jnp.int32, shape, 0) == b
        out_ref[:, at] = jnp.where(mine, jnp.broadcast_to(s, shape),
                                   out_ref[:, at])

    n = prefix_lens_ref[b]
    flash_decode._prefix_loop(
        b, page_table_ref, prefix_lens_ref, next_live_ref, layer_ref,
        buffer_index_ref, step_ref, None, pages_hbm, None, page_vmem, None,
        sem, None, None, None,
        bp=pages_per_block, page_size=page_size,
        n_pages_per_layer=n_pages_per_layer, scale=1.0,
        v_lanes=page_vmem.shape[1], attend_pages=pages_per_group,
        on_group=lambda keys, tok: put(
            keys, tok, n, pl.multiple_of(tok, page_size)))

    n_side = n_side_ref[b]

    @pl.when(n_side > 0)
    def _side():
        put(side_ref[b], 0, n_side, s_side)


@functools.partial(jax.jit, static_argnames=("interpret",
                                             "n_pages_per_layer"))
def index_scores_decode(q_idx, w, pages, page_table, prefix_lens, side,
                        n_side, layer=None, *, interpret: bool = False,
                        n_pages_per_layer: int = 0):
    """q_idx [B, Hi, Di], w float32 [B, Hi], pages [N, Di, P] (a page's keys
    TRANSPOSED, the positions on the lanes: what a pool ``[N, P, Di]`` of
    rows narrower than a lane tile IS in a TPU's memory, where XLA keeps the
    longer of a bf16 array's last two axes minor, and the only form in which
    Mosaic copies such a page; or the layers' stacked, ``layer`` folded into
    the page id), page_table [B, MP], prefix_lens [B] the cached keys valid
    a row (0: a dead row, no copy is started), side [B, Wc, Di] the chunk's
    own keys, valid below ``n_side`` -> float32 [B, s_pad]
    (``decode_layout``): ``index_scores`` of a row's query against its
    cached keys at ``[0, prefix_lens)`` and its side window's at ``[s_side,
    s_side + n_side)``, ``-inf`` elsewhere. Only LIVE pages move."""
    b, hi, di = q_idx.shape
    n, _, page_size = pages.shape
    mp, wc = page_table.shape[1], side.shape[1]
    ap, s_side, s_pad, tile = decode_layout(mp, page_size)
    if wc > tile:
        raise ValueError(f"a side window of {wc} rows in a tile of {tile}")
    # a row of scores is stored in whole 128-lane tiles: the side window's
    # keys padded to one (zeros, past ``n_side``)
    side = jnp.pad(side, ((0, 0), (0, -wc % min(tile, LANES)), (0, 0))
                   ).swapaxes(1, 2)
    bp = max(min(INDEX_PAGES_PER_BLOCK, mp) // ap, 1) * ap
    lens = prefix_lens.astype(jnp.int32)
    w = w.astype(jnp.float32)[..., None]
    whole = lambda a: pl.BlockSpec(a.shape, lambda i, *_: (0,) * a.ndim)
    return pl.pallas_call(
        functools.partial(
            _index_decode_kernel, page_size=page_size, pages_per_block=bp,
            pages_per_group=ap, n_pages_per_layer=n_pages_per_layer or n,
            s_side=s_side),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(b,),
            in_specs=[whole(q_idx), whole(w), whole(side),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((b, s_pad), lambda i, *_: (0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, di, bp * page_size), pages.dtype),
                pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, s_pad), jnp.float32),
        # rows one after another: the double-buffer state crosses grid steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="index_scores_decode",
    )(page_table, lens, flash_decode._next_live(lens),
      n_side.astype(jnp.int32), flash_decode._layer_scalar(layer),
      jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
      q_idx, w, side, pages)


def _select_decode_kernel(bounds_ref, n_ctx_ref, n_side_ref, s_ref, o_ref,
                          keys_ref, *, topk: int, bk: int, s_side: int):
    """``select_mask`` of ONE query a row, the rows on sublanes: a row sees
    its cached positions below ``n_ctx_ref`` [B, 1] and its side window's
    below ``n_side_ref`` [B, 1] (position order: the side rows last, so an
    equal score goes to the cached one). ``bounds_ref``: the tiles of the
    table that hold a visible key of some row, the most positions a row
    sees. The mask of the tiles past them is zeros."""
    b, s = s_ref.shape
    live, seen = bounds_ref[0], bounds_ref[1]
    side = s_side // bk                        # the side window's tile
    n_ctx, n_side = n_ctx_ref[...], n_side_ref[...]

    def at(j):
        return pl.ds(j * bk if isinstance(j, int)
                     else pl.multiple_of(j * bk, bk), bk)

    def order(j, _):
        c = j * bk + lax.broadcasted_iota(jnp.int32, (b, bk), 1)
        keys_ref[:, at(j)] = jnp.where(
            c < jnp.where(c < s_side, n_ctx, s_side + n_side),
            _ordered_signed(s_ref[:, at(j)]), _NO_KEY)

    def fold(fn, init):
        return fn(side, lax.fori_loop(0, live, fn, init))

    fold(order, None)

    def dead(j, _):
        o_ref[:, at(j)] = jnp.zeros((b, bk), o_ref.dtype)

    lax.fori_loop(live, side, dead, None)
    lax.fori_loop(side + 1, s // bk, dead, None)

    def write(j, keep):
        o_ref[:, at(j)] = keep.astype(o_ref.dtype)

    _counted_threshold(lambda j: keys_ref[:, at(j)], fold, write,
                       tq=b, bk=bk, s=s, topk=topk, seen=seen)


@functools.partial(jax.jit, static_argnames=("topk", "mp", "page_size",
                                             "interpret"))
def select_mask_decode(scores, prefix_lens, n_side, *, topk: int, mp: int,
                       page_size: int, interpret: bool = False):
    """scores float32 [B, s_pad] as ``index_scores_decode`` lays them over a
    table of ``mp`` pages (``decode_layout``), prefix_lens / n_side int32
    [B] -> int32 [B, s_pad] (1 = selected): ``select_mask`` of each row's
    one query over its visible positions, the whole batch's scores held in
    VMEM once."""
    b, s = scores.shape
    _ap, s_side, _s, bk = decode_layout(mp, page_size)
    lens = prefix_lens.astype(jnp.int32)
    n_side = n_side.astype(jnp.int32)
    bounds = jnp.stack([jnp.max(-(-lens // bk)), jnp.max(lens + n_side)])
    whole = lambda *_: (0, 0)
    return pl.pallas_call(
        functools.partial(_select_decode_kernel, topk=topk, bk=bk,
                          s_side=s_side),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec((b, 1), whole),
                      pl.BlockSpec((b, 1), whole),
                      pl.BlockSpec((b, s), whole)],
            out_specs=pl.BlockSpec((b, s), whole),
            scratch_shapes=[pltpu.VMEM((b, s), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, s), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="select_mask_decode",
    )(bounds, lens[:, None], n_side[:, None], scores)


def _sparse_decode_kernel(
    # scalar prefetch: as ``flash_decode._flash_decode_kernel``
    page_table_ref, prefix_lens_ref, next_live_ref, n_side_ref, layer_ref,
    buffer_index_ref, step_ref,
    # inputs
    q_ref,                     # [1, Hp, Dh] VMEM (auto-pipelined)
    side_k_ref, side_v_ref,    # [1, Wc, Hkv * Dh]
    keep_ref,                  # [B, S] int32, resident: the selection
    pages_hbm,                 # [L*N, P, 2 * Hkv * Dh] ANY: K|V rows
    # outputs
    out_ref,                   # [1, Hp, Dh]
    # scratch
    k_vmem, v_vmem,            # [2, bp, P, Hkv * Dh] double-buffered blocks
    m_scr, l_scr, acc_scr, sem,
    *,
    n_kv_heads: int, head_dim: int, n_heads: int, page_size: int,
    pages_per_block: int, pages_per_attend: int, n_pages_per_layer: int,
    s_side: int,
):
    """``flash_decode._flash_decode_kernel`` over ONE pool of K|V rows with
    every key under the selection's mask: the live prefix pages,
    ``pages_per_attend`` to one softmax update, then the side window as the
    last block, one ``(m, l, acc)``."""
    b = pl.program_id(0)
    fused = n_kv_heads * head_dim
    scale = 1.0 / (head_dim ** 0.5)

    @pl.when(b == 0)
    def _zero():
        # a group's dead pages are multiplied under the mask: by zeros or
        # an earlier block's rows, never by what the scratch held before
        k_vmem[...] = jnp.zeros_like(k_vmem)
        v_vmem[...] = jnp.zeros_like(v_vmem)

    flash_decode._init_acc(m_scr, l_scr, acc_scr)
    qbd = flash_decode._block_diag_q(q_ref[0], n_heads, n_kv_heads)
    flash_decode._prefix_loop(
        b, page_table_ref, prefix_lens_ref, next_live_ref, layer_ref,
        buffer_index_ref, step_ref, qbd, pages_hbm, pages_hbm, k_vmem,
        v_vmem, sem, m_scr, l_scr, acc_scr,
        bp=pages_per_block, page_size=page_size,
        n_pages_per_layer=n_pages_per_layer, scale=scale, kv_lanes=fused,
        attend_pages=pages_per_attend, keep_ref=keep_ref)

    n_side = n_side_ref[b]

    @pl.when(n_side > 0)
    def _side():
        keep = flash_decode._mask_row(
            keep_ref, b, pl.ds(s_side, side_k_ref.shape[1]))
        flash_decode._attend(qbd, side_k_ref[0], side_v_ref[0], 0, n_side,
                             m_scr, l_scr, acc_scr, scale, keep=keep)

    flash_decode._finish(out_ref, l_scr, acc_scr, g=n_heads // n_kv_heads,
                         dh=head_dim, n_kv_heads=n_kv_heads)


@functools.partial(jax.jit, static_argnames=(
    "n_kv_heads", "interpret", "n_pages_per_layer"))
def sparse_decode_attention(q, pages, page_table, prefix_lens, side_k,
                            side_v, n_side, keep, layer=None, *,
                            n_kv_heads: int, interpret: bool = False,
                            n_pages_per_layer: int = 0):
    """q [B, H, Dh]; pages [N, P, 2 * Hkv * Dh] K|V rows (or the layers'
    stacked); side_k / side_v [B, Wc, Hkv, Dh]; keep int32 [B, s_pad] as
    ``select_mask_decode`` lays it (``decode_layout``) -> [B, H, Dh]: the
    attention of each row's query over the positions ``keep`` selects, every
    LIVE page read whole where it lies (a page's K and V halves copied
    apart) and its unselected rows masked before the online softmax."""
    flash_decode._validate(q, pages, pages, page_table, n_kv_heads,
                           kv_fused=True)
    b, h, dh = q.shape
    n, page_size, width = pages.shape
    fused = width // 2
    mp, wc = page_table.shape[1], side_k.shape[1]
    # the pages of one softmax update: the index kernel's group, which the
    # mask's row is laid in whole
    ap, s_side, _s, _tile = decode_layout(mp, page_size)
    bp = max(min(SPARSE_PAGES_PER_BLOCK, mp) // ap, 1) * ap
    qp = flash_decode._pad_heads(q)
    hp = qp.shape[1]
    kv_scratch, acc_scratch = flash_decode._scratch(hp, fused, bp, page_size,
                                                    pages.dtype)
    row = lambda i, *_: (i, 0, 0)
    out = pl.pallas_call(
        functools.partial(
            _sparse_decode_kernel, n_kv_heads=n_kv_heads, head_dim=dh,
            n_heads=h, page_size=page_size, pages_per_block=bp,
            pages_per_attend=ap, n_pages_per_layer=n_pages_per_layer or n,
            s_side=s_side),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hp, dh), row),
                pl.BlockSpec((1, wc, fused), row),
                pl.BlockSpec((1, wc, fused), row),
                pl.BlockSpec(keep.shape, lambda i, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, hp, dh), row),
            scratch_shapes=kv_scratch + acc_scratch + [
                pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hp, dh), q.dtype),
        compiler_params=flash_decode._compiler_params(
            bp, page_size, fused, pages.dtype.itemsize),
        interpret=interpret,
        name="sparse_decode_flash",
    )(page_table, prefix_lens, flash_decode._next_live(prefix_lens), n_side,
      flash_decode._layer_scalar(layer),
      jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
      qp, side_k.reshape(b, wc, fused), side_v.reshape(b, wc, fused), keep,
      pages)
    return out[:, :h]
