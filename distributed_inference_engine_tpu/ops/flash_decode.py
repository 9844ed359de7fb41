"""Fused flash-decode attention: paged prefix + chunk side window in ONE
Pallas kernel per layer (the ``window`` decode body).

The windowed decode scheme (``models.base.forward_decode_window``) splits
each step's attention into three HLOs per layer: a paged/dense prefix
attention, ``window_decode_attention`` over the chunk's side buffer, and
``merge_attention`` over the flash stats. At bs128 those non-stream
fusions (attention compute + norms, writeback, layout/copies) are ~50% of
the step (docs/decode_profile.md), and the materialized dense-ctx slice
is HBM traffic the kernel can stream instead. This kernel computes

    softmax(q · [prefix pages ++ side window]) · V

in one pass: a flash-style online-softmax loop over the slot's live
prefix pages, then the side window as the final block, with the merge
falling out of the shared (m, l, acc) accumulators — no stats round-trip,
no separate merge fusion, no gathered ctx copy.

DMA architecture — a (slot, page) grid that DMAs ONE page per sequential
grid step through the auto-pipeliner only overlaps one step ahead: every
scattered ~128 KB page copy stalls the core for its full ~µs latency
(~13 µs unhidden per step; such a kernel measured 1,380 vs 3,623 tok/s
end-to-end, round 3). Here the page pools stay HBM-resident
(``memory_space=ANY``) and the kernel issues its own multi-page async
copies, double-buffered: while block ``i`` is being computed, the copies
for block ``i+1`` — or the FIRST block of the next live row, crossing
grid steps via mutable scalar-prefetch state — are already in flight.
This is the jax.experimental paged-attention DMA pattern grafted onto
this repo's Mosaic idioms.

Kernel math: the standard flash layout, queries on sublanes and keys on
lanes, two MXU matmuls per page against the page AS STORED
(``[P, Hkv·Dh]``, fused KV lanes): the row's query is laid out
block-diagonally over those lanes, so GQA needs no expansion of K/V, no
transpose and no per-head loop (see "shared kernel math" below). The
first version of this kernel expanded K/V to query heads in float32 and
reduced per head through 0/1 segment matmuls at HIGHEST precision; it
was written for bs64-128 and never timed. The fused KV dim must be a
multiple of 128 (TPU lanes); query rows are padded to whole sublane
tiles by the launcher.

Only live K/V moves: a page is DMA'd iff it holds a token below its
row's prefix length, a dead row (length 0) starts no DMA, and its side
window is skipped. Measured on one v5e chip at the served shape (8 rows,
32:8 heads x 128, 1-8 bf16 pages a row, PERF.md §6 PR 25): 16-24 us a
layer, 60-72 % of the HBM peak over the live pages, against 120 us for
the dense-context path's slice + attention + update at the 8-page
bucket.

``_latent_decode_kernel`` is the same loop over latent rows (MLA's
absorbed decode, ``ops/mla.py``): one pool, one copy a page, the value the
first lanes of the key.

The kernel (``_flash_decode_kernel``) is attention only: the caller
writes the step's fresh K/V into the side buffer (the XLA one-hot select),
and ``n_side`` counts it as valid. It runs under ``interpret=True`` on CPU
(the parity tests) — the interpret mode of this jax version executes
``make_async_copy`` on ANY-space refs and mutable scalar-prefetch state
faithfully (probed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import merge_attention, window_decode_attention
from .paged_attention import paged_attention_xla

NEG_INF = -1e30

# pages DMA'd per double-buffered block, keyed by (page_size, fused).
# Populated by examples/flash_decode_tune.py on hardware; unlisted shapes
# fall back to the ~512-token-block heuristic below (4 pages at the
# flagship P=128). (128, 1024), one v5e chip, 8 rows of 1-8 bf16 pages with
# 2/4/6/8 rows live (PERF.md §6, PR 25), us per layer: bp 1 18.3/19.7/20.7/
# 30.4, bp 2 15.7/18.3/17.4/25.3, bp 4 15.5/17.3/17.0/24.1, bp 8 15.6/
# 19.1/17.8/24.5.
# (128, 640): the latent kernel over both MLA families' rows, 8 rows of
# 1,500-8,400 positions in the 7-layer pool, 8 / 6 rows live, us a call by
# pages a block / pages a softmax update (PERF.md §6, PR 38;
# docs/sweeps/pr38-mla-decode-inplace-kernel.txt): 4/1 182/132, 4/2 122/89,
# 4/4 101/73, 8/2 112/82, 8/4 82.5/61.8, 8/8 85/63, 16/4 82/64, 16/8 82/62,
# 16/16 85/63.
_TUNED_PAGES_PER_BLOCK: dict = {(128, 1024): 4, (128, 640): 8}
# the latent kernel's pages to one softmax update, same key (unlisted: the
# whole block)
_TUNED_PAGES_PER_ATTEND: dict = {(128, 640): 4}


def _default_pages_per_block(page_size: int, fused: int, mp: int) -> int:
    tuned = _TUNED_PAGES_PER_BLOCK.get((page_size, fused))
    if tuned:
        return min(tuned, mp)
    return max(1, min(mp, 512 // page_size))


# ----------------------------------------------------------------- XLA path


def flash_decode_attention_xla(
    q: jnp.ndarray,            # [B, H, Dh]
    k_pages: jnp.ndarray,      # [N, P, Hkv*Dh] one layer's pools
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, MP] int32
    prefix_lens: jnp.ndarray,  # [B] frozen prefix length per slot
    side_k: jnp.ndarray,       # [B, W, Hkv, Dh] chunk side window
    side_v: jnp.ndarray,
    n_side: jnp.ndarray,       # [B] valid side entries (incl. this step's)
    *,
    n_kv_heads: int,
    first_rows=None,           # [B] first cached row a row may read
) -> jnp.ndarray:
    """Reference composition: the exact three-part path the kernel fuses
    (paged prefix with stats ⊕ windowed side, merged). Correct everywhere;
    the parity tests pin the kernel to this and this to
    ``cached_attention`` ground truth. ``first_rows``: cached rows below
    it are masked (a sliding-window layer's lower bound; the pages of
    ``page_table`` count from position 0)."""
    prefix = paged_attention_xla(
        q, k_pages, v_pages, page_table, prefix_lens,
        n_kv_heads=n_kv_heads, with_stats=True, first_rows=first_rows)
    window_part = window_decode_attention(q, side_k, side_v, n_side)
    return merge_attention([prefix, window_part], dtype=q.dtype)


# ------------------------------------------------------- shared kernel math
#
# One row's attention is two MXU matmuls per page, both in the standard
# flash layout (queries on sublanes, keys on lanes):
#
#     s   = Qbd [Hp, Hkv·Dh] · K_page[P, Hkv·Dh]ᵀ  -> [Hp, P]
#     acc += p [Hp, P] · V_page [P, Hkv·Dh]         -> [Hp, Hkv·Dh]
#
# ``Qbd`` is the row's query laid out block-diagonally over the fused KV
# lanes: head h keeps its Dh values in the lane range of ITS kv head and
# zeros elsewhere, so one matmul against the page as stored gives every
# head's scores (GQA costs nothing: K is the MXU's stationary operand and
# each of its tiles is loaded once whatever the group size). ``acc`` holds
# p·V against every kv head's lanes; the epilogue keeps each head's own
# block. Nothing is expanded, transposed or copied: a page goes from HBM
# to VMEM once and is read there by the two matmuls. K/V stay in the pool
# dtype (bf16 products are exact in the float32 accumulators); scores,
# softmax and accumulators are float32.


def _precision(dtype):
    return (lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else lax.Precision.DEFAULT)


def _block_diag_q(q, n_heads: int, n_kv_heads: int):
    """[Hp, Dh] -> [Hp, Hkv·Dh]: head h's query in kv head h // g's lanes,
    zeros elsewhere (rows past ``n_heads`` are padding: all zero)."""
    hp, dh = q.shape
    fused = n_kv_heads * dh
    g = n_heads // n_kv_heads
    qt = jnp.concatenate([q] * n_kv_heads, axis=1)
    row_kv = lax.broadcasted_iota(jnp.int32, (hp, fused), 0) // g
    lane_kv = lax.broadcasted_iota(jnp.int32, (hp, fused), 1) // dh
    return jnp.where(row_kv == lane_kv, qt, jnp.zeros_like(qt))


def _init_acc(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _attend(qbd, k, v, first_tok, n_valid, m_scr, l_scr, acc_scr, scale,
            first_valid=None, keep=None):
    """One online-softmax update over a key block.

    qbd [Hp, F], k/v [S, F] in the pool/side dtype; key j of the block is
    valid iff ``first_tok + j < n_valid`` (and, with ``first_valid``, not
    below it: a sliding window's lower edge; and, with ``keep`` bool [1, S],
    kept by it: a learned selection's mask). Invalid probs are explicitly
    zeroed (not just NEG_INF-masked): a block may be ENTIRELY masked
    (empty side window), and with m still at NEG_INF
    exp(NEG_INF - NEG_INF) = 1 would sum stale buffer contents into the
    accumulator.
    """
    # fp8 pools have no promotion path: they upcast to the query dtype
    cdt = (qbd.dtype if jnp.dtype(k.dtype).itemsize < 2
           else jnp.promote_types(qbd.dtype, k.dtype))
    qbd, k, v = qbd.astype(cdt), k.astype(cdt), v.astype(cdt)
    one_key = k.shape[0] == 1          # a matmul with N = 1 has no MXU form
    if one_key:
        s = (qbd.astype(jnp.float32) * k.astype(jnp.float32)).sum(
            axis=1, keepdims=True) * scale
    else:
        s = lax.dot_general(
            qbd, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_precision(cdt)) * scale                # [Hp, S]
    tok = first_tok + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = tok < n_valid
    if first_valid is not None:
        valid &= tok >= first_valid
    if keep is not None:
        valid &= keep
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[...]                                       # [Hp, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    if one_key:
        pv = p.astype(cdt).astype(jnp.float32) * v.astype(jnp.float32)
    else:
        pv = jnp.dot(p.astype(cdt), v,
                     preferred_element_type=jnp.float32,
                     precision=_precision(cdt))               # [Hp, F]
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new


def _mask_row(keep_ref, b, at):
    """Row ``b`` of a mask [B, S] (int32, the rows on sublanes) at lanes
    ``at`` -> bool [1, n]: the rows' tile under a sublane select (Mosaic
    loads no single sublane at a dynamic index)."""
    tile = keep_ref[:, at]
    mine = lax.broadcasted_iota(jnp.int32, tile.shape, 0) == b
    return jnp.where(mine, tile, 0).max(axis=0, keepdims=True) != 0


def _finish(out_ref, l_scr, acc_scr, *, g, dh, n_kv_heads):
    """Each head's own kv block of the accumulator, normalised."""
    acc = acc_scr[...]
    hp = acc.shape[0]
    row_kv = lax.broadcasted_iota(jnp.int32, (hp, dh), 0) // g
    out = jnp.zeros((hp, dh), jnp.float32)
    for j in range(n_kv_heads):
        out = out + jnp.where(row_kv == j, acc[:, j * dh:(j + 1) * dh], 0.0)
    out = out / jnp.maximum(l_scr[...], 1e-30)
    out_ref[0] = out.astype(out_ref.dtype)


def _prefix_loop(
    b, page_table_ref, prefix_lens_ref, next_live_ref, layer_ref,
    buffer_index_ref, step_ref, qbd, k_pages_hbm, v_pages_hbm, k_vmem,
    v_vmem, sem, m_scr, l_scr, acc_scr,
    *, bp, page_size, n_pages_per_layer, scale, kv_lanes=0, v_lanes=0,
    attend_pages=1, copied_ref=None, first_rows_ref=None, keep_ref=None,
    on_group=None,
):
    """Flash loop over row ``b``'s live prefix pages: ``bp`` pages per
    block, double-buffered manual DMA, next block (possibly the first
    block of the NEXT live row — the cross-grid-step prefetch that hides
    the per-row pipeline bubble) issued before waiting on the current.

    Only LIVE pages move: a page is copied (and waited for, and attended)
    iff it holds a token below the row's prefix length, so a row shorter
    than a block pays for its own pages only and a dead row (length 0)
    starts no DMA at all. Each buffer slot has its own DMA semaphore: the
    other slot's prefetch is in flight while this one is waited on.

    ``first_rows_ref[b]`` (the K/V kernel): the first cached row the row may
    read, a sliding-window layer's lower edge. The row's blocks then count
    from the page that holds it: no copy of a page wholly before it is
    started (the allocator has freed those), and the rows before it in the
    first page read are masked. 0 = every live page, as before.

    ``next_live_ref[b]`` holds the next row after ``b`` with a non-empty
    prefix (or B): rows that never enter this loop must not be prefetched
    for, or their unconsumed copies leave the semaphore unbalanced. The
    scan is precomputed in the launcher (a suffix-min over live rows) —
    an in-kernel while_loop over the lengths ref also defeats the
    interpret-mode state discharge the parity tests run under.

    ``copied_ref`` (SMEM, [1]) counts every page whose copy is started, by
    any row's turn: what the kernel moved, said by the kernel. (Under the
    interpreter ``step_ref`` starts every grid step at 0 again, so a later
    row's first block is issued, and counted, a second time by its own
    turn; on the chip each live page is counted once:
    ``scripts/chip_kernels.py`` ``flash_decode_kv_fused`` holds that.)

    ``v_lanes``: latent rows, ONE pool whose row is the key and whose first
    ``v_lanes`` lanes are the value (``v_pages_hbm`` / ``v_vmem`` are
    None): one copy a page into ``k_vmem`` [2, bp * P, W], read there by
    both products, ``attend_pages`` pages to one softmax update (one page
    an update leaves the MXU waiting on the update's chain of scores, max,
    exp and values: 0.53 us a page of 0.2 us of DMA, PR 38). A group runs
    if its first page is live; its dead pages hold what an earlier block
    left there (the caller zeroes the buffers once) under the mask.
    ``on_group(rows, first position)`` takes a group in place of the softmax
    update (the learned selection's index scores, ``ops/sparse_index.py``;
    ``qbd`` and the accumulators are then None): its pages are TRANSPOSED
    ([W, P], the positions on the lanes) and lie side by side along the
    lanes of ``k_vmem`` [2, W, bp * P].

    ``keep_ref`` (the K/V kernel; VMEM int32 [B, >= the table's positions]):
    a learned selection's mask, 1 where row ``b``'s cached position is
    kept. The K/V kernel then too takes ``attend_pages`` pages to one
    softmax update, each key under its entry of the mask (no lower bound
    then; dead pages of a live group as the latent rows')."""
    batch = pl.num_programs(0)
    blk_tokens = bp * page_size
    base = layer_ref[0] * n_pages_per_layer

    def first_page(row):
        return lax.div(first_rows_ref[row], page_size)

    def copies(row, blk, slot, j):
        col = blk * bp + j
        if first_rows_ref is not None:
            col = first_page(row) + col
        page = base + page_table_ref[row, col]
        if v_lanes:
            at = pl.ds(j * page_size, page_size)
            return (pltpu.make_async_copy(
                k_pages_hbm.at[page],
                k_vmem.at[slot, at] if on_group is None
                else k_vmem.at[slot, :, at],
                sem.at[slot]),)
        if kv_lanes:
            # ONE pool of K|V rows (both refs are it): a page's K is its
            # first ``kv_lanes`` lanes, its V the rest
            k_src = k_pages_hbm.at[page, :, pl.ds(0, kv_lanes)]
            v_src = v_pages_hbm.at[page, :, pl.ds(kv_lanes, kv_lanes)]
        else:
            k_src, v_src = k_pages_hbm.at[page], v_pages_hbm.at[page]
        return (pltpu.make_async_copy(k_src, k_vmem.at[slot, j],
                                      sem.at[slot]),
                pltpu.make_async_copy(v_src, v_vmem.at[slot, j],
                                      sem.at[slot]))

    def for_live_pages(row, blk, fn):
        n_live = lax.div(prefix_lens_ref[row] + page_size - 1, page_size)
        if first_rows_ref is not None:
            n_live = n_live - first_page(row)
        for j in range(bp):
            pl.when(blk * bp + j < n_live)(functools.partial(fn, j))

    def issue(row, blk, slot):
        def go(j):
            for c in copies(row, blk, slot, j):
                c.start()
            if copied_ref is not None:
                copied_ref[0] = copied_ref[0] + 1
        for_live_pages(row, blk, go)

    length = prefix_lens_ref[b]
    if first_rows_ref is None:
        first_tok, first_valid = 0, None
        nblk = lax.div(length + blk_tokens - 1, blk_tokens)
    else:
        first_tok, first_valid = first_page(b) * page_size, first_rows_ref[b]
        nblk = lax.div(lax.div(length + page_size - 1, page_size)
                       - first_page(b) + bp - 1, bp)

    def body(i, _):
        slot = lax.rem(buffer_index_ref[0], 2)

        @pl.when(step_ref[0] == 0)
        def _first():                    # very first processed block overall
            issue(b, i, slot)

        nb, ni = lax.cond(i + 1 < nblk,
                          lambda: (b, i + 1),
                          lambda: (next_live_ref[b], jnp.int32(0)))

        @pl.when(nb < batch)
        def _prefetch():
            issue(nb, ni, 1 - slot)

        def page(j):
            for c in copies(b, i, slot, j):
                c.wait()
            k, v = k_vmem[slot, j], v_vmem[slot, j]
            tok = (i * bp + j) * page_size
            if first_rows_ref is not None:
                tok = first_tok + tok
            _attend(qbd, k, v, tok, length, m_scr, l_scr, acc_scr, scale,
                    first_valid)

        def wait_group(n_live, first):
            def wait(j):
                for c in copies(b, i, slot, j):
                    c.wait()
            for j in range(first, first + attend_pages):
                pl.when(i * bp + j < n_live)(functools.partial(wait, j))

        def group(n_live, first):
            wait_group(n_live, first)
            at = pl.ds(first * page_size, attend_pages * page_size)
            tok = (i * bp + first) * page_size
            if on_group is not None:
                on_group(k_vmem[slot, :, at], tok)
                return
            k = k_vmem[slot, at]
            _attend(qbd, k, k[:, :v_lanes], tok, length, m_scr, l_scr,
                    acc_scr, scale)

        def kv_group(n_live, first):
            wait_group(n_live, first)
            rows = attend_pages * page_size
            k, v = (ref[slot, pl.ds(first, attend_pages)].reshape(
                rows, ref.shape[-1]) for ref in (k_vmem, v_vmem))
            tok = (i * bp + first) * page_size
            keep = None
            if keep_ref is not None:
                keep = _mask_row(keep_ref, b, pl.ds(
                    pl.multiple_of(tok, page_size), rows))
            _attend(qbd, k, v, tok, length, m_scr, l_scr, acc_scr, scale,
                    keep=keep)

        if v_lanes or keep_ref is not None:
            n_live = lax.div(length + page_size - 1, page_size)
            for first in range(0, bp, attend_pages):
                pl.when(i * bp + first < n_live)(functools.partial(
                    group if v_lanes else kv_group, n_live, first))
        else:
            for_live_pages(b, i, page)
        buffer_index_ref[0] = 1 - slot
        step_ref[0] = step_ref[0] + 1
        return ()

    lax.fori_loop(0, nblk, body, ())


# ----------------------------------------------- kernel: attention-only


def _flash_decode_kernel(
    # scalar prefetch
    page_table_ref,            # [B, MP] SMEM
    prefix_lens_ref,           # [B]
    next_live_ref,             # [B] next row with a page to copy
    n_side_ref,                # [B]
    layer_ref,                 # [1] layer offset into stacked pools
    # then, with ``lower_bound``, first_rows_ref [B]: the first cached row a
    # row may read; then
    #   buffer_index_ref       [1] MUTABLE: double-buffer slot
    #   step_ref               [1] MUTABLE: global processed-block count
    # inputs
    #   q_ref                  [1, Hp, Dh] VMEM (auto-pipelined)
    #   side_k_ref, side_v_ref [1, W, Hkv*Dh] VMEM (auto-pipelined)
    #   k_pages_hbm, v_pages_hbm  [L*N, P, Hkv*Dh] ANY (stays in HBM)
    # outputs
    #   out_ref                [1, Hp, Dh] VMEM
    # then, with ``count_pages``, copied_ref [1] SMEM: pages copied so far;
    # then the scratch:
    #   k_vmem, v_vmem         [2, bp, P, Hkv*Dh] double-buffered blocks
    #   m_scr, l_scr           [Hp, 1] f32 running max / denominator
    #   acc_scr                [Hp, Hkv*Dh] f32 running numerator
    #   sem                    DMA semaphores, one per buffer slot
    *rest,
    n_kv_heads: int,
    head_dim: int,
    page_size: int,
    n_heads: int,
    pages_per_block: int,
    n_pages_per_layer: int,
    kv_lanes: int = 0,
    count_pages: bool = False,
    lower_bound: bool = False,
):
    first_rows_ref = rest[0] if lower_bound else None
    (buffer_index_ref, step_ref, q_ref, side_k_ref, side_v_ref, k_pages_hbm,
     v_pages_hbm, out_ref) = rest[int(lower_bound):][:8]
    rest = rest[int(lower_bound) + 8:]
    copied_ref = rest[0] if count_pages else None
    k_vmem, v_vmem, m_scr, l_scr, acc_scr, sem = rest[int(count_pages):]
    b = pl.program_id(0)
    dh, g = head_dim, n_heads // n_kv_heads
    scale = 1.0 / (dh ** 0.5)

    if count_pages:
        @pl.when(b == 0)
        def _zero():
            copied_ref[0] = 0

    _init_acc(m_scr, l_scr, acc_scr)
    qbd = _block_diag_q(q_ref[0], n_heads, n_kv_heads)        # [Hp, F]

    _prefix_loop(
        b, page_table_ref, prefix_lens_ref, next_live_ref, layer_ref,
        buffer_index_ref, step_ref, qbd, k_pages_hbm, v_pages_hbm, k_vmem,
        v_vmem, sem, m_scr, l_scr, acc_scr,
        bp=pages_per_block, page_size=page_size,
        n_pages_per_layer=n_pages_per_layer, scale=scale,
        kv_lanes=kv_lanes, copied_ref=copied_ref,
        first_rows_ref=first_rows_ref)

    # final block: the chunk side window (auto-pipelined into VMEM — its
    # DMA overlaps the previous grid step's compute)
    n_side = n_side_ref[b]

    @pl.when(n_side > 0)
    def _side():
        _attend(qbd, side_k_ref[0], side_v_ref[0], 0, n_side,
                m_scr, l_scr, acc_scr, scale)

    _finish(out_ref, l_scr, acc_scr, g=g, dh=dh, n_kv_heads=n_kv_heads)


# ------------------------------------------------------------- launchers


def _validate(q, k_pages, v_pages, page_table, n_kv_heads, kv_fused=False):
    b, h, dh = q.shape
    if kv_fused and v_pages is not k_pages:
        raise ValueError("kv_fused: k_pages and v_pages are ONE pool of K|V "
                         "rows, passed twice")
    fused = k_pages.shape[-1] // (2 if kv_fused else 1)
    if fused != n_kv_heads * dh:
        raise ValueError(
            f"fused dim {fused} != n_kv_heads*head_dim {n_kv_heads * dh}")
    if fused % 128:
        raise ValueError(
            f"n_kv_heads*head_dim = {fused} must be a multiple of 128 "
            "(TPU lanes)")
    if k_pages.shape != v_pages.shape:
        raise ValueError("k_pages/v_pages shape mismatch")
    if page_table.shape[0] != b:
        raise ValueError("page_table batch mismatch")


def _layer_scalar(layer):
    if layer is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _next_live(prefix_lens: jnp.ndarray) -> jnp.ndarray:
    """next_live[b] = smallest row r > b with prefix_lens[r] > 0, else B —
    the kernel's cross-row prefetch target (see ``_prefix_loop``); with a
    lower bound the launcher hands in each row's pages to copy instead."""
    batch = prefix_lens.shape[0]
    rows = jnp.arange(batch, dtype=jnp.int32)
    cand = jnp.where(prefix_lens > 0, rows, jnp.int32(batch))
    sufmin = lax.cummin(cand[::-1])[::-1]         # inclusive suffix min
    return jnp.concatenate(
        [sufmin[1:], jnp.full((1,), batch, jnp.int32)])


def _pad_heads(q: jnp.ndarray) -> jnp.ndarray:
    """Query rows padded to a whole number of sublane tiles (8 rows of 32
    bits, 16 of 16): the padding rows are zero and sliced off the output."""
    tile = 8 * max(1, 4 // q.dtype.itemsize)
    pad = -q.shape[1] % tile
    return jnp.pad(q, ((0, 0), (0, pad), (0, 0))) if pad else q


def _scratch(hp, fused, bp, page_size, dtype):
    return [
        pltpu.VMEM((2, bp, page_size, fused), dtype),
        pltpu.VMEM((2, bp, page_size, fused), dtype),
    ], [
        pltpu.VMEM((hp, 1), jnp.float32),
        pltpu.VMEM((hp, 1), jnp.float32),
        pltpu.VMEM((hp, fused), jnp.float32),
    ]


# The HLO name of the kernel's op. perfbench/lib/tracered.py classes device
# ops by that name: "custom_call" puts this kernel's seconds under
# ``other_kernels`` (a functools.partial kernel is otherwise named after
# its caller, e.g. ``closed_call.13``, and lands in ``other``), and a name
# with "int4" in it would be counted as the weight kernel.
_OP_NAME = "flash_decode_custom_call"
_LATENT_OP_NAME = "latent_decode_custom_call"


def _compiler_params(bp, page_size, fused, itemsize):
    # the grid walks rows sequentially on purpose: the double-buffer/step
    # state crosses grid steps (cross-row prefetch)
    blocks = 4 * bp * page_size * fused * itemsize
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=min(blocks + (24 << 20), 100 << 20))


def _cost(b, h, dh, mp, page_size, w, fused, kv_itemsize, side_itemsize):
    return pl.CostEstimate(
        flops=4 * b * (mp * page_size + w) * h * dh,
        bytes_accessed=(b * mp * page_size * fused * kv_itemsize * 2
                        + b * w * fused * side_itemsize * 2),
        transcendentals=b * (mp * page_size + w) * h)


def flash_decode_attention_pallas(
    q: jnp.ndarray,            # [B, H, Dh]
    k_pages: jnp.ndarray,      # [N, P, fused] or stacked [L*N, P, fused]
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, MP] int32
    prefix_lens: jnp.ndarray,  # [B]
    side_k: jnp.ndarray,       # [B, W, Hkv, Dh]
    side_v: jnp.ndarray,
    n_side: jnp.ndarray,       # [B]
    *,
    n_kv_heads: int,
    interpret: bool = False,
    layer=None,
    n_pages_per_layer: int = 0,
    pages_per_block: int = 0,
    kv_fused: bool = False,
    count_pages: bool = False,
    first_rows=None,
):
    """Fused attention, side writes stay with the caller. [B, H, Dh].
    ``first_rows`` [B]: the first cached row each row may read (a
    sliding-window layer's lower edge, at most its ``prefix_lens``): no
    page wholly before it is copied and the rows before it are masked;
    None = every live page, the kernel as it was (no such scalar at all:
    the callers that pass none trace the program they always traced).
    ``kv_fused``: ``k_pages`` and ``v_pages`` are ONE pool whose rows are
    K|V side by side (``[.., P, 2 * fused]``, a per-layer family's); the
    kernel copies each half of a page's lanes where it lies.
    ``count_pages``: returns ``(out, pages)``, ``pages`` the int32 number
    of pool pages (a K and a V copy each) the kernel started a copy of,
    counted by the kernel as it starts them; the side window's ``B * W``
    rows come in besides, every call."""
    _validate(q, k_pages, v_pages, page_table, n_kv_heads, kv_fused)
    b, h, dh = q.shape
    n, page_size, fused = k_pages.shape
    if kv_fused:
        fused //= 2
    mp = page_table.shape[1]
    w = side_k.shape[1]
    bp = pages_per_block or _default_pages_per_block(page_size, fused, mp)
    bp = min(bp, mp)
    qp = _pad_heads(q)
    hp = qp.shape[1]
    kv_scratch, acc_scratch = _scratch(hp, fused, bp, page_size,
                                       k_pages.dtype)

    lower_bound = first_rows is not None
    # a row enters the prefix loop iff it has a page to copy
    enters, bound = prefix_lens, ()
    if lower_bound:
        first_rows = first_rows.astype(jnp.int32)
        enters = -(-prefix_lens // page_size) - first_rows // page_size
        bound = (first_rows,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7 + lower_bound,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hp, dh), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, w, fused), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, w, fused), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            [pl.BlockSpec((1, hp, dh), lambda i, *_: (i, 0, 0)),
             pl.BlockSpec(memory_space=pltpu.SMEM)] if count_pages
            else pl.BlockSpec((1, hp, dh), lambda i, *_: (i, 0, 0))),
        scratch_shapes=kv_scratch + acc_scratch + [
            pltpu.SemaphoreType.DMA((2,))],
    )
    kernel = functools.partial(
        _flash_decode_kernel,
        n_kv_heads=n_kv_heads, head_dim=dh, page_size=page_size,
        n_heads=h, pages_per_block=bp,
        n_pages_per_layer=n_pages_per_layer or n,
        kv_lanes=fused if kv_fused else 0, count_pages=count_pages,
        lower_bound=lower_bound)
    out_shape = jax.ShapeDtypeStruct((b, hp, dh), q.dtype)
    if count_pages:
        out_shape = [out_shape, jax.ShapeDtypeStruct((1,), jnp.int32)]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=_compiler_params(bp, page_size, fused,
                                         k_pages.dtype.itemsize),
        cost_estimate=_cost(b, h, dh, mp, page_size, w, fused,
                            k_pages.dtype.itemsize, side_k.dtype.itemsize),
        interpret=interpret,
        name=_OP_NAME,
    )(page_table, prefix_lens, _next_live(enters), n_side,
      _layer_scalar(layer), *bound,
      jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
      qp, side_k.reshape(b, w, fused), side_v.reshape(b, w, fused),
      k_pages, v_pages)
    if count_pages:
        return out[0][:, :h], out[1][0]
    return out[:, :h]


# ------------------------------------------- kernel: latent rows (MLA decode)
#
# Absorbed latent attention is flash decode with ONE K/V head shared by all
# query heads, whose value is the first ``v_lanes`` lanes of its key: the
# query heads [Hp, W] sit on sublanes, a page [P, W] is copied ONCE, ``s = q ·
# pageᵀ`` runs over the row as stored and ``acc += p · page[:, :v_lanes]``
# reads the same VMEM buffer. Everything that streams is ``_prefix_loop``.


def _latent_decode_kernel(
    # scalar prefetch: as ``_flash_decode_kernel``
    page_table_ref, prefix_lens_ref, next_live_ref, n_side_ref, layer_ref,
    buffer_index_ref, step_ref,
    # inputs
    q_ref,                     # [1, Hp, W] VMEM (auto-pipelined)
    side_ref,                  # [1, Wc, W] VMEM (auto-pipelined)
    pages_hbm,                 # [L*N, P, W] ANY (stays in HBM)
    # outputs
    out_ref,                   # [1, Hp, v_lanes] float32 VMEM
    copied_ref,                # [1] SMEM: pages copied so far
    # scratch
    page_vmem,                 # [2, bp * P, W] double-buffered blocks
    m_scr, l_scr,              # [Hp, 1] f32
    acc_scr,                   # [Hp, v_lanes] f32
    sem,
    *,
    v_lanes: int,
    scale: float,
    page_size: int,
    pages_per_block: int,
    pages_per_attend: int,
    n_pages_per_layer: int,
):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _zero():
        copied_ref[0] = 0
        # a group's dead pages are multiplied under the mask: by zeros or
        # an earlier block's rows, never by what the scratch held before
        page_vmem[...] = jnp.zeros_like(page_vmem)

    _init_acc(m_scr, l_scr, acc_scr)
    q = q_ref[0]
    _prefix_loop(
        b, page_table_ref, prefix_lens_ref, next_live_ref, layer_ref,
        buffer_index_ref, step_ref, q, pages_hbm, None, page_vmem, None,
        sem, m_scr, l_scr, acc_scr,
        bp=pages_per_block, page_size=page_size,
        n_pages_per_layer=n_pages_per_layer, scale=scale, v_lanes=v_lanes,
        attend_pages=pages_per_attend, copied_ref=copied_ref)

    n_side = n_side_ref[b]

    @pl.when(n_side > 0)
    def _side():
        side = side_ref[0]
        _attend(q, side, side[:, :v_lanes], 0, n_side,
                m_scr, l_scr, acc_scr, scale)

    out_ref[0] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)


@functools.partial(jax.jit, static_argnames=(
    "v_lanes", "scale", "interpret", "n_pages_per_layer", "pages_per_block",
    "pages_per_attend"))
def latent_decode_attention_pallas(
    q: jnp.ndarray,            # [B, H, W] in the pool's dtype
    pages: jnp.ndarray,        # [N, P, W] or stacked [L*N, P, W]
    page_table: jnp.ndarray,   # [B, MP] int32
    prefix_lens: jnp.ndarray,  # [B]
    side: jnp.ndarray,         # [B, Wc, W]
    n_side: jnp.ndarray,       # [B]
    layer=None,
    *,
    v_lanes: int,
    scale: float,
    interpret: bool = False,
    n_pages_per_layer: int = 0,
    pages_per_block: int = 0,
    pages_per_attend: int = 0,
):
    """One query token a row against latent rows read where they lie: a
    row's key is its ``W`` lanes, its value the first ``v_lanes`` of them.
    Returns ``(out [B, H, v_lanes] float32, pages)``, ``pages`` the kernel's
    own int32 count of the pool pages it started a copy of; the side
    window's ``B * Wc`` rows come in besides, every call. One ``jax.jit``:
    a program that calls it once a layer traces and lowers it once."""
    b, h, w = q.shape
    n, page_size, width = pages.shape
    if width != w or side.shape[-1] != w:
        raise ValueError(f"q / side / pool rows differ: {w}, "
                         f"{side.shape[-1]}, {width} lanes")
    if w % 128 or v_lanes > w:
        raise ValueError(
            f"rows of {w} lanes, values of {v_lanes}: the kernel copies "
            "whole 128-lane tiles and the value is a row's first lanes")
    mp = page_table.shape[1]
    wc = side.shape[1]
    bp = min(pages_per_block
             or _default_pages_per_block(page_size, w, mp), mp)
    ap = min(pages_per_attend or _TUNED_PAGES_PER_ATTEND.get(
        (page_size, w), bp), bp)
    bp -= bp % ap
    qp = _pad_heads(q)
    hp = qp.shape[1]
    rows = b * (mp * page_size + wc)
    out, copied = pl.pallas_call(
        functools.partial(
            _latent_decode_kernel, v_lanes=v_lanes, scale=scale,
            page_size=page_size, pages_per_block=bp, pages_per_attend=ap,
            n_pages_per_layer=n_pages_per_layer or n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hp, w), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, wc, w), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, hp, v_lanes), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[
                pltpu.VMEM((2, bp * page_size, w), pages.dtype),
                pltpu.VMEM((hp, 1), jnp.float32),
                pltpu.VMEM((hp, 1), jnp.float32),
                pltpu.VMEM((hp, v_lanes), jnp.float32),
                pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, hp, v_lanes), jnp.float32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        # one buffer pair, where the K/V kernel holds two
        compiler_params=_compiler_params(bp, page_size, w // 2,
                                         pages.dtype.itemsize),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * h * (w + v_lanes),
            bytes_accessed=rows * w * pages.dtype.itemsize,
            transcendentals=rows * h),
        interpret=interpret,
        name=_LATENT_OP_NAME,
    )(page_table, prefix_lens, _next_live(prefix_lens), n_side,
      _layer_scalar(layer),
      jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
      qp, side, pages)
    return out[:, :h], copied[0]


# ------------------------------------------------------------- dispatcher


def flash_decode_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    prefix_lens: jnp.ndarray,
    side_k: jnp.ndarray,
    side_v: jnp.ndarray,
    n_side: jnp.ndarray,
    *,
    n_kv_heads: int,
    impl: str = "pallas-decode",
    layer=None,
    n_pages_per_layer: int = 0,
    pages_per_block: int = 0,
) -> jnp.ndarray:
    """impl: "xla" (reference composition) | "pallas-decode" |
    "pallas-decode_interpret" (CPU correctness tests)."""
    if impl == "xla":
        if layer is not None:
            raise ValueError(
                "stacked-pool layer indexing is a pallas-path feature; "
                "slice the layer before the xla path")
        return flash_decode_attention_xla(
            q, k_pages, v_pages, page_table, prefix_lens,
            side_k, side_v, n_side, n_kv_heads=n_kv_heads)
    if impl in ("pallas-decode", "pallas-decode_interpret"):
        return flash_decode_attention_pallas(
            q, k_pages, v_pages, page_table, prefix_lens,
            side_k, side_v, n_side, n_kv_heads=n_kv_heads,
            interpret=impl.endswith("_interpret"), layer=layer,
            n_pages_per_layer=n_pages_per_layer,
            pages_per_block=pages_per_block)
    raise ValueError(f"unknown flash-decode impl {impl!r}")
