"""Fused flash-decode attention: paged prefix + chunk side window in ONE
Pallas kernel per layer (``attn_impl="pallas-decode"``).

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

DMA architecture — why this kernel is not the retired
``ops/paged_attention.py`` one: that kernel's (slot, page) grid DMA'd ONE
page per sequential grid step through the auto-pipeliner, which only
overlaps one step ahead — every scattered ~128 KB page copy stalled the
core for its full ~µs latency (~13 µs unhidden per step; 1,380 vs 3,623
tok/s end-to-end, round 3). Here the page pools stay HBM-resident
(``memory_space=ANY``) and the kernel issues its own multi-page async
copies, double-buffered: while block ``i`` is being computed, the copies
for block ``i+1`` — or the FIRST block of the next live row, crossing
grid steps via mutable scalar-prefetch state — are already in flight.
This is the jax.experimental paged-attention DMA pattern grafted onto
this repo's Mosaic idioms.

Mosaic idioms (hard-won on hardware, see ops/paged_attention.py): every
in-kernel tensor stays RANK-2 with the fused head·dim axis on lanes;
per-head segment sums/broadcasts are matmuls against constant 0/1 ``seg``
matrices; GQA expands K/V to query heads via STATIC lane-slice concats;
q/out blocks carry a singleton sublane axis so trailing block dims EQUAL
the array dims; the fused KV dim must be a multiple of 128 (TPU lanes).

Two kernels:

- ``_flash_decode_kernel`` (``impl="pallas-decode"``): attention only.
  The caller still writes the step's fresh K/V into the side buffer (the
  XLA one-hot select), and ``n_side`` counts it as valid.
- ``_flash_decode_fw_kernel`` (``impl="pallas-decode-fw"``): additionally
  routes the KV writeback through the kernel epilogue — fresh K/V arrive
  as separate [B, 1, fused] operands, attend as one extra key, and are
  DMA'd into the (input/output-aliased, HBM-resident) side buffers at
  each slot's column, replacing the per-layer one-hot rewrite of the
  whole [B, W] side slice with B row-sized copies. Whether that wins on
  hardware is an open A/B (docs/decode_profile.md); both modes share the
  flash inner loop, so parity tests pin them to the same reference.

Both run under ``interpret=True`` on CPU (the parity tests) — the
interpret mode of this jax version executes ``make_async_copy`` on
ANY-space refs, mutable scalar-prefetch state, and input/output aliasing
faithfully (probed; the aliasing index counts scalar-prefetch operands).
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

# pages DMA'd per compute block, keyed by (page_size, fused). Populated by
# examples/flash_decode_tune.py on hardware; unlisted shapes fall back to
# the ~512-token-block heuristic below (4 pages at the flagship P=128).
_TUNED_PAGES_PER_BLOCK: dict = {}


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
) -> jnp.ndarray:
    """Reference composition: the exact three-part path the kernel fuses
    (paged prefix with stats ⊕ windowed side, merged). Correct everywhere;
    the parity tests pin the kernel to this and this to
    ``cached_attention`` ground truth."""
    prefix = paged_attention_xla(
        q, k_pages, v_pages, page_table, prefix_lens,
        n_kv_heads=n_kv_heads, with_stats=True)
    window_part = window_decode_attention(q, side_k, side_v, n_side)
    return merge_attention([prefix, window_part], dtype=q.dtype)


# ------------------------------------------------------- shared kernel math


def _seg(H: int, dh: int):
    """Constant 0/1 [H·Dh, H] map: X @ seg segment-sums each head's Dh
    lanes; Y @ seg.T broadcasts per-head scalars back across lanes."""
    lane_head = lax.broadcasted_iota(jnp.int32, (H * dh, H), 0) // dh
    head_idx = lax.broadcasted_iota(jnp.int32, (H * dh, H), 1)
    return (lane_head == head_idx).astype(jnp.float32)


def _expand_gqa(xf: jnp.ndarray, H: int, g: int, dh: int) -> jnp.ndarray:
    """[S, Hkv·Dh] -> [S, H·Dh] via static lane-slice concats (a dense 0/1
    expander matmul would cost O(S·HkvDh·HDh) MACs and a VMEM constant
    that blows up at 8B-class GQA shapes)."""
    if g == 1:
        return xf
    return jnp.concatenate(
        [xf[:, (h // g) * dh: (h // g + 1) * dh] for h in range(H)], axis=1)


def _flash_block(qf, kf, vf, valid, seg, m_scr, l_scr, acc_scr, scale):
    """One online-softmax update over a key block.

    qf [1, H·Dh] f32, kf/vf [S, H·Dh] f32 (GQA-expanded), valid [S, H]
    bool. Invalid probs are explicitly zeroed (not just NEG_INF-masked):
    a block may be ENTIRELY masked (empty side window, fresh prefix), and
    with m still at NEG_INF exp(NEG_INF - NEG_INF) = 1 would sum stale
    buffer contents into the accumulator.
    """
    prod = kf * qf                                            # [S, H*Dh]
    scores = jnp.dot(prod, seg,                               # [S, H]
                     preferred_element_type=jnp.float32,
                     precision=lax.Precision.HIGHEST) * scale
    scores = jnp.where(valid, scores, NEG_INF)
    m_prev = m_scr[:]                                         # [1, H]
    l_prev = l_scr[:]
    m_new = jnp.maximum(m_prev, scores.max(axis=0, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)                           # [1, H]
    probs = jnp.exp(scores - m_new[0][None, :])               # [S, H]
    probs = jnp.where(valid, probs, 0.0)
    l_new = l_prev * alpha + probs.sum(axis=0, keepdims=True)
    pe = jnp.dot(probs, seg.T,                                # [S, H*Dh]
                 preferred_element_type=jnp.float32,
                 precision=lax.Precision.HIGHEST)
    pv = (pe * vf).sum(axis=0, keepdims=True)                 # [1, H*Dh]
    alpha_e = jnp.dot(alpha, seg.T,
                      preferred_element_type=jnp.float32,
                      precision=lax.Precision.HIGHEST)
    acc_scr[:] = acc_scr[:] * alpha_e + pv
    m_scr[:] = m_new
    l_scr[:] = l_new


def _prefix_loop(
    b, page_table_ref, prefix_lens_ref, next_live_ref, layer_ref,
    buffer_index_ref, step_ref, qf, k_pages_hbm, v_pages_hbm, k_vmem,
    v_vmem, sem, seg, m_scr, l_scr, acc_scr,
    *, bp, page_size, fused, n_pages_per_layer, H, g, dh, scale,
):
    """Flash loop over row ``b``'s live prefix pages: ``bp`` pages per
    block, double-buffered manual DMA, next block (possibly the first
    block of the NEXT live row — the cross-grid-step prefetch that hides
    the per-row pipeline bubble) issued before waiting on the current.

    ``next_live_ref[b]`` holds the next row after ``b`` with a non-empty
    prefix (or B): rows that never enter this loop must not be prefetched
    for, or their unconsumed copies leave the semaphore unbalanced. The
    scan is precomputed in the launcher (a suffix-min over live rows) —
    an in-kernel while_loop over the lengths ref also defeats the
    interpret-mode state discharge the parity tests run under."""
    batch = pl.num_programs(0)
    mp = page_table_ref.shape[1]
    blk_tokens = bp * page_size
    base = layer_ref[0] * n_pages_per_layer

    def issue(row, blk, slot):
        for j in range(bp):
            col = jnp.minimum(blk * bp + j, mp - 1)
            page = base + page_table_ref[row, col]
            pltpu.make_async_copy(
                k_pages_hbm.at[page], k_vmem.at[slot, j], sem).start()
            pltpu.make_async_copy(
                v_pages_hbm.at[page], v_vmem.at[slot, j], sem).start()

    def wait(slot):
        for j in range(bp):
            pltpu.make_async_copy(
                k_pages_hbm.at[0], k_vmem.at[slot, j], sem).wait()
            pltpu.make_async_copy(
                v_pages_hbm.at[0], v_vmem.at[slot, j], sem).wait()

    length = prefix_lens_ref[b]
    nblk = lax.div(length + blk_tokens - 1, blk_tokens)

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

        wait(slot)
        kf = k_vmem[slot].reshape(blk_tokens, fused).astype(jnp.float32)
        vf = v_vmem[slot].reshape(blk_tokens, fused).astype(jnp.float32)
        kf = _expand_gqa(kf, H, g, dh)
        vf = _expand_gqa(vf, H, g, dh)
        tok = i * blk_tokens + lax.broadcasted_iota(
            jnp.int32, (blk_tokens, H), 0)
        valid = tok < length
        _flash_block(qf, kf, vf, valid, seg, m_scr, l_scr, acc_scr, scale)
        buffer_index_ref[0] = 1 - slot
        step_ref[0] = step_ref[0] + 1
        return ()

    lax.fori_loop(0, nblk, body, ())


# ----------------------------------------------- kernel: attention-only


def _flash_decode_kernel(
    # scalar prefetch
    page_table_ref,            # [B, MP] SMEM
    prefix_lens_ref,           # [B]
    next_live_ref,             # [B] next row with a non-empty prefix
    n_side_ref,                # [B]
    layer_ref,                 # [1] layer offset into stacked pools
    buffer_index_ref,          # [1] MUTABLE: double-buffer slot
    step_ref,                  # [1] MUTABLE: global processed-block count
    # inputs
    q_ref,                     # [1, 1, H*Dh] VMEM (auto-pipelined)
    side_k_ref,                # [1, W, Hkv*Dh] VMEM (auto-pipelined)
    side_v_ref,
    k_pages_hbm,               # [L*N, P, Hkv*Dh] ANY (stays in HBM)
    v_pages_hbm,
    # outputs
    out_ref,                   # [1, 1, H*Dh] VMEM
    # scratch
    k_vmem,                    # [2, bp, P, Hkv*Dh] double-buffered blocks
    v_vmem,
    m_scr,                     # [1, H] f32 running max
    l_scr,                     # [1, H] f32 running denominator
    acc_scr,                   # [1, H*Dh] f32 running numerator
    sem,                       # DMA semaphore
    *,
    n_kv_heads: int,
    head_dim: int,
    page_size: int,
    n_heads: int,
    pages_per_block: int,
    n_pages_per_layer: int,
):
    b = pl.program_id(0)
    H, dh, g = n_heads, head_dim, n_heads // n_kv_heads
    fused = n_kv_heads * dh
    scale = 1.0 / (dh ** 0.5)
    seg = _seg(H, dh)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    qf = q_ref[0, 0, :].astype(jnp.float32)[None, :]          # [1, H*Dh]

    _prefix_loop(
        b, page_table_ref, prefix_lens_ref, next_live_ref, layer_ref,
        buffer_index_ref, step_ref, qf, k_pages_hbm, v_pages_hbm, k_vmem,
        v_vmem, sem, seg, m_scr, l_scr, acc_scr,
        bp=pages_per_block, page_size=page_size, fused=fused,
        n_pages_per_layer=n_pages_per_layer, H=H, g=g, dh=dh, scale=scale)

    # final block: the chunk side window (auto-pipelined into VMEM — its
    # DMA overlaps the previous grid step's compute)
    w = side_k_ref.shape[1]
    kf = _expand_gqa(side_k_ref[0].astype(jnp.float32), H, g, dh)
    vf = _expand_gqa(side_v_ref[0].astype(jnp.float32), H, g, dh)
    col = lax.broadcasted_iota(jnp.int32, (w, H), 0)
    _flash_block(qf, kf, vf, col < n_side_ref[b], seg,
                 m_scr, l_scr, acc_scr, scale)

    le = jnp.dot(jnp.maximum(l_scr[:], 1e-30), seg.T,
                 preferred_element_type=jnp.float32,
                 precision=lax.Precision.HIGHEST)
    out_ref[:] = (acc_scr[:] / le).reshape(1, 1, H * dh).astype(out_ref.dtype)


# ------------------------------------- kernel: fused side-write epilogue


def _flash_decode_fw_kernel(
    # scalar prefetch
    page_table_ref,            # [B, MP]
    prefix_lens_ref,           # [B]
    next_live_ref,             # [B]
    side_idx_ref,              # [B] this step's side column per slot
    active_ref,                # [B] int32 0/1
    layer_ref,                 # [1]
    buffer_index_ref,          # [1] MUTABLE
    step_ref,                  # [1] MUTABLE
    # inputs
    q_ref,                     # [1, 1, H*Dh] VMEM
    fresh_k_ref,               # [1, 1, Hkv*Dh] VMEM: this step's K
    fresh_v_ref,
    k_pages_hbm,               # [L*N, P, Hkv*Dh] ANY
    v_pages_hbm,
    side_k_in,                 # [B, W, Hkv*Dh] ANY (aliased to outputs;
    side_v_in,                 #   unused — all access via the out refs)
    # outputs
    out_ref,                   # [1, 1, H*Dh] VMEM
    side_k_out,                # [B, W, Hkv*Dh] ANY, aliased to side_k_in
    side_v_out,
    # scratch
    k_vmem,                    # [2, bp, P, Hkv*Dh]
    v_vmem,
    side_k_vmem,               # [W, Hkv*Dh] side row staging
    side_v_vmem,
    m_scr, l_scr, acc_scr,
    sem,
    side_sem,
    *,
    n_kv_heads: int,
    head_dim: int,
    page_size: int,
    n_heads: int,
    pages_per_block: int,
    n_pages_per_layer: int,
):
    b = pl.program_id(0)
    H, dh, g = n_heads, head_dim, n_heads // n_kv_heads
    fused = n_kv_heads * dh
    w = side_k_vmem.shape[0]
    scale = 1.0 / (dh ** 0.5)
    seg = _seg(H, dh)

    # side row read starts NOW so it rides under the whole prefix loop
    # (aliased buffers: reads go through the out refs — same memory)
    pltpu.make_async_copy(side_k_out.at[b], side_k_vmem, side_sem).start()
    pltpu.make_async_copy(side_v_out.at[b], side_v_vmem, side_sem).start()

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    qf = q_ref[0, 0, :].astype(jnp.float32)[None, :]

    _prefix_loop(
        b, page_table_ref, prefix_lens_ref, next_live_ref, layer_ref,
        buffer_index_ref, step_ref, qf, k_pages_hbm, v_pages_hbm, k_vmem,
        v_vmem, sem, seg, m_scr, l_scr, acc_scr,
        bp=pages_per_block, page_size=page_size, fused=fused,
        n_pages_per_layer=n_pages_per_layer, H=H, g=g, dh=dh, scale=scale)

    pltpu.make_async_copy(side_k_out.at[b], side_k_vmem, side_sem).wait()
    pltpu.make_async_copy(side_v_out.at[b], side_v_vmem, side_sem).wait()

    # epilogue writeback issued EARLY (before the side/fresh compute) so
    # its latency overlaps the remaining row work; B row-sized copies
    # replace the XLA one-hot rewrite of the whole [B, W] side slice
    act = active_ref[b]
    i_side = side_idx_ref[b]
    do_write = jnp.logical_and(act > 0, i_side < w)

    @pl.when(do_write)
    def _writeback():
        pltpu.make_async_copy(
            fresh_k_ref.at[0, 0], side_k_out.at[b, i_side], side_sem).start()
        pltpu.make_async_copy(
            fresh_v_ref.at[0, 0], side_v_out.at[b, i_side], side_sem).start()

    # side window: entries BEFORE this step's column are valid
    kf = _expand_gqa(side_k_vmem[:].astype(jnp.float32), H, g, dh)
    vf = _expand_gqa(side_v_vmem[:].astype(jnp.float32), H, g, dh)
    col = lax.broadcasted_iota(jnp.int32, (w, H), 0)
    _flash_block(qf, kf, vf, col < jnp.minimum(i_side, w), seg,
                 m_scr, l_scr, acc_scr, scale)

    # this step's token as one extra key (it never reached the buffers)
    kf1 = _expand_gqa(fresh_k_ref[0].astype(jnp.float32), H, g, dh)
    vf1 = _expand_gqa(fresh_v_ref[0].astype(jnp.float32), H, g, dh)
    valid1 = jnp.broadcast_to(act > 0, (1, H))
    _flash_block(qf, kf1, vf1, valid1, seg, m_scr, l_scr, acc_scr, scale)

    le = jnp.dot(jnp.maximum(l_scr[:], 1e-30), seg.T,
                 preferred_element_type=jnp.float32,
                 precision=lax.Precision.HIGHEST)
    out_ref[:] = (acc_scr[:] / le).reshape(1, 1, H * dh).astype(out_ref.dtype)

    @pl.when(do_write)
    def _drain():
        pltpu.make_async_copy(
            fresh_k_ref.at[0, 0], side_k_out.at[b, i_side], side_sem).wait()
        pltpu.make_async_copy(
            fresh_v_ref.at[0, 0], side_v_out.at[b, i_side], side_sem).wait()


# ------------------------------------------------------------- launchers


def _validate(q, k_pages, v_pages, page_table, n_kv_heads):
    b, h, dh = q.shape
    fused = k_pages.shape[-1]
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
    the kernel's cross-row prefetch target (see ``_prefix_loop``)."""
    batch = prefix_lens.shape[0]
    rows = jnp.arange(batch, dtype=jnp.int32)
    cand = jnp.where(prefix_lens > 0, rows, jnp.int32(batch))
    sufmin = lax.cummin(cand[::-1])[::-1]         # inclusive suffix min
    return jnp.concatenate(
        [sufmin[1:], jnp.full((1,), batch, jnp.int32)])


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
) -> jnp.ndarray:
    """Fused attention, side writes stay with the caller. [B, H, Dh]."""
    _validate(q, k_pages, v_pages, page_table, n_kv_heads)
    b, h, dh = q.shape
    n, page_size, fused = k_pages.shape
    mp = page_table.shape[1]
    w = side_k.shape[1]
    bp = pages_per_block or _default_pages_per_block(page_size, fused, mp)
    bp = min(bp, mp)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, h * dh), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, w, fused), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, w, fused), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, h * dh), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bp, page_size, fused), k_pages.dtype),
            pltpu.VMEM((2, bp, page_size, fused), v_pages.dtype),
            pltpu.VMEM((1, h), jnp.float32),
            pltpu.VMEM((1, h), jnp.float32),
            pltpu.VMEM((1, h * dh), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = functools.partial(
        _flash_decode_kernel,
        n_kv_heads=n_kv_heads, head_dim=dh, page_size=page_size,
        n_heads=h, pages_per_block=bp,
        n_pages_per_layer=n_pages_per_layer or n)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, h * dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # the grid walks rows sequentially on purpose: the double-
            # buffer/step state crosses grid steps (cross-row prefetch)
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * (mp * page_size + w) * h * dh,
            bytes_accessed=(b * mp * page_size * fused
                            * k_pages.dtype.itemsize * 2
                            + b * w * fused * side_k.dtype.itemsize * 2),
            transcendentals=b * (mp * page_size + w) * h),
        interpret=interpret,
    )(page_table, prefix_lens, _next_live(prefix_lens), n_side,
      _layer_scalar(layer),
      jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
      q.reshape(b, 1, h * dh),
      side_k.reshape(b, w, fused), side_v.reshape(b, w, fused),
      k_pages, v_pages)
    return out.reshape(b, h, dh)


def flash_decode_attention_fw_pallas(
    q: jnp.ndarray,            # [B, H, Dh]
    k_pages: jnp.ndarray,      # [N, P, fused] or stacked [L*N, P, fused]
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, MP]
    prefix_lens: jnp.ndarray,  # [B]
    side_k: jnp.ndarray,       # [B, W, Hkv, Dh] — DONATED (aliased)
    side_v: jnp.ndarray,
    fresh_k: jnp.ndarray,      # [B, 1, Hkv, Dh] this step's K/V
    fresh_v: jnp.ndarray,
    side_idx: jnp.ndarray,     # [B] side column this step writes
    active: jnp.ndarray,       # [B] bool/int — inactive slots don't write
    *,
    n_kv_heads: int,
    interpret: bool = False,
    layer=None,
    n_pages_per_layer: int = 0,
    pages_per_block: int = 0,
):
    """Fused attention + side-buffer writeback epilogue. Returns
    (out [B, H, Dh], side_k', side_v') with the fresh K/V landed."""
    _validate(q, k_pages, v_pages, page_table, n_kv_heads)
    b, h, dh = q.shape
    n, page_size, fused = k_pages.shape
    mp = page_table.shape[1]
    w = side_k.shape[1]
    bp = pages_per_block or _default_pages_per_block(page_size, fused, mp)
    bp = min(bp, mp)
    side_shape = side_k.shape
    sk = side_k.reshape(b, w, fused)
    sv = side_v.reshape(b, w, fused)
    fk = fresh_k.reshape(b, 1, fused).astype(sk.dtype)
    fv = fresh_v.reshape(b, 1, fused).astype(sv.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, h * dh), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, 1, fused), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, 1, fused), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, h * dh), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, bp, page_size, fused), k_pages.dtype),
            pltpu.VMEM((2, bp, page_size, fused), v_pages.dtype),
            pltpu.VMEM((w, fused), sk.dtype),
            pltpu.VMEM((w, fused), sv.dtype),
            pltpu.VMEM((1, h), jnp.float32),
            pltpu.VMEM((1, h), jnp.float32),
            pltpu.VMEM((1, h * dh), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = functools.partial(
        _flash_decode_fw_kernel,
        n_kv_heads=n_kv_heads, head_dim=dh, page_size=page_size,
        n_heads=h, pages_per_block=bp,
        n_pages_per_layer=n_pages_per_layer or n)
    out, sk_new, sv_new = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, 1, h * dh), q.dtype),
                   jax.ShapeDtypeStruct((b, w, fused), sk.dtype),
                   jax.ShapeDtypeStruct((b, w, fused), sv.dtype)],
        # aliasing indices COUNT the 8 scalar-prefetch operands (probed on
        # this jax version): side_k/side_v are call args 13/14
        input_output_aliases={13: 1, 14: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * (mp * page_size + w) * h * dh,
            bytes_accessed=(b * mp * page_size * fused
                            * k_pages.dtype.itemsize * 2
                            + b * w * fused * sk.dtype.itemsize * 2),
            transcendentals=b * (mp * page_size + w) * h),
        interpret=interpret,
    )(page_table, prefix_lens, _next_live(prefix_lens),
      jnp.asarray(side_idx, jnp.int32),
      jnp.asarray(active, jnp.int32), _layer_scalar(layer),
      jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
      q.reshape(b, 1, h * dh), fk, fv, k_pages, v_pages, sk, sv)
    return (out.reshape(b, h, dh),
            sk_new.reshape(side_shape), sv_new.reshape(side_shape))


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
    "pallas-decode_interpret" (CPU correctness tests). The "-fw"
    writeback variant has its own entry point (different dataflow:
    donated side buffers, returns them updated)."""
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
