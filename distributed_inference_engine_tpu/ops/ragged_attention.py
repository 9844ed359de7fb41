"""Ragged mixed-batch attention: decode and prefill-chunk rows in ONE kernel.

The continuous engine used to interleave chunked prefill and decode as
SEPARATE compiled dispatches, so admitting a long prompt stalled every live
decode for a full chunk, and decode-only steps left the MXU idle (bench r05:
0.363 HBM util). Ragged Paged Attention (arxiv 2604.15464) and Sarathi-style
mixed batching (RTP-LLM, arxiv 2605.29639) recover both ends: rows of
UNEQUAL query length share a single grid, so prefill chunks ride in the
decode step's bandwidth shadow and decode never pauses for prefill.

One ``pallas_call`` per layer, grid = one step per batch row. Every row
carries:

  - ``q_lens[r]`` fresh query tokens (0 = inert padding row, 1 = a decode
    row, >1 = a prefill chunk) packed into a ``[R, Qmax, H, Dh]`` block, and
  - ``ctx_lens[r]`` context tokens already living in the row's paged KV.

Per grid step the kernel streams the row's context pages HBM->VMEM with the
same double-buffered manual DMAs + cross-row prefetch as
``ops/flash_decode.py`` (``_prefix_loop``), runs an online-softmax flash
update vectorized over ALL the row's queries (one MXU matmul per head per
block — no per-query loop, so chunk rows are compute-dense), then in the
epilogue DMAs the row's fresh K/V back to its reserved pages (positions
``[ctx_len, ctx_len + q_len)``, page-straddling handled per token) while the
fresh-causal block and the finalize division execute in its shadow.

Masking semantics (the parity target, = ``ops.attention.suffix_attention``):
context key j is visible to every query iff ``j < ctx_len``; fresh key j is
visible to query i iff ``j <= i`` and ``j < q_len``. Rows ``i >= q_len`` of
the output are zeroed.

Correctness preconditions (engine invariants, asserted host-side by
``engine/paged_kv.py:ensure_backed``):

  - rows reference DISJOINT page sets (distinct slots never share live
    pages), so one row's writeback cannot race another row's streaming;
  - every row's pages are allocated ("backed") through
    ``ctx_len + q_len`` tokens BEFORE dispatch — the kernel writes blindly;
  - a row's own last context page may be partially filled; its writeback
    only touches offsets >= ``ctx_len % P`` of that page, after the read of
    the same page completed (wait precedes compute precedes writeback).

Mosaic constraints inherited from ``flash_decode.py``: rank-2 in-kernel
tensors with the fused ``Hkv*Dh`` dim on lanes (multiple of 128 on
hardware), 2D iota only, scratch updated by FULL stores (per-head results
are concatenated host-side of the store — Pallas ref slice-stores are not
used), and the grid is ``dimension_semantics=("arbitrary",)`` on purpose:
the double-buffer/step scalars cross grid steps.

Tuning note: the writeback epilogue is a static per-token DMA unroll
(correct for any ``ctx_len`` alignment). For large chunk buckets a
page-granular fast path (engine chunks ARE page-aligned) would cut the
instruction count ~P-fold; measured only as protocol r8 so far, so the
simple form stays until hardware numbers justify the second code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import suffix_attention
from .flash_decode import (
    NEG_INF,
    _default_pages_per_block,
    _layer_scalar,
    _next_live,
)

def _seg(H: int, dh: int):
    """Constant 0/1 [H·Dh, H] map: X @ seg segment-sums each head's Dh
    lanes; Y @ seg.T broadcasts per-head scalars back across lanes."""
    lane_head = lax.broadcasted_iota(jnp.int32, (H * dh, H), 0) // dh
    head_idx = lax.broadcasted_iota(jnp.int32, (H * dh, H), 1)
    return (lane_head == head_idx).astype(jnp.float32)


__all__ = [
    "ragged_attention",
    "ragged_attention_xla",
    "ragged_attention_pallas",
]


# ----------------------------------------------------------------- XLA path


def ragged_attention_xla(
    q: jnp.ndarray,            # [R, Qmax, H, Dh]
    k_pages: jnp.ndarray,      # [N, P, Hkv*Dh] one layer's pools
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # [R, MP] int32
    ctx_lens: jnp.ndarray,     # [R] tokens already in the row's pages
    q_lens: jnp.ndarray,       # [R] fresh queries (0 inert / 1 decode / >1 chunk)
    fresh_k: jnp.ndarray,      # [R, Qmax, Hkv, Dh] this step's K/V
    fresh_v: jnp.ndarray,
    *,
    n_kv_heads: int,
):
    """Reference mixed-batch step: gather the whole table, run
    ``suffix_attention``, scatter fresh K/V back. Returns
    ``(out [R, Qmax, H, Dh], k_pages', v_pages')``."""
    r, qmax, h, dh = q.shape
    n, p, fused = k_pages.shape
    mp = page_table.shape[1]
    ctx_lens = ctx_lens.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    # round-trip fresh K/V through the pool dtype BEFORE attending: the
    # kernel attends to the same bits it writes back, so an fp8 pool must
    # quantize here too or the two impls (and the split path they replace)
    # diverge on the fresh keys
    fk = fresh_k.astype(k_pages.dtype)
    fv = fresh_v.astype(v_pages.dtype)
    ctx_k = k_pages[page_table].reshape(r, mp * p, n_kv_heads, dh)
    ctx_v = v_pages[page_table].reshape(r, mp * p, n_kv_heads, dh)
    out = suffix_attention(
        q, ctx_k.astype(q.dtype), ctx_v.astype(q.dtype), ctx_lens,
        fk.astype(q.dtype), fv.astype(q.dtype), q_lens)
    # zero padding rows (also neutralizes the NaN a fully-masked softmax
    # row produces — inert rows have no valid keys at all)
    row_valid = jnp.arange(qmax, dtype=jnp.int32)[None, :] < q_lens[:, None]
    out = jnp.where(row_valid[..., None, None], out, 0.0).astype(q.dtype)
    # scatter fresh K/V to pages [ctx_len, ctx_len + q_len)
    local = jnp.broadcast_to(jnp.arange(qmax, dtype=jnp.int32)[None, :],
                             (r, qmax))
    pos = local + ctx_lens[:, None]
    logical = jnp.minimum(pos // p, mp - 1)
    phys = jnp.take_along_axis(page_table, logical, axis=1)
    flat = jnp.where(row_valid, phys * p + pos % p, n * p)
    kp = k_pages.reshape(n * p, fused).at[flat].set(
        fk.reshape(r, qmax, fused), mode="drop").reshape(n, p, fused)
    vp = v_pages.reshape(n * p, fused).at[flat].set(
        fv.reshape(r, qmax, fused), mode="drop").reshape(n, p, fused)
    return out, kp, vp


# ------------------------------------------------------------ kernel pieces


def _ragged_block(qf, kf, vf, key_valid, m_scr, l_scr, acc_scr, scale,
                  *, H, g, dh):
    """One online-softmax update over a key block, for ALL query rows.

    qf [Qm, H*Dh] f32, kf/vf [S, Hkv*Dh] f32, key_valid [Qm, S] bool.
    Static loop over heads, real matmuls per head ([Qm, Dh] x [S, Dh]^T),
    with each head's KV lanes sliced directly (kv = h // g) — no GQA
    expansion and no per-query loop, so a chunk row keeps the MXU busy.
    Invalid probs are explicitly zeroed, not just NEG_INF-masked: a block
    may be ENTIRELY masked for some rows (inert padding, fresh block of a
    pure-context row), and with m still at NEG_INF exp(0) = 1 would sum
    garbage into the accumulator. Scratch is read once and written back by
    FULL stores of the concatenated per-head columns (no ref slice-stores).
    """
    m_all = m_scr[:]                                      # [Qm, H]
    l_all = l_scr[:]
    acc_all = acc_scr[:]                                  # [Qm, H*Dh]
    m_cols, l_cols, acc_cols = [], [], []
    for h in range(H):
        kv = h // g
        q_h = qf[:, h * dh:(h + 1) * dh]                  # [Qm, Dh]
        k_h = kf[:, kv * dh:(kv + 1) * dh]                # [S, Dh]
        v_h = vf[:, kv * dh:(kv + 1) * dh]
        s = lax.dot_general(
            q_h, k_h, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [Qm, S]
        s = jnp.where(key_valid, s, NEG_INF)
        m_prev = m_all[:, h:h + 1]                        # [Qm, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(s - m_new)
        probs = jnp.where(key_valid, probs, 0.0)
        pv = jnp.dot(probs, v_h, preferred_element_type=jnp.float32)
        m_cols.append(m_new)
        l_cols.append(l_all[:, h:h + 1] * alpha
                      + probs.sum(axis=1, keepdims=True))
        acc_cols.append(acc_all[:, h * dh:(h + 1) * dh] * alpha + pv)
    m_scr[:] = jnp.concatenate(m_cols, axis=1)
    l_scr[:] = jnp.concatenate(l_cols, axis=1)
    acc_scr[:] = jnp.concatenate(acc_cols, axis=1)


def _ragged_kernel(
    # scalar prefetch
    page_table_ref,            # [R, MP] SMEM
    ctx_lens_ref,              # [R]
    q_lens_ref,                # [R]
    next_live_ref,             # [R] next row with a non-empty context
    layer_ref,                 # [1] layer offset into stacked pools
    buffer_index_ref,          # [1] MUTABLE: double-buffer slot
    step_ref,                  # [1] MUTABLE: global processed-block count
    # inputs
    q_ref,                     # [1, Qm, H*Dh] VMEM (auto-pipelined per row)
    fresh_k_ref,               # [1, Qm, fused] VMEM, pool dtype
    fresh_v_ref,
    k_pages_in,                # ANY — unused, all pool access via out refs
    v_pages_in,
    # outputs
    out_ref,                   # [1, Qm, H*Dh] VMEM
    k_pages_hbm,               # [N(*L), P, fused] ANY, aliased with input
    v_pages_hbm,
    # scratch
    k_vmem,                    # [2, bp, P, fused] pool dtype
    v_vmem,
    m_scr,                     # [Qm, H] f32
    l_scr,                     # [Qm, H] f32
    acc_scr,                   # [Qm, H*Dh] f32
    sem,                       # DMA: context streaming
    w_sem,                     # DMA: fresh-KV writeback
    *,
    n_kv_heads: int,
    head_dim: int,
    page_size: int,
    n_heads: int,
    pages_per_block: int,
    n_pages_per_layer: int,
    max_q: int,
):
    del k_pages_in, v_pages_in  # access via the aliased out refs
    H, dh, g = n_heads, head_dim, n_heads // n_kv_heads
    bp = pages_per_block
    fused = n_kv_heads * dh
    r = pl.program_id(0)
    batch = pl.num_programs(0)
    mp = page_table_ref.shape[1]
    blk_tokens = bp * page_size
    base = layer_ref[0] * n_pages_per_layer
    scale = 1.0 / (dh ** 0.5)
    ctx = ctx_lens_ref[r]
    qlen = q_lens_ref[r]

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    qf = q_ref[:].reshape(max_q, H * dh).astype(jnp.float32)

    # ---- context pages: flash loop, double-buffered DMA + cross-row
    # prefetch — structured exactly like flash_decode._prefix_loop, but the
    # block update is vectorized over the row's queries
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

    nblk = lax.div(ctx + blk_tokens - 1, blk_tokens)

    def body(i, _):
        slot = lax.rem(buffer_index_ref[0], 2)

        @pl.when(step_ref[0] == 0)
        def _first():                    # very first processed block overall
            issue(r, i, slot)

        nb, ni = lax.cond(i + 1 < nblk,
                          lambda: (r, i + 1),
                          lambda: (next_live_ref[r], jnp.int32(0)))

        @pl.when(nb < batch)
        def _prefetch():
            issue(nb, ni, 1 - slot)

        wait(slot)
        kf = k_vmem[slot].reshape(blk_tokens, fused).astype(jnp.float32)
        vf = v_vmem[slot].reshape(blk_tokens, fused).astype(jnp.float32)
        tok = i * blk_tokens + lax.broadcasted_iota(
            jnp.int32, (max_q, blk_tokens), 1)
        key_valid = tok < ctx            # context: visible to every query
        _ragged_block(qf, kf, vf, key_valid, m_scr, l_scr, acc_scr, scale,
                      H=H, g=g, dh=dh)
        buffer_index_ref[0] = 1 - slot
        step_ref[0] = step_ref[0] + 1
        return ()

    lax.fori_loop(0, nblk, body, ())

    # ---- epilogue writeback: start the fresh-KV DMAs NOW so they overlap
    # the fresh-causal block + finalize below. Per token because ctx may
    # straddle a page boundary at any offset; rows own disjoint pages and
    # this row's reads of its own tail page completed above, so the writes
    # race nothing.
    for j in range(max_q):
        pos = ctx + j
        col = jnp.minimum(lax.div(pos, page_size), mp - 1)
        page = base + page_table_ref[r, col]
        off = lax.rem(pos, page_size)

        @pl.when(j < qlen)
        def _start_write(j=j, page=page, off=off):
            pltpu.make_async_copy(
                fresh_k_ref.at[0, j], k_pages_hbm.at[page, off],
                w_sem).start()
            pltpu.make_async_copy(
                fresh_v_ref.at[0, j], v_pages_hbm.at[page, off],
                w_sem).start()

    # ---- fresh block: causal within the row's own queries
    fkf = fresh_k_ref[:].reshape(max_q, fused).astype(jnp.float32)
    fvf = fresh_v_ref[:].reshape(max_q, fused).astype(jnp.float32)
    qi = lax.broadcasted_iota(jnp.int32, (max_q, max_q), 0)
    kj = lax.broadcasted_iota(jnp.int32, (max_q, max_q), 1)
    key_valid = (kj <= qi) & (kj < qlen)
    _ragged_block(qf, fkf, fvf, key_valid, m_scr, l_scr, acc_scr, scale,
                  H=H, g=g, dh=dh)

    # ---- finalize: divide by the softmax denominator, zero padding rows
    seg = _seg(H, dh)
    le = jnp.dot(jnp.maximum(l_scr[:], 1e-30), seg.T,
                 preferred_element_type=jnp.float32)      # [Qm, H*Dh]
    out = acc_scr[:] / le
    rowi = lax.broadcasted_iota(jnp.int32, (max_q, H * dh), 0)
    out = jnp.where(rowi < qlen, out, 0.0)
    out_ref[:] = out.reshape(1, max_q, H * dh).astype(out_ref.dtype)

    # ---- drain the writebacks before leaving the grid step (the refs only
    # size the semaphore decrement, mirroring _prefix_loop's wait())
    for j in range(max_q):
        @pl.when(j < qlen)
        def _drain(j=j):
            pltpu.make_async_copy(
                fresh_k_ref.at[0, j], k_pages_hbm.at[0, 0], w_sem).wait()
            pltpu.make_async_copy(
                fresh_v_ref.at[0, j], v_pages_hbm.at[0, 0], w_sem).wait()


# -------------------------------------------------------------- entry point


def _validate_ragged(q, k_pages, v_pages, page_table, n_kv_heads):
    if q.ndim != 4:
        raise ValueError(f"q must be [R, Qmax, H, Dh], got {q.shape}")
    r, qmax, h, dh = q.shape
    fused = k_pages.shape[-1]
    if fused != n_kv_heads * dh:
        raise ValueError(
            f"fused dim {fused} != n_kv_heads*head_dim {n_kv_heads * dh}")
    if fused % 128:
        raise ValueError(
            f"n_kv_heads*head_dim = {fused} must be a multiple of 128 "
            "(TPU lane width) for the pallas-ragged kernel")
    if k_pages.shape != v_pages.shape:
        raise ValueError("k_pages/v_pages shape mismatch")
    if page_table.shape[0] != r:
        raise ValueError(
            f"page_table rows {page_table.shape[0]} != batch {r}")
    if h % n_kv_heads:
        raise ValueError(f"n_heads {h} not divisible by n_kv_heads "
                         f"{n_kv_heads}")


def ragged_attention_pallas(
    q: jnp.ndarray,            # [R, Qmax, H, Dh]
    k_pages: jnp.ndarray,      # [N, P, fused] or stacked [L*N, P, fused] — DONATED
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # [R, MP] int32
    ctx_lens: jnp.ndarray,     # [R]
    q_lens: jnp.ndarray,       # [R]
    fresh_k: jnp.ndarray,      # [R, Qmax, Hkv, Dh]
    fresh_v: jnp.ndarray,
    *,
    n_kv_heads: int,
    interpret: bool = False,
    layer=None,
    n_pages_per_layer: int = 0,
    pages_per_block: int = 0,
):
    """Fused ragged attention + fresh-KV page writeback. Returns
    ``(out [R, Qmax, H, Dh], k_pages', v_pages')``."""
    _validate_ragged(q, k_pages, v_pages, page_table, n_kv_heads)
    r, qmax, h, dh = q.shape
    n, page_size, fused = k_pages.shape
    mp = page_table.shape[1]
    bp = pages_per_block or _default_pages_per_block(page_size, fused, mp)
    bp = min(bp, mp)
    ctx_lens = ctx_lens.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    # DMA cannot convert dtype: land the fresh K/V in the pool dtype here
    fk = fresh_k.reshape(r, qmax, fused).astype(k_pages.dtype)
    fv = fresh_v.reshape(r, qmax, fused).astype(v_pages.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(r,),
        in_specs=[
            pl.BlockSpec((1, qmax, h * dh), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, qmax, fused), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, qmax, fused), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, qmax, h * dh), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, bp, page_size, fused), k_pages.dtype),
            pltpu.VMEM((2, bp, page_size, fused), v_pages.dtype),
            pltpu.VMEM((qmax, h), jnp.float32),
            pltpu.VMEM((qmax, h), jnp.float32),
            pltpu.VMEM((qmax, h * dh), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = functools.partial(
        _ragged_kernel,
        n_kv_heads=n_kv_heads, head_dim=dh, page_size=page_size,
        n_heads=h, pages_per_block=bp,
        n_pages_per_layer=n_pages_per_layer or n, max_q=qmax)
    out, kp, vp = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, qmax, h * dh), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # alias the pools through: operand indices COUNT the 7 scalar-
        # prefetch args, so q=7, fresh=8/9, pools=10/11 -> outputs 1/2
        input_output_aliases={10: 1, 11: 2},
        compiler_params=pltpu.CompilerParams(
            # sequential rows on purpose: the double-buffer/step state
            # crosses grid steps (cross-row prefetch)
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=4 * r * qmax * (mp * page_size + qmax) * h * dh,
            bytes_accessed=(r * mp * page_size * fused
                            * k_pages.dtype.itemsize * 2
                            + 2 * r * qmax * fused
                            * k_pages.dtype.itemsize * 2),
            transcendentals=r * qmax * (mp * page_size + qmax) * h),
        interpret=interpret,
    )(page_table, ctx_lens, q_lens, _next_live(ctx_lens),
      _layer_scalar(layer),
      jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
      q.reshape(r, qmax, h * dh), fk, fv, k_pages, v_pages)
    return out.reshape(r, qmax, h, dh), kp, vp


def ragged_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    ctx_lens: jnp.ndarray,
    q_lens: jnp.ndarray,
    fresh_k: jnp.ndarray,
    fresh_v: jnp.ndarray,
    *,
    n_kv_heads: int,
    impl: str = "xla",
    layer=None,
    n_pages_per_layer: int = 0,
    pages_per_block: int = 0,
):
    """Dispatch mixed-batch ragged attention by impl string.

    ``"xla"`` — reference path, single-layer pools only.
    ``"pallas-ragged"`` — fused kernel; ``"pallas-ragged_interpret"`` runs
    the same kernel through the CPU interpreter (parity tests).
    """
    if impl == "xla":
        if layer is not None:
            raise ValueError(
                "xla ragged path takes one layer's pools (layer=None)")
        return ragged_attention_xla(
            q, k_pages, v_pages, page_table, ctx_lens, q_lens,
            fresh_k, fresh_v, n_kv_heads=n_kv_heads)
    if impl in ("pallas-ragged", "pallas-ragged_interpret"):
        return ragged_attention_pallas(
            q, k_pages, v_pages, page_table, ctx_lens, q_lens,
            fresh_k, fresh_v, n_kv_heads=n_kv_heads,
            interpret=impl.endswith("_interpret"), layer=layer,
            n_pages_per_layer=n_pages_per_layer,
            pages_per_block=pages_per_block)
    raise ValueError(f"unknown ragged attention impl: {impl!r}")
