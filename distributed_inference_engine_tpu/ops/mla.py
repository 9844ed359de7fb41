"""Multi-head latent attention (MLA, DeepSeek-V2 form): a token's cache is
ONE row ``[c (kv_lora_rank) | rotated
k_rope (qk_rope_head_dim)]`` shared by all heads, in place of K and V.

- ``mla_causal_attention``: prefill on the EXPANDED keys and values
  (``W_kvb c`` per head). On a TPU, at a ``T`` of whole query blocks, ONE
  blocked flash-attention kernel (``_flash_kernel``): online softmax over
  key blocks with scores, running maximum / sum and accumulator in float32
  in VMEM, so no ``[.., queries, keys]`` tensor reaches HBM; the grid
  visits only the (query block, key block) pairs at or under the diagonal,
  and of those only the ones below ``seq_lens`` compute or move anything.
  Elsewhere (the CPU, a ``T`` that is no whole number of blocks: the tiny
  specs) ``mla_causal_attention_xla``: einsum / softmax over query blocks,
  each reading the keys up to its own last row. ``prefill_impl`` chooses
  from the backend and ``T``; nothing else does.
- ``mla_absorbed_decode`` / ``mla_absorbed_decode_inplace``: one query token
  against cached rows as they lie. ``W_kvb``'s key half is absorbed into the
  query (``q_nope W_k^T`` scores against ``c`` directly) and its value half
  into the output (``(p c) W_v``), so a step reads one latent row a token
  instead of expanding 2 x H x 128. Context comes in two parts, the page
  rows frozen for the chunk and the chunk's side rows; their scores share
  one softmax. The first is a plain einsum / softmax chain over rows the
  caller gathered (the CPU, ``"xla"``); the second hands ``[q_abs | q_rope]``
  to ``ops/flash_decode.py``'s latent kernel, which copies the live pages
  from the pool where they lie (the rows are held at whole 128-lane tiles:
  ``ModelSpec.cache_row_width``).

The query may be compressed (``q_lora_rank``: the family projects it; these
functions take q as heads either way). Rotary frequencies are plain RoPE's
unless the caller hands ``rope_interleaved`` YaRN's (``yarn_inv_freq``), and
the softmax scale is ``(dn + dr)^-1/2`` unless it hands YaRN's
(``yarn_softmax_scale``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_decode import latent_decode_attention_pallas

NEG_INF = -1e30
LANES = 128
_VMEM_LIMIT = 64 << 20


def yarn_mscale(scale: float, m: float) -> float:
    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(d: int, theta: float, scaling) -> Tuple[np.ndarray, float]:
    """YaRN's frequencies of the ``d / 2`` rotated pairs and the amplitude
    of cos / sin, from a published ``rope_scaling`` group (``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``mscale``, ``mscale_all_dim``): pairs that turn more than ``beta_fast``
    times over the original context keep plain RoPE's frequency, those that
    turn less than ``beta_slow`` times have it divided by ``factor``, a
    linear ramp between (DeepSeek-V3's ``yarn_find_correction_range``)."""
    sc = dict(scaling)
    factor, orig = float(sc["factor"]), float(
        sc["original_max_position_embeddings"])
    extra = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def turns(beta):
        return d * math.log(orig / (beta * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(turns(float(sc.get("beta_fast", 32)))), 0)
    hi = min(math.ceil(turns(float(sc.get("beta_slow", 1)))), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    amp = (yarn_mscale(factor, float(sc.get("mscale", 1)))
           / yarn_mscale(factor, float(sc.get("mscale_all_dim", 0))))
    return inv.astype(np.float32), amp


def yarn_softmax_scale(d_qk: int, scaling) -> float:
    """``d_qk^-1/2 mscale(factor, mscale_all_dim)^2``."""
    sc = dict(scaling)
    return d_qk ** -0.5 * yarn_mscale(
        float(sc["factor"]), float(sc.get("mscale_all_dim", 0))) ** 2


def rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                     theta: float, inv_freq=None, amp: float = 1.0
                     ) -> jnp.ndarray:
    """Rotate pairs (2i, 2i+1) of the last axis by pos * theta^(-2i/d), or
    by ``inv_freq`` [d/2] with cos / sin times ``amp`` (YaRN):
    x [B, T, N, d], positions [B, T]."""
    d = x.shape[-1]
    if inv_freq is None:
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * inv       # [B,T,d/2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    if amp != 1.0:
        cos, sin = cos * amp, sin * amp
    xf = x.astype(jnp.float32)
    xe, xo = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([xe * cos - xo * sin, xe * sin + xo * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# the kernel's grid step: Q_BLOCK queries x K_BLOCK keys of HEADS_PER_STEP
# heads (fewer where the head count has no such divisor)
Q_BLOCK = 512
K_BLOCK = 512
HEADS_PER_STEP = 4


def _whole_blocks(t: int) -> bool:
    return t % Q_BLOCK == 0 and t % K_BLOCK == 0


def prefill_impl(t: int) -> str:
    """Which body runs a causal prefill of ``t`` positions: "flash", the
    kernel, on a TPU at whole blocks; "xla" elsewhere. ("flash_interpret":
    the kernel through the interpreter, for the CPU tests.)"""
    on_tpu = jax.default_backend() == "tpu"
    return "flash" if on_tpu and _whole_blocks(t) else "xla"


def _last_key_block(qi: int, bq: int, bk: int) -> int:
    return (qi * bq + bq - 1) // bk


def prefill_key_blocks(length: int, t: int) -> Tuple[int, int]:
    """(key blocks the kernel visits for a prompt of ``length`` in a bucket
    of ``t`` positions, blocks of the whole ``t x t`` square), one layer's:
    at or under the diagonal and below ``length``. A ``t`` of no whole
    blocks is one block."""
    bq, bk = (Q_BLOCK, K_BLOCK) if _whole_blocks(t) else (t, t)
    live_k = -(-length // bk)
    visited = sum(min(_last_key_block(qi, bq, bk) + 1, live_k)
                  for qi in range(-(-length // bq)))
    return visited, (t // bq) * (t // bk)


def mla_causal_attention(q_nope, q_rope, kv, k_rope, seq_lens,
                         scale: Optional[float] = None,
                         impl: str = "") -> jnp.ndarray:
    """q_nope [B,T,H,dn], q_rope [B,T,H,dr], kv [B,T,H,dn+dv] (a head's
    k_nope | v, as ``W_kvb`` gives them), k_rope [B,T,dr] (one per token,
    shared by the heads). Causal, keys past ``seq_lens`` masked. Returns
    [B,T,H,dv]; rows past ``seq_lens`` are not specified (nothing reads
    them). ``impl``: see ``prefill_impl``, which chooses when it is empty."""
    dn = q_nope.shape[-1]
    if scale is None:
        scale = (dn + q_rope.shape[-1]) ** -0.5
    impl = impl or prefill_impl(q_nope.shape[1])
    if impl == "xla":
        return mla_causal_attention_xla(
            q_nope, q_rope, kv[..., :dn], k_rope, kv[..., dn:], seq_lens,
            scale)
    return _flash_prefill(q_nope, q_rope, kv, k_rope, seq_lens, scale,
                          interpret=impl == "flash_interpret")


def mla_causal_attention_xla(q_nope, q_rope, k_nope, k_rope, v, seq_lens,
                             scale: float, q_block: int = Q_BLOCK
                             ) -> jnp.ndarray:
    """The einsum / softmax body: query blocks of ``q_block`` rows (one
    block where ``T`` is no whole number of them), unrolled, each reading
    only the keys up to its own last row: the rest are masked for every one
    of its rows. k_nope, v [B,T,H,.]; the rest as ``mla_causal_attention``."""
    t = q_nope.shape[1]
    qb = q_block if t % q_block == 0 else t
    key_ok = jnp.arange(t)[None, :] < seq_lens[:, None]        # [B, T]

    def block(i0):
        n_keys = i0 + qb
        s = (jnp.einsum("bihd,bjhd->bhij", q_nope[:, i0:n_keys],
                        k_nope[:, :n_keys],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bihd,bjd->bhij", q_rope[:, i0:n_keys],
                          k_rope[:, :n_keys],
                          preferred_element_type=jnp.float32)) * scale
        rows = i0 + jnp.arange(qb)[:, None]
        mask = (jnp.arange(n_keys)[None, :] <= rows)[None] \
            & key_ok[:, None, :n_keys]
        s = jnp.where(mask[:, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhij,bjhd->bihd", p.astype(v.dtype),
                          v[:, :n_keys])

    return jnp.concatenate([block(i0) for i0 in range(0, t, qb)], axis=1)


# ------------------------------------------------------- the prefill kernel
#
# Everything is laid out [B, T, H * d]: a head is a block of lanes, so the
# projections' outputs are read as they lie (kv is ONE operand, k_nope and v
# of a head side by side) and the result is what ``wo`` multiplies. The grid
# is (row, head group, pair): the pairs are the (query block, key block)
# couples at or under the diagonal, a query block's together, keys
# ascending; ``seq_lens`` and the two tables are scalar-prefetched. A pair
# past the row's length computes nothing and its index maps name the block
# already held, so nothing moves either. The score is two products,
# q_nope k_nope over dn and q_rope k_rope over dr (a head group's q_rope is
# whole lanes: HEADS_PER_STEP x 64); k_rope is fetched once a key block, not
# once a head. On one v5e chip (PERF.md section 6, PR 32): 1.95 ms at 4,096
# positions, 6.2 ms at 8,192, 2.9 ms for 4,100 in the 8,192 bucket, against
# 9.8 / 36.8 / 36.8 ms for the XLA body; 8 heads a step read 3-5 % faster
# and lower twice as long, blocks of 256 or 1,024 3-25 % slower.


def _pairs(t: int, bq: int, bk: int) -> Tuple[np.ndarray, np.ndarray]:
    pairs = [(qi, ki) for qi in range(t // bq)
             for ki in range(_last_key_block(qi, bq, bk) + 1)]
    return tuple(np.asarray(c, np.int32) for c in zip(*pairs))


def _lanes(x, width: int):
    """x [rows, LANES], every lane alike -> [rows, width]."""
    if width % LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], width))
    return jnp.tile(x, (1, width // LANES))


def _dot_nt(a, b):
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _flash_kernel(lens_ref, qi_ref, ki_ref, qn_ref, qr_ref, kv_ref, kr_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, scale: float, heads: int,
                  dn: int, dr: int, dv: int, bq: int, bk: int):
    n = lens_ref[pl.program_id(0)]
    pair = pl.program_id(2)
    q0, k0 = qi_ref[pair] * bq, ki_ref[pair] * bk

    @pl.when(k0 == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def visit(masked: bool):
        # key block 0 comes first and holds key 0, live for every row of a
        # live pair: m is finite before a block masked whole for some row
        kr = kr_ref[0]
        if masked:
            rows = q0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = k0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = (cols <= rows) & (cols < n)
        for j in range(heads):
            k_at = j * (dn + dv)
            s = (_dot_nt(qn_ref[0, :, j * dn:(j + 1) * dn],
                         kv_ref[0, :, k_at:k_at + dn])
                 + _dot_nt(qr_ref[0, :, j * dr:(j + 1) * dr], kr)) * scale
            if masked:
                s = jnp.where(keep, s, NEG_INF)
            m_prev = m_ref[j]                                  # [bq, LANES]
            m_next = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
            p = jnp.exp(s - _lanes(m_next, bk))
            alpha = jnp.exp(m_prev - m_next)
            l_ref[j] = alpha * l_ref[j] + p.sum(axis=-1)[:, None]
            m_ref[j] = m_next
            v = kv_ref[0, :, k_at + dn:k_at + dn + dv]
            acc_ref[j] = _lanes(alpha, dv) * acc_ref[j] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    live = (q0 < n) & (k0 < n)
    # a masked key for some row: past that row (the diagonal) or past n
    edge = (k0 + bk - 1 > q0) | (k0 + bk > n)
    pl.when(live & edge)(lambda: visit(True))
    pl.when(live & jnp.logical_not(edge))(lambda: visit(False))

    @pl.when(k0 + bk >= q0 + bq)               # the query block's last pair
    def _():
        for j in range(heads):
            l = l_ref[j]                       # 0: a block wholly past n
            o = acc_ref[j] / _lanes(jnp.where(l == 0.0, 1.0, l), dv)
            o_ref[0, :, j * dv:(j + 1) * dv] = o.astype(o_ref.dtype)


def _flash_prefill(q_nope, q_rope, kv, k_rope, seq_lens, scale: float,
                   interpret: bool) -> jnp.ndarray:
    b, t, h, dn = q_nope.shape
    dr, dv = q_rope.shape[-1], kv.shape[-1] - dn
    hb = max(d for d in range(1, HEADS_PER_STEP + 1) if h % d == 0)
    bq, bk = Q_BLOCK, K_BLOCK
    qi, ki = _pairs(t, bq, bk)

    def q_at(row, group, pair, lens, qi, ki):
        last = jnp.maximum(lens[row] - 1, 0) // bq
        return row, jnp.minimum(qi[pair], last), group

    def k_at(row, group, pair, lens, qi, ki):
        last = jnp.maximum(lens[row] - 1, 0) // bk
        live = qi[pair] * bq < lens[row]
        return row, jnp.where(live, jnp.minimum(ki[pair], last), last), group

    kernel = functools.partial(_flash_kernel, scale=float(scale), heads=hb,
                               dn=dn, dr=dr, dv=dv, bq=bq, bk=bk)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h // hb, len(qi)),
            in_specs=[
                pl.BlockSpec((1, bq, hb * dn), q_at),
                pl.BlockSpec((1, bq, hb * dr), q_at),
                pl.BlockSpec((1, bk, hb * (dn + dv)), k_at),
                pl.BlockSpec((1, bk, dr), lambda *a: k_at(*a)[:2] + (0,)),
            ],
            out_specs=pl.BlockSpec(
                (1, bq, hb * dv),
                lambda row, group, pair, lens, qi, ki: (row, qi[pair], group)),
            scratch_shapes=[pltpu.VMEM((hb, bq, LANES), jnp.float32),
                            pltpu.VMEM((hb, bq, LANES), jnp.float32),
                            pltpu.VMEM((hb, bq, dv), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, h * dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="mla_prefill_flash",
    )(seq_lens.astype(jnp.int32), qi, ki, q_nope.reshape(b, t, h * dn),
      q_rope.reshape(b, t, h * dr), kv.reshape(b, t, h * (dn + dv)), k_rope)
    return out.reshape(b, t, h, dv)


def _absorb_query(q_nope, w_kvb, dtype):
    """(``q_nope W_k^T`` [B,H,rank] in ``dtype``, ``W_v`` [rank,H,dv])."""
    dn = q_nope.shape[-1]
    q_abs = jnp.einsum("bhd,chd->bhc", q_nope, w_kvb[..., :dn],
                       preferred_element_type=jnp.float32).astype(dtype)
    return q_abs, w_kvb[..., dn:]


def _expand_values(o_lat, w_v, dtype):
    """``(p c) W_v``: o_lat [B,H,rank] float32 -> [B,H,dv] in ``dtype``."""
    return jnp.einsum("bhc,chd->bhd", o_lat.astype(w_v.dtype), w_v,
                      preferred_element_type=jnp.float32).astype(dtype)


def mla_absorbed_decode(q_nope, q_rope, w_kvb, ctx, n_ctx, side, n_side,
                        rank: int, scale: Optional[float] = None
                        ) -> jnp.ndarray:
    """q_nope [B,H,dn], q_rope [B,H,dr]; w_kvb [rank, H, dn + dv]; ctx
    [B,S,W] cached rows (c | k_rope | lanes nothing reads) valid below
    ``n_ctx`` [B]; side [B,Wc,W] valid below ``n_side``. Returns [B,H,dv]
    in q's dtype."""
    dn, dr = q_nope.shape[-1], q_rope.shape[-1]
    if scale is None:
        scale = (dn + dr) ** -0.5
    q_abs, w_v = _absorb_query(q_nope, w_kvb, ctx.dtype)
    q_rope = q_rope.astype(ctx.dtype)

    def scores(rows, n_valid):
        s = (jnp.einsum("bhc,bsc->bhs", q_abs, rows[..., :rank],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhr,bsr->bhs", q_rope, rows[..., rank:rank + dr],
                          preferred_element_type=jnp.float32)) * scale
        ok = jnp.arange(rows.shape[1])[None, :] < n_valid[:, None]
        return jnp.where(ok[:, None], s, NEG_INF)

    s = jnp.concatenate([scores(ctx, n_ctx), scores(side, n_side)], axis=-1)
    p = jax.nn.softmax(s, axis=-1).astype(ctx.dtype)
    n = ctx.shape[1]
    o_lat = (jnp.einsum("bhs,bsc->bhc", p[..., :n], ctx[..., :rank],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhs,bsc->bhc", p[..., n:], side[..., :rank],
                          preferred_element_type=jnp.float32))
    return _expand_values(o_lat, w_v, q_nope.dtype)


def mla_absorbed_decode_inplace(q_nope, q_rope, w_kvb, pages, page_table,
                                layer, n_ctx, side, n_side, rank: int,
                                scale: Optional[float] = None,
                                n_pages_per_layer: int = 0,
                                interpret: bool = False):
    """``mla_absorbed_decode`` over the pool where it lies: pages [L * N, P,
    W], every paged layer's, of which layer ``layer``'s pages named by
    ``page_table`` [B, MP] are read below ``n_ctx``; W whole 128-lane tiles
    whose lanes past ``rank + dr`` are zero. The same arithmetic: operands
    in the pool's dtype, float32 scores and accumulators, the probabilities
    cast to the pool's dtype before the value product. Returns ([B,H,dv] in
    q's dtype, pool pages the kernel copied: int32)."""
    dn, dr = q_nope.shape[-1], q_rope.shape[-1]
    if scale is None:
        scale = (dn + dr) ** -0.5
    dt = pages.dtype
    q_abs, w_v = _absorb_query(q_nope, w_kvb, dt)
    pad = pages.shape[-1] - rank - dr
    q = jnp.concatenate(
        [q_abs, q_rope.astype(dt), jnp.zeros((*q_abs.shape[:2], pad), dt)],
        -1)
    o_lat, copied = latent_decode_attention_pallas(
        q, pages, page_table, n_ctx, side, n_side, layer, v_lanes=rank,
        scale=float(scale), interpret=interpret,
        n_pages_per_layer=n_pages_per_layer)
    return _expand_values(o_lat, w_v, q_nope.dtype), copied
