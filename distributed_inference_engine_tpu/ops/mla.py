"""Multi-head latent attention (MLA, DeepSeek-V2 form): a token's cache is
ONE row ``[c (kv_lora_rank) | rotated
k_rope (qk_rope_head_dim)]`` shared by all heads, in place of K and V.

- ``mla_causal_attention``: prefill on the EXPANDED keys and values
  (``W_kvb c`` per head), query blocks of ``q_block`` rows so the score
  tensor stays bounded at long prompts; with ``skip_masked`` a block reads
  only the keys up to its own last row (half the scores of a long prompt).
- ``mla_absorbed_decode``: one query token against cached rows as they lie.
  ``W_kvb``'s key half is absorbed into the query (``q_nope W_k^T`` scores
  against ``c`` directly) and its value half into the output (``(p c) W_v``),
  so a step reads 576 values a token instead of expanding 2 x H x 128.
  Context comes in two parts, the page rows frozen for the chunk and the
  chunk's side rows; their scores share one softmax.

The query may be compressed (``q_lora_rank``: the family projects it; these
functions take q as heads either way). Rotary frequencies are plain RoPE's
unless the caller hands ``rope_interleaved`` YaRN's (``yarn_inv_freq``), and
the softmax scale is ``(dn + dr)^-1/2`` unless it hands YaRN's
(``yarn_softmax_scale``); a spec without ``rope_scaling`` traces exactly the
program it did before these existed.

Plain einsum / softmax chains on purpose: the row width (576) is no
multiple of 128 lanes, which ``ops/flash_decode.py`` requires, and one such
layer in six leaves XLA's path a few per cent of a step.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

NEG_INF = -1e30


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


def mla_causal_attention(q_nope, q_rope, k_nope, k_rope, v, seq_lens,
                         q_block: int = 512,
                         scale: Optional[float] = None,
                         skip_masked: bool = False) -> jnp.ndarray:
    """q_nope [B,T,H,dn], q_rope [B,T,H,dr], k_nope [B,T,H,dn], k_rope
    [B,T,1,dr] (one per token, shared by the heads), v [B,T,H,dv].
    Causal, keys past ``seq_lens`` masked. Returns [B,T,H,dv].

    ``skip_masked``: a query block reads only the keys up to its own last
    row (the rest are masked for every one of its rows), blocks unrolled with
    static key lengths: the same sums, half the scores of a long prompt. The
    default scores every key in one ``lax.map`` body (the program the hybrid
    family compiled before the argument existed)."""
    b, t, h, dn = q_nope.shape
    if scale is None:
        scale = (dn + q_rope.shape[-1]) ** -0.5
    qb = q_block if t % q_block == 0 else t
    key_ok = jnp.arange(t)[None, :] < seq_lens[:, None]        # [B, T]

    def block(i0, n_keys=t):
        qn = lax.dynamic_slice_in_dim(q_nope, i0, qb, axis=1)
        qr = lax.dynamic_slice_in_dim(q_rope, i0, qb, axis=1)
        s = (jnp.einsum("bihd,bjhd->bhij", qn, k_nope[:, :n_keys],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bihd,bjd->bhij", qr, k_rope[:, :n_keys, 0],
                          preferred_element_type=jnp.float32)) * scale
        rows = i0 + jnp.arange(qb)[:, None]
        mask = (jnp.arange(n_keys)[None, :] <= rows)[None] \
            & key_ok[:, None, :n_keys]
        s = jnp.where(mask[:, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhij,bjhd->bihd", p.astype(v.dtype),
                          v[:, :n_keys])

    if qb == t:
        return block(0)
    if skip_masked:
        return jnp.concatenate(
            [block(i0, i0 + qb) for i0 in range(0, t, qb)], axis=1)
    out = lax.map(block, jnp.arange(0, t, qb))                 # [nb,B,qb,H,dv]
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, v.shape[-1])


def mla_absorbed_decode(q_nope, q_rope, w_kvb, ctx, n_ctx, side, n_side,
                        rank: int, scale: Optional[float] = None
                        ) -> jnp.ndarray:
    """q_nope [B,H,dn], q_rope [B,H,dr]; w_kvb [rank, H, dn + dv]; ctx
    [B,S,rank+dr] cached rows valid below ``n_ctx`` [B]; side [B,W,rank+dr]
    valid below ``n_side``. Returns [B,H,dv] in q's dtype."""
    dn = q_nope.shape[-1]
    if scale is None:
        scale = (dn + q_rope.shape[-1]) ** -0.5
    w_k, w_v = w_kvb[..., :dn], w_kvb[..., dn:]
    q_abs = jnp.einsum("bhd,chd->bhc", q_nope, w_k,
                       preferred_element_type=jnp.float32).astype(ctx.dtype)
    q_rope = q_rope.astype(ctx.dtype)

    def scores(rows, n_valid):
        s = (jnp.einsum("bhc,bsc->bhs", q_abs, rows[..., :rank],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhr,bsr->bhs", q_rope, rows[..., rank:],
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
    return jnp.einsum("bhc,chd->bhd", o_lat.astype(w_v.dtype), w_v,
                      preferred_element_type=jnp.float32).astype(q_nope.dtype)
