"""Attention ops for prefill and decode, XLA-native.

Replaces the reference's compute kernel — an ``asyncio.sleep``
(``src/mock_models/fake_model.py:47``) — with the real thing. Two entry
points matching the two serving phases:

- ``causal_attention``: prefill over the freshly computed K/V of the prompt
  (no history exists yet, so attending over the full cache would waste
  HBM bandwidth reading empty pages).
- ``cached_attention``: decode, one query token per slot against the
  HBM-resident KV cache, masked by each slot's live length.

Both are einsum/softmax chains: XLA fuses mask+softmax+matmul well on the
MXU at the uniform families' prompt lengths (<= 768 rows in the cells).
The Pallas kernels take over where a chain would send its scores through
HBM: ``ops/flash_decode.py`` when the cache is paged, and
``ops/flash_prefill.py`` for the long prefills of the families whose cache
rows are K|V. ``band_attention_blocked`` here is that prefill's ONE XLA
body (the CPU, a ``T`` of no whole blocks): grouped-query, causal or
banded, query blocks unrolled.

GQA layout note: K/V carry ``n_kv_heads``; queries carry ``n_heads``. We
reshape Q to [B, T, n_kv, group, Dh] and broadcast K/V across the group dim —
no materialized repeat, XLA keeps it as an indexing pattern.
"""

from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30   # large-but-finite: -inf rows would softmax to NaN

_FP8 = ("float8_e4m3fn", "float8_e5m2")


def _upcast_fp8(k: jnp.ndarray, v: jnp.ndarray, dt) -> tuple:
    """fp8 KV caches (half the KV HBM of bf16) have no implicit promotion
    path — upcast to the query dtype at the attention boundary. Wider
    caches (fp32 kv under bf16 compute) keep their implicit promotion."""
    if k.dtype.name in _FP8:
        return k.astype(dt), v.astype(dt)
    return k, v


def _group_query(q: jnp.ndarray, n_kv_heads: int) -> jnp.ndarray:
    """[B, T, H, Dh] -> [B, T, Hkv, G, Dh] where H = Hkv * G."""
    b, t, h, d = q.shape
    return q.reshape(b, t, n_kv_heads, h // n_kv_heads, d)


def causal_attention(
    q: jnp.ndarray,          # [B, T, H, Dh]
    k: jnp.ndarray,          # [B, T, Hkv, Dh]
    v: jnp.ndarray,          # [B, T, Hkv, Dh]
    seq_lens: jnp.ndarray,   # [B] valid prompt lengths (right-padded batches)
    window: int = 0,         # sliding-window size (0 = full causal)
) -> jnp.ndarray:
    """Prefill attention: causal within the prompt, padding masked out.

    Returns [B, T, H, Dh].
    """
    b, t, h, dh = q.shape
    n_kv = k.shape[2]
    k, v = _upcast_fp8(k, v, q.dtype)
    qg = _group_query(q, n_kv)                                   # [B,T,Hkv,G,Dh]
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    # scores: [B, Hkv, G, T, T]
    scores = jnp.einsum("bikgd,bjkd->bkgij", qg, k).astype(jnp.float32) * scale
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    causal = j <= i                                              # [T, T]
    if window:
        causal &= (i - j) < window                               # Mistral SWA
    valid = jnp.arange(t)[None, :] < seq_lens[:, None]           # [B, T] keys in-prompt
    mask = causal[None, :, :] & valid[:, None, :]                # [B, T, T]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgij,bjkd->bikgd", probs.astype(v.dtype), v)
    return out.reshape(b, t, h, dh)


def band_attention_blocked(
    q: jnp.ndarray,          # [B, T, H, Dh]
    k: jnp.ndarray,          # [B, T, Hkv, Dh]
    v: jnp.ndarray,          # [B, T, Hkv, Dh]
    seq_lens: jnp.ndarray,   # [B]
    window: int = 0,         # row i sees i - window < j <= i (0 = causal)
    q_block: int = 512,
) -> jnp.ndarray:
    """Grouped-query prefill attention in query blocks of ``q_block`` rows
    (one block where ``T`` is no whole number of them), unrolled, each
    reading ONLY the keys its rows can see: up to its own last row and,
    with a ``window``, from ``window - 1`` rows before its first. A key
    block wholly outside the band is not multiplied, not masked after the
    fact: a windowed layer's work grows with ``T * window``, not ``T^2``,
    and no ``[T, T]`` score tensor exists. K/V are broadcast across the
    group as an indexing pattern. Returns [B, T, H, Dh]; rows past
    ``seq_lens`` are not specified."""
    b, t, h, dh = q.shape
    n_kv = k.shape[2]
    qb = q_block if t % q_block == 0 else t
    qg = _group_query(q, n_kv)                              # [B,T,Hkv,G,Dh]
    key_ok = jnp.arange(t)[None, :] < seq_lens[:, None]     # [B, T]
    scale = dh ** -0.5

    def block(i0):
        k0 = max(0, i0 - window + 1) if window else 0
        k1 = i0 + qb
        s = jnp.einsum("bikgd,bjkd->bkgij", qg[:, i0:k1], k[:, k0:k1],
                       preferred_element_type=jnp.float32) * scale
        rows = i0 + jnp.arange(qb)[:, None]
        keys = k0 + jnp.arange(k1 - k0)[None, :]
        mask = keys <= rows
        if window:
            mask &= rows - keys < window
        mask = mask[None] & key_ok[:, None, k0:k1]           # [B, qb, nk]
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        p = jnp.exp(s - s.max(axis=-1, keepdims=True))
        p = p / p.sum(axis=-1, keepdims=True)
        o = jnp.einsum("bkgij,bjkd->bikgd", p.astype(v.dtype), v[:, k0:k1])
        return o.reshape(b, qb, h, dh)

    return jnp.concatenate([block(i0) for i0 in range(0, t, qb)], axis=1)


def suffix_attention(
    q: jnp.ndarray,            # [B, Ts, H, Dh] suffix queries
    k_ctx: jnp.ndarray,        # [B, Tc, Hkv, Dh] cached-context keys (padded)
    v_ctx: jnp.ndarray,        # [B, Tc, Hkv, Dh]
    n_ctx: jnp.ndarray,        # [B] valid context length per row
    k_suf: jnp.ndarray,        # [B, Ts, Hkv, Dh] fresh suffix keys
    v_suf: jnp.ndarray,        # [B, Ts, Hkv, Dh]
    suffix_lens: jnp.ndarray,  # [B] valid suffix length per row
    window: int = 0,           # sliding-window size (0 = full causal)
) -> jnp.ndarray:
    """Prefill of a prompt SUFFIX against cached prefix KV (prefix cache
    hit, ``engine/paged_kv.py``): suffix query i (absolute position
    n_ctx+i) attends to every valid context key and causally within the
    suffix. Returns [B, Ts, H, Dh]."""
    b, ts, h, dh = q.shape
    tc = k_ctx.shape[1]
    n_kv = k_ctx.shape[2]
    k_ctx, v_ctx = _upcast_fp8(k_ctx, v_ctx, q.dtype)
    k_suf, v_suf = _upcast_fp8(k_suf, v_suf, q.dtype)
    qg = _group_query(q, n_kv)                                   # [B,Ts,Hkv,G,Dh]
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    k_all = jnp.concatenate([k_ctx, k_suf], axis=1)              # [B,Tc+Ts,...]
    v_all = jnp.concatenate([v_ctx, v_suf], axis=1)
    scores = jnp.einsum("bikgd,bjkd->bkgij", qg, k_all).astype(jnp.float32) * scale
    i = jnp.arange(ts)[:, None]                                  # query idx
    j = jnp.arange(tc + ts)[None, :]                             # key idx
    # context keys: valid iff j < n_ctx; suffix keys: causal AND < suffix_len
    in_ctx = (j < tc)
    suf_j = j - tc                                               # suffix-local key idx
    causal = suf_j <= i                                          # [Ts, Tc+Ts]
    mask_ctx = in_ctx & (j < n_ctx[:, None, None])               # [B,1,Tc+Ts] w/ i broadcast
    mask_suf = (~in_ctx) & causal[None, :, :] & \
        (suf_j[None, :, :] < suffix_lens[:, None, None])
    mask = mask_ctx | mask_suf                                   # [B, Ts, Tc+Ts]
    if window:
        # absolute positions: query = n_ctx + i; ctx key = j; suffix key =
        # n_ctx + suf_j — the query sees only the last `window` positions
        q_abs = n_ctx[:, None, None] + i[None, :, :]             # [B, Ts, 1]
        k_abs = jnp.where(in_ctx, j, n_ctx[:, None, None] + suf_j)
        mask &= (q_abs - k_abs) < window
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgij,bjkd->bikgd", probs.astype(v_all.dtype), v_all)
    return out.reshape(b, ts, h, dh).astype(q.dtype)   # see cached_attention


def window_decode_attention(
    q: jnp.ndarray,          # [B, H, Dh] decode queries
    k_side: jnp.ndarray,     # [B, W, Hkv, Dh] chunk side-window keys
    v_side: jnp.ndarray,     # [B, W, Hkv, Dh]
    n_valid: jnp.ndarray,    # [B] valid side entries per slot
) -> tuple:
    """Decode attention over the chunk's dense side window, returning the
    normalized output PLUS its flash-style stats (row max ``m`` and
    softmax denominator ``l``, both [B, H] fp32) so the caller can merge
    it with the paged-prefix partial via ``merge_attention``.

    This is half of the windowed decode scheme (``models.base
    .forward_decode_window``): during a decode chunk the page pools are
    frozen and fresh K/V accumulates here — the per-step pool scatter it
    replaces cost ~45 ms/step at 8B bs64 (XLA scatter lowering), which
    held the paged engine at ~28% of dense-engine throughput.
    """
    b, h, dh = q.shape
    w = k_side.shape[1]
    n_kv = k_side.shape[2]
    k_side, v_side = _upcast_fp8(k_side, v_side, q.dtype)
    qg = q.reshape(b, n_kv, h // n_kv, dh)
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bwkd->bkgw", qg, k_side).astype(jnp.float32)
    scores = scores * scale
    valid = jnp.arange(w)[None, :] < n_valid[:, None]            # [B, W]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    m = scores.max(axis=-1)                                      # [B,Hkv,G]
    probs = jnp.exp(scores - m[..., None])
    # all-invalid rows: m == NEG_INF makes every exp() equal 1 — zero them
    # so l is a true denominator (their merge weight must be 0, not W)
    probs = jnp.where(valid[:, None, None, :], probs, 0.0)
    l = probs.sum(axis=-1)
    out = jnp.einsum("bkgw,bwkd->bkgd", probs.astype(v_side.dtype), v_side)
    out = out.astype(jnp.float32) / jnp.maximum(l[..., None], 1e-30)
    return (out.reshape(b, h, dh).astype(q.dtype),
            m.reshape(b, h), l.reshape(b, h))


def merge_attention(parts, dtype=None) -> jnp.ndarray:
    """Combine flash-style partial attentions over DISJOINT key sets.

    ``parts`` is a list of (out [B, H, Dh] normalized, m [B, H], l [B, H])
    as produced by ``window_decode_attention`` / ``ops.paged_attention``
    with stats: softmax over the union of key sets equals the l·e^{m-m*}
    -weighted average of the partial outputs. A part with no valid keys
    carries l = 0 (and m = NEG_INF) and contributes nothing.
    """
    m_tot = parts[0][1]
    for _, m, _ in parts[1:]:
        m_tot = jnp.maximum(m_tot, m)
    num = 0.0
    den = 0.0
    for out, m, l in parts:
        wgt = l * jnp.exp(m - m_tot)                             # [B, H]
        num = num + out.astype(jnp.float32) * wgt[..., None]
        den = den + wgt
    out = num / jnp.maximum(den, 1e-30)[..., None]
    return out.astype(dtype or parts[0][0].dtype)


def cached_attention(
    q: jnp.ndarray,          # [B, 1, H, Dh] decode queries
    cache_k: jnp.ndarray,    # [B, S, Hkv, Dh] full HBM cache rows
    cache_v: jnp.ndarray,    # [B, S, Hkv, Dh]
    lengths: jnp.ndarray,    # [B] live length per slot (incl. the new token)
    window: int = 0,         # sliding-window size (0 = full attention)
) -> jnp.ndarray:
    """Decode attention against the KV cache, masked to each slot's live
    prefix. Returns [B, 1, H, Dh]."""
    b, t, h, dh = q.shape
    s = cache_k.shape[1]
    n_kv = cache_k.shape[2]
    cache_k, cache_v = _upcast_fp8(cache_k, cache_v, q.dtype)
    qg = _group_query(q, n_kv)                                   # [B,1,Hkv,G,Dh]
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    scores = jnp.einsum("bikgd,bjkd->bkgij", qg, cache_k).astype(jnp.float32) * scale
    valid = jnp.arange(s)[None, :] < lengths[:, None]            # [B, S]
    if window:
        # query sits at position lengths-1; only keys within the window
        valid &= jnp.arange(s)[None, :] >= (lengths[:, None] - window)
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgij,bjkd->bikgd", probs.astype(cache_v.dtype), cache_v)
    # query dtype out: the KV cache may be wider/narrower than the compute
    # dtype (EngineConfig.kv_dtype), and the residual stream must not
    # change dtype mid-scan (carry mismatch)
    return out.reshape(b, t, h, dh).astype(q.dtype)
