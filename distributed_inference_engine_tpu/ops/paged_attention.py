"""Paged decode attention over the page pool, in XLA.

Attention state lives in a pool of fixed-size HBM pages instead of one
contiguous row per sequence (SURVEY.md §7 hard-part #2), so long and short
sequences share HBM without fragmentation and page recycling replaces
whole-row eviction.

Layout (per layer):

- ``k_pages`` / ``v_pages``: ``[num_pages, page_size, n_kv * head_dim]``.
- ``page_table``: ``[batch, max_pages_per_seq]`` int32 — logical page ``p`` of
  slot ``b`` lives in physical page ``page_table[b, p]``. Unused entries must
  hold a valid page id (0): they are gathered and masked.
- ``lengths``: ``[batch]`` int32 — live tokens per slot, *including* the
  token at the current decode position.

``paged_attention_xla`` is the ``inline`` decode body's attention (sliding-
window specs, ``models.base.forward_decode_paged``) and, with its flash
stats, the prefix half of the reference the in-place kernel is pinned to
(``ops.flash_decode.flash_decode_attention_xla``). The kernel that reads
pages in place is ``ops/flash_decode.py``.
"""

from __future__ import annotations

import jax.numpy as jnp

from .attention import _upcast_fp8

NEG_INF = -1e30


# ----------------------------------------------------------------- XLA path


def paged_attention_xla(
    q: jnp.ndarray,            # [B, H, Dh]
    k_pages: jnp.ndarray,      # [N, P, Hkv * Dh]
    v_pages: jnp.ndarray,      # [N, P, Hkv * Dh]
    page_table: jnp.ndarray,   # [B, MP] int32
    lengths: jnp.ndarray,      # [B] int32
    *,
    n_kv_heads: int,
    window: int = 0,           # sliding-window size (0 = full attention)
    with_stats: bool = False,
    first_rows=None,           # [B] rows below it are masked (None = 0)
):
    """Reference implementation via gather; correct everywhere (CPU tests,
    interpret-mode cross-check), but reads the whole gathered cache through
    XLA's generic scatter/gather path. Returns [B, H, Dh] in q.dtype — or
    (out, m, l) flash stats ([B, H] fp32 each) with ``with_stats`` for
    ``ops.attention.merge_attention`` (a zero-valid row carries l = 0)."""
    b, h, dh = q.shape
    n, p, fused = k_pages.shape
    mp = page_table.shape[1]
    g = h // n_kv_heads

    # gather FIRST, upcast the gathered pages only: upcasting the whole
    # pool would materialize a full wide copy per decode call — the HBM
    # traffic the fp8 cache exists to avoid
    k, v = _upcast_fp8(k_pages[page_table], v_pages[page_table], q.dtype)
    k = k.reshape(b, mp * p, n_kv_heads, dh)      # [B, S, Hkv, Dh]
    v = v.reshape(b, mp * p, n_kv_heads, dh)

    qg = q.reshape(b, n_kv_heads, g, dh)
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k).astype(jnp.float32) * scale
    valid = jnp.arange(mp * p)[None, :] < lengths[:, None]        # [B, S]
    if window:
        valid &= jnp.arange(mp * p)[None, :] >= (lengths[:, None] - window)
    if first_rows is not None:
        valid &= jnp.arange(mp * p)[None, :] >= first_rows[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    m = scores.max(axis=-1)                                       # [B,Hkv,G]
    probs = jnp.exp(scores - m[..., None])
    # zero-valid rows: m == NEG_INF turns every exp into 1 — zero them so
    # l is a true softmax denominator (merge weight 0, not S)
    probs = jnp.where(valid[:, None, None, :], probs, 0.0)
    l = probs.sum(axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(v.dtype), v)
    out = out.astype(jnp.float32) / jnp.maximum(l[..., None], 1e-30)
    out = out.reshape(b, h, dh).astype(q.dtype)
    if with_stats:
        return out, m.reshape(b, h), l.reshape(b, h)
    return out
