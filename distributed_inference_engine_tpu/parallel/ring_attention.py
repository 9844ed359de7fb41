"""Ring attention: causal attention with the sequence sharded over the ``sp``
mesh axis (long-context serving, SURVEY.md §5 long-context row).

Nothing in the reference scales with sequence length (its inputs are opaque
echoes), so this is capability-extension scoped by the build plan (SURVEY.md
§7 step 7): each device holds one sequence block of Q/K/V; K/V blocks rotate
around the ring via ``lax.ppermute`` (XLA lowers to ICI neighbor transfers)
while each device accumulates its Q block's attention with an online-softmax
(flash-attention style) running max/denominator — so the full [T, T] score
matrix never materializes and HBM per chip stays O(T/sp).

Causality across blocks falls out of absolute positions: block ownership
gives every K/V rotation step a position offset, and steps whose entire block
is in the future contribute nothing (masked to -inf, zero accumulated).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import NEG_INF


def _ring_body(q, k, v, seq_lens, *, axis: str, n_kv_heads: int,
               window: int = 0):
    """Per-device body: q/k/v are LOCAL blocks [B, Tl, H|Hkv, Dh]."""
    b, tl, h, dh = q.shape
    g = h // n_kv_heads
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)

    qg = q.reshape(b, tl, n_kv_heads, g, dh)
    q_pos = idx * tl + jnp.arange(tl)                              # [Tl]

    # online-softmax state per (batch, head-group, query); marked
    # device-varying over the ring axis so the loop carry types match (the
    # accumulators genuinely diverge per device from step 0)
    def _vary(x):
        return lax.pcast(x, axis, to="varying")

    m = _vary(jnp.full((b, n_kv_heads, g, tl), NEG_INF, dtype=jnp.float32))
    l = _vary(jnp.zeros((b, n_kv_heads, g, tl), dtype=jnp.float32))
    acc = _vary(jnp.zeros((b, tl, n_kv_heads, g, dh), dtype=jnp.float32))

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(s, carry):
        m, l, acc, k_blk, v_blk = carry
        owner = (idx - s) % n                                      # whose block we hold
        k_pos = owner * tl + jnp.arange(tl)                        # [Tl]
        scores = jnp.einsum(
            "bikgd,bjkd->bkgij", qg, k_blk
        ).astype(jnp.float32) * scale                              # [B,Hkv,G,Tl,Tl]
        mask = k_pos[None, :] <= q_pos[:, None]                    # [Tl, Tl] causal
        if window:
            # sliding window, same convention as ops.attention
            # .causal_attention ((i - j) < window): absolute positions make
            # the mask rotation-invariant — each step just masks the block
            # it happens to hold. Blocks wholly outside every query's
            # window accumulate zero (their rotation still runs; a
            # skip-if-far optimization would save ICI hops only when
            # window << T/sp, not worth divergent control flow here).
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        if seq_lens is not None:
            mask = mask[None] & (k_pos[None, None, :] < seq_lens[:, None, None])
            mask = mask[:, None, None]                             # [B,1,1,Tl,Tl]
        else:
            mask = mask[None, None, None]
        scores = jnp.where(mask, scores, NEG_INF)

        blk_max = scores.max(axis=-1)                              # [B,Hkv,G,Tl]
        new_m = jnp.maximum(m, blk_max)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(scores - new_m[..., None])                     # [B,Hkv,G,Tl,Tl]
        # fully-masked rows: p is exp(NEG_INF - NEG_INF) = 1 — zero them
        p = jnp.where(mask, p, 0.0)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgij,bjkd->bikgd", p, v_blk.astype(jnp.float32))
        acc = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        m = new_m
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        return m, l, acc, k_blk, v_blk

    m, l, acc, _, _ = lax.fori_loop(
        0, n, step, (m, l, acc, k, v), unroll=True
    )
    denom = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    out = (acc / denom).astype(q.dtype)
    return out.reshape(b, tl, h, dh)


def ring_attention(
    q: jnp.ndarray,           # [B, T, H, Dh]  (global view)
    k: jnp.ndarray,           # [B, T, Hkv, Dh]
    v: jnp.ndarray,           # [B, T, Hkv, Dh]
    mesh: Mesh,
    seq_lens: Optional[jnp.ndarray] = None,   # [B] valid lengths
    axis: str = "sp",
    window: int = 0,          # sliding-window size (0 = full causal)
) -> jnp.ndarray:
    """Causal (optionally length-masked, optionally sliding-window)
    attention with T sharded over ``axis``. Requires T % axis_size == 0.
    Returns [B, T, H, Dh] with the same sequence sharding."""
    n_kv = k.shape[2]
    body = functools.partial(_ring_body, axis=axis, n_kv_heads=n_kv,
                             window=window)
    seq_spec = P(None, axis, None, None)
    in_specs = (seq_spec, seq_spec, seq_spec)
    if seq_lens is not None:
        in_specs = in_specs + (P(),)
        args = (q, k, v, seq_lens)
        fn = body
    else:
        args = (q, k, v)
        fn = lambda q_, k_, v_: body(q_, k_, v_, None)
    return shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=seq_spec,
    )(*args)
