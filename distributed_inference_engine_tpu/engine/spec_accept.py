"""Speculative-decoding acceptance math (Leviathan et al. / Chen et al.
rejection sampling) of the synchronous ``SpeculativeEngine``
(``engine/speculative.py``).

Two exactness contracts hang off this module, both pinned by tests:

1. **r5 parity.** ``rejection_accept`` is the r5 ``_round_core``
   acceptance block verbatim — same op order, same key usage
   (tests/test_speculative.py pins this against a frozen copy).
2. **Greedy chain identity.** For greedy rows the accept rule is
   ``argmax p_j == d_j`` and the final token is ``argmax`` of the
   final distribution, so the emitted run is token-for-token the
   target's own greedy chain regardless of WHAT the draft proposed.

Per-row ``valid`` masks let a batch mix rows with fewer (or no) draft
columns: a row with an all-False mask and ZERO ``q_probs`` drives the
residual ``max(p - q, 0)`` to exactly ``p``, so its "final" token is a
plain sample from the target distribution.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.sampling import SamplingParams, masked_sampling_probs


def draft_sample(q_logits: jnp.ndarray, sampling: SamplingParams,
                 greedy: jnp.ndarray, key: jax.Array
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One draft proposal: sample from the knob-MODIFIED draft
    distribution (``masked_sampling_probs``) so the proposal stays inside
    the target's support; greedy rows take the raw argmax (exactly the r5
    propose step). Returns (token [B] int32, q_probs [B, V])."""
    probs = masked_sampling_probs(q_logits, sampling)
    d_samp = jax.random.categorical(
        key, jnp.log(jnp.maximum(probs, 1e-30)), axis=-1)
    greedy1 = greedy[:, 0] if greedy.ndim == 2 else greedy
    d_tok = jnp.where(greedy1, q_logits.argmax(-1), d_samp)
    return d_tok.astype(jnp.int32), probs


def rejection_accept(
    p_probs: jnp.ndarray,      # [B, k+1, V] knob-modified target probs
    q_probs: jnp.ndarray,      # [B, k, V] knob-modified draft probs
    drafts: jnp.ndarray,       # [B, k] int32 proposed tokens
    greedy: jnp.ndarray,       # [B] (or [B, 1]) bool: temperature <= 0
    key_resid: jax.Array,      # acceptance uniforms (r5 key order)
    key_bonus: jax.Array,      # bonus/residual categorical draw
    valid: Optional[jnp.ndarray] = None,   # [B, k] bool draft-column mask
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Rejection-sampling acceptance over one verify window.

    Greedy rows accept while ``argmax p_j == d_j``; sampled rows accept
    ``d_j`` with probability ``min(1, p_j[d_j]/q_j[d_j])`` and the first
    rejection resamples from ``norm(max(p - q, 0))`` (falling back to
    ``p`` when the residual is degenerate). All-accepted rows draw a
    bonus token from ``p_k``. Both p and q must already be the
    knob-modified distributions (``masked_sampling_probs``) — identical
    masking is what makes the ratio exact for the request's settings.

    ``valid`` force-rejects masked columns BEFORE the
    cumulative-run product, so a row with zero valid columns lands on
    ``n_acc == 0`` with its final drawn from position 0 — the plain
    decode sample when its ``q_probs`` row is zeros (see module doc).

    Returns ``(n_acc [B] int32, final [B] int32, accept [B, k] bool)``;
    the emitted run is ``drafts[:, :n_acc]`` then ``final``.
    """
    b, k = drafts.shape
    bidx = jnp.arange(b)
    greedy2 = greedy if greedy.ndim == 2 else greedy[:, None]   # [B, 1]

    p_at_d = jnp.take_along_axis(
        p_probs[:, :k], drafts[:, :, None], axis=-1)[..., 0]
    q_at_d = jnp.take_along_axis(
        q_probs, drafts[:, :, None], axis=-1)[..., 0]
    u = jax.random.uniform(key_resid, drafts.shape)
    acc_samp = u * q_at_d < p_at_d
    acc_greedy = p_probs[:, :k].argmax(-1) == drafts
    accept = jnp.where(greedy2, acc_greedy, acc_samp)           # [B, k]
    if valid is not None:
        accept = accept & valid
    acc_run = jnp.cumprod(accept.astype(jnp.int32), axis=1)
    n_acc = acc_run.sum(axis=1)                                 # [B] 0..k

    # final token: bonus sample from p_k when all accepted, else resample
    # from the residual at the first rejected position
    all_acc = n_acc == k
    pos_r = jnp.minimum(n_acc, k - 1)
    p_rej = p_probs[bidx, pos_r]                                # [B, V]
    q_rej = q_probs[bidx, pos_r]
    resid = jnp.maximum(p_rej - q_rej, 0.0)
    resid_sum = resid.sum(-1, keepdims=True)
    # degenerate residual (q covers p): fall back to p
    resid = jnp.where(resid_sum > 1e-9, resid, p_rej)
    resid = resid / resid.sum(-1, keepdims=True)
    p_bonus = p_probs[bidx, jnp.int32(k)]
    final_dist = jnp.where(all_acc[:, None], p_bonus, resid)
    f_samp = jax.random.categorical(
        key_bonus, jnp.log(jnp.maximum(final_dist, 1e-30)), axis=-1)
    final = jnp.where(greedy2[:, 0], final_dist.argmax(-1), f_samp)
    return n_acc.astype(jnp.int32), final.astype(jnp.int32), accept
