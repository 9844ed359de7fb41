"""Token sampling: greedy, temperature, top-k, top-p — all static-shape and
jit/scan-safe so the whole decode loop stays on-device.

The knobs are carried in a ``SamplingParams`` pytree of arrays (not Python
scalars), so one compiled decode program serves every request mix: greedy is
temperature==0, top-k off is k==vocab, top-p off is p==1. No recompilation
when a request changes its sampling settings.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class SamplingParams(NamedTuple):
    """Per-slot sampling knobs, each [B] fp32/int32 arrays."""

    temperature: jnp.ndarray   # 0.0 => greedy
    top_k: jnp.ndarray         # 0 or >= vocab => disabled
    top_p: jnp.ndarray         # 1.0 => disabled
    min_p: jnp.ndarray = None  # 0.0 => disabled; keep p >= min_p * p_max

    @classmethod
    def make(cls, batch: int, temperature=0.0, top_k=0, top_p=1.0,
             min_p=0.0) -> "SamplingParams":
        full = lambda v, dt: jnp.full((batch,), v, dtype=dt)
        return cls(full(temperature, jnp.float32), full(top_k, jnp.int32),
                   full(top_p, jnp.float32), full(min_p, jnp.float32))

    def min_p_or_zeros(self) -> jnp.ndarray:
        """min_p defaults to None so older positional constructions keep
        working; sampling treats None as disabled."""
        if self.min_p is None:
            return jnp.zeros_like(self.temperature)
        return self.min_p


def _mask_topk_topp(scaled: jnp.ndarray, params: SamplingParams
                    ) -> jnp.ndarray:
    """Apply top-k and top-p masks to tempered logits (three O(V log V)
    sorts — only worth running when some row actually uses the knobs)."""
    b, v = scaled.shape
    # ---- top-k mask: keep the k highest (temperature preserves order, so
    # this is identical on raw or scaled logits)
    k = jnp.where(params.top_k <= 0, v, params.top_k)            # [B]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]             # [B, V]
    kth = jnp.take_along_axis(
        sorted_desc, jnp.clip(k - 1, 0, v - 1)[:, None], axis=-1
    )                                                            # [B, 1]
    keep_topk = scaled >= kth

    # ---- top-p (nucleus) mask: smallest prefix of sorted tempered probs
    # covering p
    probs_sorted = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs_sorted, axis=-1)
    # token ranks: position of each logit in the descending sort
    ranks = jnp.argsort(jnp.argsort(-scaled, axis=-1), axis=-1)  # [B, V]
    # keep ranks whose cumulative prob (exclusive) is < p  => always keeps rank 0
    cum_excl = cum - probs_sorted
    keep_sorted = cum_excl < params.top_p[:, None]
    keep_topp = jnp.take_along_axis(keep_sorted, ranks, axis=-1)

    # ---- min-p mask: keep tokens whose tempered prob is at least
    # min_p * max prob. Reuses the sorted softmax above: p_max is its first
    # column and per-token probs come back through the same ranks gather —
    # no second softmax on the decode hot path. Clamped to [0, 1]: an
    # out-of-range client value must not mask the argmax itself (min_p>1
    # would -inf the whole row and sample uniform noise).
    minp = jnp.clip(params.min_p_or_zeros(), 0.0, 1.0)
    probs = jnp.take_along_axis(probs_sorted, ranks, axis=-1)
    keep_minp = (minp[:, None] <= 0.0) | \
        (probs >= minp[:, None] * probs_sorted[:, :1])
    return jnp.where(keep_topk & keep_topp & keep_minp, scaled, -jnp.inf)


def _masked_scaled_logits(logits: jnp.ndarray,
                          params: SamplingParams) -> jnp.ndarray:
    """Temper then mask: the shared front half of every sampling path
    ([N, V] logits, [N] params). One definition so the distribution the
    speculative engine verifies against is bit-identical to the one
    ``sample_tokens`` draws from — including the temperature clamp.

    The mask step costs three [N, V] sorts, so it hides behind a
    ``lax.cond``: the common greedy / pure-temperature batch skips the
    sorts entirely at runtime (one compiled program either way — the
    branch predicate is data).
    """
    temp = jnp.maximum(params.temperature, 1e-6)[:, None]
    scaled = logits / temp
    needs_mask = (jnp.any(params.top_k > 0) | jnp.any(params.top_p < 1.0)
                  | jnp.any(params.min_p_or_zeros() > 0.0))
    return jax.lax.cond(
        needs_mask,
        lambda s: _mask_topk_topp(s, params),
        lambda s: s,
        scaled,
    )


def masked_sampling_probs(logits: jnp.ndarray,
                          params: SamplingParams) -> jnp.ndarray:
    """Tempered, top-k/top-p/min-p-masked, renormalized probabilities.

    This is THE sampling distribution (what ``sample_tokens`` draws from),
    materialized — the speculative engine's acceptance test needs p and q
    as explicit distributions, and masking both with the same request knobs
    makes rejection sampling exact for the knob-modified target
    distribution (VERDICT r1 item 6), not just for plain temperature.

    ``logits`` is [B, V] or [B, P, V] (P scoring positions per row, each
    masked with its row's knobs); params are [B]. Greedy rows (temp 0)
    come back near-one-hot at the argmax — callers keep their explicit
    argmax path for exactness.
    """
    lg = logits.astype(jnp.float32)
    squeeze = lg.ndim == 2
    if squeeze:
        lg = lg[:, None, :]
    b, p, v = lg.shape
    rep = lambda x: jnp.repeat(x, p, axis=0)
    flat = SamplingParams(rep(params.temperature), rep(params.top_k),
                          rep(params.top_p), rep(params.min_p_or_zeros()))
    masked = _masked_scaled_logits(lg.reshape(b * p, v), flat)
    probs = jax.nn.softmax(masked, axis=-1).reshape(b, p, v)
    return probs[:, 0] if squeeze else probs


def sample_tokens(
    logits: jnp.ndarray,        # [B, V] fp32
    params: SamplingParams,
    key: jax.Array,
) -> jnp.ndarray:
    """Sample one token per row. Returns [B] int32.

    Strategy composition: temperature scales, then top-k and top-p masks,
    then a Gumbel-max draw — which avoids materializing a renormalized
    distribution. Greedy rows (temperature 0) take an argmax on the
    *masked* logits, so greedy + top-k interact correctly.

    The mask step costs three [B, V] sorts, so it hides behind a
    ``lax.cond``: the common greedy / pure-temperature batch skips the
    sorts entirely at runtime (one compiled program either way — the
    branch predicate is data).
    """
    b, v = logits.shape
    logits = logits.astype(jnp.float32)

    # temperature FIRST (HF semantics): nucleus membership is judged on
    # the tempered distribution, so high temperature widens the nucleus
    masked = _masked_scaled_logits(logits, params)

    # ---- Gumbel-max draw on the masked tempered logits
    gumbel = -jnp.log(-jnp.log(jax.random.uniform(key, (b, v), minval=1e-20, maxval=1.0)))
    stochastic = jnp.argmax(masked + gumbel, axis=-1)
    greedy = jnp.argmax(masked, axis=-1)
    return jnp.where(params.temperature <= 0.0, greedy, stochastic).astype(jnp.int32)


def sample_tokens_with_logprobs(
    logits: jnp.ndarray,        # [B, V] fp32
    params: SamplingParams,
    key: jax.Array,
) -> tuple:
    """``sample_tokens`` plus the chosen token's UNTEMPERED log-probability
    ([B] fp32) — the quantity scoring/confidence APIs report (log p under
    the model, independent of the sampling knobs used to pick the token)."""
    with jax.named_scope("sample"):
        toks = sample_tokens(logits, params, key)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        chosen = jnp.take_along_axis(logp, toks[:, None].astype(jnp.int32),
                                     axis=-1)[:, 0]
        return toks, chosen
