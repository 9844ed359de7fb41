"""Speculative decoding tests (engine/speculative.py).

Correctness bar: greedy speculative output is TOKEN-FOR-TOKEN the target
engine's own greedy chain for any draft and any k — speculation may only
change latency, never content. Acceptance math is validated with
draft == target (everything must be accepted)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_engine_tpu.config import EngineConfig, ModelConfig
from distributed_inference_engine_tpu.engine.engine import Engine
from distributed_inference_engine_tpu.engine.spec_accept import (
    rejection_accept,
)
from distributed_inference_engine_tpu.engine.speculative import (
    SpeculativeEngine,
)
from distributed_inference_engine_tpu.engine.types import GenerationRequest
from distributed_inference_engine_tpu.models import engine_from_config
from distributed_inference_engine_tpu.models.base import init_params
from distributed_inference_engine_tpu.models.llama import llama_spec

SPEC = llama_spec("llama-tiny", max_seq_len=128)
DRAFT = llama_spec("llama-tiny", max_seq_len=128, n_layers=2, d_model=128,
                   n_heads=4, n_kv_heads=2, d_ff=256)


def _cfg():
    return EngineConfig(max_slots=4, max_seq_len=128)


def _reqs():
    return [
        GenerationRequest(prompt=[1, 2, 3, 4, 5], max_new_tokens=16,
                          temperature=0.0, request_id="a"),
        GenerationRequest(prompt=[9, 8, 7], max_new_tokens=12,
                          temperature=0.0, request_id="b"),
    ]


@pytest.fixture(scope="module")
def params():
    return init_params(SPEC, jax.random.key(0))


@pytest.mark.parametrize("k", [1, 3, 4])
def test_greedy_speculative_matches_plain_engine(params, k):
    base = {r.request_id: r.tokens
            for r in Engine(SPEC, params=params, config=_cfg()
                            ).generate(_reqs())}
    se = SpeculativeEngine(SPEC, DRAFT, params=params, config=_cfg(),
                           speculate_k=k)
    out = {r.request_id: r.tokens for r in se.generate(_reqs())}
    assert out == base


def test_identical_draft_accepts_everything(params):
    k, n = 4, 20
    se = SpeculativeEngine(SPEC, SPEC, params=params, draft_params=params,
                           config=_cfg(), speculate_k=k)
    se.generate([GenerationRequest(prompt=[1, 2, 3, 4, 5],
                                   max_new_tokens=n, temperature=0.0)])
    m = se.get_metrics()
    # an identical draft never suffers a REAL rejection — the only loss
    # is the final round's clip at max_new_tokens, at most k-1 proposals.
    # Derive the bound from the observed round count instead of a fixed
    # 0.95: k=4, n=20 legitimately lands on 15/16 = 0.9375 accepted.
    rounds = m["rounds"]
    proposed = rounds * k
    assert m["draft_acceptance_rate"] >= (proposed - (k - 1)) / proposed
    # full-acceptance throughput: k+1 tokens per round until the clip
    rounds_ceiling = -(-n // (k + 1)) + 1
    assert rounds <= rounds_ceiling
    assert m["tokens_per_round"] >= n / rounds_ceiling


def test_eos_respected(params):
    # find the greedy chain, then set eos to its third token
    base = Engine(SPEC, params=params, config=_cfg()).generate(
        [GenerationRequest(prompt=[1, 2, 3, 4, 5], max_new_tokens=10,
                           temperature=0.0)])[0].tokens
    eos = base[2]
    se = SpeculativeEngine(SPEC, SPEC, params=params, draft_params=params,
                           config=_cfg(), speculate_k=4)
    out = se.generate([GenerationRequest(prompt=[1, 2, 3, 4, 5],
                                         max_new_tokens=10,
                                         temperature=0.0, eos_id=eos)])[0]
    assert out.tokens == base[:3]
    assert out.finish_reason == "stop"


def test_sampled_mode_runs_and_respects_max_new(params):
    se = SpeculativeEngine(SPEC, DRAFT, params=params, config=_cfg(),
                           speculate_k=3, seed=7)
    outs = se.generate([GenerationRequest(prompt=[4, 5, 6],
                                          max_new_tokens=9,
                                          temperature=0.9,
                                          request_id=f"s{i}")
                        for i in range(3)])
    for r in outs:
        assert len(r.tokens) == 9
        assert all(0 <= t < SPEC.vocab_size for t in r.tokens)


def test_topk1_sampled_matches_greedy_chain(params):
    """Knob exactness (VERDICT r1 item 6): top_k=1 with temperature > 0
    makes the knob-modified target distribution one-hot at the argmax, so
    speculative output must be deterministically the same chain the static
    engine produces for the same knobs — for any draft (accepted proposals
    in-support, rejections resampled from the one-hot residual)."""
    req = lambda: GenerationRequest(prompt=[1, 2, 3, 4, 5],
                                    max_new_tokens=14, temperature=0.8,
                                    top_k=1)
    base = Engine(SPEC, params=params, config=_cfg()).generate(
        [req()])[0].tokens
    se = SpeculativeEngine(SPEC, DRAFT, params=params, config=_cfg(),
                           speculate_k=3, seed=11)
    assert se.generate([req()])[0].tokens == base


def test_topp_masks_target_support(params):
    """A tiny top_p must confine sampled output to the nucleus: every
    emitted token has to be one the static sampler could emit. Checked
    against the masked target distribution position by position."""
    import jax.numpy as jnp

    from distributed_inference_engine_tpu.models.base import (
        forward_prefill, unembed,
    )
    from distributed_inference_engine_tpu.ops.sampling import (
        SamplingParams, masked_sampling_probs,
    )

    se = SpeculativeEngine(SPEC, DRAFT, params=params, config=_cfg(),
                           speculate_k=3, seed=3)
    prompt = [1, 2, 3, 4, 5]
    knobs = dict(temperature=0.9, top_p=0.3)
    out = se.generate([GenerationRequest(prompt=prompt, max_new_tokens=8,
                                         **knobs)])[0].tokens
    sp = SamplingParams.make(1, **knobs)
    ctx = list(prompt)
    for tok in out:
        toks = jnp.asarray([ctx], jnp.int32)
        lens = jnp.asarray([len(ctx)], jnp.int32)
        hid, _, _ = forward_prefill(SPEC, params, toks, lens)
        logits = unembed(SPEC, params, hid[:, len(ctx) - 1])
        probs = masked_sampling_probs(logits, sp)
        assert float(probs[0, tok]) > 0.0, \
            f"token {tok} outside the top-p nucleus"
        ctx.append(tok)


def test_vocab_mismatch_rejected(params):
    bad = llama_spec("llama-tiny", max_seq_len=128, vocab_size=999)
    with pytest.raises(ValueError, match="vocab"):
        SpeculativeEngine(SPEC, bad, params=params, config=_cfg())


def test_engine_from_config_speculative():
    cfg = ModelConfig(
        name="s", architecture="llama", dtype="float32", max_seq_len=64,
        max_batch_size=2,
        metadata={"size": "llama-tiny", "speculative": 3,
                  "draft_size": "llama-tiny"},
    )
    eng = engine_from_config(cfg)
    assert isinstance(eng, SpeculativeEngine)
    out = eng.generate([GenerationRequest(prompt=[1, 2, 3],
                                          max_new_tokens=5)])
    assert len(out[0].tokens) == 5
    assert eng.get_metrics()["speculate_k"] == 3


def test_truncated_draft_greedy_parity_and_acceptance():
    """Draft = the target's own first layers (VERDICT r2 item 4): output
    stays token-for-token the target's greedy chain (the speculative
    invariant), and the shared structure yields nonzero acceptance even
    at random init — the property an independent random draft lacks."""
    from distributed_inference_engine_tpu.engine.speculative import (
        truncated_draft,
    )

    params = init_params(SPEC, jax.random.key(0))
    d_spec, d_params = truncated_draft(SPEC, params, 2)
    assert d_spec.n_layers == 2
    assert d_params["blocks"]["wq"].shape[0] == 2
    assert d_params["tok_emb"] is params["tok_emb"]       # shared, no copy
    eng = SpeculativeEngine(SPEC, d_spec, params=params,
                            draft_params=d_params, config=_cfg(),
                            speculate_k=3)
    ref = Engine(SPEC, params=params, config=_cfg())
    out_s = {r.request_id: r.tokens for r in eng.generate(_reqs())}
    out_r = {r.request_id: r.tokens for r in ref.generate(_reqs())}
    assert out_s == out_r
    assert eng.get_metrics()["draft_acceptance_rate"] > 0.0


def test_truncated_draft_quantized_tree():
    """QuantizedTensor leaves slice payload and scales together."""
    from distributed_inference_engine_tpu.engine.speculative import (
        truncated_draft,
    )
    from distributed_inference_engine_tpu.ops.quant import (
        quantize_params,
        QuantizedTensor,
    )

    qparams = quantize_params(SPEC, init_params(SPEC, jax.random.key(1)))
    d_spec, d_params = truncated_draft(SPEC, qparams, 3)
    wq = d_params["blocks"]["wq"]
    assert isinstance(wq, QuantizedTensor)
    assert wq.q.shape[0] == 3 and wq.s.shape[0] == 3
    with pytest.raises(ValueError, match="draft layers"):
        truncated_draft(SPEC, qparams, SPEC.n_layers)


def test_scale_top_blocks_eps0_matches_draft_logits():
    """eps=0 makes every block above n_shared an exact identity on the
    residual stream: full-model logits == truncated-draft logits, so
    greedy acceptance is exactly 1 — the sweep's ceiling anchor."""
    import numpy as np

    from distributed_inference_engine_tpu.engine.speculative import (
        scale_top_blocks,
        truncated_draft,
    )
    from distributed_inference_engine_tpu.models.base import (
        forward_train,
        init_params,
    )

    import jax.numpy as jnp

    params = init_params(SPEC, jax.random.key(9))
    d_spec, d_params = truncated_draft(SPEC, params, 1)
    tp = scale_top_blocks(SPEC, params, 1, 0.0)
    toks = jnp.asarray([[1, 2, 3, 4, 5, 6]], jnp.int32)
    lens = jnp.full((1,), 6, jnp.int32)
    full = np.asarray(forward_train(SPEC, tp, toks, lens))
    draft = np.asarray(forward_train(d_spec, d_params, toks, lens))
    np.testing.assert_allclose(full, draft, rtol=1e-5, atol=1e-5)

    # eps>0 must diverge (the construction is not degenerate)
    tp2 = scale_top_blocks(SPEC, params, 1, 0.5)
    full2 = np.asarray(forward_train(SPEC, tp2, toks, lens))
    assert np.abs(full2 - draft).max() > 1e-3


def test_scale_top_blocks_quantized_scales_only():
    """Quantized trees scale only the per-channel scale arrays — the
    payload is shared with the base tree (no second 8-GB copy)."""
    from distributed_inference_engine_tpu.engine.speculative import (
        scale_top_blocks,
    )
    from distributed_inference_engine_tpu.ops.quant import (
        random_quantized_params,
    )

    base = random_quantized_params(SPEC, jax.random.key(1))
    tp = scale_top_blocks(SPEC, base, 1, 0.25)
    assert tp["blocks"]["wo"].q is base["blocks"]["wo"].q
    import numpy as np

    s0 = np.asarray(base["blocks"]["wo"].s)
    s1 = np.asarray(tp["blocks"]["wo"].s)
    np.testing.assert_allclose(s1[:1], s0[:1])
    np.testing.assert_allclose(s1[1:], s0[1:] * 0.25)


# ---------------------------------------------------------------------------
# acceptance math (engine/spec_accept.py): bit-parity against a frozen r5
# reference
# ---------------------------------------------------------------------------


def _frozen_r5_accept(p, q, drafts, greedy, key_resid, key_bonus,
                      valid=None):
    """Independent numpy reimplementation of the r5 acceptance block
    (frozen at the refactor): loop form, same key usage and op order as
    the pre-refactor ``_round_core``. Any drift in the shared module
    shows up as a bit mismatch here."""
    b, k = drafts.shape
    u = np.asarray(jax.random.uniform(key_resid, drafts.shape))
    accept = np.zeros((b, k), bool)
    for i in range(b):
        for j in range(k):
            d = int(drafts[i, j])
            if greedy[i]:
                accept[i, j] = int(np.argmax(p[i, j])) == d
            else:
                accept[i, j] = u[i, j] * q[i, j, d] < p[i, j, d]
            if valid is not None and not valid[i, j]:
                accept[i, j] = False
    n_acc = np.zeros(b, np.int32)
    for i in range(b):
        while n_acc[i] < k and accept[i, n_acc[i]]:
            n_acc[i] += 1
    final_dist = np.zeros((b, p.shape[-1]))
    for i in range(b):
        if n_acc[i] == k:
            final_dist[i] = p[i, k]
        else:
            pos = min(int(n_acc[i]), k - 1)
            resid = np.maximum(p[i, pos] - q[i, pos], 0.0)
            if resid.sum() <= 1e-9:
                resid = p[i, pos]
            final_dist[i] = resid / resid.sum()
    f_samp = np.asarray(jax.random.categorical(
        key_bonus, jnp.log(jnp.maximum(jnp.asarray(final_dist), 1e-30)),
        axis=-1))
    final = np.where(greedy, final_dist.argmax(-1), f_samp)
    return n_acc, final.astype(np.int32), accept


@pytest.mark.parametrize("greedy_all", [True, False])
@pytest.mark.parametrize("masked", [False, True])
def test_rejection_accept_bit_parity_vs_frozen_r5(greedy_all, masked):
    b, k, v = 5, 4, 32
    rng = np.random.RandomState(7 + masked)
    p = rng.dirichlet(np.ones(v) * 0.3, size=(b, k + 1))
    q = rng.dirichlet(np.ones(v) * 0.3, size=(b, k))
    drafts = rng.randint(0, v, size=(b, k)).astype(np.int32)
    greedy = np.full(b, greedy_all)
    valid = (rng.rand(b, k) < 0.6) if masked else None
    kr, kb = jax.random.split(jax.random.key(3))
    n_ref, f_ref, a_ref = _frozen_r5_accept(p, q, drafts, greedy, kr, kb,
                                            valid)
    n, f, a = rejection_accept(
        jnp.asarray(p, jnp.float32), jnp.asarray(q, jnp.float32),
        jnp.asarray(drafts), jnp.asarray(greedy), kr, kb,
        valid=None if valid is None else jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(n), n_ref)
    np.testing.assert_array_equal(np.asarray(f), f_ref)
    np.testing.assert_array_equal(np.asarray(a), a_ref)


def test_plain_rows_reduce_to_plain_decode():
    """A verify row with zero draft columns (all-False mask + zero
    q_probs) must sample exactly the target distribution at position 0 —
    that is what lets plain rows ride the verify program unchanged."""
    b, k, v = 3, 4, 16
    rng = np.random.RandomState(11)
    p = rng.dirichlet(np.ones(v), size=(b, k + 1)).astype(np.float32)
    q = np.zeros((b, k, v), np.float32)
    drafts = np.zeros((b, k), np.int32)
    kr, kb = jax.random.split(jax.random.key(5))
    n, f, _ = rejection_accept(
        jnp.asarray(p), jnp.asarray(q), jnp.asarray(drafts),
        jnp.asarray(np.ones(b, bool)), kr, kb,
        valid=jnp.zeros((b, k), bool))
    assert np.asarray(n).tolist() == [0] * b
    np.testing.assert_array_equal(np.asarray(f), p[:, 0].argmax(-1))
