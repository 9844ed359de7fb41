"""Plain reference of Xing4.0-29B-A4B (``model_type`` ``xing4_0``) as one
chip of its stated deployment holds it: straightforward ``jax.numpy`` in
float32 at highest matmul precision, the whole sequence at once, no cache, no
kernel, no batching, one layer at a time in a Python loop; attention in
blocks of ``Q_BLOCK`` queries so a 4,160-token chain fits beside the tree on
one chip. The weights are the SERVED bf16 values widened exactly.

Published layer ``l`` (``kept_layers[k]``) has a dense MLP if ``l <
first_k_dense_replace``, else experts. n = ``hc_mult``, D = ``hidden_size``,
eps = ``rms_norm_eps``.

Residual: X in R^(n x D) per token, X_0[i] = E[token] for every i. Each
sublayer F in {attention, MLP} has phi [nD, n^2 + 2n], alpha [3], b [n^2+2n]:
    x~ = vec(X) / sqrt(mean(vec(X)^2) + eps);  m = x~ phi = m_pre | m_post |
    m_res;  H_pre = sigmoid(alpha_1 m_pre + b_pre);  H_post = 2 sigmoid(
    alpha_2 m_post + b_post);  M = exp(clip(alpha_3 mat(m_res) + b_res,
    mhc_h_res_clamp_min, mhc_h_res_clamp_max)); ``hc_sinkhorn_iters`` times
    M <- M / (row sums + hc_eps), M <- M / (column sums + hc_eps); H_res = M
    (rows: the stream written, columns: the stream read);
    h = sum_i H_pre[i] X[i];  y = F(RMSNorm(h));
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y.
After the last kept layer hidden = sum_i X[i], the final RMSNorm, the head.
MLA: c_q = RMSNorm(h W_qa); q = c_q W_qb -> H x [nope | rope]; h W_kva -> c_kv
    | k_rope; c = RMSNorm(c_kv); c W_kvb -> H x [k_nope | v]; interleaved
    pairs rotate at YaRN's frequencies (f = theta^(-2i/d); f / factor past
    the ramp; ramp between the pairs that turn beta_fast and beta_slow times
    over the original context), cos / sin times mscale(factor, mscale) /
    mscale(factor, mscale_all_dim); scores (q_nope k_nope + q_rope k_rope)
    (nope + rope)^-1/2 mscale(factor, mscale_all_dim)^2, mscale(s, m) = 0.1 m
    ln s + 1; causal softmax; y = W_o (P v). No bias, no output gate.
Experts: s = sigmoid(x W_r); top-k of s + b; gates s at the chosen over
    their sum, times routed_scaling_factor; out = shared(x) + sum gate_e
    SwiGLU_e(x). (n_group = topk_group = 1: no group limits anything.)

Departure, named in the configuration file: the next-token-prediction (MTP)
layer is in neither the served tree nor here.

``logits(..., control=<name>)`` computes a WRONG model on purpose, one of
``CONTROLS``: what the CPU tests (``tests/test_xing.py``) and the builder's
long chain (``tools/longchain_xing.py``) must see fail.

``TIE_FRACTION`` and ``MIN_STRICT_SHARE`` below are this family's own and
TIGHTER than ``check.py``'s 1/8 and 0.5, each set between two chip readings
(one v5e chip, PR 31 review round, call 9, ``tools/longchain_xing.py --chains
16`` from the committed files; gap = distance below the reading's argmax over
max|logit|, the worst of a chain's 24 tokens; 16 chains of ``run.py``'s 48 +
24 tokens, each served alone by ``ContinuousEngine``, and the two chains of
the same call's traced run, other weights):

    served chains        gap 0-0.0062 (12 of 18 at 0), 22-24 of 24 strict
    H_res transposed     gap 0.042-0.127, 11-19 of 24 strict
    1 Sinkhorn round     gap 0.130-0.394,  7-12 of 24 strict
    no YaRN factor       gap 0.098-0.292,  9-17 of 24 strict
    reference, bfloat16  gap 0-0.0112 (11 of 16 at 0), 21-24 of 24 strict

0.025 lies a factor 4 above the served chains' largest gap and 1.7 below the
nearest control's SMALLEST (the room is on the served side: a served chain
outside refuses a PR, and every chain of H_res transposed is refused by the
strict share as well; at the published widths on the CPU, other weight seeds,
12 more served chains read 0-0.0087 and 22-24); 0.8 asks 20 of 24, two below the served chains'
fewest and one above the nearest control's most. Through ``check.judge``
with these limits all 18 served chains are inside and every chain of the
three wrong models is refused, by both limits (0 of 16 each). The first
round of PR 31 read the served chains at gaps of 0.011-0.206 and 15-22
strict, no better than H_res transposed (0.055-0.198), and had widened the
limit to 0.3. Two causes, both in how the weights were drawn, cured there
(``models/xing.py`` ``ROUTED_DOWN_SCALE``, ``ops/mhc.py`` ``init_hc``):
top-4 of 64 sigmoid scores in bfloat16 swaps a token's 4th best expert for
its 5th against float32 on about one token in five, and with the routed
experts drawn as large as the shared one a swap moved the hidden state by a
fifth (now an eighth of that: the rounding's own size); and at mHC biases of
spread 1 the four streams stayed so alike that a wrong H_res moved nothing
(now b_pre / b_post of spread 6, b_res of spread 3 with a shift towards the
next stream).

What these limits do NOT separate: the whole reference in bfloat16 (and mHC
alone in bfloat16) reads inside the served chains' range. The served path
is itself bfloat16 between its float32 islands (residual streams, mHC maps,
router, softmax); its distance from float32 is a rounding's (0.3 % of
max|logit| at the tiny size), the bfloat16 reference's is of the same order,
and an argmax over 24 tokens tells a wrong model from a right one, not one
rounding from two. A lower precision is held at the logits' level by
the CPU tests (``tests/test_xing.py``: mHC in bfloat16 moves them 70 times
the float32 bound), and on the chip by the long chain's strict share alone.
Nor do they see the routed experts' VALUES well (an eighth of the shared
expert's): the CPU tests hold those (``gates_not_renormalised``, the
interpreted grouped product in ``tests/test_ling.py``).

``LONG_*`` are the limits of the builder's one chain at the cell's own
lengths (4,096 + 64 tokens, served alone and again among 7 live rows: the
same 64 tokens both times; same call): served 62 of 64 strict, gap 0.0062;
bfloat16 reference 58 and 0.0060, no YaRN factor 57 and 0.0156, 1 Sinkhorn
round 47 and 0.0388, H_res transposed 39 and 0.0620. 0.94 lies between
0.969 and 0.906, 0.01 between 0.0062 and 0.0156: the served chain inside,
all four outside (a single reading on each side).
"""

import json
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512

TIE_FRACTION = 0.025          # between 0.0062 (served) and 0.042 (H_res^T)
MIN_STRICT_SHARE = 0.8        # 20 of 24: between 19 (H_res^T) and 22 (served)
LONG_TIE_FRACTION = 0.01      # between 0.0062 (served) and 0.0156 (no YaRN)
LONG_MIN_STRICT_SHARE = 0.94  # between 0.906 (bfloat16) and 0.969 (served)
# computed one precision down, not a wrong model: inside the served chains'
# range on chains of 48 + 24 (the docstring says why)
NOT_SEPARATED = ("bfloat16", "mhc_bfloat16")

CONTROLS = ("res_transposed", "pre_post_swapped", "one_sinkhorn_round",
            "mhc_bfloat16", "no_yarn_softmax_factor", "plain_rope",
            "no_shared_expert", "gates_not_renormalised")

SPEC_PAIRS = (
    ("hidden_size", "d_model"), ("num_hidden_layers", "n_layers"),
    ("num_attention_heads", "n_heads"), ("intermediate_size", "d_ff"),
    ("vocab_size", "vocab_size"), ("rope_theta", "rope_theta"),
    ("rope_scaling", "rope_scaling"), ("rms_norm_eps", "norm_eps"),
    ("q_lora_rank", "q_lora_rank"), ("kv_lora_rank", "kv_lora_rank"),
    ("qk_nope_head_dim", "qk_nope_head_dim"),
    ("qk_rope_head_dim", "qk_rope_head_dim"), ("v_head_dim", "v_head_dim"),
    ("n_routed_experts", "n_experts"),
    ("num_experts_per_tok", "experts_per_token"),
    ("moe_intermediate_size", "moe_d_ff"),
    # n_shared_experts = 1 expert of moe_intermediate_size
    ("moe_intermediate_size", "shared_d_ff"),
    ("n_group", "n_group"), ("topk_group", "topk_group"),
    ("routed_scaling_factor", "routed_scaling_factor"),
    ("hc_mult", "hc_mult"), ("hc_sinkhorn_iters", "hc_sinkhorn_iters"),
    ("hc_eps", "hc_eps"), ("mhc_h_res_clamp_min", "hc_clamp_min"),
    ("mhc_h_res_clamp_max", "hc_clamp_max"),
    ("experts_held", "experts_held"), ("kept_layers", "layer_ids"),
    ("layer_mlps", "layer_mlps"),
)


def layer_mlps(cfg):
    return ["dense" if i < cfg["first_k_dense_replace"] else "moe"
            for i in cfg["kept_layers"]]


def like(x, w):
    """A weight in the activations' precision: float32 (the reference: the
    served bf16 values widen exactly), or bfloat16 for a control."""
    return w.astype(x.dtype)


def rms_norm(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y if scale is None else y * like(x, scale)


def swiglu(x, w_gate_up, w_down):
    gate, up = jnp.split(x @ like(x, w_gate_up), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ like(x, w_down)


# ------------------------------------------------------------------- mHC


def hc_maps(cfg, hc, X, control):
    """X [T, n, D] -> H_pre [T, n], H_post [T, n], H_res [T, n, n]."""
    n = cfg["hc_mult"]
    t = X.shape[0]
    xt = rms_norm(X.reshape(t, -1), None, cfg["rms_norm_eps"])
    m = xt @ like(X, hc["phi"])
    a, b = like(X, hc["alpha"]), like(X, hc["bias"])
    pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    M = jnp.exp(jnp.clip(
        (a[2] * m[:, 2 * n:] + b[2 * n:]).reshape(t, n, n),
        cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))
    rounds = 1 if control == "one_sinkhorn_round" else cfg["hc_sinkhorn_iters"]
    for _ in range(rounds):
        M = M / (M.sum(-1, keepdims=True) + cfg["hc_eps"])
        M = M / (M.sum(-2, keepdims=True) + cfg["hc_eps"])
    if control == "res_transposed":
        M = jnp.swapaxes(M, -1, -2)
    if control == "pre_post_swapped":
        pre, post = post, pre
    return pre, post, M


def sublayer(cfg, hc, scale, X, fn, control):
    """X [T, n, D] -> X' through one mHC-wrapped sublayer ``fn``."""
    Xh = X.astype(jnp.bfloat16) if control == "mhc_bfloat16" else X
    pre, post, res = hc_maps(cfg, hc, Xh, control)
    h = jnp.einsum("ti,tid->td", pre, Xh).astype(X.dtype)
    y = fn(rms_norm(h, scale, cfg["rms_norm_eps"]))
    out = jnp.einsum("tij,tjd->tid", res, Xh) \
        + post[:, :, None] * y.astype(Xh.dtype)[:, None, :]
    return out.astype(X.dtype)


# ------------------------------------------------------------- attention


def mscale(scale, m):
    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn(cfg, control):
    """(frequencies [dr/2], amplitude of cos / sin, softmax scale)."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg["rope_scaling"]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * i / d)
    plain_scale = (cfg["qk_nope_head_dim"] + d) ** -0.5
    soft = plain_scale * mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    if control == "no_yarn_softmax_factor":
        soft = plain_scale
    if control == "plain_rope":
        return extra, 1.0, soft

    def pair_that_turns(beta):
        return d * math.log(sc["original_max_position_embeddings"]
                            / (beta * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(pair_that_turns(sc["beta_fast"])), 0)
    hi = min(math.ceil(pair_that_turns(sc["beta_slow"])), d - 1)
    ramp = jnp.clip((i - lo) / (hi - lo), 0.0, 1.0)
    freq = extra / sc["factor"] * ramp + extra * (1.0 - ramp)
    amp = mscale(sc["factor"], sc["mscale"]) \
        / mscale(sc["factor"], sc["mscale_all_dim"])
    return freq, amp, soft


def rope_pairs(x, freq, amp):
    """x [T, N, d]: pairs (2i, 2i+1) rotate by pos * freq[i]."""
    t = x.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = (jnp.cos(ang) * amp)[:, None, :].astype(x.dtype)
    sin = (jnp.sin(ang) * amp)[:, None, :].astype(x.dtype)
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.stack([xe * cos - xo * sin, xe * sin + xo * cos],
                     -1).reshape(x.shape)


def mla(cfg, blk, x, control):
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    freq, amp, scale = yarn(cfg, control)
    cq = rms_norm(x @ like(x, blk["w_qa"]), blk["q_norm"], eps)
    q = (cq @ like(x, blk["w_qb"])).reshape(t, h, dn + dr)
    kva = x @ like(x, blk["w_kva"])
    c = rms_norm(kva[:, :r], blk["kv_norm"], eps)
    k_rope = rope_pairs(kva[:, None, r:], freq, amp)              # [T,1,dr]
    kv = (c @ like(x, blk["w_kvb"])).reshape(t, h, dn + dv)
    q_full = jnp.concatenate(
        [q[..., :dn], rope_pairs(q[..., dn:], freq, amp)], -1)
    k_full = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (t, h, dr))], -1)
    outs = []
    for q0 in range(0, t, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, t)
        scores = jnp.einsum("qhd,khd->hqk", q_full[q0:q1],
                            k_full[:q1]) * scale
        causal = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, kv[:q1, :, dn:]))
    o = jnp.concatenate(outs, 0)
    return o.reshape(t, h * dv) @ like(x, blk["wo"])


# --------------------------------------------------------------- experts


def router(cfg, blk, x, control):
    """Gates over all experts, zero off the chosen: [T, E]."""
    e = cfg["n_routed_experts"]
    s = jax.nn.sigmoid(x @ like(x, blk["w_router"]))
    _, idx = jax.lax.top_k(s + like(x, blk["router_bias"]),
                           cfg["num_experts_per_tok"])
    chosen = jnp.any(jax.nn.one_hot(idx, e, dtype=bool), axis=1)
    g = jnp.where(chosen, s, 0.0)
    if control != "gates_not_renormalised":
        g = g / jnp.sum(g, -1, keepdims=True)
    return g * cfg["routed_scaling_factor"]


def experts(cfg, blk, x, control):
    gates = router(cfg, blk, x, control)
    out = jnp.zeros_like(x) if control == "no_shared_expert" else swiglu(
        x, blk["ws_gate_up"], blk["ws_down"])
    for e in range(cfg["n_routed_experts"]):
        out = out + gates[:, e:e + 1] * swiglu(
            x, blk["w_gate_up"][e], blk["w_down"][e])
    return out


def layer(cfg, mlp, control, blk, X):
    X = sublayer(cfg, blk["hc_attn"], blk["ln1_scale"], X,
                 lambda h: mla(cfg, blk, h, control), control)
    if mlp == "dense":
        def ffn(h):
            return swiglu(h, blk["w_gate_up"], blk["w_down"])
    else:
        def ffn(h):
            return experts(cfg, blk, h, control)
    return sublayer(cfg, blk["hc_mlp"], blk["ln2_scale"], X, ffn, control)


def logits(cfg, params, tokens, dtype=jnp.float32, control="", last=0):
    """Full-sequence logits [T, vocab_size] of one token sequence [T] (or of
    its ``last`` positions only: a long chain's head product would not fit
    beside the tree). ``dtype=jnp.bfloat16`` and ``control`` are CONTROLS,
    not the reference: the same equations one precision below what the
    configuration states, or with one named term wrong."""
    if control and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    if layer_mlps(cfg) != list(cfg["layer_mlps"]):
        raise ValueError("layer_mlps of the configuration is not what "
                         "first_k_dense_replace gives for kept_layers")
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        e = params["tok_emb"][tokens].astype(dtype)
        X = jnp.broadcast_to(e[:, None, :],
                             (e.shape[0], cfg["hc_mult"], e.shape[1]))
        for blk, mlp in zip(params["layers"], layer_mlps(cfg)):
            X = jax.jit(layer, static_argnums=(0, 1, 2))(
                _Frozen(cfg), mlp, control, blk, X)
        x = rms_norm(X.sum(1)[-last:], params["lnf_scale"],
                     cfg["rms_norm_eps"])
        return jax.jit(lambda w, x: x @ like(x, w))(params["lm_head"], x)


class _Frozen(dict):
    """The configuration as a hashable static argument, by its content: a
    second call with an equal configuration finds the compiled layer."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))

    def __eq__(self, other):
        return dict.__eq__(self, other)

    def __ne__(self, other):
        return not self == other


def build_params(cfg, spec, seed):
    from distributed_inference_engine_tpu.models.xing import init_params

    return init_params(
        spec.replace(dtype=cfg["serve"].get("dtype", "bfloat16")),
        jax.random.key(int(seed)))
