"""Plain reference of Mellum2-12B-A2.5B-Instruct (``model_type`` ``mellum``)
as one chip of its stated deployment holds it: straightforward ``jax.numpy``
in float32 at highest matmul precision, the whole sequence at once, no cache,
no kernel, no batching, nothing from ``ops/``; the mask written as a mask
(in blocks of ``Q_BLOCK`` queries, so a 4,160-token chain fits beside the
tree on one chip), every expert computed for every token and weighted by a
gate that is 0 off the top-k. The weights are the SERVED bf16 values widened
exactly.

D = ``hidden_size``, H = ``num_attention_heads``, H_kv =
``num_key_value_heads``, d = ``head_dim``, eps = ``rms_norm_eps``; x [T, D].

    h = x + Attn_l(RMSNorm(x));  y = h + MoE(RMSNorm(h))

Attn_l: q = x W_q [H x d], k = x W_k, v = x W_v [H_kv x d], no bias, no q/k
    normalisation (assumed: the config has no key for one); q and k rotated
    over the whole d (lane i with lane i + d/2, HF's rotate_half) by the
    table of the layer's kind; scores q k^T d^-1/2; query head h reads K/V
    head floor(h / (H / H_kv)); token i sees j <= i on a ``full_attention``
    layer and i - ``sliding_window`` < j <= i on a ``sliding_attention``
    layer (the window counts the token itself); out = (P v) W_o.
Rotary tables (``rope_parameters``): sliding layers plain RoPE, f_j =
    theta^(-2j/d). Full layers YaRN as HF computes it: with L =
    ``original_max_position_embeddings``, c(beta) = d ln(L / (2 pi beta)) /
    (2 ln theta), low = max(floor(c(beta_fast)), 0), high = min(ceil(
    c(beta_slow)), d - 1), ramp_j = clip((j - low) / (high - low), 0, 1),
    f_j = theta^(-2j/d) ((1 - ramp_j) + ramp_j / factor); cos and sin
    multiplied by ``attention_factor`` (so a full layer's logits carry its
    square).
MoE (every layer, ``mlp_layer_types`` all sparse): p = softmax(x W_r) over
    all ``num_experts`` in float32; the ``num_experts_per_tok`` largest; g =
    p_top / sum p_top (``norm_topk_prob``); sum_e g_e W_down,e (SiLU(W_gate,e
    x) * (W_up,e x)). No shared expert, no bias, no scaling factor.
After the last kept layer: the final RMSNorm and the untied head.

``logits(..., control=<name>)`` computes a WRONG model on purpose, one of
``CONTROLS``: what the tests (``perfbench/tests/test_swa_moe.py``,
``tests/test_mellum.py``) and the builder's long chain
(``tools/longchain_mellum.py``) must see fail.

``TIE_FRACTION`` / ``MIN_STRICT_SHARE`` below are this family's own; the
readings they lie between are written beside them.
"""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512

# Set between two readings at the published widths on the chip (PR 39, call
# 2, ``tools/longchain_mellum.py --chains 8``; PERF.md section 6). The served
# chains: a token that is not the reference's argmax lies at most 0.0077 of
# max|logit| below it, and 21-24 of 24 are the argmax (eight chains of 48 +
# 24, the traced runs' four more 23-24; the 4,096 + 64 chain 59 of 64, alone
# and among 7 rows the same 64 tokens). The wrong models, chain by chain: on
# the long chain plain RoPE on the full layers reads 0.193 / 37 of 64, a
# missing attention factor 0.115 / 46, gates not renormalised 0.083 / 34, a
# sigmoid router 0.061 / 42: each refused by BOTH limits. On the short
# chains (72 rows of context: a full layer's YaRN ramp has hardly begun, a
# swapped gate moves a tenth of a residual) a wrong model reads 0.028-0.075
# and 16-23 of 24 on the chains it moves and nothing on one or two of the
# eight: 0.025 lies 3.2x above the served chains' largest gap and under the
# smallest gap that a moved chain showed; 0.75 asks 18 of 24, three below the
# served chains' fewest (a served token is not the argmax one time in 28: a
# chain of 19 would come once in some hundred at 0.8) and above the 16-17 of
# the wrong rotary tables' worst chains.
TIE_FRACTION = 0.025
MIN_STRICT_SHARE = 0.75
# NOT wrong enough for a logit to show, and said so: the whole reference in
# bfloat16 reads 0-0.0184 and 21-24 of 24 (55 of 64 on the long chain),
# inside the served chains' own band; a window of 1,023 or 1,025 rows moves
# NOTHING on a chain shorter than the window and 0.015-0.024 / 56-57 of 64
# on the long chain (one row of 1,024 at the window's edge), inside the
# limits. ``correct`` cannot refuse either; the CPU tests hold both at
# float32 tolerance with a window of 32 (``tests/test_mellum.py``), and the
# engine's counters hold the window's rows on the chip.
NOT_SEPARATED = ("bfloat16", "window_minus_1", "window_plus_1")

CONTROLS = ("window_minus_1", "window_plus_1", "plain_rope_on_full",
            "no_attention_factor", "gates_not_renormalised",
            "sigmoid_router")

SPEC_PAIRS = (
    ("hidden_size", "d_model"), ("num_hidden_layers", "n_layers"),
    ("num_attention_heads", "n_heads"),
    ("num_key_value_heads", "n_kv_heads"), ("head_dim", "head_dim"),
    ("intermediate_size", "d_ff"), ("vocab_size", "vocab_size"),
    ("num_experts", "n_experts"),
    ("num_experts_per_tok", "experts_per_token"),
    ("moe_intermediate_size", "moe_d_ff"),
    ("sliding_window", "sliding_window"),
    ("rms_norm_eps", "norm_eps"),
)


def like(x, w):
    return w.astype(x.dtype)


def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rotary_table(cfg, kind, control=""):
    """(frequencies [d / 2] float64, amplitude of cos / sin) of a layer
    kind, from the published ``rope_parameters`` group of that kind."""
    d = cfg["head_dim"]
    rp = cfg["rope_parameters"][kind]
    theta = float(rp["rope_theta"])
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if rp["rope_type"] == "default" or control == "plain_rope_on_full":
        return f, 1.0
    orig = float(rp["original_max_position_embeddings"])

    def correction(beta):
        return d * math.log(orig / (beta * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(correction(float(rp["beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    f = f * (1.0 - ramp) + f / float(rp["factor"]) * ramp
    amp = 1.0 if control == "no_attention_factor" else float(
        rp["attention_factor"])
    return f, amp


def rotate(x, freqs, amp):
    """x [T, N, d] at positions 0..T-1, HF's rotate_half pairing."""
    t, _n, d = x.shape
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * amp)[:, None, :].astype(x.dtype)
    sin = (jnp.sin(ang) * amp)[:, None, :].astype(x.dtype)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(cfg, kind, control, blk, x):
    t = x.shape[0]
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    freqs, amp = rotary_table(cfg, kind, control)
    q = rotate((x @ like(x, blk["wq"])).reshape(t, h, d), freqs, amp)
    k = rotate((x @ like(x, blk["wk"])).reshape(t, hkv, d), freqs, amp)
    v = (x @ like(x, blk["wv"])).reshape(t, hkv, d)
    # query head i reads K/V head i // (h / hkv): the K/V heads repeated
    k, v = (jnp.repeat(a, h // hkv, axis=1) for a in (k, v))
    window = 0
    if kind == "sliding_attention":
        window = cfg["sliding_window"] + {"window_minus_1": -1,
                                          "window_plus_1": 1}.get(control, 0)
    out = []
    cols = jnp.arange(t)[None, :]
    for i0 in range(0, t, Q_BLOCK):
        qb = q[i0:i0 + Q_BLOCK]
        s = jnp.einsum("ihd,jhd->hij", qb, k) * d ** -0.5
        rows = i0 + jnp.arange(qb.shape[0])[:, None]
        mask = cols <= rows
        if window:
            mask &= cols > rows - window
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
        out.append(jnp.einsum("hij,jhd->ihd", p, v))
    o = jnp.concatenate(out, 0)
    return o.reshape(t, h * d) @ like(x, blk["wo"])


def experts(cfg, control, blk, x):
    """Every expert for every token, weighted by its gate (0 off the
    top-k); the router in float32 whatever ``x`` is."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    logit = x.astype(jnp.float32) @ blk["w_router"].astype(jnp.float32)
    p = (jax.nn.sigmoid(logit) if control == "sigmoid_router"
         else jax.nn.softmax(logit, axis=-1))
    top, idx = jax.lax.top_k(p, k)
    if control != "gates_not_renormalised":
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.zeros((x.shape[0], e), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)

    def one(y, xs):
        w_gate_up, w_down, g = xs
        gate, up = jnp.split(x @ like(x, w_gate_up), 2, axis=-1)
        return y + g[:, None].astype(x.dtype) * (
            (jax.nn.silu(gate) * up) @ like(x, w_down)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (blk["w_gate_up"], blk["w_down"], gates.T))
    return y


def layer(cfg, kind, control, blk, x):
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, kind, control, blk,
                      rms_norm(x, blk["attn_norm"], eps))
    return x + experts(cfg, control, blk, rms_norm(x, blk["mlp_norm"], eps))


def layer_params(cfg, params):
    """The served tree is ONE period's layers stacked over the periods
    (``models/mellum.py``); the reference walks the kept layers in their
    published order."""
    # the published list is kept whole; this stage is its first entries
    types = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    period = len(params["period"])
    if len(types) != cfg["num_hidden_layers"] or types != (
            ["sliding_attention"] * (period - 1) + ["full_attention"]) * (
                len(types) // period):
        raise ValueError("layer_types of the configuration is not whole "
                         "periods of sliding layers closed by a full one")
    for i, kind in enumerate(types):
        yield kind, jax.tree_util.tree_map(lambda a: a[i // period],
                                           params["period"][i % period])


def logits(cfg, params, tokens, dtype=jnp.float32, control="", last=0):
    """Full-sequence logits [T, vocab_size] of one token sequence [T] (or of
    its ``last`` positions only: a long chain's head product would not fit
    beside the tree). ``dtype=jnp.bfloat16`` and ``control`` are CONTROLS,
    not the reference: the same equations one precision below what the
    configuration states, or with one named term wrong."""
    if control and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        x = params["tok_emb"][tokens].astype(dtype)
        for kind, blk in layer_params(cfg, params):
            x = jax.jit(layer, static_argnums=(0, 1, 2))(
                _Frozen(cfg), kind, control, blk, x)
        x = rms_norm(x[-last:], params["lnf_scale"], cfg["rms_norm_eps"])
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
    from distributed_inference_engine_tpu.models.mellum import init_params

    return init_params(
        spec.replace(dtype=cfg["serve"].get("dtype", "bfloat16")),
        jax.random.key(int(seed)))
