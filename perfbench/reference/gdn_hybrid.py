"""Plain reference of Olmo-Hybrid-7B (``model_type`` ``olmo_hybrid``) as one
chip of its stated deployment holds it: straightforward ``jax.numpy`` in
float32 at highest matmul precision, the whole sequence at once, no cache, no
kernel, no batching, nothing from ``ops/``; the recurrence token by token
(``lax.scan`` over t, no chunks), full attention as a masked softmax (in
blocks of ``Q_BLOCK`` queries, so a 4,160-token chain fits beside the tree on
one chip). The weights are the SERVED bf16 values widened exactly.

Kept layer ``l`` is full attention where ``layer_types[l]`` says so (every
fourth), else Gated DeltaNet. D = ``hidden_size``, H = 30 heads, eps =
``rms_norm_eps``; x [T, D] is the residual, read by every sublayer AS IT IS
and written as ``x + RMSNorm(f(x))`` (the norm on the sublayer's output).

Gated DeltaNet (d_k = ``linear_key_head_dim``, d_v = ``linear_value_head_dim``):
    q = x W_q, k = x W_k (H d_k each), v = x W_v (H d_v); every channel c of
    q | k | v: y_t[c] = SiLU(sum_i w[i, c] u_{t-3+i}[c]), 4 taps, zeros
    before the sequence; q, k L2-normalised per head (x / sqrt(sum x^2 +
    1e-6)), q times d_k^-1/2; beta_t = 2 sigmoid(x_t W_b) per head; g_t =
    -exp(A_log) softplus(x_t W_a + dt_bias) per head; per head, S in R^(d_k x
    d_v) from zero:
        S' = exp(g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - k_t^T S')^T;
        o_t = S_t^T q_t
    (the issue's S in R^(d_v x d_k), transposed); f(x) = (RMSNorm_{d_v}(o_t) *
    SiLU(x_t W_g)) W_o.
Full attention: q = RMSNorm(x W_q), k = RMSNorm(x W_k) over all H * 128
    values, v = x W_v; 30 heads of 128, NO rotary embedding; causal softmax
    at 128^-1/2; f(x) = (P v) W_o.
MLP (every layer): f(x) = (SiLU(x W_gate) * (x W_up)) W_down.
After the last kept layer: the final RMSNorm and the head.

``logits(..., control=<name>)`` computes a WRONG model on purpose, one of
``CONTROLS``: what the tests (``perfbench/tests/test_gdn_hybrid.py``,
``tests/test_olmo_hybrid.py``) and the builder's long chain
(``tools/longchain_olmo.py``) must see fail.

``TIE_FRACTION`` / ``MIN_STRICT_SHARE`` below are this family's own; the
readings they lie between are written beside them.
"""

import json

import jax
import jax.numpy as jnp

Q_BLOCK = 512

# Between two readings at the published widths on the chip (PR 33, call 2,
# ``tools/longchain_olmo.py --chains 8``; PERF.md section 6). The served
# chains: a token that is not the reference's argmax lies at most 0.0038 of
# max|logit| below it and 22-24 of 24 are the argmax (ten chains of 48 + 24;
# the 4,096 + 64 chain 0.0019 and 63 of 64). The nearest wrong model, chain
# by chain: beta without its factor 2 from 0.064 and at most 17 of 24 (rotary
# embedding on the full layers 0.084 / 16; on the long chain 0.068 and 45 of
# 64); the others further out. So a wrong model fails BOTH limits on every
# chain read, a served chain has six times the gap and two tokens of room.
TIE_FRACTION = 0.025
MIN_STRICT_SHARE = 0.8
# computed one precision down, not a wrong model: the reference in bfloat16
# reads 0-0.0058 and 22-24 of 24 (62 of 64 on the long chain), inside the
# served chains' own band, so ``correct`` cannot refuse it
NOT_SEPARATED = ("bfloat16",)

CONTROLS = ("beta_no_factor_2", "no_decay", "no_conv", "rope_on_full",
            "state_axes_swapped")

SPEC_PAIRS = (
    ("hidden_size", "d_model"), ("num_hidden_layers", "n_layers"),
    ("num_attention_heads", "n_heads"),
    ("num_key_value_heads", "n_kv_heads"),
    ("intermediate_size", "d_ff"), ("vocab_size", "vocab_size"),
    ("linear_num_value_heads", "n_heads"),
    ("linear_num_key_heads", "n_heads"),
    ("linear_key_head_dim", "gdn_key_head_dim"),
    ("linear_value_head_dim", "gdn_value_head_dim"),
    ("linear_conv_kernel_dim", "gdn_conv"),
    ("rms_norm_eps", "norm_eps"),
)


def like(x, w):
    return w.astype(x.dtype)


def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def l2_normalise(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def gated_delta_net(cfg, blk, x, control):
    t = x.shape[0]
    h = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    u = jnp.concatenate([x @ like(x, blk[n]) for n in ("wq", "wk", "wv")], -1)
    if control == "no_conv":
        y = u
    else:
        w = like(x, blk["conv_w"])
        up = jnp.pad(u, ((taps - 1, 0), (0, 0)))
        y = sum(up[i:i + t] * w[i] for i in range(taps))
    y = jax.nn.silu(y)
    q = l2_normalise(y[:, :h * dk].reshape(t, h, dk)) * dk ** -0.5
    k = l2_normalise(y[:, h * dk:2 * h * dk].reshape(t, h, dk))
    v = y[:, 2 * h * dk:].reshape(t, h, dv)
    # published linear_allow_neg_eigval: beta in (0, 2)
    beta = jax.nn.sigmoid(x @ like(x, blk["w_b"])) \
        * (1.0 if control == "beta_no_factor_2" else 2.0)
    g = -jnp.exp(like(x, blk["a_log"])) * jax.nn.softplus(
        x @ like(x, blk["w_a"]) + like(x, blk["dt_bias"]))
    if control == "no_decay":
        g = jnp.zeros_like(g)

    def step(S, xs):                                 # S [H, dk, dv]
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, None, None] * S
        err = v_t - jnp.einsum("hk,hkv->hv", k_t, S)
        S = S + b_t[:, None, None] * k_t[:, :, None] * err[:, None, :]
        if control == "state_axes_swapped":
            # the state's two axes taken for each other where it is read
            return S, jnp.einsum("hk,hvk->hv", q_t, S.reshape(h, dv, dk))
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    _, o = jax.lax.scan(step, jnp.zeros((h, dk, dv), x.dtype),
                        (q, k, v, g, beta))
    o = rms_norm(o, blk["o_norm"], cfg["rms_norm_eps"]) \
        * jax.nn.silu(x @ like(x, blk["w_g"])).reshape(t, h, dv)
    return o.reshape(t, h * dv) @ like(x, blk["wo"])


def rotate_half(x, theta=10000.0):
    """The rotary embedding the full layers do NOT have (a control)."""
    t, _h, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def full_attention(cfg, blk, x, control):
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    dh = cfg["hidden_size"] // h        # the source lists no head_dim
    eps = cfg["rms_norm_eps"]
    q = rms_norm(x @ like(x, blk["wq"]), blk["q_norm"], eps).reshape(t, h, dh)
    k = rms_norm(x @ like(x, blk["wk"]), blk["k_norm"], eps).reshape(t, h, dh)
    v = (x @ like(x, blk["wv"])).reshape(t, h, dh)
    if control == "rope_on_full":
        q, k = rotate_half(q), rotate_half(k)
    out = []
    for i0 in range(0, t, Q_BLOCK):
        qb = q[i0:i0 + Q_BLOCK]
        s = jnp.einsum("ihd,jhd->hij", qb, k) * dh ** -0.5
        rows = i0 + jnp.arange(qb.shape[0])[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
        out.append(jnp.einsum("hij,jhd->ihd", p, v))
    o = jnp.concatenate(out, 0)
    return o.reshape(t, h * dh) @ like(x, blk["wo"])


def swiglu(blk, x):
    gate, up = jnp.split(x @ like(x, blk["w_gate_up"]), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ like(x, blk["w_down"])


def layer(cfg, kind, control, blk, x):
    eps = cfg["rms_norm_eps"]
    mixer = full_attention if kind == "full_attention" else gated_delta_net
    x = x + rms_norm(mixer(cfg, blk, x, control), blk["attn_norm"], eps)
    return x + rms_norm(swiglu(blk, x), blk["mlp_norm"], eps)


def layer_params(cfg, params):
    """The served tree is ONE period's layers stacked over the periods
    (``models/olmo_hybrid.py``); the reference walks the kept layers in
    their published order."""
    # the published list is kept whole; this stage is its first entries
    types = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    period = len(params["period"])
    if len(types) != cfg["num_hidden_layers"] or types != (
            ["linear_attention"] * (period - 1) + ["full_attention"]) * (
                len(types) // period):
        raise ValueError("layer_types of the configuration is not whole "
                         "periods of linear layers closed by a full one")
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
    from distributed_inference_engine_tpu.models.olmo_hybrid import (
        init_params,
    )

    return init_params(
        spec.replace(dtype=cfg["serve"].get("dtype", "bfloat16")),
        jax.random.key(int(seed)))
