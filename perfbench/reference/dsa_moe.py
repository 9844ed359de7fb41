"""Plain reference of the text decoder of Keye-VL-2.0-30B-A3B (``model_type``
``KeyeVL2``) as one chip of its stated deployment holds it: straightforward
``jax.numpy`` in float32 at highest matmul precision, the whole sequence at
once, no cache, no kernel, no batching, nothing from ``ops/``: the full
index-score matrix of a block of ``Q_BLOCK`` queries, ``lax.top_k`` a query,
the selection written as a mask, every expert computed for every token and
weighted by a gate that is 0 off the top-k. The weights are the SERVED bf16
values widened exactly.

D = ``hidden_size``, H = ``num_attention_heads``, H_kv =
``num_key_value_heads``, d = ``head_dim``, eps = ``rms_norm_eps``; from
``sa_config``: H_I = ``indexer_num_heads``, d_I = ``indexer_head_dim``, ONE
index key head (``indexer_num_kv_heads`` 1), K = ``topk``; x [T, D].

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))

Attn, u = RMSNorm(x): q = u W_q [H x d], k = u W_k, v = u W_v [H_kv x d], no
    bias; RMSNorm with a learned scale over each head's d of q and of k; q
    and k rotated over the whole d (lane i with lane i + d/2, HF's
    rotate_half) by plain RoPE, f_j = theta^(-2j/d).
Indexer (its own weights in every layer): q^I_t = u_t W^I_q [H_I x d_I],
    k^I_s = LayerNorm(u_s W^I_k) [d_I], w_t = u_t W^I_w [H_I]; q^I and k^I
    rotated over their whole d_I by plain RoPE of dimension d_I at the same
    theta; I[t, s] = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s]) in float32.
Selection: S_t = the K positions s <= t of largest I[t, s] (every s <= t
    while t + 1 <= K), equal scores to the lower position (``lax.top_k``);
    ONE set a token a layer, shared by all H heads.
    o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, h // (H /
    H_kv)] d^-1/2) v[s, h // (H / H_kv)]; out = o W_o.
MoE (every layer): p = softmax(u' W_r) over all ``num_experts`` in float32;
    the ``num_experts_per_tok`` largest; g = p_top / sum p_top
    (``norm_topk_prob``); sum_e g_e W_down,e (SiLU(W_gate,e u') * (W_up,e
    u')). No shared expert, no bias, no scaling factor.
After the last kept layer: the final RMSNorm and the untied head.

Departures from the published description, each also under ``assumed`` /
``departures`` of ``configs/keye-vl-2.0-30b-a3b-pp1.json``:

- the q/k RMSNorm a head is ASSUMED (the config has no key for it; the
  widths are the Qwen3-MoE block's, whose convention it is);
- M-RoPE (``mrope_section`` [16, 24, 24]: frequency pairs 0-15 from the
  temporal id, 16-39 from the height id, 40-63 from the width id) is
  computed as plain RoPE: a text token's three ids are equal
  (``rotate_sectioned`` is the sectioned form; a test shows the two equal);
- the indexer's rotary form (the whole d_I, plain RoPE of dimension d_I,
  rotate_half pairing), its LayerNorm's eps (``rms_norm_eps``) and the
  absence of a norm on q^I are ASSUMED;
- the positive constants DeepSeek's code multiplies into I (H_I^-1/2,
  d_I^-1/2) do not change a query's ranking and are left out; its Hadamard
  rotation and FP8 quantisation of q^I / k^I are an implementation's, not
  the model's, and are not taken;
- ``q_chunk_size`` / ``kv_chunk_size`` 512 are read as the tiles in which
  the published code computes the score matrix and enter no equation:
  selection is by token. The one place a checkpoint loader would have to
  look again;
- the vision tower is absent: text tokens only.

``logits(..., control=<name>)`` computes a WRONG model on purpose, one of
``CONTROLS``: what the tests (``perfbench/tests/test_dsa_moe.py``,
``tests/test_keye.py``) and the builder's long chain
(``tools/longchain_keye.py``) must see fail.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512

# ``correct`` (chains of 48 + 24 tokens, no selection: 72 rows of context
# are below the top-k) is judged by TIE_FRACTION / MIN_STRICT_SHARE, set
# between two readings at the published widths on the chip (PR 45, call 2,
# ``tools/longchain_keye.py --chains 4``, and the traced runs' two chains
# each; PERF.md section 6): a served token that is not the reference's
# argmax lies at most 0.0032 of max|logit| below it and 23-24 of 24 are the
# argmax; 0.025 stands 7.8 x above that gap, 0.75 asks 18 of 24. NO control
# of this family moves such a chain (each is the same model while the
# context <= the top-k: they read 0-0.0032 too), and the whole reference in
# bfloat16 reads 0-0.0037 and 23-24: ``correct`` sees the attention, the
# experts and the cache's plumbing, never the selection.
TIE_FRACTION = 0.025
MIN_STRICT_SHARE = 0.75
# The LONG chains (8,192 + 64 and 24,576 + 64, alone and among 7 live rows:
# the same 64 tokens both ways on every chain served) are the builder's
# tool's (``tools/longchain_keye.py``), and theirs are other limits: index
# scores computed from bfloat16 activations swap a median 7 (at most 13) of
# a query's 2,048 rows at the selection's edge against the float32
# reference (the 2,048th and 2,049th scores lie a median 2e-4 of the
# scores' spread apart at 8,192 rows; the swapped rows carry a median 0.2 %
# and a 99th percentile 2.7 % of the attention's mass; call 2,
# ``.scratch/measure_weights.py``). 0.08 / 0.4 were set from call 2's four
# chains of ONE weight seed and have NOT been moved since. The review
# round (calls R1 and R2: the final programs, weight seeds 7, 4100000811
# and 2045000341, other chains) reads against them, gap of max|logit| and
# strict of 64, eight distinct chains in all with call 2's:
#   served                      0.022-0.079   33-62   inside on every chain
#   the reference's OWN greedy tokens in bfloat16 against the float32
#   reference (the witness)     0.025-0.093   41-63   the same band
#   ... with no selection in both  0-0.005    62-64   so the band is the
#       selection's edge under bfloat16, not one precision's rounding
#   no selection (dense)        0.101-0.185    5-60   outside on every chain
#   index key's LayerNorm off   0.092-0.135   15-62   outside on every chain
#   a halved top-k              0.094-0.237   11-40   outside (6 chains)
#   index weights dropped       0.173-0.307    5-52   outside (6 chains)
#   index keys unrotated        0.143-0.415    3-42   outside (6 chains)
#   ReLU dropped                0.077-0.212   12-47   INSIDE on one chain
#   the reference in bfloat16   0.008-0.074   36-61   not separated
# THE ROOM IS GONE ON THE SERVED SIDE: the third seed's 24,576 chain reads
# 0.0793 (1.01 x under 0.08; the six chains before it at most 0.067), and
# on that chain the witness itself reads 0.0925, past the limit and as far
# out as the nearest held controls' smallest readings (0.092, 0.094). So
# on chains of 64 tokens at these weights the largest gap tells no
# selection from the model on every chain (0.101 against 0.079, 1.27 x),
# and the five subtler wrong indexers only on most: a fourth seed may put
# a served chain outside or a held control inside, and a judgement that is
# to hold them needs more tokens a chain or another statistic than the
# largest gap (PERF.md section 7). The strict count separates a wrong
# model on some chains only (call 2's: 5-6 of 64 for the dense control;
# the review round's: 16-60): the gap does the work, the count rides with
# it (``check.judge`` holds both). ReLU dropped read 0.0771 on one 24,576
# chain of seed 7 (0.101-0.212 on the other five it was read on):
# ``LONG_NOT_SEPARATED`` says the long chains do not hold it, as they do
# not hold one precision below; the CPU tests do (float32, 5e-5).
LONG_TIE_FRACTION = 0.08
LONG_MIN_STRICT_SHARE = 0.4
NOT_SEPARATED = ("bfloat16",)
LONG_NOT_SEPARATED = ("bfloat16", "no_relu")

CONTROLS = ("dense", "topk_halved", "no_index_weights", "no_relu",
            "index_keys_unrotated", "no_index_key_norm")

SPEC_PAIRS = (
    ("hidden_size", "d_model"), ("num_hidden_layers", "n_layers"),
    ("num_attention_heads", "n_heads"),
    ("num_key_value_heads", "n_kv_heads"), ("head_dim", "head_dim"),
    ("intermediate_size", "d_ff"), ("vocab_size", "vocab_size"),
    ("num_experts", "n_experts"),
    ("num_experts_per_tok", "experts_per_token"),
    ("moe_intermediate_size", "moe_d_ff"),
    ("rms_norm_eps", "norm_eps"), ("rope_theta", "rope_theta"),
)
# the indexer's group is nested: (``sa_config`` key, ``ModelSpec`` field)
SA_PAIRS = (("indexer_num_heads", "index_heads"),
            ("indexer_head_dim", "index_head_dim"), ("topk", "index_topk"))


def like(x, w):
    return w.astype(x.dtype)


def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
    y = (xf - mean) / jnp.sqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _rotate_by(x, ang):
    """x [T, N, d] by the angles ``ang`` [T, d / 2]: HF's rotate_half
    pairing (lane i with lane i + d / 2)."""
    d = x.shape[-1]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _freqs(d, theta):
    return jnp.asarray(
        float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d),
        jnp.float32)


def rotate(x, theta):
    """x [T, N, d] at positions 0..T-1, the whole d rotated: plain RoPE of
    dimension d."""
    t, _n, d = x.shape
    return _rotate_by(x, jnp.arange(t, dtype=jnp.float32)[:, None]
                      * _freqs(d, theta)[None, :])


def rotate_sectioned(x, theta, sections, position_ids):
    """M-RoPE as published: x [T, N, d], ``position_ids`` [3, T] (temporal,
    height, width), frequency pair j rotated by the id of the section it
    lies in (``sections`` pairs each, in that order). With three equal ids
    this is ``rotate``."""
    which = np.repeat(np.arange(len(sections)), sections)        # [d / 2]
    pos = jnp.asarray(position_ids, jnp.float32)[which, :].T     # [T, d/2]
    return _rotate_by(x, pos * _freqs(x.shape[-1], theta)[None, :])


def attention(cfg, control, blk, x):
    t = x.shape[0]
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    sa = cfg["sa_config"]
    hi, di, topk = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                    sa["topk"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    if control == "topk_halved":
        topk //= 2
    q = (x @ like(x, blk["wq"])).reshape(t, h, d)
    k = (x @ like(x, blk["wk"])).reshape(t, hkv, d)
    q = rotate(rms_norm(q, blk["q_norm"], eps), theta)
    k = rotate(rms_norm(k, blk["k_norm"], eps), theta)
    v = (x @ like(x, blk["wv"])).reshape(t, hkv, d)
    # query head i reads K/V head i // (h / hkv): the K/V heads repeated
    k, v = (jnp.repeat(a, h // hkv, axis=1) for a in (k, v))
    # the indexer
    qi = (x @ like(x, blk["w_iq"])).reshape(t, hi, di)
    ki = x @ like(x, blk["w_ik"])
    if control != "no_index_key_norm":
        ki = layer_norm(ki, blk["ik_norm_scale"], blk["ik_norm_bias"], eps)
    qi = rotate(qi, theta)
    if control != "index_keys_unrotated":
        ki = rotate(ki[:, None, :], theta)[:, 0]
    w = (x @ like(x, blk["w_iw"])).astype(jnp.float32)
    if control == "no_index_weights":
        w = jnp.ones_like(w)
    cols = jnp.arange(t)[None, :]
    n_blocks = -(-t // Q_BLOCK)
    pad = n_blocks * Q_BLOCK - t            # the last block's rows past t

    def block(xs):
        qb, qib, wb, i0 = xs                # Q_BLOCK queries from i0 on
        rows = i0 + jnp.arange(Q_BLOCK)[:, None]
        mask = cols <= rows
        if control != "dense" and t > topk:
            dots = jnp.einsum("ihd,jd->ihj", qib, ki).astype(jnp.float32)
            if control != "no_relu":
                dots = jax.nn.relu(dots)
            score = jnp.einsum("ih,ihj->ij", wb, dots)
            score = jnp.where(mask, score, -jnp.inf)
            top, idx = jax.lax.top_k(score, topk)
            picked = jnp.zeros(mask.shape, bool).at[
                jnp.arange(Q_BLOCK)[:, None], idx].set(top > -jnp.inf)
            mask &= picked
        s = jnp.einsum("ihd,jhd->hij", qb, k) * d ** -0.5
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
        return jnp.einsum("hij,jhd->ihd", p, v)

    # the same block of equations for every Q_BLOCK queries, one after
    # another (``lax.map``: written out as a Python loop, a 24,640-token
    # chain is 193 copies of it in one program)
    blocks = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)
                               ).reshape(n_blocks, Q_BLOCK, *a.shape[1:])
    o = jax.lax.map(block, (blocks(q), blocks(qi), blocks(w),
                            jnp.arange(n_blocks) * Q_BLOCK))
    o = o.reshape(n_blocks * Q_BLOCK, h, d)[:t]
    return o.reshape(t, h * d) @ like(x, blk["wo"])


def experts(cfg, blk, x):
    """Every expert for every token, weighted by its gate (0 off the
    top-k); the router in float32 whatever ``x`` is."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    logit = x.astype(jnp.float32) @ blk["w_router"].astype(jnp.float32)
    top, idx = jax.lax.top_k(jax.nn.softmax(logit, axis=-1), k)
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


def layer(cfg, control, blk, x):
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, control, blk, rms_norm(x, blk["attn_norm"], eps))
    return x + experts(cfg, blk, rms_norm(x, blk["mlp_norm"], eps))


def layer_params(cfg, params):
    """The served tree is ONE layer's dict stacked over the layers
    (``models/keye.py``: ``params["period"][0]``)."""
    (stack,) = params["period"]
    for i in range(cfg["num_hidden_layers"]):
        yield jax.tree_util.tree_map(lambda a: a[i], stack)


def logits(cfg, params, tokens, dtype=jnp.float32, control="", last=0):
    """Full-sequence logits [T, vocab_size] of one token sequence [T] (or of
    its ``last`` positions only: a long chain's head product would not fit
    beside the tree), the attention in blocks of ``Q_BLOCK`` queries.
    ``dtype=jnp.bfloat16`` and ``control`` are CONTROLS, not the reference:
    the same equations one precision below what the configuration states,
    or with one named term wrong."""
    if control and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        x = params["tok_emb"][tokens].astype(dtype)
        for blk in layer_params(cfg, params):
            x = jax.jit(layer, static_argnums=(0, 1))(
                _Frozen(cfg), control, blk, x)
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
    from distributed_inference_engine_tpu.models.keye import init_params

    for key, field in SA_PAIRS:
        if cfg["sa_config"][key] != getattr(spec, field):
            raise ValueError(
                f"sa_config.{key} = {cfg['sa_config'][key]} but the served "
                f"spec has {field} = {getattr(spec, field)}")
    return init_params(
        spec.replace(dtype=cfg["serve"].get("dtype", "bfloat16")),
        jax.random.key(int(seed)))
