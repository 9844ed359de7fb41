"""Plain reference for the Mistral-7B and Qwen2-7B configurations (and the
llama-tiny rehearsal): a pre-norm decoder with RMSNorm, rotary positions in
the split-halves layout, grouped-query attention, SwiGLU, an untied head, and
Qwen2's bias on q/k/v. Straightforward ``jax.numpy`` in float32 at highest
matmul precision: no kernel, no cache, no batching, one layer at a time in a
Python loop. It follows the published equations; the one departure is that
the weights are the SERVED values (int4 payloads times their scales, bf16
embedding and norms), widened exactly to float32, so that the comparison
sees the served path's rounding and nothing else.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def unpack_int4(q: jnp.ndarray, s: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Packed int4 -> float32: byte k holds source row k in its low nibble
    and source row K/2 + k in its high nibble, both two's complement."""
    lo = jnp.right_shift(jnp.left_shift(q, 4), 4)
    hi = jnp.right_shift(q, 4)
    return jnp.concatenate([lo, hi], axis=axis).astype(jnp.float32) * s


def weight(w: Any) -> jnp.ndarray:
    """A leaf of the served tree as a float32 matrix [K, N]."""
    if hasattr(w, "q"):
        if w.bits != 4:
            return w.q.astype(jnp.float32) * w.s
        return unpack_int4(w.q, w.s, w.pack_axis % w.q.ndim)
    return w.astype(jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, theta):
    """x [T, H, Dh]; pairs (i, i + Dh/2) rotate by pos * theta^(-2i/Dh)."""
    t, _h, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(cfg: Dict[str, Any], blk: Dict[str, Any], x: jnp.ndarray):
    """The attention half of a block over a whole sequence x [T, D], its
    residual added."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    t = x.shape[0]
    y = rms_norm(x, blk["ln1_scale"], eps)
    q, k, v = (y @ weight(blk[n]) for n in ("wq", "wk", "wv"))
    if cfg.get("qkv_bias"):
        q, k, v = (a + blk[n].astype(jnp.float32)
                   for a, n in ((q, "bq"), (k, "bk"), (v, "bv")))
    q = rope(q.reshape(t, h, dh), cfg["rope_theta"])
    k = rope(k.reshape(t, hkv, dh), cfg["rope_theta"])
    v = v.reshape(t, hkv, dh)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(dh))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    att = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, h * dh)
    return x + att @ weight(blk["wo"])


def layer(cfg: Dict[str, Any], blk: Dict[str, Any], x: jnp.ndarray):
    """One decoder block over a whole sequence x [T, D]."""
    x = attention(cfg, blk, x)
    y = rms_norm(x, blk["ln2_scale"], cfg["rms_norm_eps"])
    gate = jax.nn.silu(y @ weight(blk["w_gate"]))
    return x + (gate * (y @ weight(blk["w_up"]))) @ weight(blk["w_down"])


def logits(cfg: Dict[str, Any], params: Dict[str, Any],
           tokens: jnp.ndarray, layer_fn=layer) -> jnp.ndarray:
    """Full-sequence logits [T, vocab_size] of one token sequence [T].
    ``layer_fn`` is the block: a family whose block differs only past the
    attention (``reference/<family>.py``) passes its own."""
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda blk, x: layer_fn(cfg, blk, x))
        x = params["tok_emb"][tokens].astype(jnp.float32)
        n_layers = cfg["num_hidden_layers"]
        for i in range(n_layers):
            blk = jax.tree.map(lambda a: a[i], params["blocks"])
            x = step(blk, x)
        x = rms_norm(x, params["lnf_scale"], cfg["rms_norm_eps"])
        out = jax.jit(lambda w, x: x @ weight(w))(params["lm_head"], x)
        return out[:, : cfg["vocab_size"]]
