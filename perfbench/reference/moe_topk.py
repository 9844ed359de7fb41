"""Plain reference of the routed-expert decoder: ``decoder.py``'s attention,
then per token a float32 softmax over the router's logits, the top
``num_experts_per_tok`` experts, their gates renormalised to sum to one, and
the gate-weighted sum of those experts' SwiGLU outputs (Mixtral's published
block). Every expert is computed for every token and masked: no capacity, no
dispatch, nothing dropped.

The served tree is ``init_params`` at key 0 in ``serve.dtype``: an
unquantized deploy takes no seed (``engine_from_config`` hands
``metadata.seed`` to ``random_quantized_params`` only), so ``--seed`` moves
the traffic and the prompts, not these weights.
"""

import functools

import jax
import jax.numpy as jnp

from perfbench.reference import decoder

SPEC_PAIRS = (("hidden_size", "d_model"), ("num_hidden_layers", "n_layers"),
              ("num_attention_heads", "n_heads"),
              ("num_key_value_heads", "n_kv_heads"),
              ("intermediate_size", "d_ff"), ("vocab_size", "vocab_size"),
              ("head_dim", "head_dim"), ("rope_theta", "rope_theta"),
              ("rms_norm_eps", "norm_eps"),
              ("num_local_experts", "n_experts"),
              ("num_experts_per_tok", "experts_per_token"))


def moe_layer(cfg, blk, x):
    x = decoder.attention(cfg, blk, x)
    y = decoder.rms_norm(x, blk["ln2_scale"], cfg["rms_norm_eps"])
    probs = jax.nn.softmax(y @ decoder.weight(blk["w_router"]), -1)  # [T, E]
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1]) * top[..., None], 1)
    out = jnp.zeros_like(x)
    for e in range(cfg["num_local_experts"]):
        w_gate, w_up, w_down = (decoder.weight(blk[n][e])
                                for n in ("w_gate", "w_up", "w_down"))
        h = jax.nn.silu(y @ w_gate) * (y @ w_up)
        out = out + gates[:, e:e + 1] * (h @ w_down)
    return x + out


logits = functools.partial(decoder.logits, layer_fn=moe_layer)


def build_params(cfg, spec, seed):
    from distributed_inference_engine_tpu.models.base import init_params

    del seed                   # see the module's docstring
    return init_params(
        spec.replace(dtype=cfg["serve"].get("dtype", "bfloat16")),
        jax.random.key(0))
