"""Reference of the dense int4 GQA decoder: ``decoder.py``'s float32 forward
pass over the tree ``random_quantized_params`` gives for the seed (what
``engine_from_config`` serves for ``quantized`` with no checkpoint)."""

from perfbench.reference.decoder import logits  # noqa: F401

SPEC_PAIRS = (("hidden_size", "d_model"), ("num_hidden_layers", "n_layers"),
              ("num_attention_heads", "n_heads"),
              ("num_key_value_heads", "n_kv_heads"),
              ("intermediate_size", "d_ff"), ("vocab_size", "vocab_size"),
              ("head_dim", "head_dim"), ("rope_theta", "rope_theta"),
              ("rms_norm_eps", "norm_eps"), ("qkv_bias", "qkv_bias"))


def build_params(cfg, spec, seed):
    import jax

    from distributed_inference_engine_tpu.ops.quant import (
        random_quantized_params,
    )

    return random_quantized_params(
        spec.replace(dtype="bfloat16"), jax.random.key(int(seed)),
        bits=int(cfg["serve"]["weight_bits"]))
