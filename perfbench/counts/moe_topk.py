"""Counts of a GQA decoder whose MLP is a stack of routed experts (top-k of
``num_local_experts``, softmax router, gates renormalised), stored
unquantized in ``serve.dtype``. HF ``config.json`` key names (Mixtral's). A
cut shows in the keys themselves: ``num_hidden_layers`` kept,
``num_local_experts`` held, a ``vocab_size`` slice, each listed in
``reduced``."""

from typing import Any, Dict, List, Tuple

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
CACHE = "K and V rows of every layer, per token: 2 x L x Hkv x Dh x itemsize"


def widths(cfg: Dict[str, Any]) -> Dict[str, int]:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"L": int(cfg["num_hidden_layers"]), "D": d, "H": h,
            "Hkv": int(cfg["num_key_value_heads"]),
            "Dh": int(cfg.get("head_dim") or d // h),
            "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
            "E": int(cfg["num_local_experts"])}


def stored_dtype(cfg: Dict[str, Any]) -> str:
    return str(cfg["serve"].get("dtype", "bfloat16"))


def weight_matmuls(cfg: Dict[str, Any]) -> List[Tuple[str, int, int, int, str]]:
    """``(name, K, N, times per pass, dtype)``. The program's inference path
    runs EVERY expert over every token and keeps each token's top-k in the
    combine (``ops/moe.py`` ``exact=True``), so an expert matrix is
    multiplied by L x E times a pass, not L x top-k."""
    w = widths(cfg)
    dt = stored_dtype(cfg)
    return [("qkv", w["D"], (w["H"] + 2 * w["Hkv"]) * w["Dh"], w["L"], dt),
            ("attn_out", w["H"] * w["Dh"], w["D"], w["L"], dt),
            ("router", w["D"], w["E"], w["L"], dt),
            ("expert_gate_up", w["D"], 2 * w["F"], w["L"] * w["E"], dt),
            ("expert_down", w["F"], w["D"], w["L"] * w["E"], dt),
            ("lm_head", w["D"], w["V"], 1, dt)]


def param_bytes(cfg: Dict[str, Any]) -> int:
    """Every matrix above once as stored, the embedding, two norms a layer
    and the final norm, all in ``serve.dtype``."""
    w = widths(cfg)
    n = sum(k * n * times for _name, k, n, times, _dt in weight_matmuls(cfg))
    n += w["V"] * w["D"] + (2 * w["L"] + 1) * w["D"]
    return n * ITEMSIZE[stored_dtype(cfg)]


def kv_bytes_per_token(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    w = widths(cfg)
    return 2 * w["L"] * w["Hkv"] * w["Dh"] * kv_itemsize
