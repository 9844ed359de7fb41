"""Bytes and operations the algorithm needs, from a configuration file's own
widths (HF ``config.json`` key names). Kept with the benchmark so that no PR
that claims a gain can change how a roofline share is counted."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

LM_HEAD_PAD = 2048      # the int4 lm_head is stored padded to this multiple


def widths(cfg: Dict[str, Any]) -> Dict[str, int]:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    dh = int(cfg.get("head_dim") or d // h)
    v = int(cfg["vocab_size"])
    return {"L": int(cfg["num_hidden_layers"]), "D": d, "H": h,
            "Hkv": int(cfg["num_key_value_heads"]), "Dh": dh,
            "F": int(cfg["intermediate_size"]), "V": v,
            "Vpad": -(-v // LM_HEAD_PAD) * LM_HEAD_PAD,
            "qkv_bias": int(bool(cfg.get("qkv_bias", False)))}


def int4_matmuls(cfg: Dict[str, Any]) -> List[Tuple[str, int, int, int]]:
    """Every int4 weight matrix a forward pass multiplies by, as
    ``(name, K, N, times per pass)``. q/k/v are counted as one K x
    (H + 2 Hkv) Dh product and gate/up as one K x 2F product: fusing or
    splitting them changes the number of kernel calls, not the bytes or
    operations."""
    w = widths(cfg)
    qkv = (w["H"] + 2 * w["Hkv"]) * w["Dh"]
    return [("qkv", w["D"], qkv, w["L"]),
            ("attn_out", w["H"] * w["Dh"], w["D"], w["L"]),
            ("gate_up", w["D"], 2 * w["F"], w["L"]),
            ("down", w["F"], w["D"], w["L"]),
            ("lm_head", w["D"], w["Vpad"], 1)]


def param_bytes(cfg: Dict[str, Any]) -> int:
    """Stored bytes of the served tree: packed int4 payloads with one float32
    scale per output channel, bf16 embedding, norms and q/k/v biases."""
    w = widths(cfg)
    total = 0
    for _name, k, n, times in int4_matmuls(cfg):
        total += times * (k * n // 2 + 4 * n)
    total += w["V"] * w["D"] * 2                      # tok_emb
    total += (2 * w["L"] + 1) * w["D"] * 2            # ln1, ln2, final norm
    if w["qkv_bias"]:
        total += w["L"] * (w["H"] + 2 * w["Hkv"]) * w["Dh"] * 2
    return total


def kv_bytes_per_token(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    w = widths(cfg)
    return 2 * w["L"] * w["Hkv"] * w["Dh"] * kv_itemsize


def int4_step_cost(cfg: Dict[str, Any], rows: int) -> Dict[str, float]:
    """Bytes moved and operations of ALL int4 matmuls of one forward pass
    over ``rows`` token rows (a decode step: rows = the decode batch, i.e.
    ``max_slots`` — the program computes every slot, live or not): each
    weight byte and scale read once, bf16 activations in and out."""
    nbytes = flops = 0.0
    for _name, k, n, times in int4_matmuls(cfg):
        nbytes += times * (k * n / 2 + 4 * n + 2 * rows * (k + n))
        flops += times * 2.0 * rows * k * n
    return {"bytes": nbytes, "flops": flops}


def roofline_seconds(cost: Dict[str, float], peaks: Dict[str, float]
                     ) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_mem = cost["bytes"] / peaks["hbm_bytes_per_s"]
    t_cmp = cost["flops"] / peaks["bf16_flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_cmp else (t_cmp, "flops")
