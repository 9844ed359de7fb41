"""Counts of the ``xing4_mhc`` family (Xing4.0-29B-A4B) as ONE chip of its
stated deployment holds it (``ep_size`` 1: every layer whole): MLA with a
compressed query in every layer, a dense SwiGLU MLP in the leading layers,
``n_routed_experts`` routed experts (all held) plus one shared expert in the
rest, two mHC maps a layer, the whole vocabulary. HF ``config.json`` key
names; the kept layers are ``kept_layers`` (published indices). Stored in
``serve.dtype`` except the router, the expert bias and mHC's tensors
(float32)."""

from typing import Any, Dict, List, Tuple

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
CACHE = ("576 latent values a token (512 normalised c | 64 rotated k_rope) "
         "for EVERY layer as stored, shared by the heads; no per-sequence "
         "state")
SCOPE_READERS = "scopes_mhc"       # the module under lib/ (lib/families.py)


def widths(cfg: Dict[str, Any]) -> Dict[str, int]:
    kept = [int(i) for i in cfg["kept_layers"]]
    dense = [i for i in kept if i < int(cfg["first_k_dense_replace"])]
    n = int(cfg["hc_mult"])
    return {
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "F": int(cfg["intermediate_size"]),
        "Fm": int(cfg["moe_intermediate_size"]),
        "Fs": int(cfg["moe_intermediate_size"]) * int(cfg["n_shared_experts"]),
        "V": int(cfg["vocab_size"]), "E": int(cfg["n_routed_experts"]),
        "k": int(cfg["num_experts_per_tok"]),
        "qr": int(cfg["q_lora_rank"]), "rank": int(cfg["kv_lora_rank"]),
        "dn": int(cfg["qk_nope_head_dim"]), "dr": int(cfg["qk_rope_head_dim"]),
        "dv": int(cfg["v_head_dim"]), "n": n, "maps": n * n + 2 * n,
        "L": len(kept), "L_dense": len(dense), "L_moe": len(kept) - len(dense)}


def stored_dtype(cfg: Dict[str, Any]) -> str:
    return str(cfg["serve"].get("dtype", "bfloat16"))


def mla_matrices(cfg: Dict[str, Any]) -> List[Tuple[str, int, int]]:
    """One layer's five MLA matrices, ``(name, K, N)``."""
    w = widths(cfg)
    return [("mla_q_a", w["D"], w["qr"]),
            ("mla_q_b", w["qr"], w["H"] * (w["dn"] + w["dr"])),
            ("mla_kva", w["D"], w["rank"] + w["dr"]),
            ("mla_kvb", w["rank"], w["H"] * (w["dn"] + w["dv"])),
            ("mla_out", w["H"] * w["dv"], w["D"])]


def weight_matmuls(cfg: Dict[str, Any]
                   ) -> List[Tuple[str, int, int, float, str]]:
    """``(name, K, N, times per pass, dtype)`` for ONE token's forward pass.
    An expert matrix is multiplied only for the tokens routed to it: a token
    has ``k`` choices, all on held experts, so the expert matrices count
    ``L_moe * k`` times a token, never ``L_moe * E``."""
    w = widths(cfg)
    dt = stored_dtype(cfg)
    routed = w["L_moe"] * w["k"]
    return [(name, k, n, w["L"], dt) for name, k, n in mla_matrices(cfg)] + [
        ("mhc_phi", w["n"] * w["D"], w["maps"], 2 * w["L"], "float32"),
        ("dense_gate_up", w["D"], 2 * w["F"], w["L_dense"], dt),
        ("dense_down", w["F"], w["D"], w["L_dense"], dt),
        ("router", w["D"], w["E"], w["L_moe"], "float32"),
        ("shared_gate_up", w["D"], 2 * w["Fs"], w["L_moe"], dt),
        ("shared_down", w["Fs"], w["D"], w["L_moe"], dt),
        ("expert_gate_up", w["D"], 2 * w["Fm"], routed, dt),
        ("expert_down", w["Fm"], w["D"], routed, dt),
        ("lm_head", w["D"], w["V"], 1, dt)]


def expert_bytes(cfg: Dict[str, Any]) -> int:
    """Stored bytes of ONE routed expert (gate, up, down)."""
    w = widths(cfg)
    return 3 * w["D"] * w["Fm"] * ITEMSIZE[stored_dtype(cfg)]


def param_bytes(cfg: Dict[str, Any]) -> int:
    """Every tensor of the served tree once: the matrices above with every
    expert, the embedding, norms, the float32 vectors."""
    w = widths(cfg)
    item = ITEMSIZE[stored_dtype(cfg)]
    total = 0
    for name, k, n, times, dt in weight_matmuls(cfg):
        if name.startswith("expert_"):
            times = w["L_moe"] * w["E"]
        total += k * n * times * ITEMSIZE[dt]
    total += w["V"] * w["D"] * item                       # tok_emb
    total += (2 * w["L"] + 1) * w["D"] * item             # ln1, ln2, final
    total += w["L"] * (w["qr"] + w["rank"]) * item        # q_norm, kv_norm
    total += 2 * w["L"] * (3 + w["maps"]) * 4             # mHC alpha, bias
    total += w["L_moe"] * w["E"] * 4                      # expert bias
    return total


def kv_bytes_per_token(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    w = widths(cfg)
    return w["L"] * (w["rank"] + w["dr"]) * kv_itemsize


def expert_stream_cost(cfg: Dict[str, Any], experts_touched: float,
                       rows: float) -> Dict[str, float]:
    """Bytes and operations of the routed experts' two grouped products
    over decode steps: ``experts_touched`` distinct (layer, expert) pairs
    that got a row, each expert's three matrices read once; ``rows``
    (token, choice) pairs in and out. Counts touched experts, never all
    held."""
    w = widths(cfg)
    act = rows * (2 * w["D"] + 4 * w["D"] + 2 * 2 * w["Fm"] + 2 * w["Fm"])
    return {"bytes": experts_touched * expert_bytes(cfg) + act,
            "flops": 2.0 * rows * 3 * w["D"] * w["Fm"]}


def mla_decode_cost(cfg: Dict[str, Any], context_rows: float,
                    kv_itemsize: int = 2) -> Dict[str, float]:
    """The latent rows a run's decode steps attended to, in ALL layers:
    ``context_rows`` = the rows of the live sequences summed over the steps
    (counter ``mla.decode_context_rows``: live rows, never the table), each
    576 values read once a layer. The five matrices of a layer are NOT
    counted: the trace charges part of their read to ops outside the
    ``attn.mla`` scope, and a share's bytes and seconds must be of the same
    work. Operations: the absorbed scores and values over the context."""
    w = widths(cfg)
    width = w["rank"] + w["dr"]
    return {"bytes": w["L"] * context_rows * width * kv_itemsize,
            "flops": w["L"] * context_rows * w["H"]
            * (2.0 * width + 2.0 * w["rank"])}
