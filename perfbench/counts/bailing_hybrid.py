"""Counts of the ``bailing_hybrid`` family (Ling-3.0-flash) as ONE chip of
its stated deployment holds it: KDA and MLA attention layers, a dense SwiGLU
MLP in the leading layers, routed experts of which ``num_experts`` are held
here (``num_experts_published`` is the router's width) plus one shared
expert, a ``vocab_size`` slice. HF ``config.json`` key names; the kept
layers are ``kept_layers`` (published indices). Stored in ``serve.dtype``
except the router, the expert bias, ``A_log`` and ``dt_bias`` (float32)."""

from typing import Any, Dict, List, Tuple

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
CACHE = ("576 latent values a token for each MLA layer as stored (padding "
         "counted); KDA layers hold S and a 3-row conv tail per slot, "
         "state_bytes_per_slot(cfg)")
SCOPE_READERS = "scopes"       # the module under lib/ (lib/families.py)


def widths(cfg: Dict[str, Any]) -> Dict[str, int]:
    h = int(cfg["num_attention_heads"])
    kept = [int(i) for i in cfg["kept_layers"]]
    mla = [i for i in kept if (i + 1) % int(cfg["layer_group_size"]) == 0]
    dense = [i for i in kept if i < int(cfg["first_k_dense_replace"])]
    return {
        "D": int(cfg["hidden_size"]), "H": h, "Dh": int(cfg["head_dim"]),
        "HK": h * int(cfg["head_dim"]), "F": int(cfg["intermediate_size"]),
        "Fm": int(cfg["moe_intermediate_size"]),
        "Fs": int(cfg["moe_shared_expert_intermediate_size"]),
        "V": int(cfg["vocab_size"]), "E": int(cfg["num_experts_published"]),
        "held": int(cfg["num_experts"]), "k": int(cfg["num_experts_per_tok"]),
        "rank": int(cfg["kv_lora_rank"]), "dn": int(cfg["qk_nope_head_dim"]),
        "dr": int(cfg["qk_rope_head_dim"]), "dv": int(cfg["v_head_dim"]),
        "taps": int(cfg["short_conv_kernel_size"]),
        "L": len(kept), "L_mla": len(mla), "L_kda": len(kept) - len(mla),
        "L_dense": len(dense), "L_moe": len(kept) - len(dense)}


def stored_dtype(cfg: Dict[str, Any]) -> str:
    return str(cfg["serve"].get("dtype", "bfloat16"))


def weight_matmuls(cfg: Dict[str, Any], routed_share: float = None
                   ) -> List[Tuple[str, int, int, float, str]]:
    """``(name, K, N, times per pass, dtype)`` for ONE token's forward pass.
    An expert matrix is multiplied only for the tokens routed to it: of a
    token's ``k`` choices over ``E`` experts, ``routed_share`` land on the
    ``held`` ones here (held / E when routing is even), so the expert
    matrices count ``L_moe * k * routed_share`` times a token, never
    ``L_moe * held``."""
    w = widths(cfg)
    dt = stored_dtype(cfg)
    share = w["held"] / w["E"] if routed_share is None else routed_share
    routed = w["L_moe"] * w["k"] * share
    return [
        ("kda_qkv", w["D"], 3 * w["HK"], w["L_kda"], dt),
        ("kda_decay_a", w["D"], w["HK"], w["L_kda"], dt),
        ("kda_out_gate", w["D"], w["HK"], w["L_kda"], dt),
        ("kda_beta", w["D"], w["H"], w["L_kda"], dt),
        ("kda_out", w["HK"], w["D"], w["L_kda"], dt),
        ("mla_q", w["D"], w["H"] * (w["dn"] + w["dr"]), w["L_mla"], dt),
        ("mla_kva", w["D"], w["rank"] + w["dr"], w["L_mla"], dt),
        ("mla_kvb", w["rank"], w["H"] * (w["dn"] + w["dv"]), w["L_mla"], dt),
        ("mla_gate", w["D"], w["H"], w["L_mla"], dt),
        ("mla_out", w["H"] * w["dv"], w["D"], w["L_mla"], dt),
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
    HELD expert, the embedding, conv taps, norms, the float32 vectors."""
    w = widths(cfg)
    item = ITEMSIZE[stored_dtype(cfg)]
    total = 0
    for name, k, n, times, dt in weight_matmuls(cfg):
        if name.startswith("expert_"):
            times = w["L_moe"] * w["held"]
        total += k * n * times * ITEMSIZE[dt]
    total += w["V"] * w["D"] * item                       # tok_emb
    total += (2 * w["L"] + 1) * w["D"] * item             # ln1, ln2, final
    total += w["L_kda"] * (w["taps"] * 3 * w["HK"] + w["Dh"]) * item
    total += w["L_kda"] * (w["HK"] + w["H"]) * 4          # dt_bias, A_log
    total += w["L_mla"] * w["rank"] * item                # kv_norm
    total += w["L_moe"] * w["E"] * 4                      # expert bias
    return total


def kv_bytes_per_token(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    w = widths(cfg)
    return w["L_mla"] * (w["rank"] + w["dr"]) * kv_itemsize


def state_bytes_per_slot(cfg: Dict[str, Any]) -> int:
    """What a KDA layer keeps per SEQUENCE whatever its length: S [H, Dh,
    Dh] float32 and the last ``taps - 1`` pre-convolution rows."""
    w = widths(cfg)
    return w["L_kda"] * (w["H"] * w["Dh"] * w["Dh"] * 4
                         + (w["taps"] - 1) * 3 * w["HK"]
                         * ITEMSIZE[stored_dtype(cfg)])


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


def kda_step_cost(cfg: Dict[str, Any], rows: float) -> Dict[str, float]:
    """One decode step of the KDA recurrence over ``rows`` live sequences,
    per layer: S read and written once in float32 (projections not
    counted: they are weight_matmuls)."""
    w = widths(cfg)
    s = w["H"] * w["Dh"] * w["Dh"]
    return {"bytes": rows * 2 * s * 4.0, "flops": rows * 6.0 * s}


def kda_chunk_cost(cfg: Dict[str, Any], tokens: float, chunk: int = 64
                   ) -> Dict[str, float]:
    """The chunked prefill recurrence per layer over ``tokens`` tokens:
    per chunk and head the A and B products, the triangular solve, T V, T
    K~, and the three state products."""
    w = widths(cfg)
    per_chunk = w["H"] * (2.0 * chunk * chunk * w["Dh"] * 4
                          + chunk ** 3 / 3.0 * 2
                          + 2.0 * chunk * w["Dh"] * w["Dh"] * 3)
    return {"bytes": tokens * 5 * w["HK"] * 4.0,
            "flops": tokens / chunk * per_chunk}


def mla_decode_cost(cfg: Dict[str, Any], rows: float, context: float
                    ) -> Dict[str, float]:
    """Absorbed latent attention of one decode step, per layer: each row
    reads its ``context`` cached rows of rank + dr values once."""
    w = widths(cfg)
    width = w["rank"] + w["dr"]
    return {"bytes": rows * context * width * 2.0,
            "flops": rows * w["H"] * context * (2.0 * width
                                                + 2.0 * w["rank"])}
