"""Counts of the ``gdn_hybrid`` family (Olmo-Hybrid-7B) as ONE chip of its
stated deployment holds it: every kept layer whole (all heads, the whole
vocabulary). ``layer_types`` (its first ``num_hidden_layers`` entries: the
kept layers) says which are Gated DeltaNet
(``linear_attention``) and which full attention; every layer has the dense
SwiGLU MLP. HF ``config.json`` key names. Stored in ``serve.dtype`` except
``A_log`` and ``dt_bias`` (float32)."""

from typing import Any, Dict, List, Tuple

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
CACHE = ("K|V rows of 2 x 30 x 128 values a token for the FULL-attention "
         "layers only (4 of the 16 kept); the 12 Gated-DeltaNet layers hold "
         "per SLOT a 30 x 96 x 192 float32 state (2.21 MB) and a 3-row conv "
         "tail of 11,520 values instead: state_bytes_per_slot(cfg)")
SCOPE_READERS = "scopes_gdn"       # the module under lib/ (lib/families.py)


def widths(cfg: Dict[str, Any]) -> Dict[str, int]:
    # the file keeps the published list whole; this stage runs its first
    # num_hidden_layers entries
    types = list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]
    h = int(cfg["num_attention_heads"])
    hl = int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    return {
        "D": int(cfg["hidden_size"]), "H": h,
        "Dh": int(cfg["hidden_size"]) // h,
        "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
        "Hl": hl, "dk": dk, "dv": dv,
        "taps": int(cfg["linear_conv_kernel_dim"]),
        "C": hl * (2 * dk + dv),
        "L": len(types), "L_full": types.count("full_attention"),
        "L_gdn": types.count("linear_attention")}


def stored_dtype(cfg: Dict[str, Any]) -> str:
    return str(cfg["serve"].get("dtype", "bfloat16"))


def weight_matmuls(cfg: Dict[str, Any]
                   ) -> List[Tuple[str, int, int, float, str]]:
    """``(name, K, N, times per pass, dtype)`` for ONE token's forward pass."""
    w = widths(cfg)
    dt = stored_dtype(cfg)
    D, g, f = w["D"], w["L_gdn"], w["L_full"]
    return [
        ("gdn_q", D, w["Hl"] * w["dk"], g, dt),
        ("gdn_k", D, w["Hl"] * w["dk"], g, dt),
        ("gdn_v", D, w["Hl"] * w["dv"], g, dt),
        ("gdn_gate", D, w["Hl"] * w["dv"], g, dt),
        ("gdn_a", D, w["Hl"], g, dt), ("gdn_b", D, w["Hl"], g, dt),
        ("gdn_out", w["Hl"] * w["dv"], D, g, dt),
        ("full_q", D, D, f, dt), ("full_k", D, D, f, dt),
        ("full_v", D, D, f, dt), ("full_out", D, D, f, dt),
        ("mlp_gate_up", D, 2 * w["F"], w["L"], dt),
        ("mlp_down", w["F"], D, w["L"], dt),
        ("lm_head", D, w["V"], 1, dt)]


def param_bytes(cfg: Dict[str, Any]) -> int:
    """Every tensor of the served tree once."""
    w = widths(cfg)
    item = ITEMSIZE[stored_dtype(cfg)]
    total = sum(k * n * times * ITEMSIZE[dt]
                for _name, k, n, times, dt in weight_matmuls(cfg))
    total += w["V"] * w["D"] * item                        # tok_emb
    total += (2 * w["L"] + 1) * w["D"] * item              # output norms, final
    total += w["L_full"] * 2 * w["D"] * item               # q_norm, k_norm
    total += w["L_gdn"] * (w["taps"] * w["C"] + w["dv"]) * item   # conv, o_norm
    total += w["L_gdn"] * 2 * w["Hl"] * 4                  # A_log, dt_bias
    return total


def kv_row_bytes(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    """One token's K|V row of ONE full-attention layer."""
    w = widths(cfg)
    return 2 * w["H"] * w["Dh"] * kv_itemsize


def kv_bytes_per_token(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    """The part of the cache that grows by the token: the full layers'."""
    return widths(cfg)["L_full"] * kv_row_bytes(cfg, kv_itemsize)


def state_bytes_per_slot(cfg: Dict[str, Any]) -> int:
    """What the Gated-DeltaNet layers hold per live sequence, whatever its
    length: S float32 and the conv tail in the stored dtype."""
    w = widths(cfg)
    return w["L_gdn"] * (
        w["Hl"] * w["dk"] * w["dv"] * 4
        + (w["taps"] - 1) * w["C"] * ITEMSIZE[stored_dtype(cfg)])


def full_decode_cost(cfg: Dict[str, Any], context_rows: float,
                     kv_itemsize: int = 2) -> Dict[str, float]:
    """The K|V rows a run's decode steps attended to, in ALL full layers:
    ``context_rows`` = the rows of the live sequences summed over the steps
    (counter ``attn.full_context_rows``: live rows, never the table), each
    read once a layer. Operations: scores and values over those rows."""
    w = widths(cfg)
    return {"bytes": w["L_full"] * context_rows * kv_row_bytes(cfg,
                                                              kv_itemsize),
            "flops": w["L_full"] * context_rows * 4.0 * w["H"] * w["Dh"]}


def state_cost(cfg: Dict[str, Any], rows_updated: float) -> Dict[str, float]:
    """The recurrent states the decode steps moved, in ALL Gated-DeltaNet
    layers: ``rows_updated`` = (live row, step) pairs (counter
    ``state.rows_updated``), each state and conv tail read once and written
    once. Operations: decay, k^T S, the rank-one update and q^T S, two
    each over the state's values."""
    w = widths(cfg)
    return {"bytes": 2.0 * rows_updated * state_bytes_per_slot(cfg),
            "flops": w["L_gdn"] * rows_updated * 8.0
            * w["Hl"] * w["dk"] * w["dv"]}


def decode_stream_cost(cfg: Dict[str, Any], steps: float,
                       context_rows: float, rows_updated: float
                       ) -> Dict[str, float]:
    """Least bytes of ``steps`` whole decode steps: every kept weight and
    the head once a step (everything of the tree but the embedding table,
    of which a step reads 8 rows), the live K|V rows, the live states read
    and written. Operations: two a weight value a live row, plus the two
    caches'."""
    w = widths(cfg)
    item = ITEMSIZE[stored_dtype(cfg)]
    weights = param_bytes(cfg) - w["V"] * w["D"] * item
    kv, st = full_decode_cost(cfg, context_rows), state_cost(cfg,
                                                             rows_updated)
    return {"bytes": steps * weights + kv["bytes"] + st["bytes"],
            "flops": rows_updated * weights / item * 2.0 + kv["flops"]
            + st["flops"]}
