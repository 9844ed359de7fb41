"""Counts of the ``swa_moe`` family (Mellum2-12B-A2.5B) as ONE chip of its
stated deployment holds it: every kept layer whole (all heads, all
``num_experts`` experts, the whole vocabulary). ``layer_types`` (its first
``num_hidden_layers`` entries: the kept layers) says which are sliding-window
attention and which full attention; every layer has the routed experts and
none a shared one. HF ``config.json`` key names. Stored in ``serve.dtype``
except the router (float32)."""

from typing import Any, Dict, List, Tuple

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
CACHE = ("K|V rows of 2 x 4 x 128 values (2,048 B) a token a layer; the 3 "
         "FULL-attention layers of the 12 kept hold every row, the 9 "
         "SLIDING layers stop growing at 1,024 rows: their pages go back "
         "to a free list as the window passes them, 10 pages of 128 a slot "
         "at most: window_bytes_per_slot(cfg)")
SCOPE_READERS = "scopes_swa"       # the module under lib/ (lib/families.py)


def widths(cfg: Dict[str, Any]) -> Dict[str, int]:
    # the file keeps the published list whole; this stage runs its first
    # num_hidden_layers entries
    types = list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]
    return {
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "Hkv": int(cfg["num_key_value_heads"]), "Dh": int(cfg["head_dim"]),
        "Fm": int(cfg["moe_intermediate_size"]),
        "V": int(cfg["vocab_size"]), "E": int(cfg["num_experts"]),
        "k": int(cfg["num_experts_per_tok"]),
        "window": int(cfg["sliding_window"]),
        "L": len(types), "L_full": types.count("full_attention"),
        "L_swa": types.count("sliding_attention")}


def stored_dtype(cfg: Dict[str, Any]) -> str:
    return str(cfg["serve"].get("dtype", "bfloat16"))


def weight_matmuls(cfg: Dict[str, Any]
                   ) -> List[Tuple[str, int, int, float, str]]:
    """``(name, K, N, times per pass, dtype)`` for ONE token's forward pass.
    An expert matrix is multiplied only for the tokens routed to it: a token
    has ``k`` choices, all on held experts, so the expert matrices count
    ``L * k`` times a token, never ``L * E``."""
    w = widths(cfg)
    dt = stored_dtype(cfg)
    D, L = w["D"], w["L"]
    return [
        ("attn_q", D, w["H"] * w["Dh"], L, dt),
        ("attn_k", D, w["Hkv"] * w["Dh"], L, dt),
        ("attn_v", D, w["Hkv"] * w["Dh"], L, dt),
        ("attn_out", w["H"] * w["Dh"], D, L, dt),
        ("router", D, w["E"], L, "float32"),
        ("expert_gate_up", D, 2 * w["Fm"], L * w["k"], dt),
        ("expert_down", w["Fm"], D, L * w["k"], dt),
        ("lm_head", D, w["V"], 1, dt)]


def expert_bytes(cfg: Dict[str, Any]) -> int:
    """Stored bytes of ONE routed expert (gate, up, down)."""
    w = widths(cfg)
    return 3 * w["D"] * w["Fm"] * ITEMSIZE[stored_dtype(cfg)]


def attention_weight_bytes(cfg: Dict[str, Any]) -> int:
    """One layer's four attention matrices, its two norms and its router."""
    w = widths(cfg)
    item = ITEMSIZE[stored_dtype(cfg)]
    return ((2 * w["H"] + 2 * w["Hkv"]) * w["Dh"] * w["D"] * item
            + 2 * w["D"] * item + w["D"] * w["E"] * 4)


def param_bytes(cfg: Dict[str, Any]) -> int:
    """Every tensor of the served tree once: the matrices above with every
    expert, the embedding, the norms."""
    w = widths(cfg)
    item = ITEMSIZE[stored_dtype(cfg)]
    return (w["L"] * (attention_weight_bytes(cfg)
                      + w["E"] * expert_bytes(cfg))
            + 2 * w["V"] * w["D"] * item          # tok_emb, lm_head
            + w["D"] * item)                      # final norm


def kv_row_bytes(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    """One token's K|V row of ONE layer, either kind."""
    w = widths(cfg)
    return 2 * w["Hkv"] * w["Dh"] * kv_itemsize


def kv_bytes_per_token(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    """The part of the cache that grows by the token: the full layers'."""
    return widths(cfg)["L_full"] * kv_row_bytes(cfg, kv_itemsize)


def window_pages_per_slot(cfg: Dict[str, Any]) -> int:
    page = int(cfg["serve"]["page_size"])
    return -(-widths(cfg)["window"] // page) + 2


def window_bytes_per_slot(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    """What the sliding layers hold per live sequence at most, whatever its
    length: ``window_pages_per_slot`` pages in each of them."""
    return (widths(cfg)["L_swa"] * window_pages_per_slot(cfg)
            * int(cfg["serve"]["page_size"]) * kv_row_bytes(cfg, kv_itemsize))


def attn_decode_cost(cfg: Dict[str, Any], full_rows: float,
                     window_rows: float, kv_itemsize: int = 2
                     ) -> Dict[str, float]:
    """The K|V rows a run's decode steps attended to, in ALL layers of both
    kinds: ``full_rows`` / ``window_rows`` = the rows of the live sequences
    summed over the steps, a layer of the kind (counters
    ``attn.full_context_rows``: the whole context; ``attn.window_context_
    rows``: min(context, window); live rows, never the table), each read
    once a layer. Operations: scores and values over those rows."""
    w = widths(cfg)
    rows = w["L_full"] * full_rows + w["L_swa"] * window_rows
    return {"bytes": rows * kv_row_bytes(cfg, kv_itemsize),
            "flops": rows * 4.0 * w["H"] * w["Dh"]}


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


def decode_stream_cost(cfg: Dict[str, Any], steps: float,
                       experts_touched: float, expert_rows: float,
                       full_rows: float, window_rows: float,
                       live_rows: float) -> Dict[str, float]:
    """Least bytes of ``steps`` whole decode steps: the experts that got a
    row (never all held), every layer's attention matrices, norms and
    router and the head once a step, the live K|V rows of both kinds (full
    layers at the context, sliding layers at min(context, window)).
    ``live_rows`` = (live row, step) pairs. Operations: two a weight value
    a row it multiplies, plus the attention's."""
    w = widths(cfg)
    item = ITEMSIZE[stored_dtype(cfg)]
    per_step = (w["L"] * attention_weight_bytes(cfg)
                + w["V"] * w["D"] * item + w["D"] * item)
    ex = expert_stream_cost(cfg, experts_touched, expert_rows)
    kv = attn_decode_cost(cfg, full_rows, window_rows)
    return {"bytes": steps * per_step + ex["bytes"] + kv["bytes"],
            "flops": live_rows * per_step / item * 2.0 + ex["flops"]
            + kv["flops"]}
