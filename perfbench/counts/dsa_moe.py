"""Counts of the ``dsa_moe`` family (the text decoder of Keye-VL-2.0-30B-A3B)
as ONE chip of its stated deployment holds it: every kept layer whole (all
heads, the indexer, all ``num_experts`` experts, the whole vocabulary). Every
layer is grouped-query attention over the rows its indexer (``sa_config``)
selected, with the routed experts and no shared one. HF ``config.json`` key
names. Stored in ``serve.dtype`` except the router (float32)."""

from typing import Any, Dict, List, Tuple

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
CACHE = ("TWO rows a token a layer on ONE page table: a K|V row of 2 x 4 x "
         "128 values (2,048 B) and an index key of 64 values (128 B), 2,176 "
         "B a layer, 13,056 B a token over the 6 kept layers; a decode step "
         "reads every live index key and gathers at most 2,048 K|V rows a "
         "sequence a layer")
SCOPE_READERS = "scopes_dsa"       # the module under lib/ (lib/families.py)


def widths(cfg: Dict[str, Any]) -> Dict[str, int]:
    sa = cfg["sa_config"]
    return {
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "Hkv": int(cfg["num_key_value_heads"]), "Dh": int(cfg["head_dim"]),
        "Fm": int(cfg["moe_intermediate_size"]),
        "V": int(cfg["vocab_size"]), "E": int(cfg["num_experts"]),
        "k": int(cfg["num_experts_per_tok"]),
        "L": int(cfg["num_hidden_layers"]),
        "Hi": int(sa["indexer_num_heads"]), "Di": int(sa["indexer_head_dim"]),
        "topk": int(sa["topk"])}


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
        ("index_q", D, w["Hi"] * w["Di"], L, dt),
        ("index_k", D, w["Di"], L, dt),
        ("index_w", D, w["Hi"], L, dt),
        ("router", D, w["E"], L, "float32"),
        ("expert_gate_up", D, 2 * w["Fm"], L * w["k"], dt),
        ("expert_down", w["Fm"], D, L * w["k"], dt),
        ("lm_head", D, w["V"], 1, dt)]


def expert_bytes(cfg: Dict[str, Any]) -> int:
    """Stored bytes of ONE routed expert (gate, up, down)."""
    w = widths(cfg)
    return 3 * w["D"] * w["Fm"] * ITEMSIZE[stored_dtype(cfg)]


def attention_weight_bytes(cfg: Dict[str, Any]) -> int:
    """One layer outside its experts: the four attention matrices, the
    indexer's three, the norms (two of D, q's and k's of Dh, the index
    key's scale and bias of Di) and the router."""
    w = widths(cfg)
    item = ITEMSIZE[stored_dtype(cfg)]
    values = ((2 * w["H"] + 2 * w["Hkv"]) * w["Dh"] * w["D"]
              + (w["Hi"] * w["Di"] + w["Di"] + w["Hi"]) * w["D"]
              + 2 * w["D"] + 2 * w["Dh"] + 2 * w["Di"])
    return values * item + w["D"] * w["E"] * 4


def param_bytes(cfg: Dict[str, Any]) -> int:
    """Every tensor of the served tree once: the matrices above with every
    expert, the embedding, the head, the final norm."""
    w = widths(cfg)
    item = ITEMSIZE[stored_dtype(cfg)]
    return (w["L"] * (attention_weight_bytes(cfg)
                      + w["E"] * expert_bytes(cfg))
            + 2 * w["V"] * w["D"] * item          # tok_emb, lm_head
            + w["D"] * item)                      # final norm


def kv_row_bytes(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    """One token's K|V row of ONE layer."""
    w = widths(cfg)
    return 2 * w["Hkv"] * w["Dh"] * kv_itemsize


def index_key_bytes(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    """One token's index key of ONE layer."""
    return widths(cfg)["Di"] * kv_itemsize


def kv_bytes_per_token(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    """What a token adds to the cache: a K|V row and an index key a layer."""
    return widths(cfg)["L"] * (kv_row_bytes(cfg, kv_itemsize)
                               + index_key_bytes(cfg, kv_itemsize))


def sparse_decode_cost(cfg: Dict[str, Any], index_rows: float,
                       selected_rows: float, kv_itemsize: int = 2
                       ) -> Dict[str, float]:
    """The indexer's and the attention's reads of decode steps, in ALL
    layers: ``index_rows`` index keys read and ``selected_rows`` K|V rows
    gathered, both summed over the steps, a layer. Operations: the index
    scores of those keys and the attention's scores and values over those
    rows."""
    w = widths(cfg)
    return {"bytes": w["L"] * (index_rows * index_key_bytes(cfg, kv_itemsize)
                               + selected_rows * kv_row_bytes(cfg,
                                                              kv_itemsize)),
            "flops": w["L"] * (index_rows * 2.0 * w["Hi"] * w["Di"]
                               + selected_rows * 4.0 * w["H"] * w["Dh"])}


def index_kernel_cost(cfg: Dict[str, Any], pairs: float
                      ) -> Dict[str, float]:
    """Runs of the index-score kernel (``index_scores_flash``: a block of
    queries against a row's every key, a layer a run): ``pairs`` (query,
    key) pairs SCORED, summed over the runs, past the diagonal and the
    length too (the kernel scores the whole tile row)."""
    w = widths(cfg)
    return {"bytes": 0.0, "flops": pairs * 2.0 * w["Hi"] * w["Di"]}


# the prefill kernels' tile: ``ops/flash_prefill.py`` Q_BLOCK = K_BLOCK
PREFILL_BLOCK = 512


def sparse_prefill_least_pairs(cfg: Dict[str, Any], rows: int, chunk: int,
                               bucket: int, runs: int) -> float:
    """The FEWEST (query, key) pairs that ``runs`` runs of the masked
    prefill kernel at one shape (``rows`` x ``chunk`` queries x ``bucket``
    keys a run) can have computed: a profile names a run's shape and not
    the prompt's length, so every whole layer of ``bucket // chunk`` runs is
    taken at the shortest prompt the engine puts in that bucket (one token
    past the bucket below; ``serve``'s ``prefill_buckets`` and
    ``max_seq_len``), its blocks at or under the diagonal that start below
    that length; runs that do not fill a layer (a slice's edge) count
    nothing. Between 1 x and 4 x under what ran."""
    serve = cfg["serve"]
    buckets = sorted({int(b) for b in serve["prefill_buckets"]}
                     | {int(serve["max_seq_len"])})
    shortest = max([b for b in buckets if b < bucket], default=0) + 1
    nb = -(-shortest // PREFILL_BLOCK)
    layers = runs // (bucket // chunk)
    return float(rows * layers * (nb * (nb + 1) // 2) * PREFILL_BLOCK ** 2)


def sparse_prefill_kernel_cost(cfg: Dict[str, Any], pairs: float
                               ) -> Dict[str, float]:
    """The masked prefill kernel (``sparse_prefill_flash``) over ``pairs``
    (query, key) pairs of its live blocks: every head's score and value
    products. Bound by operations."""
    w = widths(cfg)
    return {"bytes": 0.0, "flops": pairs * 4.0 * w["H"] * w["Dh"]}


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
                       index_rows: float, selected_rows: float,
                       live_rows: float) -> Dict[str, float]:
    """Least bytes of ``steps`` whole decode steps: the experts that got a
    row (never all held), every layer's attention and indexer matrices,
    norms and router and the head once a step, the LIVE index keys and the
    K|V rows selected (min(context, top-k) a sequence, never the context).
    ``live_rows`` = (live row, step) pairs. Operations: two a weight value
    a row it multiplies, plus the indexer's and the attention's."""
    w = widths(cfg)
    item = ITEMSIZE[stored_dtype(cfg)]
    per_step = (w["L"] * attention_weight_bytes(cfg)
                + w["V"] * w["D"] * item + w["D"] * item)
    ex = expert_stream_cost(cfg, experts_touched, expert_rows)
    kv = sparse_decode_cost(cfg, index_rows, selected_rows)
    return {"bytes": steps * per_step + ex["bytes"] + kv["bytes"],
            "flops": live_rows * per_step / item * 2.0 + ex["flops"]
            + kv["flops"]}
