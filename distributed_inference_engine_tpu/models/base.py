"""Unified decoder-only transformer covering the GPT-2 and Llama families.

This is the real engine the reference never had — its ``FakeModel.predict``
is an asyncio sleep that echoes its input (``src/mock_models/fake_model.py:33-67``).
Here a single spec-driven forward serves both model families
(BASELINE.json configs[1-3]): GPT-2 = learned positions + LayerNorm + GELU
MLP + biases + tied embeddings; Llama = RoPE + RMSNorm + SwiGLU + GQA, no
biases.

TPU-first design decisions:

- **Stacked layers + lax.scan.** All per-layer weights carry a leading
  ``[n_layers, ...]`` axis and the forward scans over them: XLA traces and
  compiles ONE layer body instead of unrolling N copies (compile time stays
  flat as models grow), and the stacked layout is exactly what pipeline
  parallelism wants to split later.
- **Params are a plain pytree** (nested dict of arrays), not framework
  module state: ``jax.sharding.NamedSharding`` annotations attach directly,
  the same tree feeds jit'd inference, the training step, and the checkpoint
  loader, and donation works without adapters.
- **Prefill and decode are separate functions** with different shapes —
  prefill attends over the prompt's fresh K/V ([B, T]), decode attends over
  the HBM cache ([B, S]) — so XLA compiles each for its own hot shape
  instead of one program with dynamic behavior.
- **bf16 weights/activations, fp32 softmax/norm/logits** — MXU-friendly
  matmuls with fp32 where accumulation error actually matters.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.attention import (
    cached_attention,
    causal_attention,
    suffix_attention,
)
from ..ops.norms import layer_norm, rms_norm
from ..ops.quant import QuantizedTensor, matmul_any, split_indexed_blocks
from ..ops.rope import apply_rope

Params = Dict[str, Any]


class _Seq(tuple):
    """A tuple that also equals the list a JSON round trip turns it into
    (a spec field stays hashable; a configuration file's list compares)."""

    def __eq__(self, other):
        return tuple.__eq__(self, tuple(other) if isinstance(other, list)
                            else other)

    def __ne__(self, other):
        return not self == other

    __hash__ = tuple.__hash__


class _Map(tuple):
    """Sorted (key, value) pairs that also equal the dict (or the list of
    pairs a JSON round trip makes of them) they describe: a nested group of
    a configuration file as a hashable spec field."""

    def __new__(cls, src=()):
        items = src.items() if isinstance(src, dict) else src
        return super().__new__(cls, sorted((str(k), v) for k, v in items))

    def __eq__(self, other):
        if isinstance(other, (dict, list)):
            other = _Map(other)
        return tuple.__eq__(self, other)

    def __ne__(self, other):
        return not self == other

    __hash__ = tuple.__hash__


@dataclass(frozen=True)
class ModelSpec:
    """Static architecture description; hashable so it can be a jit static arg."""

    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    max_seq_len: int = 2048
    pos_emb: str = "rope"          # "rope" | "learned"
    norm: str = "rmsnorm"          # "rmsnorm" | "layernorm"
    mlp: str = "swiglu"            # "swiglu" | "gelu" | "geglu"
    use_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # Mixture-of-experts (0 = dense). Experts replace the MLP; routing is
    # top-`experts_per_token` with static capacity (ops/moe.py).
    n_experts: int = 0
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    # Family variations beyond the GPT-2/Llama axes:
    qkv_bias: bool = False         # Qwen2: bias on q/k/v projections only
    head_dim_override: int = 0     # Gemma: head_dim decoupled from d_model/n_heads
    emb_scale: bool = False        # Gemma: embeddings scaled by sqrt(d_model)
    norm_plus_one: bool = False    # Gemma: RMSNorm applies (1 + weight)
    logit_softcap: float = 0.0     # Gemma-2: cap * tanh(logits / cap)
    # Mistral v0.1: window size, every layer's (0 = full attention); of a
    # per-layer spec, its "swa" layers' (token i sees i - window < j <= i)
    sliding_window: int = 0
    # Per-layer description (hybrid families, ``models/ling.py``). Empty =
    # the uniform decoder above: every layer softmax attention over K/V,
    # its MLP dense or routed by ``n_experts`` (``layer_plan`` derives it).
    # per layer "kda" | "mla" (models/ling.py, models/xing.py), "gdn" |
    # "full" (models/olmo_hybrid.py), "swa" | "full" (models/mellum.py) or
    # "full" alone with ``index_topk`` (models/keye.py)
    layer_kinds: Tuple[str, ...] = ()
    layer_mlps: Tuple[str, ...] = ()    # per layer "dense" | "moe"
    layer_ids: Tuple[int, ...] = ()     # published index of each kept layer
    # latent attention (MLA): the cache row is kv_lora_rank + qk_rope_head_dim
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # KDA linear attention: a [kda_head_dim, kda_head_dim] float32 state a
    # head and a conv tail of kda_conv - 1 rows, per sequence
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_lower_bound: float = -5.0
    # Gated DeltaNet linear attention ("gdn" layers): a [gdn_key_head_dim,
    # gdn_value_head_dim] float32 state a head (one decay a head) and a conv
    # tail of gdn_conv - 1 rows, per sequence; 0 = the spec has none. Its
    # "full" layers are softmax attention over K|V rows of n_kv_heads x
    # head_dim each, no rotary embedding.
    gdn_key_head_dim: int = 0
    gdn_value_head_dim: int = 0
    gdn_conv: int = 4
    # routed experts of a hybrid spec (ops/moe_routed.py): sigmoid scores,
    # expert bias, group-limited top-k over ALL n_experts, or a softmax
    # over all of them and its top-k renormalised (``moe_scoring``); this
    # chip computes experts [first, first + count) and, where shared_d_ff
    # is set, one shared expert
    moe_d_ff: int = 0
    shared_d_ff: int = 0
    moe_scoring: str = "sigmoid"   # "sigmoid" | "softmax"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    experts_held: Tuple[int, int] = (0, 0)   # (first expert, count)
    # MLA with a compressed query (0 = the query projected in one matrix)
    # and YaRN-scaled rotary frequencies (empty = plain RoPE): the
    # published ``rope_scaling`` group, ``ops/mla.py`` ``yarn_*``; of a
    # spec with "swa" layers, its "full" layers' table (the "swa" layers
    # rotate by plain RoPE at ``rope_theta``)
    q_lora_rank: int = 0
    rope_scaling: Tuple[Tuple[str, Any], ...] = ()
    # manifold-constrained hyper-connections (``ops/mhc.py``): the residual
    # is ``hc_mult`` streams, mixed by a map made doubly stochastic in
    # ``hc_sinkhorn_iters`` Sinkhorn-Knopp rounds (0 = one plain residual)
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 0.0
    hc_clamp_min: float = 0.0
    hc_clamp_max: float = 0.0
    # learned sparse attention (``models/keye.py``, ``ops/sparse_index.py``):
    # in every "full" layer an indexer of ``index_heads`` query heads of
    # ``index_head_dim`` over ONE index key a token scores the context, and
    # the layer's attention reads the ``index_topk`` rows of largest score
    # (0 = every row). The index keys are a second paged cache, of
    # ``index_head_dim`` lanes, on the K|V pages' own table.
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0

    def __post_init__(self) -> None:
        # hashable whatever a dict/JSON round trip handed in
        for name in ("layer_kinds", "layer_mlps", "layer_ids",
                     "experts_held"):
            object.__setattr__(self, name, _Seq(getattr(self, name)))
        object.__setattr__(self, "rope_scaling", _Map(self.rope_scaling))

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def layer_plan(self) -> Tuple[Tuple[str, str, int], ...]:
        """(attention kind, MLP kind, published layer index) per layer."""
        if self.layer_kinds:
            return tuple(zip(self.layer_kinds, self.layer_mlps,
                             self.layer_ids))
        mlp = "moe" if self.n_experts else "dense"
        return tuple(("attn", mlp, i) for i in range(self.n_layers))

    @property
    def paged_layers(self) -> int:
        """Layers whose cache grows by the token (pages)."""
        return sum(k not in ("kda", "gdn", "swa")
                   for k, _m, _i in self.layer_plan)

    @property
    def window_layers(self) -> int:
        """Layers whose cache stops growing at ``sliding_window`` rows: they
        keep pages of their own, freed as the window passes them."""
        return sum(k == "swa" for k, _m, _i in self.layer_plan)

    @property
    def state_layers(self) -> int:
        """Layers that keep a fixed-size recurrent state per sequence."""
        return sum(k in ("kda", "gdn") for k, _m, _i in self.layer_plan)

    @property
    def recurrent(self) -> bool:
        return self.state_layers > 0

    @property
    def cache_row_width(self) -> int:
        """Lanes of ONE paged pool row of one layer, a token's: a latent
        row (``kv_lora_rank + qk_rope_head_dim`` values and zero lanes up to
        whole 128-lane tiles: 576 -> 640, what the decode kernel copies and
        multiplies), K|V side by side (a per-layer spec has one pool), or
        one of K and V (a uniform spec has a pool each)."""
        if self.layer_kinds:
            latent = self.kv_lora_rank + self.qk_rope_head_dim
            return 2 * self.kv_row_lanes or -(-latent // 128) * 128
        return self.n_kv_heads * self.head_dim

    @property
    def kv_row_lanes(self) -> int:
        """Lanes of a K (or V) row of a per-layer spec whose paged layers
        are softmax attention over K/V; 0 where they keep latent rows (and
        for a uniform spec, whose pools are ``models/base.py``'s)."""
        return (self.n_kv_heads * self.head_dim
                if "full" in self.layer_kinds else 0)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def residual(self) -> str:
        """What stands around the sublayers: ``"mhc"`` (``hc_mult``
        hyper-connection streams) or ``"plain"``; the engine's metrics and
        the worker's device report name it."""
        return "mhc" if self.hc_mult else "plain"

    def validate(self) -> "ModelSpec":
        if not self.head_dim_override and self.d_model % self.n_heads:
            raise ValueError("d_model must divide by n_heads")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must divide by n_kv_heads")
        if self.pos_emb not in ("rope", "learned"):
            raise ValueError(f"unknown pos_emb {self.pos_emb}")
        if self.mlp not in ("swiglu", "gelu", "geglu"):
            raise ValueError(f"unknown mlp {self.mlp}")
        if self.sliding_window < 0:
            raise ValueError("sliding_window must be >= 0")
        if self.layer_kinds:
            n = self.n_layers
            if not (len(self.layer_kinds) == len(self.layer_mlps)
                    == len(self.layer_ids) == n):
                raise ValueError("layer_kinds / layer_mlps / layer_ids must "
                                 "each describe all n_layers layers")
            kinds = set(self.layer_kinds)
            if (kinds - {"kda", "mla"} and kinds - {"gdn", "full"}
                    and kinds - {"swa", "full"}):
                raise ValueError(
                    "a per-layer spec holds 'kda' and 'mla' layers "
                    "(models/ling.py, models/xing.py), 'gdn' and 'full' "
                    "ones (models/olmo_hybrid.py) or 'swa' and 'full' ones "
                    f"(models/mellum.py), not {sorted(kinds)}: no family "
                    "runs that mix")
            period = self.layer_kinds[:self.layer_kinds.index("full") + 1
                                      ] if "full" in kinds else ()
            if "swa" in kinds:
                if (not period or self.sliding_window < 1
                        or set(self.layer_mlps) != {"moe"}
                        or self.layer_kinds
                        != period * (n // len(period))):
                    raise ValueError(
                        "'swa' / 'full' layers come in whole periods of "
                        "sliding-window layers closed by a full one (the "
                        "tree is one period stacked over the periods), "
                        "with sliding_window >= 1 and routed experts in "
                        "every layer")
            elif self.index_topk:
                if (kinds != {"full"} or set(self.layer_mlps) != {"moe"}
                        or self.index_heads < 1 or self.index_head_dim < 1
                        or self.index_head_dim % 2):
                    raise ValueError(
                        "index_topk belongs to a spec whose layers are all "
                        "'full' attention over K|V rows with routed experts "
                        "(models/keye.py), with index_heads >= 1 and an "
                        "even index_head_dim >= 2")
            elif kinds & {"gdn", "full"}:
                if (not period or self.gdn_key_head_dim < 1
                        or self.gdn_value_head_dim < 1
                        or "moe" in self.layer_mlps
                        or self.layer_kinds
                        != period * (n // len(period))):
                    raise ValueError(
                        "'gdn' / 'full' layers come in whole periods of "
                        "gdn layers closed by a full one (the tree is one "
                        "period stacked over the periods), with "
                        "gdn_key_head_dim / gdn_value_head_dim set and a "
                        "dense MLP in every layer")
            if set(self.layer_mlps) - {"dense", "moe"}:
                raise ValueError(f"unknown layer_mlps {self.layer_mlps}")
            first, count = self.experts_held
            if "moe" in self.layer_mlps and not (
                    0 <= first and 1 <= count
                    and first + count <= self.n_experts):
                raise ValueError(
                    f"experts_held {self.experts_held} outside the "
                    f"{self.n_experts} routed experts")
            if self.n_experts % max(self.n_group, 1) or not (
                    1 <= self.topk_group <= self.n_group):
                raise ValueError("n_group must divide n_experts and "
                                 "topk_group lie in [1, n_group]")
            if self.moe_scoring not in ("sigmoid", "softmax"):
                raise ValueError(f"unknown moe_scoring {self.moe_scoring!r}")
        if self.index_topk < 0 or (self.index_topk
                                   and "full" not in self.layer_kinds):
            raise ValueError(
                "index_topk >= 1 belongs to a per-layer spec of 'full' "
                "layers (models/keye.py)")
        if self.q_lora_rank and set(self.layer_kinds) != {"mla"}:
            raise ValueError(
                "a compressed query (q_lora_rank) belongs to a per-layer "
                "spec whose layers are all MLA (models/xing.py)")
        if self.hc_mult and (self.q_lora_rank < 1
                             or self.hc_sinkhorn_iters < 1):
            raise ValueError(
                "hc_mult says which RESIDUAL a compressed-query MLA spec "
                "(q_lora_rank >= 1, models/xing.py) has: 0 the plain "
                "pre-norm one, n >= 1 that many mHC streams, which need "
                "hc_sinkhorn_iters >= 1")
        if self.n_experts:
            if not 1 <= self.experts_per_token <= self.n_experts:
                raise ValueError(
                    f"experts_per_token {self.experts_per_token} out of range "
                    f"for {self.n_experts} experts"
                )
            if self.use_bias:
                raise ValueError("MoE experts do not support biases")
        return self

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def replace(self, **changes: Any) -> "ModelSpec":
        """Frozen-dataclass update (``dataclasses.replace`` as a method —
        the checkpoint/HF loaders cap ``max_seq_len`` through this)."""
        return dataclasses.replace(self, **changes)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ModelSpec":
        from ..config import build_dataclass

        return build_dataclass(cls, d).validate()


# ------------------------------------------------- per-layer (hybrid) specs


# what ``layered_family`` returns: every name a family module defines
LAYERED_FAMILY = (
    "init_params", "init_state", "zero_state_slot",
    "forward_prefill_into_pages", "PREFILL_COUNTERS", "decode_context",
    "forward_decode_step", "DECODE_COUNTERS", "write_side")


def decode_sums(spec: ModelSpec, counts, ends) -> Dict[str, int]:
    """What one decode chunk of a per-layer spec adds to the counters no
    program counts, by name (a host sum: ``counts`` / ``ends`` are numpy
    [slots], the tokens each slot emitted in the chunk and its length at
    the chunk's end). A slot that emitted c tokens and ends at length e
    attended to e - c + 1 ... e rows of a paged layer (the cached ones and
    the chunk's own, its new one included), K|V rows or latent rows; in a
    sliding layer a token at position p sees ``min(p + 1, window)``; each
    token moves the state of a recurrent layer once; an indexer
    (``index_topk``) scores every row its token may see."""
    first = ends - counts
    sums = {"attn.full_context_rows" if spec.kv_row_lanes
            else "mla.decode_context_rows":
            int((counts * first + counts * (counts + 1) // 2).sum())}
    if spec.index_topk:
        sums["attn.index_rows_scored"] = sums["attn.full_context_rows"]
    if spec.window_layers:
        window = spec.sliding_window
        below = np.clip(np.minimum(ends, window) - first, 0, None)
        sums["attn.window_context_rows"] = int(
            (below * first + below * (below + 1) // 2
             + (counts - below) * window).sum())
    if spec.recurrent:
        sums["state.rows_updated"] = int(counts.sum())
    return sums


def prefill_sums(spec: ModelSpec, prompt_len: int, bucket: int
                 ) -> Dict[str, int]:
    """What one admitted prompt of a per-layer spec adds, by name: the key
    blocks its prefill visited and the blocks of its bucket's whole square,
    a layer of each paged kind (``ops/mla.py`` for latent rows,
    ``ops/flash_prefill.py`` for K|V rows: a sliding layer's are the
    band's); with an indexer the (query, key) pairs it scored, a layer, and
    the blocks of queries whose selection the TPU's kernel made."""
    from ..ops import flash_prefill, mla

    if not spec.kv_row_lanes:
        pairs = {"mla.": mla.prefill_key_blocks(prompt_len, bucket)}
    else:
        pairs = {"attn.full_": flash_prefill.prefill_key_blocks(
            prompt_len, bucket)}
        if spec.window_layers:
            pairs["attn.window_"] = flash_prefill.prefill_key_blocks(
                prompt_len, bucket, spec.sliding_window)
    sums = {f"{prefix}prefill_key_blocks_{what}": n
            for prefix, pair in pairs.items()
            for what, n in zip(("visited", "bucket"), pair)}
    if spec.index_topk:
        sums["attn.index_prefill_pairs"] = prompt_len * (prompt_len + 1) // 2
        # blocks of queries whose top-k the selection kernel makes
        # (``ops/sparse_index.py``): a bucket above the top-k on the kernel
        # body, every layer; 0 where the rule falls back to the XLA body
        kernel = (bucket > spec.index_topk and flash_prefill.prefill_impl(
            bucket, spec.head_dim) != "xla")
        sums["attn.prefill_select_blocks"] = (
            bucket // flash_prefill.Q_BLOCK * spec.n_layers if kernel else 0)
    return sums


def layered_family(spec: ModelSpec):
    """The module that builds and runs a spec with ``layer_kinds``.
    ``engine/`` reaches a family here and names no model file; a family
    module defines every name of ``LAYERED_FAMILY``
    (``tests/test_xing.py`` holds the five tiny specs to it):

    - ``init_params(spec, key)``; ``init_state(spec, max_slots, *,
      page_size, num_pages, window_pages, max_pages_per_seq)`` (ONE call for
      every family, which takes what its storage needs of them): the
      second pool (``engine/paged_kv.py``), a dict of arrays that rides the
      programs' donation and the decode carry; ``zero_state_slot(state,
      slot)``.
    - ``forward_prefill_into_pages(spec, params, tokens, seq_lens, pages,
      state, table_rows, slot_ids)`` -> ``(hidden, pages, state,
      counters)``; ``PREFILL_COUNTERS`` names the counters, in order.
    - ``decode_context(pages, page_table, attn_impl)``: what the steps of a
      chunk read the cached rows from, frozen for the chunk;
      ``forward_decode_step(spec, params, tokens, lengths, start_lengths,
      ctx, side, state, active)`` -> ``(hidden, side, state, counters)``,
      ``side`` the window ``[window_layers + paged_layers, slots, steps,
      row]`` a chunk's own rows gather in; ``DECODE_COUNTERS`` names the
      counters, in order; ``write_side(pages, state, side, page_table,
      counts, start)`` -> ``(pages, state)``: the chunk's one write-back.

    A counter's name is ``<group>.<key>`` of ``ContinuousEngine
    .get_metrics()`` (None: an entry nothing reads); the engine sums by
    name, with the host's ``decode_sums`` / ``prefill_sums`` above, and
    knows no family's layout.

    Six families in five modules, told
    apart by what the spec holds: ``index_topk`` (``models/keye.py``:
    full-attention layers whose queries read the rows a learned indexer
    scored highest, index keys in a second paged cache on the K|V pages'
    table, routed experts everywhere), "swa" layers (``models/mellum.py``:
    sliding-window layers beside full-attention layers, K|V rows in two
    pools of unlike lifetimes, routed experts everywhere),
    ``gdn_key_head_dim`` (``models/olmo_hybrid.py``: Gated DeltaNet layers
    beside full-attention layers over K|V rows, a dense MLP everywhere),
    ``q_lora_rank`` (``models/xing.py``: MLA with a compressed query in
    every layer, no recurrent state; its two families differ in the
    residual alone, ``hc_mult`` mHC streams around every sublayer or, with
    ``hc_mult`` 0, the plain pre-norm one) or none of these (KDA + MLA
    layers, ``models/ling.py``); imported late because they import this
    module."""
    if not spec.layer_kinds:
        raise ValueError("a uniform spec has no per-layer family: its "
                         "forward_* live in models/base.py")
    if spec.index_topk:
        from . import keye

        return keye
    if "swa" in spec.layer_kinds:
        from . import mellum

        return mellum
    if spec.gdn_key_head_dim:
        from . import olmo_hybrid

        return olmo_hybrid
    if spec.q_lora_rank:
        from . import xing

        return xing
    from . import ling

    return ling


# --------------------------------------------------------------------- init


def init_params(spec: ModelSpec, key: jax.Array) -> Params:
    """Random-init parameter tree (normal(0.02), depth-scaled output projs)."""
    spec.validate()
    dt = spec.jnp_dtype
    L, D, F, V = spec.n_layers, spec.d_model, spec.d_ff, spec.vocab_size
    H, Hkv, Dh = spec.n_heads, spec.n_kv_heads, spec.head_dim
    keys = iter(jax.random.split(key, 16))
    std = 0.02
    out_std = std / jnp.sqrt(2.0 * L)   # GPT-2-style depth scaling

    def norm_(shape, k, s=std):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * s).astype(dt)

    blocks: Params = {
        "ln1_scale": jnp.ones((L, D), dtype=dt),
        "ln2_scale": jnp.ones((L, D), dtype=dt),
        "wq": norm_((L, D, H * Dh), next(keys)),
        "wk": norm_((L, D, Hkv * Dh), next(keys)),
        "wv": norm_((L, D, Hkv * Dh), next(keys)),
        "wo": norm_((L, H * Dh, D), next(keys), out_std),
    }
    if spec.n_experts:
        from ..ops.moe import init_moe_blocks

        blocks.update(init_moe_blocks(spec, keys, norm_))
    elif spec.mlp in ("swiglu", "geglu"):
        blocks["w_gate"] = norm_((L, D, F), next(keys))
        blocks["w_up"] = norm_((L, D, F), next(keys))
        blocks["w_down"] = norm_((L, F, D), next(keys), out_std)
    else:
        blocks["w_up"] = norm_((L, D, F), next(keys))
        blocks["w_down"] = norm_((L, F, D), next(keys), out_std)
    if spec.norm == "layernorm":
        blocks["ln1_bias"] = jnp.zeros((L, D), dtype=dt)
        blocks["ln2_bias"] = jnp.zeros((L, D), dtype=dt)
    if spec.use_bias or spec.qkv_bias:
        blocks["bq"] = jnp.zeros((L, H * Dh), dtype=dt)
        blocks["bk"] = jnp.zeros((L, Hkv * Dh), dtype=dt)
        blocks["bv"] = jnp.zeros((L, Hkv * Dh), dtype=dt)
    if spec.use_bias:
        blocks["bo"] = jnp.zeros((L, D), dtype=dt)
        blocks["b_up"] = jnp.zeros((L, F), dtype=dt)
        blocks["b_down"] = jnp.zeros((L, D), dtype=dt)

    params: Params = {
        "tok_emb": norm_((V, D), next(keys)),
        "blocks": blocks,
        "lnf_scale": jnp.ones((D,), dtype=dt),
    }
    if spec.norm == "layernorm":
        params["lnf_bias"] = jnp.zeros((D,), dtype=dt)
    if spec.pos_emb == "learned":
        params["pos_emb"] = norm_((spec.max_seq_len, D), next(keys))
    if not spec.tie_embeddings:
        params["lm_head"] = norm_((D, V), next(keys))
    return params


# ------------------------------------------------------------------ helpers


def _norm(spec: ModelSpec, x, scale, bias):
    if spec.norm == "layernorm":
        return layer_norm(x, scale, bias, spec.norm_eps)
    if spec.norm_plus_one:
        # Gemma stores RMSNorm weights as (w - 1); add the 1 back in fp32
        # so small stored weights keep their precision
        scale = scale.astype(jnp.float32) + 1.0
    return rms_norm(x, scale, spec.norm_eps)


def _mlp(spec: ModelSpec, blk: Params, x, exact_moe: bool = True):
    """Feed-forward block -> (out, moe_aux_loss). Dense blocks report aux 0
    so every layer body has one static structure for lax.scan.

    ``exact_moe`` selects the drop-free MoE path (inference default);
    training passes False to keep GShard capacity dispatch (ops/moe.py)."""
    if spec.n_experts:
        from ..ops.moe import moe_mlp

        return moe_mlp(spec, blk, x, exact=exact_moe)
    if spec.mlp in ("swiglu", "geglu"):
        if "w_gate_up" in blk:
            # fused gate+up (ops.quant.fuse_block_weights): one weight
            # stream of N=2F per layer instead of two F launches
            gu = matmul_any("btd,df->btf", x, blk["w_gate_up"])
            gate, up = jnp.split(gu, 2, axis=-1)
        else:
            gate = matmul_any("btd,df->btf", x, blk["w_gate"])
            up = matmul_any("btd,df->btf", x, blk["w_up"])
        act = (jax.nn.silu if spec.mlp == "swiglu"
               else partial(jax.nn.gelu, approximate=True))   # geglu: Gemma
        h = act(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:
        h = matmul_any("btd,df->btf", x, blk["w_up"])
        if spec.use_bias:
            h = h + blk["b_up"]
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(x.dtype)
    out = matmul_any("btf,fd->btd", h, blk["w_down"])
    if spec.use_bias:
        out = out + blk["b_down"]
    return out, jnp.float32(0.0)


def _qkv(spec: ModelSpec, blk: Params, x, positions):
    b, t, _ = x.shape
    H, Hkv, Dh = spec.n_heads, spec.n_kv_heads, spec.head_dim
    if "w_qkv" in blk:
        # fused q|k|v (ops.quant.fuse_block_weights): the small-N k/v
        # projections ride one N = (H+2Hkv)·Dh launch — fusion is skipped
        # at build time when qkv biases exist, so no bias branch here
        qkv = matmul_any("btd,de->bte", x, blk["w_qkv"])
        q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)
    else:
        q = matmul_any("btd,de->bte", x, blk["wq"])
        k = matmul_any("btd,de->bte", x, blk["wk"])
        v = matmul_any("btd,de->bte", x, blk["wv"])
        if spec.use_bias or spec.qkv_bias:
            q, k, v = q + blk["bq"], k + blk["bk"], v + blk["bv"]
    q = q.reshape(b, t, H, Dh)
    k = k.reshape(b, t, Hkv, Dh)
    v = v.reshape(b, t, Hkv, Dh)
    if spec.pos_emb == "rope":
        q = apply_rope(q, positions, spec.rope_theta)
        k = apply_rope(k, positions, spec.rope_theta)
    return q, k, v


def _out_proj(spec: ModelSpec, blk: Params, attn_out):
    b, t, h, dh = attn_out.shape
    out = matmul_any("bte,ed->btd", attn_out.reshape(b, t, h * dh), blk["wo"])
    if spec.use_bias:
        out = out + blk["bo"]
    return out


# ------------------------------------------------- decode layer seams


def _qkv_norm(spec: ModelSpec, blk: Params, x, positions):
    """ln1 + QKV (+ RoPE) of one layer."""
    with jax.named_scope("attn.qkv"):
        h = _norm(spec, x, blk["ln1_scale"], blk.get("ln1_bias"))
        return _qkv(spec, blk, h, positions)


def _out_residual(spec: ModelSpec, blk: Params, attn_out, x):
    """x + out_proj(attn)."""
    with jax.named_scope("attn.out"):
        return x + _out_proj(spec, blk, attn_out)


def _mlp_residual(spec: ModelSpec, blk: Params, x, exact_moe: bool = True):
    """ln2 + MLP + residual -> (new_x, moe_aux)."""
    with (jax.named_scope("mlp.moe") if spec.n_experts
          else jax.named_scope("mlp.dense")):
        h2 = _norm(spec, x, blk["ln2_scale"], blk.get("ln2_bias"))
        m, aux = _mlp(spec, blk, h2, exact_moe=exact_moe)
        return x + m, aux


def embed(spec: ModelSpec, params: Params, tokens: jnp.ndarray,
          positions: jnp.ndarray) -> jnp.ndarray:
    """[B, T] tokens -> [B, T, D] activations."""
    with jax.named_scope("embed"):
        x = params["tok_emb"][tokens]
        if spec.emb_scale:
            # Gemma: normalizer cast to the activation dtype before the
            # multiply (matches the family's published numerics)
            x = x * jnp.asarray(spec.d_model ** 0.5, dtype=x.dtype)
        if spec.pos_emb == "learned":
            x = x + params["pos_emb"][positions]
        return x


def unembed(spec: ModelSpec, params: Params, hidden: jnp.ndarray) -> jnp.ndarray:
    """Final norm + LM head. hidden [..., D] -> fp32 logits [..., V].

    An int4 lm_head may arrive VOCAB-PADDED (``ops.quant``: V=128256 =
    256·501 tiles the Mosaic kernel only at bn=256, ~338 GB/s; padded to
    a 2048-multiple it rides the big-block path) — pad columns are
    zero-weight and sliced off here before softcap/sampling."""
    with jax.named_scope("head.unembed"):
        h = _norm(spec, hidden, params["lnf_scale"], params.get("lnf_bias"))
        w = params["tok_emb"].T if spec.tie_embeddings else params["lm_head"]
        if isinstance(w, QuantizedTensor):
            logits = matmul_any("...d,dv->...v", h.astype(jnp.float32), w)
            if logits.shape[-1] != spec.vocab_size:
                logits = logits[..., : spec.vocab_size]
        else:
            # keep the [D, V] projection in its storage dtype (bf16: half
            # the HBM read of an fp32 upcast — this matmul streams the
            # largest single weight every decode step) and accumulate in
            # fp32 on the MXU
            logits = jnp.einsum("...d,dv->...v", h.astype(w.dtype), w,
                                preferred_element_type=jnp.float32)
        if spec.logit_softcap:
            cap = spec.logit_softcap
            logits = cap * jnp.tanh(logits / cap)
        return logits


# ------------------------------------------------------------------ prefill


def transformer_block(
    spec: ModelSpec,
    blk: Params,
    x: jnp.ndarray,          # [B, T, D]
    positions: jnp.ndarray,  # [B, T]
    attn_fn,                 # (q, k, v) -> attention output [B, T, H, Dh]
    exact_moe: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One pre-norm block over fresh (non-cached) K/V: returns
    (x_out, k, v, moe_aux). The single definition of the block math for
    every full-sequence path — dense prefill, pipeline stages, and the
    sequence-parallel prefill differ only in ``attn_fn``."""
    q, k, v = _qkv_norm(spec, blk, x, positions)
    with jax.named_scope("attn.core"):
        attn = attn_fn(q, k, v)
    x = _out_residual(spec, blk, attn, x)
    x, aux = _mlp_residual(spec, blk, x, exact_moe=exact_moe)
    return x, k, v, aux


def forward_prefill(
    spec: ModelSpec,
    params: Params,
    tokens: jnp.ndarray,     # [B, T] right-padded prompts
    seq_lens: jnp.ndarray,   # [B] true prompt lengths
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run the prompt through all layers.

    Returns (hidden [B, T, D], k_cache [L, B, T, Hkv, Dh], v_cache [L, ...]):
    the per-layer K/V to be written into cache slots by the engine.
    """
    x, ks, vs, _ = _prefill_scan(spec, params, tokens, seq_lens)
    return x, ks, vs


def _prefill_scan(
    spec: ModelSpec,
    params: Params,
    tokens: jnp.ndarray,
    seq_lens: jnp.ndarray,
    exact_moe: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """forward_prefill plus the summed MoE router aux loss (0 for dense)."""
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    x = embed(spec, params, tokens, positions)

    def attn(q, k, v):
        return causal_attention(q, k, v, seq_lens,
                                window=spec.sliding_window)

    xs_blocks, rebuild = split_indexed_blocks(params["blocks"])

    def body(x, per_layer):
        xs_blk, l = per_layer
        blk = rebuild(xs_blk, l)
        x, k, v, aux = transformer_block(spec, blk, x, positions, attn,
                                         exact_moe=exact_moe)
        return x, (k, v, aux)

    n_layers = spec.n_layers
    x, (ks, vs, auxs) = lax.scan(body, x,
                                 (xs_blocks, jnp.arange(n_layers)))
    return x, ks, vs, auxs.sum()


def forward_prefill_into_pages(
    spec: ModelSpec,
    params: Params,
    tokens: jnp.ndarray,      # [B, T] right-padded prompts
    seq_lens: jnp.ndarray,    # [B] true prompt lengths
    k_pages: jnp.ndarray,     # [L, N, P, Hkv*Dh] page pools (donated)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, MP] physical pages per row
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prefill with each layer's fresh KV scattered STRAIGHT into the
    page pools inside the layer scan — returns (hidden, k_pages,
    v_pages) with no ``[L, B, T, Hkv, Dh]`` intermediate.

    ``forward_prefill`` + ``write_prefill_pages`` materialize the full
    stacked KV between the two programs: ~2.1 GB at 8B bb=128, which
    made bs128 admission OOM a 16 GB chip nondeterministically (r5).
    Here the pools ride the scan CARRY as flat [L·N·P, fused] views
    (the decode chunk's established pattern) and each layer's [B, T,
    fused] block scatters immediately — the transient is one layer's
    KV (~33 MB at that shape). Padded positions get an out-of-range
    flat index and ``mode="drop"`` discards them; the oob sentinel is
    ABSOLUTE (L·N·P), never per-layer, so a padded token can't land in
    the next layer's first page."""
    b, t = tokens.shape
    L = spec.n_layers
    n, p = k_pages.shape[1], k_pages.shape[2]
    fused = spec.n_kv_heads * spec.head_dim
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    x = embed(spec, params, tokens, positions)

    def attn(q, k, v):
        return causal_attention(q, k, v, seq_lens,
                                window=spec.sliding_window)

    with jax.named_scope("step.setup"):
        valid = positions < seq_lens[:, None]
        logical = positions // p
        offset = positions % p
        phys = jnp.take_along_axis(
            page_table, jnp.minimum(logical, page_table.shape[1] - 1),
            axis=1)
        base_idx = phys * p + offset                           # [B, T]
        kp_flat = k_pages.reshape(L * n * p, fused)
        vp_flat = v_pages.reshape(L * n * p, fused)
    xs_blocks, rebuild = split_indexed_blocks(params["blocks"])

    def body(carry, per_layer):
        x, kpf, vpf = carry
        xs_blk, l = per_layer
        blk = rebuild(xs_blk, l)
        x, k, v, _aux = transformer_block(spec, blk, x, positions, attn)
        with jax.named_scope("attn.kv_update"):
            idx = jnp.where(valid, l * (n * p) + base_idx, L * n * p)
            kpf = kpf.at[idx].set(k.reshape(b, t, fused).astype(kpf.dtype),
                                  mode="drop")
            vpf = vpf.at[idx].set(v.reshape(b, t, fused).astype(vpf.dtype),
                                  mode="drop")
        return (x, kpf, vpf), None

    (x, kp_flat, vp_flat), _ = lax.scan(
        body, (x, kp_flat, vp_flat), (xs_blocks, jnp.arange(L)))
    return (x, kp_flat.reshape(L, n, p, fused),
            vp_flat.reshape(L, n, p, fused))


def forward_prefill_suffix(
    spec: ModelSpec,
    params: Params,
    tokens: jnp.ndarray,      # [B, Ts] right-padded prompt SUFFIX
    suffix_lens: jnp.ndarray, # [B] valid suffix lengths
    n_ctx: jnp.ndarray,       # [B] cached-prefix length per row
    k_ctx: jnp.ndarray,       # [L, B, Tc, Hkv, Dh] cached prefix K (padded)
    v_ctx: jnp.ndarray,       # [L, B, Tc, Hkv, Dh]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prefill a prompt suffix on top of cached prefix KV (prefix-cache
    hit): suffix positions are offset by ``n_ctx`` (RoPE/learned-pos see
    absolute positions) and attention runs over cached-context + causal
    suffix (``ops/attention.suffix_attention``).

    Returns (hidden [B, Ts, D], suffix K [L, B, Ts, Hkv, Dh], suffix V).
    """
    b, ts = tokens.shape
    positions = n_ctx[:, None] + jnp.arange(ts)[None, :]
    x = embed(spec, params, tokens, positions)

    xs_blocks, rebuild = split_indexed_blocks(params["blocks"])

    def body(x, per_layer):
        xs_blk, l, ck, cv = per_layer
        blk = rebuild(xs_blk, l)
        q, k, v = _qkv_norm(spec, blk, x, positions)
        with jax.named_scope("attn.core"):
            attn = suffix_attention(q, ck, cv, n_ctx, k, v, suffix_lens,
                                    window=spec.sliding_window)
        x = _out_residual(spec, blk, attn, x)
        x, _ = _mlp_residual(spec, blk, x)
        return x, (k, v)

    x, (ks, vs) = lax.scan(
        body, x,
        (xs_blocks, jnp.arange(k_ctx.shape[0]), k_ctx, v_ctx))
    return x, ks, vs


def forward_window(
    spec: ModelSpec,
    params: Params,
    tokens: jnp.ndarray,      # [B, W] token window per slot (right-padded)
    n_valid: jnp.ndarray,     # [B] valid tokens in each window
    start: jnp.ndarray,       # [B] absolute position of window token 0
    cache_k: jnp.ndarray,     # [L, B, S, Hkv, Dh] contiguous KV cache
    cache_v: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Multi-token decode ("verify") step: process a small window of W
    tokens at absolute positions ``start + i`` against the cache.

    The workhorse of speculative decoding (``engine/speculative.py``): the
    target model scores k draft tokens in ONE forward instead of k serial
    decode steps, and the draft model uses it to catch its cache up after
    a rejection. Window K/V is scattered into the cache at its absolute
    positions (invalid window slots dropped); attention sees the cache
    prefix (< start) plus the causal window — ``ops.attention
    .suffix_attention`` with the cache as context.

    Returns (logits [B, W, V] fp32, new cache_k, new cache_v). Position i
    of the logits is the next-token distribution AFTER window token i.
    """
    b, w = tokens.shape
    s = cache_k.shape[2]
    positions = start[:, None] + jnp.arange(w)[None, :]
    x = embed(spec, params, tokens, positions)
    batch_idx = jnp.arange(b)[:, None]
    # invalid window slots scatter out of range -> dropped
    pos_w = jnp.where(jnp.arange(w)[None, :] < n_valid[:, None],
                      positions, s)

    # full cache rides the carry (see forward_decode: stacked scan outputs
    # would copy the whole cache every verify window)
    xs_blocks, rebuild = split_indexed_blocks(params["blocks"])

    def body(carry, per_layer):
        x, ck_full, cv_full = carry
        xs_blk, l = per_layer
        blk = rebuild(xs_blk, l)
        h = _norm(spec, x, blk["ln1_scale"], blk.get("ln1_bias"))
        q, k, v = _qkv(spec, blk, h, positions)      # k,v: [B, W, Hkv, Dh]
        ck_full = ck_full.at[l, batch_idx, pos_w].set(
            k.astype(ck_full.dtype), mode="drop")
        cv_full = cv_full.at[l, batch_idx, pos_w].set(
            v.astype(cv_full.dtype), mode="drop")
        ck = lax.dynamic_index_in_dim(ck_full, l, axis=0, keepdims=False)
        cv = lax.dynamic_index_in_dim(cv_full, l, axis=0, keepdims=False)
        attn = suffix_attention(
            q, ck.astype(q.dtype), cv.astype(q.dtype), start, k, v, n_valid,
            window=spec.sliding_window,
        )
        x = x + _out_proj(spec, blk, attn)
        h2 = _norm(spec, x, blk["ln2_scale"], blk.get("ln2_bias"))
        m, _ = _mlp(spec, blk, h2)
        x = x + m
        return (x, ck_full, cv_full), None

    n_layers = cache_k.shape[0]
    (x, new_k, new_v), _ = lax.scan(
        body, (x, cache_k, cache_v),
        (xs_blocks, jnp.arange(n_layers)))
    return unembed(spec, params, x), new_k, new_v


# ------------------------------------------------------------------- decode


def forward_decode(
    spec: ModelSpec,
    params: Params,
    tokens: jnp.ndarray,     # [B] the most recent token per slot
    lengths: jnp.ndarray,    # [B] current length per slot (position of `tokens`)
    cache_k: jnp.ndarray,    # [L, B, S, Hkv, Dh]
    cache_v: jnp.ndarray,    # [L, B, S, Hkv, Dh]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step for every slot.

    Writes each slot's new K/V at its own position (scatter), attends over the
    slot's live prefix, and returns (hidden [B, D], new cache_k, new cache_v).
    The caller advances ``lengths`` afterwards.
    """
    b = tokens.shape[0]
    positions = lengths[:, None]                         # [B, 1]
    x = embed(spec, params, tokens[:, None], positions)  # [B, 1, D]
    batch_idx = jnp.arange(b)

    # The FULL stacked cache rides the scan CARRY and is updated in place
    # with [layer, slot, position] scatters. Emitting per-layer caches as
    # stacked scan outputs instead (the "natural" functional shape) forces
    # XLA to copy the entire multi-MB cache every decode step — the copy
    # was ~25% of measured step time on a v5e chip.
    xs_blocks, rebuild = split_indexed_blocks(params["blocks"])

    def body(carry, per_layer):
        x, ck_full, cv_full = carry
        xs_blk, l = per_layer
        blk = rebuild(xs_blk, l)
        q, k, v = _qkv_norm(spec, blk, x, positions)  # k,v: [B, 1, Hkv, Dh]
        with jax.named_scope("attn.kv_update"):
            ck_full = ck_full.at[l, batch_idx, lengths].set(
                k[:, 0].astype(ck_full.dtype))
            cv_full = cv_full.at[l, batch_idx, lengths].set(
                v[:, 0].astype(cv_full.dtype))
        with jax.named_scope("attn.kv_gather"):
            ck = lax.dynamic_index_in_dim(ck_full, l, axis=0, keepdims=False)
            cv = lax.dynamic_index_in_dim(cv_full, l, axis=0, keepdims=False)
        with jax.named_scope("attn.core"):
            attn = cached_attention(q, ck, cv, lengths + 1,
                                    window=spec.sliding_window)
        x = _out_residual(spec, blk, attn, x)
        x, _ = _mlp_residual(spec, blk, x)
        return (x, ck_full, cv_full), None

    n_layers = cache_k.shape[0]
    (x, new_k, new_v), _ = lax.scan(
        body, (x, cache_k, cache_v),
        (xs_blocks, jnp.arange(n_layers)))
    return x[:, 0, :], new_k, new_v


# ------------------------------------------------------------ paged decode


def forward_decode_window(
    spec: ModelSpec,
    params: Params,
    tokens: jnp.ndarray,         # [B] the most recent token per slot
    lengths: jnp.ndarray,        # [B] current length (position of `tokens`)
    start_lengths: jnp.ndarray,  # [B] length at CHUNK start (frozen prefix)
    k_pages: jnp.ndarray,        # [L, N, P, Hkv*Dh] page pools (READ-ONLY)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,     # [B, MP] int32
    side_k: jnp.ndarray,         # [L, B, W, Hkv, Dh] chunk side window
    side_v: jnp.ndarray,
    active: jnp.ndarray,         # [B] bool
    *,
    interpret: bool = False,     # run the kernel interpreted (CPU tests)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step with NO pool writes: the page pools hold the frozen
    pre-chunk prefix, fresh K/V accumulates in the dense ``side`` window,
    and ONE kernel per layer (``ops.flash_decode``) streams the prefix in
    place from the pages and folds the side window into the same
    online-softmax accumulators. The caller scatters the window into the
    pages ONCE per chunk (``write_prefill_pages``).

    Why: the per-step page scatter of ``forward_decode_paged`` costs
    ~3.8 ms/layer at 8B bs64 on v5e (XLA scatter lowering), capping the
    paged engine at ~28% of dense decode. Writing a per-slot side index is
    a [B, W] one-hot select — pure vector ops — and the chunk-end batched
    merge measures 0.03 ms.

    Returns (hidden [B, D], side_k, side_v). Not for sliding-window specs
    (the prefix part's window mask would need the per-step total length;
    those run ``forward_decode_paged``).
    """
    from ..ops.flash_decode import flash_decode_attention_pallas

    L, n_pages, page_size, fused = k_pages.shape
    w = side_k.shape[2]
    positions = lengths[:, None]                         # [B, 1]
    x = embed(spec, params, tokens[:, None], positions)  # [B, 1, D]
    with jax.named_scope("step.setup"):
        # per-slot side write index: how many side entries this slot has
        idx = lengths - start_lengths
        onehot = (jnp.arange(w)[None, :] == idx[:, None]) & active[:, None]
        n_side = idx + active.astype(idx.dtype)          # valid AFTER write
        # a row that is not live (never admitted, or finished earlier in
        # this chunk) has its output discarded: the kernel gets length 0
        # for it and moves none of its pages
        live_prefix = jnp.where(active, start_lengths, 0)
        live_side = jnp.where(active, n_side, 0)
        # stacked view: the kernel indexes pages as layer·N + table[i, p],
        # so the scan hands it the WHOLE pool — slicing a layer out per
        # step would materialize a pool-sized copy (custom-call operands
        # can't fuse a dynamic slice)
        kp_flat = k_pages.reshape(L * n_pages, page_size, fused)
        vp_flat = v_pages.reshape(L * n_pages, page_size, fused)

    xs_blocks, rebuild = split_indexed_blocks(params["blocks"])

    def body(carry, per_layer):
        x, side_k, side_v = carry
        xs_blk, l = per_layer
        blk = rebuild(xs_blk, l)
        q, k, v = _qkv_norm(spec, blk, x, positions)  # k,v: [B, 1, Hkv, Dh]
        with jax.named_scope("attn.kv_gather"):
            sk = lax.dynamic_index_in_dim(side_k, l, 0, keepdims=False)
            sv = lax.dynamic_index_in_dim(side_v, l, 0, keepdims=False)
        with jax.named_scope("attn.kv_update"):
            sk = jnp.where(onehot[:, :, None, None], k[:, 0][:, None], sk)
            sv = jnp.where(onehot[:, :, None, None], v[:, 0][:, None], sv)
        with jax.named_scope("attn.core"):
            attn = flash_decode_attention_pallas(
                q[:, 0], kp_flat, vp_flat, page_table, live_prefix,
                sk, sv, live_side, n_kv_heads=spec.n_kv_heads,
                interpret=interpret, layer=l, n_pages_per_layer=n_pages,
            )
        with jax.named_scope("attn.kv_update"):
            side_k = lax.dynamic_update_index_in_dim(side_k, sk, l, 0)
            side_v = lax.dynamic_update_index_in_dim(side_v, sv, l, 0)
        x = _out_residual(spec, blk, attn[:, None], x)
        x, _ = _mlp_residual(spec, blk, x)
        return (x, side_k, side_v), None

    (x, side_k, side_v), _ = lax.scan(
        body, (x, side_k, side_v), (xs_blocks, jnp.arange(L)))
    return x[:, 0, :], side_k, side_v


def forward_decode_paged(
    spec: ModelSpec,
    params: Params,
    tokens: jnp.ndarray,      # [B] the most recent token per slot
    lengths: jnp.ndarray,     # [B] current length per slot (position of `tokens`)
    k_pages: jnp.ndarray,     # [L, N, P, Hkv*Dh] page pools
    v_pages: jnp.ndarray,     # [L, N, P, Hkv*Dh]
    page_table: jnp.ndarray,  # [B, MP] int32 logical->physical pages
    write_mask: Optional[jnp.ndarray] = None,   # [B] bool: which slots write
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step against the paged HBM cache (``engine/paged_kv.py``).

    Each slot's fresh K/V is scattered into its page at position ``lengths``
    (page = lengths // P, offset = lengths % P — capacity must be reserved
    before the chunk, see ``PagedKVCache.reserve``), then attention runs over
    the slot's live pages (``ops.paged_attention.paged_attention_xla``).
    Returns (hidden [B, D], new k_pages, new v_pages).

    ``write_mask`` exists because decode always runs over ALL slots (static
    shapes): an inactive slot's page table points at physical page 0, which
    belongs to some live slot — its K/V write must be dropped, not landed.
    Masked-off slots get an out-of-range scatter index (``mode="drop"``).
    """
    from ..ops.paged_attention import paged_attention_xla

    b = tokens.shape[0]
    n_pages = k_pages.shape[1]
    page_size = k_pages.shape[2]
    positions = lengths[:, None]                         # [B, 1]
    x = embed(spec, params, tokens[:, None], positions)  # [B, 1, D]
    with jax.named_scope("step.setup"):
        batch_idx = jnp.arange(b)
        logical = lengths // page_size
        offset = lengths % page_size
        phys = page_table[batch_idx, logical]            # [B]
        if write_mask is not None:
            phys = jnp.where(write_mask, phys, n_pages)  # oob -> dropped

    # full page pools ride the carry (see forward_decode: stacked scan
    # outputs would copy the whole multi-GiB pool every step)
    xs_blocks, rebuild = split_indexed_blocks(params["blocks"])

    def body(carry, per_layer):
        x, kp_full, vp_full = carry
        xs_blk, l = per_layer
        blk = rebuild(xs_blk, l)
        q, k, v = _qkv_norm(spec, blk, x, positions)  # k,v: [B, 1, Hkv, Dh]
        kv_fused = k.shape[2] * k.shape[3]
        with jax.named_scope("attn.kv_update"):
            kp_full = kp_full.at[l, phys, offset].set(
                k[:, 0].reshape(b, kv_fused).astype(kp_full.dtype),
                mode="drop")
            vp_full = vp_full.at[l, phys, offset].set(
                v[:, 0].reshape(b, kv_fused).astype(vp_full.dtype),
                mode="drop")
        with jax.named_scope("attn.kv_gather"):
            kp = lax.dynamic_index_in_dim(kp_full, l, axis=0, keepdims=False)
            vp = lax.dynamic_index_in_dim(vp_full, l, axis=0, keepdims=False)
        with jax.named_scope("attn.core"):
            attn = paged_attention_xla(
                q[:, 0], kp, vp, page_table, lengths + 1,
                n_kv_heads=spec.n_kv_heads, window=spec.sliding_window,
            )
        x = _out_residual(spec, blk, attn[:, None], x)
        x, _ = _mlp_residual(spec, blk, x)
        return (x, kp_full, vp_full), None

    n_layers = k_pages.shape[0]
    (x, new_k, new_v), _ = lax.scan(
        body, (x, k_pages, v_pages),
        (xs_blocks, jnp.arange(n_layers)))
    return x[:, 0, :], new_k, new_v


def write_prefill_pages(
    k_pages: jnp.ndarray,     # [L, N, P, Hkv*Dh]
    v_pages: jnp.ndarray,
    ks: jnp.ndarray,          # [L, B, T, Hkv, Dh] fresh prefill K/V
    vs: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, MP]
    seq_lens: jnp.ndarray,    # [B] valid token count in ks/vs rows
    start: Optional[jnp.ndarray] = None,  # [B] absolute position of token 0
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter prefilled K/V into page pools. Per layer this is ONE flat
    scatter: each valid token's (physical page, offset) flattens to an index
    into the pool viewed as [num_pages * page_size, fused]; padded positions
    get an out-of-range index and ``mode="drop"`` discards them.

    ``start`` shifts the write window for suffix prefill on a prefix-cache
    hit: row b's token t lands at absolute position start[b] + t."""
    L, B, T, Hkv, Dh = ks.shape
    page_size = k_pages.shape[2]
    fused = Hkv * Dh
    local = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))      # [B, T]
    valid = local < seq_lens[:, None]
    pos = local if start is None else local + start[:, None]
    logical = pos // page_size
    offset = pos % page_size
    phys = jnp.take_along_axis(
        page_table, jnp.minimum(logical, page_table.shape[1] - 1), axis=1
    )                                                              # [B, T]
    n, p = k_pages.shape[1], k_pages.shape[2]
    flat_idx = jnp.where(valid, phys * page_size + offset, n * p)  # oob -> drop

    def per_layer(_, xs):
        kp, vp, fk, fv = xs
        kp = kp.reshape(n * p, fused).at[flat_idx].set(
            fk.reshape(B, T, fused).astype(kp.dtype), mode="drop"
        ).reshape(n, p, fused)
        vp = vp.reshape(n * p, fused).at[flat_idx].set(
            fv.reshape(B, T, fused).astype(vp.dtype), mode="drop"
        ).reshape(n, p, fused)
        return None, (kp, vp)

    _, (k_pages, v_pages) = lax.scan(per_layer, None, (k_pages, v_pages, ks, vs))
    return k_pages, v_pages


# ---------------------------------------------------------------- training


def forward_train(
    spec: ModelSpec,
    params: Params,
    tokens: jnp.ndarray,     # [B, T]
    seq_lens: jnp.ndarray,   # [B]
) -> jnp.ndarray:
    """Full-sequence logits for training/scoring: [B, T, V] fp32."""
    hidden, _, _ = forward_prefill(spec, params, tokens, seq_lens)
    return unembed(spec, params, hidden)


def forward_train_aux(
    spec: ModelSpec,
    params: Params,
    tokens: jnp.ndarray,     # [B, T]
    seq_lens: jnp.ndarray,   # [B]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(logits [B, T, V] fp32, summed MoE router aux loss — 0 for dense).

    Training path: keeps GShard capacity dispatch (drops regularize
    routing); inference prefill/decode use the exact drop-free MoE path."""
    hidden, _, _, aux = _prefill_scan(spec, params, tokens, seq_lens,
                                      exact_moe=False)
    return unembed(spec, params, hidden), aux


def next_token_xent(
    logits: jnp.ndarray,     # [B, T, V] fp32
    tokens: jnp.ndarray,     # [B, T]
    seq_lens: jnp.ndarray,   # [B]
) -> jnp.ndarray:
    """Mean next-token cross-entropy over valid positions (shared by the
    dense loss and the pipeline-parallel loss)."""
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    t = tokens.shape[1]
    valid = (jnp.arange(t - 1)[None, :] < (seq_lens[:, None] - 1)).astype(jnp.float32)
    return (nll * valid).sum() / jnp.maximum(valid.sum(), 1.0)


def causal_lm_loss(
    spec: ModelSpec,
    params: Params,
    tokens: jnp.ndarray,     # [B, T]
    seq_lens: jnp.ndarray,   # [B]
    router_aux_coef: float = 0.01,
) -> jnp.ndarray:
    """Mean next-token cross-entropy over valid positions, plus the MoE
    load-balance penalty when the spec routes experts."""
    logits, aux = forward_train_aux(spec, params, tokens, seq_lens)
    loss = next_token_xent(logits, tokens, seq_lens)
    if spec.n_experts:
        loss = loss + router_aux_coef * aux
    return loss
