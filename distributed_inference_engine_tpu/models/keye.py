"""The text decoder of Keye-VL-2.0-30B-A3B (``model_type`` ``KeyeVL2``):
grouped-query attention whose every query reads only the rows a learned
INDEXER scored highest (a DeepSeek-Sparse-Attention style ``sa_config``:
top-2,048 of the context), over softmax-routed experts (top-8 of 128, no
shared expert) in EVERY layer. The equations are written out in
``perfbench/reference/dsa_moe.py`` (the plain float32 reference).

- ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``: pre-norm, a
  final RMSNorm, an untied head.
- *Attention*: q ``H x Dh``, k, v ``Hkv x Dh``, no bias; RMSNorm with a
  learned scale over each head's ``Dh`` of q and of k (the Qwen3-MoE
  block's convention); the whole ``Dh`` rotated (HF's split-halves pairing)
  by plain RoPE at ``rope_theta``: on text M-RoPE's three position ids are
  equal and it IS plain RoPE. Scores at ``Dh^-1/2``; query head h reads K/V
  head ``h // (H / Hkv)``.
- *Indexer* (weights of its own in every layer): ``q_idx = W_iq u`` (``Hi``
  heads of ``Di``), ``k_idx = LayerNorm(W_ik u)`` (ONE key head), ``w = W_iw
  u`` (``Hi``); q_idx and k_idx rotated over their whole ``Di``. Token t's
  attention reads the ``index_topk`` positions s <= t of largest ``I[t, s] =
  sum_j w[t, j] relu(q_idx[t, j] . k_idx[s])``, one set for all heads
  (``ops/sparse_index.py``).
- *MoE*: ``ops/moe_routed.py`` with ``moe_scoring`` ``softmax``, as
  ``models/mellum.py``'s.

**The tree is ONE layer's dict stacked over the layers** (the period is
one layer: ``params["period"][0][name]`` is ``[n_layers, ...]``) and every
program ``lax.scan``s over it, as ``models/mellum.py``.

**Cache: two row widths on ONE page table** (``engine/paged_kv.py``). A
layer adds a K|V row (``2 * Hkv * Dh`` lanes) to the family's pool
``[layers, pages, page, 2 * lanes]`` and an index key (``Di`` lanes) to
``state["index_pages"]`` ``[layers, pages, page, Di]`` a token; a page id
names the same page in both. A decode step scores a row's cached index keys
through its table and the chunk's own in the side window, takes the top-k
over both in position order, and attends to the K|V rows picked. On a TPU
(``ops/sparse_index.py``'s three decode kernels, ``attn_layer_step``) each
piece reads pages where they lie: the scores over the row's LIVE index-key
pages, the top-k as a counted threshold over the rows' scores in VMEM (a
mask, no index), the attention as ``ops/flash_decode.py``'s loop over the
live K|V pages under that mask; nothing as wide as the table reaches HBM
but a row of scores and a row of mask a sequence. ``"xla"`` (the CPU, the
tests' plain form) gathers the index keys through the whole table, takes
``lax.top_k`` and gathers the picked rows. The chunk's own rows of every
layer gather in ONE side window
``[layers, B, Wc, 2 * lanes + Di]`` (K | V | index key) written back once a
chunk (``write_side``). A freed page's stale index keys lie past its next
holder's length: every score past a row's length is masked.

The vision tower is not served (no vision configuration in the
repository): image inputs and M-RoPE's unequal position ids wait for it.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import sparse_index
from ..ops.flash_prefill import kv_prefill_attention
from ..ops.moe_routed import COUNTERS as MOE_COUNTERS
from ..ops.moe_routed import PREFILL_COUNTERS
from ..ops.norms import layer_norm, rms_norm
from .base import ModelSpec, embed
from .ling import _init_table, _proj, decode_context, write_rows_into_pages
from .mellum import (ROUTED_DOWN_SCALE, _attn_out, _kv_heads, _moe,
                     _scanned)

__all__ = ["keye_spec", "init_params", "init_state", "zero_state_slot",
           "decode_context", "write_side", "DECODE_COUNTERS",
           "PREFILL_COUNTERS", "forward_prefill_into_pages",
           "forward_decode_step"]

Params = Dict[str, Any]
State = Dict[str, jnp.ndarray]

# a decode step's counters, a layer: index keys the indexer READ (the live
# pages' rows and the side window on the kernel body; every slot's whole
# table, padding included, on "xla"), the routed experts' three, K|V rows
# the attention selected (min(context, index_topk) a live row) and K|V rows
# it READ for them (the live pages' rows and the side window under the
# kernel's mask; the picked rows gathered and the side window on "xla")
DECODE_COUNTERS = (("attn.index_table_rows",) + MOE_COUNTERS
                   + ("attn.rows_selected", "attn.kv_rows_read"))

# published values (config.json of Kwai-Keye/Keye-VL-2.0-30B-A3B, the
# language model's keys; ``sa_config`` is the indexer's group)
_PUBLISHED = dict(
    vocab_size=151936, d_model=2048, n_heads=32, n_kv_heads=4,
    head_dim_override=128, d_ff=6144, n_layers_published=48,
    n_experts=128, experts_per_token=8, moe_d_ff=768, shared_d_ff=0,
    moe_scoring="softmax", rope_theta=10000000.0, norm_eps=1e-6,
    max_seq_len=262144, index_heads=16, index_head_dim=64, index_topk=2048,
)

# The learned scale of the RMSNorm over each head of q and of k is DRAWN at
# 2^1/2: normalised q and k have unit RMS, so at scale 1 a head's scores q .
# k Dh^-1/2 spread 1 at any width and a softmax over 2,048 selected rows
# rests evenly on hundreds of them, whose values average to nothing: the
# attention, and with it the indexer's choice of rows, would have no say in
# the logits. At 2^1/2 each the scores spread 2 (measured 1.9995 at the
# published widths: a query's softmax rests on about 100 of its 2,048 rows,
# ``assumed.weights`` of ``perfbench/configs/keye-vl-2.0-30b-a3b-pp1.json``).
QK_NORM_SCALE = 2.0 ** 0.5

_SIZES: Dict[str, Dict[str, Any]] = {
    # stage 1 of an 8-stage pipeline over the 48 equal layers: published
    # layers 0-5, every layer whole (all 128 experts, all heads)
    "keye-vl-2.0-30b-a3b-pp1": dict(kept_layers=tuple(range(6))),
    # test scale: a top-k of 16 rows and pages of 8, so a context of a few
    # pages is well past the top-k and a 16-step chunk crosses it
    "keye-tiny": dict(
        vocab_size=256, d_model=64, n_heads=8, n_kv_heads=2,
        head_dim_override=64, d_ff=128, n_layers_published=4,
        kept_layers=tuple(range(4)), n_experts=8, experts_per_token=2,
        moe_d_ff=32, index_heads=2, index_head_dim=32, index_topk=16,
        max_seq_len=512),
}


def keye_spec(size: str = "keye-vl-2.0-30b-a3b-pp1", **overrides
              ) -> ModelSpec:
    if size not in _SIZES:
        raise ValueError(f"unknown keye size {size!r}; choose from "
                         f"{sorted(_SIZES)}")
    c = dict(_PUBLISHED, **_SIZES[size])
    kept = tuple(c.pop("kept_layers"))
    c.pop("n_layers_published")
    base = dict(
        c, n_layers=len(kept), experts_held=(0, c["n_experts"]),
        layer_kinds=("full",) * len(kept), layer_mlps=("moe",) * len(kept),
        layer_ids=kept, pos_emb="rope", norm="rmsnorm", mlp="swiglu",
        use_bias=False, tie_embeddings=False)
    base.update(overrides)
    return ModelSpec(**base).validate()


# --------------------------------------------------------------------- init


def _layer_shapes(spec: ModelSpec
                  ) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """name -> (shape, dtype, std) of every normal-drawn tensor of ONE layer
    (the tree stacks them over the layers)."""
    D, H, Hkv, Dh = spec.d_model, spec.n_heads, spec.n_kv_heads, spec.head_dim
    Hi, Di = spec.index_heads, spec.index_head_dim
    E, F = spec.experts_held[1], spec.moe_d_ff
    dt, std = spec.dtype, 0.02
    out_std = std / (2.0 * spec.n_layers) ** 0.5
    return dict(
        # q and k are normalised a head: their matrices' scale is gone from
        # the scores, which the norms' learned scales set (QK_NORM_SCALE)
        wq=((D, H * Dh), dt, std), wk=((D, Hkv * Dh), dt, std),
        wv=((D, Hkv * Dh), dt, std), wo=((H * Dh, D), dt, out_std),
        # the indexer: k_idx is LayerNorm'd and a query's ranking does not
        # change with the scale of q_idx or of w, so the scale is free
        w_iq=((D, Hi * Di), dt, std), w_ik=((D, Di), dt, std),
        w_iw=((D, Hi), dt, std),
        # the router and the experts as models/mellum.py argues for them
        w_router=((D, spec.n_experts), "float32", D ** -0.5),
        w_gate_up=((E, D, 2 * F), dt, std),
        w_down=((E, F, D), dt, out_std * ROUTED_DOWN_SCALE))


@partial(jax.jit, static_argnums=(0,))
def _init_stack(spec: ModelSpec, key) -> Params:
    """Every layer's tensors, each drawn and cast inside one program."""
    n = spec.n_layers
    shapes = _layer_shapes(spec)
    keys = jax.random.split(key, len(shapes))
    out = {name: (jax.random.normal(k, (n, *shape), jnp.float32)
                  * std).astype(dtype)
           for k, (name, (shape, dtype, std)) in zip(keys, shapes.items())}
    dt = spec.jnp_dtype
    ones = jnp.ones((n, spec.d_model), dt)
    out["attn_norm"], out["mlp_norm"] = ones, ones
    qk = jnp.full((n, spec.head_dim), QK_NORM_SCALE, dt)
    out["q_norm"], out["k_norm"] = qk, qk
    out["ik_norm_scale"] = jnp.ones((n, spec.index_head_dim), dt)
    out["ik_norm_bias"] = jnp.zeros((n, spec.index_head_dim), dt)
    return out


def init_params(spec: ModelSpec, key: jax.Array) -> Params:
    """Random tree in ``spec.dtype``; float32 router. The worker hands
    ``metadata.seed`` as the key."""
    spec.validate()
    keys = jax.random.split(key, 3)
    v, d = spec.vocab_size, spec.d_model
    return {
        "tok_emb": _init_table((v, d), spec.dtype, keys[-1]),
        "lm_head": _init_table((d, v), spec.dtype, keys[-2]),
        "lnf_scale": jnp.ones((d,), spec.jnp_dtype),
        "period": [_init_stack(spec, keys[0])],
    }


# ------------------------------------------------------------- cache views


def init_state(spec: ModelSpec, max_slots: int, page_size: int = 0,
               num_pages: int = 0, **_pool) -> State:
    """The index keys' pool: one key of ``index_head_dim`` lanes a token a
    layer, page for page beside the K|V pool (its table, its free list, its
    lifetimes)."""
    del max_slots
    return {"index_pages": jnp.zeros(
        (spec.paged_layers, num_pages, page_size, spec.index_head_dim),
        spec.jnp_dtype)}


def zero_state_slot(state: State, slot: jnp.ndarray) -> State:
    """Nothing to zero: a freed page's index keys lie past its next
    holder's length until that holder overwrites them, and every score past
    a row's length is masked."""
    del slot
    return state


# ----------------------------------------------------------------- layers


def _rope(x, positions, theta: float):
    """x [..., T, N, d] at positions [..., T], the whole d rotated: HF's
    pairing (lane i with lane i + d / 2), float32 angles."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _attn_inputs(spec: ModelSpec, blk: Params, h, positions):
    """h [B, T, D] (normalised) -> (q [B, T, H, Dh] normalised and rotated,
    the K|V rows [B, T, 2 * lanes] = normalised rotated k | v)."""
    b, t, _ = h.shape
    dh = spec.head_dim
    q = _proj(h, blk["wq"]).reshape(b, t, spec.n_heads, dh)
    k = _proj(h, blk["wk"]).reshape(b, t, spec.n_kv_heads, dh)
    q = _rope(rms_norm(q, blk["q_norm"], spec.norm_eps), positions,
              spec.rope_theta)
    k = _rope(rms_norm(k, blk["k_norm"], spec.norm_eps), positions,
              spec.rope_theta)
    return q, jnp.concatenate([k.reshape(b, t, -1), _proj(h, blk["wv"])], -1)


def _index_inputs(spec: ModelSpec, blk: Params, h, positions):
    """h [B, T, D] (normalised) -> (q_idx [B, T, Hi, Di] rotated, k_idx
    [B, T, Di] LayerNorm'd and rotated, w [B, T, Hi] float32)."""
    b, t, _ = h.shape
    hi, di = spec.index_heads, spec.index_head_dim
    q = _rope(_proj(h, blk["w_iq"]).reshape(b, t, hi, di), positions,
              spec.rope_theta)
    k = layer_norm(_proj(h, blk["w_ik"]), blk["ik_norm_scale"],
                   blk["ik_norm_bias"], spec.norm_eps)
    k = _rope(k[:, :, None], positions, spec.rope_theta)[:, :, 0]
    return q, k, _proj(h, blk["w_iw"], jnp.float32)


def attn_layer_prefill(spec: ModelSpec, blk: Params, x, positions,
                       seq_lens):
    """x [B, T, D] -> (attention out, K|V rows [B, T, 2 * lanes], index
    keys [B, T, Di]). A bucket no longer than ``index_topk`` selects every
    row: the dense causal attention, no score computed."""
    with jax.named_scope("attn.dsa"):
        h = rms_norm(x, blk["attn_norm"], spec.norm_eps)
        q, rows = _attn_inputs(spec, blk, h, positions)
        with jax.named_scope("attn.index"):
            q_idx, k_idx, w = _index_inputs(spec, blk, h, positions)
        if x.shape[1] <= spec.index_topk:
            with jax.named_scope("attn.sparse"):
                o = kv_prefill_attention(q, rows, seq_lens, spec.n_kv_heads)
        else:
            o = sparse_index.prefill_attention(
                q, rows, q_idx, k_idx, w, seq_lens, spec.n_kv_heads,
                spec.index_topk)
        return _attn_out(blk, o, x.dtype), rows, k_idx.astype(rows.dtype)


def attn_layer_step(spec: ModelSpec, blk: Params, x, positions, ctx,
                    index_pool, layer, n_ctx, side, side_idx, active):
    """x [B, D] at ``positions`` [B]; ``ctx`` = (the pool [L, N, P, 2 *
    lanes] of K|V pages, ``page_table`` [B, MP] the rows' pages in it and in
    ``index_pool`` [L, N, P, Di], the attention's name), rows valid below
    ``n_ctx``; side [B, Wc, 2 * lanes + Di] the chunk's own rows, this
    token's written at ``side_idx`` where ``active``. Returns (attention
    out, side, index keys read, K|V rows selected, K|V rows read).

    The selection's three pieces run under ``attn.index``, ``attn.select``
    and ``attn.sparse``. On the kernel body (``ops/sparse_index.py``) each
    reads pages where they lie: index scores over the row's LIVE index-key
    pages, the top-k as a counted threshold over the rows' scores in VMEM
    (a mask, no index), the attention as ``flash_decode``'s loop over the
    live K|V pages under the mask; what it read of either pool is the live
    pages' rows and the side window. ``"xla"``: the index keys gathered
    through the whole table, ``lax.top_k``, the picked rows gathered
    (``attn.gather``)."""
    pool, page_table, impl = ctx
    n_layers, n_pages, page, width = pool.shape
    b, mp = page_table.shape
    s_tab, wc = mp * page, side.shape[1]
    di = spec.index_head_dim
    index_flat = index_pool.reshape(n_layers * n_pages, page, di)
    with jax.named_scope("attn.dsa"):
        h = rms_norm(x, blk["attn_norm"], spec.norm_eps)
        q, row = _attn_inputs(spec, blk, h[:, None], positions[:, None])
        with jax.named_scope("attn.index"):
            q_idx, k_idx, w = _index_inputs(spec, blk, h[:, None],
                                            positions[:, None])
        row = jnp.concatenate([row, k_idx.astype(row.dtype)], -1)[:, 0]
        with jax.named_scope("attn.kv_update"):
            hot = (jnp.arange(wc)[None, :] == side_idx[:, None]) \
                & active[:, None]
            side = jnp.where(hot[..., None], row[:, None].astype(side.dtype),
                             side)
        # a row that is not live gets length 0: nothing of it is selected
        n_prefix = jnp.where(active, n_ctx, 0)
        n_side = jnp.where(active, side_idx + 1, 0)
        if impl != "xla":
            interpret = impl.endswith("_interpret")
            kernel = dict(interpret=interpret, n_pages_per_layer=n_pages)
            with jax.named_scope("attn.index"):
                scores = sparse_index.index_scores_decode(
                    q_idx[:, 0], w[:, 0], index_flat.swapaxes(1, 2),
                    page_table, n_prefix, side[..., width:], n_side, layer,
                    **kernel)
            with jax.named_scope("attn.select"):
                keep = sparse_index.select_mask_decode(
                    scores, n_prefix, n_side, topk=spec.index_topk, mp=mp,
                    page_size=page, interpret=interpret)
                n_selected = keep.sum(dtype=jnp.int32)
            with jax.named_scope("attn.sparse"):
                side_k, side_v = _kv_heads(spec, side[..., :width])
                o = sparse_index.sparse_decode_attention(
                    q[:, 0], pool.reshape(n_layers * n_pages, page, width),
                    page_table, n_prefix, side_k, side_v, n_side, keep,
                    layer, n_kv_heads=spec.n_kv_heads, **kernel)
            # either pool's rows: the live pages whole, the side window
            rows_read = (-(-n_prefix // page)).sum(dtype=jnp.int32) * page \
                + b * wc
            return (_attn_out(blk, o, x.dtype), side, rows_read, n_selected,
                    rows_read)
        with jax.named_scope("attn.index"):
            # ONE layer's index keys of the rows in this batch, through the
            # table (the layer folded into the page id: no slice of the pool)
            cached = index_flat[layer * n_pages + page_table].reshape(
                b, s_tab, di)
            scores = jnp.concatenate([
                jnp.where(jnp.arange(s_tab)[None, :] < n_prefix[:, None],
                          sparse_index.index_scores(q_idx, cached, w)[:, 0],
                          -jnp.inf),
                jnp.where(jnp.arange(wc)[None, :] < n_side[:, None],
                          sparse_index.index_scores(
                              q_idx, side[..., width:], w)[:, 0],
                          -jnp.inf)], -1)            # position order
        with jax.named_scope("attn.select"):
            picked, valid = sparse_index.decode_select(scores,
                                                       spec.index_topk)
        with jax.named_scope("attn.gather"):
            in_pool = valid & (picked < s_tab)
            pos = jnp.minimum(picked, s_tab - 1)
            phys = jnp.take_along_axis(page_table, pos // page, axis=1)
            got = pool.reshape(n_layers * n_pages * page, width)[
                (layer * n_pages + phys) * page + pos % page]  # [B, k, W]
            in_side = (valid[:, :, None] & (
                picked[:, :, None] - s_tab == jnp.arange(wc)[None, None, :])
            ).any(axis=1)                                      # [B, Wc]
        with jax.named_scope("attn.sparse"):
            keys, vals = _kv_heads(spec, jnp.concatenate(
                [got, side[..., :width]], axis=1))
            keep = jnp.concatenate([in_pool, in_side], -1)[:, None, None]
            g = spec.n_heads // spec.n_kv_heads
            s = jnp.einsum(
                "bkgd,bskd->bkgs",
                q[:, 0].reshape(b, spec.n_kv_heads, g, spec.head_dim), keys,
                preferred_element_type=jnp.float32) * spec.head_dim ** -0.5
            p = sparse_index.masked_softmax(s, keep)
            o = jnp.einsum("bkgs,bskd->bkgd", p.astype(vals.dtype), vals)
        out = _attn_out(blk, o.reshape(b, spec.n_heads, spec.head_dim),
                        x.dtype)
        return (out, side, jnp.int32(b * (s_tab + wc)),
                valid.sum(dtype=jnp.int32),
                jnp.int32(b * (picked.shape[1] + wc)))


# --------------------------------------------------------------- programs


def forward_prefill_into_pages(
    spec: ModelSpec, params: Params,
    tokens: jnp.ndarray,      # [B, T] right-padded prompts
    seq_lens: jnp.ndarray,    # [B] true lengths (0 = pad row)
    pages: jnp.ndarray,       # [L, N, P, 2 * lanes] K|V pool (donated)
    state: State,             # the index keys' pool (donated)
    page_table: jnp.ndarray,  # [B, MP] physical pages per row, both pools
    slot_ids: jnp.ndarray,    # [B] the slot of each row (not needed here)
    moe_impl: str = "",
) -> Tuple[jnp.ndarray, jnp.ndarray, State, jnp.ndarray]:
    """Whole prompts from nothing: every layer scatters a K|V row and an
    index key a token into its pages. Returns (hidden [B, T, D], pages,
    state, MoE counters [3])."""
    del slot_ids
    b, t = tokens.shape
    with jax.named_scope("step.setup"):
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
        valid = (positions < seq_lens[:, None]).reshape(-1)
    x = embed(spec, params, tokens, positions)
    (light,), (heavy,) = _scanned(params)

    def layer(carry, xs):
        x, counters = carry
        blk, p = xs
        att, rows, keys = attn_layer_prefill(spec, blk, x, positions,
                                             seq_lens)
        with jax.named_scope("resid.add"):
            x = x + att
        y, c = _moe(spec, blk, heavy, p, x.reshape(b * t, -1), valid,
                    moe_impl)
        with jax.named_scope("resid.add"):
            return (x + y.reshape(b, t, -1), counters + c), (rows, keys)

    (x, counters), (rows, keys) = lax.scan(
        layer, (x, jnp.zeros((3,), jnp.int32)),
        (light, jnp.arange(spec.n_layers)))
    with jax.named_scope("attn.kv_index"):
        start = jnp.zeros_like(seq_lens)
    pages = write_rows_into_pages(pages, rows, page_table, seq_lens, start)
    state = dict(state, index_pages=write_rows_into_pages(
        state["index_pages"], keys, page_table, seq_lens, start))
    return x, pages, state, counters


def forward_decode_step(
    spec: ModelSpec, params: Params,
    tokens: jnp.ndarray,         # [B] the most recent token per slot
    lengths: jnp.ndarray,        # [B] its position
    start_lengths: jnp.ndarray,  # [B] length when the chunk began
    ctx,                         # ``decode_context``: pool, table, attention
    side: jnp.ndarray,           # [L, B, Wc, 2 * lanes + Di] chunk's rows
    state: State,                # the index keys' pool
    active: jnp.ndarray,         # [B] bool
    moe_impl: str = "",
) -> Tuple[jnp.ndarray, jnp.ndarray, State, jnp.ndarray]:
    """One token for every slot. Returns (hidden [B, D], side, state as it
    came, the family's ``DECODE_COUNTERS``); rows not ``active`` leave side
    alone. ``ctx``'s attention string picks the selection's body
    (``attn_layer_step``): the kernels over the pages where they lie, or
    ``"xla"``'s gathers."""
    index_pool = state["index_pages"]
    x = embed(spec, params, tokens[:, None], lengths[:, None])[:, 0]
    with jax.named_scope("step.setup"):
        side_idx = lengths - start_lengths
    (light,), (heavy,) = _scanned(params)

    def layer(carry, xs):
        x, side, counters, rows = carry
        blk, p = xs
        with jax.named_scope("attn.kv_gather"):
            side_l = lax.dynamic_index_in_dim(side, p, 0, keepdims=False)
        att, side_l, *r = attn_layer_step(
            spec, blk, x, lengths, ctx, index_pool, p, start_lengths, side_l,
            side_idx, active)
        with jax.named_scope("attn.kv_update"):
            side = lax.dynamic_update_index_in_dim(side, side_l, p, 0)
        with jax.named_scope("resid.add"):
            x = x + att
        y, c = _moe(spec, blk, heavy, p, x, active, moe_impl)
        with jax.named_scope("resid.add"):
            return (x + y, side, counters + c, rows + jnp.stack(r)), None

    (x, side, moe, rows), _ = lax.scan(
        layer, (x, side, jnp.zeros((3,), jnp.int32),
                jnp.zeros((3,), jnp.int32)),
        (light, jnp.arange(spec.n_layers)))
    with jax.named_scope("step.counters"):
        rows = rows // spec.n_layers   # index keys read, selected, K|V read
        return x, side, state, jnp.concatenate([rows[:1], moe, rows[1:]])


def write_side(pages, state: State, side, page_table, counts, start):
    """A decode chunk's side window into the two pools, once a chunk: the
    K|V lanes into the pages, the index keys' lanes into theirs, through
    the one table. Returns (pages, state)."""
    width = pages.shape[-1]
    with jax.named_scope("attn.kv_index"):
        keys = side[..., width:]
    state = dict(state, index_pages=write_rows_into_pages(
        state["index_pages"], keys, page_table, counts, start))
    return (write_rows_into_pages(pages, side, page_table, counts, start),
            state)
