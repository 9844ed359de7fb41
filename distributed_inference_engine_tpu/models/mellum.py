"""Mellum2-12B-A2.5B (``model_type`` ``mellum``): sliding-window attention
layers three to one with full-attention layers, grouped-query in both, and
softmax-routed experts (top-8 of 64, no shared expert) in EVERY layer.

Published layer ``i`` (0-based) is full attention if ``(i + 1) % 4 == 0``,
else sliding (``layer_types``: exactly periodic). The equations are written
out in ``perfbench/reference/swa_moe.py`` (the plain float32 reference).

- ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``: pre-norm, a
  final RMSNorm, an untied head.
- *Attention*, both kinds: q ``H x Dh``, k, v ``Hkv x Dh``, no bias, no q/k
  normalisation; the whole ``Dh`` of q and k rotated (HF's split-halves
  pairing); scores at ``Dh^-1/2``; query head h reads K/V head ``h // (H /
  Hkv)``. A *sliding* layer rotates by plain RoPE at ``rope_theta`` and
  token i sees ``i - sliding_window < j <= i`` (the window counts the token
  itself); a *full* layer rotates by the YaRN table of
  ``spec.rope_scaling`` (per-frequency blend between the beta_fast /
  beta_slow bounds, cos and sin times ``attention_factor``) and sees every
  ``j <= i``.
- *MoE*: ``ops/moe_routed.py`` with ``moe_scoring`` ``softmax``.

**The tree is ONE period's layer dicts stacked over the periods**
(``params["period"][j][name]`` is ``[n_periods, ...]``) and every program
``lax.scan``s over periods, as ``models/olmo_hybrid.py``.

**Cache: two sets of K|V pages of ONE width and unlike lifetimes**
(``engine/paged_kv.py``). A full layer adds one K|V row a token to the
family's pool ``[n_full, pages, page, 2 * lanes]`` and keeps it while the
sequence lives. A sliding layer's rows go to the WINDOW pool
``state["window_pages"]`` ``[n_swa, window pages, page, 2 * lanes]``, whose
pages a slot holds through ``state["window_table"]`` ``[slots, MP]``: the
allocator frees a page as soon as the window has passed it and hands it to
whoever asks next, so a slot holds ``ceil(window / page) + 2`` of them at
most whatever its context. Nothing reads a freed page: a prefill writes
only the rows a later step can still see, and a decode step gives the
kernel (``ops/flash_decode.py`` ``kv_fused``) the first row of its window,
before which no page is copied and no row is unmasked. The chunk's own rows
of every layer, both kinds, gather in ONE side window ``[n_layers, B, Wc,
2 * lanes]`` (sliding layers first) written back once a chunk
(``write_side``).

The MTP head the model card mentions has no key in the published config
and is not served: the next-token logits do not depend on it.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import mla
from ..ops.flash_decode import (
    flash_decode_attention_pallas,
    flash_decode_attention_xla,
)
from ..ops.flash_prefill import kv_prefill_attention
from ..ops.moe_routed import COUNTERS as MOE_COUNTERS
from ..ops.moe_routed import PREFILL_COUNTERS, moe_block
from ..ops.norms import rms_norm
from .base import ModelSpec, embed
from .ling import _init_table, _proj, decode_context, write_rows_into_pages

__all__ = ["mellum_spec", "init_params", "init_state", "zero_state_slot",
           "decode_context", "write_side", "window_pages_per_slot", "DECODE_COUNTERS", "PREFILL_COUNTERS",
           "forward_prefill_into_pages", "forward_decode_step"]

Params = Dict[str, Any]
State = Dict[str, jnp.ndarray]

# a decode step's counters: K|V rows the full layers read (a layer), the
# routed experts' three, K|V rows the sliding layers read (a layer)
DECODE_COUNTERS = (("attn.full_table_rows",) + MOE_COUNTERS
                   + ("attn.window_table_rows",))
PERIOD = 4      # published layer_types: 3 sliding_attention + 1 full_attention

_YARN = dict(rope_type="yarn", factor=16,
             original_max_position_embeddings=8192, beta_fast=32,
             beta_slow=1, attention_factor=1.2772588722239782)

# published values (config.json of JetBrains/Mellum2-12B-A2.5B-Instruct)
_PUBLISHED = dict(
    vocab_size=98304, d_model=2304, n_heads=32, n_kv_heads=4,
    head_dim_override=128, d_ff=7168, n_layers_published=28,
    n_experts=64, experts_per_token=8, moe_d_ff=896, shared_d_ff=0,
    moe_scoring="softmax", sliding_window=1024, rope_theta=500000.0,
    rope_scaling=_YARN, norm_eps=1e-6, max_seq_len=131072,
)

# The routed experts' down projections against the attention's output
# projection (the idea of ``models/xing.py``, PR 31; the number is this
# model's). Top-8 of a softmax over 64 at logits of spread 1 leaves a
# token's 8th and 9th probabilities a median 6 % apart, closer than
# bfloat16 activations move them on about one (token, layer) in ten, and
# served in bfloat16 such a token swaps that expert against a float32
# reference. At the attention's scale one swap moves the hidden state by
# 7 % in layer 0 and 2.6 % in layer 3 (published widths, 192 tokens, the
# CPU, PR 39); at half of it by 3.4 % and 1.3 %, about the rounding's own
# size, while the experts still carry a tenth of what the attention
# writes to the residual (at Xing's eighth they carried a fortieth, and no
# wrong router could show in a logit).
ROUTED_DOWN_SCALE = 0.5

_SIZES: Dict[str, Dict[str, Any]] = {
    # every layer: 12.15 B parameters, for the record and for a pipeline
    "mellum2-12b-a2.5b": dict(kept_layers=tuple(range(28))),
    # stage 1 of a pipeline over the 28 layers: published layers 0-11 =
    # three whole periods, every layer whole (all 64 experts, all heads)
    "mellum2-12b-a2.5b-pp1": dict(kept_layers=tuple(range(12))),
    # two periods at test scale; 2 K/V heads of 64 = 128 K (and V) lanes, so
    # the interpreted decode kernel runs on it; YaRN factor 4 over an
    # original context of 32 and a window of 32: a sequence of a few pages
    # of 8 crosses the ramp and the window many times
    "mellum-tiny": dict(
        vocab_size=256, d_model=64, n_heads=8, n_kv_heads=2,
        head_dim_override=64, d_ff=128, n_layers_published=8,
        kept_layers=tuple(range(8)), n_experts=8, experts_per_token=2,
        moe_d_ff=32, sliding_window=32,
        rope_scaling=dict(_YARN, factor=4,
                          original_max_position_embeddings=32,
                          attention_factor=1.1386294361119891),
        max_seq_len=512),
}


def mellum_spec(size: str = "mellum2-12b-a2.5b-pp1", **overrides
                ) -> ModelSpec:
    if size not in _SIZES:
        raise ValueError(f"unknown mellum size {size!r}; choose from "
                         f"{sorted(_SIZES)}")
    c = dict(_PUBLISHED, **_SIZES[size])
    kept = tuple(c.pop("kept_layers"))
    c.pop("n_layers_published")
    base = dict(
        c, n_layers=len(kept), experts_held=(0, c["n_experts"]),
        layer_kinds=tuple("full" if (i + 1) % PERIOD == 0 else "swa"
                          for i in kept),
        layer_mlps=("moe",) * len(kept), layer_ids=kept,
        pos_emb="rope", norm="rmsnorm", mlp="swiglu", use_bias=False,
        tie_embeddings=False)
    base.update(overrides)
    return ModelSpec(**base).validate()


def _period(spec: ModelSpec) -> Tuple[str, ...]:
    return tuple(spec.layer_kinds[:spec.layer_kinds.index("full") + 1])


def _n_periods(spec: ModelSpec) -> int:
    return spec.n_layers // len(_period(spec))


# --------------------------------------------------------------------- init


def _layer_shapes(spec: ModelSpec
                  ) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """name -> (shape, dtype, std) of every normal-drawn tensor of ONE layer
    (the tree stacks them over the periods); both kinds hold the same."""
    D, H, Hkv, Dh = spec.d_model, spec.n_heads, spec.n_kv_heads, spec.head_dim
    E, F = spec.experts_held[1], spec.moe_d_ff
    dt, std = spec.dtype, 0.02
    out_std = std / (2.0 * spec.n_layers) ** 0.5
    # q and k at 2^1/2 D^-1/2 (0.0295 at 2304): a head's scores over the
    # normalised input then spread about 2 at any width, so a row's softmax
    # rests on tens of rows of a long context, not evenly on thousands
    # (whose values would average to nothing and leave the attention, its
    # window, its rotary table and its YaRN factor without a say in the
    # logits: drawn at 0.02 the scores spread 0.9)
    qk_std = (2.0 / D) ** 0.5
    return dict(
        wq=((D, H * Dh), dt, qk_std), wk=((D, Hkv * Dh), dt, qk_std),
        wv=((D, Hkv * Dh), dt, std), wo=((H * Dh, D), dt, out_std),
        # D^-1/2 (0.0208 at 2304): the router's logits spread as the
        # normalised input's RMS, about 1, at any width: a softmax over 64
        # whose top-8 is no near-tie on every token and which still draws
        # every expert
        w_router=((D, spec.n_experts), "float32", D ** -0.5),
        w_gate_up=((E, D, 2 * F), dt, std),
        w_down=((E, F, D), dt, out_std * ROUTED_DOWN_SCALE))


@partial(jax.jit, static_argnums=(0,))
def _init_stack(spec: ModelSpec, key) -> Params:
    """One position of the period over ALL periods, each tensor drawn and
    cast inside one program."""
    n = _n_periods(spec)
    shapes = _layer_shapes(spec)
    keys = jax.random.split(key, len(shapes))
    out = {name: (jax.random.normal(k, (n, *shape), jnp.float32)
                  * std).astype(dtype)
           for k, (name, (shape, dtype, std)) in zip(keys, shapes.items())}
    ones = jnp.ones((n, spec.d_model), spec.jnp_dtype)
    out["attn_norm"], out["mlp_norm"] = ones, ones
    return out


def init_params(spec: ModelSpec, key: jax.Array) -> Params:
    """Random tree in ``spec.dtype``; float32 router. The worker hands
    ``metadata.seed`` as the key."""
    spec.validate()
    period = _period(spec)
    keys = jax.random.split(key, len(period) + 2)
    v, d = spec.vocab_size, spec.d_model
    return {
        "tok_emb": _init_table((v, d), spec.dtype, keys[-1]),
        "lm_head": _init_table((d, v), spec.dtype, keys[-2]),
        "lnf_scale": jnp.ones((d,), spec.jnp_dtype),
        "period": [_init_stack(spec, k) for k in keys[:len(period)]],
    }


# ------------------------------------------------------------- cache views


def window_pages_per_slot(spec: ModelSpec, page_size: int) -> int:
    """The most window pages one slot ever holds: the pages ``window - 1``
    cached rows can touch (``ceil(window / page) + 1`` where the window is
    whole pages) and one more for the rows a decode chunk (at most a page
    of them) writes ahead."""
    return -(-spec.sliding_window // page_size) + 2


def window_read_pages(spec: ModelSpec, page_size: int) -> int:
    """The most cached pages a decode step of a sliding layer reads a row:
    ``window - 1`` rows touch ``ceil(window / page) + 1`` pages at most."""
    return -(-spec.sliding_window // page_size) + 1


def init_state(spec: ModelSpec, max_slots: int, page_size: int = 0,
               window_pages: int = 0, max_pages_per_seq: int = 0,
               **_pool) -> State:
    """The sliding layers' cache: their page pool (all sliding layers, one
    page id naming the same page in each) and the table of the pages each
    slot holds, which the allocator (``engine/paged_kv.py``) rewrites as
    the windows slide. Entries of pages it has freed are stale: no program
    reads through them."""
    return {"window_pages": jnp.zeros(
                (spec.window_layers, window_pages, page_size,
                 spec.cache_row_width), spec.jnp_dtype),
            "window_table": jnp.zeros((max_slots, max_pages_per_seq),
                                      jnp.int32)}


def zero_state_slot(state: State, slot: jnp.ndarray) -> State:
    """Nothing to zero: a slot's window pages go back to the free list and
    whoever gets them overwrites what it will read."""
    del slot
    return state


def first_window_row(spec: ModelSpec, position):
    """The first row a sliding layer's token at ``position`` sees."""
    return jnp.maximum(position - spec.sliding_window + 1, 0)


# ----------------------------------------------------------------- layers


def _rope_table(spec: ModelSpec, kind: str) -> Tuple[np.ndarray, float]:
    """(inverse frequencies [Dh / 2], amplitude of cos / sin) of a layer
    kind: plain RoPE for "swa", the spec's YaRN group for "full"."""
    d = spec.head_dim
    if kind == "swa" or not spec.rope_scaling:
        inv = spec.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
        return inv.astype(np.float32), 1.0
    inv, amp = mla.yarn_inv_freq(d, spec.rope_theta, spec.rope_scaling)
    return inv, float(dict(spec.rope_scaling).get("attention_factor", amp))


def _rope(spec: ModelSpec, kind: str, x, positions):
    """x [..., T, N, Dh] at positions [..., T]: HF's pairing (lane i with
    lane i + Dh / 2), float32 angles."""
    inv, amp = _rope_table(spec, kind)
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv)
    cos = (jnp.cos(ang) * amp)[..., None, :]
    sin = (jnp.sin(ang) * amp)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _attn_inputs(spec: ModelSpec, kind: str, blk: Params, h, positions):
    """h [B, T, D] (normalised) -> (q [B, T, H, Dh] rotated, the cache rows
    [B, T, 2 * lanes] = rotated k | v)."""
    b, t, _ = h.shape
    dh = spec.head_dim
    q = _proj(h, blk["wq"]).reshape(b, t, spec.n_heads, dh)
    k = _proj(h, blk["wk"]).reshape(b, t, spec.n_kv_heads, dh)
    k = _rope(spec, kind, k, positions).reshape(b, t, -1)
    rows = jnp.concatenate([k, _proj(h, blk["wv"])], -1)
    return _rope(spec, kind, q, positions), rows


def _kv_heads(spec: ModelSpec, rows):
    """K|V rows [..., 2 * lanes] -> (k, v) [..., Hkv, Dh]."""
    lanes = spec.kv_row_lanes
    shape = (*rows.shape[:-1], spec.n_kv_heads, spec.head_dim)
    return rows[..., :lanes].reshape(shape), rows[..., lanes:].reshape(shape)


def _attn_out(blk: Params, o, dtype):
    return _proj(o.reshape(*o.shape[:-2], -1).astype(dtype), blk["wo"])


def attn_layer_prefill(spec: ModelSpec, kind: str, blk: Params, x,
                       positions, seq_lens):
    """x [B, T, D] -> (attention out, cache rows [B, T, 2 * lanes]). A
    sliding layer computes its band only."""
    with jax.named_scope(f"attn.{kind}"):
        h = rms_norm(x, blk["attn_norm"], spec.norm_eps)
        q, rows = _attn_inputs(spec, kind, blk, h, positions)
        o = kv_prefill_attention(
            q, rows, seq_lens, spec.n_kv_heads,
            window=spec.sliding_window if kind == "swa" else 0)
        return _attn_out(blk, o, x.dtype), rows


def attn_layer_step(spec: ModelSpec, kind: str, blk: Params, x, positions,
                    pool, page_table, impl, layer, n_ctx, side, side_idx,
                    active):
    """x [B, D] at ``positions`` [B]; ``pool`` [L, N, P, 2 * lanes] the
    kind's pages and ``page_table`` [B, MP] the rows' pages in it, rows
    valid below ``n_ctx`` read where they lie, a sliding layer's from the
    first row of its window on; side [B, Wc, 2 * lanes] the chunk's own
    rows, this token's written at ``side_idx`` where ``active``. Returns
    (attention out, side, K|V rows the body read: int32, the kernel's own
    count of the pages it copied, or the gathered table, plus the side
    window)."""
    n_layers, n_pages, page, width = pool.shape
    flat = pool.reshape(n_layers * n_pages, page, width)
    if kind == "swa" and side.shape[1] > spec.sliding_window:
        raise ValueError(
            f"a decode chunk of {side.shape[1]} steps is longer than the "
            f"window of {spec.sliding_window} rows: its side window is read "
            "whole")
    with jax.named_scope(f"attn.{kind}"):
        h = rms_norm(x, blk["attn_norm"], spec.norm_eps)
        q, row = _attn_inputs(spec, kind, blk, h[:, None], positions[:, None])
        q, row = q[:, 0], row[:, 0]
        with jax.named_scope("attn.kv_update"):
            hot = (jnp.arange(side.shape[1])[None, :] == side_idx[:, None]) \
                & active[:, None]
            side = jnp.where(hot[..., None], row[:, None].astype(side.dtype),
                             side)
        side_k, side_v = _kv_heads(spec, side)
        # a row that is not live gets length 0: nothing of it is read
        n_prefix = jnp.where(active, n_ctx, 0)
        n_side = jnp.where(active, side_idx + 1, 0)
        first = (jnp.minimum(first_window_row(spec, positions), n_prefix)
                 if kind == "swa" else jnp.zeros_like(n_prefix))
        if impl == "xla":
            # ONE layer's pages of the rows in this batch, K and V apart; a
            # sliding layer's from the page its window starts in
            with jax.named_scope("attn.kv_gather"):
                b, mp = page_table.shape
                if kind == "swa":
                    mp = min(mp, window_read_pages(spec, page))
                    p0 = first // page
                    page_table = jnp.take_along_axis(
                        page_table,
                        jnp.minimum(p0[:, None] + jnp.arange(mp)[None, :],
                                    page_table.shape[1] - 1), axis=1)
                    n_prefix = jnp.maximum(n_prefix - p0 * page, 0)
                    first = first - p0 * page
                own = flat[layer * n_pages + page_table].reshape(
                    b * mp, page, width)
                lanes = spec.kv_row_lanes
                k_pages, v_pages = own[..., :lanes], own[..., lanes:]
                table = jnp.arange(b * mp, dtype=jnp.int32).reshape(b, mp)
            with jax.named_scope("flash_decode"):
                o = flash_decode_attention_xla(
                    q, k_pages, v_pages, table, n_prefix, side_k, side_v,
                    n_side, n_kv_heads=spec.n_kv_heads, first_rows=first)
            n_pages_read = jnp.int32(b * mp)
        else:
            with jax.named_scope("flash_decode"):
                o, n_pages_read = flash_decode_attention_pallas(
                    q, flat, flat, page_table, n_prefix, side_k, side_v,
                    n_side, n_kv_heads=spec.n_kv_heads,
                    interpret=impl.endswith("_interpret"), layer=layer,
                    n_pages_per_layer=n_pages, kv_fused=True,
                    count_pages=True, first_rows=first)
        rows_read = n_pages_read * page + side.shape[0] * side.shape[1]
        return _attn_out(blk, o, x.dtype), side, rows_read


_EXPERTS = ("w_gate_up", "w_down")


def _scanned(params: Params):
    """The tree as the programs' scan over periods takes it: (the period's
    layer dicts WITHOUT the experts' matrices, which ``lax.scan`` slices a
    period at a time; the experts' matrices of each position of the period
    WHOLE, every period's experts one after another). The grouped product
    is a kernel: a slice handed to it would be copied first."""
    light = [{k: v for k, v in blk.items() if k not in _EXPERTS}
             for blk in params["period"]]
    heavy = [{k: blk[k].reshape(-1, *blk[k].shape[2:]) for k in _EXPERTS}
             for blk in params["period"]]
    return light, heavy


# rows of one grouped product: a longer prefill runs its experts over
# equal parts of its tokens, one after another (at 16,896 tokens the sorted
# rows, the float32 gate|up and the float32 outputs of ONE product over all
# of them were 3 GB of temporaries beside a 12 GB tree and cache)
MOE_ROWS = 6144


def moe_parts(n: int) -> int:
    """The equal parts a prefill of ``n`` tokens runs its experts in: the
    fewest of at most ``MOE_ROWS`` tokens each that divide ``n``."""
    return next(k for k in range(-(-n // MOE_ROWS), n + 1) if n % k == 0)


def _moe(spec: ModelSpec, blk: Params, experts: Params, p, x, valid,
         moe_impl):
    """The routed experts of period ``p`` over RMSNorm(x), x [N, D] ->
    (out, counters [3])."""
    with jax.named_scope("mlp.norm"):
        h = rms_norm(x, blk["mlp_norm"], spec.norm_eps)
        offset = p * spec.experts_held[1]
    blk = dict(blk, **experts)
    n = x.shape[0]
    parts = moe_parts(n)
    if parts == 1:
        return moe_block(spec, blk, h, valid, moe_impl, expert_offset=offset)
    y, c = lax.map(
        lambda hv: moe_block(spec, blk, *hv, moe_impl, expert_offset=offset),
        (h.reshape(parts, n // parts, -1), valid.reshape(parts, -1)))
    with jax.named_scope("moe.combine"):
        return y.reshape(n, -1), c.sum(axis=0)


# --------------------------------------------------------------- programs


def forward_prefill_into_pages(
    spec: ModelSpec, params: Params,
    tokens: jnp.ndarray,      # [B, T] right-padded prompts
    seq_lens: jnp.ndarray,    # [B] true lengths (0 = pad row)
    pages: jnp.ndarray,       # [n_full, N, P, 2 * lanes] K|V pool (donated)
    state: State,             # the window pool and its table (donated)
    page_table: jnp.ndarray,  # [B, MP] physical pages per row, full pool
    slot_ids: jnp.ndarray,    # [B] the slot of each row; pad rows >= slots
    moe_impl: str = "",
) -> Tuple[jnp.ndarray, jnp.ndarray, State, jnp.ndarray]:
    """Whole prompts from nothing. Full layers scatter every K|V row into
    their pages; sliding layers only the rows a later step can still see
    (the last ``window - 1`` of each prompt), into the window pages their
    slot holds. Returns (hidden [B, T, D], pages, state, MoE counters
    [3])."""
    b, t = tokens.shape
    with jax.named_scope("step.setup"):
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
        valid = (positions < seq_lens[:, None]).reshape(-1)
    x = embed(spec, params, tokens, positions)
    # what of a sliding layer's rows stays: [first, seq_len) of each row
    keep = min(t, spec.sliding_window)
    with jax.named_scope("step.setup"):
        first = jnp.maximum(seq_lens - (spec.sliding_window - 1), 0)
        kept_pos = first[:, None] + jnp.arange(keep)[None, :]    # [B, keep]

    light, heavy = _scanned(params)

    def layer(kind, blk, experts, p, x, counters):
        att, rows = attn_layer_prefill(spec, kind, blk, x, positions,
                                       seq_lens)
        with jax.named_scope("resid.add"):
            x = x + att
        y, c = _moe(spec, blk, experts, p, x.reshape(b * t, -1), valid,
                    moe_impl)
        with jax.named_scope("resid.add"):
            return x + y.reshape(b, t, -1), counters + c, rows

    def period(carry, xs):
        x, counters = carry
        blks, p = xs
        kept = []
        for blk, experts in zip(blks[:-1], heavy):
            x, counters, rows = layer("swa", blk, experts, p, x, counters)
            with jax.named_scope("attn.window_keep"):
                kept.append(jnp.take_along_axis(
                    rows, jnp.minimum(kept_pos, t - 1)[..., None], axis=1))
        x, counters, rows = layer("full", blks[-1], heavy[-1], p, x,
                                  counters)
        with jax.named_scope("attn.window_keep"):
            return (x, counters), (jnp.stack(kept), rows)

    (x, counters), (kept, rows) = lax.scan(
        period, (x, jnp.zeros((3,), jnp.int32)),
        (light, jnp.arange(_n_periods(spec))))
    with jax.named_scope("attn.kv_index"):
        zero = jnp.zeros_like(seq_lens)
    pages = write_rows_into_pages(pages, rows, page_table, seq_lens, zero)
    with jax.named_scope("attn.kv_index"):
        window_table = state["window_table"][
            jnp.minimum(slot_ids, state["window_table"].shape[0] - 1)]
        kept = kept.reshape(-1, *kept.shape[2:])
        n_kept = seq_lens - first
    state = dict(state, window_pages=write_rows_into_pages(
        state["window_pages"], kept, window_table, n_kept, first))
    return x, pages, state, counters


def forward_decode_step(
    spec: ModelSpec, params: Params,
    tokens: jnp.ndarray,         # [B] the most recent token per slot
    lengths: jnp.ndarray,        # [B] its position
    start_lengths: jnp.ndarray,  # [B] length when the chunk began
    ctx,                         # ``decode_context``: pool, table, attention
    side: jnp.ndarray,           # [n_layers, B, Wc, 2 * lanes] chunk's rows
    state: State,                # the window pool and its table: row b IS
                                 # slot b
    active: jnp.ndarray,         # [B] bool
    moe_impl: str = "",
) -> Tuple[jnp.ndarray, jnp.ndarray, State, jnp.ndarray]:
    """One token for every slot. Returns (hidden [B, D], side, state as it
    came, the family's five counters: K|V rows the full layers' attention
    read a layer, MoE's three, K|V rows the sliding layers' read a layer);
    rows not ``active`` leave side alone."""
    pages, page_table, impl = ctx
    window_pages, window_table = state["window_pages"], state["window_table"]
    x = embed(spec, params, tokens[:, None], lengths[:, None])[:, 0]
    with jax.named_scope("step.setup"):
        side_idx = lengths - start_lengths
    n_swa = len(_period(spec)) - 1
    n_full = pages.shape[0]
    n_window = n_swa * n_full                # the full layers' side follows

    light, heavy = _scanned(params)

    def layer(kind, blk, experts, p, carry, pool, table, li, si):
        x, side, counters, read = carry
        with jax.named_scope("attn.kv_gather"):
            side_l = lax.dynamic_index_in_dim(side, si, 0, keepdims=False)
        att, side_l, r = attn_layer_step(
            spec, kind, blk, x, lengths, pool, table, impl, li,
            start_lengths, side_l, side_idx, active)
        with jax.named_scope("attn.kv_update"):
            side = lax.dynamic_update_index_in_dim(side, side_l, si, 0)
        with jax.named_scope("resid.add"):
            x = x + att
        y, c = _moe(spec, blk, experts, p, x, active, moe_impl)
        with jax.named_scope("resid.add"):
            return x + y, side, counters + c, read + r

    def period(carry, xs):
        x, side, counters, full_read, window_read = carry
        blks, p = xs
        for j, blk in enumerate(blks[:-1]):
            with jax.named_scope("step.setup"):
                li = p * n_swa + j
            x, side, counters, window_read = layer(
                "swa", blk, heavy[j], p, (x, side, counters, window_read),
                window_pages, window_table, li, li)
        with jax.named_scope("step.setup"):
            si_full = n_window + p
        x, side, counters, full_read = layer(
            "full", blks[-1], heavy[-1], p,
            (x, side, counters, full_read), pages, page_table, p, si_full)
        return (x, side, counters, full_read, window_read), None

    (x, side, moe, full_read, window_read), _ = lax.scan(
        period, (x, side, jnp.zeros((3,), jnp.int32), jnp.int32(0),
                 jnp.int32(0)),
        (light, jnp.arange(n_full)))
    with jax.named_scope("step.counters"):
        counters = jnp.concatenate([
            (full_read // n_full)[None], moe,
            (window_read // n_window)[None]])
    return x, side, state, counters


def write_side(pages, state: State, side, page_table, counts, start):
    """A decode chunk's side window into the two pools, once a chunk: the
    sliding layers' rows through the window table, the full layers' through
    the full pool's. Returns (pages, state)."""
    n_window = state["window_pages"].shape[0]
    state = dict(state, window_pages=write_rows_into_pages(
        state["window_pages"], side[:n_window], state["window_table"],
        counts, start))
    return (write_rows_into_pages(pages, side[n_window:], page_table, counts,
                                  start), state)
