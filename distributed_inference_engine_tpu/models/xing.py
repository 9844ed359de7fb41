"""The compressed-query latent-attention families: every layer MLA with a
compressed query (``q_lora_rank``) and YaRN-scaled rotary frequencies, a
dense SwiGLU MLP in the leading layers and sigmoid-routed experts (plus one
shared expert) in the rest. Two models, one seam between them, the residual
(``_sublayer`` / ``_streams`` / ``_collapse``):

- Xing4.0-29B-A4B (``model_type`` ``xing4_0``, ``xing_spec``): a residual of
  ``hc_mult`` = 4 streams read, written and mixed around EVERY sublayer by
  manifold-constrained hyper-connections (mHC, ``ops/mhc.py``);
- Kimi-K2.5 (``model_type`` ``kimi_k2``, ``kimi_spec``): ``hc_mult`` 0, the
  plain pre-norm residual ``x + F(RMSNorm(x))`` in the activation dtype, 64
  heads, and a chip that holds a FRACTION of the one routing group
  (``experts_held`` (0, 12) of 384), whose expert layer runs over its held
  assignments only (``ops/moe_routed.py`` ``moe_block_held``).

Published layer ``l`` (0-based) has a dense MLP if ``l <
first_k_dense_replace``, else experts. The equations are written out in
``perfbench/reference/xing4_mhc.py`` (the plain float32 reference) and in
``ops/mhc.py``, ``ops/mla.py``, ``ops/moe_routed.py``.

**The residual** of an mHC spec is ``X [B, T, n, D]`` float32 inside this
file and nowhere else (a plain one is ``[B, T, D]`` in the activation dtype
and has no ``resid.mhc`` scope): ``X_0[i]`` is the token's embedding for
every stream, and after the last kept layer the streams ADD to ``hidden
[B, T, D]`` in the activation dtype, so the final norm, the head, sampling
and the packed output are the shared ones (``models/base.py`` ``unembed``).

**The tree** is a list of per-layer dicts, as ``models/ling.py``'s: a
Python loop over ``spec.layer_plan`` and XLA compiles each kept layer.
``hc_attn`` / ``hc_mlp`` hold a sublayer's three mHC tensors (float32; a
plain-residual tree has neither).

**Cache**: one latent row a token for every layer (``c`` | ``k_rope`` | zero
lanes up to whole 128-lane tiles: ``ling.latent_row``, ``engine/paged_kv.py``'s
latent pool, ``paged_layers`` = all of them), read in place by the decode
kernel (``ops/flash_decode.py``), and NO per-sequence state: ``init_state``
gives arrays whose leading
dimension is 0, which ride the programs' donation and the decode carry as
any other. The family has no prefill that continues from cached pages
(prefix reuse, chunked prefill, the host tier and ``kv_export`` are refused
at load, ``engine/continuous.py``).

The next-token-prediction (MTP) layer is left out of the served tree: the
next-token logits do not depend on it and the engine's step yields one
token.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ..ops import mhc, mla
from ..ops.moe_routed import held_fraction_of_one_group, moe_body
from ..ops.norms import rms_norm
from .base import ModelSpec, embed
from .ling import (  # the latent pool's views and reader are the same code
    DECODE_COUNTERS,
    PREFILL_COUNTERS,
    _init_table,
    _proj,
    decode_context,
    latent_attention_step,
    latent_row,
    write_rows_into_pages,
    write_side,
)

__all__ = ["xing_spec", "kimi_spec", "init_params", "init_state",
           "zero_state_slot", "decode_context", "write_side",
           "DECODE_COUNTERS", "PREFILL_COUNTERS",
           "forward_prefill_into_pages", "forward_decode_step"]

Params = Dict[str, Any]
State = Dict[str, jnp.ndarray]

_YARN = dict(type="yarn", factor=64, original_max_position_embeddings=4096,
             beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)

# published values (config.json of XingChen-AGI/Xing4.0-29B-A4B)
_PUBLISHED = dict(
    vocab_size=131072, d_model=3584, n_heads=32, d_ff=9216,
    n_layers_published=40, first_k_dense_replace=2,
    n_experts=64, experts_per_token=4, moe_d_ff=1024, shared_d_ff=1024,
    n_group=1, topk_group=1, routed_scaling_factor=2.0,
    q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, rope_theta=10000.0,
    rope_scaling=_YARN, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    hc_clamp_min=-30.0, hc_clamp_max=30.0, norm_eps=1e-6,
    max_seq_len=262144,
)

# The routed experts' down projections are drawn this much below the other
# output projections': with top-4 of 64 sigmoid scores a token in five has,
# in some layer, its 4th and 5th best scores closer than the activations'
# bfloat16 rounding moves them, and served in bfloat16 it swaps that expert
# against a float32 reference. At the shared expert's scale one swap moved
# the final hidden state by a fifth, as no trained model's would; at an
# eighth it moves it by ~2 %, the size of the rounding itself (one v5e
# chip, PR 31: ``perfbench/reference/xing4_mhc.py``).
ROUTED_DOWN_SCALE = 0.125
# A chip that holds a FRACTION of one routing group (12 of 384: a token sends
# it 0.25 assignments a layer at a gate of ~0.35) draws them at 16 x the
# shared expert's instead, so that its share weighs in the layer's output
# (0.25^1/2 x 0.35 x 16 = 2.8 shared experts) and a wrong gate or a wrong
# slice moves a served chain of 72 tokens: at an eighth and at 1 it moved it
# less than the rounding, at 4 and 8 not every chain (one v5e chip, PR 41,
# calls 8 and 9). Only 1 top-8 swap in 16 touches a held expert; where one
# does, that token lands as far from the float32 argmax as a wrong model's
# (up to 1.1 of max|logit|), so this family's chains are judged by their
# share of exact argmaxes and not by the gap
# (``perfbench/reference/mla_moe_share.py``).
ROUTED_DOWN_SCALE_SHARE = 16.0

_SIZES: Dict[str, Dict[str, Any]] = {
    # every layer, for the record and for a pipeline that can hold it
    "xing4.0-29b-a4b": dict(kept_layers=tuple(range(40))),
    # ep_size 1: one chip holds each layer whole; stage 1 of a pipeline:
    # the leading dense layer 0 (counted once) and expert layers 2-7
    "xing4.0-pp1": dict(kept_layers=(0, 2, 3, 4, 5, 6, 7)),
    # test scale; YaRN factor 4 over an original context of 32, so a
    # sequence of a few pages crosses the ramp
    "xing-tiny": dict(
        vocab_size=256, d_model=64, n_heads=4, d_ff=128,
        n_layers_published=4, first_k_dense_replace=1,
        kept_layers=(0, 1, 2, 3), n_experts=8, experts_per_token=2,
        moe_d_ff=32, shared_d_ff=32, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_scaling=dict(_YARN, factor=4,
                          original_max_position_embeddings=32),
        max_seq_len=512),
}


# published values (config.json of moonshotai/Kimi-K2.5, the language model;
# no hyper-connections: ``hc_mult`` 0 is the plain residual)
_KIMI_PUBLISHED = dict(
    vocab_size=163840, d_model=7168, n_heads=64, d_ff=18432,
    n_layers_published=61, first_k_dense_replace=1,
    n_experts=384, experts_per_token=8, moe_d_ff=2048, shared_d_ff=2048,
    n_group=1, topk_group=1, routed_scaling_factor=2.827,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, rope_theta=50000.0,
    rope_scaling=_YARN, norm_eps=1e-5, max_seq_len=262144,
)

_KIMI_SIZES: Dict[str, Dict[str, Any]] = {
    # one of 32 chips that share each layer (attention data-parallel, the
    # routed experts expert-parallel 12 a chip, the vocabulary in 8
    # slices): experts 0-11 of the ONE routing group, vocabulary rows
    # 0-20,479, stage 1 of a pipeline = published layers 0-6
    "kimi-k2.5-ep32-pp1": dict(
        kept_layers=tuple(range(7)), experts_held=(0, 12),
        vocab_size=20480),
    # test scale: a quarter of one group held, YaRN factor 4 over 32
    "kimi-tiny": dict(
        vocab_size=256, d_model=64, n_heads=4, d_ff=128,
        n_layers_published=4, first_k_dense_replace=1,
        kept_layers=(0, 1, 2, 3), n_experts=16, experts_per_token=4,
        experts_held=(0, 4), moe_d_ff=32, shared_d_ff=32, q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16,
        rope_scaling=dict(_YARN, factor=4,
                          original_max_position_embeddings=32),
        max_seq_len=512),
}


def xing_spec(size: str = "xing4.0-pp1", **overrides) -> ModelSpec:
    return _spec("xing", _PUBLISHED, _SIZES, size, overrides)


def kimi_spec(size: str = "kimi-k2.5-ep32-pp1", **overrides) -> ModelSpec:
    return _spec("kimi", _KIMI_PUBLISHED, _KIMI_SIZES, size, overrides)


def _spec(family: str, published, sizes, size: str, overrides) -> ModelSpec:
    if size not in sizes:
        raise ValueError(
            f"unknown {family} size {size!r}; choose from {sorted(sizes)}")
    c = dict(published, **sizes[size])
    kept = tuple(c.pop("kept_layers"))
    dense = c.pop("first_k_dense_replace")
    c.pop("n_layers_published")
    base = dict(
        c, n_layers=len(kept), n_kv_heads=c["n_heads"],
        head_dim_override=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        experts_held=c.get("experts_held", (0, c["n_experts"])),
        layer_kinds=("mla",) * len(kept),
        layer_mlps=tuple("dense" if i < dense else "moe" for i in kept),
        layer_ids=kept, pos_emb="rope", norm="rmsnorm", mlp="swiglu",
        use_bias=False, tie_embeddings=False)
    base.update(overrides)
    return ModelSpec(**base).validate()


# --------------------------------------------------------------------- init


def _layer_shapes(spec: ModelSpec, mlp: str
                  ) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """name -> (shape, dtype, std) of every normal-drawn matrix."""
    D, H = spec.d_model, spec.n_heads
    dt = spec.dtype
    std = 0.02
    out_std = std / (2.0 * spec.n_layers) ** 0.5
    dq = spec.qk_nope_head_dim + spec.qk_rope_head_dim
    s = dict(
        w_qa=((D, spec.q_lora_rank), dt, std),
        w_qb=((spec.q_lora_rank, H * dq), dt, std),
        w_kva=((D, spec.kv_lora_rank + spec.qk_rope_head_dim), dt, std),
        w_kvb=((spec.kv_lora_rank,
                H * (spec.qk_nope_head_dim + spec.v_head_dim)), dt, std),
        wo=((H * spec.v_head_dim, D), dt, out_std))
    if mlp == "dense":
        s.update(w_gate_up=((D, 2 * spec.d_ff), dt, std),
                 w_down=((spec.d_ff, D), dt, out_std))
    else:
        held = spec.experts_held[1]
        s.update(w_router=((D, spec.n_experts), "float32", std),
                 w_gate_up=((held, D, 2 * spec.moe_d_ff), dt, std),
                 w_down=((held, spec.moe_d_ff, D), dt, out_std * (
                     ROUTED_DOWN_SCALE_SHARE
                     if held_fraction_of_one_group(spec)
                     else ROUTED_DOWN_SCALE)),
                 ws_gate_up=((D, 2 * spec.shared_d_ff), dt, std),
                 ws_down=((spec.shared_d_ff, D), dt, out_std))
    return s


@partial(jax.jit, static_argnums=(0, 1, 2))
def _init_layer(spec: ModelSpec, mlp: str, layer_id: int, key) -> Params:
    """One layer's tensors, each drawn and cast inside one program.
    Everything follows ``key`` but the expert bias, one fixed draw by
    published layer index (as ``models/ling.py``: routing skew across seeds
    stays out of a measured cell)."""
    shapes = _layer_shapes(spec, mlp)
    dt = spec.jnp_dtype
    keys = jax.random.split(key, len(shapes) + 2)
    out = {n: (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
           for k, (n, (shape, dtype, std)) in zip(keys, shapes.items())}
    for name, width in (("ln1_scale", spec.d_model),
                        ("ln2_scale", spec.d_model),
                        ("q_norm", spec.q_lora_rank),
                        ("kv_norm", spec.kv_lora_rank)):
        out[name] = jnp.ones((width,), dt)
    if spec.hc_mult:
        out["hc_attn"] = mhc.init_hc(spec, keys[-1])
        out["hc_mlp"] = mhc.init_hc(spec, keys[-2])
    if mlp == "moe":
        out["router_bias"] = 0.01 * jax.random.normal(
            jax.random.fold_in(jax.random.key(0), layer_id),
            (spec.n_experts,), jnp.float32)
    return out


def init_params(spec: ModelSpec, key: jax.Array) -> Params:
    """Random tree in ``spec.dtype``; float32 router, expert bias and (of
    an mHC spec) mHC tensors. The worker hands ``metadata.seed`` as the key."""
    spec.validate()
    plan = spec.layer_plan
    keys = jax.random.split(key, len(plan) + 2)
    v, d = spec.vocab_size, spec.d_model
    return {
        "tok_emb": _init_table((v, d), spec.dtype, keys[-1]),
        "lm_head": _init_table((d, v), spec.dtype, keys[-2]),
        "lnf_scale": jnp.ones((d,), spec.jnp_dtype),
        "layers": [_init_layer(spec, mlp, i, k)
                   for k, (_kind, mlp, i) in zip(keys, plan)],
    }


def init_state(spec: ModelSpec, max_slots: int, **_pool) -> State:
    """No layer keeps a per-sequence state: one array of 0 layers."""
    return {"none": jnp.zeros((0, max_slots), jnp.float32)}


@jax.jit
def zero_state_slot(state: State, slot: jnp.ndarray) -> State:
    return {n: a.at[:, slot].set(0) for n, a in state.items()}


# ----------------------------------------------------------------- layers


def _rope(spec: ModelSpec, x, positions):
    inv, amp = mla.yarn_inv_freq(x.shape[-1], spec.rope_theta,
                                 spec.rope_scaling)
    return mla.rope_interleaved(x, positions, spec.rope_theta, inv, amp)


def _softmax_scale(spec: ModelSpec) -> float:
    return mla.yarn_softmax_scale(
        spec.qk_nope_head_dim + spec.qk_rope_head_dim, spec.rope_scaling)


def _mla_inputs(spec: ModelSpec, blk: Params, h, positions):
    """h [B, T, D] (normalised) -> (q_nope, q_rope [B, T, H, .], cache rows
    [B, T, W]: ``ling.latent_row``)."""
    b, t, _ = h.shape
    dn, dr, r = spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.kv_lora_rank
    cq = rms_norm(_proj(h, blk["w_qa"]), blk["q_norm"], spec.norm_eps)
    q = _proj(cq, blk["w_qb"]).reshape(b, t, spec.n_heads, dn + dr)
    kva = _proj(h, blk["w_kva"])
    c = rms_norm(kva[..., :r], blk["kv_norm"], spec.norm_eps)
    k_rope = _rope(spec, kva[..., None, r:], positions)[:, :, 0]
    return (q[..., :dn], _rope(spec, q[..., dn:], positions),
            latent_row(spec, c, k_rope))


def _mla_out(blk: Params, o, dtype):
    return _proj(o.reshape(*o.shape[:-2], -1).astype(dtype), blk["wo"])


def mla_layer_prefill(spec: ModelSpec, blk: Params, h, positions, seq_lens):
    """h [B, T, D] -> (attention out, cache rows [B, T, W])."""
    b, t, _ = h.shape
    dn, r = spec.qk_nope_head_dim, spec.kv_lora_rank
    with jax.named_scope("attn.mla"):
        q_nope, q_rope, rows = _mla_inputs(spec, blk, h, positions)
        kv = _proj(rows[..., :r], blk["w_kvb"]).reshape(
            b, t, spec.n_heads, dn + spec.v_head_dim)
        o = mla.mla_causal_attention(
            q_nope, q_rope, kv, rows[..., r:r + spec.qk_rope_head_dim],
            seq_lens, scale=_softmax_scale(spec))
        return _mla_out(blk, o, h.dtype), rows


def mla_layer_step(spec: ModelSpec, blk: Params, h, positions, ctx, layer,
                   n_ctx, side, side_idx, active):
    """h [B, D] at ``positions`` [B]; ``ctx``, ``layer``, ``n_ctx``, ``side``
    as ``ling.latent_attention_step`` takes them. Returns (attention out,
    (side, latent rows read)): ``_sublayer``'s pair."""
    with jax.named_scope("attn.mla"):
        q_nope, q_rope, row = _mla_inputs(spec, blk, h[:, None],
                                          positions[:, None])
        o, side, rows_read = latent_attention_step(
            spec, blk["w_kvb"], q_nope[:, 0], q_rope[:, 0], row[:, 0], ctx,
            layer, n_ctx, side, side_idx, active,
            scale=_softmax_scale(spec))
        return _mla_out(blk, o, h.dtype), (side, rows_read)


def _sublayer(spec: ModelSpec, hc, scale, x, fn):
    """One sublayer around the residual: ``fn`` takes the normalised
    read-out [N, D] in the activation dtype and returns (y [N, D], whatever
    else it made). mHC-wrapped over x [N, n, D] float32 (``hc`` the
    sublayer's three tensors), or with ``hc_mult`` 0 the plain pre-norm
    residual ``x + fn(RMSNorm(x))`` over x [N, D] in the activation dtype."""
    if not spec.hc_mult:
        with jax.named_scope("resid.norm"):
            h = rms_norm(x, scale, spec.norm_eps)
        y, extra = fn(h)
        with jax.named_scope("resid.add"):
            return x + y, extra
    with jax.named_scope("resid.mhc"):
        pre, post, res = mhc.hc_maps(spec, hc, x)
        h = rms_norm(mhc.hc_read(x, pre), scale,
                     spec.norm_eps).astype(spec.jnp_dtype)
    y, extra = fn(h)
    with jax.named_scope("resid.mhc"):
        return mhc.hc_write(x, y, post, res), extra


def _streams(spec: ModelSpec, emb: jnp.ndarray) -> jnp.ndarray:
    """emb [N, D] -> X_0 [N, n, D] float32, every stream the embedding (a
    plain residual: the embedding itself)."""
    if not spec.hc_mult:
        return emb
    with jax.named_scope("resid.mhc"):
        return jnp.broadcast_to(emb.astype(jnp.float32)[:, None],
                                (emb.shape[0], spec.hc_mult, emb.shape[1]))


def _collapse(spec: ModelSpec, x: jnp.ndarray) -> jnp.ndarray:
    if not spec.hc_mult:
        return x
    with jax.named_scope("resid.mhc"):
        return jnp.sum(x, axis=1).astype(spec.jnp_dtype)


# --------------------------------------------------------------- programs


def forward_prefill_into_pages(
    spec: ModelSpec, params: Params,
    tokens: jnp.ndarray,      # [B, T] right-padded prompts
    seq_lens: jnp.ndarray,    # [B] true lengths (0 = pad row)
    pages: jnp.ndarray,       # [L, N, P, W] latent page pool (donated)
    state: State,             # zero-layer state (donated, handed back)
    page_table: jnp.ndarray,  # [B, MP] physical pages per row
    slot_ids: jnp.ndarray,    # [B] unused: no per-slot state
    moe_impl: str = "",
) -> Tuple[jnp.ndarray, jnp.ndarray, State, jnp.ndarray]:
    """Whole prompts from nothing: every layer scatters its cache rows into
    the pages. Returns (hidden [B, T, D], pages, state, MoE counters [3])."""
    del slot_ids
    b, t = tokens.shape
    with jax.named_scope("step.setup"):
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
        valid = (positions < seq_lens[:, None]).reshape(-1)
    emb = embed(spec, params, tokens, positions)
    x = _streams(spec, emb.reshape(b * t, -1))
    rows: List[jnp.ndarray] = []
    with jax.named_scope("step.setup"):
        counters = jnp.zeros((3,), jnp.int32)
    for blk, (_kind, mlp, _i) in zip(params["layers"], spec.layer_plan):
        def attn(h, blk=blk):
            att, r = mla_layer_prefill(spec, blk, h.reshape(b, t, -1),
                                       positions, seq_lens)
            return att.reshape(b * t, -1), r

        x, r = _sublayer(spec, blk.get("hc_attn"), blk["ln1_scale"], x, attn)
        rows.append(r)
        x, c = _sublayer(
            spec, blk.get("hc_mlp"), blk["ln2_scale"], x,
            lambda h, blk=blk, mlp=mlp: _mlp(spec, blk, mlp, h, valid,
                                             moe_impl))
        with jax.named_scope("step.counters"):
            counters = counters + c
    with jax.named_scope("attn.kv_index"):
        stacked, zero = jnp.stack(rows), jnp.zeros_like(seq_lens)
    pages = write_rows_into_pages(pages, stacked, page_table, seq_lens, zero)
    return _collapse(spec, x).reshape(b, t, -1), pages, state, counters


def forward_decode_step(
    spec: ModelSpec, params: Params,
    tokens: jnp.ndarray,         # [B] the most recent token per slot
    lengths: jnp.ndarray,        # [B] its position
    start_lengths: jnp.ndarray,  # [B] length when the chunk began
    ctx,                         # ``decode_context``: pool, table, attention
    side: jnp.ndarray,           # [L, B, Wc, W] the chunk's own rows
    state: State,                # zero-layer state, handed back
    active: jnp.ndarray,         # [B] bool
    moe_impl: str = "",
) -> Tuple[jnp.ndarray, jnp.ndarray, State, jnp.ndarray]:
    """One token for every slot. Returns (hidden [B, D], side, state,
    counters [``DECODE_COUNTERS``]: MoE's three and the latent rows the
    attention read, a layer); rows not ``active`` leave side alone."""
    emb = embed(spec, params, tokens[:, None], lengths[:, None])[:, 0]
    x = _streams(spec, emb)
    with jax.named_scope("step.setup"):
        side_idx = lengths - start_lengths
        counters = jnp.zeros((3,), jnp.int32)
        rows_read = jnp.int32(0)
    for i, (blk, (_kind, mlp, _id)) in enumerate(
            zip(params["layers"], spec.layer_plan)):
        x, (s, read) = _sublayer(
            spec, blk.get("hc_attn"), blk["ln1_scale"], x,
            lambda h, blk=blk, i=i: mla_layer_step(
                spec, blk, h, lengths, ctx, i, start_lengths, side[i],
                side_idx, active))
        with jax.named_scope("attn.kv_side"):
            side = side.at[i].set(s)
            rows_read = rows_read + read
        x, c = _sublayer(
            spec, blk.get("hc_mlp"), blk["ln2_scale"], x,
            lambda h, blk=blk, mlp=mlp: _mlp(spec, blk, mlp, h, active,
                                             moe_impl))
        with jax.named_scope("step.counters"):
            counters = counters + c
    with jax.named_scope("step.counters"):
        counters = jnp.append(counters, rows_read // len(spec.layer_plan))
    return _collapse(spec, x), side, state, counters


def _mlp(spec: ModelSpec, blk: Params, kind: str, h, valid, moe_impl):
    """The layer's MLP over the normalised read-out h [N, D] -> (out,
    counters int32 [3])."""
    if kind == "moe":
        return moe_body(spec)(spec, blk, h, valid, moe_impl)
    with jax.named_scope("mlp.dense"):
        gate, up = jnp.split(_proj(h, blk["w_gate_up"], jnp.float32), 2,
                             axis=-1)
        out = _proj((jax.nn.silu(gate) * up).astype(h.dtype), blk["w_down"])
        return out, jnp.zeros((3,), jnp.int32)
