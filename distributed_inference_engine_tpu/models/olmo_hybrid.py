"""Olmo-Hybrid-7B (``model_type`` ``olmo_hybrid``): Gated-DeltaNet linear
attention layers three to one with full softmax-attention layers, a dense
SwiGLU MLP in every layer.

Published layer ``i`` (0-based) is full attention if ``(i + 1) % 4 == 0``,
else Gated DeltaNet (``layer_types`` of the published config: exactly
periodic, no leading odd layer). The equations are written out in
``perfbench/reference/gdn_hybrid.py`` (the plain float32 reference) and in
``ops/kda.py`` (the delta rule both gates share).

- *Gated DeltaNet layer*: q, k (H x dk) and v (H x dv) projections, each
  channel through a causal convolution of ``gdn_conv`` taps and SiLU; q, k
  L2-normalised per head, q scaled by dk^-1/2; ``beta = 2 sigmoid(W_b x)``
  (the published ``linear_allow_neg_eigval``); ONE log-decay a head ``g =
  -exp(A_log) softplus(W_a x + dt_bias)``; state ``S [dk, dv]`` a head in
  float32; the read-out through a per-head RMSNorm, times ``SiLU(W_g x)``,
  through ``W_o``.
- *Full-attention layer*: H query and H K/V heads of ``d_model / H``, q and
  k RMS-normalised over the whole projection, NO rotary embedding (the
  published ``rope_theta`` is null: order comes from the recurrent layers),
  causal softmax at ``head_dim^-1/2``.
- Every sublayer's RMSNorm is applied to its OUTPUT (``x + norm(f(x))``,
  the OLMo 2 / 3 convention); the mixers read the residual as it is.

**The tree is ONE period's layer dicts stacked over the periods**
(``params["period"][j][name]`` is ``[n_periods, ...]``) and every program
``lax.scan``s over periods: XLA compiles one period, not every kept layer
(``models/ling.py`` and ``models/xing.py`` still loop over a list).

**Cache**: a full layer adds one K|V row of ``2 * H * head_dim`` values a
token to the family's page pool ``[n_full, pages, page, 2 * lanes]``; a
decode step reads the pages IN PLACE through ``ops/flash_decode.py``
(``kv_fused``: one pool, each half of a page's lanes copied where it lies)
with the chunk's own rows in a side window written back once a chunk; the
XLA form gathers ONE layer's live pages a step (CPU, tests). No program
holds a ``[L, B, S, W]`` copy of the table. A Gated-DeltaNet layer keeps
``S [H, dk, dv]`` float32 and the last ``gdn_conv - 1`` pre-convolution
rows per SEQUENCE. Pad positions of a prefill bucket and rows that are not
live in a decode step leave a sequence's state exactly as it was.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import kda
from ..ops.flash_decode import (
    flash_decode_attention_pallas,
    flash_decode_attention_xla,
)
from ..ops.flash_prefill import kv_prefill_attention
from ..ops.norms import rms_norm
from .base import ModelSpec, embed
from .ling import (  # the paged pool's views are the same code
    _init_table,
    _proj,
    decode_context,
    write_rows_into_pages,
    write_side,
)

__all__ = ["olmo_hybrid_spec", "init_params", "init_state", "zero_state_slot",
           "decode_context", "write_side", "DECODE_COUNTERS",
           "PREFILL_COUNTERS", "forward_prefill_into_pages",
           "forward_decode_step"]

Params = Dict[str, Any]
State = Dict[str, jnp.ndarray]

# a decode step's counter: K|V rows the full layers read, a layer; a
# prefill returns three zeros (where a routed family's programs carry the
# experts')
DECODE_COUNTERS = ("attn.full_table_rows",)
PREFILL_COUNTERS = (None, None, None)
PERIOD = 4      # published layer_types: 3 linear_attention + 1 full_attention

# published values (config.json of allenai/Olmo-Hybrid-7B)
_PUBLISHED = dict(
    vocab_size=100352, d_model=3840, n_heads=30, d_ff=11008,
    n_layers_published=32, gdn_key_head_dim=96, gdn_value_head_dim=192,
    gdn_conv=4, norm_eps=1e-6, max_seq_len=65536,
)

_SIZES: Dict[str, Dict[str, Any]] = {
    # every layer: 7.43 B parameters, for the record and for two chips
    "olmo-hybrid-7b": dict(kept_layers=tuple(range(32))),
    # stage 1 of a two-stage pipeline: published layers 0-15 = four whole
    # periods, every layer whole (all heads, the whole vocabulary)
    "olmo-hybrid-7b-pp2": dict(kept_layers=tuple(range(16))),
    # two periods at test scale; 4 heads of 32 = 128 K (and V) lanes, so the
    # interpreted decode kernel runs on it
    "olmo-hybrid-tiny": dict(
        vocab_size=256, d_model=128, n_heads=4, d_ff=192,
        n_layers_published=8, kept_layers=tuple(range(8)),
        gdn_key_head_dim=16, gdn_value_head_dim=32, max_seq_len=512),
}


def olmo_hybrid_spec(size: str = "olmo-hybrid-7b-pp2",
                     **overrides) -> ModelSpec:
    if size not in _SIZES:
        raise ValueError(f"unknown olmo_hybrid size {size!r}; choose from "
                         f"{sorted(_SIZES)}")
    c = dict(_PUBLISHED, **_SIZES[size])
    kept = tuple(c.pop("kept_layers"))
    c.pop("n_layers_published")
    base = dict(
        c, n_layers=len(kept), n_kv_heads=c["n_heads"],
        layer_kinds=tuple("full" if (i + 1) % PERIOD == 0 else "gdn"
                          for i in kept),
        layer_mlps=("dense",) * len(kept), layer_ids=kept,
        # no layer rotates anything: "rope" only says no learned table
        pos_emb="rope", norm="rmsnorm", mlp="swiglu", use_bias=False,
        tie_embeddings=False)
    base.update(overrides)
    return ModelSpec(**base).validate()


def _period(spec: ModelSpec) -> Tuple[str, ...]:
    return tuple(spec.layer_kinds[:spec.layer_kinds.index("full") + 1])


def _n_periods(spec: ModelSpec) -> int:
    return spec.n_layers // len(_period(spec))


def _conv_channels(spec: ModelSpec) -> int:
    return spec.n_heads * (2 * spec.gdn_key_head_dim
                           + spec.gdn_value_head_dim)


# --------------------------------------------------------------------- init


def _layer_shapes(spec: ModelSpec, kind: str
                  ) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """name -> (shape, dtype, std) of every normal-drawn tensor of ONE layer
    (the tree stacks them over the periods)."""
    D, H, F = spec.d_model, spec.n_heads, spec.d_ff
    dt, std = spec.dtype, 0.02
    if kind == "gdn":
        hk, hv = H * spec.gdn_key_head_dim, H * spec.gdn_value_head_dim
        # w_a / w_b at D^-1/2: the decay's and beta's logits then spread as
        # the residual's RMS (about 1) at any width, so beta covers most of
        # (0, 2) and a head's decay moves with the token
        s = dict(wq=((D, hk), dt, std), wk=((D, hk), dt, std),
                 wv=((D, hv), dt, std), w_g=((D, hv), dt, std),
                 w_a=((D, H), dt, D ** -0.5), w_b=((D, H), dt, D ** -0.5),
                 conv_w=((spec.gdn_conv, _conv_channels(spec)), dt, 0.5),
                 dt_bias=((H,), "float32", 0.5),
                 wo=((hv, D), dt, std))
    else:
        s = dict(wq=((D, D), dt, std), wk=((D, D), dt, std),
                 wv=((D, D), dt, std), wo=((D, D), dt, std))
    s.update(w_gate_up=((D, 2 * F), dt, std), w_down=((F, D), dt, std))
    return s


@partial(jax.jit, static_argnums=(0, 1))
def _init_stack(spec: ModelSpec, kind: str, key) -> Params:
    """One position of the period over ALL periods, each tensor drawn and
    cast inside one program."""
    n = _n_periods(spec)
    shapes = _layer_shapes(spec, kind)
    dt = spec.jnp_dtype
    keys = jax.random.split(key, len(shapes) + 1)
    out = {name: (jax.random.normal(k, (n, *shape), jnp.float32)
                  * std).astype(dtype)
           for k, (name, (shape, dtype, std)) in zip(keys, shapes.items())}
    # the norms sit on the sublayers' OUTPUTS: their scales take the depth
    # scaling the other families give their output projections, so the
    # residual's RMS stays near the embedding's 1 through every kept layer
    post = jnp.full((n, spec.d_model), (2.0 * spec.n_layers) ** -0.5, dt)
    out["attn_norm"], out["mlp_norm"] = post, post
    if kind == "gdn":
        # one decay rate a head, spread log-uniformly: exp(-A softplus(.))
        # reads about 0.5 at A = 0.7 and 0.999 at A = 0.001
        out["a_log"] = jax.random.uniform(
            keys[-1], (n, spec.n_heads), jnp.float32,
            jnp.log(1e-3), jnp.log(0.7))
        out["o_norm"] = jnp.ones((n, spec.gdn_value_head_dim), dt)
    else:
        out["q_norm"] = jnp.ones((n, spec.d_model), dt)
        out["k_norm"] = jnp.ones((n, spec.d_model), dt)
    return out


def init_params(spec: ModelSpec, key: jax.Array) -> Params:
    """Random tree in ``spec.dtype``; float32 ``a_log`` and ``dt_bias``. The
    worker hands ``metadata.seed`` as the key. The embedding is drawn at
    unit scale: the mixers read the residual un-normalised, and at the other
    families' 0.02 the first layer's gates, beta and SiLU would sit in
    their linear range whatever the token."""
    spec.validate()
    period = _period(spec)
    keys = jax.random.split(key, len(period) + 2)
    v, d = spec.vocab_size, spec.d_model
    return {
        "tok_emb": _init_table((v, d), spec.dtype, keys[-1], 1.0),
        "lm_head": _init_table((d, v), spec.dtype, keys[-2]),
        "lnf_scale": jnp.ones((d,), spec.jnp_dtype),
        "period": [_init_stack(spec, kind, k)
                   for k, kind in zip(keys, period)],
    }


# ------------------------------------------------------------- cache views


def _lane_pack(spec: ModelSpec) -> int:
    return kda.lane_pack(spec.n_heads, spec.gdn_value_head_dim)


def init_state(spec: ModelSpec, max_slots: int, **_pool) -> State:
    """Recurrent state of every Gated-DeltaNet layer for ``max_slots``
    sequences, ``[n_periods, gdn layers a period, slots, ...]``. ``S`` keeps
    ``kda.lane_pack`` heads side by side along the lanes (two of 96 x 192:
    rows of 384 lanes, which fill whole 128-lane tiles; one head's 192 would
    be padded to 256 in HBM and every decode step would move the padding)."""
    n, g = _n_periods(spec), len(_period(spec)) - 1
    h, dk, dv = spec.n_heads, spec.gdn_key_head_dim, spec.gdn_value_head_dim
    m = _lane_pack(spec)
    return {"S": jnp.zeros((n, g, max_slots, h // m, dk, m * dv),
                           jnp.float32),
            "conv": jnp.zeros((n, g, max_slots, spec.gdn_conv - 1,
                               _conv_channels(spec)), spec.jnp_dtype)}


def state_bytes_per_slot(spec: ModelSpec) -> int:
    h, dk, dv = spec.n_heads, spec.gdn_key_head_dim, spec.gdn_value_head_dim
    return spec.state_layers * (
        h * dk * dv * 4 + (spec.gdn_conv - 1) * _conv_channels(spec)
        * spec.jnp_dtype.itemsize)


@jax.jit
def zero_state_slot(state: State, slot: jnp.ndarray) -> State:
    return {n: a.at[:, :, slot].set(0) for n, a in state.items()}


# ----------------------------------------------------------------- layers


def _gdn_inputs(spec: ModelSpec, blk: Params, x: jnp.ndarray):
    """Everything of a Gated-DeltaNet layer that is per token: the
    pre-convolution q|k|v row (in the activation dtype: the conv tail
    stores it as is), beta in (0, 2), the log-decay g [..., H, 1], and the
    output gate's pre-activation. x [..., D], the residual as it is."""
    qkv = jnp.concatenate([_proj(x, blk[n]) for n in ("wq", "wk", "wv")], -1)
    beta = 2.0 * jax.nn.sigmoid(_proj(x, blk["w_b"], jnp.float32))
    g = kda.gdn_gate(_proj(x, blk["w_a"], jnp.float32), blk["a_log"],
                     blk["dt_bias"])
    return qkv, beta, g, _proj(x, blk["w_g"], jnp.float32)


def _gdn_heads(spec: ModelSpec, qkv: jnp.ndarray):
    """Post-convolution row -> SiLU, heads, L2-normalised q (scaled) and k."""
    h, dk, dv = spec.n_heads, spec.gdn_key_head_dim, spec.gdn_value_head_dim
    y = jax.nn.silu(qkv)
    lead = qkv.shape[:-1]
    q = y[..., :h * dk].reshape(*lead, h, dk)
    k = y[..., h * dk:2 * h * dk].reshape(*lead, h, dk)
    v = y[..., 2 * h * dk:].reshape(*lead, h, dv)
    return kda.l2_normalize(q) * dk ** -0.5, kda.l2_normalize(k), v


def _gdn_out(spec: ModelSpec, blk: Params, o, gate, dtype):
    """Per-head RMSNorm, times SiLU of the gate, the out projection, and the
    sublayer's own norm on what comes out."""
    o = rms_norm(o, blk["o_norm"], spec.norm_eps) \
        * jax.nn.silu(gate).reshape(o.shape)
    y = _proj(o.reshape(*o.shape[:-2], -1).astype(dtype), blk["wo"])
    return rms_norm(y, blk["attn_norm"], spec.norm_eps)


def gdn_layer_prefill(spec: ModelSpec, blk: Params, x, seq_lens):
    """x [B, T, D] -> (sublayer out [B, T, D], S [B, H, dk, dv] at each
    row's TRUE end, conv tail [B, conv-1, C])."""
    t = x.shape[1]
    with jax.named_scope("attn.gdn.prefill"):
        qkv, beta, g, gate = _gdn_inputs(spec, blk, x)
        tail = kda.conv_tail(qkv, seq_lens, spec.gdn_conv)
        q, k, v = _gdn_heads(spec, kda.causal_conv(qkv, blk["conv_w"]))
        live = jnp.arange(t)[None, :] < seq_lens[:, None]
        beta = jnp.where(live[..., None], beta, 0.0)
        g = jnp.where(live[..., None, None], g, 0.0)
        with jax.named_scope("recurrence"):
            o, S = kda.kda_chunked(q, k, v, g, beta)
        return _gdn_out(spec, blk, o, gate, x.dtype), S, tail


def gdn_layer_step(spec: ModelSpec, blk: Params, x, S_all, layer, tail,
                   active):
    """x [B, D]; S_all [layers, B, H / m, dk, m dv], every Gated-DeltaNet
    layer's state as the engine keeps it (``init_state``), of which this
    layer's is moved where it lies; tail [B, conv-1, C]: one token. Rows not
    ``active`` keep their S and tail untouched."""
    with jax.named_scope("attn.gdn.step"):
        qkv, beta, g, gate = _gdn_inputs(spec, blk, x)
        y, new_tail = kda.conv_step(tail, qkv, blk["conv_w"])
        q, k, v = _gdn_heads(spec, y)
        with jax.named_scope("recurrence"):
            o, S_all = kda.kda_step_inplace(S_all, layer, q, k, v, g, beta,
                                            active)
        out = _gdn_out(spec, blk, o, gate, x.dtype)
    with jax.named_scope("state.update"):
        tail = jnp.where(active[:, None, None], new_tail, tail)
    return out, S_all, tail


def _full_inputs(spec: ModelSpec, blk: Params, x):
    """x [..., D] -> (q [..., H, Dh], the cache row [..., 2 * lanes] = k | v):
    q and k RMS-normalised over the whole projection, nothing rotated."""
    q = rms_norm(_proj(x, blk["wq"]), blk["q_norm"], spec.norm_eps)
    k = rms_norm(_proj(x, blk["wk"]), blk["k_norm"], spec.norm_eps)
    row = jnp.concatenate([k, _proj(x, blk["wv"])], -1)
    return q.reshape(*q.shape[:-1], spec.n_heads, spec.head_dim), row


def _kv_heads(spec: ModelSpec, rows):
    """K|V rows [..., 2 * lanes] -> (k, v) [..., H, Dh]."""
    lanes = spec.kv_row_lanes
    shape = (*rows.shape[:-1], spec.n_kv_heads, spec.head_dim)
    return rows[..., :lanes].reshape(shape), rows[..., lanes:].reshape(shape)


def _full_out(spec: ModelSpec, blk: Params, o, dtype):
    y = _proj(o.reshape(*o.shape[:-2], -1).astype(dtype), blk["wo"])
    return rms_norm(y, blk["attn_norm"], spec.norm_eps)


def full_layer_prefill(spec: ModelSpec, blk: Params, x, seq_lens):
    """x [B, T, D] -> (sublayer out, cache rows [B, T, 2 * lanes])."""
    with jax.named_scope("attn.full"):
        q, rows = _full_inputs(spec, blk, x)
        o = kv_prefill_attention(q, rows, seq_lens, spec.n_kv_heads)
        return _full_out(spec, blk, o, x.dtype), rows


def full_layer_step(spec: ModelSpec, blk: Params, x, ctx, layer, n_ctx,
                    side, side_idx, active):
    """x [B, D]; ``ctx`` = (the pool [L, N, P, 2 * lanes], the page table,
    the attention's name), rows valid below ``n_ctx`` read where they lie;
    side [B, Wc, 2 * lanes] the chunk's own rows, this token's written at
    ``side_idx`` where ``active``. Returns (sublayer out, side, K|V rows the
    body read: int32, the kernel's own count of the pages it copied, or the
    whole gathered table, plus the side window)."""
    pages, page_table, impl = ctx
    n_layers, n_pages, page, width = pages.shape
    flat = pages.reshape(n_layers * n_pages, page, width)
    with jax.named_scope("attn.full"):
        q, row = _full_inputs(spec, blk, x)
        with jax.named_scope("attn.kv_update"):
            hot = (jnp.arange(side.shape[1])[None, :] == side_idx[:, None]) \
                & active[:, None]
            side = jnp.where(hot[..., None], row[:, None].astype(side.dtype),
                             side)
        side_k, side_v = _kv_heads(spec, side)
        # a row that is not live gets length 0: nothing of it is read
        n_prefix = jnp.where(active, n_ctx, 0)
        n_side = jnp.where(active, side_idx + 1, 0)
        if impl == "xla":
            # ONE layer's pages of the rows in this batch, K and V apart
            with jax.named_scope("attn.kv_gather"):
                b, mp = page_table.shape
                own = flat[layer * n_pages + page_table].reshape(
                    b * mp, page, width)
                lanes = spec.kv_row_lanes
                k_pages, v_pages = own[..., :lanes], own[..., lanes:]
                table = jnp.arange(b * mp, dtype=jnp.int32).reshape(b, mp)
            with jax.named_scope("flash_decode"):
                o = flash_decode_attention_xla(
                    q, k_pages, v_pages, table, n_prefix, side_k, side_v,
                    n_side, n_kv_heads=spec.n_kv_heads)
            n_pages_read = jnp.int32(b * mp)
        else:
            # the cached rows and the side window, and nothing else: what
            # the kernel's share of its roofline is taken over
            with jax.named_scope("flash_decode"):
                o, n_pages_read = flash_decode_attention_pallas(
                    q, flat, flat, page_table, n_prefix, side_k, side_v,
                    n_side, n_kv_heads=spec.n_kv_heads,
                    interpret=impl.endswith("_interpret"), layer=layer,
                    n_pages_per_layer=n_pages, kv_fused=True,
                    count_pages=True)
        rows_read = n_pages_read * page + side.shape[0] * side.shape[1]
        return _full_out(spec, blk, o, x.dtype), side, rows_read


def _mlp(spec: ModelSpec, blk: Params, x):
    """The dense SwiGLU over the residual as it is, its norm on the output."""
    with jax.named_scope("mlp.dense"):
        gate, up = jnp.split(_proj(x, blk["w_gate_up"], jnp.float32), 2, -1)
        y = _proj((jax.nn.silu(gate) * up).astype(x.dtype), blk["w_down"])
        return rms_norm(y, blk["mlp_norm"], spec.norm_eps)


def _residual(spec: ModelSpec, blk: Params, x, att):
    """The layer's two residual adds around its MLP."""
    with jax.named_scope("resid.add"):
        x = x + att
    m = _mlp(spec, blk, x)
    with jax.named_scope("resid.add"):
        return x + m


# --------------------------------------------------------------- programs


def forward_prefill_into_pages(
    spec: ModelSpec, params: Params,
    tokens: jnp.ndarray,      # [B, T] right-padded prompts
    seq_lens: jnp.ndarray,    # [B] true lengths (0 = pad row)
    pages: jnp.ndarray,       # [n_full, N, P, 2 * lanes] K|V pool (donated)
    state: State,             # Gated-DeltaNet state of every slot (donated)
    page_table: jnp.ndarray,  # [B, MP] physical pages per row
    slot_ids: jnp.ndarray,    # [B] the slot of each row; pad rows >= slots
    moe_impl: str = "",
) -> Tuple[jnp.ndarray, jnp.ndarray, State, jnp.ndarray]:
    """Whole prompts from a zero state. Full layers scatter their K|V rows
    into the pages; Gated-DeltaNet layers leave S and the conv tail AS OF
    EACH ROW'S TRUE END in the row's slot (pad positions move nothing).
    Returns (hidden [B, T, D], pages, state, three zero counters: the
    per-layer programs' packed layout carries a routed family's there)."""
    del moe_impl
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    x = embed(spec, params, tokens, positions)

    def period(x, blks):
        Ss, tails = [], []
        for blk in blks[:-1]:
            att, S, tail = gdn_layer_prefill(spec, blk, x, seq_lens)
            Ss.append(S)
            tails.append(tail)
            x = _residual(spec, blk, x, att)
        att, rows = full_layer_prefill(spec, blks[-1], x, seq_lens)
        x = _residual(spec, blks[-1], x, att)
        with jax.named_scope("state.stack"):
            return x, (jnp.stack(Ss), jnp.stack(tails), rows)

    x, (S, tails, rows) = lax.scan(period, x, params["period"])
    with jax.named_scope("state.update"):
        state = {
            "S": state["S"].at[:, :, slot_ids].set(
                kda.pack_states(S, _lane_pack(spec)), mode="drop"),
            "conv": state["conv"].at[:, :, slot_ids].set(
                tails.astype(state["conv"].dtype), mode="drop")}
    with jax.named_scope("attn.kv_index"):
        zero = jnp.zeros_like(seq_lens)
    pages = write_rows_into_pages(pages, rows, page_table, seq_lens, zero)
    with jax.named_scope("step.counters"):
        return x, pages, state, jnp.zeros((3,), jnp.int32)


def forward_decode_step(
    spec: ModelSpec, params: Params,
    tokens: jnp.ndarray,         # [B] the most recent token per slot
    lengths: jnp.ndarray,        # [B] its position
    start_lengths: jnp.ndarray,  # [B] length when the chunk began
    ctx,                         # ``decode_context``: pool, table, attention
    side: jnp.ndarray,           # [n_full, B, Wc, 2 * lanes] the chunk's rows
    state: State,                # [n_periods, g, B, ...]: row b IS slot b
    active: jnp.ndarray,         # [B] bool
    moe_impl: str = "",
) -> Tuple[jnp.ndarray, jnp.ndarray, State, jnp.ndarray]:
    """One token for every slot. Returns (hidden [B, D], side, state, the
    family's counter [1]: K|V rows the full layers' attention read, a
    layer); rows not ``active`` leave side and state alone."""
    del moe_impl
    x = embed(spec, params, tokens[:, None], lengths[:, None])[:, 0]
    with jax.named_scope("step.setup"):
        side_idx = lengths - start_lengths
    # the states ride the scan as ONE list of layers: the step's kernel
    # takes the array whole and moves layer p * n_gdn + j of it
    S = state["S"]
    n_gdn = S.shape[1]

    def period(carry, xs):
        x, side, S_all, conv_all, rows_read = carry
        blks, p = xs
        with jax.named_scope("state.read"):
            conv_p = lax.dynamic_index_in_dim(conv_all, p, 0, keepdims=False)
        tails = []
        for j, blk in enumerate(blks[:-1]):
            with jax.named_scope("state.read"):
                layer, tail_j = p * n_gdn + j, conv_p[j]
            att, S_all, tail = gdn_layer_step(
                spec, blk, x, S_all, layer, tail_j, active)
            tails.append(tail)
            x = _residual(spec, blk, x, att)
        with jax.named_scope("state.update"):
            conv_all = lax.dynamic_update_index_in_dim(
                conv_all, jnp.stack(tails), p, 0)
        with jax.named_scope("attn.kv_gather"):
            side_p = lax.dynamic_index_in_dim(side, p, 0, keepdims=False)
        att, side_p, read = full_layer_step(
            spec, blks[-1], x, ctx, p, start_lengths, side_p, side_idx,
            active)
        with jax.named_scope("attn.kv_update"):
            side = lax.dynamic_update_index_in_dim(side, side_p, p, 0)
        x = _residual(spec, blks[-1], x, att)
        with jax.named_scope("step.counters"):
            rows_read = rows_read + read
        return (x, side, S_all, conv_all, rows_read), None

    n = side.shape[0]
    (x, side, S_flat, conv, rows_read), _ = lax.scan(
        period, (x, side, S.reshape(-1, *S.shape[2:]), state["conv"],
                 jnp.int32(0)),
        (params["period"], jnp.arange(n)))
    with jax.named_scope("step.counters"):
        return (x, side, {"S": S_flat.reshape(S.shape), "conv": conv},
                (rows_read // n)[None])
