"""Weight-only int8 quantization for the inference matmuls.

Realises the ``quantized`` flag the reference carries as dead metadata
(``/root/reference/src/model_registry.py:55`` stores it, nothing reads it):
here it halves the weight bytes every decode step streams from HBM — the
binding resource of the memory-bound decode loop (SURVEY.md §7; TPU decode
throughput ≈ HBM bandwidth / bytes-per-step).

Scheme: symmetric per-output-channel int8.

- For a weight ``w`` contracted over its input axes, ``scale =
  max|w| / 127`` per output channel and ``q = round(w / scale)``.
- Dequantisation happens INSIDE the matmul: ``y = einsum(x, q.astype(bf16))
  * scale`` — XLA fuses the convert into the MXU feed, so only int8 bytes
  cross HBM; the per-channel scale applies to the matmul *output* (cheap:
  O(tokens·channels), not O(weights)).
- Activations, norms, biases, embeddings and the KV cache stay in the
  compute dtype — this is weight-only quantisation (the standard serving
  trade: no activation-quant error, all the bandwidth win).

``QuantizedTensor`` is a pytree, so quantized params flow through
``lax.scan`` over stacked layer blocks unchanged: the scan slices ``q`` and
``s`` along the layer axis together.

int4 (packed nibbles, ``bits=4``) — the FASTEST measured single-chip
config since r4: 4,254 tok/s vs int8's 3,661 at the 8B bs64 rung, via
the Mosaic in-register-unpack matmul (``ops/int4_matmul.py``), which
takes the layer-STACKED payload whole and selects the layer inside the
pallas grid (``split_indexed_blocks`` + ``IndexedQuant`` below keep those
payloads out of the layer-scan xs — a scanned slice feeding an opaque
custom call would be materialized as a real HBM copy, the r3→r4
1,584→3,308 cliff). The pure-XLA path (CPU backend, kernel mode "off",
shapes the kernel cannot tile) fuses the nibble shifts into the dot
operand (``_einsum_int4``) but XLA still materializes the unpacked
operand — its measured 1,584 tok/s is why the kernel exists.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """Quantized weight + broadcastable per-channel scales.

    ``bits=8`` (default): ``q`` is int8, same shape as the original weight;
    dequant = q * s. ``bits=4``: ``q`` is int8 holding TWO int4 values per
    byte, packed along ``pack_axis`` (the matmul's contraction axis, halved
    in shape) — SPLIT-HALF layout: source index ``k < K/2`` in the low
    nibble of byte ``k``, source index ``K/2 + k`` in the high nibble.
    (Round 3 packed even/odd interleaved; split-half lets the Mosaic
    matmul kernel unpack with two contiguous activation slices instead of
    a stride-2 gather — ``ops/int4_matmul.py``.)
    ``bits``/``pack_axis`` are pytree aux data (static), so quantized trees
    flow through jit/scan/shard machinery unchanged.
    """

    q: jnp.ndarray   # int8 payload (bits=4: contraction axis halved)
    s: jnp.ndarray   # float32; shape = weight shape with input axes size 1
    bits: int = 8
    pack_axis: int = 0               # bits=4 only: the halved axis, stored
                                     # NEGATIVE (from the end) so slicing
                                     # the stacked [L, ...] layer axis off
                                     # (lax.scan, truncated_draft) leaves
                                     # it pointing at the same dim
    kernel_mode: str = ""            # per-TENSOR int4 kernel mode stamped
                                     # by resolve_kernel_modes ("" =
                                     # inherit the process default): a tp
                                     # engine's "cp" selection rides its
                                     # own params instead of a process
                                     # global, so co-resident engines on
                                     # different meshes don't
                                     # cross-contaminate

    def tree_flatten(self):
        return (self.q, self.s), (self.bits, self.pack_axis,
                                  self.kernel_mode)

    @classmethod
    def tree_unflatten(cls, aux, children):
        if not isinstance(aux, tuple):
            aux = (8, -1)
        bits, pack_axis = aux[0], aux[1]
        mode = aux[2] if len(aux) > 2 else ""
        return cls(*children, bits=bits, pack_axis=pack_axis,
                   kernel_mode=mode)

    @property
    def shape(self):
        if self.bits == 4:
            a = self.pack_axis % self.q.ndim
            return tuple(d * 2 if i == a else d
                         for i, d in enumerate(self.q.shape))
        return self.q.shape

    @property
    def nbytes(self) -> int:
        return self.q.size * 1 + self.s.size * self.s.dtype.itemsize

    def _unpacked_int8(self) -> jnp.ndarray:
        """bits=4: int8 values at the ORIGINAL shape (materializing — for
        dequantize/tests; the matmul path unpacks into the dot operand
        without a stacked intermediate)."""
        assert self.bits == 4
        a = self.pack_axis % self.q.ndim
        lo = jnp.right_shift(jnp.left_shift(self.q, 4), 4)
        hi = jnp.right_shift(self.q, 4)
        return jnp.concatenate([lo, hi], axis=a)

    def dequantize(self, dtype=jnp.float32) -> jnp.ndarray:
        q = self._unpacked_int8() if self.bits == 4 else self.q
        return (q.astype(jnp.float32) * self.s).astype(dtype)


def quantize_weight(w: jnp.ndarray, reduce_axes: Sequence[int],
                    bits: int = 8) -> QuantizedTensor:
    """Symmetric int8/int4 over ``reduce_axes`` (the matmul's contraction
    axes; remaining axes are output/batch channels, one scale each).

    ``bits=4`` halves the HBM weight stream again: values in [-7, 7]
    (symmetric — -8 is unused), two per byte, split-half packed along the
    FIRST reduce axis (must be even-sized): the axis's first half in the
    low nibbles, second half in the high."""
    w32 = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=tuple(reduce_axes), keepdims=True)
    if bits == 8:
        scale = jnp.maximum(amax, 1e-8) / 127.0
        q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
        return QuantizedTensor(q=q, s=scale)
    if bits != 4:
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    a = sorted(int(ax) % w32.ndim for ax in reduce_axes)[0]
    if w32.shape[a] % 2:
        raise ValueError(f"int4 pack axis {a} has odd size {w32.shape[a]}")
    scale = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(w32 / scale), -7, 7).astype(jnp.int8)
    half = q.shape[a] // 2
    lo = jax.lax.slice_in_dim(q, 0, half, axis=a)
    hi = jax.lax.slice_in_dim(q, half, 2 * half, axis=a)
    packed = jax.lax.bitcast_convert_type(
        (lo.astype(jnp.uint8) & 0xF) | (hi.astype(jnp.uint8) << 4),
        jnp.int8)
    return QuantizedTensor(q=packed, s=scale, bits=4,
                           pack_axis=a - w32.ndim)


def repack_int4_interleaved_to_split(qt: QuantizedTensor) -> QuantizedTensor:
    """Convert a pre-r4 int4 payload (even/odd interleave: source index
    ``2k`` in byte ``k``'s low nibble, ``2k+1`` in its high) to the
    current split-half layout. Checkpoints persist raw packed bytes, so
    restore uses the saved layout marker to call this exactly once for
    old files (utils/checkpoint.py) — without it every weight matrix
    would be silently row-permuted."""
    if qt.bits != 4:
        return qt
    a = qt.pack_axis % qt.q.ndim
    even = jnp.right_shift(jnp.left_shift(qt.q, 4), 4)
    odd = jnp.right_shift(qt.q, 4)
    full = jnp.stack([even, odd], axis=a + 1).reshape(qt.shape)
    half = full.shape[a] // 2
    lo = jax.lax.slice_in_dim(full, 0, half, axis=a)
    hi = jax.lax.slice_in_dim(full, half, 2 * half, axis=a)
    packed = jax.lax.bitcast_convert_type(
        (lo.astype(jnp.uint8) & 0xF) | (hi.astype(jnp.uint8) << 4),
        jnp.int8)
    return dataclasses.replace(qt, q=packed)


# split-half int4 layout version persisted with checkpoints (bits=4 only):
# absent = pre-r4 even/odd interleave, 1 = split-half
INT4_LAYOUT_SPLIT_HALF = 1


def _einsum_int4(pattern: str, x: jnp.ndarray,
                 w: QuantizedTensor) -> jnp.ndarray:
    """Packed-int4 einsum: the contraction axis splits into (pairs, 2) on
    BOTH operands, and the weight side is the packed byte broadcast over
    the nibble axis with per-nibble shifts — pure elementwise/broadcast
    producers that XLA fuses into the dot operand, so only the packed
    bytes cross HBM (no stacked/interleaved intermediate)."""
    lhs, out = pattern.split("->")
    xs, ws = lhs.split(",")
    contract = [ch for ch in ws if ch.isalpha() and ch in xs
                and ch not in out]
    if len(contract) != 1:
        raise ValueError(
            f"int4 matmul needs exactly one contraction axis in {pattern!r}")
    c = contract[0]
    assert "P" not in pattern and "Q" not in pattern
    new = f"{xs.replace(c, 'P' + c)},{ws.replace(c, 'P' + c)}->{out}"
    ax_w = ws.index(c)
    if ax_w != w.pack_axis % w.q.ndim:
        raise ValueError(
            f"pattern {pattern!r} contracts axis {ax_w} but the int4 "
            f"payload is packed along axis {w.pack_axis % w.q.ndim}")
    # x: split the contraction axis into (2, half) — the axis's first
    # half rides the low nibbles, the second half the high, matching
    # quantize_weight's split-half packing
    tail = xs.replace("...", "")
    ax_x = x.ndim - len(tail) + tail.index(c)
    xr = x.reshape(x.shape[:ax_x] + (2, x.shape[ax_x] // 2)
                   + x.shape[ax_x + 1:])
    # w: broadcast the packed byte over a leading nibble axis; shift
    # [4, 0] then arithmetic >> 4 sign-extends each nibble
    qb = jnp.expand_dims(w.q, ax_w)
    shift_shape = [1] * qb.ndim
    shift_shape[ax_w] = 2
    shifts = jnp.asarray([4, 0], jnp.int8).reshape(shift_shape)
    wu = jnp.right_shift(jnp.left_shift(qb, shifts), 4).astype(x.dtype)
    y = jnp.einsum(new, xr, wu)
    return y * _out_scale(w.s).astype(y.dtype)


@dataclasses.dataclass
class IndexedQuant:
    """A layer-stacked ``QuantizedTensor`` + the layer index to use —
    built inside a layer-scan body (``split_indexed_blocks``) so the
    int4 Mosaic kernel can read its layer's blocks straight out of the
    whole stacked payload (scalar-prefetch index_map) instead of a
    scanned slice, which XLA would materialize as a real HBM copy
    before the opaque custom call."""

    qt: "QuantizedTensor"
    idx: Any                    # scalar int32 (traced)


def split_indexed_blocks(blocks: Dict[str, Any]):
    """Split a stacked blocks tree for a layer scan: kernel-eligible
    int4 payloads leave the scan xs (returned tree) and are re-attached
    per-iteration as ``IndexedQuant`` by ``rebuild(xs_slice, idx)``.
    Identity when the stacked kernel is not engaged (CPU backend, int8,
    …) — the XLA paths fuse scanned slices for free."""
    from .int4_matmul import stacked_kernel_wants

    static = {name: w for name, w in blocks.items()
              if stacked_kernel_wants(w)}
    if not static:
        return blocks, (lambda xs_blk, i: xs_blk)
    xs = {name: w for name, w in blocks.items() if name not in static}

    def rebuild(xs_blk, i):
        blk = dict(xs_blk)
        for name, qt in static.items():
            blk[name] = IndexedQuant(qt, i)
        return blk

    return xs, rebuild


# Fusable same-input matmul groups (r5, decode_profile.md levers): the
# members share the activation operand and contract the same axis, so
# their payloads concatenate along the OUTPUT axis into one stacked
# [L, K/2, sum(N)] tensor — one kernel launch per layer instead of 2-3,
# and the attention projections escape the small-N regime the int8
# profile measured at ~48% of HBM peak (qkv at N∈{1024,4096} vs the
# fused N=6144). Consumers (models.base._qkv/_mlp) slice the output —
# contiguous activation slices, free next to the weight stream.
FUSED_GROUPS: Dict[str, Tuple[str, ...]] = {
    "w_qkv": ("wq", "wk", "wv"),
    "w_gate_up": ("w_gate", "w_up"),
}
# biases that would have to be carried per-member (fusion is skipped when
# any is present — of the shipped families only qwen2 sets qkv_bias, and
# its win case is covered by the unfused path)
_FUSE_BLOCKERS = {"w_qkv": ("bq", "bk", "bv"), "w_gate_up": ("b_up",)}


def resolve_kernel_modes(params: Dict[str, Any]) -> Dict[str, Any]:
    """Stamp the int4 kernel mode ON the params (per-engine scope): when
    any int4 payload in ``params`` has landed SHARDED across devices (tp
    serving), every int4 tensor in the tree gets ``kernel_mode="cp"`` —
    the GSPMD-partitionable path; the direct pallas call is opaque to
    GSPMD and would force a weight gather. Fully-replicated multi-device
    placements (dp-only meshes, a speculative draft replicated next to a
    sharded target) are NOT stamped: the direct kernel + fusion path is
    both valid and faster there.

    Pure — returns a new tree, touches no process state. (Through r5 this
    flipped the module-global mode in ``ops.int4_matmul`` as an engine-
    construction side effect, so a tp engine silently switched every
    OTHER engine in the process onto the cp path.) An explicit global
    setting ("on"/"off"/"cp" via env or ``set_kernel_mode``) is
    respected: nothing is stamped, the global applies."""
    from .int4_matmul import kernel_mode

    if kernel_mode() != "auto":
        return params

    def _is_qt(x):
        return isinstance(x, QuantizedTensor)

    leaves = jax.tree_util.tree_leaves(params, is_leaf=_is_qt)
    sharded = any(
        isinstance(leaf, QuantizedTensor) and leaf.bits == 4
        and getattr(leaf.q, "sharding", None) is not None
        and len(leaf.q.sharding.device_set) > 1
        and not leaf.q.sharding.is_fully_replicated
        for leaf in leaves)
    if not sharded:
        return params
    return jax.tree_util.tree_map(
        lambda x: dataclasses.replace(x, kernel_mode="cp")
        if _is_qt(x) and x.bits == 4 else x,
        params, is_leaf=_is_qt)


def _int4_tensors(params: Dict[str, Any]):
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, QuantizedTensor)):
        if isinstance(leaf, QuantizedTensor) and leaf.bits == 4:
            yield leaf


def int4_kernel_paths(params: Dict[str, Any]) -> Dict[str, int]:
    """How the int4 tensors of a PREPARED tree reach the MXU, by count:
    ``direct`` (bare Mosaic call), ``cp`` (Mosaic call inside the
    custom_partitioning wrapper) or ``xla`` (dequant einsum). Read off the
    same predicates the traced matmuls use, so a deploy can assert that
    its weight stream rides the kernel instead of inferring it from
    throughput."""
    from .int4_matmul import kernel_path

    counts = {"direct": 0, "cp": 0, "xla": 0}
    for leaf in _int4_tensors(params):
        counts[kernel_path(leaf)] += 1
    return counts


def int4_kernel_blocks(params: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """The schedule each int4 tensor of a PREPARED tree that rides the
    Mosaic kernel resolves to, by payload shape: ``{"K2xN": {"decode":
    [bk, bn], "prefill": [bk, bn], "tuned": bool}}`` from the function the
    kernel itself calls (``int4_matmul.blocks_for``) at a decode step's
    rows and at a prefill's. ``tuned`` false marks a shape that fell to
    the default candidates instead of a measured table entry."""
    from .int4_matmul import block_report, kernel_path

    out: Dict[str, Dict[str, Any]] = {}
    for leaf in _int4_tensors(params):
        if kernel_path(leaf) != "xla":
            k2, n = leaf.q.shape[-2:]
            out[f"{k2}x{n}"] = block_report(k2, n)
    return out


def prepare_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Engine-init param preparation, one entry point for every engine:
    (1) stamp the int4 tensors with kernel mode "cp" if placement left
    payloads sharded across devices (per-engine scope, no global state);
    (2) fuse qkv / gate+up payloads when the kernel is engaged — skipped
    per-member for tp-sharded payloads (the fused output axis would
    shard across head groups), kept for replicated trees."""
    return fuse_block_weights(resolve_kernel_modes(params))


def fuse_block_weights(params: Dict[str, Any]) -> Dict[str, Any]:
    """Concatenate kernel-eligible stacked int4 payloads of each
    ``FUSED_GROUPS`` group along the output axis — a ONE-TIME device
    copy at engine init (never inside a traced forward: params are jit
    arguments, so a trace-time concat would re-copy ~1 GB every call).

    The fused entry is an ordinary stacked ``QuantizedTensor``: every
    consumer path (Mosaic kernel, XLA int4 einsum on the CPU backend,
    checkpoint round-trip, ``truncated_draft`` layer slicing) handles it
    unchanged. Identity when a group's members are absent, not int4
    stacked payloads, shape-mismatched, or bias-carrying. NOT applied
    for TP-SHARDED payloads: the concatenated output axis would shard
    across component boundaries (q/k/v head groups) — the check is
    per-member sharding, not the global kernel mode, so a REPLICATED
    tree (a speculative draft living next to a tp-sharded target that
    flipped the mode to "cp") still fuses."""
    from .int4_matmul import stacked_kernel_wants

    def _tp_sharded(w) -> bool:
        s = getattr(w.q, "sharding", None)
        return (s is not None and len(s.device_set) > 1
                and not s.is_fully_replicated)

    blocks = dict(params["blocks"])
    changed = False
    for fused_name, members in FUSED_GROUPS.items():
        if fused_name in blocks:
            continue                          # already fused (idempotent)
        ws = [blocks.get(m) for m in members]
        if not all(isinstance(w, QuantizedTensor) and w.bits == 4
                   and stacked_kernel_wants(w) for w in ws):
            continue
        if any(b in blocks for b in _FUSE_BLOCKERS[fused_name]):
            continue
        if any(_tp_sharded(w) for w in ws):
            continue
        if len({(w.q.shape[0], w.q.shape[1], w.pack_axis % w.q.ndim)
                for w in ws}) != 1:
            continue                          # [L, K/2] or pack axis differ
        fused = QuantizedTensor(
            q=jnp.concatenate([w.q for w in ws], axis=-1),
            s=jnp.concatenate([w.s for w in ws], axis=-1),
            bits=4, pack_axis=ws[0].pack_axis,
            kernel_mode=ws[0].kernel_mode)
        if not stacked_kernel_wants(fused):
            continue                          # summed N must still tile
        for m in members:
            del blocks[m]
        blocks[fused_name] = fused
        changed = True
    if not changed:
        return params
    out = dict(params)
    out["blocks"] = blocks
    return out


def matmul_any(pattern: str, x: jnp.ndarray, w: Any) -> jnp.ndarray:
    """``einsum`` that accepts a plain array, a ``QuantizedTensor``, or a
    layer-``IndexedQuant``.

    For a quantized weight the payload is widened to the activation dtype
    at the MXU feed and the per-output-channel scale multiplies the result
    — valid because the scale is constant over every contracted axis.
    int8 streams the bytes directly; packed int4 unpacks INSIDE the dot
    operand (``_einsum_int4``), so HBM sees half the int8 bytes.
    """
    if isinstance(w, IndexedQuant):
        from .int4_matmul import int4_einsum_kernel_stacked, pattern_fits

        if pattern_fits(pattern, x, w.qt.q.shape[1]):
            return int4_einsum_kernel_stacked(pattern, x, w.qt, w.idx)
        # fallback: slice the layer out (materializes — correctness only).
        # The scale must carry the stacked layer axis (keepdims — every
        # producer in ops.quant does); a rank mismatch here would silently
        # apply all L layers' scales to one layer's output (ADVICE r4)
        if w.qt.s.ndim != w.qt.q.ndim:
            raise ValueError(
                f"stacked scale rank {w.qt.s.ndim} != payload rank "
                f"{w.qt.q.ndim}: scale must keep the layer axis")
        s = w.qt.s[w.idx]
        w = dataclasses.replace(w.qt, q=w.qt.q[w.idx], s=s)
    if isinstance(w, QuantizedTensor):
        if w.bits == 4:
            from .int4_matmul import int4_einsum_kernel, kernel_wants

            if kernel_wants(pattern, x, w):
                return int4_einsum_kernel(pattern, x, w)
            return _einsum_int4(pattern, x, w)
        y = jnp.einsum(pattern, x, w.q.astype(x.dtype))
        return y * _out_scale(w.s).astype(y.dtype)
    return jnp.einsum(pattern, x, w)


def _out_scale(s: jnp.ndarray) -> jnp.ndarray:
    """Reshape the keepdims scale so it broadcasts against the einsum
    output: drop the contracted (size-1) LEADING axes.

    Works for every pattern this codebase uses because output channels of
    the weight are always its TRAILING axes (``de->...e``;
    MoE ``edf->e·f`` keeps its interior singleton, which broadcasts over
    the token axis of the ``[E, n, F]`` result).
    """
    out = s
    while out.ndim > 0 and out.shape[0] == 1:
        out = out[0]
    return out


# --------------------------------------------------------------- param tree

# blocks-tree weights: name -> contraction axes within ONE layer's slice
# (the stored arrays carry a leading [L] layer axis, so +1 on each when
# quantizing the stacked tree). Dense slices are [D_in, D_out].
_BLOCK_WEIGHTS: Dict[str, Tuple[int, ...]] = {
    "wq": (0,), "wk": (0,), "wv": (0,), "wo": (0,),
    "w_up": (0,), "w_gate": (0,), "w_down": (0,),
}
# MoE expert slices are [E, D_in, D_out] (w_up/w_gate: [E, D, F];
# w_down: [E, F, D]) — contraction is always slice axis 1
_MOE_WEIGHTS: Dict[str, Tuple[int, ...]] = {
    "w_up": (1,), "w_gate": (1,), "w_down": (1,),
}


# int4 lm_head vocab padding (r5, decode-profile lever): V=128256 =
# 256·501 tiles the Mosaic kernel only at bn=256 (~338 GB/s measured);
# padded to the next 2048-multiple it takes the big-block path. Pad
# columns are ZERO weights (their per-channel scale is the 1e-8 floor),
# so their logits are exactly 0 and models.base.unembed slices them off
# before softcap/sampling.
_LM_HEAD_PAD = 2048


def _pad_vocab(n: int) -> int:
    return -(-n // _LM_HEAD_PAD) * _LM_HEAD_PAD


def quantize_params(spec, params: Dict[str, Any],
                    bits: int = 8) -> Dict[str, Any]:
    """Quantize the big matmul weights of a loaded/initialised param tree
    (``bits``: 8 or 4 — packed nibbles, see ``quantize_weight``).

    Kept full-precision: embeddings (gather, not matmul), norms, biases,
    the MoE router (tiny and precision-sensitive), and a tied LM head
    (shares storage with ``tok_emb``).
    """
    out = dict(params)
    blocks = dict(params["blocks"])
    moe = bool(getattr(spec, "n_experts", 0))
    for name, axes in _BLOCK_WEIGHTS.items():
        w = blocks.get(name)
        if w is None or isinstance(w, QuantizedTensor):
            continue
        if moe and name in _MOE_WEIGHTS:
            axes = _MOE_WEIGHTS[name]
        blocks[name] = quantize_weight(w, [a + 1 for a in axes], bits=bits)
    out["blocks"] = blocks
    if (not spec.tie_embeddings and "lm_head" in out
            and not isinstance(out["lm_head"], QuantizedTensor)):
        w = out["lm_head"]
        if bits == 4 and w.shape[1] != _pad_vocab(w.shape[1]):
            w = jnp.pad(w, ((0, 0), (0, _pad_vocab(w.shape[1])
                                     - w.shape[1])))
        out["lm_head"] = quantize_weight(w, (0,), bits=bits)
    return out


def random_quantized_params(spec, key, w_std: float = 0.02,
                            bits: int = 8) -> Dict[str, Any]:
    """int8 param tree initialized DIRECTLY — no full-precision source.

    Random-init quantized serving at 8B scale cannot init-then-quantize:
    the bf16 tree plus the per-leaf f32 working copy peaks well above the
    model's own HBM footprint on exactly the single-chip int8 deploys
    quantization exists for (16 GB v5e, BASELINE.md rung 3). Here every
    quantizable weight is born int8 (uniform random payload — whose std is
    ``127/sqrt(3)`` — at constant per-channel scale ``w_std*sqrt(3)/127``,
    so the effective weight std is ≈ ``w_std``, matching ``init_params``;
    ADVICE r2 caught the earlier ``w_std/127``, which undershot ~0.58x);
    norms init to ones, biases to zeros, and
    full-precision leaves (embeddings, router) to scaled normals. FLOP
    and byte counts are identical to a quantized real checkpoint, which
    is all random-init serving is for.
    """
    import itertools

    from ..models.base import init_params

    abstract = jax.eval_shape(lambda: init_params(spec, jax.random.key(0)))
    moe = bool(getattr(spec, "n_experts", 0))
    counter = itertools.count()
    nk = lambda: jax.random.fold_in(key, next(counter))

    def q_leaf(leaf, axes):
        s_shape = tuple(1 if i in axes else d
                        for i, d in enumerate(leaf.shape))
        if bits == 4:
            # two uniform nibbles in [-7, 7] per byte, born packed; a
            # uniform-int[-n, n] payload has std sqrt(n(n+1)/3), so the
            # constant scale w_std/that keeps the effective weight std at
            # ~w_std (same correction as the int8 path)
            a = axes[0]
            if leaf.shape[a] % 2:
                raise ValueError(
                    f"int4 pack axis {a} has odd size {leaf.shape[a]}")
            half = tuple(d // 2 if i == a else d
                         for i, d in enumerate(leaf.shape))
            lo = jax.random.randint(nk(), half, -7, 8, dtype=jnp.int8)
            hi = jax.random.randint(nk(), half, -7, 8, dtype=jnp.int8)
            packed = jax.lax.bitcast_convert_type(
                (lo.astype(jnp.uint8) & 0xF)
                | (hi.astype(jnp.uint8) << 4), jnp.int8)
            std4 = (7 * 8 / 3.0) ** 0.5
            return QuantizedTensor(
                q=packed, s=jnp.full(s_shape, w_std / std4, jnp.float32),
                bits=4, pack_axis=a - len(leaf.shape))
        q = jax.random.randint(nk(), leaf.shape, -127, 128, dtype=jnp.int8)
        # discrete-uniform std over [-127, 127]: sqrt(n(n+1)/3), matching
        # the int4 path above (the continuous sqrt(3)/127 approximation is
        # ~0.4% off)
        std8 = (127 * 128 / 3.0) ** 0.5
        return QuantizedTensor(
            q=q, s=jnp.full(s_shape, w_std / std8, jnp.float32))

    def f_leaf(name, leaf):
        if "scale" in name:
            return jnp.ones(leaf.shape, leaf.dtype)
        # biases: ln*_bias plus the projection biases named bq/bk/bv/bo/
        # b_up/b_down in init_params
        if "bias" in name or name.startswith("b"):
            return jnp.zeros(leaf.shape, leaf.dtype)
        return (jax.random.normal(nk(), leaf.shape, jnp.float32)
                * w_std).astype(leaf.dtype)

    blocks: Dict[str, Any] = {}
    for name, leaf in abstract["blocks"].items():
        if name in _BLOCK_WEIGHTS:
            axes = (_MOE_WEIGHTS[name] if moe and name in _MOE_WEIGHTS
                    else _BLOCK_WEIGHTS[name])
            blocks[name] = q_leaf(leaf, tuple(a + 1 for a in axes))
        else:
            blocks[name] = f_leaf(name, leaf)
    out: Dict[str, Any] = {}
    for name, leaf in abstract.items():
        if name == "blocks":
            out[name] = blocks
        elif name == "lm_head" and not spec.tie_embeddings:
            if bits == 4:                   # vocab-pad (see _pad_vocab)
                leaf = jax.ShapeDtypeStruct(
                    (leaf.shape[0], _pad_vocab(leaf.shape[1])), leaf.dtype)
            out[name] = q_leaf(leaf, (0,))
        else:
            out[name] = f_leaf(name, leaf)
    return out


def param_bytes(params: Any) -> int:
    """Total stored bytes of a (possibly quantized) param tree."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        total += leaf.size * leaf.dtype.itemsize
    return total
