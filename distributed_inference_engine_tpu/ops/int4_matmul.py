"""Mosaic (Pallas-TPU) matmul with in-register int4 unpack.

Closes the one SURVEY §2.2 "Pallas where XLA is insufficient" obligation
left open in round 3: packed-int4 weights through XLA's einsum decode at
1,584 tok/s vs int8's 3,661 at the 8B bs64 rung, because XLA materializes
the unpacked int8 operand in HBM — the decode step then streams the 2-byte
traffic AND the packed read. This kernel keeps the weight packed in HBM
and VMEM and unpacks nibbles in registers on the way into the MXU feed, so
HBM sees only the 0.5-byte/weight stream. (The reference has no analogue:
its "model" is an asyncio sleep, ``src/mock_models/fake_model.py:47``.)

Layout contract (``ops.quant.quantize_weight``): a ``[K, N]`` weight packs
SPLIT-HALF along the contraction axis into ``[K/2, N]`` int8 — source row
``k < K/2`` in the low nibble of byte row ``k``, row ``K/2 + k`` in the
high nibble. The matmul then decomposes into two contiguous-slice dots,

    y = x[:, :K/2] @ lo(P) + x[:, K/2:] @ hi(P),    P = packed bytes

with no stride-2 gather anywhere (an interleaved layout would need one on
either the activations or the unpacked weight — both Mosaic-hostile).

Grid: ``(M/bm, N/bn, K2/bk)``, k innermost ("arbitrary"), accumulating in
a VMEM f32 scratch; weight blocks stream exactly once per (m, n) tile, so
a bs64 decode step streams each weight byte exactly once. Nibble unpack is
3 VPU int32 ops + 2 converts per byte, overlapped with the MXU by Mosaic's
usual software pipeline.

Inside a layer scan the kernel must NOT take the scanned per-layer slice:
a pallas_call is an opaque custom call, so XLA materializes the slice as
a real HBM copy first (the r4 profile showed ~25% of the int4 step in
s8 dynamic-slice fusions — the 3,308 tok/s plateau). The stacked variant
(``_int4_matmul_stacked``) takes the whole ``[L, K/2, N]`` payload plus
the layer index as a scalar-prefetch argument; the grid's index_maps pick
block ``(layer, k, j)`` straight from the stacked array in HBM. Measured:
1,584 (XLA) → 3,308 (sliced kernel) → 4,254 tok/s (stacked kernel) vs
int8's 3,661 at the 8B bs64 rung.

r5 added (a) per-shape tuned blocks + engine-init payload fusion
(``ops.quant.fuse_block_weights``): 4,254 → 4,639 at bs64, and the
flagship moved to bs128 (5,315 tok/s — int4's freed HBM fits bs128 with
bf16 KV); and (b) tensor-parallel composition (mode "cp"): the kernel
rides a ``custom_partitioning`` op whose Shardy rule passes x pre-split
as (xlo, xhi) so both halves' K/2 axis and the payload's packed axis
share one reduction factor — the split-half layout then shards
COHERENTLY for row-parallel weights (each device's packed rows hold the
lo nibbles of exactly its xlo shard's columns and the hi nibbles of its
xhi shard's) and trivially for column-parallel, with no repacking and
no gather. Engines stamp "cp" onto their OWN int4 tensors when params
land sharded (``ops.quant.resolve_kernel_modes`` — per-engine scope;
the module-level mode below is only the process default / env
override).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# kernel dispatch mode (read at TRACE time):
#   auto      — use the kernel whenever the backend is an accelerator,
#               however many devices the process can see; on the CPU
#               backend (tests) take the XLA einsum path. Which FORM of
#               the kernel runs follows where the weight lives: engines
#               stamp "cp" onto int4 params that landed sharded
#               (``ops.quant.resolve_kernel_modes``); unstamped
#               (single-device or replicated) weights take the direct
#               call.
#   cp        — sharded (tp) path: the kernel rides a
#               ``custom_partitioning`` op with a Shardy rule, so the
#               partitioner splits the opaque pallas_call instead of
#               gathering around it.
#   on        — always, direct (interpreted on the CPU backend: kernel
#               tests)
#   off       — never
_MODE = os.environ.get("INT4_MATMUL_KERNEL", "auto")


def set_kernel_mode(mode: str) -> None:
    """"auto" | "cp" | "on" | "off" — see module docstring."""
    global _MODE
    if mode not in ("auto", "cp", "on", "off"):
        raise ValueError(f"bad int4 kernel mode {mode!r}")
    _MODE = mode


def kernel_mode() -> str:
    return _MODE


def _block_of(size: int, candidates: Tuple[int, ...]) -> Optional[int]:
    for b in candidates:
        if size % b == 0:
            return b
    return None


def _tensor_mode(w) -> str:
    """Effective kernel mode for one weight: the per-tensor stamp
    (``ops.quant.resolve_kernel_modes`` — tp engines mark their OWN int4
    tensors "cp" instead of flipping process state) or the module
    default."""
    return getattr(w, "kernel_mode", "") or _MODE


def _interpret() -> bool:
    """Pallas interpret mode is for the CPU backend only (the parity
    tests). On any other backend the kernel compiles or the program
    fails — it never interprets silently."""
    return jax.default_backend() == "cpu"


def _mode_engaged(mode: str = "") -> bool:
    """Mode/backend half of kernel eligibility (shared by the per-layer
    and stacked predicates): "on"/"cp" always, "auto" on every backend
    but CPU. The number of visible devices plays no part: a tp=1 deploy
    on a four-chip host holds its weights on one chip and takes the
    direct kernel like a one-chip host does; weights that landed sharded
    carry the "cp" stamp (``ops.quant.resolve_kernel_modes``), which
    wraps the kernel in a custom_partitioning op so the partitioner can
    split it — a bare pallas_call is opaque and would force a gather."""
    mode = mode or _MODE
    if mode == "off":
        return False
    return mode in ("on", "cp") or not _interpret()


def pattern_fits(pattern: str, x, k2: int) -> bool:
    """Structural half of kernel eligibility (shared with ``matmul_any``'s
    ``IndexedQuant`` routing): contraction on x's LAST axis and the
    weight's axis 0, out = x batch dims + N, x width = 2·K/2."""
    lhs, out = pattern.split("->")
    xs, ws = lhs.split(",")
    if len(ws) != 2 or not xs.endswith(ws[0]) or ws[0] in out \
            or ws[1] not in out:
        return False     # contraction must be x's LAST axis and w's axis 0
    if not out.endswith(ws[1]) or xs.replace(ws[0], "") + ws[1] != out:
        return False                    # out = x batch dims + N
    return x.shape[-1] == 2 * k2


def _payload_wants(w) -> bool:
    """Weight half of kernel eligibility for an unstacked ``[K/2, N]``
    payload: mode allows it, packed on axis 0, and K/2 and N divide the
    block candidates."""
    if not _mode_engaged(_tensor_mode(w)):
        return False
    if w.q.ndim != 2 or w.pack_axis % w.q.ndim != 0:
        return False                    # payload must be packed on axis 0
    k2, n = w.q.shape
    return (_block_of(k2, _K_BLOCKS) is not None
            and _block_of(n, _N_BLOCKS) is not None)


def kernel_wants(pattern: str, x, w) -> bool:
    """True when the Mosaic kernel should take this einsum: the payload
    is eligible (``_payload_wants``) and contracted on its packed axis.
    Everything else takes the XLA path."""
    return _payload_wants(w) and pattern_fits(pattern, x, w.q.shape[0])


def kernel_path(w) -> str:
    """"direct" | "cp" | "xla" — how an int4 ``QuantizedTensor`` of a
    prepared tree reaches the MXU (``ops.quant.int4_kernel_paths``)."""
    wants = stacked_kernel_wants(w) if w.q.ndim == 3 else \
        w.q.ndim == 2 and _payload_wants(w)
    if not wants:
        return "xla"
    return "cp" if _tensor_mode(w) == "cp" else "direct"


# preference order measured on v5e at the 8B decode shape ([64,4096] @
# [4096,14336]): bk1024/bn2048 runs 24.9 us/iter vs 82.5 at bk512/bn512 —
# bigger blocks amortize the per-block VPU unpack + loop overhead; the
# unpack STYLE (int32 shifts vs xor-bias) measured within noise of itself.
# int8-typed shifts don't compile on this Mosaic — keep the int32 widen.
_K_BLOCKS = (1024, 512, 256, 128)
_N_BLOCKS = (2048, 1024, 512, 256, 128)

# measured per-shape winners, (K/2, N) -> (bk, bn): the r5 tuning sweep
# (examples/int4_kernel_tune.py, v5e, M=64 decode tile, median of 5
# device-side timed passes) found no single block pair wins every shape —
# the 8B fused gate+up stream runs 601 GB/s at bk2048/bn1024 vs ~495 at
# the table default, and the fused-qkv shape actively pathologies at
# bn=2048 (168-336 GB/s vs 461 at bk1024/bn1024). Shapes not listed fall
# back to the preference tables above.
_TUNED_BLOCKS = {
    (2048, 6144): (1024, 1024),     # qkv fused     461 GB/s
    (2048, 4096): (512, 4096),      # wo / wq       449 GB/s
    (2048, 28672): (2048, 1024),    # gate+up fused 601 GB/s
    (7168, 4096): (512, 4096),      # w_down        532 GB/s
    (2048, 129024): (2048, 2048),   # padded lm_head 619 GB/s (vs 551 at
                                    # the table default; measured with a
                                    # 4x-stacked payload — a single-layer
                                    # stack is loop-INVARIANT in the tune
                                    # scan and XLA hoists the call)
}


def _blocks_for(k2: int, n: int) -> Tuple[Optional[int], Optional[int]]:
    bk, bn = _TUNED_BLOCKS.get((k2, n), (None, None))
    return (bk or _block_of(k2, _K_BLOCKS), bn or _block_of(n, _N_BLOCKS))


def _int4_matmul_2d(x, packed, scale, *, interpret: bool = False):
    """``[M, K] @ unpack([K/2, N]) * scale -> [M, N]`` (dtype of x) —
    the degenerate L=1 case of the stacked kernel (one code path, one
    set of tuning constants)."""
    k2, n = packed.shape
    return _int4_matmul_stacked(x, packed[None], scale.reshape(1, 1, n),
                                jnp.int32(0), interpret=interpret)


def int4_einsum_kernel(pattern: str, x, w):
    """``matmul_any``'s kernel path: flatten x's batch dims to M, run the
    2-D kernel, restore. ``kernel_wants(pattern, x, w)`` must hold.
    Mode "cp" routes through the GSPMD-partitionable wrapper — a
    quantized lm_head is tp-sharded on vocab (``parallel/sharding.py``),
    and feeding the sharded payload to the direct (opaque) pallas call
    would force GSPMD to gather it every step."""
    k2, n = w.q.shape
    lead = x.shape[:-1]
    xm = x.reshape(-1, x.shape[-1])
    interpret = _interpret()
    if _tensor_mode(w) == "cp":
        y = _cp_stacked(interpret)(xm[:, :k2], xm[:, k2:], w.q[None],
                                   w.s.astype(jnp.float32).reshape(1, 1, n),
                                   jnp.zeros((1,), jnp.int32))
    else:
        y = _int4_matmul_2d(xm, w.q, w.s.astype(jnp.float32),
                            interpret=interpret)
    return y.reshape(lead + (n,))


# ------------------------------------------------- stacked (layer-indexed)


def stacked_kernel_wants(w) -> bool:
    """True when a layer-stacked ``[L, K/2, N]`` int4 payload should ride
    the scalar-prefetch kernel: the layer slice then happens INSIDE the
    pallas grid (the index_map picks block (layer, k, j) straight from
    HBM). Pulling the weight through the scan xs instead would make XLA
    materialize each layer's slice as a real HBM copy before the opaque
    custom call — measured at ~25% of the int4 decode step (r4 profile:
    ~230 ms of s8 dynamic-slice fusions per 930 ms of chunks)."""
    from .quant import QuantizedTensor

    if not isinstance(w, QuantizedTensor) \
            or not _mode_engaged(_tensor_mode(w)):
        return False
    if w.bits != 4 or w.q.ndim != 3 or w.pack_axis % (w.q.ndim - 1) != 0:
        return False                # per-layer slice must pack on axis 0
    _l, k2, n = w.q.shape
    return (_block_of(k2, _K_BLOCKS) is not None
            and _block_of(n, _N_BLOCKS) is not None)


def _kernel_stacked(l_ref, xlo_ref, xhi_ref, p_ref, s_ref, o_ref, acc_ref):
    del l_ref                       # consumed by the index_maps
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p = p_ref[0].astype(jnp.int32)
    lo = jax.lax.shift_right_arithmetic(jax.lax.shift_left(p, 28), 28)
    hi = jax.lax.shift_right_arithmetic(p, 4)
    dt = xlo_ref.dtype
    acc_ref[...] += (
        jnp.dot(xlo_ref[...], lo.astype(dt),
                preferred_element_type=jnp.float32)
        + jnp.dot(xhi_ref[...], hi.astype(dt),
                  preferred_element_type=jnp.float32))

    @pl.when(k == pl.num_programs(2) - 1)
    def _emit():
        o_ref[...] = (acc_ref[...] * s_ref[0]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "bk", "bn"))
def _int4_matmul_stacked(x, packed, scale, layer, *, interpret: bool = False,
                         bk: Optional[int] = None, bn: Optional[int] = None):
    """``[M, K] @ unpack(packed[layer]) * scale[layer] -> [M, N]``;
    ``packed [L, K/2, N]`` stays whole in HBM — the grid's index_map
    selects the layer via scalar prefetch, so no slice is materialized.

    ``bk``/``bn`` override the block-size preference tables — the tuning
    surface ``examples/int4_kernel_tune.py`` sweeps on hardware; defaults
    are the measured winners."""
    m, kdim = x.shape
    nl, k2, n = packed.shape
    if kdim != 2 * k2:
        raise ValueError(f"x K={kdim} vs packed K/2={k2}")
    tbk, tbn = _blocks_for(k2, n)
    bk = bk or tbk
    bn = bn or tbn
    if bk is None or bn is None:
        raise ValueError(f"untileable shapes K/2={k2} N={n}")
    if k2 % bk or n % bn:
        # explicit overrides must divide: a flooring grid would silently
        # drop trailing K rows / leave output columns unwritten
        raise ValueError(f"blocks bk={bk} bn={bn} do not divide "
                         f"K/2={k2} N={n}")
    # activations tile at (16, 128) for bf16 — pad M up, slice back after.
    # bm tops out at 128 to keep the f32 accumulator block ≤1 MB alongside
    # the 2 MB double-buffered weight blocks
    bm = _block_of(m, (128, 64, 32, 16))
    if bm is None:
        bm = min(-(-m // 16) * 16, 128)
        x = jnp.pad(x, ((0, -m % bm), (0, 0)))
    mp = x.shape[0]

    grid = (mp // bm, n // bn, k2 // bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, l: (i, k)),
            pl.BlockSpec((bm, bk), lambda i, j, k, l: (i, k)),
            pl.BlockSpec((1, bk, bn), lambda i, j, k, l: (l[0], k, j)),
            pl.BlockSpec((1, 1, bn), lambda i, j, k, l: (l[0], 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, l: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        _kernel_stacked,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # the int32 nibble-widening temporaries ([bk, bn] lo+hi) top
            # 16 MB at the prefill tile (bm=128, bn=2048) — past the
            # default scoped-vmem limit but well inside v5e's 128 MB
            # physical VMEM (measured: compiles + runs at 64 MB)
            vmem_limit_bytes=64 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(
            flops=2 * mp * n * kdim,
            bytes_accessed=(k2 * n) + 2 * mp * kdim * (n // bn)
                           + mp * n * x.dtype.itemsize,
            transcendentals=0),
        interpret=interpret,
    )(jnp.atleast_1d(layer).astype(jnp.int32),
      x[:, :k2], x[:, k2:], packed,
      scale.reshape(nl, 1, n))
    return out[:m] if mp != m else out


def int4_einsum_kernel_stacked(pattern: str, x, w, layer):
    """Stacked-kernel path for a layer-indexed weight (``IndexedQuant``):
    flatten x's batch dims to M, run the scalar-prefetch kernel against
    the WHOLE stacked payload, restore. Pattern must satisfy
    ``kernel_wants`` on the per-layer 2-D slice shape. Mode "cp" routes
    through the GSPMD-partitionable wrapper instead of the direct call."""
    _l, k2, n = w.q.shape
    lead = x.shape[:-1]
    xm = x.reshape(-1, x.shape[-1])
    interpret = _interpret()
    if _tensor_mode(w) == "cp":
        y = _cp_stacked(interpret)(xm[:, :k2], xm[:, k2:], w.q,
                                   w.s.astype(jnp.float32),
                                   jnp.atleast_1d(layer).astype(jnp.int32))
    else:
        y = _int4_matmul_stacked(xm, w.q, w.s.astype(jnp.float32), layer,
                                 interpret=interpret)
    return y.reshape(lead + (n,))


# ------------------------------------------ tp composition (mode "cp", r5)
#
# Under tensor parallelism the stacked payload arrives sharded: column-
# parallel weights (wq/wk/wv/w_gate/w_up) on N — P(None, None, tp) — and
# row-parallel ones (wo/w_down) on the packed contraction axis —
# P(None, tp, None). A plain pallas_call is an opaque unit, so GSPMD
# would all-gather the weight (the exact 1,584 tok/s loss the kernel
# exists to avoid). The fix is a ``custom_partitioning`` wrapper with a
# Shardy rule: x is passed PRE-SPLIT as (xlo, xhi) so both halves' K/2
# axis and the payload's packed axis share one factor "j" — the
# split-half layout then shards COHERENTLY (device d's packed rows hold
# the lo nibbles of source rows [d·K2/t, (d+1)·K2/t) and the hi nibbles
# of [K/2 + d·K2/t, ...), which is exactly device d's shard of xlo and
# xhi) — no repacking, no gather:
#
#   column (n sharded): local kernel on [L, K/2, N/t], out n-sharded;
#   row (j sharded):    local kernel on [L, K2/t, N] + psum over tp
#                       ("j" is declared a reduction factor).
#
# Local-shape tiling is re-checked inside the partition callback: a
# shard whose K2/N no longer divides the block candidates falls back to
# the XLA dequant einsum LOCALLY (correct, slower) rather than failing
# to lower.
#
# STATUS ON REAL CHIPS (PR 21, v5e 2x2 host, jax 0.9.0 / libtpu 0.0.34):
# any program containing this op fails to compile — "INVALID_ARGUMENT:
# Custom emitter for CustomSPMDPartitioning not found". jax creates the
# TPU client through ``make_tpu_client``, which never passes libtpu's PJRT
# C API to the plug-in callbacks that register the Python partitioner, so
# the op reaches the TPU compiler unpartitioned. The virtual CPU mesh
# partitions in-process and passes. No catch here: a tp>1 int4 deploy
# fails at its first compile until this is rebuilt on shard_map
# (ROADMAP S9).


def _cp_local_fallback(xlo, xhi, packed, scale):
    """Local-shard XLA path: nibble-unpack fused into two dots."""
    p = packed.astype(jnp.int32)
    lo = jax.lax.shift_right_arithmetic(jax.lax.shift_left(p, 28), 28)
    hi = jax.lax.shift_right_arithmetic(p, 4)
    dt = xlo.dtype
    y = (jnp.einsum("mk,kn->mn", xlo, lo.astype(dt))
         + jnp.einsum("mk,kn->mn", xhi, hi.astype(dt)))
    return (y.astype(jnp.float32) * scale.reshape(1, -1)).astype(xlo.dtype)


@functools.lru_cache(maxsize=2)
def _cp_stacked(interpret: bool):
    from jax.experimental.custom_partitioning import (
        SdyShardingRule,
        custom_partitioning,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    def _impl(xlo, xhi, packed, scale, layer):
        xx = jnp.concatenate([xlo, xhi], axis=-1)
        return _int4_matmul_stacked(xx, packed, scale, layer[0],
                                    interpret=interpret)

    cp = custom_partitioning(_impl)

    def _partition(mesh, arg_infos, result_infos):
        xs = arg_infos[0].sharding.spec if arg_infos[0].sharding else P()
        ps = (arg_infos[2].sharding.spec if arg_infos[2].sharding
              else P(None, None, None))
        m_ax = xs[0] if len(xs) > 0 else None
        j_ax = ps[1] if len(ps) > 1 else None
        n_ax = ps[2] if len(ps) > 2 else None
        arg_shardings = (NamedSharding(mesh, P(m_ax, j_ax)),
                         NamedSharding(mesh, P(m_ax, j_ax)),
                         NamedSharding(mesh, P(None, j_ax, n_ax)),
                         NamedSharding(mesh, P(None, None, n_ax)),
                         NamedSharding(mesh, P()))
        out_sharding = NamedSharding(mesh, P(m_ax, n_ax))

        def _axis_size(ax):
            if ax is None:
                return 1
            names = (ax,) if isinstance(ax, str) else ax
            size = 1
            for nm in names:
                size *= mesh.shape[nm]
            return size

        def lower_fn(xlo, xhi, packed, scale, layer):
            _nl, k2l, nloc = packed.shape
            if _block_of(k2l, _K_BLOCKS) and _block_of(nloc, _N_BLOCKS):
                y = _impl(xlo, xhi, packed, scale, layer)
            else:                       # untileable local shard
                sl = jax.lax.dynamic_index_in_dim(scale, layer[0], 0,
                                                  keepdims=False)
                y = _cp_local_fallback(
                    xlo, xhi,
                    jax.lax.dynamic_index_in_dim(packed, layer[0], 0,
                                                 keepdims=False), sl)
            if _axis_size(j_ax) > 1:
                y = jax.lax.psum(y, j_ax)
            return y

        return mesh, lower_fn, out_sharding, arg_shardings

    rule = SdyShardingRule(
        operand_mappings=(("m", "j"), ("m", "j"), ("l", "j", "n"),
                          ("l", "z", "n"), ("o",)),
        result_mappings=(("m", "n"),),
        reduction_factors=("j",),
    )
    cp.def_partition(partition=_partition, sharding_rule=rule)
    return cp
