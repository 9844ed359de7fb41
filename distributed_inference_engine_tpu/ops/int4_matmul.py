"""Mosaic (Pallas-TPU) matmul with in-register int4 unpack.

XLA's einsum on packed-int4 weights materializes the unpacked int8 operand
in HBM, so a decode step streams the 2-byte traffic AND the packed read.
This kernel keeps the weight packed in HBM and VMEM and unpacks nibbles in
registers on the way into the MXU feed, so HBM sees only the
0.5-byte/weight stream. (The reference has no analogue: its "model" is an
asyncio sleep, ``src/mock_models/fake_model.py:47``.)

Layout contract (``ops.quant.quantize_weight``): a ``[K, N]`` weight packs
SPLIT-HALF along the contraction axis into ``[K/2, N]`` int8 — source row
``k < K/2`` in the low nibble of byte row ``k``, row ``K/2 + k`` in the
high nibble. The matmul then decomposes into two contiguous-slice dots,

    y = x[:, :K/2] @ lo(P) + x[:, K/2:] @ hi(P),    P = packed bytes

with no stride-2 gather anywhere (an interleaved layout would need one on
either the activations or the unpacked weight — both Mosaic-hostile).

Inside a layer scan the kernel must NOT take the scanned per-layer slice:
a pallas_call is an opaque custom call, so XLA materializes the slice as
a real HBM copy first. The kernel takes the whole ``[L, K/2, N]`` payload
plus the layer index as a scalar-prefetch argument and picks the layer's
bytes straight from the stacked array in HBM
(``_int4_matmul_stacked``; a 2-D payload is its L=1 case). Engine init
fuses qkv and gate+up payloads (``ops.quant.fuse_block_weights``), so a
dense 7B layer makes four calls.

One pallas_call, two schedules, chosen from the ROWS of the activation it
is handed (``_DECODE_ROWS``) and the payload's ``(K/2, N)``
(``blocks_for``) — never from a model's name or an option:

  decode rows (<= 16)  ``_kernel_stream``: HBM-bound, every weight byte
      once. The payload stays in HBM; the kernel's own DMAs bring
      ``[bk, bn]`` chunks two ahead of the unpack and across grid steps.
  prefill rows         ``_kernel_stacked``: MXU-bound. Grid
      ``(M/bm, N/bn, K2/bk)``, k innermost, f32 accumulator in VMEM,
      weight blocks by the grid's double-buffered pipeline.

Measured on one v5e chip at the mistral-7b shapes the benchmark's cells
serve (8 rows; PERF.md section 6, PR 28): 5.05 ms for the 129 calls of a
forward pass against 5.93 ms on the one-schedule kernel before it, 87 %
of the pass's 3.6 GB at 819 GB/s; per shape in ``_TUNED_BLOCKS``. With the
unpack cut out the same DMAs take 4.94 ms: what is left is the stream.

Tensor-parallel composition (mode "cp"): the kernel rides a
``custom_partitioning`` op whose Shardy rule passes x pre-split as
(xlo, xhi) so both halves' K/2 axis and the payload's packed axis share
one reduction factor — the split-half layout then shards COHERENTLY for
row-parallel weights (each device's packed rows hold the lo nibbles of
exactly its xlo shard's columns and the hi nibbles of its xhi shard's) and
trivially for column-parallel, with no repacking and no gather. Engines
stamp "cp" onto their OWN int4 tensors when params land sharded
(``ops.quant.resolve_kernel_modes`` — per-engine scope; the module-level
mode below is only the process default / env override).
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
    return _tileable(*w.q.shape)


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


# An activation of at most this many rows is a decode step's (one 16-row
# bf16 tile): the payload then arrives through the kernel's own DMAs
# (``_kernel_stream``). Anything taller is a prefill's and takes the
# pipelined grid (``_kernel_stacked``). The bucket is read off the
# activation the kernel is handed, nothing else.
_DECODE_ROWS = 16

# Blocks for a payload shape the table below does not hold, per bucket:
# the first candidates that divide it. Decode chunks are short and wide:
# (512, 2048) lies within 4 % of the best chunk on all five swept shapes,
# (1024, 2048) 13 % behind on the K/2 = 2048 ones. int8-typed shifts don't
# compile on this Mosaic — keep the int32 widen.
_K_BLOCKS = {True: (512, 256, 128), False: (1024, 512, 256, 128)}
_N_BLOCKS = (2048, 1024, 512, 256, 128)

# How many chunks the decode kernel's DMAs run ahead of the unpack: one
# chunk ahead leaves the DMA queue empty at every wait (gate+up 653 GB/s
# against 680 at two, v5e); three measures the same as two.
_STREAM_AHEAD = 2

# (K/2, N) -> ((bk, bn) at decode rows, (bk, bn) at prefill rows); None =
# not swept in that bucket, resolved like a shape the table does not hold.
# Measured on one v5e chip (examples/int4_kernel_tune.py, PERF.md section 6,
# PR 28); the comments give us a call and GB/s of the packed stream at 8
# rows:
#   decode: chunks of the streaming kernel; the best and the worst chunk of
#     a shape lie 20-40 % apart (wo 13.8 us at (256, 4096), 18.9 at
#     (1024, 4096)).
#   prefill (256 and 768 rows, bm 128): MXU-bound (gate+up 188 TFLOP/s at
#     768 rows, 95 % of the bf16 peak), so the blocks move it little:
#     (2048, 1024) takes 6 % off qkv and 5 % off the head at 768 rows,
#     1.5 % off wo; gate+up and w_down keep the r5 sweep's.
_TUNED_BLOCKS = {
    (2048, 6144): ((256, 3072), (2048, 1024)),      # qkv     19.3 us  651
    (2048, 4096): ((256, 4096), (2048, 1024)),      # wo      13.8 us  607
    (2048, 28672): ((256, 4096), (2048, 1024)),     # gate+up 80.5 us  729
    (7168, 4096): ((256, 4096), (512, 4096)),       # w_down  41.8 us  703
    (2048, 32768): ((256, 4096), (2048, 1024)),     # mistral lm_head
                                                    #         91.7 us  732
    (2048, 129024): (None, (2048, 2048)),           # llama lm_head, padded
}


def blocks_for(rows: int, k2: int, n: int
               ) -> Tuple[Optional[int], Optional[int]]:
    """``(bk, bn)`` the kernel streams a ``[K/2, N]`` payload in when the
    activation has ``rows`` rows: the table's entry for the row bucket, or
    the first default candidates that divide the shape (None where none
    does)."""
    decode = rows <= _DECODE_ROWS
    entry = _TUNED_BLOCKS.get((k2, n), (None, None))[0 if decode else 1]
    return entry or (_block_of(k2, _K_BLOCKS[decode]),
                     _block_of(n, _N_BLOCKS))


def block_report(k2: int, n: int) -> dict:
    """What ``blocks_for`` answers for one payload shape in both row
    buckets, and whether the table held both (``ops.quant
    .int4_kernel_blocks``, the worker's ``int4_blocks``)."""
    return {"decode": list(blocks_for(_DECODE_ROWS, k2, n)),
            "prefill": list(blocks_for(_DECODE_ROWS + 1, k2, n)),
            "tuned": None not in _TUNED_BLOCKS.get((k2, n), (None, None))}


def _tileable(k2: int, n: int) -> bool:
    return None not in blocks_for(_DECODE_ROWS, k2, n) + \
        blocks_for(_DECODE_ROWS + 1, k2, n)


def _int4_matmul_2d(x, packed, scale, *, interpret: bool = False, **blocks):
    """``[M, K] @ unpack([K/2, N]) * scale -> [M, N]`` (dtype of x) —
    the degenerate L=1 case of the stacked kernel (one code path, one
    set of tuning constants)."""
    k2, n = packed.shape
    return _int4_matmul_stacked(x, packed[None], scale.reshape(1, 1, n),
                                jnp.int32(0), interpret=interpret, **blocks)


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
    return _tileable(*w.q.shape[1:])


_LO_SHIFT = 28      # a low nibble shifted to an int32's top is lo * 2**28


def _block_product(xlo, xhi, p):
    """f32 ``xlo @ lo(p) + xhi @ hi(p)`` for one block of packed bytes.

    The high nibble is one arithmetic shift of the widened byte. The low
    nibble is NOT shifted back down: ``p << 28`` is ``lo * 2**28`` exactly,
    exact again as bf16 (four significant bits), and the power of two
    comes off the f32 product afterwards, which rounds nothing: the same
    bits as shifting down first (checked on the chip and interpreted), at
    one VPU op a byte less. At decode rows the unpack bounds this kernel:
    gate+up 86.2 -> 80.5 us a call against 79.3 for its DMAs alone."""
    dt = xlo.dtype
    p = p.astype(jnp.int32)
    lo = jax.lax.shift_left(p, _LO_SHIFT)
    hi = jax.lax.shift_right_arithmetic(p, 4)
    return (jnp.dot(xlo, lo.astype(dt), preferred_element_type=jnp.float32)
            * 2.0 ** -_LO_SHIFT
            + jnp.dot(xhi, hi.astype(dt), preferred_element_type=jnp.float32))


def _kernel_stacked(l_ref, xlo_ref, xhi_ref, p_ref, s_ref, o_ref, acc_ref):
    """Prefill rows: grid ``(M/bm, N/bn, K2/bk)``, the weight block brought
    by the grid's own double-buffered pipeline."""
    del l_ref                       # consumed by the index_maps
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _block_product(xlo_ref[...], xhi_ref[...], p_ref[0])

    @pl.when(k == pl.num_programs(2) - 1)
    def _emit():
        o_ref[...] = (acc_ref[...] * s_ref[0]).astype(o_ref.dtype)


def _kernel_stream(l_ref, x_ref, p_hbm, s_ref, o_ref, buf, sem, *, bk, bn):
    """Decode rows: one grid step per ``bn`` output columns, its K/2 in
    ``bk``-row chunks (a loop, so the body is one chunk's: unrolled it ran
    0.4 % faster and took ten times as long to lower and compile, which a
    warm start pays too). The payload stays in HBM and arrives by
    this kernel's own DMAs, ``_STREAM_AHEAD`` chunks ahead of the unpack
    and across grid steps, so the DMA queue is never empty between the
    first chunk and the last (the grid's pipeline fetches one block ahead
    and waits for it at every step: 620 against 730 GB/s on gate+up)."""
    j = pl.program_id(0)
    _, k2, n = p_hbm.shape
    nj, nk = n // bn, k2 // bk
    slots = _STREAM_AHEAD + 1

    def chunk(jj, kk, slot):
        return pltpu.make_async_copy(
            p_hbm.at[l_ref[0], pl.ds(kk * bk, bk), pl.ds(jj * bn, bn)],
            buf.at[slot], sem.at[slot])

    @pl.when(j == 0)
    def _prime():
        for c in range(min(_STREAM_AHEAD, nj * nk)):
            chunk(c // nk, c % nk, c % slots).start()

    def step(kk, acc):
        c = j * nk + kk             # this chunk's place in the whole stream
        ahead = kk + _STREAM_AHEAD
        ja, ka = j + ahead // nk, ahead % nk

        @pl.when(ja < nj)
        def _fetch():               # into the slot chunk c - 1 just left
            chunk(ja, ka, (c + _STREAM_AHEAD) % slots).start()

        slot = c % slots
        chunk(j, kk, slot).wait()
        at = pl.multiple_of(kk * bk, bk)
        return acc + _block_product(x_ref[:, pl.ds(at, bk)],
                                    x_ref[:, pl.ds(k2 + at, bk)], buf[slot])

    acc = jax.lax.fori_loop(0, nk, step,
                            jnp.zeros(o_ref.shape, jnp.float32))
    o_ref[...] = (acc * s_ref[0]).astype(o_ref.dtype)


# the int32 nibble-widening temporaries ([bk, bn] lo + hi, then bf16) top
# 16 MB at the prefill tile (bm=128, bn=2048) — past the default
# scoped-vmem limit but well inside v5e's 128 MB physical VMEM (measured:
# compiles + runs at 64 MB)
_VMEM_LIMIT = 64 * 1024 * 1024


@functools.partial(jax.jit, static_argnames=("interpret", "bk", "bn"))
def _int4_matmul_stacked(x, packed, scale, layer, *, interpret: bool = False,
                         bk: Optional[int] = None, bn: Optional[int] = None):
    """``[M, K] @ unpack(packed[layer]) * scale[layer] -> [M, N]``;
    ``packed [L, K/2, N]`` stays whole in HBM — the layer is a
    scalar-prefetch argument, so no slice is materialized.

    ONE pallas_call whatever the rows (the benchmark counts decode steps
    by this op's name); which kernel it holds follows the rows of ``x``:
    ``_kernel_stream`` up to ``_DECODE_ROWS``, ``_kernel_stacked`` above.
    ``bk``/``bn`` override ``blocks_for`` — the tuning surface
    ``examples/int4_kernel_tune.py`` sweeps on hardware."""
    m, kdim = x.shape
    nl, k2, n = packed.shape
    if kdim != 2 * k2:
        raise ValueError(f"x K={kdim} vs packed K/2={k2}")
    tbk, tbn = blocks_for(m, k2, n)
    bk = bk or tbk
    bn = bn or tbn
    if bk is None or bn is None:
        raise ValueError(f"untileable shapes K/2={k2} N={n}")
    if k2 % bk or n % bn:
        # explicit overrides must divide: a flooring grid would silently
        # drop trailing K rows / leave output columns unwritten
        raise ValueError(f"blocks bk={bk} bn={bn} do not divide "
                         f"K/2={k2} N={n}")
    decode = m <= _DECODE_ROWS
    if decode:
        # one row tile: 8 rows where they suffice (a block that is the
        # whole array may be shorter than bf16's 16-row tile), else 16
        bm = 8 if m <= 8 else 16
    else:
        # bm tops out at 128 to keep the f32 accumulator block ≤1 MB
        # alongside the 2 MB double-buffered weight blocks
        bm = _block_of(m, (128, 64, 32)) or min(-(-m // 16) * 16, 128)
    if m % bm:
        x = jnp.pad(x, ((0, -m % bm), (0, 0)))
    mp = x.shape[0]
    layer = jnp.atleast_1d(layer).astype(jnp.int32)
    scale = scale.reshape(nl, 1, n)
    if decode:
        kernel = functools.partial(_kernel_stream, bk=bk, bn=bn)
        operands = (x, packed, scale)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // bn,),
            in_specs=[
                pl.BlockSpec((mp, kdim), lambda j, l: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, 1, bn), lambda j, l: (l[0], 0, j)),
            ],
            out_specs=pl.BlockSpec((mp, bn), lambda j, l: (0, j)),
            scratch_shapes=[
                pltpu.VMEM((_STREAM_AHEAD + 1, bk, bn), jnp.int8),
                pltpu.SemaphoreType.DMA((_STREAM_AHEAD + 1,))],
        )
        semantics = ("arbitrary",)      # the DMAs run across grid steps
    else:
        kernel = _kernel_stacked
        nk = k2 // bk
        # x twice: its low and its high half are blocks of one array, so
        # no slice of it is materialized either
        operands = (x, x, packed, scale)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(mp // bm, n // bn, nk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k, l: (i, k)),
                pl.BlockSpec((bm, bk), lambda i, j, k, l: (i, k + nk)),
                pl.BlockSpec((1, bk, bn), lambda i, j, k, l: (l[0], k, j)),
                pl.BlockSpec((1, 1, bn), lambda i, j, k, l: (l[0], 0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, l: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        )
        semantics = ("parallel", "parallel", "arbitrary")
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * mp * n * kdim,
            bytes_accessed=k2 * n + mp * kdim * x.dtype.itemsize
                           * (1 if decode else n // bn)
                           + mp * n * x.dtype.itemsize,
            transcendentals=0),
        interpret=interpret,
        name="_int4_matmul_stacked",
    )(layer, *operands)
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
            if _tileable(k2l, nloc):
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
