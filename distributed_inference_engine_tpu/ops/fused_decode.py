"""Fused decode-megastep kernels: RMSNorm+matmul and matmul+residual.

ISSUE 5 (r10): the bs128 decode step reads each *weight* byte once (int4
keeps dequant inside the Mosaic matmul — ``ops/int4_matmul.py``), but the
XLA lowering of the surrounding glue still round-trips the *activations*
through HBM between the norm, the projection, and the residual add: at
8B/bs128 the step timeline shows the norm→matmul and matmul→add seams as
separate fusions. These two kernels close the seams for PLAIN (bf16/f32)
weights:

  ``norm_matmul(x, gain, w)``      = rms_norm(x, gain) @ w
  ``matmul_residual(x, w, res)``   = res + x @ w

Numerics contract — BIT-PARITY with the unfused path. The kernel bodies
execute the exact op sequence of ``ops.norms.rms_norm`` (fp32 mean of
squares, ``x * (1/sqrt(ms+eps))``, scale multiply in fp32, cast back to
the activation dtype) followed by a dot accumulated in fp32 and rounded
once to the activation dtype (``_dot``) — what ``matmul_any``'s
plain-ndarray branch (``jnp.einsum``) computes, spelled the only way
Mosaic accepts: a bf16-typed accumulator is rejected on the chip
("'tpu.matmul' op Expected matmul acc to be 32-bit", libtpu 0.0.34). The
fused and unfused engines produce the same tokens greedily and under fixed
sampling keys (tests/test_fused_decode.py).

Grid: 1-D over N output blocks. The [B, D] activation block uses a
constant index map, so it is DMA'd into VMEM once and stays resident
across the whole grid; each weight block [D, bn] streams exactly once.
The fp32 RMS scale is recomputed per grid step — a [B, D] VPU reduction,
which is noise next to the [D, bn] weight DMA it overlaps with — rather
than carried in scratch, keeping the kernel single-pass and stateless.

QUANTIZED weights (the int4 flagship) do not route here: their dequant is
already fused into the Mosaic matmul prologue and per-output-channel
scales live on N, so an RMS gain on the contraction axis cannot fold into
them — those layers run the unfused ``_norm`` + ``matmul_any`` chain,
whose activation traffic is <0.5% of the packed weight stream at bs128.
RoPE likewise stays outside (it permutes per-head lanes *after* the
split of the fused QKV projection; folding it in would burn a transpose
inside the kernel to save ~0.1% of the byte stream).

Like ``ops/int4_matmul.py``, ``interpret`` defaults to on for the CPU
backend only (the parity tests); on any other backend the kernels compile
or the program fails.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# sublane minimum for the second-to-last dim: f32 tiles at (8, 128),
# bf16 at (16, 128) — pad batch to 16 and both dtypes are served
_SUBLANE = 16
_LANE = 128
_BN_CANDIDATES = (512, 256, 128)
# VMEM budget for x + w + out blocks (v5e has 16 MiB/core; leave room
# for the double-buffered weight stream)
_VMEM_BUDGET = 8 * 1024 * 1024


def _interpret_default(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def _pick_bn(n: int) -> Optional[int]:
    for bn in _BN_CANDIDATES:
        if n % bn == 0:
            return bn
    return None


def _pad_batch(x: jnp.ndarray) -> Tuple[jnp.ndarray, int]:
    b = x.shape[0]
    bp = -(-b // _SUBLANE) * _SUBLANE
    if bp != b:
        x = jnp.pad(x, ((0, bp - b), (0, 0)))
    return x, b


def _plain_2d(w) -> bool:
    """True for an ordinary (non-quantized) rank-2 float array/tracer.
    QuantizedTensor / IndexedQuant carry a packed payload under ``.q`` /
    ``.qt`` and must keep riding ``matmul_any``'s kernel dispatch."""
    if hasattr(w, "q") or hasattr(w, "qt"):
        return False
    return getattr(w, "ndim", 0) == 2 and \
        jnp.issubdtype(getattr(w, "dtype", jnp.int32), jnp.floating)


def _shapes_fit(b: int, d: int, n: int, itemsize: int) -> bool:
    if d % _LANE or n % _LANE:
        return False
    bn = _pick_bn(n)
    if bn is None:
        return False
    bp = -(-b // _SUBLANE) * _SUBLANE
    vmem = (bp * d + d * bn + bp * bn) * itemsize
    return vmem <= _VMEM_BUDGET


def norm_matmul_wants(x, w) -> bool:
    """Shape/dtype half of kernel eligibility: plain 2-D float weight,
    matching activation dtype, TPU-tileable dims, VMEM-resident blocks.
    Ineligible shapes fall back to the unfused chain — never an error."""
    if not _plain_2d(w) or getattr(x, "ndim", 0) != 2:
        return False
    if x.dtype != w.dtype or x.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if x.shape[1] != w.shape[0]:
        return False
    return _shapes_fit(x.shape[0], w.shape[0], w.shape[1], x.dtype.itemsize)


def matmul_residual_wants(x, w) -> bool:
    return norm_matmul_wants(x, w)


def _dot(a, b):
    """``a @ b`` with the fp32 accumulator Mosaic requires, rounded once
    to the operand dtype."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def _norm_matmul_kernel(x_ref, g_ref, w_ref, o_ref, *, eps, plus_one):
    # exact rms_norm op sequence (ops/norms.py) — do not "simplify" to
    # rsqrt or fold the gain into the scale: bit-parity is the contract
    xf = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * (1.0 / jnp.sqrt(ms + eps))
    g = g_ref[...].astype(jnp.float32)
    if plus_one:
        g = g + 1.0
    h = (y * g).astype(x_ref.dtype)
    o_ref[...] = _dot(h, w_ref[...])


def norm_matmul(
    x: jnp.ndarray,          # [B, D] activations
    gain: jnp.ndarray,       # [D] RMSNorm scale
    w: jnp.ndarray,          # [D, N] plain weight
    *,
    eps: float = 1e-6,
    plus_one: bool = False,  # Gemma stores (w - 1); add it back in fp32
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``rms_norm(x, gain, eps) @ w`` in one kernel — [B, N].

    Caller must have checked ``norm_matmul_wants(x, w)``."""
    interpret = _interpret_default(interpret)
    d, n = w.shape
    bn = _pick_bn(n)
    x, b = _pad_batch(x)
    bp = x.shape[0]
    out = pl.pallas_call(
        functools.partial(_norm_matmul_kernel, eps=eps, plus_one=plus_one),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bp, d), lambda j: (0, 0)),   # VMEM-resident
            pl.BlockSpec((1, d), lambda j: (0, 0)),
            pl.BlockSpec((d, bn), lambda j: (0, j)),   # streams once
        ],
        out_specs=pl.BlockSpec((bp, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((bp, n), x.dtype),
        interpret=interpret,
    )(x, gain.reshape(1, d), w)
    return out[:b]


def _matmul_residual_kernel(x_ref, w_ref, r_ref, o_ref):
    o_ref[...] = r_ref[...] + _dot(x_ref[...], w_ref[...])


def matmul_residual(
    x: jnp.ndarray,          # [B, D] activations
    w: jnp.ndarray,          # [D, N] plain weight
    res: jnp.ndarray,        # [B, N] residual stream
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``res + x @ w`` in one kernel — [B, N], res read once alongside
    the weight stream instead of in a separate add fusion.

    Caller must have checked ``matmul_residual_wants(x, w)``."""
    interpret = _interpret_default(interpret)
    d, n = w.shape
    bn = _pick_bn(n)
    x, b = _pad_batch(x)
    res_p, _ = _pad_batch(res)
    bp = x.shape[0]
    out = pl.pallas_call(
        _matmul_residual_kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bp, d), lambda j: (0, 0)),   # VMEM-resident
            pl.BlockSpec((d, bn), lambda j: (0, j)),   # streams once
            pl.BlockSpec((bp, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bp, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((bp, n), res.dtype),
        interpret=interpret,
    )(x, w, res_p)
    return out[:b]
