"""Prefill attention of the families whose cache rows are K|V
(``models/mellum.py``'s sliding and full layers, ``models/olmo_hybrid.py``'s
full layers): grouped-query, causal, or a band ``i - window < j <= i``.

``kv_prefill_attention`` is the one entry. On a TPU, at a ``T`` of whole
blocks and heads of whole 128-lane tiles, ONE blocked flash-attention kernel
(``_flash_kernel``, the pattern of ``ops/mla.py``'s): online softmax over
key blocks with scores, running maximum / sum and accumulator in float32 in
VMEM, so no ``[.., queries, keys]`` tensor reaches HBM. Elsewhere (the CPU,
the tiny specs) ``ops/attention.py`` ``band_attention_blocked``, the einsum
/ softmax body. ``prefill_impl`` chooses from the backend and the shapes;
nothing else does.

Everything is laid out as the projections leave it: q ``[B, T, H * Dh]``
(the ``G = H / Hkv`` query heads of one K/V head are adjacent lane blocks),
the cache rows ``[B, T, 2 * Hkv * Dh]`` = rotated ``k | v`` handed over as
they are (the K block of K/V head ``j`` is lane block ``j``, its V block
lane block ``Hkv + j``: two block specs over one array), the result what
``wo`` multiplies. The grid is (row, head group, pair): a group is up to
``HEADS_PER_STEP`` adjacent query heads and the K/V heads they read (one K/V
head's ``G`` query heads or a part of them; where ``G`` is small, several
K/V heads with theirs), so K and V are fetched once a group, not once a
query head. The pairs are the
(query block, key block) couples a query block can SEE, a query block's
together, keys ascending: at or under the diagonal and, with a ``window``,
from the block that holds row ``q0 - window + 1`` on (``band_pairs``);
``seq_lens`` and the two tables are scalar-prefetched. A pair past the
row's length computes nothing and its index maps name the block already
held, so nothing moves either. Only edge blocks (the diagonal, the band's
lower edge, the row's length) are masked.

With a band a query block's first pair is not key block 0, so the running
sums start at its first PAIR. That block is masked whole for the query
block's later rows, and ``NEG_INF`` is finite: ``exp(s - m)`` of such a row
is 1, not 0. The next live key wipes that (``exp(NEG_INF - m)`` is 0), but a
row past the prompt whose window holds no key never meets one; a masked
``p`` is zeroed on edge blocks, so ``l`` is a true denominator throughout
and such a row is written as zeros.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, band_attention_blocked
from .mla import _VMEM_LIMIT, LANES, _dot_nt, _lanes, _last_key_block

# the kernel's grid step: Q_BLOCK queries x K_BLOCK keys of HEADS_PER_STEP
# query heads (fewer where the head counts have no such divisor). On one v5e
# chip (docs/sweeps/pr40-kv-prefill-flash.txt; PERF.md section 6, PR 40), 32 : 4
# heads of 128, one row, ms a call at 4,096 / 8,192 / 16,384 positions, q and
# the result as the kernel takes them: full 1.14 / 3.82 / 14.5 against the XLA
# body's 5.17 / 20.0 / 79.8, the band of 1,024 0.77 / 1.54 / 3.35 against 1.07
# / 2.27 / 4.30; 256 x 512 reads 1-4 % slower (2 % faster for a short row in
# a long bucket), 4 heads a step 5-6 %, 256 x 256 and 1,024 x 512 up to 25 %,
# 128 queries a block 50 %. Called with q and the result as [B, T, H, Dh] it
# pays a relayout of both around the call: + 5 % alone in a program.
Q_BLOCK = 512
K_BLOCK = 512
HEADS_PER_STEP = 8


def _whole_blocks(t: int) -> bool:
    return t % Q_BLOCK == 0 and t % K_BLOCK == 0


def prefill_impl(t: int, head_dim: int) -> str:
    """Which body runs a prefill of ``t`` positions: "flash", the kernel, on
    a TPU at whole blocks and heads of whole lane tiles; "xla" elsewhere.
    ("flash_interpret": the kernel through the interpreter, for the CPU
    tests.)"""
    on_tpu = jax.default_backend() == "tpu"
    return ("flash" if on_tpu and _whole_blocks(t) and head_dim % LANES == 0
            else "xla")


def _first_key_block(qi: int, bq: int, bk: int, window: int) -> int:
    return max(qi * bq - window + 1, 0) // bk if window else 0


def band_pairs(t: int, bq: int, bk: int, window: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """The (query block, key block) pairs of a ``t x t`` square inside the
    band, a query block's together, keys ascending."""
    pairs = [(qi, ki) for qi in range(t // bq)
             for ki in range(_first_key_block(qi, bq, bk, window),
                             _last_key_block(qi, bq, bk) + 1)]
    return tuple(np.asarray(c, np.int32) for c in zip(*pairs))


def prefill_key_blocks(length: int, t: int, window: int = 0
                       ) -> Tuple[int, int]:
    """(key blocks the kernel visits for a prompt of ``length`` in a bucket
    of ``t`` positions, blocks of the whole ``t x t`` square), one layer's:
    inside the band (``window`` 0: at or under the diagonal) and below
    ``length``. A ``t`` of no whole blocks is one block."""
    bq, bk = (Q_BLOCK, K_BLOCK) if _whole_blocks(t) else (t, t)
    live_k = -(-length // bk)
    visited = sum(
        max(min(_last_key_block(qi, bq, bk) + 1, live_k)
            - _first_key_block(qi, bq, bk, window), 0)
        for qi in range(-(-length // bq)))
    return visited, (t // bq) * (t // bk)


def kv_prefill_attention(q, rows, seq_lens, n_kv_heads: int,
                         window: int = 0, impl: str = "") -> jnp.ndarray:
    """q [B, T, H, Dh]; rows [B, T, 2 * Hkv * Dh], a token's ``k | v``.
    Row i sees ``i - window < j <= i`` (``window`` 0: every ``j <= i``),
    keys past ``seq_lens`` masked; scores at ``Dh^-1/2``. Returns
    [B, T, H, Dh]; rows past ``seq_lens`` are not specified (nothing reads
    them). ``impl``: see ``prefill_impl``, which chooses when it is empty."""
    b, t, h, dh = q.shape
    lanes = n_kv_heads * dh
    impl = impl or prefill_impl(t, dh)
    if impl == "xla":
        k, v = (rows[..., at:at + lanes].reshape(b, t, n_kv_heads, dh)
                for at in (0, lanes))
        return band_attention_blocked(q, k, v, seq_lens, window=window)
    with jax.named_scope("flash_prefill"):
        out = _flash_prefill(
            q.reshape(b, t, h * dh), rows, seq_lens.astype(jnp.int32),
            n_kv_heads=n_kv_heads, window=window, bq=Q_BLOCK, bk=K_BLOCK,
            heads_per_step=HEADS_PER_STEP,
            interpret=impl == "flash_interpret")
    return out.reshape(b, t, h, dh)


def _flash_kernel(lens_ref, qi_ref, ki_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, heads: int,
                  group: int, dh: int, bq: int, bk: int, window: int):
    n = lens_ref[pl.program_id(0)]
    pair = pl.program_id(2)
    q0, k0 = qi_ref[pair] * bq, ki_ref[pair] * bk
    first = jnp.maximum(q0 - window + 1, 0) // bk * bk if window else 0

    @pl.when(k0 == first)                      # the query block's first pair
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def visit(masked: bool):
        if masked:
            rows = q0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = k0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = (cols <= rows) & (cols < n)
            if window:
                keep &= rows - cols < window
        for j in range(heads):
            at = j // group * dh               # the head's K/V head's lanes
            s = _dot_nt(q_ref[0, :, j * dh:(j + 1) * dh],
                        k_ref[0, :, at:at + dh]) * scale
            if masked:
                s = jnp.where(keep, s, NEG_INF)
            m_prev = m_ref[j]                                  # [bq, LANES]
            m_next = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
            p = jnp.exp(s - _lanes(m_next, bk))
            if masked:
                # a row with no key in this block and none before it: its
                # m is still NEG_INF and exp(s - m) is 1
                p = jnp.where(keep, p, 0.0)
            alpha = jnp.exp(m_prev - m_next)
            l_ref[j] = alpha * l_ref[j] + p.sum(axis=-1)[:, None]
            m_ref[j] = m_next
            v = v_ref[0, :, at:at + dh]
            acc_ref[j] = _lanes(alpha, dh) * acc_ref[j] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    live = (q0 < n) & (k0 < n)
    # a masked key for some row: past that row (the diagonal), past n, or
    # behind that row's window
    edge = (k0 + bk - 1 > q0) | (k0 + bk > n)
    if window:
        edge |= q0 + bq - 1 - k0 >= window
    pl.when(live & edge)(lambda: visit(True))
    pl.when(live & jnp.logical_not(edge))(lambda: visit(False))

    @pl.when(k0 + bk >= q0 + bq)               # the query block's last pair
    def _():
        for j in range(heads):
            l = l_ref[j]                       # 0: a row that saw no key
            o = acc_ref[j] / _lanes(jnp.where(l == 0.0, 1.0, l), dh)
            o_ref[0, :, j * dh:(j + 1) * dh] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_kv_heads", "window", "bq", "bk", "heads_per_step", "interpret"))
def _flash_prefill(q, rows, seq_lens, *, n_kv_heads: int, window: int,
                   bq: int, bk: int, heads_per_step: int, interpret: bool):
    """q [B, T, H * Dh], rows [B, T, 2 * Hkv * Dh], seq_lens int32 [B] ->
    [B, T, H * Dh]. One ``jax.jit``: a program that calls it for several
    layers traces and lowers the kernel once."""
    b, t, width = q.shape
    dh = rows.shape[-1] // (2 * n_kv_heads)
    h = width // dh
    g = h // n_kv_heads
    # query heads a step: whole K/V heads' groups, or a part of one group
    hb = max(d for d in range(1, heads_per_step + 1)
             if h % d == 0 and (d % g == 0 or g % d == 0))
    kb = max(hb // g, 1)                       # K/V heads a step
    qi, ki = band_pairs(t, bq, bk, window)

    def q_at(row, grp, pair, lens, qi, ki):
        last = jnp.maximum(lens[row] - 1, 0) // bq
        return row, jnp.minimum(qi[pair], last), grp

    def k_at(row, grp, pair, lens, qi, ki):
        last = jnp.maximum(lens[row] - 1, 0) // bk
        live = qi[pair] * bq < lens[row]
        return (row, jnp.where(live, jnp.minimum(ki[pair], last), last),
                grp * hb // g // kb)

    def v_at(*a):
        row, blk, lane_blk = k_at(*a)
        return row, blk, n_kv_heads // kb + lane_blk

    kernel = functools.partial(
        _flash_kernel, scale=dh ** -0.5, heads=hb, group=g, dh=dh, bq=bq,
        bk=bk, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h // hb, len(qi)),
            in_specs=[
                pl.BlockSpec((1, bq, hb * dh), q_at),
                pl.BlockSpec((1, bk, kb * dh), k_at),
                pl.BlockSpec((1, bk, kb * dh), v_at),
            ],
            out_specs=pl.BlockSpec(
                (1, bq, hb * dh),
                lambda row, grp, pair, lens, qi, ki: (row, qi[pair], grp)),
            scratch_shapes=[pltpu.VMEM((hb, bq, LANES), jnp.float32),
                            pltpu.VMEM((hb, bq, LANES), jnp.float32),
                            pltpu.VMEM((hb, bq, dh), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="kv_prefill_flash",
    )(seq_lens, qi, ki, q, rows, rows)
