"""The K|V-row families' prefill attention (``ops/flash_prefill.py``): the
blocked flash kernel with a band, through the Pallas interpreter on the CPU,
against the XLA body it stands in for (``ops/attention.py``
``band_attention_blocked``) and against a plain dense masked softmax; the
pair tables against the counter function the engine reports them by; and
which body a shape resolves to.

Tolerances: in float32 the kernel and either reference differ by rounding
order alone (online softmax over key blocks against one softmax a row):
2e-6 on outputs of magnitude <= 1. In bfloat16 (operands and the cast
``p``, float32 scores and sums, as the XLA body) 2e-2 of the largest output.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from distributed_inference_engine_tpu.ops import flash_prefill as fp  # noqa: E402
from distributed_inference_engine_tpu.ops.attention import (  # noqa: E402
    NEG_INF,
    band_attention_blocked,
)

pytestmark = pytest.mark.kernels

F32_TOL = 2e-6
BF16_TOL = 2e-2          # of max|out|


def inputs(lens, t, h, hkv, dh, dtype=jnp.float32, seed=0):
    b = len(lens)
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, t, h, dh), dtype)
    k = jax.random.normal(ks[1], (b, t, hkv, dh), dtype)
    # values a quarter as large: outputs of magnitude <= 1, as the layers'
    v = (0.25 * jax.random.normal(ks[2], (b, t, hkv, dh))).astype(dtype)
    rows = jnp.concatenate([k.reshape(b, t, -1), v.reshape(b, t, -1)], -1)
    return q, k, v, rows, jnp.asarray(lens, jnp.int32)


def dense(q, k, v, lens, window):
    """One masked softmax a row over the whole ``T x T`` square, every
    query head against its K/V head repeated: nothing blocked or skipped."""
    t, g = q.shape[1], q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, g, axis=2) for a in (k, v))
    s = jnp.einsum("bihd,bjhd->bhij", q, k,
                   preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i
    if window:
        mask &= i - j < window
    mask = mask[None] & (j[None] < lens[:, None, None])
    p = jax.nn.softmax(jnp.where(mask[:, None], s, NEG_INF), axis=-1)
    return jnp.einsum("bhij,bjhd->bihd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def worst(got, ref, lens):
    live = (jnp.arange(got.shape[1])[None, :] < lens[:, None])[..., None, None]
    return float(jnp.abs(jnp.where(
        live, got.astype(jnp.float32) - ref.astype(jnp.float32), 0)).max())


def flash(q, rows, lens, hkv, window):
    return jax.jit(lambda *a: fp.kv_prefill_attention(
        *a, hkv, window=window, impl="flash_interpret"))(q, rows, lens)


# (G, Hkv, window, T, seq_lens of two rows, dtype): the served blocks (512
# x 512) at the published head width. Lengths: a whole bucket, one token, one past a
# block's edge, inside a block, short enough that whole query blocks are
# dead, a pad row; G 8 = the sliding-window family's group (its 8 query
# heads share every K and V block), G 1 = the Gated-DeltaNet family's MHA
# (two K/V heads a step), G 4 x 2 K/V heads = a step over two groups, G 7 =
# a group no power of two
KERNEL_CASES = [
    (8, 1, 0, 1024, (1024, 1), "float32"),
    (8, 1, 32, 1024, (513, 300), "float32"),
    (8, 1, 1024, 1024, (1024, 700), "float32"),
    (8, 1, 0, 2048, (1100, 2048), "float32"),
    (8, 1, 32, 2048, (2048, 513), "float32"),
    (8, 1, 1024, 2048, (2048, 1537), "float32"),
    (1, 2, 0, 1024, (1024, 513), "float32"),
    (1, 2, 32, 1024, (1, 1024), "float32"),
    (1, 2, 1024, 1024, (700, 0), "float32"),
    (1, 2, 0, 2048, (2048, 1025), "float32"),
    (1, 2, 32, 2048, (700, 2048), "float32"),
    (1, 2, 1024, 2048, (1025, 2048), "float32"),
    (4, 2, 1024, 2048, (2048, 600), "float32"),
    (7, 1, 0, 1024, (1024, 513), "float32"),
    (8, 1, 1024, 2048, (2048, 1537), "bfloat16"),
    (1, 2, 0, 2048, (1100, 2048), "bfloat16"),
]


@pytest.mark.parametrize("g,hkv,window,t,lens,dtype", KERNEL_CASES)
def test_the_kernel_is_the_xla_body_and_the_dense_softmax(g, hkv, window, t,
                                                          lens, dtype):
    """The interpreted kernel against ``band_attention_blocked`` and against
    the dense masked softmax, rows below ``seq_lens``; query blocks wholly
    past a prompt are zeros, and nothing anywhere is NaN or infinite."""
    q, k, v, rows, lens = inputs(lens, t, g * hkv, hkv, 128,
                                 jnp.dtype(dtype))
    got = flash(q, rows, lens, hkv, window)
    assert got.shape == q.shape and got.dtype == q.dtype
    ref = dense(q, k, v, lens, window)
    tol = F32_TOL if dtype == "float32" \
        else BF16_TOL * float(jnp.abs(ref).max())
    assert worst(got, ref, lens) < tol
    assert worst(got, band_attention_blocked(q, k, v, lens, window=window),
                 lens) < tol
    for row, n in enumerate(lens.tolist()):
        assert not bool(jnp.any(got[row, -(-n // fp.Q_BLOCK) * fp.Q_BLOCK:]))
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())


def test_a_row_whose_first_visited_key_block_is_masked_whole():
    """THE TRAP of a band: window 1,024 and blocks of 512 x 512. Query block
    2 (rows 1,024-1,535) visits key block 0 first, for its row 1,024 alone
    sees key 1; row ``q0 + 511`` = 1,535 sees nothing of it (its window
    starts at key 512), and query block 3 never visits key block 0 at all.
    ``NEG_INF`` is finite, so with the running maximum still ``NEG_INF``
    every ``exp(s - m)`` of such a row is 1. A kernel that starts its sums
    at ``k0 == 0`` (as the causal one may) carries query block 2's into
    block 3; the unseen block's values are far from the rest, so a leak is
    no rounding."""
    window, t, q0 = 1024, 2048, 1024
    assert fp.Q_BLOCK == fp.K_BLOCK == 512
    qi, ki = fp.band_pairs(t, 512, 512, window)
    assert ki[list(qi).index(q0 // 512)] == 0          # visited first
    assert q0 + 511 - window + 1 == 512                # and masked whole
    assert ki[list(qi).index(3)] == 1                  # block 3: never k0 0
    q, k, v, rows, lens = inputs((t,), t, 8, 1, 128, seed=3)
    v = v.at[:, :512].add(5.0)
    rows = jnp.concatenate([k.reshape(1, t, -1), v.reshape(1, t, -1)], -1)
    got = flash(q, rows, lens, 1, window)
    ref = dense(q, k, v, lens, window)
    # outputs of magnitude 5 here: ten times the rounding of the cases above
    assert float(jnp.abs(got[:, q0 + 511] - ref[:, q0 + 511]).max()) \
        < 10 * F32_TOL
    assert worst(got, ref, lens) < 10 * F32_TOL


def test_a_row_that_sees_no_key_is_zeros_not_a_mean_of_unseen_values():
    """``l`` is a true denominator: a prompt of 513 tokens under a window of
    32 leaves rows 545-1,023 of the live query block 1 with no key at all
    (their windows lie past the prompt). Their one visited block is masked
    whole with the maximum still ``NEG_INF``: unless the masked ``p`` is
    zeroed, ``l`` is 512 and the row is written as the mean of values it
    cannot see. No caller reads such a row; it is zeros all the same, as a
    query block wholly past the prompt is."""
    q, k, v, rows, lens = inputs((513,), 1024, 8, 1, 128, seed=4)
    got = flash(q, rows, lens, 1, 32)
    assert worst(got, dense(q, k, v, lens, 32), lens) < F32_TOL
    assert not bool(jnp.any(got[:, 513 + 32 - 1:]))


@pytest.mark.parametrize("window", [0, 40])
@pytest.mark.parametrize("bq,bk", [(16, 32), (32, 16), (64, 16), (16, 16)])
def test_unequal_blocks_visit_the_same_keys(monkeypatch, bq, bk, window):
    """Query and key blocks of different heights, with and without a band
    (40: no multiple of either): the pair tables, the band's lower edge, the
    diagonal, the first and the finishing pair follow the rows, not the
    block index; and the tables are the counter function's ``visited``."""
    monkeypatch.setattr(fp, "Q_BLOCK", bq)
    monkeypatch.setattr(fp, "K_BLOCK", bk)
    q, k, v, rows, lens = inputs((128, 45, 64), 128, 4, 2, 16)
    got = fp.kv_prefill_attention(q, rows, lens, 2, window=window,
                                  impl="flash_interpret")
    assert worst(got, dense(q, k, v, lens, window), lens) < F32_TOL
    qi, ki = fp.band_pairs(128, bq, bk, window)
    assert len(qi) == fp.prefill_key_blocks(128, 128, window)[0]
    assert all(k * bk <= q * bq + bq - 1 for q, k in zip(qi, ki))
    if window:       # a pair's block holds a key inside some row's window
        assert all(k * bk + bk - 1 > q * bq - window for q, k in zip(qi, ki))


@pytest.mark.parametrize("t,window", [(1024, 0), (2048, 1024), (16384, 0),
                                      (16384, 1024), (16896, 1024),
                                      (8192, 32)])
def test_the_pair_tables_are_what_the_counter_counts(t, window):
    """One function beside the kernel says what it visits: a whole bucket's
    ``visited`` is the length of the tables the kernel walks."""
    qi, ki = fp.band_pairs(t, fp.Q_BLOCK, fp.K_BLOCK, window)
    visited, square = fp.prefill_key_blocks(t, t, window)
    assert len(qi) == len(ki) == visited
    assert square == (t // fp.Q_BLOCK) * (t // fp.K_BLOCK)


@pytest.mark.parametrize("length,t,window,visited,square", [
    (4100, 8192, 0, 45, 256),        # 9 query blocks: 1 + 2 + ... + 9
    (4100, 8192, 1024, 24, 256),     # 1 + 2 + 7 x 3: the band's three
    (16384, 16384, 1024, 93, 1024),  # 1 + 2 + 30 x 3
    (16384, 16384, 0, 528, 1024), (512, 1024, 1024, 1, 4),
    (513, 1024, 32, 3, 4),           # the second block sees back 31 rows
    (1025, 2048, 32, 5, 16),         # 1 + 2 + 2: row 1,024 sees row 1,023
    (0, 1024, 1024, 0, 4), (37, 48, 32, 1, 1)])
def test_key_blocks_visited_by_hand(length, t, window, visited, square):
    assert fp.prefill_key_blocks(length, t, window) == (visited, square)


@pytest.mark.parametrize("length,t,window", [
    (2048, 2048, 0), (1100, 2048, 0), (2048, 2048, 1024), (1537, 2048, 1024),
    (700, 2048, 32), (1, 1024, 1024), (3000, 4096, 600)])
def test_key_blocks_visited_are_the_blocks_with_a_visible_pair(length, t,
                                                               window):
    """The count against the mask itself: of the live query blocks' (first
    row below the prompt's length), the key blocks that hold at least one
    (row, key) pair some row of the block can see."""
    import numpy as np

    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (j <= i) & (j < length)
    if window:
        seen &= i - j < window
    bq, bk = fp.Q_BLOCK, fp.K_BLOCK
    blocks = seen[:-(-length // bq) * bq].reshape(-1, bq, t // bk, bk)
    assert fp.prefill_key_blocks(length, t, window)[0] \
        == int(blocks.any(axis=(1, 3)).sum())


@pytest.mark.parametrize("backend,t,dh,impl", [
    ("tpu", 1024, 128, "flash"), ("tpu", 16896, 128, "flash"),
    ("tpu", 512, 256, "flash"), ("tpu", 48, 128, "xla"),
    ("tpu", 1000, 128, "xla"), ("tpu", 1024, 64, "xla"),
    ("cpu", 1024, 128, "xla"), ("gpu", 8192, 128, "xla")])
def test_the_kernel_is_chosen_on_a_tpu_at_whole_blocks_and_lane_tiles(
        monkeypatch, backend, t, dh, impl):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert fp.prefill_impl(t, dh) == impl


@pytest.mark.parametrize("t,dh", [(1024, 128), (48, 128)])
def test_on_the_cpu_the_entry_is_the_xla_body(t, dh):
    """What this process resolves itself (the CPU backend; a ``T`` of no
    whole blocks besides): no kernel in the program, and the XLA body's own
    numbers to the bit."""
    q, k, v, rows, lens = inputs((t, 7), t, 4, 2, dh)
    text = str(jax.make_jaxpr(
        lambda *a: fp.kv_prefill_attention(*a, 2, window=32))(q, rows, lens))
    assert "pallas_call" not in text
    got = fp.kv_prefill_attention(q, rows, lens, 2, window=32)
    assert bool((got == band_attention_blocked(q, k, v, lens,
                                               window=32)).all())
