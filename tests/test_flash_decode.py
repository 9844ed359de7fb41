"""Fused flash-decode attention kernel (ops/flash_decode.py): parity of the
Pallas kernel (interpret mode on CPU) against the XLA reference composition
paged_attention_xla ⊕ window_decode_attention ⊕ merge_attention, across
dtypes (fp32 / bf16 / fp8-KV pools), GQA head groupings, masked tails, empty
rows and stacked-pool layer indexing. Plus model-level forward_decode_window
wiring and the engine's choice of decode body."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_engine_tpu.ops.flash_decode import (
    flash_decode_attention,
    flash_decode_attention_pallas,
    flash_decode_attention_xla,
)

IMPL = "pallas-decode_interpret"

pytestmark = pytest.mark.kernels


def _inputs(key, *, b=4, h=4, hkv=2, dh=64, n=16, p=8, mp=3, w=5,
            layers=1, q_dtype=jnp.float32, kv_dtype=jnp.float32,
            side_dtype=None):
    ks = jax.random.split(key, 8)
    side_dtype = side_dtype or q_dtype
    q = jax.random.normal(ks[0], (b, h, dh), q_dtype)
    kp = jax.random.normal(ks[1], (layers * n, p, hkv * dh),
                           jnp.float32).astype(kv_dtype)
    vp = jax.random.normal(ks[2], (layers * n, p, hkv * dh),
                           jnp.float32).astype(kv_dtype)
    pt = jax.random.randint(ks[3], (b, mp), 0, n, jnp.int32)
    sk = jax.random.normal(ks[4], (b, w, hkv, dh), jnp.float32)
    sv = jax.random.normal(ks[5], (b, w, hkv, dh), jnp.float32)
    return q, kp, vp, pt, sk.astype(side_dtype), sv.astype(side_dtype)


def _ref(q, kp, vp, pt, plen, sk, sv, n_side, hkv):
    return flash_decode_attention_xla(q, kp, vp, pt, plen, sk, sv, n_side,
                                      n_kv_heads=hkv)


# ------------------------------------------------------ kernel-level parity


def test_parity_fp32_masked_tails():
    """Prefix lengths that end mid-page and mid-block, plus an empty-prefix
    row and an empty-side row — the explicit prob-zeroing path."""
    q, kp, vp, pt, sk, sv = _inputs(jax.random.key(0))
    plen = jnp.array([17, 0, 24, 5], jnp.int32)
    n_side = jnp.array([3, 0, 5, 1], jnp.int32)
    ref = _ref(q, kp, vp, pt, plen, sk, sv, n_side, 2)
    out = flash_decode_attention(
        q, kp, vp, pt, plen, sk, sv, n_side, n_kv_heads=2, impl=IMPL,
        layer=0, n_pages_per_layer=16, pages_per_block=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_parity_all_rows_empty():
    """Fully idle batch (zero prefix AND zero side everywhere): out must be
    exactly the reference's zeros-over-eps, not stale accumulator garbage."""
    q, kp, vp, pt, sk, sv = _inputs(jax.random.key(1))
    plen = jnp.zeros((4,), jnp.int32)
    n_side = jnp.zeros((4,), jnp.int32)
    ref = _ref(q, kp, vp, pt, plen, sk, sv, n_side, 2)
    out = flash_decode_attention(
        q, kp, vp, pt, plen, sk, sv, n_side, n_kv_heads=2, impl=IMPL,
        layer=0, n_pages_per_layer=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2), (8, 2)])
def test_parity_gqa_groups(h, hkv):
    dh = 128 // hkv          # keep fused = hkv*dh = 128
    q, kp, vp, pt, sk, sv = _inputs(jax.random.key(2), h=h, hkv=hkv, dh=dh)
    plen = jnp.array([9, 24, 1, 16], jnp.int32)
    n_side = jnp.array([2, 5, 4, 0], jnp.int32)
    ref = _ref(q, kp, vp, pt, plen, sk, sv, n_side, hkv)
    out = flash_decode_attention(
        q, kp, vp, pt, plen, sk, sv, n_side, n_kv_heads=hkv, impl=IMPL,
        layer=0, n_pages_per_layer=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv_dtype,tol", [
    (jnp.bfloat16, 2e-2),
    (jnp.float8_e4m3fn, 8e-2),
])
def test_parity_low_precision_kv_pools(kv_dtype, tol):
    """bf16 / fp8 pools with bf16 side buffers (the serving configuration:
    pool dtype = cfg.kv_dtype, side dtype = spec dtype)."""
    q, kp, vp, pt, sk, sv = _inputs(
        jax.random.key(3), q_dtype=jnp.bfloat16, kv_dtype=kv_dtype,
        side_dtype=jnp.bfloat16)
    plen = jnp.array([17, 3, 24, 8], jnp.int32)
    n_side = jnp.array([3, 1, 5, 2], jnp.int32)
    ref = _ref(q, kp, vp, pt, plen, sk, sv, n_side, 2)
    out = flash_decode_attention(
        q, kp, vp, pt, plen, sk, sv, n_side, n_kv_heads=2, impl=IMPL,
        layer=0, n_pages_per_layer=16)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol)


def test_parity_stacked_layer_indexing():
    """The kernel addresses pages as layer*N + table entry inside the
    stacked [L*N, P, F] pool: each layer must read ITS pages."""
    layers, n = 3, 16
    q, kp, vp, pt, sk, sv = _inputs(jax.random.key(4), layers=layers, n=n)
    plen = jnp.array([17, 0, 24, 5], jnp.int32)
    n_side = jnp.array([3, 0, 5, 1], jnp.int32)
    for layer in range(layers):
        ref = _ref(q, kp[layer * n:(layer + 1) * n],
                   vp[layer * n:(layer + 1) * n], pt, plen, sk, sv,
                   n_side, 2)
        out = flash_decode_attention(
            q, kp, vp, pt, plen, sk, sv, n_side, n_kv_heads=2, impl=IMPL,
            layer=layer, n_pages_per_layer=n)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_parity_pages_per_block_sweep():
    """Block size is a pure tuning knob: every bp gives the same answer
    (exercises partial tail blocks and multi-DMA issue batches)."""
    q, kp, vp, pt, sk, sv = _inputs(jax.random.key(5), mp=4)
    plen = jnp.array([29, 8, 32, 15], jnp.int32)
    n_side = jnp.array([1, 4, 0, 3], jnp.int32)
    ref = _ref(q, kp, vp, pt, plen, sk, sv, n_side, 2)
    for bp in (1, 2, 4):
        out = flash_decode_attention_pallas(
            q, kp, vp, pt, plen, sk, sv, n_side, n_kv_heads=2,
            interpret=True, layer=0, n_pages_per_layer=16,
            pages_per_block=bp)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=f"bp={bp}")


# --------------------------------------------------- model-level wiring


def _window_setup(seed=0):
    from distributed_inference_engine_tpu.models.base import (
        ModelSpec, init_params)

    spec = ModelSpec(
        vocab_size=256, d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq_len=128, dtype="float32",
    )
    params = init_params(spec, jax.random.key(seed))
    L, hkv, dh = spec.n_layers, spec.n_kv_heads, spec.head_dim
    b, n, p, mp, w = 4, 16, 16, 4, 6
    ks = jax.random.split(jax.random.key(seed + 1), 6)
    kp = jax.random.normal(ks[0], (L, n, p, hkv * dh), jnp.float32) * 0.3
    vp = jax.random.normal(ks[1], (L, n, p, hkv * dh), jnp.float32) * 0.3
    pt = jax.random.randint(ks[2], (b, mp), 0, n, jnp.int32)
    sk = jax.random.normal(ks[3], (L, b, w, hkv, dh), jnp.float32) * 0.3
    sv = jax.random.normal(ks[4], (L, b, w, hkv, dh), jnp.float32) * 0.3
    tokens = jax.random.randint(ks[5], (b,), 1, spec.vocab_size, jnp.int32)
    start_lengths = jnp.array([17, 0, 40, 5], jnp.int32)
    lengths = start_lengths + jnp.array([2, 0, 4, 1], jnp.int32)
    active = jnp.array([True, False, True, True])
    return (spec, params, tokens, lengths, start_lengths, kp, vp, pt,
            sk, sv, active)


def test_forward_decode_window_matches_dense_decode():
    """One ``window`` step (the kernel over pages + side window) against
    one ``dense`` step (``forward_decode`` over the same context laid out
    as a dense cache): the same hidden state for every live row, and the
    step's fresh K/V in the side window where the dense cache has it."""
    from distributed_inference_engine_tpu.models.base import (
        forward_decode, forward_decode_window)

    (spec, params, tokens, lengths, start, kp, vp, pt, sk, sv,
     active) = _window_setup()
    L, b, w = sk.shape[0], sk.shape[1], sk.shape[2]
    p, mp = kp.shape[2], pt.shape[1]
    hkv, dh = spec.n_kv_heads, spec.head_dim
    # dense cache: each row's prefix pages, then its side entries at
    # [start, start + w)
    s_tot = mp * p + w
    ck = jnp.zeros((L, b, s_tot, hkv, dh), jnp.float32)
    cv = jnp.zeros_like(ck)
    ck = ck.at[:, :, : mp * p].set(kp[:, pt].reshape(L, b, mp * p, hkv, dh))
    cv = cv.at[:, :, : mp * p].set(vp[:, pt].reshape(L, b, mp * p, hkv, dh))
    bi = jnp.arange(b)[:, None]
    pos = start[:, None] + jnp.arange(w)[None, :]
    ck = ck.at[:, bi, pos].set(sk)
    cv = cv.at[:, bi, pos].set(sv)
    x_ref, ck, cv = forward_decode(spec, params, tokens, lengths, ck, cv)

    x, sk_new, sv_new = forward_decode_window(
        spec, params, tokens, lengths, start, kp, vp, pt, sk, sv, active,
        interpret=True)
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(x)[live], np.asarray(x_ref)[live],
                               rtol=2e-4, atol=2e-4)
    col = np.asarray(lengths - start)
    for i in np.flatnonzero(live):
        np.testing.assert_allclose(
            np.asarray(sk_new)[:, i, col[i]],
            np.asarray(ck)[:, i, int(lengths[i])], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(sv_new)[:, i, col[i]],
            np.asarray(cv)[:, i, int(lengths[i])], rtol=1e-5, atol=1e-5)
    # a dead row writes nothing
    dead = np.flatnonzero(~live)
    np.testing.assert_array_equal(np.asarray(sk_new)[:, dead],
                                  np.asarray(sk)[:, dead])


@pytest.mark.slow
def test_engine_generate_parity_pallas_decode():
    """End-to-end: a continuous engine configured with
    attention_impl="pallas-decode_interpret" emits token-identical greedy
    output to the xla engine (windowed decode path)."""
    from distributed_inference_engine_tpu.config import EngineConfig
    from distributed_inference_engine_tpu.engine.continuous import (
        ContinuousEngine)
    from distributed_inference_engine_tpu.engine.types import (
        GenerationRequest)
    from distributed_inference_engine_tpu.models.base import ModelSpec

    spec = ModelSpec(
        vocab_size=256, d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq_len=128, dtype="float32",
    )
    base = dict(max_slots=2, max_seq_len=64, prefill_buckets=[16],
                page_size=16, num_pages=16, decode_steps_per_call=4)
    xla = ContinuousEngine(spec, config=EngineConfig(
        attention_impl="xla", **base), seed=0)
    fd = ContinuousEngine(spec, params=xla.params, config=EngineConfig(
        attention_impl="pallas-decode_interpret", **base), seed=0)
    reqs = lambda: [GenerationRequest(prompt=[3 + i, 7, 11],
                                      max_new_tokens=6, temperature=0.0,
                                      request_id=f"r{i}") for i in range(2)]
    a = {r.request_id: r.tokens for r in xla.generate(reqs())}
    b = {r.request_id: r.tokens for r in fd.generate(reqs())}
    assert a == b


# ------------------------------------- the served row shape (B = 8, GQA 4:1)


def _dense_truth(q, kp, vp, pt, plen, sk, sv, n_side, hkv):
    """Ground truth independent of the paged/side split: every row's
    prefix pages and side window laid out as ONE dense context, its live
    keys packed at the front, through ``cached_attention``."""
    from distributed_inference_engine_tpu.ops.attention import (
        cached_attention)

    b, h, dh = q.shape
    p = kp.shape[1]
    mp, w = pt.shape[1], sk.shape[1]
    ks, vs = [], []
    for i in range(b):
        n = int(plen[i])
        rows_k = kp[pt[i]].reshape(mp * p, hkv, dh)[:n]
        rows_v = vp[pt[i]].reshape(mp * p, hkv, dh)[:n]
        pad = jnp.zeros((mp * p - n, hkv, dh), rows_k.dtype)
        ks.append(jnp.concatenate(
            [rows_k, sk[i].astype(rows_k.dtype), pad], axis=0))
        vs.append(jnp.concatenate(
            [rows_v, sv[i].astype(rows_v.dtype), pad], axis=0))
    out = cached_attention(q[:, None], jnp.stack(ks), jnp.stack(vs),
                           plen + n_side)
    return out[:, 0]


@pytest.mark.parametrize("bp", [1, 4, 8])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_served_row_shape_matches_cached_attention(bp, dtype, tol):
    """The cells' row shape: 8 rows, four query heads a KV head, up to 8
    pages a row; lengths 0, 1, one short of a page, exactly a page, all 8
    pages, with dead rows (no prefix, no side entries) mixed in. Dead rows
    give exact zeros; live rows match ``cached_attention`` over the same
    keys laid out densely."""
    p, mp, hkv, h, dh, w = 16, 8, 2, 8, 64, 8
    q, kp, vp, pt, sk, sv = _inputs(
        jax.random.key(11), b=8, h=h, hkv=hkv, dh=dh, n=80, p=p, mp=mp,
        w=w, q_dtype=dtype, kv_dtype=dtype)
    plen = jnp.array([0, 1, p - 1, 0, p, mp * p, 0, 3 * p + 5], jnp.int32)
    n_side = jnp.array([0, 3, w, 0, 1, 2, 0, 5], jnp.int32)
    out = flash_decode_attention_pallas(
        q, kp, vp, pt, plen, sk, sv, n_side, n_kv_heads=hkv,
        interpret=True, layer=0, n_pages_per_layer=80, pages_per_block=bp)
    ref = _dense_truth(q, kp, vp, pt, plen, sk, sv, n_side, hkv)
    dead = np.asarray(plen + n_side) == 0
    got = np.asarray(out, np.float32)
    assert not got[dead].any()
    np.testing.assert_allclose(got[~dead], np.asarray(ref, np.float32)[~dead],
                               rtol=tol, atol=tol)


def _record_copies(monkeypatch):
    """Every pool page a kernel traced from here on starts a copy of, in
    order (the first index of the copy's source)."""
    from jax.experimental.pallas import tpu as pltpu

    started = []
    real = pltpu.make_async_copy

    class Recording:
        def __init__(self, src, dst, sem):
            self._page = src.transforms[-1].indices[0]
            self._c = real(src, dst, sem)

        def start(self):
            jax.debug.callback(lambda pg: started.append(int(pg)),
                               self._page)
            self._c.start()

        def wait(self):
            self._c.wait()

    monkeypatch.setattr(pltpu, "make_async_copy", Recording)
    return started


def test_dead_rows_and_short_rows_start_no_dma(monkeypatch):
    """Only live pages move: the pool pages the kernel starts copies from
    are exactly those holding a token below their row's length — none for
    a dead row, one page for a one-token row in a 4-page block, none past
    a row's last page. (Which pages, not how often: the interpreter
    resets the scalar-prefetch state at each grid step, so a row's first
    block is issued again there; on the chip the state persists.)"""
    started = _record_copies(monkeypatch)
    p, mp, n = 16, 8, 80
    q, kp, vp, _, sk, sv = _inputs(jax.random.key(12), b=8, h=8, hkv=2,
                                   dh=64, n=n, p=p, mp=mp, w=4, layers=2)
    pt = jax.random.permutation(jax.random.key(13), n)[:8 * mp].reshape(
        8, mp).astype(jnp.int32)
    plen = jnp.array([0, 1, p - 1, 0, p, mp * p, 0, 3 * p + 5], jnp.int32)
    n_side = jnp.array([0, 1, 1, 0, 1, 1, 0, 1], jnp.int32)
    out = flash_decode_attention_pallas(
        q, kp, vp, pt, plen, sk, sv, n_side, n_kv_heads=2, interpret=True,
        layer=1, n_pages_per_layer=n, pages_per_block=4)
    jax.block_until_ready(out)
    jax.effects_barrier()
    live = {n + int(pt[i, c]) for i in range(8)
            for c in range(-(-int(plen[i]) // p))}
    assert len(live) == 1 + 1 + 1 + 8 + 4
    assert set(started) == live


# ------------------------------------ latent rows (MLA's absorbed decode)


def _latent_case(dtype, *, layers=3, n=40, p=16, mp=4, wc=8, h=4, rank=32,
                 dr=8, dn=16, dv=16, lanes=128):
    """ONE pool of every layer's pages whose rows are c | k_rope | zero
    lanes; 8 rows: dead, one token, one short of / at / one past a page
    boundary, a full table, and two mid-page; a part-filled side window
    (full for one row, empty for the dead one and one live one)."""
    ks = jax.random.split(jax.random.key(38), 6)
    keep = jnp.arange(lanes) < rank + dr
    pool = (jax.random.normal(ks[0], (layers * n, p, lanes)) * keep).astype(
        dtype)
    side = (jax.random.normal(ks[1], (8, wc, lanes)) * keep).astype(dtype)
    table = jax.random.permutation(ks[2], n)[:8 * mp].reshape(8, mp).astype(
        jnp.int32)
    qn = jax.random.normal(ks[3], (8, h, dn)).astype(dtype)
    qr = jax.random.normal(ks[4], (8, h, dr)).astype(dtype)
    w_kvb = (0.3 * jax.random.normal(ks[5], (rank, h, dn + dv))).astype(dtype)
    plen = jnp.array([0, 1, p - 1, p, p + 1, mp * p, 2 * p + 5, 3 * p + 9],
                     jnp.int32)
    n_side = jnp.array([0, 1, wc, 3, 0, 5, 1, 2], jnp.int32)
    return pool, side, table, qn, qr, w_kvb, plen, n_side, rank, n, p


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("bp,ap", [(1, 1), (2, 1), (2, 2), (4, 2), (4, 4)])
def test_latent_kernel_matches_the_absorbed_xla_body(dtype, tol, bp, ap):
    """``mla_absorbed_decode_inplace`` (the kernel through the interpreter,
    rows read where they lie from the middle layer of a stacked pool)
    against ``mla_absorbed_decode`` on that layer's gathered pages, at every
    pages a block / pages a softmax update; a dead row's output is zero."""
    from distributed_inference_engine_tpu.ops import mla
    from distributed_inference_engine_tpu.ops.flash_decode import (
        latent_decode_attention_pallas)

    (pool, side, table, qn, qr, w_kvb, plen, n_side, rank, n,
     p) = _latent_case(dtype)
    layer = 1
    own = pool[layer * n + table].reshape(8, -1, pool.shape[-1])
    ref = mla.mla_absorbed_decode(qn, qr, w_kvb, own, plen, side, n_side,
                                  rank, scale=0.2)
    w_k = w_kvb[..., :qn.shape[-1]]
    q_abs = jnp.einsum("bhd,chd->bhc", qn, w_k,
                       preferred_element_type=jnp.float32).astype(dtype)
    q = jnp.concatenate(
        [q_abs, qr, jnp.zeros((8, 4, pool.shape[-1] - rank - 8), dtype)], -1)
    o_lat, _pages = latent_decode_attention_pallas(
        q, pool, table, plen, side, n_side, layer, v_lanes=rank, scale=0.2,
        interpret=True, n_pages_per_layer=n, pages_per_block=bp,
        pages_per_attend=ap)
    got = jnp.einsum("bhc,chd->bhd", o_lat.astype(dtype),
                     w_kvb[..., qn.shape[-1]:],
                     preferred_element_type=jnp.float32)
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    assert not got[0].any()
    np.testing.assert_allclose(got[1:], ref[1:], rtol=tol, atol=tol)
    # the one entry the models call gives the same
    out, _ = mla.mla_absorbed_decode_inplace(
        qn, qr, w_kvb, pool, table, layer, plen, side, n_side, rank,
        scale=0.2, n_pages_per_layer=n, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32)[1:], ref[1:],
                               rtol=tol, atol=tol)


def test_latent_kernel_copies_the_live_pages_and_counts_them(monkeypatch):
    """ONE copy a live page, from the layer the scalar names, none for a
    dead row or past a row's last page; the kernel's own count is the live
    pages (under the interpreter, which starts the scalar-prefetch state
    anew each grid step, plus each later live row's first block, issued
    again by its own turn; the chip's count is exact:
    ``scripts/chip_kernels.py`` ``latent_decode`` holds that)."""
    from distributed_inference_engine_tpu.ops.flash_decode import (
        latent_decode_attention_pallas)

    started = _record_copies(monkeypatch)
    (pool, side, table, _qn, _qr, _w, plen, n_side, rank, n,
     p) = _latent_case(jnp.float32)
    q = jax.random.normal(jax.random.key(1), (8, 4, pool.shape[-1]))
    bp = 2
    # the launcher is ONE jax.jit: a scale no other test uses, so that it is
    # traced here, with the recording copies
    out, copied = latent_decode_attention_pallas(
        q, pool, table, plen, side, n_side, 2, v_lanes=rank, scale=0.125,
        interpret=True, n_pages_per_layer=n, pages_per_block=bp,
        pages_per_attend=2)
    jax.block_until_ready(out)
    jax.effects_barrier()
    pages = [-(-int(x) // p) for x in plen]
    live = {2 * n + int(table[i, c]) for i in range(8)
            for c in range(pages[i])}
    assert len(live) == sum(pages) == 0 + 1 + 1 + 1 + 2 + 4 + 3 + 4
    assert set(started) == live
    first_live = next(i for i, x in enumerate(pages) if x)
    again = sum(min(x, bp) for x in pages[first_live + 1:])
    assert int(copied) == len(started) == sum(pages) + again


# --------------------------------------------- what "auto" resolves to


def _resolve_spec(**kw):
    from distributed_inference_engine_tpu.models.base import ModelSpec

    if kw.pop("latent_rows", False):
        # a per-layer spec whose paged layers keep latent rows (32 + 8
        # values, held at one 128-lane tile)
        from distributed_inference_engine_tpu.models.xing import xing_spec

        return xing_spec("xing-tiny", **kw)
    base = dict(vocab_size=256, d_model=256, n_layers=2, n_heads=4,
                n_kv_heads=2, d_ff=256, max_seq_len=128)    # Hkv*Dh = 128
    base.update(kw)
    return ModelSpec(**base)


@pytest.mark.parametrize("impl,backend,spec_kw,sharded,want", [
    ("auto", "tpu", {}, False, ("window", "pallas-decode")),
    ("auto", "cpu", {}, False, ("dense", "xla")),
    ("auto", "gpu", {}, False, ("dense", "xla")),
    ("auto", "tpu", {"sliding_window": 64}, False, ("inline", "xla")),
    ("auto", "tpu", {}, True, ("dense", "xla")),
    # Hkv*Dh = 2 * 48 = 96 lanes: not whole 128-lane tiles
    ("auto", "tpu", {"d_model": 192}, False, ("dense", "xla")),
    ("xla", "tpu", {}, False, ("dense", "xla")),
    ("pallas-decode", "cpu", {}, False, ("window", "pallas-decode")),
    ("pallas-decode_interpret", "cpu", {}, False,
     ("window", "pallas-decode_interpret")),
    # a sliding window has one body, whatever the string asks for
    ("pallas-decode_interpret", "cpu", {"sliding_window": 64}, True,
     ("inline", "xla")),
    # latent rows take the kernel under exactly a K|V spec's conditions
    ("auto", "tpu", {"latent_rows": True}, False,
     ("hybrid", "pallas-decode")),
    ("auto", "cpu", {"latent_rows": True}, False, ("hybrid", "xla")),
    ("auto", "tpu", {"latent_rows": True}, True, ("hybrid", "xla")),
    ("xla", "tpu", {"latent_rows": True}, False, ("hybrid", "xla")),
    ("pallas-decode", "cpu", {"latent_rows": True}, False,
     ("hybrid", "pallas-decode")),
    ("pallas-decode_interpret", "cpu", {"latent_rows": True}, False,
     ("hybrid", "pallas-decode_interpret")),
])
def test_auto_resolution_is_a_pure_function(impl, backend, spec_kw, sharded,
                                            want):
    """The decode body and its attention resolve from (string, backend,
    spec, pool sharding) alone: ``(body, attn_impl)``."""
    from distributed_inference_engine_tpu.engine.continuous import (
        resolve_decode_body)

    spec = _resolve_spec(**dict(spec_kw))
    for _ in range(2):
        assert resolve_decode_body(impl, backend, spec,
                                   sharded=sharded) == want


# ------------------------------------------- engine level, tier-1 sized


def _tiny_engines(impl, **cfg_kw):
    from distributed_inference_engine_tpu.config import EngineConfig
    from distributed_inference_engine_tpu.engine.continuous import (
        ContinuousEngine)
    from distributed_inference_engine_tpu.models.base import ModelSpec

    spec = ModelSpec(
        vocab_size=128, d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, dtype="float32",
    )                                                   # Hkv*Dh = 128 lanes
    base = dict(max_slots=2, max_seq_len=96, prefill_buckets=[16, 64],
                page_size=16, num_pages=12, decode_steps_per_call=4,
                prefix_cache=False)
    base.update(cfg_kw)
    ref = ContinuousEngine(spec, config=EngineConfig(**base), seed=0)
    fd = ContinuousEngine(spec, params=ref.params, config=EngineConfig(
        attention_impl=impl, **base), seed=0)
    return ref, fd


def _requests(shapes):
    from distributed_inference_engine_tpu.engine.types import (
        GenerationRequest)

    return [GenerationRequest(prompt=[3 + i] + [7, 11, 5] * (n // 3),
                              max_new_tokens=m, temperature=0.0,
                              request_id=f"r{i}")
            for i, (n, m) in enumerate(shapes)]


@pytest.mark.parametrize("impl", ["pallas-decode_interpret"])
def test_engine_greedy_parity_through_admission_and_slot_reuse(impl):
    """A default (``auto``) engine on the CPU runs the dense XLA path; the
    same engine on the kernel path emits its greedy tokens exactly —
    through batched admission, first tokens installed device-side and a
    slot handed on behind the chunk its request ends in. The counters
    say which path each engine's decode chunks took."""
    ref, fd = _tiny_engines(impl)
    assert ref.attn_impl == "xla"            # auto, on the CPU backend
    # five requests over two slots: every finish frees a slot that the
    # next request takes while the other slot is mid-decode
    shapes = [(4, 5), (19, 11), (7, 3), (33, 9), (16, 6)]
    a = {r.request_id: r.tokens for r in ref.generate(_requests(shapes))}
    b = {r.request_id: r.tokens for r in fd.generate(_requests(shapes))}
    assert len(a) == len(shapes) and a == b
    assert [len(a[f"r{i}"]) for i in range(5)] == [m for _, m in shapes]
    ma, mb = ref.get_metrics(), fd.get_metrics()
    assert ma["admissions_ahead"] > 0
    assert mb["admissions_ahead"] == ma["admissions_ahead"]
    assert ma["attn_impl"] == "xla" and mb["attn_impl"] == impl
    assert ma["decode_chunks_dense"] > 0 and ma["decode_chunks_in_place"] == 0
    assert mb["decode_chunks_in_place"] > 0 and mb["decode_chunks_dense"] == 0
    assert mb["decode_chunks_in_place"] == ma["decode_chunks_dense"]


@pytest.mark.parametrize("impl", ["pallas-decode_interpret"])
def test_decode_programs_do_not_grow_with_context_bucket(impl):
    """The dense path compiles one decode program per (steps x pow2
    context-page bucket); the kernel path reads the pages in place, so one
    program per ``n_steps`` serves every context length."""
    ref, fd = _tiny_engines(impl, max_slots=1, num_pages=6)
    # contexts of 1, 2 and 4+ pages of 16, the same chunk length each
    shapes = [(4, 4), (22, 4), (55, 4)]
    for eng in (ref, fd):
        for req in _requests(shapes):
            eng.generate([req])
    assert ref._decode_chunk._cache_size() >= 3
    assert fd._decode_chunk._cache_size() == 1


# ------------------------------- the other families' programs, as they were


def _pallas_eqn(fn, *args):
    """The one ``pallas_call`` equation of ``fn``'s jaxpr, found through any
    ``jit`` that wraps it."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    yield from find(getattr(sub.jaxpr, "jaxpr", sub.jaxpr))
    (eqn,) = find(jax.make_jaxpr(fn)(*args).jaxpr)
    return eqn


@pytest.mark.parametrize("caller,operands,kernel_refs", [
    # Mistral (models/base.py forward_decode_window): K and V pools apart
    ("window", 12, 19),
    # Olmo-Hybrid: ONE pool of K|V rows, the kernel's own page count
    ("kv_fused", 12, 20),
    # Mellum's sliding layers: a lower bound besides
    ("kv_fused_lower_bound", 13, 21),
    # Ling / Xing / Kimi: latent rows
    ("latent", 10, 17),
])
def test_the_other_families_kernels_take_no_mask(caller, operands,
                                                 kernel_refs):
    """``_attend`` and ``_prefix_loop`` took a selection's mask for the
    learned-sparse family (``ops/sparse_index.py``), as arguments absent at
    trace time for every other caller: the ``window``, ``kv_fused`` and
    latent launchers called as Mistral, Olmo / Mellum and Ling / Xing / Kimi
    call them trace the operands and the kernel refs they had before it."""
    from distributed_inference_engine_tpu.ops.flash_decode import (
        latent_decode_attention_pallas)

    b, h, hkv, dh, n, p, mp, w = 2, 4, 2, 64, 8, 8, 3, 4
    q = jnp.zeros((b, h, dh))
    table = jnp.zeros((b, mp), jnp.int32)
    lens = jnp.zeros((b,), jnp.int32)
    side = jnp.zeros((b, w, hkv, dh))
    if caller == "window":
        pool = jnp.zeros((n, p, hkv * dh))
        eqn = _pallas_eqn(
            lambda *a: flash_decode_attention_pallas(*a, n_kv_heads=hkv),
            q, pool, pool, table, lens, side, side, lens)
    elif caller.startswith("kv_fused"):
        pool = jnp.zeros((n, p, 2 * hkv * dh))
        bound = {"first_rows": lens} if caller.endswith("bound") else {}
        eqn = _pallas_eqn(
            lambda q, pool, *a: flash_decode_attention_pallas(
                q, pool, pool, *a, n_kv_heads=hkv, layer=0,
                n_pages_per_layer=n, kv_fused=True, count_pages=True,
                **bound),
            q, pool, table, lens, side, side, lens)
    else:
        pool = jnp.zeros((n, p, 128))
        eqn = _pallas_eqn(
            lambda *a: latent_decode_attention_pallas(
                *a, v_lanes=64, scale=0.1, n_pages_per_layer=n),
            jnp.zeros((b, h, 128)), pool, table, lens,
            jnp.zeros((b, w, 128)), lens, jnp.int32(0))
    kernel = eqn.params["jaxpr"]
    assert len(eqn.invars) == operands
    assert len(kernel.invars) == kernel_refs
