"""Paged KV cache + paged attention (the XLA path; the in-place kernel is
tests/test_flash_decode.py): allocator accounting, masking of stale pool
data, flash-stats merging, and paged-vs-contiguous decode parity.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_engine_tpu.engine.paged_kv import PagedKVCache
from distributed_inference_engine_tpu.models.base import (
    ModelSpec,
    forward_decode,
    forward_decode_paged,
    forward_prefill,
    init_params,
    write_prefill_pages,
)
from distributed_inference_engine_tpu.ops.paged_attention import (
    paged_attention_xla,
)

# fused kv dim must be a multiple of 128: 2 heads * 64 = 128
SPEC = ModelSpec(
    vocab_size=256, d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=256, max_seq_len=256, dtype="float32",
)


# ------------------------------------------------------------- allocator


def test_alloc_slot_and_pages():
    kv = PagedKVCache(SPEC, max_slots=4, page_size=16, num_pages=8, max_seq_len=128)
    s0 = kv.alloc_slot(20)          # 2 pages
    s1 = kv.alloc_slot(5)           # 1 page
    assert s0 is not None and s1 is not None and s0 != s1
    assert kv.n_free_pages == 5
    assert kv.slot_capacity(s0) == 32
    kv.free_slot(s0)
    assert kv.n_free_pages == 7
    assert kv.n_free_slots == 3


def test_alloc_exhaustion_returns_none():
    kv = PagedKVCache(SPEC, max_slots=8, page_size=16, num_pages=2, max_seq_len=128)
    assert kv.alloc_slot(32) is not None      # takes both pages
    assert kv.alloc_slot(1) is None           # no pages left
    stats = kv.get_stats()
    assert stats["pages_free"] == 0 and stats["utilization"] == 1.0


def test_reserve_grows_across_page_boundary():
    kv = PagedKVCache(SPEC, max_slots=2, page_size=16, num_pages=4, max_seq_len=128)
    s = kv.alloc_slot(15)
    assert kv.slot_capacity(s) == 16
    assert kv.reserve(s, 8) == 8              # 15+8=23 -> 2 pages
    assert kv.slot_capacity(s) == 32
    assert kv.reserve(s, 1000) == 0           # would need more than the pool
    kv.free_slot(s)
    assert kv.n_free_pages == 4


def test_reserve_truncated_by_max_seq_len():
    """A grant clipped by max_seq_len reports the partial amount, and a slot
    already at max_seq_len gets 0 — the decode chunk must stop, not index
    past the page table (code-review finding: silent True here corrupted
    the slot's last page)."""
    kv = PagedKVCache(SPEC, max_slots=1, page_size=16, num_pages=8, max_seq_len=64)
    s = kv.alloc_slot(60)
    assert kv.reserve(s, 16) == 4             # clipped at 64
    assert kv.reserve(s, 16) == 0             # already at cap
    assert kv.slot_capacity(s) == 64


def test_page_table_device_mirror_updates():
    kv = PagedKVCache(SPEC, max_slots=2, page_size=16, num_pages=4, max_seq_len=64)
    t0 = kv.page_table
    assert t0.shape == (2, 4)
    s = kv.alloc_slot(30)
    t1 = kv.page_table
    assert not np.array_equal(np.asarray(t0), np.asarray(t1))
    # no accounting change -> same device array object (no re-upload)
    assert kv.page_table is t1
    kv.free_slot(s)


def test_misaligned_fused_dim_rejected():
    # a valid spec whose kv width is misaligned: 1 kv head * 16 dims = 16
    bad = ModelSpec(vocab_size=16, d_model=64, n_layers=1, n_heads=4,
                    n_kv_heads=1, d_ff=64)
    with pytest.raises(ValueError, match="multiple of 128"):
        PagedKVCache(bad, max_slots=1, page_size=8, num_pages=2)


# ------------------------------------------------------- paged attention


def _random_paged_case(seed, b=3, h=4, n_kv=2, dh=64, page_size=16,
                       num_pages=16, max_pages=4, dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    fused = n_kv * dh
    q = jnp.asarray(rs.randn(b, h, dh), dtype=dtype)
    k_pages = jnp.asarray(rs.randn(num_pages, page_size, fused), dtype=dtype)
    v_pages = jnp.asarray(rs.randn(num_pages, page_size, fused), dtype=dtype)
    # distinct physical pages per slot (as the allocator guarantees)
    perm = rs.permutation(num_pages)[: b * max_pages].reshape(b, max_pages)
    table = jnp.asarray(perm, dtype=jnp.int32)
    lengths = jnp.asarray(rs.randint(1, page_size * max_pages + 1, size=b),
                          dtype=jnp.int32)
    return q, k_pages, v_pages, table, lengths


def test_xla_path_masks_stale_pool_data():
    """Garbage in unused pages/positions must not leak into the output."""
    q, kp, vp, table, _ = _random_paged_case(3)
    lengths = jnp.asarray([5, 5, 5], dtype=jnp.int32)
    out1 = paged_attention_xla(q, kp, vp, table, lengths, n_kv_heads=2)
    # poison everything past position 5 in each slot's first page + all later pages
    kp2 = kp.at[:, 5:, :].set(1e4)
    out2 = paged_attention_xla(q, kp2, vp, table, lengths, n_kv_heads=2)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


# ------------------------------------------------- end-to-end decode parity


def test_paged_decode_matches_contiguous():
    """forward_decode_paged == forward_decode given identical KV history."""
    spec = SPEC
    key = jax.random.key(0)
    params = init_params(spec, key)
    rs = np.random.RandomState(0)
    B, T = 2, 24
    prompts = jnp.asarray(rs.randint(0, spec.vocab_size, size=(B, T)), jnp.int32)
    seq_lens = jnp.asarray([24, 9], dtype=jnp.int32)

    _, ks, vs = forward_prefill(spec, params, prompts, seq_lens)

    # contiguous cache
    S = 64
    L, Hkv, Dh = spec.n_layers, spec.n_kv_heads, spec.head_dim
    ck = jnp.zeros((L, B, S, Hkv, Dh), jnp.float32).at[:, :, :T].set(ks)
    cv = jnp.zeros((L, B, S, Hkv, Dh), jnp.float32).at[:, :, :T].set(vs)

    # paged cache via the real allocator + prefill scatter
    kv = PagedKVCache(spec, max_slots=B, page_size=16, num_pages=12,
                      max_seq_len=S, dtype="float32")
    slots = [kv.alloc_slot(int(seq_lens[i]) + 8) for i in range(B)]
    assert slots == [0, 1]
    kp, vp = write_prefill_pages(
        kv.k_pages, kv.v_pages, ks, vs, kv.page_table, seq_lens
    )

    tok = jnp.asarray(rs.randint(0, spec.vocab_size, size=B), jnp.int32)
    h_ref, _, _ = forward_decode(spec, params, tok, seq_lens, ck, cv)
    h_paged, kp2, vp2 = forward_decode_paged(
        spec, params, tok, seq_lens, kp, vp, kv.page_table
    )
    np.testing.assert_allclose(np.asarray(h_paged), np.asarray(h_ref),
                               rtol=2e-4, atol=2e-4)

    # and one more step after the write (checks the scatter landed right):
    # the first decode call wrote fresh K/V into both cache forms
    tok2 = jnp.asarray(rs.randint(0, spec.vocab_size, size=B), jnp.int32)
    _, ck2, cv2 = forward_decode(spec, params, tok, seq_lens, ck, cv)
    h_ref2, _, _ = forward_decode(spec, params, tok2, seq_lens + 1, ck2, cv2)
    h_paged2, _, _ = forward_decode_paged(
        spec, params, tok2, seq_lens + 1, kp2, vp2, kv.page_table,
    )
    np.testing.assert_allclose(np.asarray(h_paged2), np.asarray(h_ref2),
                               rtol=2e-4, atol=2e-4)


def test_prefill_page_scatter_roundtrip():
    """Tokens written by write_prefill_pages land at (table[b,pos//P], pos%P)."""
    spec = SPEC
    params = init_params(spec, jax.random.key(1))
    rs = np.random.RandomState(5)
    B, T = 2, 20
    prompts = jnp.asarray(rs.randint(0, spec.vocab_size, size=(B, T)), jnp.int32)
    seq_lens = jnp.asarray([20, 13], dtype=jnp.int32)
    _, ks, vs = forward_prefill(spec, params, prompts, seq_lens)

    kv = PagedKVCache(spec, max_slots=B, page_size=16, num_pages=8,
                      max_seq_len=64, dtype="float32")
    for i in range(B):
        kv.alloc_slot(int(seq_lens[i]))
    kp, vp = write_prefill_pages(
        kv.k_pages, kv.v_pages, ks, vs, kv.page_table, seq_lens
    )
    table = np.asarray(kv.page_table)
    kp_np = np.asarray(kp)
    ks_np = np.asarray(ks).reshape(spec.n_layers, B, T, -1)
    for b in range(B):
        for pos in [0, 7, int(seq_lens[b]) - 1]:
            page, off = table[b, pos // 16], pos % 16
            np.testing.assert_allclose(
                kp_np[:, page, off], ks_np[:, b, pos], rtol=1e-6
            )
    # padded tail of slot 1 (positions 13..19) must NOT have been written
    np.testing.assert_allclose(kp_np[:, table[1, 0], 14], 0.0, atol=0)


# -------------------------------------------- flash stats + stacked pools


def test_stats_merge_matches_single_softmax():
    """Splitting the key set into paged-prefix + side-window partials and
    merging their flash stats must equal one softmax over the union —
    the invariant the windowed decode chunk rests on."""
    from distributed_inference_engine_tpu.ops.attention import (
        merge_attention, window_decode_attention,
    )

    q, kp, vp, table, _ = _random_paged_case(5)
    lengths = jnp.asarray([30, 17, 64], jnp.int32)
    rs = np.random.RandomState(9)
    b, h = q.shape[0], q.shape[1]
    W, n_kv, dh = 8, 2, q.shape[2]
    ks = jnp.asarray(rs.randn(b, W, n_kv, dh), jnp.float32)
    vs = jnp.asarray(rs.randn(b, W, n_kv, dh), jnp.float32)
    n_side = jnp.asarray([3, 0, 8], jnp.int32)   # incl. a zero-valid row

    prefix = paged_attention_xla(q, kp, vp, table, lengths, n_kv_heads=2,
                                 with_stats=True)
    window = window_decode_attention(q, ks, vs, n_side)
    merged = merge_attention([prefix, window])

    # reference: one dense softmax over gathered prefix + valid side keys
    mp, p = table.shape[1], kp.shape[1]
    k_all = kp[table].reshape(b, mp * p, n_kv, dh)
    v_all = vp[table].reshape(b, mp * p, n_kv, dh)
    k_cat = jnp.concatenate([k_all, ks], axis=1)
    v_cat = jnp.concatenate([v_all, vs], axis=1)
    s_tot = mp * p + W
    valid = (jnp.arange(s_tot)[None, :] < lengths[:, None]) | (
        (jnp.arange(s_tot)[None, :] >= mp * p)
        & (jnp.arange(s_tot)[None, :] - mp * p < n_side[:, None]))
    qg = q.reshape(b, n_kv, h // n_kv, dh)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_cat) / np.sqrt(dh)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ref = jnp.einsum("bkgs,bskd->bkgd", probs, v_cat).reshape(b, h, dh)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_forward_prefill_into_pages_matches_two_program_path():
    """The fused admission prefill (per-layer KV scattered into the
    pools inside the scan, r5) must produce byte-identical pools and
    hidden states to forward_prefill + write_prefill_pages — including
    a seq_len=0 pad row, whose positions must all drop."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_inference_engine_tpu.models.base import (
        ModelSpec,
        forward_prefill,
        forward_prefill_into_pages,
        init_params,
        write_prefill_pages,
    )

    spec = ModelSpec(vocab_size=128, d_model=256, n_layers=2, n_heads=4,
                     n_kv_heads=2, d_ff=128, max_seq_len=64,
                     dtype="float32")
    params = init_params(spec, jax.random.key(0))
    L, Hkv, Dh = 2, 2, 64
    n_pages, page_size = 8, 16
    fused = Hkv * Dh
    rs = np.random.RandomState(0)
    tokens = jnp.asarray(rs.randint(1, 128, size=(4, 32)), jnp.int32)
    seq_lens = jnp.asarray([32, 20, 5, 0], jnp.int32)   # incl. pad row
    table = jnp.asarray([[1, 2, 0, 0], [3, 4, 0, 0],
                         [5, 0, 0, 0], [0, 0, 0, 0]], jnp.int32)
    kp0 = jnp.full((L, n_pages, page_size, fused), -7.0, jnp.float32)
    vp0 = jnp.full_like(kp0, -9.0)

    h_ref, ks, vs = forward_prefill(spec, params, tokens, seq_lens)
    kp_ref, vp_ref = write_prefill_pages(
        kp0, vp0, ks, vs, table, seq_lens)
    h_got, kp_got, vp_got = forward_prefill_into_pages(
        spec, params, tokens, seq_lens, kp0, vp0, table)
    np.testing.assert_array_equal(np.asarray(h_got), np.asarray(h_ref))
    np.testing.assert_array_equal(np.asarray(kp_got), np.asarray(kp_ref))
    np.testing.assert_array_equal(np.asarray(vp_got), np.asarray(vp_ref))
    # pad row's pages (incl. page 0, which its zeroed table row points
    # at) keep the sentinel fill where no valid token landed
    assert float(kp_got[:, 6:].min()) == -7.0


# --------------------------------------- eviction order under pin pressure


def _cache(num_pages, max_slots=4):
    return PagedKVCache(SPEC, max_slots=max_slots, page_size=16,
                        num_pages=num_pages, max_seq_len=256,
                        dtype="float32")


def _prompt(base, n_tokens=16):
    return list(range(base, base + n_tokens))


def test_pinned_prefix_pages_never_reclaimed():
    """A cached page re-pinned by a live slot must be invisible to
    _take_free, even when it is the ONLY reclaimable candidate left —
    allocation fails rather than stealing pinned KV (the hazard at
    alloc_slot_prefix's pin-before-source ordering)."""
    kv = _cache(num_pages=3)
    pa = _prompt(0)
    s1, _ = kv.alloc_slot_prefix(pa)            # 1 page
    kv.register_prefix(s1, pa)
    kv.free_slot(s1)
    assert list(kv._reclaimable)                # cached, ref 0

    s2, n2 = kv.alloc_slot_prefix(pa + _prompt(100, 32))   # re-pins pa's page
    assert s2 is not None and n2 == 16
    pinned = kv._slot_pages[s2][0]
    assert pinned not in kv._reclaimable and kv._page_ref[pinned] == 1

    # pool: 3 pages, all owned by s2 now → nothing reclaimable, nothing free
    assert kv.available_pages == 0
    assert kv._take_free(1) is None             # must NOT hand out the pin
    assert kv.alloc_slot(4) is None
    # s2's table is intact and alias-free
    pages = kv._slot_pages[s2]
    assert len(set(pages)) == len(pages) == 3


def test_reclaim_order_is_recency_not_registration():
    """Re-pinning a cached chain and releasing it moves it to MRU: the
    next reclaim under pressure takes the least-recently-USED chain, not
    the first-registered one."""
    kv = _cache(num_pages=3)
    chains = [_prompt(0), _prompt(1000), _prompt(2000)]
    for c in chains:                            # cache A, then B, then C
        s, _ = kv.alloc_slot_prefix(c)
        kv.register_prefix(s, c)
        kv.free_slot(s)
    assert kv.get_stats()["pages_cached"] == 3

    # touch A: re-admit + free → A becomes most-recently-used
    s, n = kv.alloc_slot_prefix(chains[0] + [7])
    assert n == 16
    kv.free_slot(s)

    ha, hb, hc = (kv._page_hashes(c, 1)[0] for c in chains)
    # one writable page under full-cache pressure must evict B (oldest)
    s2 = kv.alloc_slot(4)
    assert s2 is not None
    assert hb not in kv._prefix_index
    assert ha in kv._prefix_index and hc in kv._prefix_index


def test_pin_churn_stress_invariants():
    """Deterministic churn of shared-prefix admissions, growth, and frees
    against a tight pool: after every operation the allocator invariants
    hold — no page in two tables, no pinned page free/reclaimable, and
    free/reclaimable disjoint."""
    kv = _cache(num_pages=10, max_slots=4)
    rs = np.random.RandomState(7)
    prompts = [_prompt(b, 40) for b in (0, 500, 0, 9000)]  # 0 shared twice
    live = {}

    def check():
        owned = [p for pages in kv._slot_pages.values() for p in pages]
        for pages in kv._slot_pages.values():
            assert len(set(pages)) == len(pages), f"aliased table {pages}"
        free, recl = set(kv._free), set(kv._reclaimable)
        assert not free & recl
        assert not set(owned) & free and not set(owned) & recl
        for p, r in kv._page_ref.items():
            assert r >= 1
            assert p not in free and p not in recl
        # every reclaimable page is indexed; every indexed page exists
        for p in recl:
            assert p in kv._page_key
        for h, p in kv._prefix_index.items():
            assert kv._page_key.get(p) == h

    for it in range(60):
        op = rs.randint(3)
        if op == 0 and len(live) < 4:
            pi = rs.randint(len(prompts))
            got = kv.alloc_slot_prefix(prompts[pi])
            if got is not None:
                slot, _ = got
                kv.register_prefix(slot, prompts[pi])
                live[slot] = prompts[pi]
        elif op == 1 and live:
            slot = list(live)[rs.randint(len(live))]
            kv.ensure_capacity(slot, kv._slot_len[slot] + 16)
        elif live:
            slot = list(live)[rs.randint(len(live))]
            kv.free_slot(slot)
            del live[slot]
        check()
    for slot in list(live):
        kv.free_slot(slot)
    check()
    assert kv.available_pages == 10
