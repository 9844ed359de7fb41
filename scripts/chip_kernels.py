"""Compile and run every Pallas kernel in ``ops/`` once, non-interpreted, at
the mistral-7b serving shapes, each against its XLA path.

    python chip_smoke.py --kernels            # the one command, on the chip
    python -m scripts.chip_kernels --tiny     # CPU debug: interpreted, tiny

Shapes (full): B=128, H=32, Hkv=8, Dh=128, page 128, ctx 256; the latent
prefill at 32 heads of 128 | 64 | 128, T 1,024 and 8,192; the K|V-row
families' prefill (``kv_prefill``: 32 : 4 heads full and banded, 30 MHA
heads, timed alone beside the XLA body at 4,096 / 8,192 / 16,384 and over a
sweep of block shapes); the K|V-row read
of a per-layer family (``fused``: heads, pages a row, side window, stacked
layers: 30 MHA heads of 128, 8 rows of up to 6,144 positions, timed alone);
the latent rows' decode read of both MLA families (``latent``: 32 heads over
rows of 640 lanes, 8 rows of up to 8,704 positions in a 7-layer pool, timed
alone per pages a block); the grouped expert product alone at a
prefill's rows (``gmm_prefill``: the five served width pairs, 64-2,048 rows
an expert, few-rows tiles against K whole); the five int4
payload shapes of mistral-7b (N=32,768 for the lm_head) at M=128 (the
prefill bucket of ``ops.int4_matmul.blocks_for``) and, for the 2-D and
stacked legs, at M=8 (the decode bucket: what a served decode step runs). Tolerances are the ones the CPU parity tests use
for the same dtypes (``tests/test_int4_matmul.py``, ``test_flash_decode.py``).

Each kernel gets one outcome line — ``ok`` (compiled, ran, matched XLA),
``mismatch`` or ``refused`` with the compiler's message — and the table is
written to ``chiprun_out/chip_kernels.json`` after every kernel, so a crash
keeps what was learned. Exit 0 only when every kernel is ``ok``. The catch
around each check belongs to this harness: the kernels themselves have no
fallback, so a refusal here is a refusal in an engine that selects them.
Not part of the tier-1 CPU suite.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

FULL = dict(B=128, H=32, Hkv=8, Dh=128, P=128, ctx=256, W=8, M=128, L=2,
            int4_rows=(128, 8),
            int4_shapes=((2048, 6144), (2048, 4096), (2048, 28672),
                         (7168, 4096), (2048, 32768)),
            mla_dims=(128, 64, 128),
            mla_cases=((1024, 48), (1024, 1024), (8192, 48), (8192, 8192)),
            # the K|V-row families' prefill: (H, Hkv, window, T, length)
            # checked against the XLA body, then (H, Hkv, window, T, length)
            # timed alone beside it, then the (Q_BLOCK, K_BLOCK,
            # HEADS_PER_STEP) each timed shape is swept over
            kv_prefill=((32, 4, 0, 1024, 1024), (32, 4, 1024, 2048, 2048),
                        (32, 4, 1024, 8192, 4100), (32, 4, 0, 8192, 600),
                        (30, 30, 0, 1024, 700), (30, 30, 0, 4096, 4096)),
            kv_prefill_timed=((32, 4, 0, 4096, 4096), (32, 4, 0, 8192, 8192),
                              (32, 4, 0, 16384, 16384),
                              (32, 4, 0, 8192, 4100),
                              (32, 4, 1024, 4096, 4096),
                              (32, 4, 1024, 8192, 8192),
                              (32, 4, 1024, 16384, 16384),
                              (32, 4, 1024, 8192, 4100),
                              (30, 30, 0, 1024, 1024),
                              (30, 30, 0, 4096, 4096)),
            kv_prefill_sweep=((512, 512, 8), (512, 512, 4), (512, 256, 8),
                              (256, 512, 8), (256, 512, 4), (256, 256, 8),
                              (128, 512, 8), (256, 1024, 8), (128, 256, 8),
                              (1024, 512, 8)),
            fused=(30, 48, 16, 4),
            # (heads, rank, rope lanes, pages a row, side window, layers):
            # both latent-row families' MLA, the Xing cell's 7-layer pool
            latent=(32, 512, 64, 68, 16, 7),
            # (layers, heads, dk, dv, the gate's last axis): the Gated-
            # DeltaNet cell's state, the KDA cell's, and the KDA cell's
            # twice over (201 MB: alone in a program the cell's 101 MB can
            # sit in the v5e's 128 MiB of VMEM, and then reads above the
            # HBM peak)
            delta=((12, 30, 96, 192, 1), (6, 32, 128, 128, 128),
                   (12, 32, 128, 128, 128)),
            # the grouped expert product in a prefill: (D, F, experts a
            # layer) of Mellum, Keye, Xing, Ling (held) and Kimi (held); rows
            # an expert; the rows at which (row tile, N tile) is swept
            gmm_widths=((2304, 896, 64), (2048, 768, 128), (3584, 1024, 64),
                        (2560, 768, 128), (7168, 2048, 12)),
            gmm_rows=(64, 128, 256, 512, 1024, 2048), gmm_sweep_rows=512,
            gmm_row_tiles=(128, 256, 512))
TINY = dict(B=4, H=4, Hkv=2, Dh=64, P=8, ctx=16, W=4, M=16, L=2,
            int4_rows=(32, 3),
            int4_shapes=((128, 256), (256, 128)),
            mla_dims=(16, 8, 16), mla_cases=((1024, 48), (1024, 1024)),
            kv_prefill=((4, 2, 0, 1024, 700), (4, 2, 600, 1024, 1024),
                        (2, 2, 0, 512, 48)),
            kv_prefill_timed=((4, 2, 600, 1024, 700),),
            kv_prefill_sweep=((256, 512, 4),),
            fused=(2, 6, 4, 3), latent=(4, 32, 8, 6, 4, 2),
            delta=((3, 4, 16, 32, 1), (2, 4, 16, 16, 16)),
            gmm_widths=((256, 128, 4),), gmm_rows=(128, 256),
            gmm_sweep_rows=256, gmm_row_tiles=(128, 256))
OUT = os.path.join("chiprun_out", "chip_kernels.json")


def _close(got, ref, tol: float) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.max(np.abs(got - ref)))
    np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)
    return err


def _int4_inputs(cfg, k2, n, layers, rows=None):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.key(k2 + n), 3)
    packed = jax.random.randint(ks[0], (layers, k2, n), -128, 128,
                                dtype=jnp.int8)
    scale = jnp.full((layers, 1, n), 0.02 / 4.3, jnp.float32)
    x = jax.random.normal(ks[1], (rows or cfg["M"], 2 * k2),
                          jnp.float32).astype(jnp.bfloat16)
    return x, packed, scale


def _int4_ref(x, packed, scale, layer):
    """XLA path: ``ops.quant._einsum_int4`` on the same packed bytes."""
    from distributed_inference_engine_tpu.ops.quant import (
        QuantizedTensor,
        _einsum_int4,
    )

    w = QuantizedTensor(q=packed[layer], s=scale[layer], bits=4,
                        pack_axis=-2)
    return _einsum_int4("md,df->mf", x, w)


def check_int4_2d(cfg, interpret):
    from distributed_inference_engine_tpu.ops.int4_matmul import (
        _int4_matmul_2d,
    )

    errs = []
    for rows in cfg["int4_rows"]:
        for k2, n in cfg["int4_shapes"]:
            x, packed, scale = _int4_inputs(cfg, k2, n, 1, rows)
            got = _int4_matmul_2d(x, packed[0], scale[0, 0],
                                  interpret=interpret)
            errs.append(_close(got, _int4_ref(x, packed, scale, 0), 1e-2))
    return (f"{len(cfg['int4_shapes'])} shapes x rows {cfg['int4_rows']}, "
            f"max|err| {max(errs):.2e}")


def check_int4_stacked(cfg, interpret):
    import jax

    from distributed_inference_engine_tpu.ops.int4_matmul import (
        _int4_matmul_stacked,
    )

    errs = []
    layer = cfg["L"] - 1
    fn = jax.jit(lambda x, p, s, l: _int4_matmul_stacked(
        x, p, s, l, interpret=interpret))
    for rows in cfg["int4_rows"]:
        for k2, n in cfg["int4_shapes"]:
            x, packed, scale = _int4_inputs(cfg, k2, n, cfg["L"], rows)
            if not interpret:
                text = fn.lower(x, packed, scale, layer).as_text()
                assert "tpu_custom_call" in text, \
                    "stacked int4 matmul did not lower to the Mosaic " \
                    "custom call"
            got = fn(x, packed, scale, layer)
            errs.append(_close(got, _int4_ref(x, packed, scale, layer),
                               1e-2))
    return (f"{len(cfg['int4_shapes'])} shapes x rows {cfg['int4_rows']}, "
            f"max|err| {max(errs):.2e}")


def check_int4_cp(cfg, interpret):
    """The custom_partitioning wrapper under Shardy on a tp mesh over every
    visible device (up to 4): column-parallel (N sharded) and row-parallel
    (packed K sharded, psum) placements of each payload shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_inference_engine_tpu.ops.int4_matmul import _cp_stacked

    tp = min(4, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))
    cp = _cp_stacked(interpret)
    errs = []
    for k2, n in cfg["int4_shapes"]:
        x, packed, scale = _int4_inputs(cfg, k2, n, cfg["L"])
        layer = jnp.full((1,), cfg["L"] - 1, jnp.int32)
        ref = _int4_ref(x, packed, scale, cfg["L"] - 1)
        for name, pspec, sspec in (
                ("col", P(None, None, "tp"), P(None, None, "tp")),
                ("row", P(None, "tp", None), P())):
            pk = jax.device_put(packed, NamedSharding(mesh, pspec))
            sc = jax.device_put(scale, NamedSharding(mesh, sspec))
            got = jax.jit(cp)(x[:, :k2], x[:, k2:], pk, sc, layer)
            errs.append(_close(got, ref, 2e-2 if name == "row" else 1e-2))
    return (f"tp={tp}, {len(errs)} placements, "
            f"max|err| {max(errs):.2e}")


def _paged_inputs(cfg):
    import jax
    import jax.numpy as jnp

    b, h, hkv, dh, p = (cfg[k] for k in ("B", "H", "Hkv", "Dh", "P"))
    mp = cfg["ctx"] // p
    n = b * mp + 8
    ks = jax.random.split(jax.random.key(7), 8)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, h, dh), jnp.float32).astype(bf)
    kp = jax.random.normal(ks[1], (n, p, hkv * dh), jnp.float32).astype(bf)
    vp = jax.random.normal(ks[2], (n, p, hkv * dh), jnp.float32).astype(bf)
    # rows own DISJOINT pages (the engine invariant)
    pt = jax.random.permutation(ks[3], n)[: b * mp].reshape(b, mp)
    return q, kp, vp, pt.astype(jnp.int32), ks[4:], n


def _side_inputs(cfg, ks):
    import jax
    import jax.numpy as jnp

    b, hkv, dh, w = cfg["B"], cfg["Hkv"], cfg["Dh"], cfg["W"]
    sk = jax.random.normal(ks[0], (b, w, hkv, dh),
                           jnp.float32).astype(jnp.bfloat16)
    sv = jax.random.normal(ks[1], (b, w, hkv, dh),
                           jnp.float32).astype(jnp.bfloat16)
    plen = jax.random.randint(ks[2], (b,), 0, cfg["ctx"] - w + 1)
    return sk, sv, plen


def check_flash_decode(cfg, interpret):
    import jax

    from distributed_inference_engine_tpu.ops.flash_decode import (
        flash_decode_attention_pallas,
        flash_decode_attention_xla,
    )

    q, kp, vp, pt, ks, n = _paged_inputs(cfg)
    sk, sv, plen = _side_inputs(cfg, ks)
    n_side = jax.random.randint(ks[3], (cfg["B"],), 0, cfg["W"] + 1)
    ref = flash_decode_attention_xla(q, kp, vp, pt, plen, sk, sv, n_side,
                                     n_kv_heads=cfg["Hkv"])
    got = jax.jit(lambda *a: flash_decode_attention_pallas(
        *a, n_kv_heads=cfg["Hkv"], interpret=interpret, layer=0,
        n_pages_per_layer=n))(q, kp, vp, pt, plen, sk, sv, n_side)
    return f"max|err| {_close(got, ref, 2e-2):.2e}"


def check_flash_decode_served(cfg, interpret):
    """The served cells' row shape (8 rows, up to 8 pages a row, layer 1 of
    a stacked pool): dead rows, a one-token row, a row one short of a page,
    exactly a page, every page; each ``pages_per_block``."""
    import jax
    import jax.numpy as jnp

    from distributed_inference_engine_tpu.ops.flash_decode import (
        flash_decode_attention_pallas,
        flash_decode_attention_xla,
    )

    p, w, hkv = cfg["P"], cfg["W"], cfg["Hkv"]
    served = dict(cfg, B=8, ctx=8 * p)
    q, kp, vp, pt, ks, n = _paged_inputs(served)
    sk, sv, _ = _side_inputs(served, ks)
    kp2, vp2 = jnp.concatenate([vp, kp]), jnp.concatenate([kp, vp])
    plen = jnp.array([0, 1, p - 1, 0, p, 8 * p - w, 0, 3 * p + 5], jnp.int32)
    n_side = jnp.array([0, 1, w, 0, 2, w, 0, 5], jnp.int32)
    ref = flash_decode_attention_xla(q, kp, vp, pt, plen, sk, sv, n_side,
                                     n_kv_heads=hkv)
    errs = []
    for bp in (0, 1, 2, 8):
        got = jax.jit(lambda *a: flash_decode_attention_pallas(
            *a, n_kv_heads=hkv, interpret=interpret, layer=1,
            n_pages_per_layer=n, pages_per_block=bp))(
                q, kp2, vp2, pt, plen, sk, sv, n_side)
        errs.append(_close(got, ref, 2e-2))
        dead = (plen + n_side) == 0
        assert not bool(jnp.any(jnp.where(dead[:, None, None], got, 0))), \
            "a dead row's output is not zero"
    return f"4 block sizes, max|err| {max(errs):.2e}"


def check_flash_decode_kv_fused(cfg, interpret):
    """The per-layer family's read: ONE pool of K|V rows, 4 layers stacked
    (``kv_fused``), MHA (full: 30 heads of 128, 48 pages a row, 8 rows at
    the cell's contexts of 1,300-2,100 and then at 3,800-6,128, one dead row
    each time), layer 2, a side window of 16: against the XLA form on that
    layer's halves, the kernel's own page count against the lengths, and,
    on the chip, its time alone: a scan of calls, each fed the one before,
    on the host's clock."""
    import jax
    import jax.numpy as jnp

    from distributed_inference_engine_tpu.ops.flash_decode import (
        flash_decode_attention_pallas,
        flash_decode_attention_xla,
    )

    p, (h, mp, w, layers) = cfg["P"], cfg["fused"]
    dh, lanes = cfg["Dh"], cfg["fused"][0] * cfg["Dh"]
    shape = dict(cfg, B=8, H=h, Hkv=h, ctx=mp * p, W=w)
    q, kp, vp, pt, ks, n = _paged_inputs(shape)
    sk, sv, _ = _side_inputs(shape, ks)
    layer = layers - 2
    pool = jnp.zeros((layers * n, p, 2 * lanes), kp.dtype).at[
        layer * n:(layer + 1) * n].set(jnp.concatenate([kp, vp], -1))
    def call(q, pool, *rest):
        # the pool (3 GB at the full size) is an argument, never a constant
        return flash_decode_attention_pallas(
            q, pool, pool, *rest, n_kv_heads=h,
            interpret=interpret, layer=layer, n_pages_per_layer=n,
            kv_fused=True, count_pages=True)

    n_calls = 256

    @jax.jit
    def many(q, *args):
        def body(q, _):
            out, _pages = call(q, *args)
            return q + (out * 1e-3).astype(q.dtype), None
        return jax.lax.scan(body, q, None, length=n_calls)[0]

    details = []
    for name, shares in (
            ("cell", (0.22, 0.30, 0.0, 0.34, 0.25, 0.28, 0.21, 0.32)),
            ("long", (0.62, 0.81, 0.0, 1.0, 0.7, 0.9, 0.65, 0.75))):
        plen = (jnp.array(shares) * (mp * p - w)).astype(jnp.int32)
        n_side = jnp.where(plen > 0,
                           jnp.array([1, 5, 0, w, 9, 2, 16, 3]) % (w + 1),
                           0).astype(jnp.int32)
        ref = flash_decode_attention_xla(q, kp, vp, pt, plen, sk, sv, n_side,
                                         n_kv_heads=h)
        args = (pool, pt, plen, sk, sv, n_side)
        got, pages = jax.jit(call)(q, *args)
        err = _close(got, ref, 2e-2)
        live_pages = int(jnp.sum(-(-plen // p)))
        # under the interpreter the kernel's mutable scalars start every
        # grid step anew, so each later row's first block is issued (and
        # counted) again by its own turn; the chip's count is exact
        first_again = int(jnp.sum(jnp.minimum(-(-plen[1:] // p), 6))) \
            if interpret else 0
        detail = (f"{name}: max|err| {err:.2e}, {int(pages)} pages copied, "
                  f"{live_pages} live")
        if not interpret:
            many(q, *args).block_until_ready()
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                many(q, *args).block_until_ready()
                best = min(best, time.perf_counter() - t0)
            row = 2 * lanes * kp.dtype.itemsize
            live = row * int(jnp.sum(plen + n_side))
            moved = row * (live_pages * p + 8 * w)
            detail += (
                f", {1e6 * best / n_calls:.1f} us a call, live rows "
                f"{live / 1e6:.1f} MB = {live * n_calls / best / 1e9:.1f} "
                f"GB/s, copied {moved / 1e6:.1f} MB = "
                f"{moved * n_calls / best / 1e9:.1f} GB/s")
        details.append(detail)
        assert live_pages <= int(pages) <= live_pages + first_again, (
            "the kernel's count of page copies is not the lengths': "
            + "; ".join(details))
    return "; ".join(details)


# pages a block / pages a softmax update of the latent kernel's sweep
SWEEP = ((4, 1), (4, 2), (4, 4), (8, 2), (8, 4), (8, 8), (16, 4), (16, 8),
         (16, 16))


def check_latent_decode(cfg, interpret):
    """MLA's absorbed decode read in place (``ops/mla.py``
    ``mla_absorbed_decode_inplace`` over ``ops/flash_decode.py``'s latent
    kernel): 8 rows over ONE pool of every layer's pages (full: 7 layers x
    544 pages of 128 rows of 640 lanes, 624 MB), lengths drawn as the Xing
    cell's mix draws them (log-normal, median 3,072, sigma 0.7, 512-8,192,
    plus up to 256 decoded), 8 and 6 rows live: against
    ``mla_absorbed_decode`` on a layer's gathered pages, the kernel's own
    page count against the lengths, and, on the chip, its time alone swept
    over pages a block and pages a softmax update: a scan of calls that walks the layers, so the
    working set is the whole pool (one layer's live pages would sit in the
    v5e's VMEM-side cache and read above the HBM peak), on the host's
    clock, in us a call and % of 819 GB/s for the live rows' 1,152 B."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_inference_engine_tpu.ops import mla
    from distributed_inference_engine_tpu.ops.flash_decode import (
        _default_pages_per_block,
        latent_decode_attention_pallas,
    )

    p, (h, rank, dr, mp, w, layers) = cfg["P"], cfg["latent"]
    b, dn, dv = 8, cfg["mla_dims"][0], cfg["mla_dims"][2]
    lanes = -(-(rank + dr) // 128) * 128
    n = b * mp
    ks = jax.random.split(jax.random.key(38), 8)
    bf = jnp.bfloat16
    keep = (jnp.arange(lanes) < rank + dr)

    def rows(key, shape):
        return (jax.random.normal(key, (*shape, lanes), jnp.float32)
                * keep).astype(bf)

    pool = jax.jit(lambda k: rows(k, (layers * n, p)))(ks[0])
    side = rows(ks[1], (b, w))
    table = jax.random.permutation(ks[2], n).reshape(b, mp).astype(jnp.int32)
    qn = jax.random.normal(ks[3], (b, h, dn), jnp.float32).astype(bf)
    qr = jax.random.normal(ks[4], (b, h, dr), jnp.float32).astype(bf)
    w_kvb = (0.05 * jax.random.normal(ks[5], (rank, h, dn + dv))).astype(bf)
    scale = (dn + dr) ** -0.5
    rng = np.random.default_rng(38)
    cap = mp * p - w
    drawn = np.clip(np.exp(rng.normal(np.log(0.35 * cap), 0.7, b)),
                    0.06 * cap, 0.94 * cap) + rng.integers(0, cap // 32, b)
    n_calls = 7 * 32

    def timed(plen, n_side, bp, ap):
        @jax.jit
        def many(q, pool):
            def body(q, i):
                out, _ = latent_decode_attention_pallas(
                    q, pool, table, plen, side, n_side, i % layers,
                    v_lanes=rank, scale=scale, n_pages_per_layer=n,
                    pages_per_block=bp, pages_per_attend=ap)
                return q + (jnp.pad(out, ((0, 0), (0, 0), (0, lanes - rank)))
                            * 1e-3).astype(q.dtype), None
            return jax.lax.scan(body, q, jnp.arange(n_calls))[0]

        q = jnp.zeros((b, h, lanes), bf)
        many(q, pool).block_until_ready()
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            many(q, pool).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best / n_calls

    details = []
    layer = layers - 2
    for name, dead in (("8 live", ()), ("6 live", (2, 5))):
        plen = np.minimum(drawn.astype(np.int64), cap)
        plen[list(dead)] = 0
        if interpret:
            plen[1] = 1          # a one-token row, which the mix never draws
        plen = jnp.asarray(plen, jnp.int32)
        n_side = jnp.where(plen > 0,
                           jnp.array([1, 5, 0, w, 9, 2, 16, 3]) % (w + 1),
                           0).astype(jnp.int32)
        own = pool[layer * n + table].reshape(b, mp * p, lanes)
        ref = jax.jit(mla.mla_absorbed_decode, static_argnums=(7,))(
            qn, qr, w_kvb, own, plen, side, n_side, rank, scale)
        got, pages = jax.jit(
            lambda *a: mla.mla_absorbed_decode_inplace(
                *a, rank, scale=scale, n_pages_per_layer=n,
                interpret=interpret))(
            qn, qr, w_kvb, pool, table, layer, plen, side, n_side)
        live_rows = np.asarray((plen > 0) | (n_side > 0))
        err = _close(got[live_rows], ref[live_rows], 2e-2)
        live_pages = int(jnp.sum(-(-plen // p)))
        bp0 = _default_pages_per_block(p, lanes, mp)
        first_again = int(jnp.sum(jnp.minimum(-(-plen[1:] // p), bp0))) \
            if interpret else 0
        detail = (f"{name}: lengths {[int(x) for x in plen]}, max|err| "
                  f"{err:.2e}, {int(pages)} pages copied, {live_pages} live")
        assert live_pages <= int(pages) <= live_pages + first_again, (
            "the kernel's count of page copies is not the lengths': "
            + detail)
        if not interpret:
            live = (rank + dr) * 2 * int(jnp.sum(plen + n_side))
            moved = lanes * 2 * (live_pages * p + b * w)
            detail += (f", live rows {live / 1e6:.1f} MB, copied "
                       f"{moved / 1e6:.1f} MB; pages a block / pages a "
                       "softmax update -> us a call (% of 819 GB/s, live "
                       "rows):")
            for bp, ap in SWEEP:
                t = timed(plen, n_side, bp, ap)
                detail += (f" {bp}/{ap} -> {1e6 * t:.1f} "
                           f"({100 * live / t / 819e9:.1f} %)")
        details.append(detail)
    return " | ".join(details)


def check_mla_prefill(cfg, interpret):
    """The latent-attention prefill kernel at the published head shape (32
    heads of 128 | 64 | 128) against the XLA body: the smallest and the
    largest whole-block buckets, a prompt inside the first block and one
    that fills the bucket; rows below ``seq_lens`` compared."""
    import jax
    import jax.numpy as jnp

    from distributed_inference_engine_tpu.ops import mla

    h, (dn, dr, dv) = cfg["H"], cfg["mla_dims"]
    errs = []
    for t, n in cfg["mla_cases"]:
        ks = jax.random.split(jax.random.key(t + n), 4)
        qn, qr, kv = (jax.random.normal(k, (1, t, h, d), jnp.bfloat16)
                      for k, d in zip(ks, (dn, dr, dn + dv)))
        kr = jax.random.normal(ks[3], (1, t, dr), jnp.bfloat16)
        lens = jnp.asarray([n], jnp.int32)
        got, ref = (jax.jit(lambda *a, impl=impl: mla.mla_causal_attention(
            *a, impl=impl))(qn, qr, kv, kr, lens)
            for impl in ("flash_interpret" if interpret else "flash", "xla"))
        errs.append(_close(got[:, :n], ref[:, :n], 2e-2))
        assert not bool(jnp.any(got[:, -(-n // mla.Q_BLOCK) * mla.Q_BLOCK:])), \
            "a query block past the prompt is not zero"
    return f"{len(errs)} cases, max|err| {max(errs):.2e}"


def check_kv_prefill(cfg, interpret):
    """The K|V-row families' prefill kernel (``ops/flash_prefill.py``) at the
    sliding-window family's head shape (32 : 4 heads of 128, full and with
    its window of 1,024) and the Gated-DeltaNet family's (30 MHA heads),
    one row, bfloat16, against the XLA body: whole buckets and a short row
    in a long bucket, rows below ``seq_lens`` compared and the query blocks
    past them zeros; then its time alone beside the XLA body's (ms a call,
    the host's clock over calls ended by ``block_until_ready``), at the
    blocks the served path resolves and over ``kv_prefill_sweep``."""
    import jax
    import jax.numpy as jnp

    from distributed_inference_engine_tpu.ops import flash_prefill as fp

    dh = cfg["Dh"]
    flash = "flash_interpret" if interpret else "flash"

    def inputs(h, hkv, t, n):
        ks = jax.random.split(jax.random.key(h + t + n), 2)
        q = jax.random.normal(ks[0], (1, t, h, dh), jnp.bfloat16)
        rows = jax.random.normal(ks[1], (1, t, 2 * hkv * dh), jnp.bfloat16)
        # values a quarter as large: outputs of magnitude <= 1
        rows = rows.at[..., hkv * dh:].multiply(0.25)
        return q, rows, jnp.asarray([n], jnp.int32)

    def ms_a_call(fn, args, n):
        jax.block_until_ready(fn(*args))
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t0) / n

    def body(hkv, window, impl):
        return jax.jit(lambda *a: fp.kv_prefill_attention(
            *a, hkv, window=window, impl=impl))

    errs = []
    for h, hkv, window, t, n in cfg["kv_prefill"]:
        args = inputs(h, hkv, t, n)
        got, ref = (body(hkv, window, impl)(*args)
                    for impl in (flash, "xla"))
        errs.append(_close(got[:, :n], ref[:, :n], 2e-2))
        assert not bool(jnp.any(got[:, -(-n // fp.Q_BLOCK) * fp.Q_BLOCK:])), \
            "a query block past the prompt is not zero"
    detail = f"{len(errs)} cases, max|err| {max(errs):.2e}"
    if interpret:
        detail += " (interpreted: the times below are the host's, of the " \
            "control flow)"
    reps = 1 if interpret else 10
    for h, hkv, window, t, n in cfg["kv_prefill_timed"]:
        args = inputs(h, hkv, t, n)
        visited, square = fp.prefill_key_blocks(n, t, window)
        line = (f"{h}:{hkv} window {window} T {t} length {n} (blocks "
                f"{visited} of {square}): xla "
                f"{ms_a_call(body(hkv, window, 'xla'), args, 3):.3f} ms, "
                f"kernel {ms_a_call(body(hkv, window, flash), args, reps):.3f}"
                f" ms at {fp.Q_BLOCK}/{fp.K_BLOCK}/{fp.HEADS_PER_STEP}")
        for bq, bk, hb in cfg["kv_prefill_sweep"]:
            if t % bq or t % bk:
                continue
            fn = jax.jit(lambda q, rows, lens, bq=bq, bk=bk, hb=hb:
                         fp._flash_prefill(
                             q.reshape(1, t, h * dh), rows, lens,
                             n_kv_heads=hkv, window=window, bq=bq, bk=bk,
                             heads_per_step=hb, interpret=interpret))
            try:
                line += f"; {bq}/{bk}/{hb} {ms_a_call(fn, args, reps):.3f}"
            except Exception as e:    # a refused block shape: record, go on
                line += f"; {bq}/{bk}/{hb} refused ({str(e)[:80]})"
        print("kv_prefill: " + line, flush=True)
        detail += " | " + line
    return detail


def check_kda_step_inplace(cfg, interpret):
    """The delta rule's decode step over the engine's whole state array, in
    place (``ops/kda.py`` ``kda_step_inplace``), at the Gated-DeltaNet
    cell's shape (12 layers of 8 x 30 states of 96 x 192, two heads side by
    side along the lanes as the engine keeps them, one decay a head, the
    layer a traced index) and the KDA cell's (6 of 8 x 32 of 128 x 128,
    one a key channel): ``o`` and the live states against ``kda_step``,
    the dead slots' and every other layer's states bit-equal; on the chip
    its time alone, a scan of calls that walks the layers, each fed the one
    before, on the host's clock, all 8 slots live and then 6 of 8, beside
    the XLA body (``kda_step`` on the layer's leaf, a select, the leaf
    written where it lies; it un-packs and re-packs a lane-packed state,
    which the CPU alone serves with)."""
    import jax
    import jax.numpy as jnp

    from distributed_inference_engine_tpu.ops import kda

    b, n_calls = 8, 256
    kernel = "inplace_interpret" if interpret else "inplace"

    def many(step):
        @jax.jit
        def run(S_all, q, k, v, g, beta, active):
            def body(carry, i):
                S_all, v = carry
                o, S_all = step(S_all, i % S_all.shape[0], q, k, v, g, beta,
                                active)
                return (S_all, v + 1e-3 * o), None
            return jax.lax.scan(body, (S_all, v), jnp.arange(n_calls))[0]
        return run

    details = []
    for nl, h, dk, dv, dg in cfg["delta"]:
        ks = jax.random.split(jax.random.key(dk), 6)
        m = kda.lane_pack(h, dv)
        S_all = kda.pack_states(
            jax.random.normal(ks[0], (nl, b, h, dk, dv), jnp.float32), m)
        q = kda.l2_normalize(jax.random.normal(ks[1], (b, h, dk))) * dk ** -.5
        k = kda.l2_normalize(jax.random.normal(ks[2], (b, h, dk)))
        v = jax.random.normal(ks[3], (b, h, dv), jnp.float32)
        g = -2.0 * jax.random.uniform(ks[4], (b, h, dg), jnp.float32)
        beta = 2.0 * jax.random.uniform(ks[5], (b, h), jnp.float32)
        layer = nl - 2
        detail = f"{nl} x {b} x {h} x {dk} x {dv}, {m} a row, gate {dg}"
        for active in (jnp.ones((b,), bool),
                       jnp.arange(b) % 4 != 2, jnp.zeros((b,), bool)):
            n_live = int(active.sum())
            ref_o, ref_S = kda.kda_step(kda.unpack_states(S_all[layer], m),
                                        q, k, v, g, beta)
            ref_S = kda.pack_states(ref_S, m)
            got_o, got = jax.jit(
                lambda *a: kda.kda_step_inplace(*a, impl=kernel))(
                    S_all, jnp.int32(layer), q, k, v, g, beta, active)
            err = max(_close(got_o[active], ref_o[active], 1e-5),
                      _close(got[layer][active], ref_S[active], 1e-5)) \
                if n_live else 0.0
            others = jnp.arange(nl) != layer
            assert bool(jnp.all(got[others] == S_all[others])), \
                "another layer's states moved"
            assert bool(jnp.all(got[layer][~active]
                                == S_all[layer][~active])), \
                "a dead slot's state moved"
            detail += f"; {n_live} live: max|err| {err:.1e}"
            if interpret or not n_live:
                continue
            moved = 2 * n_live * h * dk * dv * 4
            for name, step in (
                    ("kernel", lambda *a: kda.kda_step_inplace(
                        *a, impl="inplace")),
                    ("xla body", lambda *a: kda.kda_step_inplace(
                        *a, impl="xla"))):
                run = many(step)
                args = (q, k, v, g, beta, active)
                jax.block_until_ready(run(S_all + 0.0, *args))
                best = float("inf")
                for _ in range(5):
                    fresh = S_all + 0.0        # the scan's carry is donated
                    jax.block_until_ready(fresh)
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(fresh, *args))
                    best = min(best, time.perf_counter() - t0)
                detail += (f", {name} {1e6 * best / n_calls:.1f} us a call = "
                           f"{100 * moved * n_calls / best / 819e9:.1f} % of "
                           f"819 GB/s for {moved / 1e6:.1f} MB")
        details.append(detail)
    return " | ".join(details)


def check_gmm_prefill(cfg, interpret):
    """The grouped expert product (``ops/moe_routed.py`` ``grouped_matmul``,
    jax's Mosaic ``megablox.gmm``) ALONE at a prefill's rows: for each
    served width pair ``(D, F, experts)`` the gate|up product ``[M, D] x
    [E, D, 2F]`` and the down product ``[M, F] x [E, F, D]``, M = rows an
    expert x E sorted rows dealt to the experts by a multinomial draw,
    bfloat16 in, float32 out. First the tiles ``gmm_tiling`` resolves
    against a plain product of three experts' rows; then us a call (the host's
    clock over calls ended by ``block_until_ready``) and the share of 197
    TFLOP/s for the rows' own operations, under the few-rows tiles (what
    every call ran before PR 52), the tiles resolved for these rows, K
    whole with the widest N tile inside the budget at each row tile whatever
    the rows (where it crosses the few-rows tiles is what
    ``GMM_PREFILL_ROWS`` is set against), and at ``gmm_sweep_rows`` every
    (row tile, N tile) the scoped VMEM could hold with K whole."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from distributed_inference_engine_tpu.ops import moe_routed as mr

    rng = np.random.default_rng(52)
    reps = 1 if interpret else 5

    def inputs(m, k, n, e):
        ks = jax.random.split(jax.random.key(k + n + m), 2)
        lhs = jax.random.normal(ks[0], (m, k), jnp.bfloat16)
        rhs = 0.02 * jax.random.normal(ks[1], (e, k, n), jnp.bfloat16)
        sizes = jnp.asarray(rng.multinomial(m, np.full(e, 1 / e)), jnp.int32)
        return lhs, rhs, sizes

    def us_a_call(tiles, args):
        fn = jax.jit(lambda *a: gmm(*a, jnp.float32, tiles,
                                    interpret=interpret))
        jax.block_until_ready(fn(*args))
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return 1e6 * (time.perf_counter() - t0) / reps

    errs, lines = [], []
    for d, f, e in cfg["gmm_widths"]:
        for name, (k, n) in (("gate|up", (d, 2 * f)), ("down", (f, d))):
            # parity at the resolved prefill tiles: three experts' rows
            # against a plain product (``ragged_dot`` on the CPU only: what
            # XLA makes of it on a TPU at these sizes is not this leg's)
            m = cfg["gmm_rows"][-1] * e
            args = inputs(m, k, n, e)
            got = jax.jit(lambda *a: mr.grouped_matmul(
                *a, "gmm_interpret" if interpret else "gmm", e))(*args)
            ends = np.cumsum(np.asarray(args[2]))
            for g in (0, e // 2, e - 1):
                rows_g = slice(int(ends[g] - args[2][g]), int(ends[g]))
                ref = jnp.dot(args[0][rows_g], args[1][g],
                              preferred_element_type=jnp.float32)
                errs.append(_close(got[rows_g], ref, 2e-2))
            del got, ref
            for rows in cfg["gmm_rows"]:
                m = rows * e
                args = inputs(m, k, n, e)
                flop = 2.0 * m * k * n
                cands = [("few-rows", mr.gmm_tiling(128, k, n, 1)),
                         ("resolved", mr.gmm_tiling(m, k, n, e))]
                cands += [("k-whole", (tm, k, mr.gmm_widest_n_tile(tm, k, n)))
                          for tm in cfg["gmm_row_tiles"] if m % tm == 0]
                if rows == cfg["gmm_sweep_rows"]:
                    cands += [
                        ("sweep", (tm, k, tn))
                        for tm in cfg["gmm_row_tiles"]
                        for tn in range(n, 255, -128)
                        if n % tn == 0 and m % tm == 0 and
                        mr.gmm_vmem_bytes(tm, k, tn) <= mr.GMM_SCOPED_VMEM]
                line = (f"D {d} F {f} E {e} {name} [{m}, {k}] x [{k}, {n}], "
                        f"{rows} rows an expert:")
                timed = set()
                for tag, t in cands:
                    if t in timed or not t[2]:   # no N tile beside K whole
                        continue
                    timed.add(t)
                    try:
                        us = us_a_call(t, args)
                        line += (f" {tag} {t} {us:.0f} us "
                                 f"{100 * flop / us / 197e6:.1f} %;")
                    except Exception as exc:   # refused tiles: record, go on
                        line += f" {tag} {t} refused ({str(exc)[-60:]!r});"
                print("gmm_prefill: " + line, flush=True)
                lines.append(line)
    detail = (f"{len(errs)} experts' rows at the resolved tiles against a "
              f"plain product, max|err| {max(errs):.2e}")
    if interpret:
        detail += " (interpreted: the times are the host's)"
    with open(os.path.join("chiprun_out", "gmm_prefill.txt"), "w") as fh:
        fh.write("\n".join([detail] + lines) + "\n")
    return detail + " | " + " | ".join(lines)


# name -> (check, on the default serving path?)
CHECKS = {
    "int4_matmul_2d": (check_int4_2d, True),
    "int4_matmul_stacked": (check_int4_stacked, True),
    "int4_matmul_cp": (check_int4_cp, True),
    "flash_decode": (check_flash_decode, True),
    "flash_decode_served": (check_flash_decode_served, True),
    "flash_decode_kv_fused": (check_flash_decode_kv_fused, True),
    "latent_decode": (check_latent_decode, True),
    "mla_prefill": (check_mla_prefill, True),
    "kv_prefill": (check_kv_prefill, True),
    "kda_step_inplace": (check_kda_step_inplace, True),
    "gmm_prefill": (check_gmm_prefill, True),
}


def _message(exc: BaseException) -> str:
    """The compiler's own words: the first lines of the exception text
    (Mosaic errors lead with the failing op and reason), capped."""
    lines = [ln for ln in str(exc).splitlines() if ln.strip()]
    return f"{type(exc).__name__}: " + " | ".join(lines[:6])[:1200]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="tiny shapes for a CPU (interpret-mode) debug run")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: " + ",".join(CHECKS))
    args = ap.parse_args(argv)

    from distributed_inference_engine_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    import jax

    dev = jax.devices()
    platform = dev[0].platform
    interpret = platform == "cpu"
    cfg = TINY if args.tiny else FULL
    if interpret and not args.tiny:
        print("chip_kernels: the full shapes compile for the chip; on the "
              "cpu backend pass --tiny", flush=True)
        return 2
    print(f"chip_kernels: platform={platform} "
          f"device_kind={dev[0].device_kind!r} n_devices={len(dev)} "
          f"interpret={interpret} shapes={'tiny' if args.tiny else 'full'}",
          flush=True)
    names = [n for n in args.only.split(",") if n] or list(CHECKS)
    unknown = [n for n in names if n not in CHECKS]
    if unknown:
        print(f"chip_kernels: unknown kernel(s) {unknown}", flush=True)
        return 2
    rows = []
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    for name in names:
        fn, default_path = CHECKS[name]
        t0 = time.perf_counter()
        try:
            detail = fn(cfg, interpret)
            outcome = "ok"
        except AssertionError as e:
            outcome, detail = "mismatch", _message(e)
        except Exception as e:   # harness boundary: record, go on, fail below
            outcome, detail = "refused", _message(e)
            traceback.print_exc()
        row = {"kernel": name, "outcome": outcome, "detail": detail,
               "default_path": default_path,
               "seconds": round(time.perf_counter() - t0, 1),
               "platform": platform, "device_kind": dev[0].device_kind,
               "n_devices": len(dev), "interpret": interpret}
        rows.append(row)
        print(f"kernel {name}: {outcome} ({row['seconds']}s) {detail}",
              flush=True)
        with open(OUT, "w") as f:
            json.dump(rows, f, indent=1)
    bad = [r["kernel"] for r in rows if r["outcome"] != "ok"]
    print(f"chip_kernels: {len(rows) - len(bad)}/{len(rows)} ok"
          + (f"; not ok: {bad}" if bad else ""), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
