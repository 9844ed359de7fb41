"""pages_per_block tuning sweep for the fused flash-decode kernel on
hardware (ops/flash_decode.py). The knob trades DMA batching (more pages
in flight per issue, deeper latency hiding) against VMEM scratch
(2 x bp x P x fused x dtype per K and V) and tail waste on short rows.

Measurement discipline follows examples/int4_kernel_tune.py: a host
clock around one dispatch measures the dispatch and the result fetch as
much as the kernel, so each config is timed as a DEVICE-side ``lax.scan``
over L layers x P passes inside ONE jit returning one scalar, at two pass
counts; the difference cancels the dispatch + round-trip constant:

    per-layer-us = (t(2P) - t(P)) / (P * L)

Prints one JSON row per (ctx, pages_per_block) with the achieved KV-read
GB/s. Feed the winners into ``_TUNED_PAGES_PER_BLOCK`` in
``ops/flash_decode.py`` (keyed by (page_size, fused)).

    python examples/flash_decode_tune.py                  # 8B serving shape
    BENCH_BATCH=64 BENCH_CTX=512 python examples/flash_decode_tune.py
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_inference_engine_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()

import jax
import jax.numpy as jnp

from distributed_inference_engine_tpu.ops.flash_decode import (
    flash_decode_attention_pallas,
)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


# 8B flagship decode shape: 32 q heads / 8 kv heads x 128 -> fused = 1024,
# page_size = 128 (bench.py), bs 128, fp8 KV pools + bf16 activations.
B = int(os.environ.get("BENCH_BATCH", "128"))
H = int(os.environ.get("BENCH_HEADS", "32"))
HKV = int(os.environ.get("BENCH_KV_HEADS", "8"))
DH = int(os.environ.get("BENCH_HEAD_DIM", "128"))
PAGE = int(os.environ.get("BENCH_PAGE", "128"))
W = int(os.environ.get("BENCH_WINDOW", "16"))        # decode_steps_per_call
L = int(os.environ.get("BENCH_LAYERS", "32"))
CTXS = [int(c) for c in os.environ.get("BENCH_CTX", "512,1024,2048").split(",")]
KV_DTYPE = jnp.dtype(os.environ.get("BENCH_KV_DTYPE", "float8_e4m3fn"))
PASSES = int(os.environ.get("BENCH_PASSES", "16"))
BPS = [int(x) for x in os.environ.get("BENCH_BP", "1,2,4,8").split(",")]
PEAK_GBPS = 819.0                                    # v5e HBM


@functools.partial(jax.jit, static_argnames=("bp", "passes", "n_pages"))
def _loop(q, kp, vp, pt, plen, sk, sv, n_side, *, bp, passes, n_pages):
    """passes x L sequential kernel calls on-device; scalar out."""

    def body(acc, l):
        y = flash_decode_attention_pallas(
            q, kp, vp, pt, plen, sk, sv, n_side, n_kv_heads=HKV,
            layer=l, n_pages_per_layer=n_pages, pages_per_block=bp)
        # fold a few output elements into the carry: the scan carry is the
        # data dependency that keeps XLA from reordering/eliding calls
        return acc + y[0, 0, :8].astype(jnp.float32).sum(), None

    acc, _ = jax.lax.scan(body, jnp.float32(0.0),
                          jnp.tile(jnp.arange(L, dtype=jnp.int32), passes))
    return acc


def _timed(args, bp, n_pages, passes):
    t0 = time.perf_counter()
    v = _loop(*args, bp=bp, passes=passes, n_pages=n_pages)
    float(v)                       # scalar fetch = the only sync point
    return time.perf_counter() - t0


def main():
    fused = HKV * DH
    log(f"devices: {jax.devices()}  B={B} H={H}/{HKV} Dh={DH} "
        f"page={PAGE} kv={KV_DTYPE.name} passes={PASSES}")
    key = jax.random.key(0)
    best = {}
    for ctx in CTXS:
        mp = -(-ctx // PAGE)
        n_pages = B * mp + 8
        ks = jax.random.split(jax.random.fold_in(key, ctx), 6)
        q = jax.random.normal(ks[0], (B, H, DH), jnp.bfloat16)
        kp = jax.random.normal(ks[1], (L * n_pages, PAGE, fused),
                               jnp.float32).astype(KV_DTYPE)
        vp = jax.random.normal(ks[2], (L * n_pages, PAGE, fused),
                               jnp.float32).astype(KV_DTYPE)
        pt = jax.random.randint(ks[3], (B, mp), 0, n_pages, jnp.int32)
        plen = jnp.full((B,), ctx, jnp.int32)
        sk = jax.random.normal(ks[4], (B, W, HKV, DH), jnp.bfloat16)
        sv = jax.random.normal(ks[5], (B, W, HKV, DH), jnp.bfloat16)
        n_side = jnp.full((B,), W // 2, jnp.int32)
        args = (q, kp, vp, pt, plen, sk, sv, n_side)
        # bytes the kernel must stream per call: every live page of K and V
        kv_bytes = 2 * B * mp * PAGE * fused * KV_DTYPE.itemsize
        for bp in BPS:
            try:
                _timed(args, bp, n_pages, PASSES)     # compile
                _timed(args, bp, n_pages, 2 * PASSES)
                t1 = _timed(args, bp, n_pages, PASSES)
                t2 = _timed(args, bp, n_pages, 2 * PASSES)
            except Exception as e:   # VMEM overflow etc: record, move on
                log(f"ctx={ctx} bp={bp}: FAIL {type(e).__name__}: "
                    f"{str(e)[:120]}")
                continue
            dt = max(t2 - t1, 1e-9) / (PASSES * L)    # overhead cancels
            gbps = kv_bytes / dt / 1e9
            row = {"ctx": ctx, "pages_per_block": bp, "B": B,
                   "page_size": PAGE, "fused": fused,
                   "us_per_layer": round(dt * 1e6, 1),
                   "kv_gbps": round(gbps, 1),
                   "pct_peak": round(gbps / PEAK_GBPS, 3)}
            print(json.dumps(row), flush=True)
            cur = best.get(ctx)
            if cur is None or gbps > cur[1]:
                best[ctx] = (bp, gbps)
    log("--- best per ctx ---")
    for ctx, (bp, gbps) in best.items():
        log(f"ctx={ctx}: pages_per_block={bp} {gbps:.0f} GB/s "
            f"({gbps / PEAK_GBPS:.0%} of peak)")


if __name__ == "__main__":
    main()
