"""Block-size tuning sweep for the stacked Mosaic int4 kernel on hardware
(r5, decode_profile.md "stream efficiency" lever: the kernel ran its
packed stream at ~510 GB/s, 62% of the 819 GB/s v5e peak).

Measurement discipline: a host clock around one dispatch measures the
dispatch and the result fetch as much as the kernel. So each config is
timed as a DEVICE-side ``lax.scan`` over all L layers x P passes inside
ONE jit returning one scalar, at two pass counts; the difference cancels
the dispatch + round-trip constant:

    per-layer-us = (t(2P) - t(P)) / (P * L)

Prints one JSON row per (shape, bk, bn) with achieved GB/s on the packed
stream. The defaults in ``ops/int4_matmul.py`` (``_K_BLOCKS``/
``_N_BLOCKS`` preference order) should be the winners printed here.

    python examples/int4_kernel_tune.py            # decode tile (M=64)
    BENCH_M=128 python examples/int4_kernel_tune.py
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

from distributed_inference_engine_tpu.ops.int4_matmul import (
    _int4_matmul_stacked,
)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


# 8B decode shapes: (name, L, K, N) — the r5 FUSED shapes (qkv N=6144,
# gate+up N=28672) plus wo / w_down and the vocab-PADDED lm_head
# (128256 → 129024 = 2048·63; the raw width tiles only at bn=256).
SHAPES = [
    ("qkv_fused", 32, 4096, 6144),
    ("wo", 32, 4096, 4096),
    ("gate_up_fused", 32, 4096, 28672),
    ("w_down", 32, 14336, 4096),
    ("lm_head_padded", 1, 4096, 129024),
]
BKS = (2048, 1024, 512)
BNS = (4096, 2048, 1024)
M = int(os.environ.get("BENCH_M", "64"))
PASSES = int(os.environ.get("BENCH_PASSES", "24"))


@functools.partial(jax.jit, static_argnames=("bk", "bn", "passes"))
def _loop(x, packed, scale, *, bk, bn, passes):
    """passes x L sequential kernel calls on-device; scalar out."""
    nl = packed.shape[0]

    def body(acc, l):
        y = _int4_matmul_stacked(x, packed, scale, l, bk=bk, bn=bn)
        # fold a few output elements into the carry: the scan carry is the
        # data dependency that keeps XLA from reordering/eliding calls
        return acc + y[0, :8].astype(jnp.float32).sum(), None

    acc, _ = jax.lax.scan(body, jnp.float32(0.0),
                          jnp.tile(jnp.arange(nl, dtype=jnp.int32), passes))
    return acc


def _timed(x, packed, scale, bk, bn, passes):
    t0 = time.perf_counter()
    v = _loop(x, packed, scale, bk=bk, bn=bn, passes=passes)
    float(v)                       # scalar fetch = the only sync point
    return time.perf_counter() - t0


def main():
    log(f"devices: {jax.devices()}  M={M}  passes={PASSES}")
    key = jax.random.key(0)
    best = {}
    for name, nl, k, n in SHAPES:
        k2 = k // 2
        kq, kx = jax.random.split(jax.random.fold_in(key, hash(name) % 97))
        packed = jax.random.randint(kq, (nl, k2, n), -128, 128, jnp.int8)
        scale = jnp.full((nl, 1, n), 1e-3, jnp.float32)
        x = jax.random.normal(kx, (M, k), jnp.bfloat16)
        for bk in BKS:
            if k2 % bk:
                continue
            for bn in BNS:
                if n % bn:
                    continue
                try:
                    _timed(x, packed, scale, bk, bn, PASSES)   # compile
                    _timed(x, packed, scale, bk, bn, 2 * PASSES)
                    t1 = _timed(x, packed, scale, bk, bn, PASSES)
                    t2 = _timed(x, packed, scale, bk, bn, 2 * PASSES)
                except Exception as e:   # untileable/VMEM: record, move on
                    log(f"{name} bk={bk} bn={bn}: FAIL {type(e).__name__}: "
                        f"{str(e)[:120]}")
                    continue
                dt = max(t2 - t1, 1e-9) / (PASSES * nl)   # overhead cancels
                gbps = (k2 * n) / dt / 1e9
                row = {"shape": name, "bk": bk, "bn": bn, "M": M,
                       "us_per_layer": round(dt * 1e6, 1),
                       "packed_gbps": round(gbps, 1),
                       "pct_peak": round(gbps / 819.0, 3)}
                print(json.dumps(row), flush=True)
                cur = best.get(name)
                if cur is None or gbps > cur[2]:
                    best[name] = (bk, bn, gbps)
    log("--- best per shape ---")
    for name, (bk, bn, gbps) in best.items():
        log(f"{name}: bk={bk} bn={bn} {gbps:.0f} GB/s "
            f"({gbps / 819.0:.0%} of peak)")


if __name__ == "__main__":
    main()
