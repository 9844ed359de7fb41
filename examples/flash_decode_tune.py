"""The flash-decode kernel (ops/flash_decode.py) on hardware, at the shape
the served cells run: 8 rows, GQA 32:8 x 128, 128-token pages, 1-8 pages a
row, lengths drawn from the chat mix with 2-8 rows live.

Two measurements, each its own mode (both on one chip, one process):

``kernel`` (default)
    One layer's decode attention, per ``pages_per_block``, against what it
    replaces on the dense XLA path: the per-layer slice of the dense
    ``[L, B, S, Hkv, Dh]`` context, ``cached_attention`` over it and the
    new row's dynamic-update-slice (``models.base.forward_decode``'s K/V
    part). Each is timed as a DEVICE-side ``lax.scan`` over L layers x P
    passes inside ONE jit returning one scalar, at two pass counts; the
    difference cancels the dispatch + round-trip constant:

        per-layer-us = (t(2P) - t(P)) / (P * L)

    Feed the winner into ``_TUNED_PAGES_PER_BLOCK`` (keyed by
    (page_size, fused)).

``step``
    One whole decode step of mistral-7b int4 through ``ContinuousEngine``
    on both paths (``attention_impl="xla"`` and ``"pallas-decode"``, the
    same weights): rows admitted at the drawn lengths, then decode chunks
    timed dispatch to harvest; ms per step = chunk seconds / steps.

    python examples/flash_decode_tune.py              # kernel sweep
    python examples/flash_decode_tune.py step         # engine step A/B
    BENCH_BATCH=64 BENCH_CTX=2048 python examples/flash_decode_tune.py

Rows are written as JSON lines to stdout and to
``chiprun_out/flash_decode_tune.jsonl``.
"""

import functools
import json
import math
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_inference_engine_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()

import jax
import jax.numpy as jnp
from jax import lax

from distributed_inference_engine_tpu.ops.attention import cached_attention
from distributed_inference_engine_tpu.ops.flash_decode import (
    flash_decode_attention_pallas,
)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


B = int(os.environ.get("BENCH_BATCH", "8"))
H = int(os.environ.get("BENCH_HEADS", "32"))
HKV = int(os.environ.get("BENCH_KV_HEADS", "8"))
DH = int(os.environ.get("BENCH_HEAD_DIM", "128"))
PAGE = int(os.environ.get("BENCH_PAGE", "128"))
W = int(os.environ.get("BENCH_WINDOW", "8"))         # decode_steps_per_call
L = int(os.environ.get("BENCH_LAYERS", "32"))
CTX = int(os.environ.get("BENCH_CTX", "1024"))       # max_seq_len
KV_DTYPE = jnp.dtype(os.environ.get("BENCH_KV_DTYPE", "bfloat16"))
PASSES = int(os.environ.get("BENCH_PASSES", "16"))
BPS = [int(x) for x in os.environ.get("BENCH_BP", "1,2,4,8").split(",")]
LIVE = [n for n in (2, 4, 6, 8) if n <= B]           # rows live of B
PEAK_GBPS = 819.0                                    # v5e HBM
# off the chip the kernel only interprets: a rehearsal of the control
# flow at a tiny size (and a tiny model), never a timing
INTERPRET = jax.default_backend() == "cpu"
ARCH, SIZE = (("llama", "llama-tiny") if INTERPRET
              else ("mistral", "mistral-7b"))
N_CHUNKS = 3 if INTERPRET else 12                    # timed chunks a row set
OUT = os.path.join("chiprun_out", "flash_decode_tune.jsonl")


def chat_lengths(n_live: int, seed: int):
    """Context lengths of ``n_live`` requests of the chat mix somewhere in
    their decode (perfbench/traffic: prompt log-normal median 256, sigma
    0.8, clipped 32-768; output median 128, sigma 0.6, clipped 16-256; a
    request is caught uniformly along its output), dead rows 0."""
    rng = random.Random(seed)
    lens = []
    for _ in range(n_live):
        prompt = min(768, max(32, round(math.exp(
            rng.gauss(math.log(256), 0.8)))))
        out = min(256, max(16, round(math.exp(
            rng.gauss(math.log(128), 0.6)))))
        n = prompt + int(rng.random() * out)
        # a tiny rehearsal (BENCH_CTX under 1024) keeps the mix's shape
        lens.append(max(1, min(CTX - 2 * W, n * CTX // 1024)))
    lens += [0] * (B - n_live)
    rng.shuffle(lens)
    return lens


def emit(row):
    print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(row) + "\n")


# ---------------------------------------------------------------- kernel


@functools.partial(jax.jit, static_argnames=("bp", "passes", "n_pages"))
def _kernel_loop(q, kp, vp, pt, plen, sk, sv, n_side, *, bp, passes,
                 n_pages):
    """passes x L sequential kernel calls on-device; scalar out."""

    def body(acc, l):
        y = flash_decode_attention_pallas(
            q, kp, vp, pt, plen, sk, sv, n_side, n_kv_heads=HKV,
            layer=l, n_pages_per_layer=n_pages, pages_per_block=bp,
            interpret=INTERPRET)
        # fold a few output elements into the carry: the scan carry is the
        # data dependency that keeps XLA from reordering/eliding calls
        return acc + y[0, 0, :8].astype(jnp.float32).sum(), None

    acc, _ = lax.scan(body, jnp.float32(0.0),
                      jnp.tile(jnp.arange(L, dtype=jnp.int32), passes))
    return acc


@functools.partial(jax.jit, static_argnames=("passes",), donate_argnums=(1, 2))
def _dense_loop(q, ck, cv, lengths, k_new, v_new, *, passes):
    """What the kernel replaces, as ``forward_decode`` does it: slice the
    layer out of the dense context, write the new row, attend, put the
    slice back. ck/cv [L, B, S, Hkv, Dh]."""
    b = q.shape[0]
    bi = jnp.arange(b)

    def body(carry, l):
        acc, ck, cv = carry
        k_l = lax.dynamic_index_in_dim(ck, l, 0, keepdims=False)
        v_l = lax.dynamic_index_in_dim(cv, l, 0, keepdims=False)
        k_l = k_l.at[bi, lengths].set(k_new)
        v_l = v_l.at[bi, lengths].set(v_new)
        y = cached_attention(q[:, None], k_l, v_l, lengths + 1)
        ck = lax.dynamic_update_index_in_dim(ck, k_l, l, 0)
        cv = lax.dynamic_update_index_in_dim(cv, v_l, l, 0)
        return (acc + y[0, 0, 0, :8].astype(jnp.float32).sum(), ck, cv), None

    (acc, ck, cv), _ = lax.scan(
        body, (jnp.float32(0.0), ck, cv),
        jnp.tile(jnp.arange(L, dtype=jnp.int32), passes))
    return acc, ck, cv


def _timed(fn):
    t0 = time.perf_counter()
    float(fn())                    # scalar fetch = the only sync point
    return time.perf_counter() - t0


def _per_layer_us(run):
    run(PASSES)                    # compile
    run(2 * PASSES)
    t1 = min(_timed(lambda: run(PASSES)) for _ in range(3))
    t2 = min(_timed(lambda: run(2 * PASSES)) for _ in range(3))
    return max(t2 - t1, 1e-9) / (PASSES * L) * 1e6


def kernel_mode():
    fused = HKV * DH
    mp = CTX // PAGE
    n_pages = B * mp
    log(f"devices: {jax.devices()}  B={B} H={H}/{HKV} Dh={DH} "
        f"page={PAGE} kv={KV_DTYPE.name} W={W} passes={PASSES}")
    ks = jax.random.split(jax.random.key(0), 8)
    q = jax.random.normal(ks[0], (B, H, DH), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (L * n_pages, PAGE, fused),
                           jnp.bfloat16).astype(KV_DTYPE)
    vp = jax.random.normal(ks[2], (L * n_pages, PAGE, fused),
                           jnp.bfloat16).astype(KV_DTYPE)
    pt = jax.random.permutation(ks[3], n_pages).reshape(B, mp).astype(
        jnp.int32)
    sk = jax.random.normal(ks[4], (B, W, HKV, DH), jnp.bfloat16)
    sv = jax.random.normal(ks[5], (B, W, HKV, DH), jnp.bfloat16)
    k_new = jax.random.normal(ks[6], (B, HKV, DH), jnp.bfloat16)
    for n_live in LIVE:
        lens = chat_lengths(n_live, seed=n_live)
        plen = jnp.asarray(lens, jnp.int32)
        n_side = jnp.where(plen > 0, W // 2, 0).astype(jnp.int32)
        live_pages = sum(-(-n // PAGE) for n in lens)
        kv_bytes = 2 * live_pages * PAGE * fused * KV_DTYPE.itemsize
        base = {"B": B, "live_rows": n_live, "lengths": lens,
                "live_pages": live_pages, "page_size": PAGE, "fused": fused}
        for bp in BPS:
            try:
                us = _per_layer_us(lambda p: _kernel_loop(
                    q, kp, vp, pt, plen, sk, sv, n_side, bp=bp, passes=p,
                    n_pages=n_pages))
            except Exception as e:   # VMEM overflow etc: record, move on
                log(f"live={n_live} bp={bp}: FAIL {type(e).__name__}: "
                    f"{str(e)[:300]}")
                continue
            emit({**base, "path": "flash_decode", "pages_per_block": bp,
                  "us_per_layer": round(us, 2),
                  "ms_per_step": round(us * L / 1e3, 3),
                  "kv_gbps": round(kv_bytes / us / 1e3, 1),
                  "pct_hbm_peak": round(kv_bytes / us / 1e3 / PEAK_GBPS
                                        * 100, 1)})
        # the dense path at the pow2 page bucket of the longest live row,
        # as engine/continuous.py picks it
        bucket = 1
        while bucket * PAGE < max(lens) + 1:
            bucket *= 2
        s_buf = min(bucket * PAGE + W, CTX)
        state = [jnp.zeros((L, B, s_buf, HKV, DH), KV_DTYPE) + 0.5,
                 jnp.zeros((L, B, s_buf, HKV, DH), KV_DTYPE) + 0.25]

        def dense(p):
            acc, state[0], state[1] = _dense_loop(
                q, state[0], state[1], plen, k_new, k_new, passes=p)
            return acc

        us = _per_layer_us(dense)
        emit({**base, "path": "dense_xla", "ctx_bucket_tokens": s_buf,
              "us_per_layer": round(us, 2),
              "ms_per_step": round(us * L / 1e3, 3)})
        del state


# ------------------------------------------------------------------ step


def step_mode():
    from distributed_inference_engine_tpu.config import EngineConfig
    from distributed_inference_engine_tpu.engine.continuous import (
        ContinuousEngine,
    )
    from distributed_inference_engine_tpu.engine.types import (
        GenerationRequest,
    )
    from distributed_inference_engine_tpu.models import spec_for_architecture
    from distributed_inference_engine_tpu.ops.quant import (
        random_quantized_params,
    )

    log(f"devices: {jax.devices()}")
    spec = spec_for_architecture(ARCH, size=SIZE, max_seq_len=CTX)
    params = random_quantized_params(spec.replace(dtype="bfloat16"),
                                     jax.random.key(0), bits=4)
    n_chunks = N_CHUNKS
    engines = {}
    for impl in ("xla", "pallas-decode" + ("_interpret" if INTERPRET
                                           else "")):
        engines[impl] = ContinuousEngine(
            spec, params=(params if not engines
                          else next(iter(engines.values())).params),
            config=EngineConfig(
                max_slots=B, max_seq_len=CTX, page_size=PAGE,
                num_pages=B * (CTX // PAGE),
                prefill_buckets=[CTX // 4, CTX // 2, 3 * CTX // 4],
                decode_steps_per_call=W, attention_impl=impl,
                prefix_cache=False), seed=0)
    params = None
    # nothing may reach max_seq_len inside a timed run: a chunk cut short
    # at the cap is another program
    longest = CTX - W * (n_chunks + 6)

    def run(impl, eng, lens, timed):
        rng = random.Random(7)
        for n in lens:
            eng.submit(GenerationRequest(
                prompt=[rng.randrange(1, spec.vocab_size)
                        for _ in range(min(n, longest))],
                max_new_tokens=W * (n_chunks + 4), temperature=0.0))
        while eng.n_waiting or eng.get_metrics()["prefilling_slots"]:
            eng.step()                         # admissions, first chunks
        eng.step()
        c0, t0 = eng.chunk_stats.count, eng.chunk_stats.total
        for _ in range(n_chunks):
            eng.step()
        chunks = eng.chunk_stats.count - c0
        dt = eng.chunk_stats.total - t0
        m = eng.get_metrics()
        if timed:
            emit({"path": impl, "live_rows": len(lens), "lengths": lens,
                  "chunks": chunks, "live_slots": m["live_slots"],
                  "ms_per_step": round(dt / (chunks * W) * 1e3, 3),
                  "decode_chunks_in_place": m["decode_chunks_in_place"],
                  "decode_chunks_dense": m["decode_chunks_dense"]})
        eng.run_until_idle()

    for impl, eng in engines.items():          # every program, untimed
        for n_live in LIVE:
            run(impl, eng, [n for n in chat_lengths(n_live, n_live) if n],
                False)
    for n_live in LIVE:
        lens = [n for n in chat_lengths(n_live, seed=n_live) if n]
        for impl, eng in engines.items():
            run(impl, eng, lens, True)


if __name__ == "__main__":
    if os.path.exists(OUT):
        os.remove(OUT)
    modes = sys.argv[1:] or ["kernel"]
    if "kernel" in modes:
        kernel_mode()
    if "step" in modes:
        step_mode()
