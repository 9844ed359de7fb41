"""The latent-attention prefill kernel (ops/mla.py) on hardware, at the
published head shape of both per-layer families: 32 heads of 128 | 64 | 128,
one row, bfloat16.

``kernel`` (default)
    ``mla_causal_attention`` alone, the kernel per (Q_BLOCK, K_BLOCK,
    HEADS_PER_STEP) against the XLA body, at buckets of 1,024 /
    4,096 / 8,192 full and at a 4,100-token prompt in the 8,192 bucket
    (what block skipping buys). ms a call = host clock over N calls ended by
    ``block_until_ready`` (a call is >= 0.3 ms: dispatch hides under it).
    Feed the winner into ``ops/mla.py``'s three constants.

``layer``
    One whole ``models.xing.mla_layer_prefill`` (projections, RoPE, the
    attention, ``wo``) at the published widths with both bodies: what a
    prefill program pays a layer, relayout copies included.

    python examples/mla_prefill_tune.py [kernel|layer] [bq,bk,heads ...]

Rows are written as JSON lines to stdout and to
``chiprun_out/mla_prefill_tune.jsonl``. On the CPU (``--tiny`` as the first
argument) it rehearses the control flow through the interpreter: host times
only, never a device number.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_inference_engine_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from distributed_inference_engine_tpu.models import xing  # noqa: E402
from distributed_inference_engine_tpu.ops import mla  # noqa: E402

OUT = os.path.join("chiprun_out", "mla_prefill_tune.jsonl")
SWEEP = [(512, 512, 4), (512, 512, 8), (512, 512, 2), (256, 512, 4),
         (512, 256, 4), (1024, 512, 4), (512, 1024, 4), (1024, 1024, 2),
         (256, 256, 8)]
CASES = [(1024, 1024), (4096, 4096), (8192, 8192), (8192, 4100)]


def emit(row):
    dev = jax.devices()[0]
    row = dict(row, platform=dev.platform, device_kind=dev.device_kind)
    print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(row) + "\n")


def ms_a_call(fn, args, n=10):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


def set_blocks(bq, bk, heads):
    mla.Q_BLOCK, mla.K_BLOCK, mla.HEADS_PER_STEP = bq, bk, heads


def kernel_mode(sweep, tiny):
    h, (dn, dr, dv) = (4, (16, 8, 16)) if tiny else (32, (128, 64, 128))
    flash = "flash_interpret" if tiny else "flash"
    for t, n in ([(1024, 600)] if tiny else CASES):
        ks = jax.random.split(jax.random.key(t), 4)
        qn, qr, kv = (jax.random.normal(k, (1, t, h, d), jnp.bfloat16)
                      for k, d in zip(ks, (dn, dr, dn + dv)))
        args = (qn, qr, kv, jax.random.normal(ks[3], (1, t, dr), jnp.bfloat16),
                jnp.asarray([n], jnp.int32))
        set_blocks(512, 512, 4)
        ref_fn = jax.jit(lambda *a: mla.mla_causal_attention(*a, impl="xla"))
        ref = ref_fn(*args)
        emit({"mode": "kernel", "body": "xla", "t": t, "len": n,
              "ms": ms_a_call(ref_fn, args, 3)})
        for cfg in sweep:
            if t % cfg[0] or t % cfg[1]:
                continue
            set_blocks(*cfg)
            fn = jax.jit(lambda *a: mla.mla_causal_attention(*a, impl=flash))
            try:
                err = float(jnp.abs(fn(*args)[:, :n].astype(jnp.float32)
                                    - ref[:, :n].astype(jnp.float32)).max())
                emit({"mode": "kernel", "body": "flash", "t": t, "len": n,
                      "blocks": cfg, "ms": ms_a_call(fn, args),
                      "max_err": err})
            except Exception as e:        # a refused block shape: record, go on
                emit({"mode": "kernel", "body": "flash", "t": t, "len": n,
                      "blocks": cfg, "refused": str(e)[:300]})


def layer_mode(sweep, tiny):
    spec = xing.xing_spec("xing-tiny" if tiny else "xing4.0-pp1",
                          max_seq_len=1024 if tiny else 8704)
    d, hd = spec.d_model, spec.n_heads
    dn, dr, dv = (spec.qk_nope_head_dim, spec.qk_rope_head_dim,
                  spec.v_head_dim)
    shapes = {"w_qa": (d, spec.q_lora_rank), "q_norm": (spec.q_lora_rank,),
              "w_qb": (spec.q_lora_rank, hd * (dn + dr)),
              "w_kva": (d, spec.kv_lora_rank + dr),
              "kv_norm": (spec.kv_lora_rank,),
              "w_kvb": (spec.kv_lora_rank, hd * (dn + dv)),
              "wo": (hd * dv, d)}
    ks = jax.random.split(jax.random.key(0), len(shapes) + 1)
    blk = {name: (jax.random.normal(k, s, jnp.float32) * s[0] ** -0.5
                  ).astype(spec.jnp_dtype) if len(s) > 1
           else jnp.ones(s, spec.jnp_dtype)
           for k, (name, s) in zip(ks, shapes.items())}
    flash = "flash_interpret" if tiny else "flash"
    for t, n in ([(1024, 600)] if tiny else CASES):
        x = jax.random.normal(ks[-1], (1, t, d), spec.jnp_dtype)
        pos = jnp.arange(t)[None]
        lens = jnp.asarray([n], jnp.int32)
        for body, cfg in [("xla", (512, 512, 4))] + [
                (flash, c) for c in sweep]:
            set_blocks(*cfg)
            real = mla.prefill_impl
            mla.prefill_impl = lambda t, body=body: body
            try:
                fn = jax.jit(lambda blk, x: xing.mla_layer_prefill(
                    spec, blk, x, pos, lens)[0])
                emit({"mode": "layer", "body": body, "t": t, "len": n,
                      "blocks": cfg, "ms": ms_a_call(fn, (blk, x), 5)})
            finally:
                mla.prefill_impl = real


def main(argv):
    tiny = argv[:1] == ["--tiny"]
    argv = argv[1:] if tiny else argv
    mode = argv[0] if argv else "kernel"
    sweep = [tuple(int(v) for v in a.split(",")) for a in argv[1:]]
    if mode == "kernel":
        kernel_mode(sweep or SWEEP, tiny)
    else:
        layer_mode(sweep or SWEEP[:1], tiny)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
