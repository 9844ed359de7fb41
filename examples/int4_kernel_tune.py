"""Schedule sweep for the stacked Mosaic int4 kernel at the shapes and rows
the benchmark's Mistral cells run (PERF.md section 6, PR 28).

Shapes: the four fused per-layer payloads of a Llama-width 7B tree, each in
its 32-layer stack, and Mistral's head ``(2048, 32768)`` in a 4-layer stack
(a one-layer stack is loop-invariant in the timing scan and XLA hoists the
call). Rows: 8 (the cells' decode step: ``max_batch_size`` 8), 256 and 768
(their prefill programs: batch x bucket).

Two ways through the kernel:

  resolved  ``_int4_matmul_stacked(x, packed, scale, layer)`` with nothing
            overridden: the blocks ``ops.int4_matmul.blocks_for`` resolves
            from the rows and the payload's shape, i.e. what a served
            program runs.
  sweep     explicit ``bk`` / ``bn`` over a grid of block shapes (full-K,
            wide-N against tall-K, 0.25-2 MB): the chunks of the streaming
            kernel at decode rows, the grid's blocks at prefill rows.

Measurement: every configuration runs ``passes`` x L sequential calls
inside one jitted ``lax.scan`` under ``jax.profiler``; a call's time is the
mean device duration of the ops whose HLO name matches ``int4`` (the same
events ``perfbench/lib/tracered.py`` sums into ``int4_matmul_roofline.*``),
and ``loop_us`` is first start to last end over the calls (kernel + launch
+ the scan's own per-iteration ops). Off the chip (CPU:
interpreted kernel, a rehearsal of the control flow) there is no device
trace and only a host clock around the loop is printed, under ``host_us``:
never a device number.

After the per-shape rows: the pass total (32 x the four + the head; should
match ``int4 kernel ms a step`` of a traced cell run) and the least-squares
fit ``us = c + bytes / r`` over the five shapes: c is what a call costs
whatever its size (fill, drain, launch), r the rate of the stream itself.

    python examples/int4_kernel_tune.py                    # resolved, M=8
    python examples/int4_kernel_tune.py --mode sweep --m 8
    python examples/int4_kernel_tune.py --mode both --m 8,256,768
    JAX_PLATFORMS=cpu python examples/int4_kernel_tune.py --tiny  # rehearsal
"""

import argparse
import functools
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_inference_engine_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from distributed_inference_engine_tpu.ops.int4_matmul import (  # noqa: E402
    _int4_matmul_stacked,
    blocks_for,
)

HBM_GBPS = 819.0        # v5e, perfbench/lib/peaks.py

# (name, layers in the stack, calls of it in one forward pass, K/2, N)
SHAPES = [
    ("qkv", 32, 32, 2048, 6144),
    ("wo", 32, 32, 2048, 4096),
    ("gate_up", 32, 32, 2048, 28672),
    ("w_down", 32, 32, 7168, 4096),
    ("head", 4, 1, 2048, 32768),
]
TINY_SHAPES = [
    ("qkv", 2, 2, 128, 384),
    ("w_down", 2, 2, 256, 128),
    ("head", 2, 1, 128, 512),
]


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def sweep_grid(k2, n, tiny=False):
    """Explicit (bk, bn) candidates for one payload shape: every block of
    0.25-2 MB whose sides divide the shape (bk a multiple of 128): the
    chunks of the streaming kernel at decode rows, the grid's blocks at
    prefill rows."""
    lo, hi = (1 << 12, 1 << 16) if tiny else (1 << 18, 1 << 21)
    bks = [b for b in (k2, 3584, 2048, 1792, 1024, 896, 512, 256, 128)
           if b <= k2 and k2 % b == 0]
    bns = [b for b in (n, 8192, 4096, 3072, 2048, 1024, 512, 256, 128)
           if b <= n and n % b == 0]
    return [(bk, bn) for bk in dict.fromkeys(bks) for bn in dict.fromkeys(bns)
            if lo <= bk * bn <= hi]


@functools.partial(jax.jit, static_argnames=("kw", "interpret"))
def _loop(x, packed, scale, passes, *, kw, interpret):
    """passes x L sequential kernel calls on-device; scalar out."""
    nl = packed.shape[0]

    def layer(acc, l):
        y = _int4_matmul_stacked(x, packed, scale, l, interpret=interpret,
                                 **dict(kw))
        # fold a few output elements into the carry: the data dependency
        # that keeps XLA from reordering or eliding calls
        return acc + y[0, :8].astype(jnp.float32).sum(), None

    def one_pass(_, acc):
        return jax.lax.scan(layer, acc, jnp.arange(nl, dtype=jnp.int32))[0]

    return jax.lax.fori_loop(0, passes, one_pass, jnp.float32(0.0))


def _int4_ops(trace_dir):
    """``(start_ns, dur_ns)`` of the device ops whose HLO name matches
    ``int4``, by start (first device plane that has an op line)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    data = ProfileData.from_file(paths[-1])
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        for ln in plane.lines:
            if ln.name == "XLA Ops":
                return sorted((float(e.start_ns), float(e.duration_ns))
                              for e in ln.events
                              if "int4" in e.name.partition(" = ")[0])
    raise RuntimeError("no device plane with XLA Ops in the trace")


def measure(x, packed, scale, configs, passes, on_chip):
    """Per config ``{"us": device us a call, "loop_us": ...}`` (chip) or
    ``{"host_us": ...}`` (CPU); a config the compiler refuses gives
    ``{"error": ...}``."""
    interpret = not on_chip
    nl = packed.shape[0]
    ran, out = [], [None] * len(configs)
    for i, kw in enumerate(configs):
        try:
            float(_loop(x, packed, scale, 1, kw=kw, interpret=interpret))
            ran.append(i)
        except Exception as e:       # refused block shape / VMEM: a row
            out[i] = {"error": f"{type(e).__name__}: {str(e)[:160]}"}
    if not on_chip:
        for i in ran:
            t0 = time.perf_counter()
            float(_loop(x, packed, scale, 1, kw=configs[i],
                        interpret=True))
            out[i] = {"host_us": (time.perf_counter() - t0) / nl * 1e6}
        return out
    with tempfile.TemporaryDirectory() as td:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(td, profiler_options=options)
        for i in ran:
            float(_loop(x, packed, scale, passes, kw=configs[i],
                        interpret=False))
        jax.profiler.stop_trace()
        ops = _int4_ops(td)
    per = passes * nl           # the programs ran in order, ``per`` ops each
    if len(ops) != per * len(ran):
        raise RuntimeError(f"{len(ops)} int4 ops traced for {len(ran)} "
                           f"programs of {per} calls: the count broke")
    for j, i in enumerate(ran):
        mine = ops[j * per:(j + 1) * per]
        out[i] = {"us": sum(d for _s, d in mine) / per / 1e3,
                  "loop_us": (mine[-1][0] + mine[-1][1] - mine[0][0])
                  / per / 1e3}
    return out


def fit(rows):
    """Least squares ``us = c + bytes / r`` over (bytes, us) rows:
    ``(c_us, r_gbps)``."""
    n = len(rows)
    sx = sum(b for b, _ in rows)
    sy = sum(u for _, u in rows)
    sxx = sum(b * b for b, _ in rows)
    sxy = sum(b * u for b, u in rows)
    slope = (n * sxy - sx * sy) / (n * sxx - sx * sx)    # us a byte
    return (sy - slope * sx) / n, 1e-3 / slope


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", default="8", help="rows, comma separated")
    ap.add_argument("--mode", default="resolved",
                    choices=("resolved", "sweep", "both"))
    ap.add_argument("--shapes", default="", help="names, comma separated")
    ap.add_argument("--passes", type=int, default=4)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=os.path.join("chiprun_out",
                                                  "int4_tune.jsonl"))
    args = ap.parse_args()
    on_chip = jax.default_backend() != "cpu"
    if not on_chip and not args.tiny:
        sys.exit("the real shapes need the chip; --tiny rehearses on the CPU")
    shapes = TINY_SHAPES if args.tiny else SHAPES
    if args.shapes:
        shapes = [s for s in shapes if s[0] in args.shapes.split(",")]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind}  mode={args.mode}")
    key = jax.random.key(0)
    with open(args.out, "a") as sink:
        for m in [int(v) for v in args.m.split(",")]:
            resolved, best = {}, {}
            for name, nl, calls, k2, n in shapes:
                kq, kx = jax.random.split(jax.random.fold_in(key, k2 + n))
                packed = jax.random.randint(kq, (nl, k2, n), -128, 128,
                                            jnp.int8)
                scale = jnp.full((nl, 1, n), 1e-3, jnp.float32)
                x = jax.random.normal(kx, (m, 2 * k2), jnp.bfloat16)
                configs = []
                if args.mode in ("resolved", "both"):
                    configs.append(())
                if args.mode in ("sweep", "both"):
                    configs += [(("bk", bk), ("bn", bn)) for bk, bn in
                                sweep_grid(k2, n, args.tiny)]
                got = measure(x, packed, scale, configs, args.passes,
                              on_chip)
                del packed
                for kw, res in zip(configs, got):
                    bk, bn = (dict(kw)["bk"], dict(kw)["bn"]) if kw \
                        else blocks_for(m, k2, n)
                    row = {"shape": name, "k2": k2, "n": n, "M": m,
                           "platform": dev.platform, "resolved": not kw,
                           "bk": bk, "bn": bn, **res}
                    if "us" in res:
                        row["gbps"] = k2 * n / res["us"] / 1e3
                        row["pct_peak"] = row["gbps"] / HBM_GBPS
                        entry = (calls, k2 * n, res["us"], [bk, bn])
                        if not kw:
                            resolved[name] = entry
                        if name not in best or res["us"] < best[name][2]:
                            best[name] = entry
                    line = json.dumps(row)
                    print(line, flush=True)
                    sink.write(line + "\n")
                    sink.flush()
            for label, table in (("resolved", resolved), ("best", best)):
                if len(table) < 3 or (label == "best"
                                      and args.mode == "resolved"):
                    continue
                total = sum(v[0] * v[2] for v in table.values()) / 1e3
                nbytes = sum(v[0] * v[1] for v in table.values())
                c, r = fit([(v[1], v[2]) for v in table.values()])
                line = json.dumps({
                    "summary": label, "M": m, "pass_ms": total,
                    "pass_bytes": nbytes,
                    "pct_peak": nbytes / HBM_GBPS / 1e6 / total,
                    "fit_c_us": c, "fit_r_gbps": r,
                    "shapes": {k: {"us": v[2], "gbps": v[1] / v[2] / 1e3,
                                   "blocks": v[3]}
                               for k, v in table.items()}})
                print(line, flush=True)
                sink.write(line + "\n")


if __name__ == "__main__":
    main()
