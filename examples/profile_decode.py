"""Profile the flagship decode chunk and attribute device time per op.

Captures a ``jax.profiler`` trace of a few steady-state decode chunks on
the continuous engine (same env knobs as bench.py), parses the xplane
protobuf directly (the tensorboard converter is broken against the
installed protobuf), and prints a device-time table grouped by op class —
the itemization VERDICT r3 item 5 asked for.

    BENCH_QUANT=1 python examples/profile_decode.py      # int8 rung
    BENCH_QUANT=4 python examples/profile_decode.py      # int4 kernel rung
"""

import collections
import glob
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_inference_engine_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()

import bench  # noqa: E402
from bench import log  # noqa: E402


def classify(name: str, d_ff: int = 14336, vocab: int = 128256) -> str:
    """Bucket an HLO op name by what it streams, keyed on the operand
    shapes XLA prints into the name (rung-specific dims passed in: the
    weight fusions carry the stacked s8/int-packed operand, the KV reads
    a [1, B, S, Hkv, Dh] slice of the stacked cache)."""
    n = name.lower()
    if "int4_matmul" in n or ("tpu_custom_call" in n and "int4" in n):
        return "int4 kernel (weights)"
    if "flash_decode" in n:
        return "flash-decode kernel (attn + KV read)"
    if "tpu_custom_call" in n or "pallas" in n:
        return "pallas kernel (other)"
    # the int4 lm_head is vocab-PADDED (ops.quant._pad_vocab) — match
    # both widths or padded-lm_head fusions silently land in the generic
    # matmul bucket
    from distributed_inference_engine_tpu.ops.quant import _pad_vocab

    if any(f"{v}]" in n or f",{v}" in n for v in {vocab, _pad_vocab(vocab)}):
        return "lm_head matmul + sampling"
    if "s8[" in n or "s4[" in n:
        if str(d_ff) in n:
            return "mlp weight stream (quantized)"
        return "attn weight stream (quantized)"
    if "dynamic-slice" in n and "fusion(bf16[" in n:
        return "KV ctx read (per-layer slice)"
    if "scatter" in n or "dynamic-update" in n:
        return "KV writeback/scatter"
    if "gather" in n:
        return "ctx gather (KV pages)"
    if "dot" in n or "convolution" in n or "einsum" in n:
        return "matmul fusions (unquantized weights)"
    if "fusion" in n:
        return "other fusions (elementwise/attn)"
    if "copy" in n or "bitcast" in n or "transpose" in n or "reshape" in n:
        return "layout/copies"
    if "infeed" in n or "outfeed" in n or "send" in n or "recv" in n:
        return "host transfer"
    return "other"


# HLO container ops whose duration INCLUDES their children (which appear
# on the same 'XLA Ops' line — summing both double-counts), plus async
# start/done markers
_CONTAINERS = ("while", "call", "conditional", "copy-start", "copy-done",
               "async-start", "async-done")


def _op_kind(name: str) -> str:
    """'%fusion.16 = ...' -> 'fusion'; '%while.75 = ...' -> 'while'."""
    head = name.lstrip("%").split(" ", 1)[0]
    return head.split(".", 1)[0]


def parse_xplane(trace_dir: str):
    """Per-op leaf device time (ps) + module wall time on the TPU plane.

    Only the 'XLA Ops' line is read (the 'XLA Modules'/'Steps' lines cover
    the same wall time — summing every line would double-count), container
    ops are dropped (their children are on the same line), and the module
    wall time is returned separately as the ground truth the leaf shares
    are scaled against."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    # the profiler writes plugins/profile/<timestamp>/; a reused trace_dir
    # accumulates captures across runs and summing them MERGES profiles
    # (caught in r5: the int4 table silently included the r4 int8 capture
    # from hours earlier — numbers matched the old table to the 0.1 ms).
    # Parse the NEWEST capture only.
    latest = max(os.path.dirname(p) for p in paths)
    paths = [p for p in paths if os.path.dirname(p) == latest]
    per_op = collections.Counter()
    total_ps = 0
    module_ps = 0
    for path in paths:
        space = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
        for plane in space.planes:
            if "TPU" not in plane.name or "device" not in plane.name.lower():
                continue
            meta = {m_id: m.name for m_id, m in plane.event_metadata.items()}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    module_ps += sum(ev.duration_ps for ev in line.events)
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    name = meta.get(ev.metadata_id, "?")
                    if _op_kind(name) in _CONTAINERS:
                        continue
                    per_op[name] += ev.duration_ps
                    total_ps += ev.duration_ps
    return per_op, total_ps, module_ps


def main() -> None:
    import jax

    log(f"devices: {jax.devices()}")
    spec = bench._spec()
    steps = int(os.environ.get("BENCH_STEPS", "16"))
    params = bench._build_params(spec, bench.QUANT)
    engine = bench._engine(spec, params, "continuous", bench.BATCH, steps)
    log("engine up; warming")
    engine.generate(bench._requests(spec, 1, bench.BATCH))   # compile+prime

    # steady state: fill slots, then profile a few pure-decode chunks
    for r in bench._requests(spec, 2, bench.BATCH):
        engine.submit(r)
    engine.step()                                    # admission + chunk 1
    trace_dir = os.environ.get("PROFILE_DIR", "/tmp/decode_trace")
    with jax.profiler.trace(trace_dir):
        for _ in range(3):
            engine.step()
    engine.abort_all()
    log(f"trace captured in {trace_dir}")

    per_op, total_ps, module_ps = parse_xplane(trace_dir)
    by_class = collections.Counter()
    for name, ps in per_op.items():
        by_class[classify(name, d_ff=spec.d_ff,
                          vocab=spec.vocab_size)] += ps
    print(f"\ndevice time over 3 decode chunks "
          f"({steps} steps each, bs{bench.BATCH}, "
          f"int{'4' if bench.QUANT_BITS == 4 and bench.QUANT else '8' if bench.QUANT else 'none'}):")
    print(f"module wall time: {module_ps / 1e9:.1f} ms "
          f"(leaf-op sum {total_ps / 1e9:.1f} ms; shares below are of the "
          f"leaf sum, ms scaled to module wall)")
    print(f"{'class':36s} {'ms':>9s} {'share':>7s}")
    scale = (module_ps / total_ps) if total_ps else 1.0
    for cls, ps in by_class.most_common():
        print(f"{cls:36s} {ps * scale / 1e9:9.2f} {ps / total_ps:7.1%}")
    print(f"{'TOTAL (module wall)':36s} {module_ps / 1e9:9.2f}")
    print("\ntop 20 ops (leaf ps, unscaled):")
    for name, ps in per_op.most_common(20):
        print(f"  {ps / 1e9:8.2f} ms  {name[:100]}")


if __name__ == "__main__":
    main()
