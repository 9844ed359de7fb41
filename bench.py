"""Benchmark entry point — runs on a TPU and nowhere else.

Prints exactly ONE JSON line on stdout:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
     "platform": "tpu", "device_kind": ..., "n_devices": N, ...}
Diagnostics go to stderr. Without a TPU backend the run exits non-zero
before building anything: a number from another backend is never printed
under a device metric's name. ``hbm_util`` and ``prefill_mfu`` divide by
the peaks of the chip the run is ON (``DEVICE_PEAKS``, keyed by
``device_kind``); a kind that is not in the table is an error.

Default rung (BASELINE.md ladder rung 3-4, VERDICT r1 item 1): steady-state
decode throughput of an **8B-class Llama-shaped model, packed-int4 weights
(the fastest measured config — stacked Mosaic kernel with fused
qkv/gate+up payloads, per-shape tuned blocks, and a vocab-padded
lm_head; 5,458 tok/s r5), continuous engine with paged KV at bs128** on
one chip — random-init (weights' values don't change the FLOP/byte
counts; zero-egress environment has no checkpoint on disk). Alongside tok/s it reports the HBM roofline:
``hbm_util`` = achieved bytes/s ÷ the chip's peak HBM bandwidth — decode is
bandwidth-bound, so this is the honest "how much headroom is left" number.

``vs_baseline``: the reference publishes no numbers (BASELINE.md — its
"model" is an asyncio sleep), so this repo's north star is the denominator:
BASELINE.json's ≥1,000 output tok/s target for the 8B class. (Round 1
divided by the mock's simulated 20 responses/s — a vacuous ratio, retired.)

Env knobs:
    BENCH_MODEL    spec name (default llama3-8b; gpt2 = round-1 rung)
    BENCH_QUANT    4 = packed int4 (default for 8B-class since r4 — the
                   fastest measured config, 5,458 tok/s at bs128 via the
                   stacked Mosaic kernel + r5 fusions), 1/8 = int8,
                   0 = full precision (default for small models)
    BENCH_ENGINE   continuous (default) | static | serving
    BENCH_BATCH    decode slots (default 128 for the 8B int4 continuous
                   flagship — the bs that int4's freed HBM affords, 5,453
                   tok/s measured; 64 otherwise)
    BENCH_PROMPT / BENCH_NEW_TOKENS   lengths (default 128 / 128)
    BENCH_KV_DTYPE paged-KV dtype (continuous; default bfloat16)
    BENCH_ATTN     attention impl: xla (default) | pallas-decode (the
                   in-place kernel: paged prefix + side window in one
                   pallas_call per layer, ops/flash_decode.py) | auto
    BENCH_OVERLAP  1 (default) = serving mode overlaps pump batch formation
                   with in-flight device steps (engine.overlap_hook);
                   0 = drain the inbox only at the top of the pump loop
    BENCH_KV_OFFLOAD   1 = host-RAM KV tier (continuous engine;
                   engine/kv_offload.py): evicted prefix pages offload to
                   host instead of dropping, admission prefetches them
                   back, pool exhaustion swaps decode victims instead of
                   finishing them; BENCH_KV_OFFLOAD_BYTES caps the host
                   store (default 1 GiB)
    BENCH_ENGINE=speculative: draft = the target's own first
                   BENCH_DRAFT_LAYERS layers (default 8), k=BENCH_SPEC_K
                   (default 4) — deterministic acceptance from shared
                   structure (engine.speculative.truncated_draft)
    serving mode:  BENCH_RATE (req/s Poisson, default 16),
                   BENCH_REQUESTS (default 64), BENCH_STEPS (chunk, def 16),
                   BENCH_MAX_WAITING (queue cap, default 4x slots; 0 = off),
                   BENCH_DEADLINE_S (queue deadline shed, default 10; 0 = off)
    BENCH_RUNS     timed repetitions, best-of reported (default 3)
    BENCH_STREAM   1 = sub-chunk streaming: streaming-flagged slots decode
                   in BENCH_STREAM_STEPS-step chunks (pow2-bucketed,
                   default 2); pure-batch slots keep the full megastep
    BENCH_MIX_EVERY / BENCH_MIX_PROMPT   mixed workload: every Nth serving
                   request carries a BENCH_MIX_PROMPT-token prompt
                   (default 0 = off / 2048)
    fleet sweep (examples/fleet_sweep.py — fake-fleet goodput scaling
                   through the coordinator; the constants are read HERE so
                   the knob catalog stays one file):
                   BENCH_FLEET_DIR (per-leg fleet JSON output dir, default
                   bench_obs; "0" disables), BENCH_FLEET_NS (fleet sizes,
                   default 1,2,4), BENCH_FLEET_REQUESTS (requests per
                   worker per leg, default 160), BENCH_FLEET_RATE (offered
                   req/s per worker, default 120 — ~20% past a fake
                   worker's capacity so the scaling legs measure sustained
                   goodput, not offered load), BENCH_FLEET_NEW_TOKENS
                   (default 16), BENCH_FLEET_STEP_MS (fake decode step
                   latency, default 5), BENCH_FLEET_SLOTS (fake decode
                   slots, default 8), BENCH_FLEET_SEED (arrivals + retry
                   jitter, default 1234), BENCH_FLEET_TINY (1 = run the
                   llama-tiny disaggregated token-exactness leg, default 1),
                   BENCH_FLEET_MIN (autoscale leg min fleet, default 1),
                   BENCH_FLEET_MAX (autoscale leg max fleet, default 3),
                   BENCH_FLEET_BURST (autoscale leg mid-run load
                   multiplier vs one worker's capacity, default 3.5 —
                   keep it ABOVE BENCH_FLEET_MAX so the burst saturates
                   even the full fleet: every smaller fleet is clearly
                   insufficient and the full one never reads as idle
                   mid-burst, which keeps the decision sequence
                   replay-stable)
    The sweep's non-BENCH knobs (SWEEP_* family, shared naming with
    examples/serving_sweep.py): serving_sweep reads SWEEP_RATES /
    SWEEP_REQUESTS / SWEEP_TRIALS / SWEEP_SHAPE; fleet_sweep reads
    SWEEP_LEGS (comma list to run a subset of
    replicated,disagg,affinity,kill,kvfabric,stream,autoscale,upgrade,
    multimodel,long,tiny; SWEEP_SHAPE=long raises the long leg's
    prompts from 2k to 8k, SWEEP_SHAPE=moe points serving_sweep at the
    capacity-bound int4 mixtral-16g rung).
"""

import json
import math
import os
import sys
import time

# Published per-chip peaks, keyed by jax's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s dense bf16, 819 GB/s HBM).
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
}
# filled by main() from jax.devices() before any rung runs: rides every
# JSON line, and selects the DEVICE_PEAKS row
DEVICE: dict = {}
NORTH_STAR_TOKS = 1000.0      # BASELINE.json: >=1k output tok/s, 8B class


def device_peaks() -> dict:
    return DEVICE_PEAKS[DEVICE["device_kind"]]

MODEL = os.environ.get("BENCH_MODEL", "llama3-8b")
IS_BIG = "8b" in MODEL or "7b" in MODEL
# BENCH_QUANT: 0 = full precision, 1/8 = int8 weight-only, 4 = packed int4.
# Default for the 8B class is int4 — the fastest measured config since the
# r4 stacked Mosaic kernel (4,254 tok/s vs int8's 3,661 at bs64). The
# default is DOWNGRADED to int8 by resolve_quant() when the Mosaic kernel
# cannot engage (MoE expert weights are 4-D; multi-device processes kept
# the XLA path until r5's shard_map wrapper): the pure-XLA int4 path
# measured 1,584 tok/s — a silent 2.3x loss vs int8 (ADVICE r4).
_Q_EXPLICIT = "BENCH_QUANT" in os.environ
_Q = os.environ.get("BENCH_QUANT", "4" if IS_BIG else "0")
QUANT = _Q not in ("0", "")
QUANT_BITS = 4 if _Q == "4" else 8


def resolve_quant(spec) -> None:
    """Finalize the quant default once the model spec is known (ADVICE
    r4): a DEFAULTED int4 drops to int8 when the Mosaic kernel cannot
    take the weights under ANY mode — i.e. MoE specs, whose 4-D expert
    payloads the stacked kernel rejects. Multi-device processes no
    longer downgrade: sharded int4 params flip the kernel to its
    GSPMD-partitionable "cp" mode at engine init (r5). An EXPLICIT
    BENCH_QUANT=4 on a MoE spec is honored but logged."""
    global QUANT_BITS, BATCH
    if not (QUANT and QUANT_BITS == 4) or not spec.n_experts:
        return
    if _Q_EXPLICIT:
        log("WARNING: BENCH_QUANT=4 on a MoE spec — expert weights are "
            "4-D, the Mosaic kernel disengages, and the XLA int4 path "
            "measured 2.3x slower than int8")
    else:
        log("int4 default downgraded to int8: MoE expert weights are 4-D")
        QUANT_BITS = 8
        if _BIG_INT4_CONT and "BENCH_BATCH" not in os.environ:
            # the bs128 default rode the int4 assumption (int8 bs128
            # with bf16 KV does not fit a 16 GB chip — README table);
            # re-derive alongside the quant downgrade
            BATCH = 64
            log("batch default re-derived to 64 (int8 bs128 needs fp8 KV)")
ENGINE_KIND = os.environ.get("BENCH_ENGINE", "continuous")
# default slots: the throughput-serving configuration. The 8B int4
# continuous flagship moved to bs128 in r5 — int4 frees enough HBM that
# bs128 fits with bf16 KV, and weights amortize over 2x the tokens:
# 5,315 tok/s vs 4,639 at bs64 (fp8 KV at bs128 measured SLOWER, 4,634 —
# the convert overhead now outweighs the saved KV bandwidth, so fp8 KV
# is a capacity lever only on this engine). Other configs keep bs64
# (batch sweep in README — aggregate tok/s scales ~5x from bs8 while
# TTFT stays sub-second).
_BIG_INT4_CONT = IS_BIG and _Q == "4" and \
    os.environ.get("BENCH_ENGINE", "continuous") == "continuous"
BATCH = int(os.environ.get("BENCH_BATCH", "128" if _BIG_INT4_CONT else "64"))
PROMPT_LEN = int(os.environ.get("BENCH_PROMPT", "128"))
NEW_TOKENS = int(os.environ.get("BENCH_NEW_TOKENS", "128"))
RUNS = int(os.environ.get("BENCH_RUNS", "3"))
# mixed workload (ISSUE 3): every BENCH_MIX_EVERY-th serving request
# carries a BENCH_MIX_PROMPT-token prompt instead of PROMPT_LEN — a steady
# decode stream with periodic long-prompt admissions, the shape whose ITL
# cliff chunked prefill (BENCH_PREFILL_CHUNK) bounds. 0 disables.
MIX_EVERY = int(os.environ.get("BENCH_MIX_EVERY", "0"))
MIX_PROMPT = int(os.environ.get("BENCH_MIX_PROMPT", "2048"))
MAX_PROMPT = max(PROMPT_LEN, MIX_PROMPT) if MIX_EVERY else PROMPT_LEN


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def pct(xs, q: float):
    """Nearest-rank percentile (shared with examples/serving_sweep.py)."""
    return (sorted(xs)[min(len(xs) - 1, math.ceil(q * len(xs)) - 1)]
            if xs else 0.0)


# Fleet-sweep knobs (examples/fleet_sweep.py imports these; docstring above
# documents them — reading them here keeps every BENCH_* knob in one file
# for the knob-drift check). Shapes the fake fleet and its offered load.
FLEET_DIR = os.environ.get("BENCH_FLEET_DIR", "bench_obs")
FLEET_NS = [int(n) for n in
            os.environ.get("BENCH_FLEET_NS", "1,2,4").split(",")]
FLEET_REQUESTS = int(os.environ.get("BENCH_FLEET_REQUESTS", "160"))
FLEET_RATE = float(os.environ.get("BENCH_FLEET_RATE", "120"))
FLEET_NEW_TOKENS = int(os.environ.get("BENCH_FLEET_NEW_TOKENS", "16"))
FLEET_STEP_MS = float(os.environ.get("BENCH_FLEET_STEP_MS", "5"))
FLEET_SLOTS = int(os.environ.get("BENCH_FLEET_SLOTS", "8"))
FLEET_SEED = int(os.environ.get("BENCH_FLEET_SEED", "1234"))
FLEET_TINY = os.environ.get("BENCH_FLEET_TINY", "1") not in ("0", "")
FLEET_MIN = int(os.environ.get("BENCH_FLEET_MIN", "1"))
FLEET_MAX = int(os.environ.get("BENCH_FLEET_MAX", "3"))
FLEET_BURST = float(os.environ.get("BENCH_FLEET_BURST", "3.5"))


def _spec():
    from distributed_inference_engine_tpu.models import spec_for_architecture

    return spec_for_architecture(MODEL)


def _build_params(spec, quant: bool):
    import jax

    from distributed_inference_engine_tpu.ops.quant import (
        random_quantized_params,
    )

    if not quant:
        return None                      # engine does its own random init
    return random_quantized_params(spec, jax.random.key(0),
                                   bits=QUANT_BITS)


def _engine(spec, params, kind: str, batch: int, steps: int):
    from distributed_inference_engine_tpu.config import EngineConfig

    cfg = EngineConfig(
        max_slots=batch,
        max_seq_len=min(spec.max_seq_len, MAX_PROMPT + NEW_TOKENS),
        prefill_buckets=sorted({PROMPT_LEN, MAX_PROMPT}),
        decode_steps_per_call=steps,
    )
    if os.environ.get("BENCH_KV_DTYPE"):
        cfg.kv_dtype = os.environ["BENCH_KV_DTYPE"]
    if os.environ.get("BENCH_ATTN"):
        cfg.attention_impl = os.environ["BENCH_ATTN"]
    if os.environ.get("BENCH_STREAM", "") not in ("", "0"):
        # sub-chunk streaming (ISSUE 13): while any live slot has a
        # stream callback, clamp decode chunks to BENCH_STREAM_STEPS
        # (pow2-bucketed) so tokens reach the host at sub-chunk cadence;
        # pure-batch waves keep the full megastep
        cfg.stream_chunk_steps = int(
            os.environ.get("BENCH_STREAM_STEPS", "2"))
    if kind == "static":
        from distributed_inference_engine_tpu.engine.engine import Engine

        return Engine(spec, params=params, config=cfg)
    if kind == "speculative":
        import jax

        from distributed_inference_engine_tpu.engine.speculative import (
            SpeculativeEngine,
            truncated_draft,
        )

        if params is None:
            from distributed_inference_engine_tpu.models.base import (
                init_params,
            )

            params = init_params(spec, jax.random.key(0))
        d_spec, d_params = truncated_draft(
            spec, params, int(os.environ.get("BENCH_DRAFT_LAYERS", "8")))
        return SpeculativeEngine(
            spec, d_spec, params=params, draft_params=d_params, config=cfg,
            speculate_k=int(os.environ.get("BENCH_SPEC_K", "4")))
    from distributed_inference_engine_tpu.engine.continuous import (
        ContinuousEngine,
    )

    cfg.page_size = 128
    per_seq = -(-(PROMPT_LEN + NEW_TOKENS) // cfg.page_size)  # ceil
    cfg.num_pages = max(64, batch * per_seq + 8)
    if os.environ.get("BENCH_PREFILL_CHUNK"):
        # chunked prefill: long prompts prefill in page-aligned chunks
        # interleaved with decode (bounds the admission stall on live
        # decodes). Mirror the engine's page rounding when building the
        # bucket set, or every chunk pads to the raw (unrounded) bucket
        raw = int(os.environ["BENCH_PREFILL_CHUNK"])
        cfg.prefill_chunk = raw
        chunk = max(cfg.page_size, raw // cfg.page_size * cfg.page_size)
        cfg.prefill_buckets = sorted({chunk, PROMPT_LEN, MAX_PROMPT})
    if os.environ.get("BENCH_KV_OFFLOAD", "") not in ("", "0"):
        # host-RAM KV tier: evicted prefix pages offload instead of
        # dropping, admission prefetches host hits back, pool exhaustion
        # swaps decode victims out and resumes them (engine/kv_offload.py)
        cfg.kv_offload = True
        cfg.kv_offload_bytes = int(
            os.environ.get("BENCH_KV_OFFLOAD_BYTES", str(1 << 30)))
    return ContinuousEngine(spec, params=params, config=cfg)


def _roofline(spec, params, batch: int, toks_per_s: float,
              kv_dtype_bytes: int) -> dict:
    """Streamed bytes per decode step → fraction of the chip's HBM peak.

    Weights stream fully each step EXCEPT the token embedding (a gather of
    ``batch`` rows; when embeddings are tied the unembed matmul streams the
    table, so it counts). KV reads grow with context: mean over the decode
    phase ≈ prompt + new/2 tokens per slot.
    """
    from distributed_inference_engine_tpu.ops.quant import param_bytes

    total = param_bytes(params)
    emb_bytes = 0
    if not spec.tie_embeddings:
        emb = params["tok_emb"]
        emb_bytes = emb.size * emb.dtype.itemsize
    kv_per_token = (2 * spec.n_layers * spec.n_kv_heads * spec.head_dim
                    * kv_dtype_bytes)
    mean_ctx = PROMPT_LEN + NEW_TOKENS / 2
    step_bytes = (total - emb_bytes) + batch * mean_ctx * kv_per_token
    steps_per_s = toks_per_s / batch
    gbps = step_bytes * steps_per_s / 1e9
    return {
        "param_gib": round(total / (1 << 30), 2),
        "step_mb": round(step_bytes / 1e6, 1),
        "achieved_gbps": round(gbps, 1),
        "hbm_util": round(gbps / device_peaks()["hbm_gbps"], 3),
    }


def _matmul_flops_per_token(spec) -> float:
    """2 × (matmul weight elements) per token — the dense-forward FLOP
    count prefill MFU is judged against. Embedding gather is free; an
    untied lm_head is a real matmul and counts. Attention score/value
    FLOPs (≈ 4·ctx·H·dh per token, <0.1% at the bench prompt lengths)
    are excluded, which slightly UNDERSTATES MFU — conservative."""
    d, dh = spec.d_model, spec.head_dim
    per_layer = (d * spec.n_heads * dh              # wq
                 + 2 * d * spec.n_kv_heads * dh     # wk, wv
                 + spec.n_heads * dh * d            # wo
                 + 3 * d * spec.d_ff)               # gate, up, down
    total = spec.n_layers * per_layer
    if not spec.tie_embeddings:
        total += d * spec.vocab_size
    return 2.0 * total


def prime_pump(pump, spec, n: int) -> None:
    """Unmeasured priming trial (VERDICT r3 item 7): the first full-shape
    trial after engine init absorbs XLA cache lookups and reads as a
    stall — burn one batch through the pump before the clock
    starts. Shared by serving_main and examples/serving_sweep.py."""
    import asyncio

    from distributed_inference_engine_tpu.engine.types import (
        EngineOverloadedError,
    )

    t0 = time.perf_counter()

    async def _prime():
        async def one(req):
            try:
                await pump.generate_streaming(req, lambda toks: None)
            except EngineOverloadedError:
                pass
        await asyncio.gather(*(one(r) for r in _requests(spec, 5, n)))

    asyncio.run(_prime())
    log(f"priming trial: {time.perf_counter() - t0:.1f}s (unmeasured)")


def _requests(spec, seed: int, n: int):
    import numpy as np

    from distributed_inference_engine_tpu.engine.types import (
        GenerationRequest,
    )

    rs = np.random.RandomState(seed)

    def _plen(i: int) -> int:
        # periodic long-prompt admissions into a steady short-prompt
        # stream (SWEEP_SHAPE=mixed); the first request stays short so
        # the decode stream establishes before the first admission burst
        if MIX_EVERY and i > 0 and i % MIX_EVERY == 0:
            return min(MIX_PROMPT, spec.max_seq_len - NEW_TOKENS)
        return PROMPT_LEN

    return [
        GenerationRequest(
            prompt=rs.randint(0, spec.vocab_size, size=_plen(i)).tolist(),
            max_new_tokens=NEW_TOKENS,
            temperature=0.0,
            request_id=f"bench-{seed}-{i}",
        )
        for i in range(n)
    ]


def dump_obs(engine, result_rows, label, pump=None) -> None:
    """Drop a /metrics-equivalent registry snapshot, a per-request trace
    JSONL, and the engine's step timeline (Perfetto-loadable) next to the
    BENCH json. ``BENCH_OBS_DIR`` picks the directory (default bench_obs;
    "0" disables)."""
    out_dir = os.environ.get("BENCH_OBS_DIR", "bench_obs")
    if out_dir in ("0", ""):
        return
    try:
        from distributed_inference_engine_tpu.obs import (
            collectors as obs_collectors,
        )
        from distributed_inference_engine_tpu.obs.registry import (
            MetricsRegistry,
        )

        os.makedirs(out_dir, exist_ok=True)
        reg = MetricsRegistry()
        obs_collectors.ensure_families(reg)
        obs_collectors.apply_engine(reg, engine.get_metrics(),
                                    model=MODEL, worker_id="bench")
        if pump is not None:
            ps = {k: v for k, v in pump.get_stats().items()
                  if k != "engine"}
            obs_collectors.apply_pump(reg, ps, model=MODEL,
                                      worker_id="bench")
        with open(os.path.join(out_dir, f"bench_metrics_{label}.prom"),
                  "w") as f:
            f.write(reg.render())
        # only terminal traces are dumped: a row with no finish_reason is
        # a request that never completed (cancelled mid-run / in flight at
        # teardown) and its latency fields are garbage — skipping beats
        # poisoning downstream percentile tooling with partial marks
        terminal = [r for r in result_rows if r.get("finish_reason")]
        skipped = len(result_rows) - len(terminal)
        with open(os.path.join(out_dir, f"bench_traces_{label}.jsonl"),
                  "w") as f:
            for row in terminal:
                f.write(json.dumps(row) + "\n")
        if skipped:
            log(f"obs dump: skipped {skipped} non-terminal trace(s)")
        tl = getattr(engine, "timeline", None)
        if tl is not None and len(tl):
            tl.dump(os.path.join(out_dir, f"bench_timeline_{label}.json"))
        log(f"obs dump -> {out_dir}/bench_*_{label}.*")
    except Exception as e:             # observability must not fail the rung
        log(f"obs dump failed: {e}")


def _result_row(res) -> dict:
    return {
        "request_id": res.request_id,
        "tokens": len(res.tokens),
        "finish_reason": res.finish_reason,
        "ttft_s": round(float(res.ttft_s), 6),
        "decode_s": round(float(res.decode_s), 6),
    }


def decode_main() -> None:
    """Batch-decode throughput rung (static or continuous engine)."""
    spec = _spec()
    resolve_quant(spec)
    # continuous default chunk 128 (= NEW_TOKENS): with the round-3 dense-
    # ctx chunk scheme the whole decode runs as ONE chunk — one ctx gather,
    # one host sync — measuring 3623 tok/s at 8B bs64 vs 3173 at chunk 64
    # (each extra chunk pays a host round trip + a re-gather; the round-2
    # side-window scheme peaked at chunk 64 because its side buffer grew
    # with the chunk). Serving keeps small chunks (admission cadence).
    default_steps = (min(128, NEW_TOKENS) if ENGINE_KIND == "continuous"
                     else NEW_TOKENS)
    steps = int(os.environ.get("BENCH_STEPS", str(default_steps)))
    t0 = time.perf_counter()
    params = _build_params(spec, QUANT)
    engine = _engine(spec, params, ENGINE_KIND, BATCH, steps)
    # drop the pre-fusion tree reference: the engine's prepare_params
    # replaced qkv/gate+up members with fused payloads, and holding the
    # originals alive here pins ~2.2 GB of dead HBM — enough to OOM the
    # int4 bs128 rung on a 16 GB chip (engine.params is the live tree)
    params = None
    log(f"engine init ({MODEL}, {ENGINE_KIND}, "
        f"quant={QUANT_BITS if QUANT else 0}): "
        f"{time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    engine.generate(_requests(spec, 1, BATCH))   # compile all programs
    log(f"warmup (compile): {time.perf_counter() - t0:.1f}s")

    best_toks = 0.0
    ttfts = []
    t_measure = time.perf_counter()   # host-gap split covers measured runs
    for r in range(RUNS):
        t0 = time.perf_counter()
        results = engine.generate(_requests(spec, 100 + r, BATCH))
        wall = time.perf_counter() - t0
        gen = sum(len(x.tokens) for x in results)
        decode_s = results[0].decode_s
        toks = (gen - len(results)) / decode_s   # first token is prefill's
        ttfts.append(results[0].ttft_s)
        log(f"run {r}: {gen} tokens, e2e {wall:.2f}s "
            f"({gen / wall:.1f} tok/s e2e), decode {decode_s:.2f}s -> "
            f"{toks:.1f} tok/s (ttft {results[0].ttft_s * 1e3:.1f} ms)")
        best_toks = max(best_toks, toks)

    kv_bytes = 1 if getattr(engine.config, "kv_dtype", "") == "float8_e4m3fn" \
        else 2
    roof = _roofline(spec, engine.params, BATCH, best_toks, kv_bytes)
    # decompose the roofline gap (ISSUE 5): hbm_util divides streamed bytes
    # by WALL time, so host bubbles between dispatches read as missing
    # bandwidth. Split the measured window into kernel-time vs host-bubble
    # from the step timeline; hbm_util_kernel rescales to dispatch-bracket
    # time only — "what the kernels achieve when they are actually running".
    tl = getattr(engine, "timeline", None)
    if tl is not None and len(tl):
        from distributed_inference_engine_tpu.obs.timeline import (
            busy_gap_split,
        )

        split = busy_gap_split(tl.events(since=t_measure))
        roof["host_bubble_frac"] = round(split["bubble_frac"], 3)
        denom = 1.0 - split["bubble_frac"]
        roof["hbm_util_kernel"] = round(
            min(1.0, roof["hbm_util"] / denom) if denom > 0
            else roof["hbm_util"], 3)
        log(f"host-gap split over {split['n_events']} dispatches: "
            f"busy {split['busy_s']:.2f}s gap {split['gap_s']:.2f}s "
            f"(bubble {split['bubble_frac']:.1%})")
    ttft_ms = sorted(ttfts)[len(ttfts) // 2] * 1e3
    # prefill efficiency (VERDICT r3 item 4): prefill is compute-bound, so
    # judge it as MFU over the whole-batch TTFT (submit -> first token:
    # includes sampling + the packed readback, so this is a lower bound)
    prefill_flops = _matmul_flops_per_token(spec) * BATCH * PROMPT_LEN
    prefill_mfu = (prefill_flops / (ttft_ms / 1e3)
                   / (device_peaks()["bf16_tflops"] * 1e12)) if ttft_ms else 0.0
    log(f"p50 TTFT: {ttft_ms:.1f} ms; prefill MFU {prefill_mfu:.2f} "
        f"({prefill_flops / 1e12:.1f} TF batch); roofline: {roof}")
    suffix = "" if ENGINE_KIND == "continuous" else f"_{ENGINE_KIND}"
    row = {
        "metric": f"decode_throughput_{MODEL}"
                  f"{f'_int{QUANT_BITS}' if QUANT else ''}"
                  f"_bs{BATCH}{suffix}",
        "value": round(best_toks, 1),
        "unit": "tok/s",
        "vs_baseline": round(best_toks / NORTH_STAR_TOKS, 2),
        **DEVICE,
        "hbm_util": roof["hbm_util"],
        "achieved_gbps": roof["achieved_gbps"],
        "ttft_p50_ms": round(ttft_ms, 1),
        "prefill_mfu": round(prefill_mfu, 3),
    }
    if "host_bubble_frac" in roof:
        row["host_bubble_frac"] = roof["host_bubble_frac"]
        row["hbm_util_kernel"] = roof["hbm_util_kernel"]
    m = engine.get_metrics()
    if "draft_acceptance_rate" in m:
        row["acceptance"] = round(m["draft_acceptance_rate"], 3)
        row["tokens_per_round"] = round(m["tokens_per_round"], 2)
        row["speculate_k"] = m["speculate_k"]
        # the roofline model assumes one weight pass per decode step per
        # token — speculation exists to break that assumption, so the
        # util fields would be nonsense here
        row.pop("hbm_util", None)
        row.pop("achieved_gbps", None)
    print(json.dumps(row), flush=True)
    dump_obs(engine, [_result_row(x) for x in results], "decode")


def serving_main() -> None:
    """Serving load test (VERDICT r1 item 5): Poisson arrivals through
    ``EnginePump`` — N independent clients, each streaming one request —
    measuring throughput, TTFT p50/p99 (from submit, queue wait included),
    streaming ITL p99, and decode-batch occupancy."""
    import asyncio

    import numpy as np

    from distributed_inference_engine_tpu.serving.pump import EnginePump

    from distributed_inference_engine_tpu.engine.types import (
        EngineOverloadedError,
    )

    spec = _spec()
    resolve_quant(spec)
    # default offered load ~near capacity: an 8B chip serves ~4 requests/s
    # of 128 fresh tokens; small models far more
    rate = float(os.environ.get("BENCH_RATE", "4" if IS_BIG else "16"))
    n_requests = int(os.environ.get("BENCH_REQUESTS", "64"))
    steps = int(os.environ.get("BENCH_STEPS", "16"))

    t0 = time.perf_counter()
    params = _build_params(spec, QUANT)
    engine = _engine(spec, params, "continuous", BATCH, steps)
    params = None                     # see decode_main: free pre-fusion tree
    # overload handling on by default in serving mode: past saturation the
    # engine sheds (typed error) instead of growing an unbounded queue, so
    # the latency curve has a knee instead of a cliff (VERDICT r2 item 2)
    engine.config.max_waiting = int(
        os.environ.get("BENCH_MAX_WAITING", str(4 * BATCH)))
    engine.config.queue_deadline_s = float(
        os.environ.get("BENCH_DEADLINE_S", "10"))
    log(f"engine init ({MODEL}, serving, quant={QUANT_BITS if QUANT else 0}): "
        f"{time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    # Poisson arrivals admit in small bursts: EVERY pow2 admission bucket
    # must be compiled before the clock starts, not just bb=BATCH
    engine.warmup(max_new_tokens=2)
    log(f"warmup (compile all buckets): {time.perf_counter() - t0:.1f}s")

    # batch-formation overlap (ISSUE 5c): the pump wires engine.overlap_hook
    # so inbox draining (validation, submit, prefetch probes) runs in the
    # shadow of in-flight device steps instead of the host gap between them
    overlap = os.environ.get("BENCH_OVERLAP", "1") not in ("0", "")
    pump = EnginePump(engine, idle_wait_s=0.01, overlap_forms=overlap)
    prime_pump(pump, spec, min(BATCH, n_requests))
    reqs = _requests(spec, 7, n_requests)
    itls: list = []
    ttfts: list = []
    # occupancy must cover the MEASURED window only — warmup ticks the
    # engine's cumulative counters too
    m0 = engine.get_metrics()
    steps0 = m0["engine_steps"]
    occ_sum0 = m0["batch_occupancy"] * steps0 * engine.max_slots
    dispatch0 = m0.get("dispatch_s_total", 0.0)
    gap0 = m0.get("host_gap_s_total", 0.0)

    rejected = [0]                     # queue-full + deadline sheds

    trace_rows: list = []

    async def client(req):
        marks = []

        def on_tokens(toks):
            marks.append((time.perf_counter(), len(toks)))

        try:
            res = await pump.generate_streaming(req, on_tokens)
        except EngineOverloadedError:
            rejected[0] += 1
            return 0
        trace_rows.append(_result_row(res))
        ttfts.append(res.ttft_s)
        prev = None
        for t, k in marks:
            if prev is not None:
                itls.append(t - prev)      # chunk gap: the consumer-visible
                itls.extend([0.0] * (k - 1))   # intra-chunk tokens co-arrive
            prev = t
        return len(res.tokens)

    async def run():
        rs = np.random.RandomState(3)
        tasks = []
        t_start = time.perf_counter()
        for req in reqs:
            tasks.append(asyncio.create_task(client(req)))
            await asyncio.sleep(float(rs.exponential(1.0 / rate)))
        counts = await asyncio.gather(*tasks)
        wall = time.perf_counter() - t_start
        await pump.stop()
        return sum(counts), wall

    total_toks, wall = asyncio.run(run())
    m = engine.get_metrics()
    toks_per_s = total_toks / wall
    ttft_p50, ttft_p99 = pct(ttfts, 0.5) * 1e3, pct(ttfts, 0.99) * 1e3
    # p50 next to p99: a long-prompt admission shows in the TAIL (it must
    # not cliff p99 above ~2x the steady-state median), so both ends of
    # the ITL distribution are first-class outputs
    itl_p50 = pct(itls, 0.5) * 1e3
    itl_p99 = pct(itls, 0.99) * 1e3
    d_steps = m["engine_steps"] - steps0
    occ = ((m["batch_occupancy"] * m["engine_steps"] * engine.max_slots
            - occ_sum0) / (d_steps * engine.max_slots)) if d_steps else 0.0
    rej_rate = rejected[0] / len(reqs) if reqs else 0.0
    # host-gap split over the measured window (same delta idiom as
    # occupancy): dispatch = inside device-dispatch brackets, gap = host
    # time between them — attributes a goodput shortfall to the scheduler
    # side vs the kernel side
    d_dispatch = m.get("dispatch_s_total", 0.0) - dispatch0
    d_gap = m.get("host_gap_s_total", 0.0) - gap0
    bubble = d_gap / (d_dispatch + d_gap) if (d_dispatch + d_gap) > 0 else 0.0
    overlap_admitted = pump.get_stats().get("overlap_admitted", 0)
    log(f"served {len(reqs)} reqs ({total_toks} tokens) in {wall:.1f}s at "
        f"offered rate {rate}/s -> {toks_per_s:.1f} tok/s goodput; "
        f"rejected {rejected[0]} ({rej_rate:.0%}); TTFT p50 "
        f"{ttft_p50:.0f} ms p99 {ttft_p99:.0f} ms; ITL p50 {itl_p50:.1f} ms "
        f"p99 {itl_p99:.1f} ms; occupancy {occ:.2f}; host bubble "
        f"{bubble:.1%} (dispatch {d_dispatch:.1f}s gap {d_gap:.1f}s, "
        f"{overlap_admitted} overlap-admitted)")
    print(json.dumps({
        "metric": f"serving_throughput_{MODEL}"
                  f"{f'_int{QUANT_BITS}' if QUANT else ''}"
                  f"_rate{rate:g}",
        "value": round(toks_per_s, 1),
        "unit": "tok/s",
        "vs_baseline": round(toks_per_s / NORTH_STAR_TOKS, 2),
        **DEVICE,
        "ttft_p50_ms": round(ttft_p50, 1),
        "ttft_p99_ms": round(ttft_p99, 1),
        "itl_p50_ms": round(itl_p50, 2),
        "itl_p99_ms": round(itl_p99, 2),
        "occupancy": round(occ, 3),
        "rejected": rejected[0],
        "rejection_rate": round(rej_rate, 3),
        "host_bubble_frac": round(bubble, 3),
        "dispatch_s": round(d_dispatch, 2),
        "host_gap_s": round(d_gap, 2),
        "overlap_admitted": overlap_admitted,
    }), flush=True)
    dump_obs(engine, trace_rows, "serving", pump=pump)


def main() -> None:
    from distributed_inference_engine_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    log(f"compile cache: {configure_compile_cache()}")
    import jax

    devices = jax.devices()
    DEVICE.update(platform=devices[0].platform,
                  device_kind=devices[0].device_kind,
                  n_devices=len(devices))
    log(f"devices: {DEVICE}")
    if DEVICE["platform"] != "tpu":
        sys.exit(f"bench.py measures a TPU; jax found platform="
                 f"{DEVICE['platform']!r} — nothing measured")
    if DEVICE["device_kind"] not in DEVICE_PEAKS:
        sys.exit(f"no peaks for device_kind {DEVICE['device_kind']!r} in "
                 f"DEVICE_PEAKS (have {sorted(DEVICE_PEAKS)}) — add the "
                 "published figures with their source")
    if ENGINE_KIND == "serving":
        serving_main()
    else:
        decode_main()


if __name__ == "__main__":
    main()
