"""Serve a real HF checkpoint directory end-to-end: config.json →
``spec_from_hf_config``, safetensors → ``load_checkpoint`` (optionally
quantized), vocab.json+merges.txt → ``BPETokenizer`` (byte-level
fallback when tokenizer files are absent), prompts → continuous engine
→ detokenized text.

This is the path a user with real weights runs; the environment this
repo is benchmarked in is zero-egress with no checkpoint on disk
(README "Real-checkpoint status"), so CI drives it with a synthetic
checkpoint (tests/test_serve_checkpoint.py) and the perf tables use
random-init (byte/FLOP counts are weight-value-independent).

    python examples/serve_checkpoint.py /path/to/ckpt "prompt text" \
        [--quant 4|8] [--max-new 64]
"""

import argparse
import os
import pathlib
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_inference_engine_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def build_engine(path: str, quant: int = 0, max_slots: int = 4,
                 max_seq_len: int = 0):
    """(engine, tokenizer, eos_ids) serving the checkpoint at ``path``;
    ``eos_ids`` comes from config.json's eos_token_id (possibly several —
    wire [0] into ``GenerationRequest.eos_id`` and the rest into
    ``stop_ids``, as main() does)."""
    from distributed_inference_engine_tpu.config import EngineConfig
    from distributed_inference_engine_tpu.engine.continuous import (
        ContinuousEngine,
    )
    from distributed_inference_engine_tpu.models.loader import (
        load_checkpoint,
        spec_from_hf_config,
    )
    from distributed_inference_engine_tpu.ops.quant import quantize_params
    from distributed_inference_engine_tpu.utils.tokenizer import (
        BPETokenizer,
        build_tokenizer,
    )

    import json

    p = pathlib.Path(path)
    t0 = time.perf_counter()
    hf_cfg = json.loads((p / "config.json").read_text())   # parsed ONCE:
    spec = spec_from_hf_config(str(p), cfg=hf_cfg)         # spec + eos
    if max_seq_len:
        spec = spec.replace(max_seq_len=min(spec.max_seq_len, max_seq_len))
    params = load_checkpoint(str(p), spec)
    if quant:
        params = quantize_params(spec, params, bits=quant)
    log(f"loaded {spec.n_layers}L/{spec.d_model}d checkpoint"
        f"{f' (int{quant})' if quant else ''}: "
        f"{time.perf_counter() - t0:.1f}s")

    tok = build_tokenizer(str(p))       # BPE from vocab.json+merges.txt or
    if isinstance(tok, BPETokenizer):   # tokenizer.json; else byte-level
        log(f"BPE tokenizer: {tok.vocab_size} tokens "
            f"(native merge core: {tok.native_enabled})")
    else:
        log("no tokenizer files — byte-level fallback")

    seq_cap = min(spec.max_seq_len, 4096)
    cfg = EngineConfig(
        max_slots=max_slots, max_seq_len=seq_cap,
        prefill_buckets=[min(128, seq_cap), min(512, seq_cap)],
        page_size=min(128, seq_cap),
        num_pages=max(64, max_slots * (-(-seq_cap // min(128, seq_cap)))
                      + 8),
    )
    # eos: config.json's eos_token_id is authoritative (a list for
    # multi-eos checkpoints like Llama-3 — the engine takes one id; the
    # rest ride GenerationRequest.stop_ids in main())
    eos = hf_cfg.get("eos_token_id")
    eos_ids = ([] if eos is None
               else [eos] if isinstance(eos, int) else list(eos))
    return ContinuousEngine(spec, params=params, config=cfg), tok, eos_ids


def main() -> None:
    from distributed_inference_engine_tpu.engine.types import (
        GenerationRequest,
    )

    ap = argparse.ArgumentParser()
    ap.add_argument("path", help="HF checkpoint dir (config.json + "
                                 "*.safetensors [+ vocab.json/merges.txt])")
    ap.add_argument("prompts", nargs="+")
    ap.add_argument("--quant", type=int, default=0, choices=(0, 4, 8))
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    engine, tok, eos_ids = build_engine(args.path, quant=args.quant)
    reqs = [
        GenerationRequest(prompt=tok.encode(p),
                          max_new_tokens=args.max_new,
                          temperature=args.temperature,
                          eos_id=eos_ids[0] if eos_ids else -1,
                          stop_ids=eos_ids[1:],
                          request_id=f"p{i}")
        for i, p in enumerate(args.prompts)
    ]
    t0 = time.perf_counter()
    results = engine.generate(reqs)
    wall = time.perf_counter() - t0
    total = sum(len(r.tokens) for r in results)
    for p, r in zip(args.prompts, results):
        print(f"--- {r.request_id} ({r.finish_reason}, "
              f"{len(r.tokens)} tokens)")
        print(p + tok.decode(r.tokens))
    log(f"{total} tokens in {wall:.2f}s ({total / wall:.1f} tok/s)")


if __name__ == "__main__":
    main()
