"""One served chain at the ``mellum2-repo-overload`` cell's own lengths,
judged by ``reference/check.py``'s ``judge`` against this family's float32
reference, and the controls a chain can show. A builder's tool, run on the
chip in ONE process (it holds the chip: no worker, no coordinator):

    python3 perfbench/tools/longchain_mellum.py [--prompt 4096] [--out 64] \\
        [--seed 7] [--controls window_minus_1,plain_rope_on_full,...]

``run.py`` judges two chains of 48 + 24 tokens, which never reach the window
of 1,024 rows nor leave the first K|V page; this serves a 4,096-token prompt
(four windows, 32 pages, of which a sliding layer keeps the last 8) + 64
tokens (four decode chunks: the side window written back four times, a 33rd
page, window pages released and taken) through ``ContinuousEngine`` twice
(alone, and again while 7 other slots are live), then computes the
reference's logits for the chain's last positions, and the same with one
named term wrong (``reference/swa_moe.py`` ``CONTROLS``) and with the whole
reference in bfloat16. It prints, per chain and reading, the
strict count and the worst gap as a share of max|logit|; ``--chains N``
serves N chains of ``run.py``'s own 48 + 24 first. ``TIE_FRACTION`` /
``MIN_STRICT_SHARE`` of the family (what ``correct`` judges) lie between the
served chains' readings and the nearest control's; it says PASS when every
served chain is inside and, on the long chain, every control outside (the
reference's ``NOT_SEPARATED`` readings and the short chains' are printed and
not held to that: 72 rows of context need not show a wrong model). ``--config
mellum-tiny --prompt 90 --out 32 --others 3`` rehearses the control flow on
the CPU from ``perfbench/rehearse/`` (its ``max_seq_len`` is 128, its window
32).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="mellum2-12b-a2.5b-pp1")
    ap.add_argument("--prompt", type=int, default=4096)
    ap.add_argument("--out", type=int, default=64)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--others", type=int, default=7)
    ap.add_argument("--chains", type=int, default=0,
                    help="short chains (run.py's 48 + 24) served alone first")
    ap.add_argument("--chain-prompt", type=int, default=48)
    ap.add_argument("--chain-out", type=int, default=24)
    ap.add_argument("--controls", default="",
                    help="default: the reference's CONTROLS and bfloat16")
    args = ap.parse_args(argv)

    from distributed_inference_engine_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_inference_engine_tpu.config import ModelConfig
    from distributed_inference_engine_tpu.engine.types import (
        GenerationRequest,
    )
    from distributed_inference_engine_tpu.models import engine_from_config
    from perfbench.lib import families, procs, session
    from perfbench.reference import check
    from perfbench.tools import rehearse
    from perfbench.tools.longchain_xing import gaps, readings_of

    path = os.path.join(ROOT, "perfbench", "configs", f"{args.config}.json")
    cfg = (session.load_config(args.config) if os.path.exists(path)
           else rehearse.load(args.config))
    ref = families.reference(cfg)
    dev = jax.devices()[0]
    print(f"longchain: platform={dev.platform} kind={dev.device_kind!r} "
          f"config={args.config} prompt={args.prompt} out={args.out}",
          flush=True)
    model = procs.model_dict(cfg["serve"], args.seed)
    model["metadata"]["warmup"] = 0
    t0 = time.monotonic()
    engine = engine_from_config(ModelConfig.from_dict(model))
    rng = random.Random(f"longchain:{args.seed}")
    vocab = int(cfg["vocab_size"])

    def prompt(n):
        return [rng.randrange(1, vocab) for _ in range(n)]

    chain = prompt(args.prompt)

    def req(p, n, rid):
        return GenerationRequest(prompt=list(p), max_new_tokens=n,
                                 temperature=0.0, eos_id=-1, request_id=rid)

    # short chains first, each served alone: run.py's lengths, more of them
    cases = []
    for i in range(args.chains):
        p = prompt(args.chain_prompt)
        (res,) = engine.generate([req(p, args.chain_out, f"s{i}")])
        cases.append((f"short-{i}", p, [int(t) for t in res.tokens]))
    (alone,) = engine.generate([req(chain, args.out, "alone")])
    print(f"longchain: {args.chains} short chains and the long one served "
          f"alone in {time.monotonic() - t0:.0f}s", flush=True)
    # 7 others first (one row a prefill), long enough to outlive the chain
    other_len = max(8, args.prompt // 4)
    for i in range(args.others):
        engine.submit(req(prompt(other_len), 4 * args.out, f"o{i}"))
    engine.submit(req(chain, args.out, "among"))
    done = {r.request_id: r for r in engine.run_until_idle()}
    among = done["among"]
    m = engine.get_metrics()
    print(f"longchain: served among {args.others} others "
          f"({len(done)} finished, decode_steps {m['decode_steps']}, "
          f"context rows full {m['attn']['full_context_rows']} / window "
          f"{m['attn']['window_context_rows']}, table rows "
          f"{m['attn']['full_table_rows']} / "
          f"{m['attn']['window_table_rows']}, window pages peak "
          f"{m['kv']['peak_window_pages_used']} of "
          f"{m['kv']['window_num_pages']}, released "
          f"{m['kv']['window_pages_released']})", flush=True)
    params = engine.params
    engine.kv.k_pages = engine.kv.state = None
    del engine, done
    long_cases = [("alone", chain, [int(t) for t in alone.tokens]),
                  ("among", chain, [int(t) for t in among.tokens])]
    print(f"longchain: the two long chains agree on "
          f"{sum(a == b for a, b in zip(long_cases[0][2], long_cases[1][2]))}"
          f" of {args.out} tokens", flush=True)

    limits = (float(ref.TIE_FRACTION), float(ref.MIN_STRICT_SHARE))
    out = {"limits": limits, "rows": []}
    ok = True
    summary = {}
    for label, p, tokens in cases + long_cases:
        seq = jnp.asarray(p + tokens, jnp.int32)
        for name, kw in readings_of(args.controls or ",".join(
                ref.CONTROLS + tuple(n for n in ref.NOT_SEPARATED
                                     if n not in ref.CONTROLS))):
            t1 = time.monotonic()
            lg = np.asarray(ref.logits(cfg, params, seq,
                                       last=len(tokens) + 1, **kw),
                            np.float32)[:-1]
            strict, worst = gaps(lg, tokens)
            kind = "long" if label in ("alone", "among") else "short"
            verdict = check.judge(lg, 1, tokens, *limits)
            row = {"chain": label, "against": name, "strict": strict,
                   "n": len(tokens), "worst_gap": round(worst, 4),
                   "judge_ok": verdict["ok"],
                   "seconds": round(time.monotonic() - t1, 1)}
            out["rows"].append(row)
            print("  " + json.dumps(row), flush=True)
            agg = summary.setdefault((kind, name), [[], [], 0])
            agg[0].append(worst)
            agg[1].append(strict / len(tokens))
            agg[2] += verdict["ok"]
            if name == "reference":
                ok = ok and verdict["ok"]
            elif name not in ref.NOT_SEPARATED and kind == "long":
                # a short chain (72 rows) need not show a wrong model
                ok = ok and not verdict["ok"]
    # both ends: a served chain's FARTHEST reading and a control's NEAREST
    # are the two a limit lies between
    for (kind, name), (worst, share, n_ok) in summary.items():
        line = {"chains": kind, "against": name,
                "worst_gap": [round(min(worst), 4), round(max(worst), 4)],
                "strict_share": [round(min(share), 3), round(max(share), 3)],
                "judged_ok": f"{n_ok}/{len(worst)}"}
        out.setdefault("summary", []).append(line)
        print("SUMMARY " + json.dumps(line), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "longchain_mellum.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(f"longchain: {'PASS' if ok else 'FAIL'}: every served chain "
          f"{'is' if ok else 'is NOT'} inside the limits {limits} with the "
          f"controls outside", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
